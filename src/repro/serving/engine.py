"""Real JAX serving engine: batched prefill + greedy decode with KV cache,
and hot-swappable model variants (the data plane under IPA's control plane).

A ``StageServer`` owns one inference *task* (a stage of the pipeline) and a
family of model variants for it.  ``set_variant`` switches the active
parameter pytree — the serving analogue of the paper's model switching.  A
``PipelineEngine`` chains stages: the token output of stage i is the prompt
of stage i+1 (the abstraction the paper uses for e.g. detector -> classifier
or ASR -> QA chains).
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models import model as M

# one program per config: the weights are written once, in their own dtype
_init_params = jax.jit(M.init, static_argnums=1)

# Host spans inside ``StageServer.process``. They cost under a microsecond
# each with no profiler running, and land in a profile on the device ops'
# clock, so device idle can be put down to the host work around it.
SPAN_PREFILL = "stage.prefill"   # prefill dispatch and its first argmax
SPAN_DECODE = "stage.decode"     # one token step; its index as ``step``
SPAN_FETCH = "stage.fetch"       # gather the tokens and copy them back


class StageServer:
    def __init__(self, name: str,
                 variants: Sequence[Tuple[str, ModelConfig, float]],
                 *, gen_tokens: int = 8, max_ctx: int = 192, seed: int = 0,
                 params_by_variant: Optional[Dict[str, dict]] = None):
        self.name = name
        self.gen_tokens = gen_tokens
        self.max_ctx = max_ctx
        self.variants: Dict[str, Tuple[ModelConfig, float]] = {}
        self.params: Dict[str, dict] = {}
        for i, (vname, cfg, acc) in enumerate(variants):
            self.variants[vname] = (cfg, acc)
            if params_by_variant and vname in params_by_variant:
                self.params[vname] = params_by_variant[vname]
            else:
                self.params[vname] = _init_params(jax.random.PRNGKey(seed + i), cfg)
        self.active = list(self.variants)[0]
        self._prefill_cache = {}
        self._decode_cache = {}

    # -- control plane hooks -------------------------------------------------
    def set_variant(self, vname: str) -> None:
        assert vname in self.variants, (vname, list(self.variants))
        self.active = vname

    @property
    def accuracy(self) -> float:
        return self.variants[self.active][1]

    @property
    def config(self) -> ModelConfig:
        return self.variants[self.active][0]

    # -- data plane -----------------------------------------------------------
    def _get_prefill(self, vname: str, b: int, s: int):
        key = (vname, b, s)
        if key not in self._prefill_cache:
            cfg = self.variants[vname][0]
            cap = min(self.max_ctx, s + self.gen_tokens)

            @jax.jit
            def prefill_step(params, tokens):
                hl, caches, _ = M.prefill(params, cfg, {"tokens": tokens},
                                          impl="naive", capacity=cap)
                lg = jnp.einsum("bd,vd->bv", hl, params["embed"])
                return lg, caches
            self._prefill_cache[key] = prefill_step
        return self._prefill_cache[key]

    def _get_decode(self, vname: str, b: int):
        key = (vname, b)
        if key not in self._decode_cache:
            cfg = self.variants[vname][0]

            @jax.jit
            def decode_step(params, caches, clen, tok):
                return M.decode_step(params, cfg, caches, clen, tok)
            self._decode_cache[key] = decode_step
        return self._decode_cache[key]

    def process(self, tokens: np.ndarray) -> Tuple[np.ndarray, float]:
        """tokens: (B, S) int32 prompts. Greedy-decodes ``gen_tokens``.

        Returns (generated (B, gen_tokens), wall_seconds).
        """
        cfg = self.config
        tokens = np.asarray(tokens, np.int32) % cfg.vocab
        b, s = tokens.shape
        t0 = time.perf_counter()
        prefill = self._get_prefill(self.active, b, s)
        decode = self._get_decode(self.active, b)
        params = self.params[self.active]
        with TraceAnnotation(SPAN_PREFILL):
            lg, caches = prefill(params, jnp.asarray(tokens))
            tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, None]
        out = []
        clen = s
        for i in range(self.gen_tokens):
            with TraceAnnotation(SPAN_DECODE, step=i):
                out.append(tok)
                lg, caches = decode(params, caches, jnp.int32(clen), tok)
                tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, None]
            clen += 1
        with TraceAnnotation(SPAN_FETCH):
            gen = jnp.concatenate(out, axis=1)
            gen.block_until_ready()
            gen = np.asarray(gen)
        return gen, time.perf_counter() - t0


class PipelineEngine:
    """Chains StageServers; stage i's generated tokens prompt stage i+1."""

    def __init__(self, stages: Sequence[StageServer]):
        self.stages = list(stages)

    def configure(self, variants: Sequence[str]) -> None:
        for st, v in zip(self.stages, variants):
            st.set_variant(v)

    def serve(self, tokens: np.ndarray) -> Tuple[np.ndarray, List[float]]:
        lats = []
        cur = tokens
        for st in self.stages:
            cur, lat = st.process(cur)
            lats.append(lat)
        return cur, lats

    @property
    def pas(self) -> float:
        """Pipeline Accuracy Score of the currently active variants (Eq. 8)."""
        p = 1.0
        for st in self.stages:
            p *= st.accuracy / 100.0
        return p * 100.0
