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
SPAN_PREFILL = "stage.prefill"   # prefill dispatch
SPAN_DECODE = "stage.decode"     # pick, keep and advance token ``step``
SPAN_FETCH = "stage.fetch"       # wait for the kept tokens, copy them back


def _keep(lg, gen):
    """The greedy pick from ``lg`` (B, V), shifted into the last column of
    ``gen`` (B, n): after n picks, column i holds token i."""
    tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, None]
    return tok, jnp.concatenate([gen[:, 1:], tok], axis=1)


@functools.partial(jax.jit, donate_argnums=1)
def keep_last(lg, gen):
    """Pick and keep the last token; no decode step follows it."""
    return _keep(lg, gen)[1]


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
        """(params, tokens (B, S)) -> (logits of token 0, caches, cache
        length S, an empty (B, gen_tokens) buffer), all on the device."""
        key = (vname, b, s)
        if key not in self._prefill_cache:
            cfg = self.variants[vname][0]
            cap = min(self.max_ctx, s + self.gen_tokens)
            empty = (b, self.gen_tokens)

            @jax.jit
            def prefill_step(params, tokens):
                hl, caches, _ = M.prefill(params, cfg, {"tokens": tokens},
                                          impl="naive", capacity=cap)
                lg = jnp.einsum("bd,vd->bv", hl, params["embed"])
                return lg, caches, jnp.int32(s), jnp.zeros(empty, jnp.int32)
            self._prefill_cache[key] = prefill_step
        return self._prefill_cache[key]

    def _get_decode(self, vname: str, b: int):
        """(params, logits of token i, caches, cache length, buffer) ->
        the same for token i + 1: token i picked, kept and decoded."""
        key = (vname, b)
        if key not in self._decode_cache:
            cfg = self.variants[vname][0]

            @functools.partial(jax.jit, donate_argnums=(1, 2, 4))
            def decode_step(params, lg, caches, clen, gen):
                tok, gen = _keep(lg, gen)
                lg, caches = M.decode_step(params, cfg, caches, clen, tok)
                return lg, caches, clen + 1, gen
            self._decode_cache[key] = decode_step
        return self._decode_cache[key]

    def process(self, tokens: np.ndarray) -> Tuple[np.ndarray, float]:
        """tokens: (B, S) int32 prompts. Greedy-decodes ``gen_tokens``: the
        first from prefill's logits, each later one from a decode step, so
        ``gen_tokens - 1`` steps run, one program each.

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
            lg, caches, clen, gen = prefill(params, jnp.asarray(tokens))
        last = self.gen_tokens - 1
        for i in range(last):
            with TraceAnnotation(SPAN_DECODE, step=i):
                lg, caches, clen, gen = decode(params, lg, caches, clen, gen)
        with TraceAnnotation(SPAN_DECODE, step=last):
            gen = keep_last(lg, gen)
        with TraceAnnotation(SPAN_FETCH):
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
