"""Generic layer stack: scan over repeating pattern blocks.

Architectures repeat a short layer pattern (gemma3: 5 local + 1 global;
jamba: 7 mamba + 1 attention with alternating MoE; most others: period 1).
We run `lax.scan` over the repeated blocks (keeping the lowered HLO to ~one
block regardless of depth) and unroll only the non-repeating remainder
layers.  Parameters/caches for scanned blocks carry a leading `n_rep` dim.
"""
from __future__ import annotations

import math
import os
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.api import constrain
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S


_SEQ_PARALLEL = os.environ.get("REPRO_SEQ_PARALLEL", "0") == "1"


class LayerSpec(NamedTuple):
    is_attn: bool
    is_global: bool
    is_moe: bool
    has_cross: bool = False


class StackPlan(NamedTuple):
    period: int
    n_rep: int
    pattern: tuple            # LayerSpec per pattern position
    rem: tuple                # LayerSpec per remainder layer


def _spec(cfg: ModelConfig, i: int, cross: bool) -> LayerSpec:
    return LayerSpec(cfg.is_attn_layer(i), cfg.is_global_layer(i),
                     cfg.is_moe_layer(i), cross)


def plan(cfg: ModelConfig, *, cross: bool = False,
         n_layers: Optional[int] = None) -> StackPlan:
    n = n_layers if n_layers is not None else cfg.n_layers
    period = 1
    if cfg.sliding_window is not None and cfg.global_every > 0:
        period = math.lcm(period, cfg.global_every)
    if cfg.family == "hybrid" and cfg.attn_every > 0:
        period = math.lcm(period, cfg.attn_every)
    if cfg.moe is not None:
        period = math.lcm(period, cfg.moe.every)
    period = min(period, n)
    n_rep = n // period
    pattern = tuple(_spec(cfg, i, cross) for i in range(period))
    rem = tuple(_spec(cfg, n_rep * period + j, cross)
                for j in range(n - n_rep * period))
    return StackPlan(period, n_rep, pattern, rem)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_layer(rng, cfg: ModelConfig, spec: LayerSpec):
    d = cfg.d_model
    dt = cfg.dtype
    ks = jax.random.split(rng, 6)
    p = {"ln1": jnp.zeros((d,), dt)}
    if spec.is_attn:
        p["attn"] = L.init_attention(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                     cfg.head_dim_, dt)
    else:
        p["ssm"] = S.init_mamba(ks[1], d, cfg.ssm, dt)
    if spec.has_cross:
        p["ln_x"] = jnp.zeros((d,), dt)
        p["cross"] = L.init_attention(ks[2], d, cfg.n_heads, cfg.n_kv_heads,
                                      cfg.head_dim_, dt)
    if spec.is_moe:
        p["ln2"] = jnp.zeros((d,), dt)
        p["moe"] = M.init_moe(ks[3], d, cfg.moe, cfg.mlp_gated, dt)
    elif cfg.d_ff > 0:
        p["ln2"] = jnp.zeros((d,), dt)
        p["mlp"] = L.init_mlp(ks[4], d, cfg.d_ff, cfg.mlp_gated, dt)
    return p


def init_stack(rng, cfg: ModelConfig, pl: StackPlan):
    blocks = []
    for j, spec in enumerate(pl.pattern):
        # vmapped over the repeats: each leaf is built stacked, never as a
        # list of per-layer arrays plus a stacked copy
        keys = jax.vmap(lambda r: jax.random.fold_in(rng, r * pl.period + j))(
            jnp.arange(pl.n_rep))
        blocks.append(jax.vmap(lambda k: init_layer(k, cfg, spec))(keys))
    rem = [init_layer(jax.random.fold_in(rng, pl.n_rep * pl.period + j), cfg, spec)
           for j, spec in enumerate(pl.rem)]
    return {"blocks": tuple(blocks), "rem": tuple(rem)}


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------
def _layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, capacity: int,
                 enc_len: int = 0):
    dt = cfg.dtype
    c = {}
    if spec.is_attn:
        cap = capacity
        if cfg.sliding_window is not None and not spec.is_global:
            cap = min(cfg.sliding_window, capacity)
        kv, hd = cfg.n_kv_heads, cfg.head_dim_
        c["k"] = jnp.zeros((batch, cap, kv, hd), dt)
        c["v"] = jnp.zeros((batch, cap, kv, hd), dt)
    else:
        s = cfg.ssm
        conv_dim = s.d_inner(cfg.d_model) + 2 * s.n_groups * s.d_state
        c["conv"] = jnp.zeros((batch, s.d_conv - 1, conv_dim), dt)
        c["state"] = jnp.zeros((batch, s.n_heads(cfg.d_model), s.head_dim,
                                s.d_state), jnp.float32)
    if spec.has_cross:
        kv, hd = cfg.n_kv_heads, cfg.head_dim_
        c["xk"] = jnp.zeros((batch, enc_len, kv, hd), dt)
        c["xv"] = jnp.zeros((batch, enc_len, kv, hd), dt)
    return c


def init_cache(cfg: ModelConfig, pl: StackPlan, batch: int, capacity: int,
               enc_len: int = 0):
    blocks = []
    for spec in pl.pattern:
        one = _layer_cache(cfg, spec, batch, capacity, enc_len)
        blocks.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (pl.n_rep,) + x.shape), one))
    rem = [_layer_cache(cfg, spec, batch, capacity, enc_len) for spec in pl.rem]
    return {"blocks": tuple(blocks), "rem": tuple(rem)}


# ---------------------------------------------------------------------------
# single layer application
# ---------------------------------------------------------------------------
def layer_apply(params, cfg: ModelConfig, spec: LayerSpec, x, positions, *,
                impl="chunked", moe_impl="einsum", enc_out=None, cache=None,
                cache_len=None, mode="train", capacity: Optional[int] = None):
    """Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    h = L.rms_norm(x, params["ln1"], cfg.norm_eps)
    window = None
    if cfg.sliding_window is not None and not spec.is_global:
        window = cfg.sliding_window

    if spec.is_attn:
        if mode == "decode":
            a, new_kv = _attn_decode(params["attn"], cfg, h, cache, cache_len, window)
            new_cache.update(new_kv)
        else:
            a, (k, v) = L.attn_block(params["attn"], h, positions, cfg.rope_theta,
                                     window=window, causal=True, impl=impl)
            if mode == "prefill":
                new_cache.update(_build_kv_cache(cfg, k, v, window, capacity))
    else:
        if mode == "decode":
            a, st = S.mamba_decode(params["ssm"], h, cache, cfg.d_model, cfg.ssm)
        else:
            a, st = S.mamba_forward(params["ssm"], h, cfg.d_model, cfg.ssm)
        if mode != "train":
            new_cache.update(st)
    x = x + a

    if spec.has_cross:
        h = L.rms_norm(x, params["ln_x"], cfg.norm_eps)
        if mode == "decode":
            q = jnp.einsum("bsd,dhk->bshk", h, params["cross"]["wq"])
            o = L.attention_decode(q, cache["xk"], cache["xv"],
                                   jnp.full((x.shape[0],), cache["xk"].shape[1]))
            a = jnp.einsum("bshk,hkd->bsd", o, params["cross"]["wo"])
            new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]
        else:
            a, (xk, xv) = L.attn_block(params["cross"], h, positions,
                                       cfg.rope_theta, impl="naive",
                                       kv_override=enc_out)
            if mode == "prefill":
                new_cache["xk"], new_cache["xv"] = xk, xv
        x = x + a

    if "moe" in params:
        h = L.rms_norm(x, params["ln2"], cfg.norm_eps)
        mo, aux = M.moe_apply(params["moe"], h, cfg.moe, impl=moe_impl)
        x = x + mo
    elif "mlp" in params:
        h = L.rms_norm(x, params["ln2"], cfg.norm_eps)
        x = x + L.mlp(params["mlp"], h)
    if _SEQ_PARALLEL and mode != "decode":
        # Megatron-SP-style: keep the residual stream sequence-sharded over
        # `model` between layers; XLA turns the per-layer f32 all-reduce into
        # a bf16 reduce-scatter + all-gather pair (§Perf hillclimb knob).
        x = constrain(x, "data", "model", None)
    else:
        x = constrain(x, "data", None, None)
    return x, new_cache, aux


def _build_kv_cache(cfg, k, v, window, capacity):
    """Arrange prefill K/V into the decode cache layout."""
    b, s = k.shape[:2]
    if window is not None:
        cap = min(window, capacity if capacity else window)
        if s >= cap:
            k_c, v_c = k[:, -cap:], v[:, -cap:]
            shift = s % cap
            k_c = jnp.roll(k_c, shift, axis=1)
            v_c = jnp.roll(v_c, shift, axis=1)
        else:
            pad = cap - s
            k_c = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_c = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return {"k": k_c, "v": v_c}
    cap = capacity if capacity else s
    if cap == s:
        return {"k": k, "v": v}
    pad = cap - s
    k_c = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    v_c = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return {"k": k_c, "v": v_c}


def _attn_decode(params, cfg, h, cache, cache_len, window):
    """h: (B, 1, d). Insert the new K/V and attend over the cache."""
    b = h.shape[0]
    pos = jnp.full((b, 1), cache_len, jnp.int32)
    q = jnp.einsum("bsd,dhk->bshk", h, params["wq"])
    k1 = jnp.einsum("bsd,dhk->bshk", h, params["wk"])
    v1 = jnp.einsum("bsd,dhk->bshk", h, params["wv"])
    from repro.distributed.api import constrain as _con
    from repro.distributed.api import mesh_axis_size as _mas
    # engage the context-parallel decode plan only when the cache is big
    # enough that gathering it would dominate (small sliding-window ring
    # buffers are cheaper to gather than to re-shard q/k/v around — measured
    # 12% regression on starcoder2's 4k windows, see §Perf).
    seq_sharded = (cache["k"].shape[-2] % max(_mas("model"), 1) != 0
                   and cache["k"].shape[-3] > 8192)
    if seq_sharded:
        # context-parallel cache (kv heads don't divide the model axis; the
        # cache seq dim is model-sharded instead): replicate the tiny query
        # heads so the q@K einsum stays seq-local — otherwise XLA gathers
        # the whole cache per layer (EXPERIMENTS.md §Perf/kimi).
        q = _con(q, "data", None, None, None)
        k1 = _con(k1, "data", None, None, None)
        v1 = _con(v1, "data", None, None, None)
    q = L.apply_rope(q, pos, cfg.rope_theta)
    k1 = L.apply_rope(k1, pos, cfg.rope_theta)
    cap = cache["k"].shape[1]
    idx = jnp.mod(cache_len, cap)
    # masked insert instead of dynamic_update_slice: a DUS at a traced index
    # along a SHARDED cache dim triggers SPMD "involuntary full
    # rematerialization" (an f32 all-gather of the whole cache per layer —
    # see EXPERIMENTS.md §Perf/kimi); the select keeps every shard local and
    # fuses into the (donated, aliased) cache buffer.
    mask = (jax.lax.broadcasted_iota(jnp.int32, (1, cap, 1, 1), 1) == idx)
    k_c = jnp.where(mask, k1, cache["k"])
    v_c = jnp.where(mask, v1, cache["v"])
    valid = jnp.full((b,), cache_len + 1, jnp.int32)
    o = L.attention_decode(q, k_c, v_c, valid, window=window,
                           seq_sharded=seq_sharded)
    a = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    return a, {"k": k_c, "v": v_c}


# ---------------------------------------------------------------------------
# full stack application
# ---------------------------------------------------------------------------
def apply_stack(params, cfg: ModelConfig, pl: StackPlan, x, positions, *,
                impl="chunked", moe_impl="einsum", enc_out=None, caches=None,
                cache_len=None, mode="train", capacity=None, remat=False,
                unroll=False):
    """Returns (x, new_caches, aux_total).

    ``unroll=True`` replaces the lax.scan over repeated blocks with a python
    loop — used by the dry-run cost probes (XLA's cost_analysis counts a
    while-loop body once, so scanned programs under-report flops).
    """
    want_cache = mode in ("prefill", "decode")

    def block_fn(x, block_params, block_caches):
        aux = jnp.zeros((), jnp.float32)
        new_caches = []
        for j, spec in enumerate(pl.pattern):
            cache_j = block_caches[j] if block_caches is not None else None
            x, nc, a = layer_apply(
                block_params[j], cfg, spec, x, positions, impl=impl,
                moe_impl=moe_impl, enc_out=enc_out, cache=cache_j,
                cache_len=cache_len, mode=mode, capacity=capacity)
            new_caches.append(nc)
            aux = aux + a
        return x, tuple(new_caches), aux

    if remat:
        block_fn = jax.checkpoint(block_fn)

    if pl.n_rep > 0 and unroll:
        aux = jnp.zeros((), jnp.float32)
        reps = []
        for r in range(pl.n_rep):
            bp = jax.tree.map(lambda t: t[r], params["blocks"])
            bc = (jax.tree.map(lambda t: t[r], caches["blocks"])
                  if caches is not None else None)
            x, nc, a = block_fn(x, bp, bc)
            aux = aux + a
            reps.append(nc)
        new_blocks = (jax.tree.map(lambda *xs: jnp.stack(xs), *reps)
                      if want_cache else None)
    elif pl.n_rep > 0:
        if want_cache:
            def body(carry, xs):
                x, aux = carry
                bp, bc = xs if caches is not None else (xs, None)
                x, nc, a = block_fn(x, bp, bc)
                return (x, aux + a), nc
            xs = (params["blocks"], caches["blocks"]) if caches is not None \
                else params["blocks"]
            (x, aux), new_blocks = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)), xs)
        else:
            def body(carry, bp):
                x, aux = carry
                x, _, a = block_fn(x, bp, None)
                return (x, aux + a), None
            (x, aux), _ = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)), params["blocks"])
            new_blocks = None
    else:
        aux = jnp.zeros((), jnp.float32)
        new_blocks = caches["blocks"] if caches else None

    new_rem = []
    for j, spec in enumerate(pl.rem):
        cache_j = caches["rem"][j] if caches is not None else None
        x, nc, a = layer_apply(
            params["rem"][j], cfg, spec, x, positions, impl=impl,
            moe_impl=moe_impl, enc_out=enc_out, cache=cache_j,
            cache_len=cache_len, mode=mode, capacity=capacity)
        new_rem.append(nc)
        aux = aux + a

    new_caches = {"blocks": new_blocks, "rem": tuple(new_rem)} if want_cache else None
    return x, new_caches, aux
