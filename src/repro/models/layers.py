"""Shared building blocks for every architecture family.

All functions are pure (params-in, activations-out) and mesh-agnostic; the
sharding of intermediates is steered by ``repro.distributed.api.constrain``
which is a no-op outside a mesh context.  Attention offers three
implementations:

  * ``naive``   -- materializes the (S, S) score matrix (oracle / tiny seqs),
  * ``chunked`` -- lax.scan over query chunks with online softmax; O(S * C)
                   memory, the XLA analogue of flash attention (default for
                   long sequences and the dry-run path),
  * ``pallas``  -- the Pallas TPU kernel from ``repro.kernels`` (lowers to
                   Mosaic, so TPU only; the tests run it in interpret mode).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.api import constrain

DEFAULT_QUERY_CHUNK = 1024


# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(dt)


def init_dense(rng, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / jnp.sqrt(jnp.asarray(d_in, jnp.float32))
    return (jax.random.normal(rng, (d_in, d_out), jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, n_heads, head_dim); positions: broadcastable to (..., S)."""
    freqs = rope_freqs(x.shape[-1], theta)                      # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (..., S, hd/2)
    sin = jnp.sin(angles)[..., None, :]                          # (..., S, 1, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------
def init_mlp(rng, d_model: int, d_ff: int, gated: bool, dtype):
    ks = jax.random.split(rng, 3)
    p = {"w_in": init_dense(ks[0], d_model, d_ff, dtype),
         "w_out": init_dense(ks[1], d_ff, d_model, dtype)}
    if gated:
        p["w_gate"] = init_dense(ks[2], d_model, d_ff, dtype)
    return p


def mlp(params, x: jax.Array) -> jax.Array:
    h = x @ params["w_in"]
    if "w_gate" in params:
        h = jax.nn.silu(x @ params["w_gate"]) * h
    else:
        h = jax.nn.gelu(h)
    h = constrain(h, "data", None, "model")
    return h @ params["w_out"]


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def init_attention(rng, d_model: int, n_heads: int, n_kv: int, head_dim: int, dtype):
    ks = jax.random.split(rng, 4)
    return {
        "wq": init_dense(ks[0], d_model, n_heads * head_dim, dtype).reshape(d_model, n_heads, head_dim),
        "wk": init_dense(ks[1], d_model, n_kv * head_dim, dtype).reshape(d_model, n_kv, head_dim),
        "wv": init_dense(ks[2], d_model, n_kv * head_dim, dtype).reshape(d_model, n_kv, head_dim),
        "wo": init_dense(ks[3], n_heads * head_dim, d_model, dtype).reshape(n_heads, head_dim, d_model),
    }


def _repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, n_kv, hd) -> (B, S, n_heads, hd) by group broadcast."""
    n_kv = k.shape[-2]
    if n_kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // n_kv, axis=-2)


def _mask_bias(q_pos, k_pos, window: Optional[int]) -> jax.Array:
    """Additive causal (+ sliding window) mask bias: (..., Sq, Sk) float32."""
    ok = k_pos[..., None, :] <= q_pos[..., :, None]
    if window is not None:
        ok &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def attention_naive(q, k, v, q_pos, k_pos, window: Optional[int] = None,
                    causal: bool = True) -> jax.Array:
    """q: (B, Sq, H, hd); k, v: (B, Sk, Kv, hd). Returns (B, Sq, H, hd)."""
    h = q.shape[-2]
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        scores = scores + _mask_bias(q_pos, k_pos, window)[:, None]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def attention_chunked(q, k, v, q_pos, k_pos, window: Optional[int] = None,
                      causal: bool = True,
                      query_chunk: int = DEFAULT_QUERY_CHUNK) -> jax.Array:
    """Flash-style online-softmax attention, scanned over query chunks.

    Memory is O(Sq_chunk * Sk) per step instead of O(Sq * Sk).  For
    sliding-window layers only the KV slab that the chunk can see is sliced,
    making prefill O(S * (C + W)) instead of O(S^2).
    """
    b, sq, hq, hd = q.shape
    sk = k.shape[1]
    if sq % query_chunk != 0 or sq == query_chunk:
        return attention_naive(q, k, v, q_pos, k_pos, window, causal)
    n_chunks = sq // query_chunk
    k = _repeat_kv(k, hq)
    v = _repeat_kv(v, hq)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    # sliding window: each query chunk only sees a bounded KV slab.
    slab = sk
    if window is not None and causal:
        slab = min(sk, ((window + query_chunk + 127) // 128) * 128)

    qc = q.reshape(b, n_chunks, query_chunk, hq, hd).transpose(1, 0, 2, 3, 4)
    qp = q_pos.reshape(b, n_chunks, query_chunk).transpose(1, 0, 2)

    def body(_, xs):
        i, q_i, qp_i = xs
        if slab == sk:
            k_i, v_i, kp_i = k, v, k_pos
        else:
            # chunk i covers queries [i*C, (i+1)*C); visible kv start:
            start = jnp.maximum(i * query_chunk + query_chunk - slab, 0)
            start = jnp.minimum(start, sk - slab)
            k_i = jax.lax.dynamic_slice_in_dim(k, start, slab, axis=1)
            v_i = jax.lax.dynamic_slice_in_dim(v, start, slab, axis=1)
            kp_i = jax.lax.dynamic_slice_in_dim(k_pos, start, slab, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_i, k_i,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            s = s + _mask_bias(qp_i, kp_i, window)[:, None]
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v_i)
        return None, o

    _, out = jax.lax.scan(body, None,
                          (jnp.arange(n_chunks), qc, qp))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, hq, hd)


def attention_decode(q, k_cache, v_cache, cache_len, window: Optional[int] = None,
                     seq_sharded: bool = False):
    """Single-token decode attention.

    q: (B, 1, H, hd); caches: (B, L, Kv, hd) where L is the cache capacity
    (ring buffer for sliding-window layers).  ``cache_len`` (B,) int32 is the
    number of valid entries (== absolute position + 1 for full caches).

    ``seq_sharded``: the cache seq dim is context-parallel (model axis);
    constrain the score/prob tensors so the softmax stays seq-local with a
    small partial-max/sum collective — otherwise XLA gathers the whole cache
    per layer.
    """
    b, _, hq, hd = q.shape
    L = k_cache.shape[1]
    k = _repeat_kv(k_cache, hq)
    v = _repeat_kv(v_cache, hq)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    idx = jnp.arange(L)[None, :]                      # (1, L)
    valid = idx < jnp.minimum(cache_len, L)[:, None]  # ring buffer: all L valid once full
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    if seq_sharded:
        s = constrain(s, "data", None, None, "model")
    p = jax.nn.softmax(s, axis=-1)
    if seq_sharded:
        p = constrain(p, "data", None, None, "model")
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def attention(q, k, v, q_pos, k_pos, window=None, causal=True, impl="chunked",
              query_chunk: int = DEFAULT_QUERY_CHUNK):
    if impl == "naive":
        return attention_naive(q, k, v, q_pos, k_pos, window, causal)
    if impl == "chunked":
        return attention_chunked(q, k, v, q_pos, k_pos, window, causal, query_chunk)
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, q_pos, k_pos, window=window, causal=causal)
    raise ValueError(f"unknown attention impl {impl!r}")


# ---------------------------------------------------------------------------
# attention block (projections + rope + residual-less core)
# ---------------------------------------------------------------------------
def attn_block(params, x, positions, theta, window=None, causal=True,
               impl="chunked", kv_override=None):
    """x: (B, S, d). Returns (out, (k, v)) so callers can build caches."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    q = constrain(q, "data", None, "model", None)
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
        k = apply_rope(k, positions, theta)
        kv_pos = positions
    else:  # cross attention: kv comes from the encoder
        enc = kv_override
        k = jnp.einsum("bsd,dhk->bshk", enc, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc, params["wv"])
        kv_pos = jnp.broadcast_to(jnp.arange(enc.shape[1])[None], enc.shape[:2])
        causal = False
    q = apply_rope(q, positions, theta) if kv_override is None else q
    o = attention(q, k, v, positions, kv_pos, window=window, causal=causal, impl=impl)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    return out, (k, v)
