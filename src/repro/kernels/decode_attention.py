"""Pallas TPU decode attention: one query token over a long KV cache.

The decode hot spot is memory-bound (the whole KV cache streams HBM->VMEM
once per token), so the kernel is organized to read each cache block exactly
once: grid (B, KV_heads, num_cache_blocks), sequential over cache blocks with
the per-(batch, kv-head) group of GQA query heads (H/KV of them) resident in
VMEM.  A `lengths` operand masks ring-buffer slots past the valid length.
The wrapper moves kv heads ahead of the cache's sequence axis, so each cache
block is (1, 1, block_k, hd): a sublane-aligned tile over the whole head dim,
the tiling the TPU compiler requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, bk: int, nk: int, scale: float):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                                         # (group, hd)
    k = k_ref[0, 0]                                         # (bk, hd)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    valid_len = len_ref[pl.program_id(0)]
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_pos < valid_len, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, lengths, *, block_k: int = 128,
                     interpret: bool = False):
    """q: (B, H, hd); caches: (B, L, KV, hd); lengths: (B,) valid entries.

    Returns (B, H, hd).
    """
    b, h, hd = q.shape
    L, kv = k_cache.shape[1], k_cache.shape[2]
    assert h % kv == 0 and L % block_k == 0, (q.shape, k_cache.shape, block_k)
    group = h // kv
    nk = L // block_k
    scale = 1.0 / (hd ** 0.5)
    qg = q.reshape(b, kv, group, hd)
    # kv heads ahead of the cache's sequence axis: (B, KV, L, hd)
    kt = k_cache.transpose(0, 2, 1, 3)
    vt = v_cache.transpose(0, 2, 1, 3)

    kernel = functools.partial(_decode_kernel, bk=block_k, nk=nk, scale=scale)

    out = pl.pallas_call(
        kernel,
        grid=(b, kv, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, group, hd), lambda b, g, ki: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, g, ki: (b, g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, g, ki: (b, g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd), lambda b, g, ki: (b, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, group, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, hd), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, kt, vt)
    return out.reshape(b, h, hd)
