"""Pallas TPU flash attention (prefill path).

Block-tiled online-softmax attention.  Grid is (B, H, num_q_blocks,
num_kv_blocks) with the KV axis sequential ("arbitrary") so the f32
accumulator/row-max/row-sum scratch in VMEM carries across KV blocks.
GQA is handled in the index map (kv head = h // (H // KV)) — the kernel never
materializes repeated K/V.  The wrapper moves heads ahead of the sequence
axis, so every block is (1, 1, block, hd): its last two dimensions are a
sublane-aligned sequence tile and the whole head dim, the tiling the TPU
compiler requires.  Block sizes default to MXU-aligned 128s; the per-step
VMEM working set is
  bq*hd (q) + 2*bk*hd (k,v) + bq*bk (scores) + bq*hd (acc)  floats.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, nk: int, scale: float,
                  causal: bool, window: Optional[int]):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                                         # (bq, hd)
    k = k_ref[0, 0]                                         # (bk, hd)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qi = pl.program_id(2)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        ok = k_pos <= q_pos
        if window is not None:
            ok &= k_pos > q_pos - window
        s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                                     # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                                  # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                         # (bq, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd) with KV | H.  Causal masking
    assumes queries and keys are position-aligned (Sq == Sk)."""
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    assert h % kv == 0 and sq % block_q == 0 and sk % block_k == 0, \
        (q.shape, k.shape, block_q, block_k)
    group = h // kv
    nq, nk = sq // block_q, sk // block_k
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _flash_kernel, bq=block_q, bk=block_k, nk=nk, scale=scale,
        causal=causal, window=window)

    # heads ahead of the sequence: (B, H, S, hd)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
