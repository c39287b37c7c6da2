"""Pallas TPU kernel for the Mamba2 SSD chunked scan (arXiv:2405.21060).

TPU adaptation of the SSD algorithm: the sequence is split into chunks of
length `l`; each grid step loads one chunk's x/dt/B/C blocks into VMEM,
computes the intra-chunk (L x L) decay-masked attention-like matmuls on the
MXU, and carries the (P x N) inter-chunk SSM state in an f32 VMEM scratch
across the sequential chunk axis.  This replaces the GPU implementation's
warp-level scan with a grid-sequential state carry — the natural TPU
equivalent.  Grid: (B, H, num_chunks) with chunk axis "arbitrary".  The
wrapper moves heads (and B/C groups) ahead of the sequence axis and gives dt
a trailing unit axis, so every block's last two dimensions are a
sublane-aligned chunk and a whole array dimension, as the TPU compiler
requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, s0_ref,
                y_ref, sf_ref, st_ref, *, li: int, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        st_ref[...] = s0_ref[0, 0, :, :].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)                     # (l, p)
    dt = dt_ref[0, 0].astype(jnp.float32)                   # (l, 1)
    a = a_ref[pl.program_id(1)]                             # this head's decay rate
    bm = b_ref[0, 0].astype(jnp.float32)                    # (l, n)
    cm = c_ref[0, 0].astype(jnp.float32)                    # (l, n)

    da = dt * a                                             # (l, 1) log decay
    # inclusive cumsum without a scan (Mosaic lowers no cumsum): a masked
    # sublane reduction gives it as a row, a transpose as columns
    ii = jax.lax.broadcasted_iota(jnp.int32, (li, li), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (li, li), 1)
    cum_row = jnp.sum(jnp.where(ii <= jj, da, 0.0), axis=0,
                      keepdims=True)                        # (1, l): cum[j]
    cum_rows = jnp.broadcast_to(cum_row, (li, li))          # [i, j] = cum[j]
    cum_cols = cum_rows.T                                   # [i, j] = cum[i]
    cum = cum_cols[:, :1]                                   # (l, 1)
    total = cum_row[:, li - 1:]                             # (1, 1) = cum[-1]
    # decay matrix L[i, j] = exp(sum_{k in (j, i]} da_k), lower triangular
    lmat = jnp.where(ii >= jj, jnp.exp(cum_cols - cum_rows), 0.0)  # (l, l)

    # every operand is f32: ask the MXU for f32 contraction, not bf16 passes
    mm = functools.partial(jax.lax.dot_general, precision=_F32,
                           preferred_element_type=jnp.float32)
    xdt = x * dt                                            # (l, p)
    scores = mm(cm, bm, (((1,), (1,)), ((), ())))           # (l, l)
    y_diag = mm(scores * lmat, xdt, (((1,), (0,)), ((), ())))  # (l, p)

    state = st_ref[...]                                     # (p, n)
    y_off = mm(cm, state, (((1,), (1,)), ((), ()))) * jnp.exp(cum)

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    decay_to_end = jnp.exp(total - cum)                     # (l, 1)
    new_contrib = mm(xdt * decay_to_end, bm, (((0,), (0,)), ((), ())))  # (p, n)
    st_ref[...] = state * jnp.exp(total) + new_contrib

    @pl.when(ci == nc - 1)
    def _flush():
        sf_ref[0, 0, :, :] = st_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a_neg, b_mat, c_mat, *, chunk: int = 256,
             init_state=None, interpret: bool = False):
    """x: (B, S, H, P); dt: (B, S, H); a_neg: (H,);
    b_mat/c_mat: (B, S, G, N), H = G * hpg.  S must be a chunk multiple.
    Returns (y (B, S, H, P), final_state (B, H, P, N))."""
    b, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    hpg = h // g
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)

    kernel = functools.partial(_ssd_kernel, li=chunk, nc=nc)

    y, sf = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, n), lambda b, h, c: (b, h // hpg, c, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda b, h, c: (b, h // hpg, c, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1)[..., None],
      a_neg.astype(jnp.float32), b_mat.transpose(0, 2, 1, 3),
      c_mat.transpose(0, 2, 1, 3), init_state)
    return y.transpose(0, 2, 1, 3), sf
