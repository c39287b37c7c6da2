"""Plain float32 building blocks shared by the references of every family.

Every contraction asks for ``Precision.HIGHEST``: on a TPU a float32
matrix product otherwise runs in bfloat16 passes, and the reference would
round as coarsely as the program it judges.

``quant`` names the control's precision: the reference run with the inputs
of every projection and of the output head rounded to that format (weights
per output channel, activations per token, both symmetric), the step a
later change might take to serve the bfloat16 configuration faster.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
QUANTS = ("int8", "fp8")


def round_to(x: jax.Array, quant: Optional[str], axes: Sequence[int]):
    """``x`` rounded to ``quant`` with one scale per slice over ``axes``."""
    if quant is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=tuple(axes), keepdims=True)
    if quant == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if quant == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown control precision {quant!r}")


def proj(eq: str, x, w, quant: Optional[str] = None, *, x_axes=(-1,),
         w_axes=(0,)):
    """Projection ``einsum(eq, x, w)`` in float32; under ``quant`` both
    inputs are first rounded (``x`` over its contracted ``x_axes``, ``w``
    over its contracted ``w_axes``)."""
    x = round_to(x.astype(F32), quant, x_axes)
    w = round_to(w.astype(F32), quant, w_axes)
    return jnp.einsum(eq, x, w, precision=HIGHEST,
                      preferred_element_type=F32)


def rms_norm(x, scale, eps: float):
    """RMS norm with the scale stored as an offset from 1."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))


def head(x, embed, quant: Optional[str] = None):
    """Logits against the tied embedding: (..., d) x (V, d) -> (..., V)."""
    return proj("...d,vd->...v", x, embed, quant, w_axes=(1,))


def normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)
