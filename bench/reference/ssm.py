"""Plain reference of the state-space family (mamba2), in float32.

Imports nothing of the program. Each layer is Mamba2's block (arXiv:
2405.21060): an RMS norm, one input projection to (z, x, B, C, dt), a
causal depthwise convolution with SiLU over (x, B, C), the selective state
recurrence run one token at a time (the plain form, not the chunked
algorithm the program uses), the skip ``D``, a gated RMS norm of
``y * silu(z)``, and the output projection; the head is tied to the
embedding. Where the program departs from the published block, the
reference departs with it, as the configuration file lists: norm scales
are offsets from 1, the gated norm takes ``gate_norm_eps``, and the
residual stream is not kept in float32 (here everything is float32).

``init_params`` makes the benchmark's weights in the program's layout and
serving types: the decay ``A_log``, the skip ``D`` and ``dt_bias`` in
float32, the rest in the configured type. ``A`` and ``dt`` follow
Mamba2's initialisation ranges, so the state carries information over
hundreds of tokens.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from bench.reference.common import F32, HIGHEST, head, normal, proj, rms_norm


class Sizes(NamedTuple):
    d_model: int
    vocab: int
    d_state: int
    head_dim: int
    expand: int
    d_conv: int
    n_groups: int
    chunk: int
    norm_eps: float
    gate_norm_eps: float
    dtype: str

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state


def sizes(group: dict) -> Sizes:
    """Sizes from a mamba2 ``config.json`` group (its ``ssm_cfg`` spelled
    out with the Mamba2 module's defaults)."""
    s = group["ssm_cfg"]
    mult = group.get("pad_vocab_size_multiple", 1)
    return Sizes(
        d_model=group["d_model"],
        vocab=-(-group["vocab_size"] // mult) * mult,
        d_state=s["d_state"], head_dim=s["headdim"], expand=s["expand"],
        d_conv=s["d_conv"], n_groups=s["ngroups"], chunk=s["chunk_size"],
        norm_eps=float(group["norm_epsilon"]),
        gate_norm_eps=float(group["gate_norm_epsilon"]),
        dtype=group["torch_dtype"])


def init_params(key, sz: Sizes, n_layers: int):
    d, din, h, c, k = sz.d_model, sz.d_inner, sz.n_heads, sz.conv_dim, sz.d_conv
    dt = jnp.dtype(sz.dtype)
    n_in = 2 * din + 2 * sz.n_groups * sz.d_state + h
    k_embed, k_norm, k_layers = jax.random.split(key, 3)

    def layer(key):
        ks = jax.random.split(key, 9)
        bound = k ** -0.5
        step = jnp.exp(jax.random.uniform(ks[5], (h,), F32, math.log(1e-3),
                                          math.log(1e-1)))
        return {
            "ln1": normal(ks[0], (d,), 0.1, dt),
            "ssm": {
                "in_proj": normal(ks[1], (d, n_in), d ** -0.5, dt),
                "conv_w": jax.random.uniform(ks[2], (c, k), F32, -bound,
                                             bound).astype(dt),
                "conv_b": jax.random.uniform(ks[3], (c,), F32, -bound,
                                             bound).astype(dt),
                "A_log": jnp.log(jax.random.uniform(ks[4], (h,), F32, 1.0,
                                                    16.0)),
                "D": 1.0 + normal(ks[6], (h,), 0.1, F32),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
                "norm": normal(ks[7], (din,), 0.1, dt),
                "out_proj": normal(ks[8], (din, d), din ** -0.5, dt),
            },
        }

    blocks = jax.lax.map(layer, jax.random.split(k_layers, n_layers))
    return {"embed": normal(k_embed, (sz.vocab, d), d ** -0.5, dt),
            "stack": {"blocks": (blocks,), "rem": ()},
            "final_norm": normal(k_norm, (d,), 0.1, dt)}


def _mixer(p, x, sz: Sizes, quant):
    b, t, _ = x.shape
    din, gn, h, hd = sz.d_inner, sz.n_groups * sz.d_state, sz.n_heads, sz.head_dim
    zxbcdt = proj("btd,de->bte", x, p["in_proj"], quant)
    z, xbc, dt_raw = (zxbcdt[..., :din], zxbcdt[..., din:2 * din + 2 * gn],
                      zxbcdt[..., 2 * din + 2 * gn:])
    w = p["conv_w"].astype(F32)                                   # (C, K)
    k = w.shape[-1]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + t] * w[:, i] for i in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(F32))
    xs = xbc[..., :din].reshape(b, t, h, hd)
    grp = jnp.arange(h) // (h // sz.n_groups)
    bm = xbc[..., din:din + gn].reshape(b, t, sz.n_groups, sz.d_state)[:, :, grp]
    cm = xbc[..., din + gn:].reshape(b, t, sz.n_groups, sz.d_state)[:, :, grp]
    dt = jax.nn.softplus(dt_raw + p["dt_bias"])                  # (B, T, H)
    a = -jnp.exp(p["A_log"])

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (x_t * dt_t[..., None])[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision=HIGHEST)

    s0 = jnp.zeros((b, h, hd, sz.d_state), F32)
    seq = tuple(jnp.moveaxis(v, 1, 0) for v in (xs, dt, bm, cm))
    _, y = jax.lax.scan(step, s0, seq)
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * xs
    y = y.reshape(b, t, din) * jax.nn.silu(z)
    y = rms_norm(y, p["norm"], sz.gate_norm_eps)
    return proj("bte,ed->btd", y, p["out_proj"], quant)


def logits(params, sz: Sizes, tokens, first: int, count: int,
           quant: Optional[str] = None):
    """Logits (B, count, V) at positions first..first+count-1 of ``tokens``
    (B, T), each predicting the token after it."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)

    def layer(x, p):
        h = rms_norm(x, p["ln1"], sz.norm_eps)
        return x + _mixer(p["ssm"], h, sz, quant), None

    for block in params["stack"]["blocks"]:
        x, _ = jax.lax.scan(layer, x, block)
    for p in params["stack"]["rem"]:
        x, _ = layer(x, p)
    x = rms_norm(x[:, first:first + count], params["final_norm"], sz.norm_eps)
    return head(x, params["embed"], quant)
