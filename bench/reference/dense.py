"""Plain reference of the dense decoder family (starcoder2), in float32.

Imports nothing of the program. It follows starcoder2 (arXiv:2402.19173)
as the program serves it; where the program departs from the published
block, the reference departs with it, and the configuration file lists
each departure: RMS norms whose scale is an offset from 1 in place of
LayerNorm with bias, and projections without biases. Kept as published:
grouped-query attention with rotary embeddings (rotate-half convention),
a causal sliding window, a two-matrix MLP with tanh-approximated GELU, and
the output head tied to the embedding.

``init_params`` makes the benchmark's weights, in the parameter layout the
program takes (``{"embed", "stack": {"blocks": (layers,), "rem": ()},
"final_norm"}``, the layers stacked on a leading axis) and in the type
they are served in. ``logits`` runs the whole sequence, layer by layer.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from bench.reference.common import F32, HIGHEST, head, normal, proj, rms_norm


class Sizes(NamedTuple):
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    window: Optional[int]
    rope_theta: float
    norm_eps: float
    dtype: str


def sizes(group: dict) -> Sizes:
    """Sizes from a starcoder2 ``config.json`` group."""
    return Sizes(
        d_model=group["hidden_size"],
        n_heads=group["num_attention_heads"],
        n_kv_heads=group["num_key_value_heads"],
        head_dim=group["hidden_size"] // group["num_attention_heads"],
        d_ff=group["intermediate_size"],
        vocab=group["vocab_size"],
        window=group.get("sliding_window"),
        rope_theta=float(group["rope_theta"]),
        norm_eps=float(group["norm_epsilon"]),
        dtype=group["torch_dtype"])


def init_params(key, sz: Sizes, n_layers: int):
    """Weights from ``key``: matrices N(0, 1/fan_in), norm scales N(0, 0.01)
    around 1. Built layer by layer under ``lax.map`` so that no
    float32 copy of the whole stack exists at once."""
    d, h, kv, hd, f = sz.d_model, sz.n_heads, sz.n_kv_heads, sz.head_dim, sz.d_ff
    dt = jnp.dtype(sz.dtype)
    k_embed, k_norm, k_layers = jax.random.split(key, 3)

    def layer(k):
        ks = jax.random.split(k, 8)
        return {
            "ln1": normal(ks[0], (d,), 0.1, dt),
            "attn": {"wq": normal(ks[1], (d, h, hd), d ** -0.5, dt),
                     "wk": normal(ks[2], (d, kv, hd), d ** -0.5, dt),
                     "wv": normal(ks[3], (d, kv, hd), d ** -0.5, dt),
                     "wo": normal(ks[4], (h, hd, d), (h * hd) ** -0.5, dt)},
            "ln2": normal(ks[5], (d,), 0.1, dt),
            "mlp": {"w_in": normal(ks[6], (d, f), d ** -0.5, dt),
                    "w_out": normal(ks[7], (f, d), f ** -0.5, dt)},
        }

    blocks = jax.lax.map(layer, jax.random.split(k_layers, n_layers))
    return {"embed": normal(k_embed, (sz.vocab, d), d ** -0.5, dt),
            "stack": {"blocks": (blocks,), "rem": ()},
            "final_norm": normal(k_norm, (d,), 0.1, dt)}


def _rope(x, theta: float):
    """x: (B, T, heads, hd) at positions 0..T-1."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs      # (T, hd/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _attention(p, x, sz: Sizes, quant):
    t = x.shape[1]
    q = _rope(proj("btd,dhk->bthk", x, p["wq"], quant), sz.rope_theta)
    k = _rope(proj("btd,dhk->bthk", x, p["wk"], quant), sz.rope_theta)
    v = proj("btd,dhk->bthk", x, p["wv"], quant)
    rep = sz.n_heads // sz.n_kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HIGHEST) / math.sqrt(
        sz.head_dim)
    qi, ki = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    ok = ki <= qi
    if sz.window is not None:
        ok &= ki > qi - sz.window
    s = jnp.where(ok, s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    return proj("bthk,hkd->btd", o, p["wo"], quant, x_axes=(-2, -1),
                w_axes=(0, 1))


def logits(params, sz: Sizes, tokens, first: int, count: int,
           quant: Optional[str] = None):
    """Logits (B, count, V) at positions first..first+count-1 of ``tokens``
    (B, T), each predicting the token after it."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)

    def layer(x, p):
        h = rms_norm(x, p["ln1"], sz.norm_eps)
        x = x + _attention(p["attn"], h, sz, quant)
        h = rms_norm(x, p["ln2"], sz.norm_eps)
        u = _gelu_tanh(proj("btd,df->btf", h, p["mlp"]["w_in"], quant))
        return x + proj("btf,fd->btd", u, p["mlp"]["w_out"], quant), None

    for block in params["stack"]["blocks"]:
        x, _ = jax.lax.scan(layer, x, block)
    for p in params["stack"]["rem"]:
        x, _ = layer(x, p)
    x = rms_norm(x[:, first:first + count], params["final_norm"], sz.norm_eps)
    return head(x, params["embed"], quant)
