"""The dense family's sizes as the program's ``ModelConfig``."""
from __future__ import annotations


def model_config(name: str, sz, n_layers: int):
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig
    return ModelConfig(
        arch_id=name, family="dense", n_layers=n_layers, d_model=sz.d_model,
        n_heads=sz.n_heads, n_kv_heads=sz.n_kv_heads, head_dim=sz.head_dim,
        d_ff=sz.d_ff, vocab=sz.vocab, rope_theta=sz.rope_theta,
        sliding_window=sz.window, global_every=0, norm_eps=sz.norm_eps,
        mlp_gated=False, tie_embeddings=True, dtype=jnp.dtype(sz.dtype))
