"""The state-space family's sizes as the program's ``ModelConfig``."""
from __future__ import annotations


def model_config(name: str, sz, n_layers: int):
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig, SSMConfig
    return ModelConfig(
        arch_id=name, family="ssm", n_layers=n_layers, d_model=sz.d_model,
        n_heads=0, n_kv_heads=0, d_ff=0, vocab=sz.vocab,
        ssm=SSMConfig(d_state=sz.d_state, head_dim=sz.head_dim,
                      expand=sz.expand, d_conv=sz.d_conv,
                      n_groups=sz.n_groups, chunk_size=sz.chunk),
        norm_eps=sz.norm_eps, tie_embeddings=True, dtype=jnp.dtype(sz.dtype))
