"""Operations and bytes one step of the dense family needs, from its sizes.

Counted as the least the step must do: every matrix product once, causal
attention over the pairs a query may see (inside the sliding window), the
weights and the keys and values read once, the new keys and values written
once. Work the program does beyond that (scores it masks out, a cache read
past the filled positions) is not counted, so a share of a peak built on
these counts stays under 100%.
"""
from __future__ import annotations

import numpy as np


def _itemsize(sz) -> int:
    return 2 if sz.dtype in ("bfloat16", "float16") else 4


def _layer_matmul_params(sz) -> int:
    d, h, kv, hd = sz.d_model, sz.n_heads, sz.n_kv_heads, sz.head_dim
    return d * h * hd * 2 + 2 * d * kv * hd + 2 * d * sz.d_ff


def _visible(pos_last: int, n_queries: int, window) -> int:
    """Key positions seen by the queries at positions
    pos_last-n_queries+1 .. pos_last, summed (causal, windowed)."""
    q = np.arange(pos_last - n_queries + 1, pos_last + 1)
    seen = q + 1 if window is None else np.minimum(q + 1, window)
    return int(seen.sum())


def _weight_bytes(sz, n_layers: int) -> int:
    per_layer = _layer_matmul_params(sz) + 2 * sz.d_model
    return (n_layers * per_layer + (sz.vocab + 1) * sz.d_model) * _itemsize(sz)


def _kv_bytes_per_pos(sz, n_layers: int) -> int:
    return n_layers * 2 * sz.n_kv_heads * sz.head_dim * _itemsize(sz)


def prefill(sz, n_layers: int, batch: int, seq: int):
    """(flops, bytes) of one prompt of ``seq`` tokens for ``batch`` rows,
    ending in the logits of the last position."""
    pairs = _visible(seq - 1, seq, sz.window)
    flops = batch * (2 * seq * n_layers * _layer_matmul_params(sz)
                     + 4 * n_layers * sz.n_heads * sz.head_dim * pairs
                     + 2 * sz.d_model * sz.vocab)
    kv = batch * min(seq, sz.window or seq) * _kv_bytes_per_pos(sz, n_layers)
    return flops, _weight_bytes(sz, n_layers) + kv


def decode(sz, n_layers: int, batch: int, pos: int):
    """(flops, bytes) of one generated token per row at position ``pos``
    (it attends to ``pos + 1`` positions, itself included)."""
    seen = _visible(pos, 1, sz.window)
    flops = batch * (2 * n_layers * _layer_matmul_params(sz)
                     + 4 * n_layers * sz.n_heads * sz.head_dim * seen
                     + 2 * sz.d_model * sz.vocab)
    kv = batch * (seen + 1) * _kv_bytes_per_pos(sz, n_layers)
    return flops, _weight_bytes(sz, n_layers) + kv
