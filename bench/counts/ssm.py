"""Operations and bytes one step of the state-space family needs.

Counted as the least the step must do: the projections and the
convolution once per token, and the state recurrence as one decay-and-add
and one read-out of the (heads x head_dim x d_state) state per token, which
is what any algorithm for it (the program's chunked one included) must at
least compute. Bytes: the weights read once, the state and the convolution
window written once after a prompt, read and written once per generated
token.
"""
from __future__ import annotations


def _itemsize(sz) -> int:
    return 2 if sz.dtype in ("bfloat16", "float16") else 4


def _per_token_flops(sz, n_layers: int) -> int:
    d, din, h = sz.d_model, sz.d_inner, sz.n_heads
    n_in = 2 * din + 2 * sz.n_groups * sz.d_state + h
    per_layer = (2 * d * n_in + 2 * sz.conv_dim * sz.d_conv + 2 * din * d
                 + 4 * h * sz.head_dim * sz.d_state)
    return n_layers * per_layer


def _weight_bytes(sz, n_layers: int) -> int:
    d, din, h = sz.d_model, sz.d_inner, sz.n_heads
    n_in = 2 * din + 2 * sz.n_groups * sz.d_state + h
    narrow = d * n_in + sz.conv_dim * (sz.d_conv + 1) + din + din * d + d
    return (n_layers * (narrow * _itemsize(sz) + 3 * h * 4)
            + (sz.vocab + 1) * d * _itemsize(sz))


def _state_bytes(sz, n_layers: int) -> int:
    return n_layers * (sz.n_heads * sz.head_dim * sz.d_state * 4
                       + (sz.d_conv - 1) * sz.conv_dim * _itemsize(sz))


def prefill(sz, n_layers: int, batch: int, seq: int):
    flops = batch * (seq * _per_token_flops(sz, n_layers)
                     + 2 * sz.d_model * sz.vocab)
    return flops, _weight_bytes(sz, n_layers) + batch * _state_bytes(sz, n_layers)


def decode(sz, n_layers: int, batch: int, pos: int):
    flops = batch * (_per_token_flops(sz, n_layers) + 2 * sz.d_model * sz.vocab)
    return flops, (_weight_bytes(sz, n_layers)
                   + 2 * batch * _state_bytes(sz, n_layers))
