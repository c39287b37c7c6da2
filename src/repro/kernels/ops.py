"""jit'd public wrappers around the Pallas kernels.

The kernels lower to Mosaic for the TPU.  ``interpret=True`` runs them in
the Pallas interpreter instead, which is how the tests check them on a host
without a chip; nothing switches to it on its own.
"""
from __future__ import annotations

from typing import Optional

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_scan as _ssd


def flash_attention(q, k, v, q_pos=None, k_pos=None, *, causal: bool = True,
                    window: Optional[int] = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """Signature-compatible with repro.models.layers.attention."""
    bq = min(block_q, q.shape[1])
    bk = min(block_k, k.shape[1])
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=bq, block_k=bk, interpret=interpret)


def decode_attention(q, k_cache, v_cache, lengths, *, block_k: int = 128,
                     interpret: bool = False):
    bk = min(block_k, k_cache.shape[1])
    return _dec.decode_attention(q, k_cache, v_cache, lengths,
                                 block_k=bk, interpret=interpret)


def ssd_scan(x, dt, a_neg, b_mat, c_mat, *, chunk: int = 256,
             init_state=None, interpret: bool = False):
    chunk = min(chunk, x.shape[1])
    return _ssd.ssd_scan(x, dt, a_neg, b_mat, c_mat, chunk=chunk,
                         init_state=init_state, interpret=interpret)
