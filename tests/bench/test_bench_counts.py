"""bench/counts against operations and bytes worked out by hand for tiny
dense and state-space configurations."""
from bench.reference import dense as RD
from bench.reference import ssm as RS
from bench.counts import dense as CD
from bench.counts import ssm as CS

from conftest import DENSE, SSM


def test_dense_counts_by_hand():
    sz = RD.sizes(DENSE)      # d 128, 4 heads (2 kv) of 32, d_ff 256, V 512
    # per layer: wq + wo 2*128*4*32, wk + wv 2*128*2*32, MLP 2*128*256
    per_layer = 32768 + 16384 + 65536
    # prefill of 8 tokens, 2 rows, 2 layers: 36 causal (query, key) pairs
    # (window 64 > 8); head on the last position only
    flops = 2 * (2 * 8 * 2 * per_layer + 4 * 2 * 4 * 32 * 36 + 2 * 128 * 512)
    weights = (2 * (per_layer + 2 * 128) + 513 * 128) * 2
    kv_per_pos = 2 * 2 * 2 * 32 * 2                 # layers, k+v, kv heads, hd, bf16
    assert CD.prefill(sz, 2, 2, 8) == (flops, weights + 2 * 8 * kv_per_pos)
    assert flops == 7_675_904
    # decode at position 8: attends to 9 positions, reads 9 and writes 1
    flops = 2 * (2 * 2 * per_layer + 4 * 2 * 4 * 32 * 9 + 2 * 128 * 512)
    assert CD.decode(sz, 2, 2, 8) == (flops, weights + 2 * 10 * kv_per_pos)
    assert flops == 1_198_080
    # past the window, a query sees 64 keys
    f_far, _ = CD.decode(sz, 1, 1, 1000)
    assert f_far == 2 * per_layer + 4 * 4 * 32 * 64 + 2 * 128 * 512


def test_ssm_counts_by_hand():
    sz = RS.sizes(SSM)        # d 128, d_inner 256, 8 heads of 32, N 16, V 512
    assert (sz.vocab, sz.n_heads, sz.conv_dim) == (512, 8, 288)
    n_in = 2 * 256 + 2 * 16 + 8                     # z, x, B, C, dt
    per_token = (2 * 128 * n_in + 2 * 288 * 4 + 2 * 256 * 128
                 + 4 * 8 * 32 * 16)                 # in, conv, out, state
    assert per_token == 225_536
    weights = 2 * ((128 * n_in + 288 * 5 + 256 + 256 * 128 + 128) * 2
                   + 3 * 8 * 4) + 513 * 128 * 2
    state = 2 * (8 * 32 * 16 * 4 + 3 * 288 * 2)     # per row, both layers
    flops = 2 * (24 * 2 * per_token + 2 * 128 * 512)
    assert CS.prefill(sz, 2, 2, 24) == (flops, weights + 2 * state)
    assert flops == 21_913_600
    flops = 2 * (2 * per_token + 2 * 128 * 512)
    assert CS.decode(sz, 2, 2, 30) == (flops, weights + 2 * 2 * state)
