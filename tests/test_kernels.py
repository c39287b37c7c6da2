"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

pytestmark = pytest.mark.slow  # jax model hot loops: run via `pytest -m slow`



def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("b,s,h,kv,hd", [
    (1, 128, 4, 4, 64), (2, 256, 4, 2, 64), (1, 256, 8, 1, 32),
    (2, 128, 2, 2, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, s, h, kv, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd), dtype)
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [32, 64])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 32))
    k = jax.random.normal(ks[1], (2, 256, 2, 32))
    v = jax.random.normal(ks[2], (2, 256, 2, 32))
    out = ops.flash_attention(q, k, v, window=window, block_q=64, block_k=64,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-4,
                               rtol=2e-4)


@pytest.mark.parametrize("b,h,kv,hd,L", [
    (2, 8, 2, 64, 256), (1, 4, 4, 32, 128), (3, 16, 2, 128, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, h, kv, hd, L, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (b, h, hd), dtype)
    kc = jax.random.normal(ks[1], (b, L, kv, hd), dtype)
    vc = jax.random.normal(ks[2], (b, L, kv, hd), dtype)
    lens = jax.random.randint(ks[3], (b,), 1, L + 1)
    out = ops.decode_attention(q, kc, vc, lens, block_k=64, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_decode_attention_respects_length_mask():
    """Entries past `lengths` must not influence the output."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    b, h, kv, hd, L = 1, 4, 2, 32, 128
    q = jax.random.normal(ks[0], (b, h, hd))
    kc = jax.random.normal(ks[1], (b, L, kv, hd))
    vc = jax.random.normal(ks[2], (b, L, kv, hd))
    lens = jnp.array([64])
    out1 = ops.decode_attention(q, kc, vc, lens, block_k=64, interpret=True)
    kc2 = kc.at[:, 64:].set(999.0)
    vc2 = vc.at[:, 64:].set(-999.0)
    out2 = ops.decode_attention(q, kc2, vc2, lens, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (2, 128, 4, 16, 2, 8, 32), (1, 64, 2, 32, 1, 16, 16),
    (2, 96, 4, 16, 4, 8, 32),
])
def test_ssd_scan_sweep(b, s, h, p, g, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a_neg = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, s, g, n))
    cm = jax.random.normal(ks[4], (b, s, g, n))
    y, f = ops.ssd_scan(x, dt, a_neg, bm, cm, chunk=chunk, interpret=True)
    yr, fr = ref.ssd_scan_ref(x, dt, a_neg, bm, cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=5e-4,
                               rtol=5e-3)
    np.testing.assert_allclose(np.asarray(f), np.asarray(fr), atol=5e-4,
                               rtol=5e-3)


def test_ssd_scan_initial_state_continuation():
    """Splitting a sequence in half and carrying state == one pass."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    b, s, h, p, g, n = 1, 128, 2, 16, 1, 8
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a_neg = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, s, g, n))
    cm = jax.random.normal(ks[4], (b, s, g, n))
    y_full, f_full = ops.ssd_scan(x, dt, a_neg, bm, cm, chunk=32,
                                  interpret=True)
    m = s // 2
    y1, f1 = ops.ssd_scan(x[:, :m], dt[:, :m], a_neg, bm[:, :m], cm[:, :m],
                          chunk=32, interpret=True)
    y2, f2 = ops.ssd_scan(x[:, m:], dt[:, m:], a_neg, bm[:, m:], cm[:, m:],
                          chunk=32, init_state=f1, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=1)),
                               np.asarray(y_full), atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(f2), np.asarray(f_full), atol=1e-4,
                               rtol=1e-3)


def test_model_layer_pallas_path_matches_jnp(monkeypatch):
    """attention(impl='pallas') inside the model layer == chunked/naive."""
    from repro.models import layers as L
    monkeypatch.setattr(ops, "flash_attention",
                        functools.partial(ops.flash_attention, interpret=True))
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    b, s, h, kv, hd = 2, 128, 4, 2, 64
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, kv, hd))
    v = jax.random.normal(ks[2], (b, s, kv, hd))
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    a = L.attention(q, k, v, pos, pos, impl="naive")
    b_ = L.attention(q, k, v, pos, pos, impl="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4,
                               rtol=2e-4)


def test_mamba_layer_pallas_path_matches_jnp(monkeypatch):
    from repro.configs.base import SSMConfig
    from repro.models import ssm as S
    monkeypatch.setattr(ops, "ssd_scan",
                        functools.partial(ops.ssd_scan, interpret=True))
    scfg = SSMConfig(d_state=16, head_dim=32, expand=2, chunk_size=32)
    d = 64
    params = S.init_mamba(jax.random.PRNGKey(7), d, scfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 64, d))
    y1, c1 = S.mamba_forward(params, x, d, scfg, use_pallas=False)
    y2, c2 = S.mamba_forward(params, x, d, scfg, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-3,
                               rtol=1e-2)
    np.testing.assert_allclose(np.asarray(c1["state"]), np.asarray(c2["state"]),
                               atol=1e-3, rtol=1e-2)
