"""The Pallas kernels compile for a TPU v5e chip at the served widths.

The chip is described, not attached: the TPU compiler installed with JAX
compiles for it here and refuses what the chip's compiler would refuse
(block tiling, unsupported primitives, scoped VMEM), which interpret mode
does not check.  Nothing runs.  The topology is described inside a fixture,
so collecting this file never loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.sharding import SingleDeviceSharding

from repro.configs import mamba2_2p7b, starcoder2_3b
from repro.kernels import ops

SEQ = 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    sharding = SingleDeviceSharding(topo.devices[0])
    # pay the compiler's and Mosaic's one-time start-up here, not in a test
    _compile(lambda x: pl.pallas_call(
        _copy, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x),
        sharding, ((8, 128), jnp.float32))
    return sharding


def _copy(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo          # the Mosaic kernel is in there


def test_flash_attention_compiles_for_v5e(one_chip):
    cfg = starcoder2_3b.full()
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    bf = jnp.bfloat16
    _compile(lambda q, k, v: ops.flash_attention(q, k, v,
                                                 window=cfg.sliding_window),
             one_chip, ((1, SEQ, h, hd), bf), ((1, SEQ, kv, hd), bf),
             ((1, SEQ, kv, hd), bf))


def test_decode_attention_compiles_for_v5e(one_chip):
    cfg = starcoder2_3b.full()
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    bf = jnp.bfloat16
    _compile(ops.decode_attention, one_chip, ((4, h, hd), bf),
             ((4, SEQ, kv, hd), bf), ((4, SEQ, kv, hd), bf), ((4,), jnp.int32))


def test_ssd_scan_compiles_for_v5e(one_chip):
    cfg = mamba2_2p7b.full()
    s = cfg.ssm
    nh, g, n = s.n_heads(cfg.d_model), s.n_groups, s.d_state
    f32 = jnp.float32
    _compile(lambda x, dt, a, b, c: ops.ssd_scan(x, dt, a, b, c,
                                                 chunk=s.chunk_size),
             one_chip, ((1, SEQ, nh, s.head_dim), cfg.dtype),
             ((1, SEQ, nh), f32), ((nh,), f32), ((1, SEQ, g, n), cfg.dtype),
             ((1, SEQ, g, n), cfg.dtype))
