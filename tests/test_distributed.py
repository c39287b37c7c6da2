"""Sharding rules + a real multi-device jit on a small host-device mesh.

The 512-device production dry-run needs its own process (XLA device count is
locked at first init), so the full sweep lives in launch/dryrun.py; here we
verify the same code path on an 8-device subprocess and the spec rules
in-process.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.distributed import sharding as shd
from repro.models import model as M

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeMesh:
    """Just enough Mesh interface for spec-rule tests."""

    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)
        self.axis_sizes = tuple(shape.values())


def test_param_specs_shard_big_dims():
    cfg = configs.get_config("yi-34b")
    params_shape = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), cfg))
    mesh = FakeMesh({"data": 16, "model": 16})
    specs = shd.param_specs(params_shape, mesh, fsdp=False)
    assert specs["embed"] == P("model", None)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    # every mlp w_in shards its ffn dim over model
    for path, spec in flat:
        s = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
        if s.endswith("mlp/w_in"):
            assert spec[-1] == "model", (s, spec)


def test_param_specs_divisibility_respected():
    """starcoder2 kv=2 heads can't shard 16 ways -> replicated, not padded."""
    cfg = configs.get_config("starcoder2-3b")
    params_shape = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), cfg))
    mesh = FakeMesh({"data": 16, "model": 16})
    specs = shd.param_specs(params_shape, mesh, fsdp=False)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    for path, spec in flat:
        s = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
        if "attn/wk" in s:
            assert spec[-2] is None     # 2 kv heads stay replicated


def test_fsdp_adds_data_axis():
    cfg = configs.get_config("kimi-k2-1t-a32b")
    params_shape = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), cfg))
    mesh = FakeMesh({"data": 16, "model": 16})
    s_no = shd.param_specs(params_shape, mesh, fsdp=False)
    s_yes = shd.param_specs(params_shape, mesh, fsdp=True)
    def count_data(t):
        return sum("data" in str(s) for s in jax.tree.leaves(
            t, is_leaf=lambda x: isinstance(x, P)))
    assert count_data(s_yes) > count_data(s_no)


def test_cache_specs_context_parallel_when_batch_1():
    cfg = configs.get_config("gemma3-27b")
    cache_shape = jax.eval_shape(lambda: M.init_cache(cfg, 1, 8192))
    mesh = FakeMesh({"data": 16, "model": 16})
    shape = configs.INPUT_SHAPES["long_500k"]
    specs = shd.cache_specs(cfg, shape, mesh, cache_shape)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    seq_sharded = [spec for path, spec in flat
                   if str(path[-1]).find("k") >= 0 and spec[-3] == "data"]
    assert seq_sharded, "long-context decode must context-parallel the cache"


@pytest.mark.slow
def test_small_mesh_train_step_runs():
    """Actually execute a sharded train step on 8 host devices."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import configs
from repro.distributed import api as dapi, sharding as shd
from repro.models import model as M
from repro.training import optim
from repro.training.train import make_train_step

cfg = configs.get_config("qwen2-moe-a2.7b", reduced=True)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
dapi.set_axis_rules(shd.axis_rules(mesh))
params = M.init(jax.random.PRNGKey(0), cfg)
opt = optim.init_state(params)
pspec = shd.param_specs(jax.eval_shape(lambda: params), mesh, fsdp=True)
ospec = {"mu": pspec, "nu": pspec, "step": P()}
step = make_train_step(cfg, optim.AdamWConfig(total_steps=5), impl="naive")
rng = jax.random.PRNGKey(1)
batch = {"tokens": jax.random.randint(rng, (8, 32), 0, cfg.vocab)}
batch["labels"] = batch["tokens"]
bspec = {k: P("data", None) for k in batch}
with jax.set_mesh(mesh):
    jitted = jax.jit(step, in_shardings=(pspec, ospec, bspec),
                     out_shardings=(pspec, ospec, None))
    p2, o2, m = jitted(params, opt, batch)
print("LOSS", float(m["loss"]))
assert jnp.isfinite(m["loss"])
"""
    # pin the subprocess to cpu: the host-platform device-count trick works
    # on the cpu backend, and without the pin jax probes for TPUs (slow
    # GCP-metadata retries on plain containers)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "LOSS" in out.stdout


def test_dryrun_results_if_present():
    """Validate any dry-run artifacts already produced by the sweep."""
    d = os.path.join(REPO, "results", "dryrun")
    if not os.path.isdir(d) or not os.listdir(d):
        pytest.skip("no dry-run artifacts yet")
    bad = []
    for f in os.listdir(d):
        if not f.endswith(".json"):
            continue
        rec = json.load(open(os.path.join(d, f)))
        if not rec.get("ok"):
            bad.append((f, rec.get("error")))
            continue
        assert rec["hlo_flops_per_dev"] > 0
        assert rec["bottleneck"] in ("compute", "memory", "collective")
    assert not bad, bad
