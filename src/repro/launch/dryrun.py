import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, print memory/cost analysis, and derive the roofline
terms (DESIGN.md §7).  MUST be run as its own process (the device-count flag
above is locked in at first jax init) — never import this module from tests.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""
import argparse
import json
import re
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.configs.base import InputShape, ModelConfig
from repro.distributed import api as dapi
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.training import optim
from repro.training.data import input_specs

# --- TPU v5e hardware constants (roofline) ---------------------------------
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum result-shape bytes of every collective op in (partitioned) HLO."""
    out = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.search(r"=\s*(.*?)\s+(%?[a-z0-9\-]*?)"
                      r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                      r"collective-permute)(-start|-done)?(\.[0-9]+)?\(",
                      stripped)
        if not m:
            continue
        kind = m.group(3)
        if m.group(4) == "-done":            # avoid double counting async pairs
            continue
        total = 0
        for dt, dims in _SHAPE_RE.findall(m.group(1)):
            nbytes = _DTYPE_BYTES.get(dt)
            if nbytes is None:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * nbytes
        out[kind] += total
        counts[kind] += 1
    out["_counts"] = counts
    return out


def serving_fsdp(cfg: ModelConfig, mesh) -> bool:
    """Shard serving weights over data too when TP-only exceeds ~8 GB/chip."""
    model_sz = mesh.shape.get("model", 1)
    return cfg.n_params() * 2 / model_sz > 8e9


# ---------------------------------------------------------------------------
# step builders: (fn, arg ShapeDtypeStructs, in_shardings, donate)
# ---------------------------------------------------------------------------
def _weights(cfg, mesh, weights_mode):
    """-> (fsdp, expert_mode) for serving param specs."""
    if weights_mode == "auto":
        return serving_fsdp(cfg, mesh), "none"
    if weights_mode == "tp":
        return False, "none"
    if weights_mode == "fsdp":
        return True, "none"
    if weights_mode == "expert2d":
        return True, "hidden_data"
    if weights_mode == "expertff":
        return False, "hidden_model"
    raise ValueError(weights_mode)


def build_case(cfg: ModelConfig, shape: InputShape, mesh, *,
               moe_impl: str = "einsum", attn_chunk: int = 1024,
               unroll: bool = False, weights_mode: str = "auto",
               microbatch: int = 1):
    ax = shd.MeshAxes.of(mesh)
    data_axes = ax.data
    batch_dim = shape.global_batch
    bspec_axis = data_axes if batch_dim % max(
        np.prod([mesh.shape[a] for a in data_axes]), 1) == 0 else None
    if bspec_axis is not None and len(bspec_axis) == 1:
        bspec_axis = bspec_axis[0]

    params_shape = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), cfg))

    if shape.kind == "train":
        ocfg = optim.AdamWConfig()
        opt_shape = jax.eval_shape(lambda: optim.init_state(params_shape))

        def loss_fn_u(p, batch):
            from repro.training.train import cross_entropy
            hidden, aux = M.forward(p, cfg, batch, impl="chunked",
                                    moe_impl=moe_impl, remat=True,
                                    unroll=unroll)
            ce = cross_entropy(hidden, p["embed"], batch["labels"])
            return ce + aux, {"ce": ce, "aux": aux}

        def grads_of(params, batch):
            return jax.value_and_grad(
                lambda p: loss_fn_u(p, batch), has_aux=True)(params)

        def step(params, opt_state, batch):
            from repro.training import optim as _optim
            if microbatch > 1:
                # gradient accumulation: peak activation memory ~ 1/N of the
                # full-batch step (§Perf capacity iteration for *train_4k)
                mb = {k: v.reshape((microbatch, v.shape[0] // microbatch)
                                   + v.shape[1:]) for k, v in batch.items()}

                def body(acc, one):
                    (l, parts), g = grads_of(params, one)
                    acc_g, acc_l = acc
                    return (jax.tree.map(jnp.add, acc_g, g), acc_l + l), None

                zero = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (gsum, lsum), _ = jax.lax.scan(
                    body, (zero, jnp.zeros((), jnp.float32)), mb)
                grads = jax.tree.map(lambda g: g / microbatch, gsum)
                loss = lsum / microbatch
                parts = {"ce": loss, "aux": jnp.zeros((), jnp.float32)}
            else:
                (loss, parts), grads = grads_of(params, batch)
            params, opt_state, om = _optim.apply_updates(
                params, grads, opt_state, ocfg)
            return params, opt_state, {"loss": loss, **parts, **om}
        pspec = shd.param_specs(params_shape, mesh, fsdp=True)
        ospec = {
            "mu": pspec, "nu": pspec, "step": P(),
        }
        batch_shape = input_specs(cfg, shape.seq_len, batch_dim, "train",
                                  dtype=cfg.dtype)
        bspec = {k: P(bspec_axis, *([None] * (len(v.shape) - 1)))
                 for k, v in batch_shape.items()}
        args = (params_shape, opt_shape, batch_shape)
        in_shardings = (pspec, ospec, bspec)
        out_shardings = (pspec, ospec, None)
        donate = (0, 1)
        fn = step
    elif shape.kind == "prefill":
        fsdp, e2d = _weights(cfg, mesh, weights_mode)
        pspec = shd.param_specs(params_shape, mesh, fsdp=fsdp, expert_mode=e2d)
        batch_shape = input_specs(cfg, shape.seq_len, batch_dim, "prefill",
                                  dtype=cfg.dtype)
        bspec = {k: P(bspec_axis, *([None] * (len(v.shape) - 1)))
                 for k, v in batch_shape.items()}

        def fn(params, batch):
            hl, caches, _ = M.prefill(params, cfg, batch, impl="chunked",
                                      moe_impl=moe_impl, unroll=unroll)
            return hl, caches

        args = (params_shape, batch_shape)
        in_shardings = (pspec, bspec)
        out_shardings = None
        donate = ()
    elif shape.kind == "decode":
        fsdp, e2d = _weights(cfg, mesh, weights_mode)
        pspec = shd.param_specs(params_shape, mesh, fsdp=fsdp, expert_mode=e2d)
        prefix = cfg.n_patches if cfg.family == "vlm" else 0
        cache_shape = jax.eval_shape(
            lambda: M.init_cache(cfg, batch_dim, shape.seq_len + prefix))
        cspec = shd.cache_specs(cfg, shape, mesh, cache_shape)
        batch_shape = input_specs(cfg, shape.seq_len, batch_dim, "decode",
                                  dtype=cfg.dtype)
        tspec = P(bspec_axis, None)

        def fn(params, caches, cache_len, tokens):
            return M.decode_step(params, cfg, caches, cache_len, tokens,
                                 moe_impl=moe_impl, unroll=unroll)

        args = (params_shape, cache_shape,
                jax.ShapeDtypeStruct((), jnp.int32), batch_shape["tokens"])
        in_shardings = (pspec, cspec, P(), tspec)
        out_shardings = (None, cspec)
        donate = (1,)
    else:
        raise ValueError(shape.kind)
    return fn, args, in_shardings, out_shardings, donate


def model_flops_per_device(cfg: ModelConfig, shape: InputShape,
                           n_devices: int) -> float:
    n = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n * tokens / n_devices
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n * tokens / n_devices
    return 2.0 * n * shape.global_batch / n_devices   # decode: 1 tok/seq


def _probe_cfg(cfg: ModelConfig, k: int) -> ModelConfig:
    """k-block-deep clone of cfg (same pattern period + remainder)."""
    import dataclasses as dc

    from repro.models.stack import plan
    pl = plan(cfg, cross=(cfg.family == "encdec"))
    changes = {"n_layers": k * pl.period + len(pl.rem)}
    if cfg.family == "encdec":
        changes["n_encoder_layers"] = k
    return dc.replace(cfg, **changes)


def probe_costs(cfg: ModelConfig, shape: InputShape, mesh, *,
                moe_impl: str = "einsum", weights_mode: str = "auto",
                microbatch: int = 1):
    """Exact per-block cost via two unrolled probes (k=1, k=2 blocks).

    XLA's cost_analysis counts a while-loop body once, so the scanned
    deployment program under-reports flops/bytes/collectives by ~n_rep.
    cost(k) is affine in k for a homogeneous stack, so
      total(n_rep) = cost(1) + (n_rep - 1) * (cost(2) - cost(1)).
    """
    from repro.models.stack import plan
    pl_full = plan(cfg, cross=(cfg.family == "encdec"))
    res = {}
    for k in (1, 2):
        pcfg = _probe_cfg(cfg, k)
        fn, args, in_sh, out_sh, donate = build_case(
            pcfg, shape, mesh, moe_impl=moe_impl, unroll=True,
            weights_mode=weights_mode, microbatch=microbatch)
        with jax.set_mesh(mesh):
            compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                               donate_argnums=donate).lower(*args).compile()
        ca = compiled.cost_analysis() or {}
        col = collective_bytes(compiled.as_text())
        res[k] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": {kk: v for kk, v in col.items() if not kk.startswith("_")},
        }
    n_rep = pl_full.n_rep

    def extrap(a, b):
        return max(a + (n_rep - 1) * (b - a), 0.0)

    out = {
        "flops": extrap(res[1]["flops"], res[2]["flops"]),
        "bytes": extrap(res[1]["bytes"], res[2]["bytes"]),
        "coll": {kk: extrap(res[1]["coll"][kk], res[2]["coll"][kk])
                 for kk in res[1]["coll"]},
        "probe_raw": res,
        "n_rep": n_rep,
    }
    return out


def make_custom_mesh(spec: str):
    """'32x8' -> (data=32, model=8) mesh over the first 256 host devices."""
    d, m = (int(x) for x in spec.split("x"))
    devs = np.array(jax.devices()[:d * m]).reshape(d, m)
    from jax.sharding import AxisType, Mesh
    return Mesh(devs, ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def run_case(arch: str, shape_name: str, *, multi_pod: bool = False,
             moe_impl: str = "einsum", verbose: bool = True,
             save_hlo: Optional[str] = None, mesh_shape: Optional[str] = None,
             weights_mode: str = "auto", microbatch: int = 1) -> Dict:
    cfg = configs.get_config(arch)
    shape = configs.INPUT_SHAPES[shape_name]
    mesh = (make_custom_mesh(mesh_shape) if mesh_shape
            else make_production_mesh(multi_pod=multi_pod))
    n_dev = int(np.prod(list(mesh.shape.values())))
    rec = {"arch": arch, "shape": shape_name, "mesh": "x".join(
        f"{k}={v}" for k, v in mesh.shape.items()), "devices": n_dev,
        "moe_impl": moe_impl, "weights_mode": weights_mode, "ok": False}
    t0 = time.time()
    try:
        dapi.set_axis_rules(shd.axis_rules(mesh))
        fn, args, in_sh, out_sh, donate = build_case(
            cfg, shape, mesh, moe_impl=moe_impl, weights_mode=weights_mode,
            microbatch=microbatch)
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            rec["lower_s"] = round(time.time() - t0, 1)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 1)
        mem = compiled.memory_analysis()
        if verbose:
            print(mem)
        ca = compiled.cost_analysis() or {}
        if verbose:
            print({k: ca.get(k) for k in ("flops", "bytes accessed",
                                          "transcendentals")})
        hlo = compiled.as_text()
        col = collective_bytes(hlo)
        if save_hlo:
            with open(save_hlo, "w") as f:
                f.write(hlo)

        # exact costs from the unrolled 1-/2-block probes (scan bodies are
        # counted once by XLA's cost model — see probe_costs)
        probe = probe_costs(cfg, shape, mesh, moe_impl=moe_impl,
                            weights_mode=weights_mode, microbatch=microbatch)
        flops = probe["flops"]
        bytes_acc = probe["bytes"]
        col_total = sum(probe["coll"].values())
        col = {**probe["coll"], "_counts": col.get("_counts", {}),
               "_scanned_raw": {k: v for k, v in col.items()
                                if not k.startswith("_")}}
        mflops = model_flops_per_device(cfg, shape, n_dev)
        rec.update({
            "ok": True,
            "hlo_flops_per_dev": flops,
            "hlo_bytes_per_dev": bytes_acc,
            "collective_bytes_per_dev": col_total,
            "collectives": col,
            "mem": {
                "argument_gb": mem.argument_size_in_bytes / 2**30,
                "output_gb": mem.output_size_in_bytes / 2**30,
                "temp_gb": mem.temp_size_in_bytes / 2**30,
                "alias_gb": mem.alias_size_in_bytes / 2**30,
            },
            "model_flops_per_dev": mflops,
            "compute_s": flops / PEAK_FLOPS,
            "memory_s": bytes_acc / HBM_BW,
            "collective_s": col_total / ICI_BW,
            "useful_flops_ratio": mflops / flops if flops else 0.0,
        })
        terms = {"compute": rec["compute_s"], "memory": rec["memory_s"],
                 "collective": rec["collective_s"]}
        rec["bottleneck"] = max(terms, key=terms.get)
        if verbose:
            print({k: f"{v:.3e}" for k, v in terms.items()},
                  "->", rec["bottleneck"],
                  f"useful={rec['useful_flops_ratio']:.3f}")
    except Exception as e:  # noqa: BLE001 — report, don't die mid-sweep
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print("FAILED:", rec["error"])
    finally:
        dapi.set_axis_rules(None)
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--moe-impl", default="einsum")
    ap.add_argument("--mesh-shape", default=None,
                    help="override mesh, e.g. 32x8 (hillclimb experiments)")
    ap.add_argument("--weights-mode", default="auto",
                    choices=["auto", "tp", "fsdp", "expert2d", "expertff"])
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    pairs = (configs.all_dryrun_pairs() if args.all
             else [(args.arch, configs.INPUT_SHAPES[args.shape])])
    tag = "multipod" if args.multi_pod else "singlepod"
    if args.mesh_shape:
        tag = f"mesh{args.mesh_shape}"
    if args.weights_mode != "auto":
        tag += f"__{args.weights_mode}"
    if args.microbatch > 1:
        tag += f"__mb{args.microbatch}"
    n_ok = 0
    for arch, shape in pairs:
        sname = shape.name if hasattr(shape, "name") else shape
        path = os.path.join(args.out,
                            f"{arch}__{sname}__{tag}__{args.moe_impl}.json")
        if args.skip_existing and os.path.exists(path):
            print(f"skip {arch} x {sname} ({tag})")
            n_ok += 1
            continue
        print(f"=== {arch} x {sname} ({tag}, moe={args.moe_impl}) ===",
              flush=True)
        rec = run_case(arch, sname, multi_pod=args.multi_pod,
                       moe_impl=args.moe_impl, mesh_shape=args.mesh_shape,
                       weights_mode=args.weights_mode,
                       microbatch=args.microbatch)
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        n_ok += int(rec["ok"])
        print(f"    -> ok={rec['ok']} total={rec['total_s']}s", flush=True)
    print(f"dry-run complete: {n_ok}/{len(pairs)} ok")


if __name__ == "__main__":
    main()
