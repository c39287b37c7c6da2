"""Serve a published-width stage once on one TPU chip, and check what it gives.

  python chip_smoke.py

One process drives one chip through the system's own entry points.  It exits
non-zero, and prints no result, unless JAX's first device is a TPU: there is
no CPU path.  Each phase prints one JSON line; any failure ends the run.

  serve        starcoder2-3b at its published widths in bf16, random weights
               from a seed, in two resident variants (30 and 15 layers).
               ``launch.serve.build_pipeline`` profiles both on the chip,
               ``optimizer.solve`` picks a config at one fixed rate, and
               ``PipelineEngine`` serves batched requests before and after
               one variant switch.
  cache_check  the served variant's logits from prefill plus decode steps
               through the KV cache, against ``model.forward`` over the
               whole sequence with the same tokens and weights.
  kernels      flash_attention, decode_attention and ssd_scan lowered to
               Mosaic (``interpret=False``), against ``repro.kernels.ref``.

The last line is {"ok": true, "device": {...}} as JAX reports the device.
No number printed here is a benchmark metric: the walls are one cold or warm
run's set-up and smoke times.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
PROFILE_BATCHES = (1, 4)
PROMPT_LEN = 16
GEN_TOKENS = 8
BATCH = 4                  # prompts per served batch
N_BATCHES = 4              # batches served per variant
RATE = 2.0                 # requests/s given to the planner
DECODE_STEPS = 4           # decode steps checked in cache_check
KERNEL_SEQ = 1024          # sequence length of the kernel checks

# Logits of the bf16 model have a spread of about 1 (rms-normed hidden
# state against an embedding scaled by 1/sqrt(d_model)), and bf16 keeps 8
# significant bits: one ulp at the largest logits is already 1/32.  The
# cached path (naive prefill attention, one-token decode) and the full
# forward (chunked attention) round differently at every op of 30 layers;
# they differ by 0.055 at published widths on a TPU v5e, and by 0.04-0.05 in
# a bf16 copy of this check at d_model 128 and 512 on XLA's CPU backend.
# Masking out the newest cache entry alone moves the logits by 0.4 there.
CACHE_ATOL = 0.2

# Kernel tolerances, the ones tests/test_kernels.py holds the kernels to:
# bf16 attention rounds p and the output to 8 significant bits; ssd_scan
# runs in f32 with f32 MXU contraction, against an f32 recurrence.
BF16_TOL = dict(atol=2e-2, rtol=2e-2)
F32_TOL = dict(atol=5e-4, rtol=5e-3)


def stage_family():
    """starcoder2-3b at its published widths, and the same cut to 15 layers.

    The accuracies are assumed, not measured (the weights are random):
    31.7 is the published HumanEval pass@1 of starcoder2-3b; the 15-layer
    cut has no published score and is given half of it.
    """
    from repro.configs import starcoder2_3b
    full = starcoder2_3b.full()
    return [(f"{starcoder2_3b.ARCH_ID}-30l", full, 31.7),
            (f"{starcoder2_3b.ARCH_ID}-15l",
             dataclasses.replace(full, n_layers=15), 15.85)]


class CompileCounter:
    """Backend compiles and persistent-cache hits seen by this process."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _n_params(tree) -> int:
    import jax
    return int(sum(x.size for x in jax.tree.leaves(tree)))


def phase_serve(device, counter: CompileCounter):
    from repro.core import optimizer as OPT
    from repro.launch.serve import build_pipeline

    family = stage_family()
    t0 = time.perf_counter()
    c0 = counter.seconds
    pipe, engine = build_pipeline(
        "chip-smoke", [(family[0][1].arch_id, family)],
        gen_tokens=GEN_TOKENS, profile_batches=PROFILE_BATCHES,
        verbose=False)
    build_s = time.perf_counter() - t0
    resident = device.memory_stats()
    srv = engine.stages[0]

    sol = OPT.solve(pipe, RATE, OPT.Objective(alpha=10.0, beta=0.5))
    if not sol.feasible or sol.config is None:
        raise RuntimeError(f"no feasible config at {RATE} req/s: {sol}")
    chosen = sol.config.stages[0].variant
    other = next(v for v in srv.variants if v != chosen)

    vocab = srv.variants[chosen][0].vocab
    prompts = np.random.default_rng(SEED).integers(
        0, vocab, (N_BATCHES, BATCH, PROMPT_LEN), dtype=np.int32)
    compiles_before = counter.compiles
    t1 = time.perf_counter()
    served = {}
    for variant in (chosen, other):
        engine.configure([variant])
        for p in prompts:
            out, _ = engine.serve(p)
            if out.shape != (BATCH, GEN_TOKENS) or out.min() < 0 \
                    or out.max() >= vocab:
                raise RuntimeError(f"bad output {out.shape} from {variant}")
            served[variant] = served.get(variant, 0) + BATCH
    serve_s = time.perf_counter() - t1
    stats = device.memory_stats()
    per_variant = {v: {"layers": cfg.n_layers,
                       "params": _n_params(srv.params[v])}
                   for v, (cfg, _) in srv.variants.items()}
    line = {
        "phase": "serve",
        "variants": per_variant,
        "params": sum(v["params"] for v in per_variant.values()),
        "bytes_in_use_both_resident": resident["bytes_in_use"],
        "peak_bytes_in_use": stats["peak_bytes_in_use"],
        "bytes_limit": stats.get("bytes_limit"),
        "build_and_profile_s": build_s,
        "compile_s": counter.seconds - c0,
        "serve_s": serve_s,
        "compiles_while_serving": counter.compiles - compiles_before,
        "planned": {"rate": RATE, "variant": chosen,
                    "batch": sol.config.stages[0].batch,
                    "replicas": sol.config.stages[0].replicas},
        "switched_to": other,
        "requests_served": served,
    }
    print(json.dumps(line), flush=True)
    return engine


def phase_cache_check(engine):
    import jax
    import jax.numpy as jnp
    from repro.models import model as M

    srv = engine.stages[0]
    variant = stage_family()[0][0]      # the 30-layer published config
    cfg = srv.variants[variant][0]
    params = srv.params[variant]
    seq = np.random.default_rng(SEED + 1).integers(
        0, cfg.vocab, (BATCH, PROMPT_LEN + DECODE_STEPS), dtype=np.int32)

    # the server's own compiled prefill and decode programs
    prefill = srv._get_prefill(variant, BATCH, PROMPT_LEN)
    decode = srv._get_decode(variant, BATCH)
    lg, caches = prefill(params, jnp.asarray(seq[:, :PROMPT_LEN]))
    cached = [lg]                       # position PROMPT_LEN - 1
    for t in range(DECODE_STEPS):       # positions PROMPT_LEN .. + t
        pos = PROMPT_LEN + t
        lg, caches = decode(params, caches, jnp.int32(pos),
                            jnp.asarray(seq[:, pos:pos + 1]))
        cached.append(lg)
    cached = np.asarray(jnp.stack(cached, axis=1), np.float32)

    @jax.jit
    def full_logits(params, tokens):
        hidden, _ = M.forward(params, cfg, {"tokens": tokens})
        return M.logits(params, cfg, hidden)

    full = full_logits(params, jnp.asarray(seq))
    want = np.asarray(full[:, PROMPT_LEN - 1:], np.float32)
    if not (np.isfinite(cached).all() and np.isfinite(want).all()):
        raise RuntimeError("non-finite logits")
    err = float(np.max(np.abs(cached - want)))
    line = {"phase": "cache_check", "variant": variant,
            "positions": [PROMPT_LEN - 1, PROMPT_LEN - 1 + DECODE_STEPS],
            "max_abs_logit_err": err, "atol": CACHE_ATOL,
            "logit_std": float(want.std()),
            "max_abs_logit": float(np.max(np.abs(want)))}
    print(json.dumps(line), flush=True)
    if not err <= CACHE_ATOL:
        raise RuntimeError(f"cached logits off by {err} > {CACHE_ATOL}")


def _check(name, got, want, tol, errs, failed):
    """Record the largest error; fail where |got - want| > atol + rtol|want|."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    diff = np.abs(got - want)
    errs[name] = float(np.max(diff))
    if not np.all(diff <= tol["atol"] + tol["rtol"] * np.abs(want)):
        failed.append(name)


def phase_kernels():
    import jax
    import jax.numpy as jnp
    from repro.configs import mamba2_2p7b, starcoder2_3b
    from repro.kernels import ops, ref

    sc = starcoder2_3b.full()
    h, kv, hd = sc.n_heads, sc.n_kv_heads, sc.head_dim_
    seq = KERNEL_SEQ
    ks = jax.random.split(jax.random.PRNGKey(SEED + 2), 12)
    bf = jnp.bfloat16
    errs, failed = {}, []

    q = jax.random.normal(ks[0], (1, seq, h, hd), bf)
    k = jax.random.normal(ks[1], (1, seq, kv, hd), bf)
    v = jax.random.normal(ks[2], (1, seq, kv, hd), bf)
    _check("flash_attention",
           ops.flash_attention(q, k, v, window=sc.sliding_window),
           ref.flash_attention_ref(q, k, v, window=sc.sliding_window),
           BF16_TOL, errs, failed)

    qd = jax.random.normal(ks[3], (BATCH, h, hd), bf)
    kc = jax.random.normal(ks[4], (BATCH, seq, kv, hd), bf)
    vc = jax.random.normal(ks[5], (BATCH, seq, kv, hd), bf)
    lens = jnp.array([seq, 700, 129, 1], jnp.int32)
    _check("decode_attention",
           ops.decode_attention(qd, kc, vc, lens),
           ref.decode_attention_ref(qd, kc, vc, lens), BF16_TOL, errs, failed)

    mb = mamba2_2p7b.full()
    s = mb.ssm
    nh, n, g = s.n_heads(mb.d_model), s.d_state, s.n_groups
    x = jax.random.normal(ks[6], (1, seq, nh, s.head_dim))
    dt = jax.nn.softplus(jax.random.normal(ks[7], (1, seq, nh)))
    a_neg = -jnp.exp(jax.random.normal(ks[8], (nh,)))
    bm = jax.random.normal(ks[9], (1, seq, g, n))
    cm = jax.random.normal(ks[10], (1, seq, g, n))
    y, final = ops.ssd_scan(x, dt, a_neg, bm, cm, chunk=s.chunk_size)
    with jax.default_matmul_precision("highest"):
        y_ref, final_ref = ref.ssd_scan_ref(x, dt, a_neg, bm, cm)
    _check("ssd_scan", y, y_ref, F32_TOL, errs, failed)
    _check("ssd_scan_state", final, final_ref, F32_TOL, errs, failed)

    line = {"phase": "kernels", "interpret": False, "max_abs_err": errs,
            "tol": {"bf16": BF16_TOL, "f32": F32_TOL},
            "widths": {"attention": {"heads": h, "kv_heads": kv,
                                     "head_dim": hd, "seq": seq},
                       "ssd_scan": {"heads": nh, "head_dim": s.head_dim,
                                    "d_state": n, "chunk": s.chunk_size,
                                    "seq": seq}}}
    print(json.dumps(line), flush=True)
    if failed:
        raise RuntimeError(f"outside tolerance: {failed}")


def main() -> int:
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device.platform}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    counter = CompileCounter()
    print(json.dumps({"phase": "setup", "compile_cache": cache_dir}),
          flush=True)
    engine = phase_serve(device, counter)
    phase_cache_check(engine)
    phase_kernels()
    print(json.dumps({"phase": "compile", "compile_s": counter.seconds,
                      "compiles": counter.compiles,
                      "persistent_cache_hits": counter.cache_hits}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
