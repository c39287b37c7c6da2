"""Run one cell of the chip benchmark and print its result.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything runs in this one process, on the chip it is started on: set-up
(weights from the seed, profiling, warm-up, compiles), a window of
``--seconds`` of open-loop traffic with the planner choosing variants and
batch sizes, the drain, then the check of what was served against the
plain reference. It exits non-zero, and prints no result, unless JAX's
devices are TPUs, as many as the cell asks for.

Standard output: a few JSON lines the last line leaves out (peak memory,
the generator's lateness, the planner's choice at each boundary, in a
traced run the idle share of the whole window), then one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` a ``breakdown``, and last ``compared``: each number the
check compared, beside its limit. The same numbers end standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices, or exit non-zero with no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX has {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(2)
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def start_trace(trace_dir: str) -> None:
    """Device ops and the harness's annotations only: no Python tracer, no
    HLO protos, host events at the annotations' level."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def info_lines(rec: dict, peak_bytes: int, trace: dict) -> list:
    late = rec["generator_late_s"]
    lines = [
        {"memory_peak_bytes": peak_bytes},
        {"generator_late_s": {"n": len(late),
                              "mean": sum(late) / len(late) if late else 0.0,
                              "max": max(late, default=0.0)}},
        {"plans": rec["plans"]},
        {"window": {"seconds": rec["window_s"], "rate_rps": rec["rate_rps"],
                    "sla_s": rec["sla_s"], "drain_end_s": rec["end_s"],
                    "batches": len(rec["batches"]),
                    "setup_s": rec["setup_s"]}},
    ]
    if trace:
        lines.append({"trace_window": {
            "busy_s": trace["busy_s"], "window_s": trace["window_s"],
            "idle_share": 1.0 - trace["busy_s"] / trace["window_s"],
            "xplane_bytes": trace["xplane_bytes"],
            "stop_s": rec["trace_stop_s"], "reduce_s": trace["reduce_s"]}})
    return lines


def set_up(spec, seed: int):
    """The cell's chips, checked, and its system under test set up from
    ``seed`` (weights, profiles, warm-up, compiles into the cache)."""
    devices = tpu_devices(spec.chips)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from bench.driver import Cell
    cell = Cell(spec, seed)
    cell.setup()
    return cell, devices


def run(cell, args, devices) -> dict:
    """Serve the window on the set-up ``driver.Cell``, check; returns the
    result object and the lines that go before it."""
    import jax
    from bench import check as CK
    from bench import counts, metrics, trace_reduce

    spec = cell.spec
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        if trace_dir:
            start_trace(trace_dir)
        t_window = time.perf_counter()
        rec = cell.run_window(args.seconds, trace=bool(args.trace))
        rec["setup_s"] = t_window - T_START
        if trace_dir:
            t = time.perf_counter()
            jax.profiler.stop_trace()
            rec["trace_stop_s"] = time.perf_counter() - t
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        tr = {}
        if trace_dir:
            t = time.perf_counter()
            xplane = trace_reduce.find_xplane(trace_dir)
            tr = trace_reduce.reduce(trace_reduce.load(xplane))
            tr["reduce_s"] = time.perf_counter() - t
            tr["xplane_bytes"] = os.path.getsize(xplane)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    verdict = CK.check(cell, rec)
    kind = devices[0].device_kind
    rec["peaks"] = peaks_for(kind)
    rec["trace"] = tr
    rec["accuracy"] = {s: {v: acc for v, _, acc in st.variants}
                       for s, st in enumerate(spec.stages)}
    counts.annotate(spec, rec, rec["peaks"])
    wanted = spec.per_layer if args.trace else spec.end_to_end
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {
        "correct": verdict["correct"],
        "attempted": len(rec["requests"]),
        "failed": sum(1 for r in rec["requests"] if r.done is None),
        "metrics": metrics.read(wanted, rec),
        "device": device,
    }
    if args.trace:
        if not tr:
            raise RuntimeError("the trace holds no device operation")
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["compared"] = {n["name"]: {"value": n["value"], "limit": n["limit"]}
                          for n in verdict["numbers"]}
    return {"before": info_lines(rec, peak, tr), "result": result}


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import spec as SP
    cell, devices = set_up(SP.cell(args.workload), args.seed)
    out = run(cell, args, devices)
    for line in out["before"]:
        print(json.dumps(line), flush=True)
    print(json.dumps(out["result"]), flush=True)
    for name, n in out["result"]["compared"].items():
        print(f"compared {name} {n['value']!r} limit {n['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
