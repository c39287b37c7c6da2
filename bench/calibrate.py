"""Readings that fix a cell's rate and its correctness limits, on the chip,
each in one process through ``bench/run.py``'s own set-up. Not part of a
benchmark run.

  python3 bench/calibrate.py knee --workload <cell> --seed <n> --seconds <s> \
      --rates 8,12,16 [--derive-sla]
  python3 bench/calibrate.py limits --workload <cell> --seconds <s> \
      --seeds 1,2,3 [--controls fp8,int8]

``knee`` serves the cell's traffic at each fixed Poisson rate in turn and
prints, per rate, the share of requests that met the pipeline SLA. With
``--derive-sla`` each stage's SLA is the paper's rule
(``profiler.derive_stage_sla``: 5 x the mean batch-1 latency of its
variants, profiled in this process) in place of the traffic file's. The
knee is the highest rate at which at least 90% of requests meet the SLA.

``limits`` serves a window at the cell's own load for each seed (weights
and traffic made anew from the seed; compiled programs and profiles those
of the first) and prints the program's verdict and each control's (the
reference in the program's place in a lower precision, by default the
configuration's ``check.control``), all held to the cell's limits.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def knee(spec, args) -> None:
    from bench import metrics
    from bench import run as R
    if args.derive_sla:
        spec = dataclasses.replace(spec, stages=tuple(
            dataclasses.replace(st, sla_s=None) for st in spec.stages))
    cell, devices = R.set_up(spec, args.seed)
    print(json.dumps({"device": devices[0].device_kind, "stage_sla_s": [
        s.sla for s in cell.pipe.stages], "sla_s": cell.pipe.sla}), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.spec = dataclasses.replace(spec, traffic=dict(
            spec.traffic, arrivals={"kind": "poisson", "rate_rps": rate}))
        rec = cell.run_window(args.seconds)
        ok = sum(1 for r in rec["requests"] if r.done is not None
                 and r.done - r.due <= rec["sla_s"])
        n = len(rec["requests"])
        print(json.dumps({
            "rate_rps": rate, "requests": n, "met_sla": ok / n,
            "p95_latency_s": metrics.reader("p95_latency_s")(rec),
            "failed": sum(1 for r in rec["requests"] if r.done is None),
            "plans": [[p["t"], p["rate_rps"], p["feasible"], p["config"]]
                      for p in rec["plans"]]}), flush=True)


def limits(spec, args) -> None:
    from bench import check as CK
    from bench import run as R
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = tuple(c for c in (args.controls
                                 or spec.config["check"]["control"]).split(",")
                     if c)
    cell, _ = R.set_up(spec, seeds[0])
    for seed in seeds:
        if seed != cell.seed:
            cell.reseed(seed)
        rec = cell.run_window(args.seconds)
        t = time.perf_counter()
        v = CK.check(cell, rec, controls)
        done = [r for r in rec["requests"] if r.done is not None]
        verdict = lambda x: {"correct": x["correct"], **{
            n["name"]: n["value"] for n in x["numbers"]}}
        print(json.dumps({
            "seed": seed, "program": verdict(v),
            "controls": {q: verdict(c) for q, c in v["controls"].items()},
            "limits": {n["name"]: n["limit"] for n in v["numbers"]},
            "sampled": v["sampled"], "tokens": v["tokens"],
            "finished": len(done), "requests": len(rec["requests"]),
            "variants": sorted({tuple(r.variants) for r in done}),
            "check_s": time.perf_counter() - t}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("knee", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, help="knee: the seed")
    ap.add_argument("--rates", help="knee: fixed rates, req/s")
    ap.add_argument("--derive-sla", action="store_true")
    ap.add_argument("--seeds", help="limits: the seeds")
    ap.add_argument("--controls", help="limits: control precisions")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import spec as SP
    spec = SP.cell(args.workload)
    (knee if args.what == "knee" else limits)(spec, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
