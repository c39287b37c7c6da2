#!/usr/bin/env python
"""Parallel Pareto sweep: the study runner over
(policy x SLA x core budget C x trace replicate x objective weights).

IPA's claim is a trade-off *surface* — accuracy vs cost vs
reconfigurations under varying SLAs and budgets — so this bench replaces
spot checks with a grid of full policy-trace runs and emits one tidy
``BENCH_sweep.json`` of Pareto surfaces with seed-level 95% confidence
intervals.  The worker side lives in ``repro.core.study``; this script is
the scheduler:

* **fan-out**: cells run on a ``ProcessPoolExecutor`` (spawn context,
  ``study.worker_init`` as the pool initializer so every worker keeps a
  long-lived warm ``FrontierCache`` + trace memo across the cells it
  drains).  Cells are sorted heavy-first (budget x trace length) and
  submitted in small chunks, so free workers steal queued chunks and a
  heavy cell can never straggle the tail of the pool.
* **no chip in the workers**: they run only the numpy planner and
  simulator.  ``repro.core.study`` imports no JAX, so no worker needs the
  chip or can contend for it with a parent that holds it.  Keep it so
  (``tests/test_sweep.py`` checks the import).
* **determinism**: every cell derives its streams from
  ``np.random.SeedSequence`` spawn keys rooted at the grid seed, so the
  aggregate is byte-identical for any worker count; ``--smoke`` proves it
  by running the same tiny grid at nproc=1 and nproc=4 and comparing
  ``study.result_hash`` (wall-clock fields stripped).
* **resume**: each finished cell is an atomic shard in ``--shards``;
  rerunning skips shards whose embedded spec still matches (crash-safe
  incremental progress; ``--fresh`` wipes them).
* **evidence**: the JSON carries per-cell ``solver_wall_s`` /
  ``sim_wall_s`` and per-cell ``FrontierCache`` hit/miss deltas plus a
  straggler rollup, so slow cells and cache-cold policies are diagnosable
  from the artifact alone.

Gates (``--smoke``, wired into ``scripts/tier1.sh``): (a) the nproc=1
and nproc=4 result hashes must be identical; (b) parallel wall at 4
workers must be >= 2x serial on the smoke grid — enforced only on hosts
with >= 4 CPUs (skipped, and recorded as skipped, below that: a
single-core container cannot physically speed up CPU-bound work by
fanning it out).
"""
from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ProcessPoolExecutor, as_completed

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import study as ST                        # noqa: E402

SPEEDUP_FLOOR = 2.0
SPEEDUP_MIN_CPUS = 4

FULL_GRID = dict(policies=("ipa", "ipa_hyst", "split_ipa", "split_fa2_high"),
                 sla_scales=(0.85, 1.0, 1.3), budget_fracs=(0.6, 0.85),
                 reps=3, betas=(0.02,), seconds=240, n_pipelines=3)
SMOKE_GRID = dict(policies=("ipa", "split_ipa"), sla_scales=(1.0, 1.3),
                  budget_fracs=(0.7,), reps=2, betas=(0.02,), seconds=30,
                  n_pipelines=2)


def build_specs(g: dict, root_seed: int) -> tuple:
    budgets = ST.resolve_budgets(g["n_pipelines"], g["budget_fracs"])
    specs = ST.build_grid(g["policies"], g["sla_scales"], budgets,
                          g["reps"], g["betas"], g["seconds"],
                          g["n_pipelines"], root_seed=root_seed)
    return specs, budgets


def run_grid(specs, nproc: int, shard_dir=None, resume: bool = True,
             chunk=None, quiet: bool = False):
    """Drain the grid and return (records in canonical grid order, stats).

    nproc<=1 runs inline in this process (same code path as a worker,
    modulo the process boundary); nproc>1 fans chunks out over a spawn
    pool.  With ``shard_dir`` set, finished cells are persisted as atomic
    shards and — with ``resume`` — matching shards are loaded instead of
    recomputed.
    """
    t0 = time.perf_counter()
    done = {}
    if shard_dir and resume:
        for s in specs:
            rec = ST.load_shard(shard_dir, s)
            if rec is not None:
                done[s.cell_id] = rec
    todo = [s for s in specs if s.cell_id not in done]
    # heavy-first scheduling: the most expensive cells (big C, long
    # traces, wide clusters) enter the pool first, so the inevitable
    # stragglers overlap with the bulk instead of trailing it
    todo.sort(key=lambda s: -(s.seconds * s.budget * s.n_pipelines))
    n_done = 0
    if todo and nproc <= 1:
        ST.worker_init()
        for s in todo:
            rec = ST.run_cell_spec(s)
            if shard_dir:
                ST.write_shard(shard_dir, rec)
            done[s.cell_id] = rec
            n_done += 1
            if not quiet and n_done % 20 == 0:
                print(f"  serial: {n_done}/{len(todo)} cells")
    elif todo:
        # small chunks amortize task dispatch while keeping the queue
        # deep enough for work stealing (a free worker always finds a
        # pending chunk until the very tail)
        if chunk is None:
            chunk = max(1, len(todo) // (nproc * 4))
        chunks = [todo[i:i + chunk] for i in range(0, len(todo), chunk)]
        ctx = mp.get_context("spawn")
        with ProcessPoolExecutor(max_workers=nproc, mp_context=ctx,
                                 initializer=ST.worker_init) as ex:
            futs = [ex.submit(ST.run_chunk, c) for c in chunks]
            for fut in as_completed(futs):
                for rec in fut.result():
                    if shard_dir:
                        ST.write_shard(shard_dir, rec)
                    done[rec["cell"]] = rec
                    n_done += 1
                if not quiet:
                    print(f"  pool({nproc}): {n_done}/{len(todo)} cells")
    records = [done[s.cell_id] for s in specs]
    stats = {"wall_s": round(time.perf_counter() - t0, 3),
             "computed": len(todo), "from_shards": len(specs) - len(todo)}
    return records, stats


def measure_parallel(specs, nproc: int, shard_dir, resume: bool,
                     quiet: bool = False):
    """Serial pass (throwaway shards) then parallel pass (real shards);
    returns (parallel records, parallel-evidence dict, failures)."""
    fails = []
    print(f"serial pass (nproc=1, {len(specs)} cells)...")
    with tempfile.TemporaryDirectory() as td:
        rec_s, st_s = run_grid(specs, 1, td, resume=False, quiet=quiet)
    print(f"  serial wall {st_s['wall_s']}s")
    print(f"parallel pass (nproc={nproc})...")
    rec_p, st_p = run_grid(specs, nproc, shard_dir, resume=resume,
                           quiet=quiet)
    print(f"  parallel wall {st_p['wall_s']}s "
          f"({st_p['from_shards']} from shards)")
    h_s, h_p = ST.result_hash(rec_s), ST.result_hash(rec_p)
    if h_s != h_p:
        fails.append(f"nproc-invariance broken: serial hash {h_s[:16]} != "
                     f"nproc={nproc} hash {h_p[:16]}")
    speedup = round(st_s["wall_s"] / max(st_p["wall_s"], 1e-9), 3)
    cpus = os.cpu_count() or 1
    gate = "enforced" if cpus >= SPEEDUP_MIN_CPUS else \
        f"skipped (<{SPEEDUP_MIN_CPUS} CPUs: host has {cpus})"
    # a fair speedup needs the parallel pass to have computed every cell
    # (a shard-resumed pass measures disk reads, not the pool)
    if st_p["from_shards"] > 0:
        gate = "skipped (parallel pass resumed from shards)"
    if gate == "enforced" and speedup < SPEEDUP_FLOOR:
        fails.append(f"parallel speedup {speedup} < {SPEEDUP_FLOOR}x at "
                     f"{nproc} workers on {cpus} CPUs")
    evidence = {"serial_wall_s": st_s["wall_s"],
                "parallel_wall_s": st_p["wall_s"],
                "workers": nproc, "speedup": speedup,
                "cpu_count": cpus, "speedup_gate": gate,
                "nproc_invariant": h_s == h_p, "result_hash": h_p}
    return rec_p, evidence, fails


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid + the two tier-1 gates; writes no "
                         "JSON unless --out is given")
    ap.add_argument("--nproc", type=int, default=4,
                    help="parallel worker count (default 4)")
    ap.add_argument("--out", default=None,
                    help="output JSON (default <repo>/BENCH_sweep.json; "
                         "smoke: none)")
    ap.add_argument("--shards", default=None,
                    help="shard directory for incremental resume "
                         "(default <repo>/.sweep_shards; smoke: a temp dir)")
    ap.add_argument("--fresh", action="store_true",
                    help="ignore and wipe existing shards")
    ap.add_argument("--no-measure-parallel", action="store_true",
                    help="skip the serial reference pass (resume-friendly; "
                         "the JSON then carries no parallel evidence)")
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--pipelines", type=int, default=None)
    ap.add_argument("--root-seed", type=int, default=0)
    ap.add_argument("--policies", default=None,
                    help="comma-separated subset of "
                         f"{sorted(ST.SWEEP_POLICIES)}")
    ap.add_argument("--sla-scales", default=None, help="comma-separated")
    ap.add_argument("--budget-fracs", default=None, help="comma-separated")
    ap.add_argument("--betas", default=None, help="comma-separated")
    args = ap.parse_args()

    g = dict(SMOKE_GRID if args.smoke else FULL_GRID)
    if args.seconds:
        g["seconds"] = args.seconds
    if args.reps:
        g["reps"] = args.reps
    if args.pipelines:
        g["n_pipelines"] = args.pipelines
    if args.policies:
        g["policies"] = tuple(args.policies.split(","))
    if args.sla_scales:
        g["sla_scales"] = tuple(float(x) for x in args.sla_scales.split(","))
    if args.budget_fracs:
        g["budget_fracs"] = tuple(float(x)
                                  for x in args.budget_fracs.split(","))
    if args.betas:
        g["betas"] = tuple(float(x) for x in args.betas.split(","))

    repo = os.path.join(os.path.dirname(__file__), "..")
    specs, budgets = build_specs(g, args.root_seed)
    print(f"grid: {len(g['policies'])} policies x "
          f"{len(g['sla_scales'])} SLA scales x {len(budgets)} budgets "
          f"{budgets} x {g['reps']} reps x {len(g['betas'])} betas "
          f"= {len(specs)} cells ({g['seconds']}s traces, "
          f"{g['n_pipelines']} pipelines)")

    tmp_ctx = None
    if args.smoke and args.shards is None:
        tmp_ctx = tempfile.TemporaryDirectory()
        shard_dir = tmp_ctx.name
    else:
        shard_dir = args.shards or os.path.join(repo, ".sweep_shards")
    if args.fresh and os.path.isdir(shard_dir):
        shutil.rmtree(shard_dir)

    try:
        if args.no_measure_parallel:
            records, st = run_grid(specs, args.nproc, shard_dir, resume=True)
            print(f"  wall {st['wall_s']}s ({st['from_shards']} from shards)")
            evidence, fails = None, []
        else:
            records, evidence, fails = measure_parallel(
                specs, args.nproc, shard_dir, resume=not args.fresh)
    finally:
        if tmp_ctx is not None:
            tmp_ctx.cleanup()

    agg = ST.aggregate(records)
    rhash = ST.result_hash(records)
    if evidence is not None:
        print(f"nproc-invariance: {'OK' if evidence['nproc_invariant'] else 'BROKEN'}"
              f" (hash {rhash[:16]}); speedup {evidence['speedup']}x "
              f"[{evidence['speedup_gate']}]")

    # surface sanity on any grid: every (sla, beta, budget) slice must
    # keep joint ipa's mean PAS >= split_ipa's (the feasible-set-superset
    # argument survives aggregation over paired replicates, which see
    # identical arrivals under both policies)
    by_key = {(r["policy"], r["sla_scale"], r["budget"], r["beta"]): r
              for r in agg["groups"]}
    for (pol, sla, c, beta), row in by_key.items():
        if pol != "ipa":
            continue
        split = by_key.get(("split_ipa", sla, c, beta))
        if split and row["mean_pas"]["mean"] < split["mean_pas"]["mean"] - 1e-9:
            fails.append(f"ipa mean PAS {row['mean_pas']['mean']} < "
                         f"split_ipa {split['mean_pas']['mean']} at "
                         f"sla={sla} C={c} beta={beta}")

    if fails:
        for f in fails:
            print(f"FAIL: {f}")
        return 1
    print(f"PASS: {len(specs)} cells, {len(agg['groups'])} surface groups, "
          f"{len(agg['pareto'])} Pareto slices")

    result = {
        "bench": "sweep_pareto",
        "grid": {**{k: list(v) if isinstance(v, tuple) else v
                    for k, v in g.items()},
                 "budgets": budgets, "root_seed": args.root_seed,
                 "adaptation_delay_s": ST.ADAPT_DELAY_S,
                 "hysteresis_switch_cost": ST.HYSTERESIS_SWITCH_COST,
                 "n_cells": len(specs)},
        "result_hash": rhash,
        "parallel": evidence,
        "timing": ST.timing_rollup(records),
        "aggregate": agg,
        "cells": records,
    }
    out = args.out
    if out is None and not args.smoke:
        out = os.path.join(repo, "BENCH_sweep.json")
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=2)
        print(f"wrote {os.path.abspath(out)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
