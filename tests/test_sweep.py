"""Sweep-harness contracts (PR 7).

* **nproc invariance**: the same grid drained inline (nproc=1) and
  through a 2-worker spawn pool must aggregate to the identical result
  hash — cell results are pure functions of their specs, independent of
  scheduling, worker identity, and warm-cache history.
* **FrontierCache on/off parity per cell**: a cell computed against a
  warm shared cache equals the same cell computed with caching bypassed,
  modulo the volatile (wall-clock / cache-stats) fields — the invariant
  that makes per-worker warm state a pure wall-clock optimization.
* **seed hygiene**: per-cell streams derive from ``SeedSequence`` spawn
  keys; two distinct replicates never share an arrival stream, while the
  same replicate under different policies shares it exactly (paired
  comparison).  The legacy int-seed arithmetic is pinned bit-for-bit.
* **resume**: missing/corrupt/stale shards are recomputed, matching ones
  are trusted, and a resumed run reproduces the fresh run's hash.
* **no JAX in the workers**: the worker module imports no JAX, so a
  worker never contends for a chip.
"""
import importlib
import json
import os
import pickle
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))

from repro.core import adapter as AD                           # noqa: E402
from repro.core import optimizer as OPT                        # noqa: E402
from repro.core import study as ST                             # noqa: E402
from repro.core import trace as TR                             # noqa: E402

import sweep as SW                                             # noqa: E402


def tiny_grid(reps: int = 2, seconds: int = 20):
    budgets = ST.resolve_budgets(2, (0.7,))
    return ST.build_grid(("ipa", "split_ipa"), (1.0,), budgets, reps,
                         (0.02,), seconds=seconds, n_pipelines=2)


def test_worker_module_imports_no_jax(monkeypatch):
    """Spawn workers import repro.core.study; it must not pull in JAX.

    Imported afresh with every jax module blocked (a None entry in
    sys.modules makes its import raise); teardown restores both."""
    for name in [m for m in sys.modules if m.split(".")[0] == "repro"]:
        monkeypatch.delitem(sys.modules, name)
    for name in [m for m in sys.modules if m.split(".")[0] == "jax"]:
        monkeypatch.setitem(sys.modules, name, None)
    importlib.import_module("repro.core.study")


# ---------------------------------------------------------------------------
# determinism across worker counts
# ---------------------------------------------------------------------------
def test_sweep_worker_schedule_invariance_hash():
    """Inline drain in canonical order vs two worker-style chunk drains
    (heavy-first scheduling order, fresh per-worker warm state each —
    the pool's code path minus the process boundary): identical hash and
    identical volatile-stripped records cell-for-cell.  The real spawn
    pool is covered by the slow-marked test below, and nproc=1-vs-4
    hash identity is independently gated every tier-1 run by
    ``benchmarks/sweep.py --smoke``."""
    specs = tiny_grid()
    rec1, _ = SW.run_grid(specs, 1, shard_dir=None, quiet=True)
    todo = sorted(specs,
                  key=lambda s: -(s.seconds * s.budget * s.n_pipelines))
    by_cell = {}
    for half in (todo[0::2], todo[1::2]):    # interleaved "workers"
        ST.worker_init()                     # fresh warm state per worker
        for rec in ST.run_chunk(list(half)):
            by_cell[rec["cell"]] = rec
    rec2 = [by_cell[s.cell_id] for s in specs]
    assert ST.result_hash(rec1) == ST.result_hash(rec2)
    for a, b in zip(rec1, rec2):
        assert ST.strip_volatile(a) == ST.strip_volatile(b)


@pytest.mark.slow
def test_sweep_nproc_invariance_hash():
    """Same grid, nproc=1 inline vs nproc=2 spawn pool: identical hash,
    and identical volatile-stripped records cell-for-cell.  Slow (the
    spawn pool costs ~2.4 s to boot); the fast tier covers the same
    property via the chunk-drain test above and the tier-1 sweep smoke
    gate."""
    specs = tiny_grid()
    rec1, _ = SW.run_grid(specs, 1, shard_dir=None, quiet=True)
    rec2, _ = SW.run_grid(specs, 2, shard_dir=None, quiet=True)
    assert ST.result_hash(rec1) == ST.result_hash(rec2)
    for a, b in zip(rec1, rec2):
        assert ST.strip_volatile(a) == ST.strip_volatile(b)


def test_sweep_rerun_same_process_identical():
    """Two inline drains in one process (second one on fully warm caches)
    are byte-identical — warm state cannot leak into results."""
    specs = tiny_grid(reps=1)
    rec1, _ = SW.run_grid(specs, 1, shard_dir=None, quiet=True)
    rec2, _ = SW.run_grid(specs, 1, shard_dir=None, quiet=True)
    assert ST.result_hash(rec1) == ST.result_hash(rec2)


def test_frontier_cache_on_off_parity_per_cell():
    """One cell against a warm shared FrontierCache vs caching bypassed:
    identical deterministic fields."""
    spec = tiny_grid(reps=1)[0]
    ST.worker_init()
    # warm the cache with a *different* cell first — parity must hold
    # even when the cache already carries other cells' frontiers
    other = tiny_grid(reps=2)[1]
    ST.run_cell_spec(other)
    cached = ST.run_cell_spec(spec)
    assert cached["frontier_cache"]["hits"] + \
        cached["frontier_cache"]["misses"] > 0
    policy, switch_cost = ST.SWEEP_POLICIES[spec.policy]
    uncached = AD.run_cell(
        ST.sweep_cluster(spec.n_pipelines, spec.sla_scale,
                         float(spec.budget)),
        ST.sweep_traces(spec.seconds, spec.n_pipelines,
                        np.random.default_rng(ST.trace_seedseq(spec))),
        policy=policy,
        obj=OPT.Objective(alpha=spec.alpha, beta=spec.beta, delta=1e-6),
        seed=ST.arrival_seedseq(spec), switch_cost=switch_cost,
        adaptation_delay=spec.adaptation_delay, frontier_cache=None,
        event_core=spec.event_core)
    uncached["cell"] = spec.cell_id
    uncached["spec"] = spec.to_dict()
    assert ST.strip_volatile(cached) == ST.strip_volatile(uncached)


def test_result_hash_ignores_wall_and_cache_fields():
    specs = tiny_grid(reps=1)
    rec, _ = SW.run_grid(specs, 1, shard_dir=None, quiet=True)
    h0 = ST.result_hash(rec)
    mutated = [dict(r) for r in rec]
    for r in mutated:
        r["wall_s"] = 999.0
        r["solver_wall_s"] = 123.0
        r["sim_wall_s"] = 876.0
        r["frontier_cache"] = {"hits": 0, "misses": 0}
    assert ST.result_hash(mutated) == h0
    # but a deterministic field must change the hash
    mutated[0]["mean_pas"] += 1.0
    assert ST.result_hash(mutated) != h0


# ---------------------------------------------------------------------------
# seed hygiene
# ---------------------------------------------------------------------------
def test_pipeline_seeds_int_path_is_legacy_arithmetic():
    assert AD._pipeline_seeds(11, 3) == [11, 1000014, 2000017]


def test_pipeline_seeds_seedsequence_idempotent():
    ss = np.random.SeedSequence(entropy=7, spawn_key=(3, 1))
    a = AD._pipeline_seeds(ss, 3)
    b = AD._pipeline_seeds(ss, 3)     # same object, second call
    assert [s.spawn_key for s in a] == [s.spawn_key for s in b]
    assert all(np.random.default_rng(x).random() ==
               np.random.default_rng(y).random()
               for x, y in zip(a, b))


def test_distinct_replicates_never_share_arrival_streams():
    """The satellite contract: two distinct cells (replicates) produce
    disjoint arrival streams on every pipeline — no arithmetic-collision
    class of bug can reintroduce sharing."""
    rates = np.full(30, 20.0)
    streams = {}
    for rep in (0, 1, 2):
        spec = ST.CellSpec(policy="ipa", sla_scale=1.0, budget=20, rep=rep,
                           beta=0.02, seconds=30, n_pipelines=3)
        for p, s in enumerate(AD._pipeline_seeds(ST.arrival_seedseq(spec),
                                                 3)):
            streams[(rep, p)] = TR.arrivals_from_rates(rates, seed=s)
    keys = list(streams)
    for i, a in enumerate(keys):
        for b in keys[i + 1:]:
            ta, tb = streams[a], streams[b]
            assert len(ta) != len(tb) or not np.array_equal(ta, tb), \
                f"streams {a} and {b} are identical"


def test_same_replicate_shares_workload_across_policies():
    """Paired design: cells differing only in policy/budget/SLA judge
    their policies on byte-identical traces and arrival seeds."""
    a = ST.CellSpec(policy="ipa", sla_scale=1.0, budget=20, rep=1,
                    beta=0.02, seconds=30, n_pipelines=2)
    b = ST.CellSpec(policy="split_ipa", sla_scale=1.3, budget=30, rep=1,
                    beta=0.02, seconds=30, n_pipelines=2)
    ta = ST.sweep_traces(30, 2, np.random.default_rng(ST.trace_seedseq(a)))
    tb = ST.sweep_traces(30, 2, np.random.default_rng(ST.trace_seedseq(b)))
    for x, y in zip(ta, tb):
        np.testing.assert_array_equal(x, y)
    assert ST.arrival_seedseq(a).spawn_key == ST.arrival_seedseq(b).spawn_key


def test_run_cluster_trace_accepts_seedsequence():
    cluster = ST.sweep_cluster(2, 1.0, 30.0)
    rates = ST.sweep_traces(20, 2, np.random.default_rng(0))
    ss = np.random.SeedSequence(5)
    r1 = AD.run_cluster_trace(cluster, rates, policy="split_ipa", seed=ss)
    r2 = AD.run_cluster_trace(cluster, rates, policy="split_ipa", seed=ss)
    assert r1.arrived == r2.arrived and r1.completed == r2.completed
    np.testing.assert_array_equal(r1.per_pipeline[0].latencies,
                                  r2.per_pipeline[0].latencies)


# ---------------------------------------------------------------------------
# FrontierCache pickling (warm caches cross the process boundary)
# ---------------------------------------------------------------------------
def test_frontier_cache_pickle_roundtrip():
    cache = OPT.FrontierCache(max_entries=64)
    pipe = ST.sweep_cluster(1).pipelines[0]
    obj = OPT.Objective()
    pts = cache.frontier(pipe, 12.0, obj)
    assert cache.misses == 1
    clone = pickle.loads(pickle.dumps(cache))
    assert clone.stats == cache.stats
    assert len(clone) == len(cache) == 1
    # the warm entry must hit in the clone and return equal frontiers
    pts2 = clone.frontier(pipe, 12.0, obj)
    assert clone.hits == cache.hits + 1
    assert [(p.cost, p.objective, p.config) for p in pts2] == \
        [(p.cost, p.objective, p.config) for p in pts]


def test_frontier_cache_stats_since():
    cache = OPT.FrontierCache()
    pipe = ST.sweep_cluster(1).pipelines[0]
    obj = OPT.Objective()
    cache.frontier(pipe, 10.0, obj)
    snap = cache.stats_snapshot()
    cache.frontier(pipe, 10.0, obj)      # hit
    cache.frontier(pipe, 11.0, obj)      # miss
    d = cache.stats_since(snap)
    assert d["hits"] == 1 and d["misses"] == 1 and d["hit_rate"] == 0.5


# ---------------------------------------------------------------------------
# shards + resume
# ---------------------------------------------------------------------------
def test_resume_recomputes_only_missing_and_stale(tmp_path, monkeypatch):
    specs = tiny_grid(reps=2)            # 4 cells
    shard_dir = str(tmp_path)
    rec1, st1 = SW.run_grid(specs, 1, shard_dir=shard_dir, quiet=True)
    assert st1["computed"] == len(specs) and st1["from_shards"] == 0
    h1 = ST.result_hash(rec1)

    # sabotage: delete one shard, corrupt a second, stale-spec a third
    os.unlink(ST.shard_path(shard_dir, specs[0]))
    with open(ST.shard_path(shard_dir, specs[1]), "w") as f:
        f.write("{not json")
    p2 = ST.shard_path(shard_dir, specs[2])
    with open(p2) as f:
        stale = json.load(f)
    stale["spec"]["seconds"] = 999       # as if the grid had been edited
    with open(p2, "w") as f:
        json.dump(stale, f)

    calls = []
    real = ST.run_cell_spec
    monkeypatch.setattr(ST, "run_cell_spec",
                        lambda s: calls.append(s.cell_id) or real(s))
    rec2, st2 = SW.run_grid(specs, 1, shard_dir=shard_dir, quiet=True)
    assert st2["computed"] == 3 and st2["from_shards"] == 1
    assert sorted(calls) == sorted(s.cell_id for s in specs[:3])
    assert ST.result_hash(rec2) == h1

    # a third run touches nothing
    calls.clear()
    rec3, st3 = SW.run_grid(specs, 1, shard_dir=shard_dir, quiet=True)
    assert st3["computed"] == 0 and not calls
    assert ST.result_hash(rec3) == h1


def test_shard_write_is_atomic_no_tmp_left(tmp_path):
    rec = {"cell": "x__y", "spec": {"a": 1}, "mean_pas": 1.0}
    ST.write_shard(str(tmp_path), rec)
    files = os.listdir(tmp_path)
    assert files == ["x__y.json"]
    with open(tmp_path / "x__y.json") as f:
        assert json.load(f) == rec


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------
def test_aggregate_ci_and_pareto_flags():
    specs = tiny_grid(reps=2)
    rec, _ = SW.run_grid(specs, 1, shard_dir=None, quiet=True)
    agg = ST.aggregate(rec)
    assert len(agg["groups"]) == 2       # 2 policies x 1 sla x 1 C x 1 beta
    for row in agg["groups"]:
        assert row["mean_pas"]["n"] == 2
        assert row["mean_pas"]["ci95"] is not None
    (sl,) = agg["pareto"]
    assert {p["policy"] for p in sl["points"]} == {"ipa", "split_ipa"}
    # at equal budget the joint policy's PAS >= split's, so ipa can never
    # be flagged dominated by split_ipa alone
    ipa_pt = next(p for p in sl["points"] if p["policy"] == "ipa")
    split_pt = next(p for p in sl["points"] if p["policy"] == "split_ipa")
    assert ipa_pt["mean_pas"] >= split_pt["mean_pas"] - 1e-9


def test_ci_student_t_values():
    out = ST._ci([1.0, 2.0, 3.0])
    assert out["mean"] == 2.0 and out["n"] == 3
    # t(0.975, df=2) = 4.3027; sd = 1.0; ci95 = 4.3027 / sqrt(3)
    assert out["ci95"] == pytest.approx(4.3027 / np.sqrt(3), rel=1e-3)
    assert ST._ci([5.0])["ci95"] is None


def test_build_grid_rejects_unknown_policy():
    with pytest.raises(ValueError):
        ST.build_grid(("nope",), (1.0,), (20,), 1, (0.02,), 30, 2)
