"""The driver loop in-process on the CPU, on a tiny two-stage chain: requests
flow through both stages and across planner boundaries, and the check
passes a sound run and fails each fault a cell can have."""
import numpy as np

from bench import check as CK
from bench import generator as GEN
from repro.serving.engine import StageServer

from conftest import LIMIT


def test_requests_flow_through_both_stages_and_boundaries(tiny_cell):
    rec = tiny_cell.run_window(1.2)
    reqs = rec["requests"]
    assert len(reqs) == 48                                  # 40 req/s x 1.2 s
    assert [p["t"] for p in rec["plans"]] == [0.0, 0.5, 1.0]
    assert all(p["feasible"] for p in rec["plans"])
    done = [r for r in reqs if r.done is not None]
    assert len(done) == len(reqs)
    for r in done:
        assert [len(t) for t in r.served] == [8, 4]
        assert len(r.variants) == 2 and r.done >= r.start[1] >= r.enter[1]
    assert {b["stage"] for b in rec["batches"]} == {0, 1}
    assert max(b["size"] for b in rec["batches"]) == 2
    assert rec["compiles_in_window"] == 0
    verdict = CK.check(tiny_cell, rec)
    assert verdict["correct"], verdict
    assert all(n["value"] <= LIMIT for n in verdict["numbers"])


def test_generator_same_work_for_every_seed():
    arr = {"kind": "poisson", "rate_rps": 13.0}
    a = GEN.arrival_times(arr, 30.0, 2 ** 31 + 7)
    assert np.array_equal(a, GEN.arrival_times(arr, 30.0, 2 ** 31 + 7))
    b = GEN.arrival_times(arr, 30.0, 5)
    assert len(a) == len(b) == 390 and not np.array_equal(a, b)
    assert a.min() >= 0 and a.max() < 30.0 and np.all(np.diff(a) >= 0)
    shape = {"kind": "shape", "points": [[0, 10], [10, 10], [10.001, 30],
                                         [20, 30]]}
    c = GEN.arrival_times(shape, 20.0, 1)
    assert len(c) == 400 and abs(np.sum(c < 10) - 100) < 40
    fixed = dict(arr, schedule_seed=185)
    d = GEN.arrival_times(fixed, 30.0, 5)
    assert np.array_equal(d, GEN.arrival_times(fixed, 30.0, 2 ** 31 + 7))
    assert np.array_equal(d, GEN.arrival_times(arr, 30.0, 185))


def test_control_in_the_programs_place_fails(tiny_cell):
    """The reference computed in fp8, put in the program's place, comes out
    not correct by the limits a sound run keeps."""
    rec = tiny_cell.run_window(0.6)
    verdict = CK.check(tiny_cell, rec, controls=("fp8",))
    assert verdict["correct"] is True
    control = verdict["controls"]["fp8"]
    assert control["correct"] is False
    assert [n["limit"] for n in control["numbers"]] == [LIMIT, LIMIT]
    assert max(n["value"] for n in control["numbers"]) > LIMIT


PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _run(cell, monkeypatch):
    """The rest of a benchmark run on the already set-up cell: window, peak
    memory, check, metrics and the result object, the look for a chip
    skipped (the CPU's device kind has no peaks, so the test gives some)."""
    import jax
    from bench import run as R
    monkeypatch.setattr(R, "peaks_for", lambda kind: PEAKS)
    args = R.parse(["--workload", cell.spec.name, "--seed", str(cell.seed),
                    "--seconds", "0.6", "--trace", "0"])
    return R.run(cell, args, jax.devices()[:1])["result"]


def _broken_run(cell, monkeypatch):
    result = _run(cell, monkeypatch)
    assert list(result)[-1] == "compared"
    return result


def test_run_result_of_a_sound_run(tiny_cell, monkeypatch):
    result = _run(tiny_cell, monkeypatch)
    assert result["correct"] is True
    assert result["attempted"] == 24 and result["failed"] == 0
    assert set(result["metrics"]) == {m["name"] for m in
                                      tiny_cell.spec.end_to_end}
    assert list(result)[-1] == "compared"
    assert all(n["value"] <= n["limit"] == LIMIT
               for n in result["compared"].values())


def test_fault_state_unchanged_fails(tiny_cell, monkeypatch):
    """Each decode step returns the cache it was given."""
    for srv in tiny_cell.servers:
        for key, fn in list(srv._decode_cache.items()):
            monkeypatch.setitem(
                srv._decode_cache, key,
                lambda p, c, n, t, f=fn: (f(p, c, n, t)[0], c))
    result = _broken_run(tiny_cell, monkeypatch)
    assert result["correct"] is False
    assert max(n["value"] for n in result["compared"].values()) > LIMIT


def test_fault_half_batch_left_out_fails(tiny_cell, monkeypatch):
    """Only the first half of each batch is served; the other rows get
    copies of its answers."""
    process = StageServer.process

    def half(self, tokens):
        n = len(tokens)
        k = (n + 1) // 2
        gen, wall = process(self, tokens[:k])
        return np.concatenate([gen, gen[:n - k]]), wall

    monkeypatch.setattr(StageServer, "process", half)
    result = _broken_run(tiny_cell, monkeypatch)
    assert result["correct"] is False


def test_fault_token_altered_where_produced_fails(tiny_cell, monkeypatch):
    """The third generated token of every row is forced to token 0."""
    for srv in tiny_cell.servers:
        prompt = next(iter(srv._prefill_cache))[2]
        for key, fn in list(srv._decode_cache.items()):
            def altered(p, c, n, t, f=fn, at=prompt + 1):
                lg, c = f(p, c, n, t)
                return (lg.at[:, 0].set(1e4) if int(n) == at else lg), c
            monkeypatch.setitem(srv._decode_cache, key, altered)
    result = _broken_run(tiny_cell, monkeypatch)
    assert result["correct"] is False
    assert max(n["value"] for n in result["compared"].values()) > LIMIT


def test_calibrate_reads_limits_and_knee_through_run_set_up(
        tiny_cell, monkeypatch, capsys):
    """``bench/calibrate.py`` on the set-up cell: the program's verdict and
    the control's, by the cell's limits, then the share meeting the SLA at
    a fixed rate."""
    import argparse
    import json

    import jax
    from bench import calibrate as CA
    from bench import run as R
    monkeypatch.setattr(R, "set_up",
                        lambda spec, seed: (tiny_cell, jax.devices()[:1]))
    monkeypatch.setattr(tiny_cell, "spec", tiny_cell.spec)
    args = argparse.Namespace(seconds=0.3, seed=tiny_cell.seed,
                              seeds=str(tiny_cell.seed), controls="fp8",
                              rates="20", derive_sla=False)
    CA.limits(tiny_cell.spec, args)
    CA.knee(tiny_cell.spec, args)
    limits, _, knee = [json.loads(line) for line in
                       capsys.readouterr().out.splitlines()]
    assert limits["program"]["correct"] is True
    assert limits["controls"]["fp8"]["correct"] is False
    assert set(limits["limits"].values()) == {LIMIT}
    assert knee["rate_rps"] == 20.0 and knee["requests"] == 6
    assert 0.0 <= knee["met_sla"] <= 1.0
