"""bench/trace_reduce: interval arithmetic on synthetic events, and the
whole reduction on a short trace recorded on a TPU v5e in a traced run of
``sc2-3b.decode-steady``, trimmed to its harness spans and XLA ops
(``tests/bench/data``)."""
from pathlib import Path

import pytest

from bench import trace_reduce as TR

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def test_union_and_covered():
    merged = TR.union([(5, 9), (0, 2), (1, 3), (9, 10), (20, 30)])
    assert merged == [(0, 3), (5, 10), (20, 30)]
    cov = TR.Covered(merged)
    assert cov.within(0, 100) == 18
    assert cov.within(2, 6) == 2
    assert cov.within(3, 5) == 0
    assert cov.within(25, 26) == 1
    assert cov.within(-5, 1) == 1


def test_reduce_synthetic():
    spans = [("window", 0, 100 * MS), ("plan", 0, 1 * MS),
             ("process/s/v/b2", 10 * MS, 50 * MS), ("wait", 50 * MS, 100 * MS)]
    ops = {"/device:TPU:0": [(12 * MS, 20 * MS, "fusion.1"),
                             (20 * MS, 30 * MS, "fusion.2"),
                             (40 * MS, 45 * MS, "fusion.1")]}
    out = TR.reduce({"ops": ops, "spans": spans})
    assert out["busy_s"] == pytest.approx(0.023)
    assert out["window_s"] == pytest.approx(0.1)
    assert out["process_s"] == pytest.approx(0.04)
    assert out["process_idle_s"] == pytest.approx(0.017)
    assert out["device_ops"] == [["fusion.1", pytest.approx(0.013)],
                                 ["fusion.2", pytest.approx(0.010)]]
    gaps = dict((k, v) for k, v in out["idle_gaps"])
    assert gaps["wait"] == pytest.approx(0.050)      # 50..100 ms
    assert gaps["process/s/v/b2"] == pytest.approx(0.017)
    assert gaps["plan"] == pytest.approx(0.001)
    assert gaps["driver"] == pytest.approx(0.009)    # 1..10 ms: no span
    assert TR.reduce({"ops": {}, "spans": spans}) == {}


def test_reduce_recorded_chip_trace():
    """The first 620 ms of a traced window on the chip: waits with the
    queue empty, then the start of a 15-layer batch of six. The numbers
    are this file's; busy and idle time add up to the window."""
    tr = TR.load(str(DATA / "sc2-3b.decode-steady.xplane.pb"))
    assert list(tr["ops"]) == ["/device:TPU:0"]
    assert sum(1 for *_, name in tr["ops"]["/device:TPU:0"]
               if name is None) == 26                  # scanned-layer loops
    out = TR.reduce(tr)
    assert out["window_s"] == pytest.approx(0.62)
    assert out["busy_s"] == pytest.approx(0.155466089)
    assert out["process_s"] == pytest.approx(0.155968009)
    assert out["process_idle_s"] == pytest.approx(0.00050192)
    ops = dict(out["device_ops"])
    assert len(ops) == 10 and all(" " not in n for n in ops)
    assert ops["bitcast_add_fusion.3"] == pytest.approx(0.036732633)
    gaps = dict(out["idle_gaps"])
    assert set(gaps) == {"wait", "driver",
                         "process/starcoder2-3b/starcoder2-3b-15l/b6"}
    assert gaps["wait"] == pytest.approx(0.463548511)
    assert out["busy_s"] + sum(gaps.values()) == pytest.approx(0.62)
