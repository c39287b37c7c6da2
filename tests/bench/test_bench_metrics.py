"""The metric readers' arithmetic on synthetic run records."""
import numpy as np
import pytest

from bench import metrics
from bench.driver import Req


def _req(due, done=None, failed_at=None, variants=("a",), enter=None,
         start=None):
    r = Req(due, np.zeros(4, np.int32))
    r.done, r.failed_at, r.variants = done, failed_at, list(variants)
    r.enter = list(enter if enter is not None else [due])
    r.start = list(start if start is not None else [due])
    return r


def _rec(**kw):
    rec = {"window_s": 10.0, "sla_s": 1.0, "requests": [], "batches": [],
           "plans": [], "compiles_in_window": 0, "setup_s": 12.5,
           "accuracy": {0: {"a": 50.0, "b": 20.0}, 1: {"c": 80.0}},
           "peaks": {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0},
           "trace": {}}
    rec.update(kw)
    return rec


def read(name, rec):
    return metrics.reader(name)(rec)


def test_goodput_counts_only_finished_within_sla():
    reqs = [_req(0.0, done=0.5),                  # good
            _req(1.0, done=1.99),                 # good (0.99 s)
            _req(2.0, done=3.5),                  # finished late: misses
            _req(3.0, failed_at=5.5),             # dropped
            _req(4.0, failed_at=11.0)]            # unfinished at the stop
    assert read("goodput_rps", _rec(requests=reqs)) == pytest.approx(0.2)


def test_p95_is_over_every_request_failed_ones_at_their_age():
    # 19 fast requests and one that failed after 7 s: the 95th percentile
    # of 20 (nearest rank: the 19th) is still a fast one; with a second
    # failure it is a failed one's age
    reqs = [_req(float(i), done=i + 0.1 * (i + 1)) for i in range(19)]
    reqs.append(_req(0.0, failed_at=7.0))
    assert read("p95_latency_s", _rec(requests=reqs)) == pytest.approx(1.9)
    reqs.append(_req(1.0, failed_at=4.0))
    assert read("p95_latency_s", _rec(requests=reqs)) == pytest.approx(3.0)
    assert read("p95_latency_s", _rec(requests=[])) is None


def test_mean_pas_over_finished_requests_only():
    reqs = [_req(0, done=1, variants=("a", "c")),     # 0.5 * 0.8 -> 40
            _req(0, done=1, variants=("b", "c")),     # 0.2 * 0.8 -> 16
            _req(0, failed_at=3, variants=("a",))]
    assert read("mean_pas", _rec(requests=reqs)) == pytest.approx(28.0)


def test_mfu_and_roofline_are_normalised_by_busy_time():
    batches = [{"start": 0.0, "end": 2.0, "size": 4, "planned": 8,
                "flops": 100.0, "roofline_s": 1.0},
               {"start": 5.0, "end": 7.0, "size": 8, "planned": 8,
                "flops": 60.0, "roofline_s": 0.6}]
    # 4.5 s inside process spans on the host, 0.5 s of it with the device
    # idle: 160 FLOPs over 4 device-busy seconds at 100 FLOP/s is 40%,
    # whatever the window or the host's wall
    tr = {"process_s": 4.5, "process_idle_s": 0.5}
    rec = _rec(batches=batches, trace=tr)
    assert read("model.mfu", rec) == pytest.approx(40.0)
    assert read("model.mfu", dict(rec, window_s=100.0)) == pytest.approx(40.0)
    assert read("model.step_roofline", rec) == pytest.approx(40.0)
    assert read("model.step_roofline", _rec(batches=batches)) is None
    assert read("queue.batch_fill", rec) == pytest.approx(75.0)
    assert read("stage.busy_share", rec) == pytest.approx(40.0)
    # a batch running past the window's close counts only inside it
    late = dict(batches[1], start=9.0, end=12.0)
    assert read("stage.busy_share", _rec(batches=[late])) == pytest.approx(10.0)
    assert read("model.mfu", _rec()) is None


def test_queue_wait_sums_stages_planner_and_counters():
    reqs = [_req(0.0, done=3.0, enter=[0.0, 1.5], start=[0.5, 2.0]),
            _req(1.0, done=4.0, enter=[1.0, 2.5], start=[1.0, 3.5])]
    rec = _rec(requests=reqs, plans=[{"solve_s": 0.002}, {"solve_s": 0.004}],
               compiles_in_window=2)
    assert read("queue.wait_s", rec) == pytest.approx(1.0)
    assert read("planner.solve_ms", rec) == pytest.approx(3.0)
    assert read("stage.compiles_in_window", rec) == 2
    assert read("setup_s", rec) == 12.5


def test_idle_share_needs_a_trace():
    assert read("device.idle_share", _rec()) is None
    tr = {"process_s": 4.0, "process_idle_s": 1.0}
    assert read("device.idle_share", _rec(trace=tr)) == pytest.approx(25.0)


def test_read_leaves_out_metrics_that_found_nothing():
    out = metrics.read([{"name": "setup_s", "unit": "s"},
                        {"name": "device.idle_share", "unit": "%"}], _rec())
    assert out == {"setup_s": {"value": 12.5, "unit": "s"}}
