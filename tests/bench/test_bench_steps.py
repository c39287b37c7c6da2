"""bench/trace_steps: the program's spans and step programs on synthetic
events, and the reduction of a trace recorded before the program had
either, which must read as ``trace_reduce`` reads it."""
from pathlib import Path

import pytest

from bench import trace_reduce as TR
from bench import trace_steps as TS

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000
PROCESS = "process/s/v/b2"


def synthetic():
    """One ``process`` call with the program's spans nested in it: prefill
    10-14 ms, two decode steps 14-22 and 22-30, the fetch 30-49; device
    work in the prefill, two decode steps and two glue modules."""
    spans = [("window", 0, 100 * MS), ("plan", 0, 1 * MS),
             (PROCESS, 10 * MS, 50 * MS),
             ("stage.prefill", 10 * MS, 14 * MS),
             ("stage.decode", 14 * MS, 22 * MS),
             ("stage.decode", 22 * MS, 30 * MS),
             ("stage.fetch", 30 * MS, 49 * MS),
             ("wait", 50 * MS, 100 * MS)]
    modules = [(12 * MS, 20 * MS, "prefill_step"),
               (23 * MS, 28 * MS, "decode_step"),
               (31 * MS, 36 * MS, "decode_step"),
               (40 * MS, 41 * MS, "_argmax"),
               (45 * MS, 46 * MS, "concatenate")]
    ops = {"/device:TPU:0": [(12 * MS, 20 * MS, "fusion.1"),
                             (23 * MS, 28 * MS, None),        # a loop
                             (23 * MS, 28 * MS, "fusion.2"),
                             (31 * MS, 35 * MS, "fusion.2"),
                             (40 * MS, 41 * MS, "argmax.3"),
                             (45 * MS, 46 * MS, "concatenate.4")]}
    return {"ops": ops, "spans": spans, "modules": modules}


def test_module_name():
    assert TS.module_name("jit_decode_step(42)") == "decode_step"
    assert TS.module_name("jit_prefill_step") == "prefill_step"
    assert TS.module_name("jit__argmax") == "_argmax"


def test_innermost_pieces():
    spans = [("a", 0, 10), ("b", 2, 6), ("c", 4, 5), ("d", 8, 12)]
    assert TS.innermost(spans, 0, 14) == [
        ("a", 0, 2), ("b", 2, 4), ("c", 4, 5), ("b", 5, 6), ("a", 6, 8),
        ("d", 8, 12), ("driver", 12, 14)]


def test_nested_idle_goes_to_innermost_span():
    tr = synthetic()
    out = TS.reduce(tr)
    gaps = dict(out["idle_gaps"])
    assert gaps == {"wait": pytest.approx(0.050),
                    "driver": pytest.approx(0.009),
                    "stage.fetch": pytest.approx(0.013),
                    "stage.decode": pytest.approx(0.005),
                    "stage.prefill": pytest.approx(0.002),
                    PROCESS: pytest.approx(0.001),
                    "plan": pytest.approx(0.001)}
    assert out["busy_s"] + sum(gaps.values()) == pytest.approx(out["window_s"])
    # with the program's spans left out, the split is trace_reduce's
    flat = {"ops": tr["ops"],
            "spans": [s for s in tr["spans"] if s[0] not in TS.STAGE_SPANS]}
    old = TR.reduce(flat)
    for key in ("busy_s", "window_s", "process_s", "process_idle_s"):
        assert out[key] == old[key]
    old_gaps = dict(old["idle_gaps"])
    assert old_gaps[PROCESS] == pytest.approx(
        sum(gaps[n] for n in (PROCESS, *TS.STAGE_SPANS)))
    assert TS.reduce(flat)["idle_gaps"] == old["idle_gaps"]


def test_idle_gaps_list_every_name():
    """Twelve calls of as many batch sizes: every name is listed, so busy
    and idle time add up to the window."""
    spans = [("window", 0, 130 * MS)]
    ops = []
    for i in range(12):
        spans.append((f"process/s/v/b{i + 1}", 10 * i * MS, 10 * (i + 1) * MS))
        ops.append((10 * i * MS, (10 * i + 9) * MS, "fusion.1"))
    out = TS.reduce({"ops": {"/device:TPU:0": ops}, "spans": spans,
                     "modules": []})
    gaps = dict(out["idle_gaps"])
    assert len(gaps) == 13 and gaps["driver"] == pytest.approx(0.010)
    assert out["busy_s"] + sum(gaps.values()) == pytest.approx(0.130)


def test_steps_and_their_three_numbers():
    out = TS.reduce(synthetic())
    st = out["steps"]["s/v/b2"]
    assert st["calls"] == 1
    assert st["programs"] == {
        "prefill_step": {"executions": 1, "device_ms": pytest.approx(8.0)},
        "decode_step": {"executions": 2, "device_ms": pytest.approx(9.0)},
        "_argmax": {"executions": 1, "device_ms": pytest.approx(1.0)},
        "concatenate": {"executions": 1, "device_ms": pytest.approx(1.0)}}
    assert st["glue_device_ms"] == pytest.approx(2.0)
    assert st["host_ms"] == {"stage.prefill": pytest.approx(4.0),
                             "stage.decode": pytest.approx(16.0),
                             "stage.fetch": pytest.approx(19.0)}
    assert out["prefill_ms"] == pytest.approx(8.0)
    assert out["decode_step_ms"] == pytest.approx(4.5)
    assert out["dispatch_ms"] == pytest.approx(20.0)
    assert dict(out["device_ops"]) == {
        "decode_step/fusion.2": pytest.approx(0.009),
        "prefill_step/fusion.1": pytest.approx(0.008),
        "_argmax/argmax.3": pytest.approx(0.001),
        "concatenate/concatenate.4": pytest.approx(0.001)}


def test_three_numbers_left_out_without_spans_or_modules():
    tr = synthetic()
    bare = {"ops": tr["ops"],
            "spans": [s for s in tr["spans"] if s[0] not in TS.STAGE_SPANS]}
    out = TS.reduce(bare)
    assert not {"prefill_ms", "decode_step_ms", "dispatch_ms"} & set(out)
    assert out["steps"] == {"s/v/b2": {"calls": 1, "programs": {},
                                       "glue_device_ms": 0.0, "host_ms": {}}}
    assert TS.reduce({"ops": {}, "spans": tr["spans"], "modules": []}) == {}


@pytest.fixture(scope="module")
def parent_trace():
    """The trace recorded before the program had spans or named steps, as
    each module loads it."""
    path = str(DATA / "sc2-3b.decode-steady.xplane.pb")
    return TS.load(path), TR.load(path)


def test_reduce_parent_chip_trace_as_trace_reduce(parent_trace):
    """Every key reads as ``trace_reduce`` reads it, and the three numbers
    are left out."""
    tr, old = parent_trace
    assert tr["modules"] == []
    assert {"ops": tr["ops"], "spans": tr["spans"]} == old
    out = TS.reduce(tr)
    steps = out.pop("steps")
    assert out == TR.reduce(old)
    assert steps == {"starcoder2-3b/starcoder2-3b-15l/b6": {
        "calls": 1, "programs": {}, "glue_device_ms": 0.0, "host_ms": {}}}


def test_reduce_recorded_chip_trace_with_spans():
    """A 530-ms cut of a traced ``sc2-3b.decode-steady`` window on a TPU
    v5e, from the program with spans and named steps: a wait, one 15-layer
    batch of eight, the next wait. Inside the scanned-layer loops only the
    first two loops' ops are kept (the loops stay whole), so these numbers
    are the uncut range's. The prefill starts 51 us before the call's span
    on the trace's clock and still belongs to it."""
    path = str(DATA / "sc2-3b.decode-steady.steps.xplane.pb")
    tr = TS.load(path)
    assert {"ops": tr["ops"], "spans": tr["spans"]} != TR.load(path)
    out = TS.reduce(tr)
    old = TR.reduce(TR.load(path))
    for key in ("busy_s", "window_s", "process_s", "process_idle_s"):
        assert out[key] == old[key]
    assert out["window_s"] == pytest.approx(0.53)
    assert out["busy_s"] == pytest.approx(0.398559063)
    assert out["process_idle_s"] == pytest.approx(0.00434696)
    gaps = dict(out["idle_gaps"])
    assert gaps == {"wait": pytest.approx(0.126925454),
                    "stage.fetch": pytest.approx(0.002815361),
                    "stage.decode": pytest.approx(0.001213812),
                    "process/starcoder2-3b/starcoder2-3b-15l/b8":
                        pytest.approx(0.000317787),
                    "driver": pytest.approx(0.000168523)}
    assert out["busy_s"] + sum(gaps.values()) == pytest.approx(0.53, abs=1e-9)
    assert dict(old["idle_gaps"])[
        "process/starcoder2-3b/starcoder2-3b-15l/b8"] == pytest.approx(
            0.00434696)
    (key, st), = out["steps"].items()
    assert key == "starcoder2-3b/starcoder2-3b-15l/b8" and st["calls"] == 1
    progs = st["programs"]
    assert progs["prefill_step"] == {"executions": 1,
                                     "device_ms": pytest.approx(35.91301)}
    assert progs["decode_step"] == {"executions": 64,
                                    "device_ms": pytest.approx(362.38366)}
    assert {n: p["executions"] for n, p in progs.items()} == {
        "prefill_step": 1, "decode_step": 64, "_argmax": 65,
        "broadcast_in_dim": 65, "convert_element_type": 64, "concatenate": 5}
    assert st["glue_device_ms"] == pytest.approx(0.262374)
    assert st["host_ms"] == {"stage.prefill": pytest.approx(2.09555),
                             "stage.decode": pytest.approx(355.105608),
                             "stage.fetch": pytest.approx(44.991076)}
    assert out["prefill_ms"] == pytest.approx(35.91301)
    assert out["decode_step_ms"] == pytest.approx(5.6622446875)
    assert out["dispatch_ms"] == pytest.approx(357.201158)
    ops = dict(out["device_ops"])
    assert ops["decode_step/fusion.75"] == pytest.approx(0.026194228)
    assert all(n.split("/")[0] in progs for n in ops)
