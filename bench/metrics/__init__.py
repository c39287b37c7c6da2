"""One reader per metric, found by the metric's name: ``<name>.py`` here
defines ``read(rec) -> float | None``. ``rec`` is the run's record: the
driver's requests, batches and plans, ``setup_s``, each batch's counted
``flops`` and ``bytes``, the device's ``peaks``, and in a traced run the
reduced ``trace``. A reader that finds nothing to read returns None, and
the metric is left out of the result."""
from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Dict, Iterable

HERE = Path(__file__).resolve().parent


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name}", HERE / f"{name}.py")
    if spec is None:
        raise FileNotFoundError(HERE / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read(metrics: Iterable[dict], rec: dict) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` for each metric whose reader found
    something."""
    out = {}
    for m in metrics:
        value = reader(m["name"])(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def latencies(rec: dict):
    """Each request due in the window with its latency from its due time:
    to its end if it finished, else to when it was given up."""
    out = []
    for r in rec["requests"]:
        end = r.done if r.done is not None else r.failed_at
        out.append((r, end - r.due))
    return out


def process_busy_s(rec: dict) -> float:
    """From the device trace: seconds inside ``process`` spans in which an
    operation ran on the device (0 without a trace)."""
    tr = rec.get("trace") or {}
    return tr.get("process_s", 0.0) - tr.get("process_idle_s", 0.0)
