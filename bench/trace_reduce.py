"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

From the trace it takes the operations that ran on each TPU device (the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane) and the harness's host
spans (``window``, ``plan``, ``wait`` and ``process/<stage>/<variant>/b<B>``,
written as ``jax.profiler.TraceAnnotation``). Inside the ``window`` span:

- ``busy_s``: the union of the intervals in which an operation ran, per
  device, averaged over devices; ``window_s``: the span's length;
- ``process_s`` and ``process_idle_s``: the seconds inside ``process``
  spans, and those of them in which no operation ran (first device);
- ``device_ops``: the ten operations that took most device time, by HLO
  instruction name, loops and other ops that only hold others left out;
- ``idle_gaps``: idle device seconds summed by the host span that covers
  them (``driver`` where none does: the loop's own bookkeeping), the ten
  largest.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

HOST_SPANS = ("window", "plan", "wait")
# ops that only hold others (a scanned layer stack is one ``while``): they
# count as busy time but are left out of the list of ops by time
CONTAINERS = (" while(", " conditional(", " call(")
Interval = Tuple[int, int]


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _is_harness_span(name: str) -> bool:
    return name in HOST_SPANS or name.startswith("process/")


def load(path: str) -> dict:
    """{"ops": {device: [(start_ns, end_ns, name)]}, "spans": [(name,
    start_ns, end_ns)]} from one ``.xplane.pb`` file; a container op's name
    is None."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, list] = {}
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns,
                         None if any(c in e.name for c in CONTAINERS)
                         else op_name(e.name)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if _is_harness_span(e.name):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return {"ops": ops, "spans": spans}


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class Covered:
    """Length of a sorted disjoint interval set inside any query interval."""

    def __init__(self, merged: List[Interval]):
        self.starts = [a for a, _ in merged]
        self.ends = [b for _, b in merged]
        self.merged = merged
        self.prefix = [0]
        for a, b in merged:
            self.prefix.append(self.prefix[-1] + b - a)

    def within(self, lo: int, hi: int) -> int:
        i = bisect.bisect_right(self.ends, lo)      # first ending after lo
        j = bisect.bisect_left(self.starts, hi)     # first starting at hi
        if i >= j:
            return 0
        total = self.prefix[j] - self.prefix[i]
        total -= max(0, lo - self.merged[i][0])
        total -= max(0, self.merged[j - 1][1] - hi)
        return total


def _gaps(merged: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for a, b in merged:
        if b <= lo or a >= hi:
            continue
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def reduce(tr: dict) -> dict:
    spans = tr["spans"]
    windows = [(a, b) for n, a, b in spans if n == "window"]
    ops = tr["ops"]
    if not ops or not any(ops.values()):
        return {}
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(a for ev in ops.values() for a, _, _ in ev)
        hi = max(b for ev in ops.values() for _, b, _ in ev)
    busy = []
    for dev in sorted(ops):
        merged = union([(max(a, lo), min(b, hi)) for a, b, _ in ops[dev]
                        if b > lo and a < hi])
        busy.append(sum(b - a for a, b in merged))
    first = sorted(ops)[0]
    merged = union([(max(a, lo), min(b, hi)) for a, b, _ in ops[first]
                    if b > lo and a < hi])
    cov = Covered(merged)
    process = [(n, a, b) for n, a, b in spans if n.startswith("process/")]
    proc_ns = sum(b - a for _, a, b in process)
    proc_busy = sum(cov.within(a, b) for _, a, b in process)
    by_op: Dict[str, int] = defaultdict(int)
    for a, b, name in ops[first]:
        if name is not None and b > lo and a < hi:
            by_op[name] += min(b, hi) - max(a, lo)
    host = sorted((a, b, n) for n, a, b in spans if n != "window")
    host_starts = [a for a, _, _ in host]
    by_span: Dict[str, int] = defaultdict(int)
    for g0, g1 in _gaps(merged, lo, hi):
        covered = 0
        k = bisect.bisect_right(host_starts, g1)
        for a, b, n in host[max(0, k - 64):k]:
            ov = min(b, g1) - max(a, g0)
            if ov > 0:
                by_span[n] += ov
                covered += ov
        if g1 - g0 > covered:
            by_span["driver"] += g1 - g0 - covered
    top = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"busy_s": sum(busy) / len(busy) / 1e9, "window_s": (hi - lo) / 1e9,
            "process_s": proc_ns / 1e9,
            "process_idle_s": (proc_ns - proc_busy) / 1e9,
            "device_ops": top(by_op), "idle_gaps": top(by_span)}
