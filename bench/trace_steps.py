"""The program's own spans and step programs in a profiler trace, on top of
``bench/trace_reduce``; a traced run can call ``load`` and ``reduce`` here
in place of that module's.

``load`` reads what ``trace_reduce.load`` reads and besides:

- the program's spans inside ``StageServer.process`` (``stage.prefill``,
  ``stage.decode``, ``stage.fetch``), in the same host-span list;
- ``modules``: the program executions of the first device (the ``XLA
  Modules`` line), as ``(start_ns, end_ns, name)`` with ``jit_`` and the
  program id taken off the name (``decode_step``).

``reduce`` returns every key of ``trace_reduce.reduce``, computed by it on
the harness's spans alone, so ``busy_s``, ``window_s``, ``process_s`` and
``process_idle_s`` read as they did before the program had spans. Two keys
change where the trace has more to say:

- ``idle_gaps``: each idle nanosecond goes to the innermost host span that
  covers it (the latest-starting), so nested spans do not count it twice;
  every name is listed, not the ten largest, so the list adds up to the
  window's idle time; with no nested spans and at most ten names this is
  ``trace_reduce``'s split;
- ``device_ops``: with module executions, each op is named with the module
  it ran in (``decode_step/fusion.111``).

And it adds, over the ``process`` spans that start in the window (an
execution belongs to the call that holds its midpoint: the device's events
can sit up to a fraction of a millisecond before the host's span that
dispatched them):

- ``steps``: per ``<stage>/<variant>/b<B>``, the ``calls``; per module,
  its ``executions`` and ``device_ms`` (the union of op intervals inside
  each execution, summed); ``glue_device_ms``, the part of that of modules
  other than the two step programs; and ``host_ms`` per program span;
- ``prefill_ms`` and ``decode_step_ms``: device-busy ms per execution of
  ``prefill_step`` and ``decode_step``, the mean over executions;
- ``dispatch_ms``: host ms inside ``stage.prefill`` and ``stage.decode``
  spans per ``process`` call, the mean over calls: the host's dispatch of
  the steps, with any wait for the runtime to take the next program.

Each of the three is left out where the trace has no such execution or
span, as in a trace of a program that predates them.
"""
from __future__ import annotations

import bisect
import heapq
from collections import Counter, defaultdict
from typing import Dict, List, Tuple

from bench import trace_reduce as TR

STAGE_SPANS = ("stage.prefill", "stage.decode", "stage.fetch")
DISPATCH_SPANS = ("stage.prefill", "stage.decode")
PREFILL, DECODE = "prefill_step", "decode_step"
MODULE_LINE = "XLA Modules"
Span = Tuple[str, int, int]


def module_name(name: str) -> str:
    """``jit_decode_step(42)`` -> ``decode_step``."""
    name = name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def load(path: str) -> dict:
    """``trace_reduce.load``'s ``ops`` and ``spans``, the program's spans
    among the latter, and the first device's ``modules``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns,
                         None if any(c in e.name for c in TR.CONTAINERS)
                         else TR.op_name(e.name)) for e in line.events]
                elif line.name == MODULE_LINE:
                    modules[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns,
                         module_name(e.name)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if TR._is_harness_span(e.name) or e.name in STAGE_SPANS:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    first = sorted(ops)[0] if ops else None
    return {"ops": ops, "spans": spans, "modules": modules.get(first, [])}


def innermost(spans: List[Span], lo: int, hi: int) -> List[Span]:
    """``[lo, hi)`` cut into ``(name, start, end)`` pieces, each named by
    the host span that covers it and started last (``driver`` where none
    does)."""
    order = sorted(spans, key=lambda s: s[1])
    edges = sorted({lo, hi} | {t for _, a, b in spans for t in (a, b)
                               if lo < t < hi})
    live: list = []          # (-start, end, name): the latest start on top
    out: List[Span] = []
    k = 0
    for t0, t1 in zip(edges, edges[1:]):
        while k < len(order) and order[k][1] <= t0:
            name, a, b = order[k]
            heapq.heappush(live, (-a, b, name))
            k += 1
        while live and live[0][1] <= t0:
            heapq.heappop(live)
        name = live[0][2] if live else "driver"
        if out and out[-1][0] == name and out[-1][2] == t0:
            out[-1] = (name, out[-1][1], t1)
        else:
            out.append((name, t0, t1))
    return out


def idle_by_span(merged, spans: List[Span], lo: int,
                 hi: int) -> Dict[str, int]:
    """Idle ns inside ``[lo, hi)`` by the innermost host span over them."""
    pieces = innermost(spans, lo, hi)
    out: Dict[str, int] = defaultdict(int)
    j = 0
    for g0, g1 in TR._gaps(merged, lo, hi):
        while j < len(pieces) and pieces[j][2] <= g0:
            j += 1
        i = j
        while i < len(pieces) and pieces[i][1] < g1:
            name, a, b = pieces[i]
            out[name] += min(b, g1) - max(a, g0)
            i += 1
    return out


def _enclosing(process: List[Span]):
    """A function from a time to the index of the ``process`` span that
    holds it, or None."""
    starts = [a for _, a, _ in process]

    def find(t):
        i = bisect.bisect_right(starts, t) - 1
        return i if i >= 0 and t < process[i][2] else None
    return find


def _by_size(d: Dict[str, int], n=None):
    return [[k, v / 1e9] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def reduce(tr: dict) -> dict:
    spans = [s for s in tr["spans"] if TR._is_harness_span(s[0])]
    out = TR.reduce({"ops": tr["ops"], "spans": spans})
    if not out:
        return out
    ops = tr["ops"]
    windows = [(a, b) for n, a, b in tr["spans"] if n == "window"]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(a for ev in ops.values() for a, _, _ in ev)
        hi = max(b for ev in ops.values() for _, b, _ in ev)
    first = sorted(ops)[0]
    merged = TR.union([(max(a, lo), min(b, hi)) for a, b, _ in ops[first]
                       if b > lo and a < hi])
    cov = TR.Covered(merged)
    host = [s for s in tr["spans"] if s[0] != "window"]
    out["idle_gaps"] = _by_size(idle_by_span(merged, host, lo, hi))

    modules = sorted(m for m in tr.get("modules", ())
                     if lo <= (m[0] + m[1]) / 2 < hi)
    if modules:
        mstarts = [a for a, _, _ in modules]
        by_op: Dict[str, int] = defaultdict(int)
        for a, b, name in ops[first]:
            if name is None or b <= lo or a >= hi:
                continue
            i = bisect.bisect_right(mstarts, a) - 1
            where = modules[i][2] if i >= 0 and a < modules[i][1] else "none"
            by_op[f"{where}/{name}"] += min(b, hi) - max(a, lo)
        out["device_ops"] = _by_size(by_op, 10)

    process = sorted((s for s in tr["spans"]
                      if s[0].startswith("process/") and lo <= s[1] < hi),
                     key=lambda s: s[1])
    keys = [n[len("process/"):] for n, _, _ in process]
    find = _enclosing(process)
    steps = {k: {"calls": n, "programs": {}, "glue_device_ms": 0.0,
                 "host_ms": {}} for k, n in Counter(keys).items()}
    execs: Dict[str, List[float]] = defaultdict(list)
    for a, b, name in modules:
        busy_ms = cov.within(a, b) / 1e6
        execs[name].append(busy_ms)
        i = find((a + b) / 2)
        if i is None:
            continue
        st = steps[keys[i]]
        prog = st["programs"].setdefault(name, {"executions": 0,
                                                "device_ms": 0.0})
        prog["executions"] += 1
        prog["device_ms"] += busy_ms
        if name not in (PREFILL, DECODE):
            st["glue_device_ms"] += busy_ms
    dispatch = defaultdict(float)                  # call index -> host ms
    for n, a, b in tr["spans"]:
        i = find(a) if n in STAGE_SPANS else None
        if i is None:
            continue
        host_ms = steps[keys[i]]["host_ms"]
        host_ms[n] = host_ms.get(n, 0.0) + (b - a) / 1e6
        if n in DISPATCH_SPANS:
            dispatch[i] += (b - a) / 1e6
    out["steps"] = steps
    for key, name in (("prefill_ms", PREFILL), ("decode_step_ms", DECODE)):
        if execs.get(name):
            out[key] = sum(execs[name]) / len(execs[name])
    if dispatch:
        out["dispatch_ms"] = sum(dispatch.values()) / len(process)
    return out

