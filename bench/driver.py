"""The path a cell's window drives: the program's stage servers, queues and
planner, glued by the smallest loop that serves open-loop traffic.

One ``StageServer`` per stage holds every variant of its model, each with
the benchmark's weights. Set-up profiles each stage on this device
(``profiler.profile_stage_server``), builds the planner's stage models
(``profiler.build_stage``, with ``th`` 0 so that every variant's Eq.-1
allocation is one replica) and warms up every batch size a queue can pop.

The window is one thread. Requests become due on the traffic's schedule and
join the first stage's ``CentralQueue``; a ready batch (full, or its oldest
request older than the Eq.-7 wait) goes to the stage's ``process``, which
blocks until its tokens are on the host; those tokens are the next stage's
prompts. Downstream queues are served first. At each planner boundary
``optimizer.solve`` (one replica per stage) plans for the rate seen over
the last interval; its variants go to ``set_variant`` and its batch sizes
to ``reconfigure``; an infeasible plan holds the one in force. Requests
older than ``DROP_FACTOR`` x SLA are dropped (``drain_expired``). After the
window closes no request arrives, and the loop drains for at most
``DROP_FACTOR`` x SLA; what is left then has failed.

All times are seconds from the start of the window, due times included, so
a request's latency runs from when it was due.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Callable, List, Optional

import numpy as np

from bench import generator as TR
from bench.spec import Cell as CellSpec

OBJECTIVE = dict(alpha=10.0, beta=0.5)
# a request older than twice the pipeline SLA is dropped (the paper, §4.5)
DROP_FACTOR = 2.0


class CompileCounter:
    """Backend compiles seen by this process (``jax.monitoring``)."""

    def __init__(self):
        import jax
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


@dataclasses.dataclass
class Req:
    due: float
    prompt: np.ndarray
    enter: List[float] = dataclasses.field(default_factory=list)
    start: List[float] = dataclasses.field(default_factory=list)
    variants: List[str] = dataclasses.field(default_factory=list)
    served: List[np.ndarray] = dataclasses.field(default_factory=list)
    done: Optional[float] = None          # finished the last stage
    failed_at: Optional[float] = None     # dropped, or left at the stop


def _stderr(msg: str) -> None:
    import sys
    print(msg, file=sys.stderr, flush=True)


class Cell:
    """One cell's system under test, built from its spec and seed."""

    def __init__(self, spec: CellSpec, seed: int,
                 log: Callable[[str], None] = _stderr):
        self.spec = spec
        self.seed = seed
        self.log = log
        self.servers = []
        self.weights = {}
        self.pipe = None
        self.compiles = CompileCounter()

    # -- set-up ---------------------------------------------------------------
    def _weights(self, seed: int):
        """{stage: {variant: params}}, each variant made on the device in
        one jitted call from ``seed``."""
        import jax
        key = jax.random.PRNGKey(TR.jax_seed(seed))
        out = {}
        for i, st in enumerate(self.spec.stages):
            init = st.module("reference").init_params
            out[st.name] = {}
            for j, (vname, layers, _) in enumerate(st.variants):
                make = jax.jit(functools.partial(init, sz=st.sizes,
                                                 n_layers=layers))
                out[st.name][vname] = make(jax.random.fold_in(key, 16 * i + j))
        return out

    def setup(self) -> None:
        from repro.core import profiler as PF
        from repro.core.pipeline import PipelineModel
        from repro.serving.engine import StageServer

        tr = self.spec.traffic
        batches = tuple(tr["batch_choices"])
        weights = self.weights = self._weights(self.seed)
        stages = []
        for st in self.spec.stages:
            program = st.module("program")
            family = [(v, program.model_config(st.name, st.sizes, layers), acc)
                      for v, layers, acc in st.variants]
            srv = StageServer(st.name, family, gen_tokens=st.output_tokens,
                              max_ctx=st.prompt_tokens + st.output_tokens,
                              params_by_variant=weights[st.name])
            profs = PF.profile_stage_server(srv, batches=batches,
                                            prompt_len=st.prompt_tokens)
            sla = st.sla_s if st.sla_s is not None else PF.derive_stage_sla(profs)
            stages.append(PF.build_stage(st.name, profs, th=0.0,
                                         batch_choices=batches, sla=sla,
                                         max_batch=max(batches)))
            for vname in srv.variants:           # every size pop_batch returns
                srv.set_variant(vname)
                for b in range(1, max(batches) + 1):
                    if b not in batches:
                        srv.process(np.zeros((b, st.prompt_tokens), np.int32))
            self.servers.append(srv)
            self.log(f"profile {st.name}: " + ", ".join(
                f"{p.name} " + " ".join(f"b{b}={lat:.4f}s" for b, lat in
                                        zip(p.batches, p.latencies))
                for p in profs) + f"; stage SLA {sla:.4f}s")
        self.pipe = PipelineModel(self.spec.name, tuple(stages))

    def reseed(self, seed: int) -> None:
        """New weights and traffic from ``seed``; the compiled programs and
        the planner's profiles stay."""
        for srv in self.servers:
            for v in srv.params:
                srv.params[v] = None
        self.weights = {}
        weights = self.weights = self._weights(seed)
        for st, srv in zip(self.spec.stages, self.servers):
            srv.params.update(weights[st.name])
        self.seed = seed

    # -- the window -----------------------------------------------------------
    def run_window(self, seconds: float, trace: bool = False) -> dict:
        """Serve the traffic due in ``seconds`` and drain; returns the run's
        record (requests, batches, plans, counters)."""
        import jax
        from repro.serving.batching import CentralQueue
        from repro.serving.request import Request

        tr = self.spec.traffic
        arrivals = tr["arrivals"]
        nominal = float(np.mean(TR.rate_at(arrivals, np.array([0.0]))))
        due = TR.arrival_times(arrivals, seconds, self.seed)
        st0 = self.spec.stages[0]
        prompts = TR.prompts(len(due), st0.prompt_tokens, st0.sizes.vocab,
                             self.seed)
        sla = self.pipe.sla
        interval = float(tr["interval_s"])
        reqs = [Req(float(t), p) for t, p in zip(due, prompts)]
        queues = [CentralQueue(1, 0.0) for _ in self.servers]
        batches, plans, late = [], [], []
        span = (jax.profiler.TraceAnnotation if trace
                else lambda name: contextlib.nullcontext())

        t0 = time.perf_counter()

        def clock() -> float:
            return time.perf_counter() - t0

        def plan(lam: float, at: float) -> None:
            from repro.core import optimizer as OPT
            from repro.core.queueing import wait_bound
            with span("plan"):
                a = time.perf_counter()
                sol = OPT.solve(self.pipe, lam, OPT.Objective(**OBJECTIVE),
                                max_replicas=1)
                solve_s = time.perf_counter() - a
                if sol.feasible:
                    for srv, q, sc in zip(self.servers, queues,
                                          sol.config.stages):
                        srv.set_variant(sc.variant)
                        q.reconfigure(sc.batch, wait_bound(sc.batch, lam))
            plans.append({"t": at, "rate_rps": lam, "feasible": sol.feasible,
                          "solve_s": solve_s,
                          "config": [[s.active, q.batch_size] for s, q in
                                     zip(self.servers, queues)]})

        def serve(s: int) -> None:
            srv, q = self.servers[s], queues[s]
            planned = q.batch_size
            group = q.pop_batch(clock())
            tokens = np.stack([r.payload for r in group])
            with span(f"process/{srv.name}/{srv.active}/b{len(group)}"):
                a = clock()
                gen, _ = srv.process(tokens)
                b = clock()
            batches.append({"stage": s, "variant": srv.active,
                            "size": len(group), "planned": planned,
                            "start": a, "end": b, "prompt": tokens.shape[1],
                            "gen": gen.shape[1]})
            for r, out in zip(group, gen):
                rec = reqs[r.req_id]
                rec.start.append(a)
                rec.variants.append(srv.active)
                rec.served.append(out)
                if s + 1 < len(queues):
                    rec.enter.append(b)
                    r.payload = out
                    queues[s + 1].push(r)
                else:
                    rec.done = b

        compiles0 = self.compiles.compiles
        i, n = 0, len(reqs)
        boundary = interval
        stop = seconds + DROP_FACTOR * sla
        plan(nominal, 0.0)
        with span("window"):
            while True:
                now = clock()
                while i < n and reqs[i].due <= now:
                    r = Request(arrival=reqs[i].due, payload=reqs[i].prompt,
                                req_id=i, sla=sla)
                    reqs[i].enter.append(reqs[i].due)
                    queues[0].push(r)
                    i += 1
                if boundary < seconds and now >= boundary:
                    lo = np.searchsorted(due, boundary - interval)
                    hi = np.searchsorted(due, boundary)
                    plan(float(hi - lo) / interval, boundary)
                    boundary += interval
                for s, q in enumerate(queues):
                    for r in q.drain_expired(now, s,
                                                 drop_factor=DROP_FACTOR):
                        reqs[r.req_id].failed_at = now
                if i == n and not any(len(q) for q in queues):
                    break
                if now >= stop:
                    break
                for s in reversed(range(len(queues))):
                    if queues[s].ready(now):
                        serve(s)
                        break
                else:
                    wake = [stop]
                    if i < n:
                        wake.append(reqs[i].due)
                    if boundary < seconds:
                        wake.append(boundary)
                    wake += [now + q.max_wait - q.oldest_wait(now)
                             for q in queues if len(q)]
                    nxt = min(wake)
                    with span("wait"):
                        time.sleep(max(0.0, nxt - clock()))
                    if i < n and reqs[i].due <= nxt:
                        late.append(clock() - reqs[i].due)
        end = clock()
        for q in queues:
            while len(q):
                for r in q.pop_batch(end):
                    reqs[r.req_id].failed_at = end
        return {
            "cell": self.spec.name, "seed": self.seed, "window_s": seconds,
            "rate_rps": nominal, "sla_s": sla, "end_s": end,
            "requests": reqs, "batches": batches, "plans": plans,
            "compiles_in_window": self.compiles.compiles - compiles0,
            "generator_late_s": late,
        }
