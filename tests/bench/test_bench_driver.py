"""The driver loop in-process on the CPU, on a tiny two-stage chain: requests
flow through both stages and across planner boundaries, and the check
passes a sound run and fails each fault a cell can have.

The faults in the served tokens are planted in the model function
``repro.models.model.decode_step``, which every way of driving the token
loop calls, and not in how ``StageServer.process`` drives the device: the
check judges what ``process`` returns."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check as CK
from bench import generator as GEN
from repro.models import model as M
from repro.serving import engine
from repro.serving.engine import StageServer

from conftest import LIMIT, SEED, tiny_chain


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


def _state_unchanged(step, spec):
    """The real step's logits, with the caches it was given."""
    def broken(params, cfg, caches, cache_len, tokens, **kw):
        return step(params, cfg, caches, cache_len, tokens, **kw)[0], caches
    return broken


def _token_altered(step, spec):
    """Token 0 wins the step whose context is the stage's prompt + 1: the
    third generated token of every row. The condition is on the traced
    length, so it holds inside any loop."""
    at = {st.name: st.prompt_tokens + 1 for st in spec.stages}

    def broken(params, cfg, caches, cache_len, tokens, **kw):
        lg, caches = step(params, cfg, caches, cache_len, tokens, **kw)
        return jnp.where(cache_len == at[cfg.arch_id],
                         lg.at[:, 0].set(1e4), lg), caches
    return broken


def _cell_with(monkeypatch, fault=None, server=None):
    """A fresh tiny cell whose programs trace ``fault`` wrapped round the
    program's ``decode_step``, served by ``server`` in place of
    ``StageServer`` where given. The patches come before the cell is built
    and set up, so profiling, warm-up and the window all run them; the
    reference imports nothing of ``repro.models``."""
    from bench.driver import Cell
    spec = tiny_chain()
    if fault is not None:
        monkeypatch.setattr(M, "decode_step", fault(M.decode_step, spec))
    if server is not None:
        monkeypatch.setattr(engine, "StageServer", server)
    cell = Cell(spec, seed=SEED, log=lambda msg: None)
    cell.setup()
    return cell


def test_fault_state_unchanged_fails(monkeypatch):
    """Each decode step returns the cache it was given."""
    cell = _cell_with(monkeypatch, _state_unchanged)
    result = _broken_run(cell, monkeypatch)
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


def test_fault_token_altered_where_produced_fails(monkeypatch):
    """The third generated token of every row is forced to token 0."""
    cell = _cell_with(monkeypatch, _token_altered)
    result = _broken_run(cell, monkeypatch)
    assert result["correct"] is False
    assert max(n["value"] for n in result["compared"].values()) > LIMIT


class _DeviceLoopServer(StageServer):
    """A stage server whose token loop runs on the device: the prefill
    program, then one program of ``gen_tokens - 1`` decode steps with the
    argmax inside. A stand-in for such a program, to show that the faults
    above do not depend on how ``process`` drives the device."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.programs = {}

    def _compiled(self, vname: str, s: int):
        if (vname, s) not in self.programs:
            cfg = self.variants[vname][0]
            cap = min(self.max_ctx, s + self.gen_tokens)
            n_steps = self.gen_tokens - 1

            @jax.jit
            def prefill(params, tokens):
                hl, caches, _ = M.prefill(params, cfg, {"tokens": tokens},
                                          impl="naive", capacity=cap)
                lg = jnp.einsum("bd,vd->bv", hl, params["embed"])
                tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, None]
                return tok, caches

            @jax.jit
            def generate(params, caches, tok):
                def step(carry, clen):
                    caches, tok = carry
                    lg, caches = M.decode_step(params, cfg, caches, clen, tok)
                    tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, None]
                    return (caches, tok), tok[:, 0]
                clens = s + jnp.arange(n_steps, dtype=jnp.int32)
                _, toks = jax.lax.scan(step, (caches, tok), clens)
                return jnp.concatenate([tok, toks.T], axis=1)
            self.programs[vname, s] = prefill, generate
        return self.programs[vname, s]

    def process(self, tokens):
        tokens = np.asarray(tokens, np.int32) % self.config.vocab
        t0 = time.perf_counter()
        prefill, generate = self._compiled(self.active, tokens.shape[1])
        params = self.params[self.active]
        tok, caches = prefill(params, jnp.asarray(tokens))
        gen = np.asarray(generate(params, caches, tok))
        return gen, time.perf_counter() - t0


FAULTS = {"sound": None, "state_unchanged": _state_unchanged,
          "token_altered": _token_altered}


@pytest.mark.parametrize("case", list(FAULTS))
def test_verdicts_hold_for_a_device_token_loop(case, monkeypatch):
    """With the token loop on the device, a sound run is correct with
    nothing compiled in the window, and each fault planted in
    ``decode_step`` fails the check."""
    cell = _cell_with(monkeypatch, FAULTS[case], _DeviceLoopServer)
    assert all(isinstance(srv, _DeviceLoopServer) for srv in cell.servers)
    rec = cell.run_window(0.6)
    verdict = CK.check(cell, rec)
    gap = max(n["value"] for n in verdict["numbers"])
    if case == "sound":
        assert verdict["correct"] is True and rec["compiles_in_window"] == 0
        assert all(r.done is not None for r in rec["requests"])
        assert gap <= LIMIT
    else:
        assert verdict["correct"] is False and gap > LIMIT


def test_calibrate_reads_limits_and_knee_through_run_set_up(
        tiny_cell, monkeypatch, capsys):
    """``bench/calibrate.py`` on the set-up cell: the program's verdict and
    the control's, by the cell's limits, then the share meeting the SLA at
    a fixed rate."""
    import argparse
    import json

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
