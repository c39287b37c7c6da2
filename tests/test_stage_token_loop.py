"""``StageServer.process``'s token loop, on the CPU: it serves exactly the
tokens of a plain step-by-step greedy reference, and a warmed call runs one
program per kept token, with no decode step whose token it drops and no
eager glue between steps."""
import contextlib
import dataclasses
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import mamba2_2p7b, starcoder2_3b
from repro.models import model as M
from repro.serving import engine
from repro.serving.engine import StageServer

PROMPT = 16
# served precision: bf16 weights, as the benchmark's cells hold them
CONFIGS = {"dense": dataclasses.replace(starcoder2_3b.reduced(2, 128),
                                        dtype="bfloat16"),
           "ssm": dataclasses.replace(mamba2_2p7b.reduced(2, 128),
                                      dtype="bfloat16")}


@pytest.fixture(scope="module")
def servers():
    made = {}

    def get(family, gen):
        if (family, gen) not in made:
            made[family, gen] = StageServer(
                family, [("v", CONFIGS[family], 50.0)], gen_tokens=gen,
                max_ctx=PROMPT + gen)
        return made[family, gen]
    return get


def reference(params, cfg, tokens, gen):
    """Greedy decoding one step at a time: ``M.prefill``, then
    ``M.decode_step`` on each picked token, the argmax on the host."""
    s = tokens.shape[1]

    @jax.jit
    def prefill(params, tokens):
        hl, caches, _ = M.prefill(params, cfg, {"tokens": tokens},
                                  impl="naive", capacity=s + gen)
        return jnp.einsum("bd,vd->bv", hl, params["embed"]), caches

    decode = jax.jit(lambda params, caches, clen, tok:
                     M.decode_step(params, cfg, caches, clen, tok))
    lg, caches = prefill(params, jnp.asarray(tokens))
    out = []
    for i in range(gen):
        tok = np.argmax(np.asarray(lg), axis=-1).astype(np.int32)
        out.append(tok)
        if i < gen - 1:
            lg, caches = decode(params, caches, jnp.int32(s + i),
                                jnp.asarray(tok[:, None]))
    return np.stack(out, axis=1)


@pytest.mark.parametrize("gen", [1, 2, 5])
@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("family", list(CONFIGS))
def test_process_serves_the_greedy_reference(servers, family, b, gen):
    srv = servers(family, gen)
    cfg = CONFIGS[family]
    tokens = np.random.default_rng(100 * b + gen).integers(
        0, cfg.vocab, (b, PROMPT), dtype=np.int32)
    got, wall = srv.process(tokens)
    assert got.shape == (b, gen) and got.dtype == np.int32 and wall > 0
    np.testing.assert_array_equal(
        got, reference(srv.params["v"], cfg, tokens, gen))


@contextlib.contextmanager
def compiled_programs():
    """The names of the programs JAX compiles inside the block."""
    names = []

    class Names(logging.Handler):
        def emit(self, record):
            m = re.match(r"Compiling jit\((\w+)\)", record.getMessage())
            if m:
                names.append(m.group(1))

    handler = Names(logging.DEBUG)
    log = logging.getLogger("jax")
    log.addHandler(handler)
    try:
        with jax.log_compiles(True):
            yield names
    finally:
        log.removeHandler(handler)


@pytest.mark.parametrize("gen", [1, 5])
@pytest.mark.parametrize("family", list(CONFIGS))
def test_one_program_per_kept_token(servers, family, gen, monkeypatch):
    """A warmed call compiles nothing; each call runs one prefill, then
    ``gen - 1`` decode steps and one pick of the last token, with no
    separate argmax, convert, broadcast or concatenate: after the caches
    are cleared, those three programs are all that a call compiles."""
    srv = servers(family, gen)
    tokens = np.zeros((2, PROMPT), np.int32)
    srv.process(tokens)                                  # warm
    with compiled_programs() as names:
        srv.process(tokens)
    assert names == []

    calls = {"prefill_step": 0, "decode_step": 0, "keep_last": 0}

    def counted(name, program):
        def run(*args):
            calls[name] += 1
            return program(*args)
        return run

    for cache, name in ((srv._prefill_cache, "prefill_step"),
                        (srv._decode_cache, "decode_step")):
        for key, program in list(cache.items()):
            monkeypatch.setitem(cache, key, counted(name, program))
    monkeypatch.setattr(engine, "keep_last",
                        counted("keep_last", engine.keep_last))
    jax.clear_caches()
    with compiled_programs() as names:
        srv.process(tokens)
    assert calls == {"prefill_step": 1, "decode_step": gen - 1,
                     "keep_last": 1}
    assert sorted(names) == sorted(
        ["prefill_step", "keep_last"] + ["decode_step"] * (gen > 1))
