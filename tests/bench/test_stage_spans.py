"""The program's spans inside ``StageServer.process``, on the CPU: one call
on the tiny dense stage, profiled with the options ``bench/run.py`` traces
with, holds one ``stage.prefill``, one ``stage.decode`` per token step
(its index as the ``step`` stat) and one ``stage.fetch``, in that order
and inside the call."""
import glob

import numpy as np
import pytest

from conftest import tiny_chain

GEN = 4


@pytest.fixture(scope="module")
def dense_server():
    from repro.serving.engine import StageServer
    st = tiny_chain().stages[1]
    program = st.module("program")
    family = [(v, program.model_config(st.name, st.sizes, layers), acc)
              for v, layers, acc in st.variants]
    srv = StageServer(st.name, family, gen_tokens=GEN, max_ctx=8 + GEN)
    srv.process(np.zeros((2, 8), np.int32))            # compile
    return srv


def test_process_spans_in_a_profile(dense_server, tmp_path):
    import jax
    from jax.profiler import ProfileData

    from bench import run as R
    tokens = np.arange(16, dtype=np.int32).reshape(2, 8)
    R.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("process/dense/d2/b2"):
            gen, _ = dense_server.process(tokens)
    finally:
        jax.profiler.stop_trace()
    assert gen.shape == (2, GEN)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                            dict(e.stats)) for e in line.events
                           if e.name.startswith(("stage.", "process/"))]
    events.sort()
    (p0, p1, _, _), *inner = events
    assert [n for _, _, n, _ in inner] == (
        ["stage.prefill"] + ["stage.decode"] * GEN + ["stage.fetch"])
    assert [s.get("step") for _, _, n, s in inner
            if n == "stage.decode"] == list(range(GEN))
    assert all(s == {} for _, _, n, s in inner if n != "stage.decode")
    t = p0
    for a, b, _, _ in inner:          # one after another, inside the call
        assert t <= a <= b
        t = b
    assert t <= p1
