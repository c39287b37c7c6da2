"""``bench/run.py`` refuses to run without a TPU, and prints no result."""
import json

import pytest

from bench import metrics
from bench import run as R
from bench import spec as SP


def test_run_exits_nonzero_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        R.main(["--workload", "sc2-3b.decode-steady", "--seed", "3000000019",
                "--seconds", "1", "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_every_cell_resolves_from_benchmark_json():
    bench = SP.load_benchmark()
    for w in bench["workloads"]:
        cell = SP.cell(w["name"])
        assert cell.stages and cell.end_to_end and cell.per_layer
        assert all(st.sla_s for st in cell.stages), w["name"]
        assert all(st.max_logit_gap for st in cell.stages), w["name"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) > 1
        for m in cell.end_to_end + cell.per_layer:
            assert callable(metrics.reader(m["name"]))
    assert json.loads(json.dumps(bench)) == bench
