"""Tiny cells for the benchmark's tests, on the CPU: the same harness and
program path as the chip cells, at sizes a test run holds."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

DENSE = {"family": "dense", "hidden_size": 128, "intermediate_size": 256,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 2, "vocab_size": 512, "sliding_window": 64,
         "rope_theta": 10000.0, "norm_epsilon": 1e-5,
         "torch_dtype": "bfloat16",
         "variants": [{"name": "d2", "layers": 2, "accuracy": 60.0},
                      {"name": "d1", "layers": 1, "accuracy": 30.0}]}
SSM = {"family": "ssm", "d_model": 128, "n_layer": 2, "vocab_size": 500,
       "pad_vocab_size_multiple": 16,
       "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 4,
                   "expand": 2, "headdim": 32, "ngroups": 1,
                   "chunk_size": 16},
       "norm_epsilon": 1e-5, "gate_norm_epsilon": 1e-6,
       "torch_dtype": "bfloat16",
       "variants": [{"name": "s2", "layers": 2, "accuracy": 50.0},
                    {"name": "s1", "layers": 1, "accuracy": 25.0}]}
# The check's limit at these sizes, set as the cells' limits are: over nine
# seeds on the CPU, sound runs of the tiny chain read at most 0.017 logits
# (bf16 rounding of a one- or two-layer model), and the fp8 control at
# least 0.097 (each seed's worse stage); the faults tested read far more.
LIMIT = 0.05
SEED = 2 ** 31 + 11


def tiny_chain(rate=40.0, sla=(0.6, 0.4), interval=0.5, batches=(2,)):
    """A two-stage chain: ssm (24 in, 8 out) then dense (8 in, 4 out).
    With the one batch choice 2, the planner forms batches of two where
    the rate allows (a timeout pops one)."""
    from bench import spec as SP
    config = {"stages": ["ssm", "dense"], "ssm": SSM, "dense": DENSE,
              "check": {"sample_requests": 4,
                        "max_logit_gap": {"ssm": LIMIT, "dense": LIMIT}}}
    traffic = {"arrivals": {"kind": "poisson", "rate_rps": rate},
               "stages": [{"prompt_tokens": 24, "output_tokens": 8,
                           "sla_s": sla[0]},
                          {"prompt_tokens": 8, "output_tokens": 4,
                           "sla_s": sla[1]}],
               "interval_s": interval, "batch_choices": list(batches)}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = dict(bench, per_layer=[dict(m, workloads=["tiny"])
                                   for m in bench["per_layer"]],
                 end_to_end=[dict(m, workloads=["tiny"])
                             for m in bench["end_to_end"]])
    return SP.build("tiny", 1, config, traffic, bench)


@pytest.fixture(scope="module")
def tiny_cell():
    """The tiny chain, set up once per module (weights, profiles, warm-up,
    and the reference's and the control's programs compiled by one
    short checked window)."""
    from bench import check as CK
    from bench.driver import Cell
    cell = Cell(tiny_chain(), seed=SEED, log=lambda msg: None)
    cell.setup()
    CK.check(cell, cell.run_window(0.3), controls=("fp8",))
    return cell
