"""What ``BENCHMARK.json`` says about one cell, with its configuration and
traffic files loaded and each stage's family modules found by name."""
from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path
from typing import List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclasses.dataclass(frozen=True)
class Stage:
    name: str
    family: str
    sizes: tuple
    variants: Tuple[Tuple[str, int, float], ...]   # (name, layers, accuracy)
    prompt_tokens: int
    output_tokens: int
    sla_s: Optional[float]
    max_logit_gap: Optional[float]

    def module(self, kind: str):
        """``bench.<kind>.<family>``: reference, counts or program."""
        return importlib.import_module(f"bench.{kind}.{self.family}")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    stages: Tuple[Stage, ...]
    end_to_end: Tuple[dict, ...]
    per_layer: Tuple[dict, ...]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _stages(config: dict, traffic: dict) -> List[Stage]:
    limits = config["check"]["max_logit_gap"]
    out = []
    for name, mix in zip(config["stages"], traffic["stages"], strict=True):
        group = config[name]
        family = group["family"]
        sz = importlib.import_module(f"bench.reference.{family}").sizes(group)
        out.append(Stage(
            name=name, family=family, sizes=sz,
            variants=tuple((v["name"], v["layers"], v["accuracy"])
                           for v in group["variants"]),
            prompt_tokens=mix["prompt_tokens"],
            output_tokens=mix["output_tokens"],
            sla_s=mix.get("sla_s"), max_logit_gap=limits.get(name)))
    for prev, nxt in zip(out, out[1:]):
        if nxt.prompt_tokens != prev.output_tokens:
            raise ValueError(f"stage {nxt.name} takes {nxt.prompt_tokens} "
                             f"tokens; {prev.name} gives {prev.output_tokens}")
    return out


def cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return build(name, w["chips"], config, traffic, bench)


def build(name: str, chips: int, config: dict, traffic: dict,
          bench: dict) -> Cell:
    """A cell from its configuration and traffic as loaded, with the
    metrics ``bench`` (``BENCHMARK.json`` as loaded) gives it."""
    return Cell(
        name=name, chips=chips, config=config, traffic=traffic,
        stages=tuple(_stages(config, traffic)),
        end_to_end=tuple(m for m in bench["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)))
