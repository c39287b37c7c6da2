"""Serving launcher: an IPA-managed pipeline on the real JAX engine.

Builds a pipeline from assigned-architecture variant families, profiles it
(paper §4.2) on this machine, then replays a workload excerpt with the IPA
adapter making variant/batch/replica decisions online.

  PYTHONPATH=src python -m repro.launch.serve --pipeline vlm-classify \
      --trace bursty --seconds 120
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from repro import configs
from repro.core import adapter as AD
from repro.core import optimizer as OPT
from repro.core import profiler as PF
from repro.core import trace as TR
from repro.core.pipeline import PipelineModel
from repro.launch.compile_cache import use_compile_cache
from repro.serving.engine import PipelineEngine, StageServer

# pipelines over the assigned architectures (analogues of the paper's five)
ENGINE_PIPELINES = {
    # video-monitoring analogue: VLM "detector" -> dense classifier
    "vlm-classify": [("phi-3-vision-4.2b", 4), ("yi-34b", 4)],
    # audio-qa analogue: whisper ASR backbone -> code/QA dense model
    "asr-qa": [("whisper-medium", 4), ("starcoder2-3b", 4)],
    # nlp analogue: gemma3 -> qwen2-moe -> mamba2 chain
    "nlp-chain": [("gemma3-27b", 4), ("qwen2-moe-a2.7b", 4),
                  ("mamba2-2.7b", 4)],
}


def pipeline_families(name: str):
    """The (stage name, variant family) list of an ENGINE_PIPELINES entry."""
    return [(arch, configs.get_variant_family(arch))
            for arch, _ in ENGINE_PIPELINES[name]]


def build_pipeline(name: str, families, *, gen_tokens: int = 4,
                   profile_batches=(1, 2, 4), th: float = 2.0,
                   verbose: bool = True):
    """Profile one StageServer per stage and build the control-plane model.

    ``families``: (stage name, variant family) per stage, in pipeline order;
    a family is a list of (variant name, ModelConfig, accuracy).
    Returns (PipelineModel for the control plane, PipelineEngine).
    """
    servers = []
    stages = []
    for stage_name, fam in families:
        srv = StageServer(stage_name, fam, gen_tokens=gen_tokens)
        if verbose:
            print(f"profiling stage {stage_name} ({len(fam)} variants)...",
                  flush=True)
        profs = PF.profile_stage_server(srv, batches=profile_batches)
        stage = PF.build_stage(stage_name, profs, th=th,
                               batch_choices=profile_batches,
                               max_batch=max(profile_batches))
        servers.append(srv)
        stages.append(stage)
    return PipelineModel(name, tuple(stages)), PipelineEngine(servers)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pipeline", default="vlm-classify",
                    choices=list(ENGINE_PIPELINES))
    ap.add_argument("--trace", default="bursty", choices=list(TR.EXCERPTS))
    ap.add_argument("--seconds", type=int, default=120)
    ap.add_argument("--policy", default="ipa",
                    choices=["ipa", "fa2_low", "fa2_high", "rim"])
    ap.add_argument("--alpha", type=float, default=10.0)
    ap.add_argument("--beta", type=float, default=0.5)
    ap.add_argument("--scale-rps", type=float, default=0.25,
                    help="scale the trace to this machine's capacity")
    args = ap.parse_args()

    use_compile_cache()
    pipe, engine = build_pipeline(args.pipeline,
                                  pipeline_families(args.pipeline))
    print(f"pipeline SLA_P = {pipe.sla:.2f}s")
    rates = TR.excerpt(args.trace, seconds=args.seconds) * args.scale_rps
    obj = OPT.Objective(alpha=args.alpha, beta=args.beta, metric="pas")
    res = AD.run_trace(pipe, rates, policy=args.policy, obj=obj)
    print(json.dumps(res.summary(), indent=1))

    # demonstrate the data plane actually serving under the chosen config
    last = res.intervals[-1]
    print(f"final interval PAS={last.pas:.2f} cost={last.cost:.0f}")
    toks = np.random.randint(0, 400, (2, 16)).astype(np.int32)
    out, lats = engine.serve(toks)
    print("engine sanity:", out.shape,
          [f"{l*1e3:.0f}ms" for l in lats])


if __name__ == "__main__":
    main()
