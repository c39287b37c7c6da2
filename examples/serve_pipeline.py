"""End-to-end driver (deliverable b): serve a small real model pipeline with
batched requests under IPA control.

Two assigned architectures (phi-3-vision -> yi-34b reduced families) form a
video-monitoring-style pipeline on the REAL JAX engine: the profiler measures
each variant's prefill+decode latency on this machine, Eq. 1 computes base
allocations, and the IPA adapter replays a workload excerpt, switching
variants/batches/replicas online.  Finally the chosen config serves actual
batched token requests through both stages.

  PYTHONPATH=src python examples/serve_pipeline.py
"""
import numpy as np

from repro.core import adapter as AD
from repro.core import optimizer as OPT
from repro.core import trace as TR
from repro.launch.serve import build_pipeline, pipeline_families


def main() -> None:
    pipe, engine = build_pipeline("vlm-classify",
                                  pipeline_families("vlm-classify"),
                                  gen_tokens=2,
                                  profile_batches=(1, 2), th=0.5)
    print(f"profiled pipeline SLA_P = {pipe.sla:.2f}s")
    for st in pipe.stages:
        for v in st.variants:
            print(f"  {st.name}/{v.name}: l(1)={v.latency(1)*1e3:.0f}ms "
                  f"R={v.base_alloc} acc={v.accuracy}")

    rates = TR.excerpt("fluctuating", seconds=60) * 0.1  # laptop-scale RPS
    obj = OPT.Objective(alpha=10.0, beta=0.5, metric="pas")
    res = AD.run_trace(pipe, rates, policy="ipa", obj=obj, seed=0)
    print("adaptation summary:", res.summary())

    # apply the final decision to the real engine and serve a batch
    final = res.intervals[-1]
    print(f"final interval: PAS={final.pas:.2f} cost={final.cost:.0f}")
    prompts = np.random.default_rng(0).integers(0, 400, (4, 12)).astype(np.int32)
    out, lats = engine.serve(prompts)
    print(f"served batch of 4 through 2 stages -> output tokens {out.shape}, "
          f"stage latencies {[f'{l*1e3:.0f}ms' for l in lats]}, "
          f"engine PAS={engine.pas:.2f}")


if __name__ == "__main__":
    main()
