"""Operations and bytes of the steps a served batch ran, per model family:
``<family>.py`` here gives ``prefill(sizes, layers, batch, seq)`` and
``decode(sizes, layers, batch, pos)``, each returning (flops, bytes)."""
from __future__ import annotations


def annotate(spec, rec: dict, peaks: dict) -> None:
    """Give each batch of ``rec`` its ``flops``, ``bytes`` and
    ``roofline_s``: one prefill of its prompt and one decode step for each
    kept token after the first (the program's ``process`` runs one decode
    step more, whose token it drops; that step is not counted)."""
    for b in rec["batches"]:
        st = spec.stages[b["stage"]]
        mod = st.module("counts")
        layers = {v: n for v, n, _ in st.variants}[b["variant"]]
        steps = [mod.prefill(st.sizes, layers, b["size"], b["prompt"])]
        steps += [mod.decode(st.sizes, layers, b["size"], b["prompt"] + i - 1)
                  for i in range(1, b["gen"])]
        b["flops"] = sum(f for f, _ in steps)
        b["bytes"] = sum(n for _, n in steps)
        b["roofline_s"] = sum(max(f / peaks["bf16_flops_per_s"],
                                  n / peaks["hbm_bytes_per_s"])
                              for f, n in steps)
