"""Whether what the window served is correct, against the plain reference.

After the window closes, a sample of the finished requests is drawn from
the seed: at least one request of each combination of variants that served
in the window, the rest at random, ``sample_requests`` in all. For each
stage, the reference runs once over each sampled request's prompt at that
stage (the first stage's prompt, or the previous stage's served tokens
modulo this stage's vocabulary, as the stage server takes them) followed by
the tokens the stage served, with the benchmark's own weights of the
variant that served it. Each served token was chosen greedily, so its
reference logit should be the reference's best at that position, up to the
program's rounding; the number compared per stage is the widest gap by
which a served token's logit lies below the reference's best, in logits.

The control is the reference in the program's place, computed in a lower
precision (``bench.reference.common.QUANTS``): at each of the same
positions, the gap of the token it puts first. Each control gets a verdict
of its own, by the same limits, and a limit is sound only where the
control's comes out not correct.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import numpy as np

from bench import generator as TR


def sample(requests: Sequence, k: int, seed: int) -> List[int]:
    """Indices of up to ``k`` finished requests (at least one per
    combination of variants that served), drawn from ``seed``."""
    rng = TR.rng(seed, "sample")
    groups: Dict[tuple, List[int]] = {}
    for i, r in enumerate(requests):
        if r.done is not None:
            groups.setdefault(tuple(r.variants), []).append(i)
    chosen = [int(rng.choice(g)) for _, g in sorted(groups.items())]
    rest = [i for g in groups.values() for i in g if i not in chosen]
    extra = max(0, k - len(chosen))
    if rest and extra:
        chosen += [int(i) for i in rng.choice(rest, min(extra, len(rest)),
                                              replace=False)]
    return sorted(chosen)


@functools.lru_cache(maxsize=None)
def _reference(family_module, sz, first: int, count: int, quant):
    import jax
    return jax.jit(functools.partial(family_module.logits, sz=sz, first=first,
                                     count=count, quant=quant))


def _gaps(ref, served, ctrl=None):
    """Widest gap below the reference's best of the served tokens (and of
    the control's first choices)."""
    import jax.numpy as jnp
    best = jnp.max(ref, axis=-1)
    pick = lambda tok: jnp.take_along_axis(ref, tok[..., None], -1)[..., 0]
    out = [float(jnp.max(best - pick(jnp.asarray(served))))]
    if ctrl is not None:
        out.append(float(jnp.max(best - pick(jnp.argmax(ctrl, axis=-1)))))
    return out


def _verdict(worst: Dict[str, float], stages, sound: bool) -> dict:
    """``{"correct", "numbers"}``: each stage's widest gap beside its limit;
    correct where every gap is within its limit."""
    numbers = [{"name": f"logit_gap.{st.name}", "value": worst[st.name],
                "limit": st.max_logit_gap} for st in stages]
    ok = sound and all(n["limit"] is not None and n["value"] <= n["limit"]
                       for n in numbers)
    return {"correct": bool(ok), "numbers": numbers}


def check(cell, rec: dict, controls: Sequence[str] = ()) -> dict:
    """``{"correct", "numbers": [{"name", "value", "limit"}], "sampled",
    "tokens", "controls": {quant: {"correct", "numbers"}}}``: the program's
    verdict, and each control's, held to the same limits; ``cell`` is the
    ``driver.Cell`` that served ``rec``."""
    spec = cell.spec
    k = int(spec.config["check"]["sample_requests"])
    reqs = rec["requests"]
    idx = sample(reqs, k, rec["seed"])
    sound = bool(idx)
    quants = (None,) + tuple(controls)
    worst = {q: {} for q in quants}
    tokens = 0
    for s, st in enumerate(spec.stages):
        ref_mod = st.module("reference")
        vocab, p, g = st.sizes.vocab, st.prompt_tokens, st.output_tokens
        for q in quants:
            worst[q][st.name] = 0.0
        by_variant: Dict[str, List[int]] = {}
        for i in idx:
            by_variant.setdefault(reqs[i].variants[s], []).append(i)
        for vname, rows in sorted(by_variant.items()):
            prompt = np.stack([reqs[i].prompt if s == 0 else reqs[i].served[s - 1]
                               for i in rows]).astype(np.int64) % vocab
            served = np.stack([reqs[i].served[s] for i in rows])
            if served.shape != (len(rows), g) or served.min() < 0 \
                    or served.max() >= vocab:
                sound = False
                continue
            pad = k - len(rows)                 # one shape per variant
            seq = np.concatenate([prompt, served], 1).astype(np.int32)
            seq = np.concatenate([seq, np.repeat(seq[:1], pad, 0)])
            params = cell.weights[st.name][vname]
            ref = _reference(ref_mod, st.sizes, p - 1, g, None)(params, tokens=seq)
            ref = ref[:len(rows)]
            gaps = {None: _gaps(ref, served)[0]}
            for q in controls:
                ctl = _reference(ref_mod, st.sizes, p - 1, g, q)(params, tokens=seq)
                gaps[q] = _gaps(ref, served, ctl[:len(rows)])[1]
            for q, gap in gaps.items():
                worst[q][st.name] = max(worst[q][st.name], gap)
            tokens += served.size
    out = _verdict(worst[None], spec.stages, sound)
    out.update(sampled=len(idx), tokens=tokens, controls={
        q: _verdict(worst[q], spec.stages, sound) for q in controls})
    return out
