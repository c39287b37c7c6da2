"""Mean over finished requests of the pipeline accuracy score (Eq. 8) of
the variants that served each, in percent."""


def read(rec):
    acc = rec["accuracy"]               # {stage index: {variant: accuracy}}
    scores = []
    for r in rec["requests"]:
        if r.done is None:
            continue
        p = 1.0
        for s, v in enumerate(r.variants):
            p *= acc[s][v] / 100.0
        scores.append(100.0 * p)
    return sum(scores) / len(scores) if scores else None
