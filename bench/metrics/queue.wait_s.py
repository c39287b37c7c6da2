"""Mean over finished requests of the time spent in queues, summed over
stages: from entering a stage's queue (the due time, for the first) to
the start of the batch that served it."""


def read(rec):
    waits = [sum(a - e for a, e in zip(r.start, r.enter))
             for r in rec["requests"] if r.done is not None]
    return sum(waits) / len(waits) if waits else None
