"""Requests due in the window that finished within the pipeline SLA, per
second of the window. Dropped and unfinished requests miss."""


def read(rec):
    good = sum(1 for r in rec["requests"]
               if r.done is not None and r.done - r.due <= rec["sla_s"])
    return good / rec["window_s"]
