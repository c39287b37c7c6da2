"""Share of the window in which a stage server's ``process`` call was in
flight, in percent."""


def read(rec):
    w = rec["window_s"]
    busy = sum(max(0.0, min(b["end"], w) - min(b["start"], w))
               for b in rec["batches"])
    return 100.0 * busy / w
