"""The least time the chip could take for every step served (for each
step the larger of its FLOPs over peak and its bytes over HBM bandwidth)
over the device-busy seconds inside ``process`` spans, from the trace, in
percent."""
from bench.metrics import process_busy_s


def read(rec):
    busy = process_busy_s(rec)
    if busy <= 0:
        return None
    least = sum(b["roofline_s"] for b in rec["batches"])
    return 100.0 * least / busy
