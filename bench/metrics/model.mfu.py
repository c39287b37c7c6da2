"""Model FLOPs of every step served (prefill and the decode steps whose
tokens were kept, counted by ``bench/counts``) over the device-busy seconds
inside ``process`` spans, from the trace, times the chip's peak, in percent.
Normalised by busy time, not by the window: at a fixed rate the work in a
window is fixed. Host stalls show in ``device.idle_share`` instead."""
from bench.metrics import process_busy_s


def read(rec):
    busy = process_busy_s(rec)
    if busy <= 0:
        return None
    flops = sum(b["flops"] for b in rec["batches"])
    return 100.0 * flops / (busy * rec["peaks"]["bf16_flops_per_s"])
