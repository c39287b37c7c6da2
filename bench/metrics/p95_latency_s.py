"""95th percentile (nearest rank) of end-to-end latency from the due time,
over every request due in the window; one that failed counts with its age
when it was given up."""
import math

from bench.metrics import latencies


def read(rec):
    lat = sorted(t for _, t in latencies(rec))
    if not lat:
        return None
    return lat[math.ceil(0.95 * len(lat)) - 1]
