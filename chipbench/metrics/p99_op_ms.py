"""99th percentile, nearest rank, of the call-to-return latency of every
operation completed inside the window.  The percentile is copied from
the program's fleet recorder (``repro.fleet.recorder.percentile``)."""

import math


def percentile(sorted_values, q):
    """Nearest-rank percentile (q in [0, 1]) over an ascending list."""
    rank = max(1, math.ceil(q * len(sorted_values)))
    return sorted_values[min(rank, len(sorted_values)) - 1]


def read(obs):
    lat = obs["latencies_s"]
    return percentile(lat, 0.99) * 1e3 if lat else None
