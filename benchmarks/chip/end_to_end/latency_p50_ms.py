"""latency_p50_ms: median over every query answered in the window of the
time from its knn_batch call to its answer on the host (host clock)."""

import numpy as np


def read(run):
    lat = [b.latency for b in run.batches for _ in range(b.queries)]
    return 1e3 * float(np.percentile(lat, 50)) if lat else None
