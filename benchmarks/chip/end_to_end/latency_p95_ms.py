"""latency_p95_ms: 95th percentile over every query answered in the
window of the time from its knn_batch call to its answer on the host."""

import numpy as np


def read(run):
    lat = [b.latency for b in run.batches for _ in range(b.queries)]
    return 1e3 * float(np.percentile(lat, 95)) if lat else None
