"""host_ms: per batch, the length of the benchmark's annotation around
knn_batch minus the part of it in which any chip ran an operation
(profiler trace), in ms, averaged over the window's batches."""

import numpy as np

import tracesum


def read(run):
    if run.trace is None or not run.trace.batches or not run.trace.ops:
        return None
    return 1e-6 * float(np.mean(tracesum.host_ns_per_batch(run.trace)))
