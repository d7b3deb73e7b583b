"""rescore_ms: per batch, the ms of the scan engine's exact float64 host
rescore of the fetched candidates (its ``scan.rescore`` spans inside the
batch annotations; profiler trace)."""

import programspans

SPANS = ("scan.rescore",)


def read(run):
    return programspans.ms_per_batch(run.trace, SPANS)
