"""scan_prep_ms: per batch, the ms the scan engine spent padding and
uploading the queries and dispatching the scan (the union of its
``scan.prep`` and ``scan.dispatch`` spans inside the batch annotations;
profiler trace)."""

import programspans

SPANS = ("scan.prep", "scan.dispatch")


def read(run):
    return programspans.ms_per_batch(run.trace, SPANS)
