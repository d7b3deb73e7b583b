"""device_idle_pct: share of the traced window in which no operation ran
on a chip, averaged over the cell's chips (profiler trace)."""

import tracesum


def read(run):
    if run.trace is None or not run.trace.batches or not run.trace.ops:
        return None
    return tracesum.idle_pct(run.trace)
