"""fetch_wait_ms: per batch, the ms the scan engine's host blocked on
the device's top-K ids and their copy to the host (its ``scan.fetch``
spans inside the batch annotations; profiler trace)."""

import programspans

SPANS = ("scan.fetch",)


def read(run):
    return programspans.ms_per_batch(run.trace, SPANS)
