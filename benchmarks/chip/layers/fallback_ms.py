"""fallback_ms: device time per batch of the AMIH walk's scan fallback,
the scan engine's ``scan_topk`` program run over the queries the walk
gave up on (profiler trace). 0 where the walk ran and no batch of the
window bailed; None where the trace holds no walk."""

import tracesum

PROGRAM = "scan_topk"
WALK = "device_probe_walk_batched"


def read(run):
    if run.trace is None:
        return None
    ms = tracesum.program_ms_per_batch(run.trace, PROGRAM)
    if ms is None and tracesum.program_ms_per_batch(run.trace, WALK):
        return 0.0
    return ms
