"""walk_ms: device time per batch of the fused AMIH walk program
(``device_probe_walk_batched``, with the verify kernel inside it;
profiler trace)."""

import tracesum

PROGRAM = "device_probe_walk_batched"


def read(run):
    if run.trace is None:
        return None
    return tracesum.program_ms_per_batch(run.trace, PROGRAM)
