"""scan_ms: device time per batch of the scan program (scan_topk, with
the hamming_scan_scores kernel inside it)."""

import tracesum

PROGRAM = "scan_topk"


def read(run):
    if run.trace is None:
        return None
    return tracesum.program_ms_per_batch(run.trace, PROGRAM)
