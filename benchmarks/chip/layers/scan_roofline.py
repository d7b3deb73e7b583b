"""scan_roofline: the scan's share of the HBM roofline, in %: the
least bytes of an exhaustive top-K of each batch (codes read once, K
results written per query) at the chip's peak HBM bandwidth, over the
scan program's device time (profiler trace)."""

import roofline
import tracesum

PROGRAM = "scan_topk"


def read(run):
    if run.trace is None or not run.trace.batches or not run.peaks:
        return None
    seconds = 1e-9 * sum(tracesum.program_ns(run.trace, PROGRAM).values())
    if seconds <= 0:
        return None
    cell = run.cell
    least = sum(roofline.scan_least_bytes(cell.n, cell.p, b.queries,
                                          cell.traffic.k)
                for b in run.batches[: len(run.trace.batches)])
    return roofline.roofline_pct(least, seconds, run.peaks["hbm_bytes_per_s"])
