"""walk_roofline: the fused AMIH walk's share of the HBM roofline, in %:
the bytes of its own candidates per batch (``walkbytes``: ids pulled,
distinct codes verified, results written, from the program's counters)
at the chip's peak HBM bandwidth, over the walk program's device time
per batch (profiler trace). None where the program keeps no such
counters or the trace holds no walk."""

import roofline
import tracesum
import walkbytes

PROGRAM = "device_probe_walk_batched"


def read(run):
    if run.trace is None or not run.trace.batches or not run.peaks:
        return None
    try:
        from repro.obs.metrics import REGISTRY
    except ImportError:
        return None
    batches = REGISTRY.value("engine.batches")
    retrieved = REGISTRY.value("amih.retrieved")
    if batches == 0 or retrieved == 0:
        return None
    ns = sum(tracesum.program_ns(run.trace, PROGRAM).values())
    if ns <= 0:
        return None
    seconds = 1e-9 * ns / len(run.trace.batches)
    cell = run.cell
    least = walkbytes.walk_bytes_per_batch(
        retrieved, REGISTRY.value("amih.verified"), batches, cell.p,
        cell.traffic.batch, cell.traffic.k)
    return roofline.roofline_pct(least, seconds, run.peaks["hbm_bytes_per_s"])
