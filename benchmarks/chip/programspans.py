"""Per-batch time of the program's own spans in a profiler trace.

The program opens a ``jax.profiler.TraceAnnotation`` for each of its
spans while a profiler records (``repro.obs.trace``), on the thread that
calls ``knn_batch``; ``tracesum.read_xspace`` keeps every event of that
thread in ``TraceSummary.host``. A parent commit without those spans
leaves them out of the trace, and its readers return None.
"""

from __future__ import annotations

from typing import Iterable, Optional

import tracesum


def ms_per_batch(summary, names: Iterable[str]) -> Optional[float]:
    """Per annotated batch, the ms in which any span named in ``names``
    was open (their union, clipped to the batch annotations); None when
    the trace holds no batch or no such span."""
    if summary is None or not summary.batches:
        return None
    names = set(names)
    events = [e for e in summary.host if e[0] in names]
    if not events:
        return None
    lo, hi = summary.window
    starts, ends = tracesum.merged(events, lo, hi)
    ns = sum(tracesum.covered(starts, ends, a, b)
             for a, b in summary.batches)
    return 1e-6 * ns / len(summary.batches)
