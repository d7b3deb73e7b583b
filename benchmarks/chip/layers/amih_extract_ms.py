"""amih_extract_ms: per batch, the ms the AMIH engine's host spent
reading each query's answer out of the walk's (position, id) pairs and
filling its stats (its ``amih.extract`` spans inside the batch
annotations; profiler trace)."""

import programspans

SPANS = ("amih.extract",)


def read(run):
    return programspans.ms_per_batch(run.trace, SPANS)
