"""amih_prep_ms: per batch, the ms the AMIH engine's host spent before
the walk's launch: schedule rows, substrings, flip tables and stop
positions (its ``amih.prep`` spans inside the batch annotations;
profiler trace)."""

import programspans

SPANS = ("amih.prep",)


def read(run):
    return programspans.ms_per_batch(run.trace, SPANS)
