"""bailed_pct: the share of queries, in %, that the fused AMIH walk gave
up on (stream or iteration budget spent) and left to the scan fallback,
from the program's own counters: 100 ``amih.bailed_queries`` /
``amih.queries`` in ``repro.obs.metrics.REGISTRY`` over the process (its
warm-up answers each pool batch once, the window in turn). None where
the program keeps no such counters."""


def read(run):
    try:
        from repro.obs.metrics import REGISTRY
    except ImportError:
        return None
    queries = REGISTRY.value("amih.queries")
    if queries == 0:
        return None
    return 100.0 * REGISTRY.value("amih.bailed_queries") / queries
