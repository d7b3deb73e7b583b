"""d2h_bytes_per_batch: the bytes of device arrays the program copied to
the host per ``knn_batch`` call, from its own counters: ``d2h.bytes``
over ``engine.batches`` in ``repro.obs.metrics.REGISTRY``. One process
is one run, and its warm-up and window calls move the same bytes per
batch. None where the program keeps no such counter (engine.batches 0)."""


def read(run):
    try:
        from repro.obs.metrics import REGISTRY
    except ImportError:
        return None
    batches = REGISTRY.value("engine.batches")
    if batches == 0:
        return None
    return REGISTRY.value("d2h.bytes") / batches
