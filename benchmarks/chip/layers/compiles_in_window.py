"""compiles_in_window: the number of compiles that started inside a
``knn_batch`` call of the traced window: JAX's own
``backend_compile_and_load`` host events on the calling thread whose
start lies inside a batch annotation (profiler trace). 0 when the
window's batches compiled nothing."""

EVENT = "backend_compile_and_load"


def read(run):
    trace = run.trace
    if trace is None or not trace.batches:
        return None
    starts = [s for name, s, _ in trace.host if name == EVENT]
    return sum(any(a <= s < b for a, b in trace.batches) for s in starts)
