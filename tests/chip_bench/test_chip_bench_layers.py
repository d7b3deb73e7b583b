"""The readers of the program's own spans and counters, checked on a
small profiler trace recorded on the CPU around the scan engine: each
reads its number where the program wrote it, and falls silent (or reads
0) where a parent commit without those spans or counters wrote none."""

from __future__ import annotations

import pytest

from chip_bench_util import BENCH, harness, tiny_cell

import tracesum  # noqa: E402  (on the path through chip_bench_util)

B, K, N, BATCHES = 6, 9, 2048, 3


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """(run, expected d2h bytes per batch): three annotated knn_batch
    calls after a warm one, the last one with a fresh program compiled
    inside its annotation."""
    import jax
    import jax.numpy as jnp
    import jax.profiler as jp
    import numpy as np

    from repro.core import make_engine
    from repro.kernels import ops
    from repro.obs.metrics import REGISTRY

    rng = np.random.default_rng(3)
    db = rng.integers(0, 2**32, (N, 2), dtype=np.uint64).astype(np.uint32)
    q = db[:B].copy()
    eng = make_engine("linear_scan", db, 64, compute_backend="pallas")
    REGISTRY.reset("d2h.")
    REGISTRY.reset("engine.")
    eng.knn_batch(q, K)                       # uploads and compiles
    fresh = jax.jit(lambda x: x * 3 + 1)
    x = jnp.ones(7)
    log_dir = tmp_path_factory.mktemp("layers_trace")
    opts = jp.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jp.start_trace(str(log_dir), profiler_options=opts)
    try:
        for i in range(BATCHES):
            with jp.TraceAnnotation(tracesum.BATCH_ANNOTATION):
                eng.knn_batch(q, K)
                if i == BATCHES - 1:          # one compile in the window
                    fresh(x).block_until_ready()
    finally:
        jp.stop_trace()
    summary = tracesum.read_xspace(tracesum.find_xspace(log_dir))
    run = harness.Run(cell=tiny_cell("scan64.k100"), setup_s=0.0,
                      window_s=0.0, batches=[], peaks={}, trace=summary)
    k_fetch = min(N, ops.pad_bucket(K + eng._topk_slack, minimum=8))
    return run, ops.pad_bucket(B, minimum=8) * k_fetch * 4


def _read(metric, run):
    return harness.load_module(BENCH / "layers" / f"{metric}.py").read(run)


def _without_program(run):
    """The same window as a parent commit records it: the batches and
    JAX's events, none of the program's spans."""
    s = run.trace
    bare = tracesum.TraceSummary(
        ops=s.ops, modules=s.modules, batches=s.batches,
        host=[e for e in s.host if not e[0].startswith(("scan.", "engine."))
              and e[0] != "backend_compile_and_load"])
    return harness.Run(cell=run.cell, setup_s=0.0, window_s=0.0,
                       batches=[], peaks={}, trace=bare)


@pytest.mark.parametrize("metric", [
    "scan_prep_ms", "fetch_wait_ms", "rescore_ms", "d2h_bytes_per_batch",
    "compiles_in_window",
])
def test_program_reader(metric, recorded, monkeypatch):
    run, d2h_per_batch = recorded
    value = _read(metric, run)
    silent = _read(metric, _without_program(run))
    if metric == "d2h_bytes_per_batch":
        assert value == d2h_per_batch
        from repro.obs import metrics

        # a program that counts no batches (the control, a parent)
        monkeypatch.setattr(metrics, "REGISTRY", metrics.MetricsRegistry())
        assert _read(metric, run) is None
    elif metric == "compiles_in_window":
        assert value == 1
        assert silent == 0
    else:
        lengths = [b - a for a, b in run.trace.batches]
        assert 0 < value * 1e6 < max(lengths)
        assert silent is None
    no_trace = harness.Run(cell=run.cell, setup_s=0.0, window_s=0.0,
                           batches=[], peaks={}, trace=None)
    if metric != "d2h_bytes_per_batch":
        assert _read(metric, no_trace) is None


def test_span_time_is_clipped_to_the_batches():
    import programspans

    s = tracesum.TraceSummary(
        batches=[(0, 100), (200, 300)],
        host=[("scan.prep", -50, 10), ("scan.dispatch", 5, 20),
              ("scan.prep", 150, 250), ("other", 0, 300)])
    # union inside the batches: [0, 20) and [200, 250) -> 70 ns / 2
    assert programspans.ms_per_batch(s, ["scan.prep", "scan.dispatch"]) \
        == pytest.approx(35e-6)
    assert programspans.ms_per_batch(s, ["scan.fetch"]) is None
    assert programspans.ms_per_batch(tracesum.TraceSummary(), ["x"]) is None
