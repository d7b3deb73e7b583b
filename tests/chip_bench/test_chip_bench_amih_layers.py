"""The AMIH cells' readers, checked on a small profiler trace recorded on
the CPU around the device-walk engine, and on counters the test sets:
each reads its number where the program wrote it, and falls silent
where a parent commit without the walk's spans, programs or counters
wrote none."""

from __future__ import annotations

import pytest

from chip_bench_util import BENCH, harness, tiny_cell

import tracesum  # noqa: E402  (on the path through chip_bench_util)

B, N, BATCHES = 8, 2048, 3
HBM = 819e9


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """(run at K = 100, run at K = 1): three annotated knn_batch calls
    each, after a warm one, over clustered codes; K = 100 bails part of
    each batch to the scan fallback, K = 1 none."""
    import jax.profiler as jp

    import corpus
    from repro.core import make_engine
    from repro.obs.metrics import REGISTRY

    db = corpus.clustered_codes(5, N, 64, n_clusters=16, flip_prob=0.08)
    q = corpus.near_queries(5, db, 64, B, flip_prob=0.05)
    runs = []
    for k in (100, 1):
        eng = make_engine("amih", db, 64, m=3, probe_backend="device",
                          query_cache_size=0)
        for prefix in ("amih.", "engine."):
            REGISTRY.reset(prefix)
        eng.knn_batch(q, k)                   # builds and compiles
        log_dir = tmp_path_factory.mktemp(f"amih_trace_k{k}")
        opts = jp.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jp.start_trace(str(log_dir), profiler_options=opts)
        try:
            for _ in range(BATCHES):
                with jp.TraceAnnotation(tracesum.BATCH_ANNOTATION):
                    eng.knn_batch(q, k)
        finally:
            jp.stop_trace()
        summary = tracesum.read_xspace(tracesum.find_xspace(log_dir))
        cell = tiny_cell("amih64.k100", n=N)
        counters = {name: REGISTRY.value(name) for name in (
            "amih.queries", "amih.bailed_queries", "amih.retrieved",
            "amih.verified", "engine.batches")}
        runs.append((harness.Run(cell=cell, setup_s=0.0, window_s=0.0,
                                 batches=[], peaks={"hbm_bytes_per_s": HBM},
                                 trace=summary), counters))
    return runs


def _read(metric, run):
    return harness.load_module(BENCH / "layers" / f"{metric}.py").read(run)


def _as_parent(run):
    """The same window as a commit without this walk records it: the
    batches and JAX's events, none of the walk's spans or programs (its
    fallback was a program of the walk's own, not ``scan_topk``)."""
    s = run.trace
    bare = tracesum.TraceSummary(
        ops=s.ops,
        modules={d: [e for e in evs if "device_probe" not in e[0]
                     and "scan_topk" not in e[0]]
                 for d, evs in s.modules.items()},
        batches=s.batches,
        host=[e for e in s.host
              if not e[0].startswith(("amih.", "launch.", "scan.",
                                      "engine."))])
    return harness.Run(cell=run.cell, setup_s=0.0, window_s=0.0,
                       batches=[], peaks=run.peaks, trace=bare)


def _counters(monkeypatch, values):
    """Stand the registry's counters in for the program's."""
    from repro.obs import metrics

    reg = metrics.MetricsRegistry()
    for name, v in values.items():
        reg.counter(name).add(v)
    monkeypatch.setattr(metrics, "REGISTRY", reg)


@pytest.mark.parametrize("metric", ["walk_ms", "fallback_ms"])
def test_program_time_readers(metric, recorded):
    (bailing, _), (walking, _) = recorded
    lengths = [b - a for a, b in bailing.trace.batches]
    value = _read(metric, bailing)
    assert 0 < value * 1e6 < max(lengths)
    if metric == "fallback_ms":
        # no batch bailed: the fallback took no device time
        assert _read(metric, walking) == 0.0
    else:
        assert _read(metric, walking) > 0
    assert _read(metric, _as_parent(bailing)) is None
    no_trace = harness.Run(cell=bailing.cell, setup_s=0.0, window_s=0.0,
                           batches=[], peaks={}, trace=None)
    assert _read(metric, no_trace) is None


@pytest.mark.parametrize("metric", ["amih_prep_ms", "amih_extract_ms"])
def test_host_span_readers(metric, recorded):
    for run, _ in recorded:
        lengths = [b - a for a, b in run.trace.batches]
        assert 0 < _read(metric, run) * 1e6 < max(lengths)
        assert _read(metric, _as_parent(run)) is None


def test_bailed_pct(recorded, monkeypatch):
    (_, counters), _ = recorded
    _counters(monkeypatch, counters)
    run = recorded[0][0]
    want = 100.0 * counters["amih.bailed_queries"] / counters["amih.queries"]
    assert 0 < _read("bailed_pct", run) == pytest.approx(want)
    _counters(monkeypatch, {"amih.queries": 64, "amih.bailed_queries": 16})
    assert _read("bailed_pct", run) == pytest.approx(25.0)
    _counters(monkeypatch, {"engine.batches": 3})     # a parent's counters
    assert _read("bailed_pct", run) is None


def test_walk_roofline(recorded, monkeypatch):
    import walkbytes

    run, _ = recorded[0]
    _counters(monkeypatch, {"engine.batches": 4, "amih.retrieved": 4000,
                            "amih.verified": 1000})
    walk_s = 1e-9 * sum(tracesum.program_ns(
        run.trace, "device_probe_walk_batched").values())
    per_batch = walk_s / len(run.trace.batches)
    least = (4 * 4000 + 8 * 1000) / 4 + 64 * 100 * 8
    assert walkbytes.walk_bytes_per_batch(4000, 1000, 4, 64, 64, 100) \
        == least
    got = _read("walk_roofline", run)
    assert got == pytest.approx(100.0 * least / HBM / per_batch)
    assert 0 < got < 100
    # off the chip there are no peaks; a parent has no walk or counters
    off_chip = harness.Run(cell=run.cell, setup_s=0.0, window_s=0.0,
                           batches=[], peaks={}, trace=run.trace)
    assert _read("walk_roofline", off_chip) is None
    assert _read("walk_roofline", _as_parent(run)) is None
    _counters(monkeypatch, {"engine.batches": 4})
    assert _read("walk_roofline", run) is None
