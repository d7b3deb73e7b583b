"""The program's spans on the JAX profiler's clock, and the counters
beside them (``d2h.bytes``, ``engine.batches``, ``launches.scan_topk``
and ``launches.scan_topk.two_stage``).

A profiler trace is recorded on the CPU around annotated ``knn_batch``
calls and read with the chip benchmark's own reduction
(``benchmarks/chip/tracesum.py``), so the tests check what the
benchmark's readers will find on the chip.
"""

import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np

from repro.core.engine import make_engine
from repro.kernels import ops
from repro.obs import trace as obs_trace
from repro.obs.metrics import REGISTRY
from repro.obs.trace import NOOP_SPAN, Tracer

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

import tracesum  # noqa: E402

SCAN_SPANS = ("scan.prep", "scan.dispatch", "scan.fetch", "scan.rescore")


def _codes(n, B, seed=0):
    rng = np.random.default_rng(seed)
    db = rng.integers(0, 2**32, (n, 2), dtype=np.uint64).astype(np.uint32)
    return db, db[rng.choice(n, B, replace=False)].copy()


def _traced(fn, log_dir, batches=1):
    """Run ``fn`` ``batches`` times, each inside the benchmark's batch
    annotation, under a profiler session; returns (results, summary)."""
    import jax.profiler as jp

    opts = jp.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    out = []
    jp.start_trace(str(log_dir), profiler_options=opts)
    try:
        for _ in range(batches):
            with jp.TraceAnnotation(tracesum.BATCH_ANNOTATION):
                out.append(fn())
    finally:
        jp.stop_trace()
    return out, tracesum.read_xspace(tracesum.find_xspace(log_dir))


def _inside_batches(summary, name):
    evs = [(s, t) for n, s, t in summary.host if n == name]
    return evs and all(any(a <= s and t <= b for a, b in summary.batches)
                       for s, t in evs)


def test_scan_spans_reach_the_profiler_trace(tmp_path):
    db, q = _codes(2048, 6)
    eng = make_engine("linear_scan", db, 64, compute_backend="pallas")
    assert obs_trace.current().enabled is False     # the obs tracer is off
    (res,), summary = _traced(lambda: eng.knn_batch(q, 9), tmp_path)
    assert len(summary.batches) == 1
    for name in SCAN_SPANS + ("scan.upload_codes", "engine.knn_batch"):
        assert _inside_batches(summary, name), name
    # in order on the caller's thread: prep, dispatch, fetch, rescore
    first = {n: s for n, s, _ in reversed(summary.host) if n in SCAN_SPANS}
    assert sorted(first, key=first.get) == list(SCAN_SPANS)


def test_span_is_noop_without_profiler_and_results_match(tmp_path):
    assert obs_trace.current().span("scan.prep", B=1) is NOOP_SPAN
    assert Tracer(enabled=False).span("x") is NOOP_SPAN
    db, q = _codes(1024, 5, seed=1)
    eng = make_engine("linear_scan", db, 64, compute_backend="pallas")
    ids, sims, _ = eng.knn_batch(q, 7)
    (traced,), _ = _traced(lambda: eng.knn_batch(q, 7), tmp_path)
    np.testing.assert_array_equal(traced[0], ids)
    np.testing.assert_array_equal(traced[1], sims)
    ref = make_engine("linear_scan", db, 64).knn_batch(q, 7)
    np.testing.assert_array_equal(ref[0], ids)
    np.testing.assert_array_equal(ref[1], sims)


def test_enabled_tracer_records_and_annotates(tmp_path):
    """With the obs tracer on, a span goes into its buffer and into the
    profiler trace; a sampled-out span still reaches the profiler."""
    kept, dropped = Tracer(enabled=True), Tracer(enabled=True, sample=0.0)

    def both():
        with kept.span("kept.span", n=1):
            pass
        with dropped.span("dropped.span"):
            pass

    _, summary = _traced(both, tmp_path)
    assert [s["name"] for s in kept.snapshot()] == ["kept.span"]
    assert len(dropped) == 0
    names = {n for n, _, _ in summary.host}
    assert {"kept.span", "dropped.span"} <= names


def test_scan_d2h_bytes_and_launches_per_batch():
    db, q = _codes(4096, 5, seed=2)
    k = 7
    eng = make_engine("linear_scan", db, 64, compute_backend="pallas")
    names = ("d2h.bytes", "engine.batches", "launches.scan_topk")
    before = {n: REGISTRY.value(n) for n in names}
    for _ in range(3):
        eng.knn_batch(q, k)
    delta = {n: REGISTRY.value(n) - before[n] for n in names}
    b_pad = ops.pad_bucket(5, minimum=8)
    k_fetch = min(4096, ops.pad_bucket(k + eng._topk_slack, minimum=8))
    assert delta["engine.batches"] == 3
    assert delta["launches.scan_topk"] == 3
    assert delta["d2h.bytes"] == 3 * b_pad * k_fetch * 4


def test_two_stage_merge_counted_per_launch_and_on_the_dispatch_span():
    n, k = 4096, 7
    db, q = _codes(n, 5, seed=4)
    names = ("launches.scan_topk", "launches.scan_topk.two_stage")

    def launches(eng, batches):
        before = {c: REGISTRY.value(c) for c in names}
        prev = obs_trace.set_tracer(Tracer(enabled=True))
        try:
            for _ in range(batches):
                eng.knn_batch(q, k)
            spans = obs_trace.current().snapshot()
        finally:
            obs_trace.set_tracer(prev)
        return [REGISTRY.value(c) - before[c] for c in names], spans

    # the device scan at its default block: the two-stage merge
    eng = make_engine("linear_scan", db, 64, compute_backend="pallas")
    k_fetch = min(n, ops.pad_bucket(k + eng._topk_slack, minimum=8))
    group = ops.topk_group_width(n, k_fetch)
    assert group > 0
    counts, spans = launches(eng, 3)
    assert counts == [3, 3]
    dispatch = [s["args"] for s in spans if s["name"] == "scan.dispatch"]
    assert [(a["k"], a["group"]) for a in dispatch] == [(k_fetch, group)] * 3

    # blocks of 64 codes leave too few groups: the direct merge
    eng = make_engine("sharded_scan", db, 64, num_shards=2, chunk=64)
    counts, _ = launches(eng, 2)
    assert counts == [4, 0]


def test_every_engine_counts_its_batches():
    db, q = _codes(600, 3, seed=3)
    for backend in ("linear_scan", "single_table", "amih"):
        eng = make_engine(backend, db, 64)
        before = REGISTRY.value("engine.batches")
        eng.knn_batch(q, 4)
        eng.knn_batch(q[0], 4)
        assert REGISTRY.value("engine.batches") - before == 2, backend


def test_obs_stays_importable_without_jax():
    code = textwrap.dedent("""
        import sys
        sys.path.insert(0, "src")
        from repro.obs import trace
        with trace.current().span("x", n=1):
            pass
        with trace.enable().span("y"):
            pass
        assert "jax" not in sys.modules
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=ROOT, timeout=120)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr


def test_sharded_amih_device_walk_spans(tmp_path):
    """On 4 virtual CPU devices, a fused device walk with a schedule too
    short to finish (so the scan fallback runs) shows every AMIH span and
    the shard merge on the caller's thread, and counts its D2H bytes."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, "src")
        sys.path.insert(0, "benchmarks/chip")
        import jax
        import jax.profiler as jp
        import numpy as np
        import tracesum
        from repro.core import linear_scan_knn, make_engine, pack_bits
        from repro.data import synthetic_binary_codes, synthetic_queries
        from repro.obs.metrics import REGISTRY

        assert len(jax.devices()) == 4
        p, n, B, k = 64, 2000, 8, 5
        bits = synthetic_binary_codes(n, p, seed=7)
        db = pack_bits(bits)
        qs = pack_bits(synthetic_queries(bits, B, seed=8))
        eng = make_engine("sharded_amih", db, p, num_shards=4,
                          probe_backend="device", probe_stream_cap=64)
        eng.knn_batch(qs, k)                       # compiles
        d2h0 = REGISTRY.value("d2h.bytes")
        jp.start_trace({str(tmp_path)!r})
        with jp.TraceAnnotation(tracesum.BATCH_ANNOTATION):
            ids, sims, st = eng.knn_batch(qs, k)
        jp.stop_trace()
        assert REGISTRY.value("d2h.bytes") > d2h0
        assert sum(d["fell_back_to_scan"] for d in st.per_shard) > 0
        for i in range(B):
            _, sims_l = linear_scan_knn(qs[i], db, k)
            np.testing.assert_array_equal(sims[i], sims_l)
        s = tracesum.read_xspace(tracesum.find_xspace({str(tmp_path)!r}))
        (lo, hi), = s.batches
        names = {{name for name, a, b in s.host if lo <= a and b <= hi}}
        print(sorted(names))
        want = {{"amih.prep", "launch.device_probe.dispatch",
                 "launch.device_probe.resolve", "amih.fallback",
                 "amih.extract", "shard.merge"}}
        assert want <= names, want - names
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=ROOT, timeout=560)
    assert out.returncode == 0 and "OK" in out.stdout, \
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"

