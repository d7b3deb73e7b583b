"""repro.obs: tracer semantics, metrics math, export/report, wire compat.

Covers the observability tentpole's contracts:

  - Tracer: disabled no-op path, span nesting/containment/ordering,
    sampling inheritance, bounded buffer, ingest with clock shift.
  - Metrics: Counter/Histogram math (empty window, single sample,
    window wraparound), registry reads, JSONL dump, scoped reset.
  - LatencyTracker keeps its historical snapshot shape on top of
    Histogram; EngineStats per-query counters aggregate across
    shards/hosts through the wire codec and the coordinator's fold.
  - Chrome export loads back validated; the report CLI enforces its
    host/stage floors with documented exit codes.
  - AMRP frames without the optional ``trace`` meta still parse
    (backward compatibility), and frames with it round-trip.
"""

import json
import socket
import threading
import time

import numpy as np
import pytest

from repro.obs import trace as obs_trace
from repro.obs.export import (
    chrome_trace_doc,
    load_chrome_trace,
    write_chrome_trace,
    write_metrics_jsonl,
)
from repro.obs.metrics import Counter, Histogram, MetricsRegistry
from repro.obs.report import main as report_main, summarize
from repro.obs.trace import NOOP_SPAN, Tracer


# ------------------------------------------------------------------ tracer
def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    assert tr.span("anything") is NOOP_SPAN
    with tr.span("anything", cat="x", foo=1):
        pass
    tr.record("manual", 0.0, 1.0)
    assert len(tr) == 0


def test_module_default_tracer_disabled():
    assert obs_trace.current().enabled is False


def test_set_tracer_returns_previous():
    live = Tracer(enabled=True)
    prev = obs_trace.set_tracer(live)
    try:
        assert obs_trace.current() is live
    finally:
        assert obs_trace.set_tracer(prev) is live
    assert obs_trace.current() is prev


def test_span_records_fields():
    tr = Tracer(enabled=True, host="h", trace_id="tid123")
    with tr.span("work", cat="test", n=3):
        time.sleep(0.001)
    (s,) = tr.snapshot()
    assert s["name"] == "work"
    assert s["cat"] == "test"
    assert s["host"] == "h"
    assert s["trace"] == "tid123"
    assert s["dur"] >= 1000.0          # >= 1 ms in µs
    assert s["args"]["n"] == 3
    assert isinstance(s["pid"], int) and isinstance(s["tid"], int)
    # spans are JSON-safe by construction (they cross pipes and frames)
    json.dumps(s)


def test_span_nesting_containment_and_order():
    tr = Tracer(enabled=True)
    with tr.span("outer"):
        with tr.span("inner"):
            time.sleep(0.001)
        with tr.span("inner2"):
            pass
    spans = {s["name"]: s for s in tr.snapshot()}
    assert set(spans) == {"outer", "inner", "inner2"}
    out, inn, inn2 = spans["outer"], spans["inner"], spans["inner2"]
    # interval containment: children nest inside the parent
    for child in (inn, inn2):
        assert child["ts"] >= out["ts"]
        assert child["ts"] + child["dur"] <= out["ts"] + out["dur"]
    # sibling ordering on the timeline
    assert inn["ts"] + inn["dur"] <= inn2["ts"]
    # depth args record the nesting level
    assert out["args"]["depth"] == 0
    assert inn["args"]["depth"] == 1
    # append-on-exit: children land in the buffer before their parent
    names = [s["name"] for s in tr.snapshot()]
    assert names.index("inner") < names.index("outer")


def test_span_stack_balanced_on_exception():
    tr = Tracer(enabled=True)
    with pytest.raises(RuntimeError):
        with tr.span("outer"):
            with tr.span("inner"):
                raise RuntimeError("boom")
    # both spans still recorded, and the stack is clean for the next one
    assert {s["name"] for s in tr.snapshot()} == {"outer", "inner"}
    with tr.span("after"):
        pass
    assert tr.snapshot()[-1]["args"]["depth"] == 0


def test_sampling_zero_drops_subtree_but_not_record():
    tr = Tracer(enabled=True, sample=0.0)
    for _ in range(10):
        with tr.span("top"):
            with tr.span("child"):   # inherits the sampled-out decision
                pass
    assert len(tr) == 0
    tr.record("manual", 0.0, 1.0)    # record() bypasses sampling
    assert len(tr) == 1


def test_sampling_decision_inherited_whole():
    # sample=0.5: every recorded child must come with its parent —
    # a subtree is kept or dropped as a unit, never split
    tr = Tracer(enabled=True, sample=0.5)
    tr._rng.seed(7)
    for i in range(50):
        with tr.span("top", i=i):
            with tr.span("child", i=i):
                pass
    spans = tr.snapshot()
    tops = {s["args"]["i"] for s in spans if s["name"] == "top"}
    children = {s["args"]["i"] for s in spans if s["name"] == "child"}
    assert tops == children
    assert 0 < len(tops) < 50


def test_max_spans_bounds_buffer():
    tr = Tracer(enabled=True, max_spans=3)
    for i in range(5):
        tr.record(f"s{i}", 0.0, 1.0)
    assert len(tr) == 3
    assert tr.dropped == 2


def test_ingest_shifts_and_retags():
    tr = Tracer(enabled=True, trace_id="parent")
    child = [{"name": "w", "cat": "x", "ts": 1000.0, "dur": 5.0,
              "pid": 9, "tid": 1, "host": "worker", "trace": "other"}]
    tr.ingest(child, shift_us=250.0)
    (s,) = tr.snapshot()
    assert s["ts"] == 750.0            # shifted onto the parent clock
    assert s["trace"] == "parent"      # merged under one trace id
    assert s["host"] == "worker"
    assert child[0]["ts"] == 1000.0    # caller's list untouched


def test_ingest_defaults_missing_host():
    tr = Tracer(enabled=True)
    tr.ingest([{"name": "w", "ts": 0.0, "dur": 1.0}], host="h3")
    assert tr.snapshot()[0]["host"] == "h3"


def test_drain_empties_buffer():
    tr = Tracer(enabled=True)
    tr.record("a", 0.0, 1.0)
    assert [s["name"] for s in tr.drain()] == ["a"]
    assert len(tr) == 0


def test_spans_from_threads_keep_independent_stacks():
    tr = Tracer(enabled=True)
    errors = []

    def work(tag):
        try:
            for _ in range(50):
                with tr.span(f"outer-{tag}"):
                    with tr.span(f"inner-{tag}"):
                        pass
        except Exception as e:          # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    spans = tr.snapshot()
    assert len(spans) == 200
    # every inner span is depth 1: the two threads never saw each
    # other's stack
    for s in spans:
        want = 1 if s["name"].startswith("inner") else 0
        assert s["args"]["depth"] == want


# ----------------------------------------------------------------- metrics
def test_counter_add_set():
    c = Counter()
    assert c.value == 0
    c.add()
    c.add(4)
    assert c.value == 5
    c.set(2)
    assert c.value == 2


def test_histogram_empty_window():
    assert Histogram().snapshot() == {}


def test_histogram_single_sample():
    h = Histogram()
    h.record(7.0)
    snap = h.snapshot()
    assert snap["p50"] == snap["p99"] == snap["mean"] == snap["max"] == 7.0
    assert snap["count"] == 1


def test_histogram_window_wraparound():
    h = Histogram(window=4)
    for v in range(10):                 # 0..9; window keeps 6,7,8,9
        h.record(float(v))
    snap = h.snapshot()
    assert snap["count"] == 10          # lifetime count survives the trim
    assert snap["max"] == 9.0
    assert snap["mean"] == pytest.approx((6 + 7 + 8 + 9) / 4)
    assert snap["p50"] >= 6.0           # percentiles score the window only


def test_histogram_batch_count():
    h = Histogram(window=8)
    h.record(3.0, count=5)
    snap = h.snapshot()
    assert snap["count"] == 5 and snap["p50"] == 3.0


def test_registry_reads_and_reset():
    reg = MetricsRegistry()
    reg.counter("a.x").add(2)
    reg.counter("a.y").add(1)
    reg.counter("b.z").add(9)
    reg.histogram("a.h").record(1.5)
    assert reg.value("a.x") == 2
    assert reg.value("never.touched") == 0
    assert reg.values("a.") == {"a.x": 2, "a.y": 1}
    snap = reg.snapshot()
    assert snap["b.z"] == 9 and snap["a.h"]["count"] == 1
    reg.reset("a.")
    assert reg.value("a.x") == 0
    assert reg.value("b.z") == 9        # prefix scoped the reset
    assert "a.h" not in reg.snapshot()


def test_registry_dump_jsonl(tmp_path):
    reg = MetricsRegistry()
    reg.counter("launches.verify").add(3)
    reg.histogram("lat").record(2.0)
    path = tmp_path / "metrics.jsonl"
    reg.dump_jsonl(str(path))
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    by_name = {r["metric"]: r["value"] for r in rows}
    assert by_name["launches.verify"] == 3
    assert by_name["lat"]["count"] == 1
    write_metrics_jsonl(str(tmp_path / "global.jsonl"))   # global smoke


# --------------------------------------------- latency tracker / stats agg
def test_latency_tracker_empty_window():
    from repro.pipeline.stream import LatencyTracker

    assert LatencyTracker().snapshot() == {}


def test_latency_tracker_single_sample():
    from repro.pipeline.stream import LatencyTracker

    t = LatencyTracker()
    t.record(12.5)
    snap = t.snapshot()
    assert snap["p50"] == snap["p99"] == snap["mean"] == 12.5
    assert snap["count"] == 1.0


def test_latency_tracker_window_wraparound():
    from repro.pipeline.stream import LatencyTracker

    t = LatencyTracker(window=4)
    for v in range(10):
        t.record(float(v))
    snap = t.snapshot()
    assert snap["count"] == 10.0        # lifetime, like before
    assert snap["mean"] == pytest.approx((6 + 7 + 8 + 9) / 4)
    # np.percentile interpolates inside the window (historical shape)
    assert snap["p50"] == pytest.approx(7.5)
    assert 6.0 <= snap["p99"] <= 9.0


def test_latency_tracker_is_histogram():
    from repro.pipeline.stream import LatencyTracker

    assert issubclass(LatencyTracker, Histogram)


def test_engine_stats_aggregate_across_shards_and_hosts():
    """Per-query rows travel the wire codec and fold across hosts the
    way the coordinator merges them: ints sum, max_radius maxes, bools
    or."""
    from repro.cluster.coordinator import _fold_counters
    from repro.cluster.worker import stats_from_wire, stats_to_wire
    from repro.core.amih import AMIHStats
    from repro.core.engine import EngineStats

    host_stats = []
    for h, (probes, radius, fell) in enumerate(
        [(10, 2, False), (7, 5, True)]
    ):
        st = EngineStats(
            backend="sharded_amih", queries=1,
            per_query=[AMIHStats(probes=probes, verified=3,
                                 max_radius=radius,
                                 fell_back_to_scan=fell)],
            shards=2,
            per_shard=[{"shard": h, "launches": 1}],
        )
        host_stats.append(stats_from_wire(stats_to_wire(st)))

    agg = AMIHStats()
    for st in host_stats:
        assert isinstance(st.per_query[0], AMIHStats)   # codec keeps kind
        _fold_counters(agg, st.per_query[0])
    assert agg.probes == 17
    assert agg.verified == 6
    assert agg.max_radius == 5          # max across hosts, not sum
    assert agg.fell_back_to_scan is True
    # EngineStats.aggregate applies the same rules across a batch
    combined = EngineStats(backend="x", queries=2,
                           per_query=[st.per_query[0]
                                      for st in host_stats])
    totals = combined.aggregate()
    assert totals["probes"] == 17 and totals["max_radius"] == 5


# ---------------------------------------------------------- export/report
def _spans_two_hosts():
    return [
        {"name": "engine.knn_batch", "cat": "engine", "ts": 0.0,
         "dur": 100.0, "pid": 1, "tid": 1, "host": "coordinator",
         "trace": "t1"},
        {"name": "amih.probe", "cat": "amih", "ts": 10.0, "dur": 20.0,
         "pid": 2, "tid": 1, "host": "host0", "trace": "t1"},
        {"name": "amih.verify", "cat": "amih", "ts": 30.0, "dur": 40.0,
         "pid": 2, "tid": 1, "host": "host0", "trace": "t1"},
    ]


def test_chrome_trace_doc_structure():
    doc = chrome_trace_doc(_spans_two_hosts(), trace_id="t1")
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {m["args"]["name"] for m in metas} == {"coordinator", "host0"}
    assert len(xs) == 3
    # one synthetic pid per host lane, trace id carried in args
    assert len({e["pid"] for e in xs}) == 2
    assert all(e["args"]["trace"] == "t1" for e in xs)
    assert doc["metadata"]["trace_id"] == "t1"


def test_write_load_chrome_trace_roundtrip(tmp_path):
    path = str(tmp_path / "trace.json")
    assert write_chrome_trace(_spans_two_hosts(), path) == 3
    doc = load_chrome_trace(path)
    assert len(doc["traceEvents"]) == 5   # 3 spans + 2 process_name


def test_load_chrome_trace_rejects_garbage(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text('{"notTraceEvents": 1}')
    with pytest.raises(ValueError):
        load_chrome_trace(str(bad))
    worse = tmp_path / "worse.json"
    worse.write_text('{"traceEvents": [{"ph": "X", "name": "x"}]}')
    with pytest.raises(ValueError):       # X event without ts/dur
        load_chrome_trace(str(worse))


def test_report_summarize():
    doc = chrome_trace_doc(_spans_two_hosts())
    summary = summarize(doc)
    assert summary["hosts"] == ["coordinator", "host0"]
    assert summary["wall_ms"] == pytest.approx(0.1)   # 100 µs
    st = summary["stages"]["amih.probe"]
    assert st["count"] == 1 and st["total_ms"] == pytest.approx(0.02)
    assert st["hosts"] == ["host0"]


def test_report_cli_exit_codes(tmp_path, capsys):
    path = str(tmp_path / "trace.json")
    write_chrome_trace(_spans_two_hosts(), path)
    assert report_main([path, "--min-hosts", "2", "--min-stages", "3"]) == 0
    out = capsys.readouterr().out
    assert "engine.knn_batch" in out and "% wall" in out
    # unmet floors -> 1
    assert report_main([path, "--min-hosts", "3"]) == 1
    assert report_main([path, "--min-stages", "4"]) == 1
    # unreadable/invalid file -> 2
    bad = tmp_path / "bad.json"
    bad.write_text("not json")
    assert report_main([str(bad)]) == 2
    assert report_main([str(tmp_path / "missing.json")]) == 2


# ------------------------------------------------------- engine integration
def test_make_engine_tracer_spans_observed():
    from repro.core.engine import make_engine
    from repro.core.packing import pack_bits

    rng = np.random.default_rng(0)
    db = pack_bits(rng.integers(0, 2, (300, 64), dtype=np.uint8))
    qs = pack_bits(rng.integers(0, 2, (4, 64), dtype=np.uint8))
    base = make_engine("amih", db, 64)
    ref_ids, ref_sims, _ = base.knn_batch(qs, 5)

    tracer = Tracer(enabled=True)
    prev = obs_trace.current()
    try:
        eng = make_engine("amih", db, 64, tracer=tracer)
        assert eng.tracer is tracer
        ids, sims, _ = eng.knn_batch(qs, 5)
    finally:
        obs_trace.set_tracer(prev)
    # spans observe, never reorder: bit-identical to the untraced engine
    np.testing.assert_array_equal(ids, ref_ids)
    np.testing.assert_array_equal(sims, ref_sims)
    names = {s["name"] for s in tracer.snapshot()}
    assert "engine.knn_batch" in names
    assert {"amih.probe", "amih.emit"} <= names


# ------------------------------------------------------------ wire compat
def _frame_roundtrip(kind, meta, arrays=None):
    from repro.cluster.transport import recv_frame, send_frame

    a, b = socket.socketpair()
    try:
        send_frame(a, kind, meta, arrays)
        return recv_frame(b)
    finally:
        a.close()
        b.close()


def test_frames_without_trace_meta_still_parse():
    """Backward compatibility: the optional ``trace`` field is absent
    from old coordinators' search frames and old workers' results."""
    kind, meta, arrays = _frame_roundtrip(
        "search", {"req": 1, "k": 5},
        {"q": np.arange(4, dtype=np.uint64).reshape(2, 2),
         "floor": np.zeros(2)},
    )
    assert kind == "search"
    assert meta["req"] == 1 and "trace" not in meta
    assert arrays["q"].shape == (2, 2)


def test_frames_with_trace_meta_roundtrip():
    trace = {"id": "abc123", "host": "host1"}
    spans = [{"name": "amih.probe", "cat": "amih", "ts": 1.0, "dur": 2.0,
              "pid": 5, "tid": 6, "host": "host1", "trace": "abc123"}]
    kind, meta, _ = _frame_roundtrip(
        "search", {"req": 2, "k": 3, "trace": trace}, {"q": np.zeros(1)}
    )
    assert meta["trace"] == trace
    kind, meta, _ = _frame_roundtrip(
        "result", {"req": 2, "stats": {}, "spans": spans},
        {"ids": np.zeros(1, np.int64), "sims": np.zeros(1),
         "lens": np.ones(1, np.int64)},
    )
    assert meta["spans"] == spans
    kind, meta, _ = _frame_roundtrip("pong", {"seq": 7, "ts": 123.5})
    assert meta["ts"] == 123.5
