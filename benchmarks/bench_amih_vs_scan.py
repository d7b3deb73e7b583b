"""Paper Fig. 5 + Table 2 through the unified SearchEngine: AMIH vs
linear scan, with a batch-size axis (the serving shape).

For every (p, n, K, batch) cell the same workload is timed three ways:

  - engine "amih", batched ``knn_batch`` (probing-sequence sharing),
  - the seed-style single-query loop (``AMIHIndex.knn`` per query), and
  - engine "linear_scan" (batched exhaustive baseline).

The paper sweeps SIFT-1B/TRC2 up to 10^9 items on a 256 GB machine; this
container sweeps synthetic AQBC-like clustered codes (env
REPRO_BENCH_MAX_N / --max-n override the ceiling) and validates the
paper's *claims*: query time growing ~sqrt(n) for AMIH vs linearly for
scan, speedups growing with n into orders of magnitude, and batched
probing amortizing the per-query overhead.

A ``--shards`` axis times the pod-scale backends ("sharded_scan" /
"sharded_amih", repro.shard) over host-mode ShardPlan layouts at each
shard count (default 1 vs 8), so the perf trajectory covers the sharded
cells too.

A ``--probe-backend`` axis times every amih / sharded_amih cell under
both probing walks — "host" (the reference Python walk) and "device"
(the fused batch walk, ONE launch per knn_batch call with every z-group
stacked in; repro.core.probe_device) — and each row records which one
answered it, so scripts/bench_check.py gates host-vs-host and
device-vs-device separately. Device rows also record the launch economy
(walk/scan launches per sweep, ``launches_per_batch``), which
bench_check gates against the committed baseline.

Emits artifacts/bench/amih_vs_scan.csv plus a machine-readable
BENCH_engine.json at the repo root (per-backend, per-batch-size,
per-shard-count latency/probes/verifications) so future PRs have a perf
trajectory.

Run:  PYTHONPATH=src python benchmarks/bench_amih_vs_scan.py --batch 64
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if __package__ in (None, ""):  # run as a script: fix up both import roots
    sys.path.insert(0, _HERE)
    sys.path.insert(0, os.path.join(_ROOT, "src"))
    from common import make_db, make_queries, write_csv
else:
    from .common import make_db, make_queries, write_csv

from repro.core import make_engine
from repro.core.probing import probing_cache_clear

BENCH_JSON = os.path.join(_ROOT, "BENCH_engine.json")


REPEATS = 3  # best-of; host timing at sub-ms/query is noisy, and a
             # single transient (GC, scheduler) can poison a 2-sample min


@contextlib.contextmanager
def _gc_paused():
    """Collect outside the timed region, then keep the collector off
    inside it. A long sweep keeps every engine/db/jit cache alive, so a
    gen-2 collection grows to tens of ms — and a cell timed as ONE
    fused-launch call per sweep can't dodge a pause by best-of-REPEATS
    the way a many-small-calls cell does. Timing with the collector
    paused measures the algorithm for both shapes alike."""
    gc.collect()
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was_enabled:
            gc.enable()


def _verify_launches(engine) -> int:
    """Grouped-verify dispatches so far: the single index's counter, or
    the per-shard sum for the sharded AMIH backend."""
    index = getattr(engine, "index", None)
    if index is not None:
        return getattr(index, "verify_launches", 0)
    return sum(
        ix.verify_launches for _, ix in getattr(engine, "indexes", [])
    )


def _probe_launch_counts():
    """(walk, scan fallback) device probe launch counters so far, read
    from the metrics registry (repro.obs.metrics — the counters exist as
    0 even before jax/the device probe path was ever imported). The AMIH
    engines run ``scan_topk`` only as the walk's fallback."""
    from repro.obs.metrics import REGISTRY

    return (REGISTRY.value("launches.device_probe"),
            REGISTRY.value("launches.scan_topk"))


def _time_batched(engine, qs, k, batch):
    """Best-of-REPEATS wall seconds + aggregated stats for all queries,
    batch at a time (first repeat warms caches, as serving would).
    ``verify_launches`` and the walk/scan probe-launch counters are
    per-sweep (one pass over all queries); ``launches_per_batch`` is the
    launch-economy number bench_check gates on — fused probing keeps it
    O(1) per knn_batch call no matter how many z-groups a batch mixes."""
    best, totals = float("inf"), {}
    cache_info = {}
    launches0 = _verify_launches(engine)
    walk0, scan0 = _probe_launch_counts()
    for _ in range(REPEATS):
        with _gc_paused():
            t0 = time.perf_counter()
            totals = {"probes": 0, "verified": 0, "fell_back_to_scan": 0}
            for lo in range(0, len(qs), batch):
                _, _, stats = engine.knn_batch(qs[lo : lo + batch], k)
                agg = stats.aggregate()
                for key in totals:
                    totals[key] += agg.get(key, 0)
                cache_info = getattr(stats, "cache_info", {}) or cache_info
            best = min(best, time.perf_counter() - t0)
    launches = _verify_launches(engine) - launches0
    walk1, scan1 = _probe_launch_counts()
    totals["verify_launches"] = launches // REPEATS
    totals["walk_launches"] = (walk1 - walk0) // REPEATS
    totals["scan_launches"] = (scan1 - scan0) // REPEATS
    calls = max(1, -(-len(qs) // batch))   # knn_batch calls per sweep
    totals["launches_per_batch"] = round(
        totals["walk_launches"] / calls, 4
    )
    totals["cache_info"] = cache_info
    return best, totals


def _capture_trace(engine, qs, k, out_path):
    """One traced repetition OUTSIDE the timed reps: the timed sweeps run
    with tracing disabled (a span site costs one attribute check), then
    this single extra call records every span layer and writes a
    Perfetto-loadable Chrome trace — validated by reading it back."""
    from repro.obs import trace as _obs
    from repro.obs.export import load_chrome_trace, write_chrome_trace

    tracer = _obs.Tracer(enabled=True, host="bench")
    prev = _obs.set_tracer(tracer)
    try:
        engine.knn_batch(qs, k)
    finally:
        _obs.set_tracer(prev)
    n_spans = write_chrome_trace(tracer, out_path)
    load_chrome_trace(out_path)   # raises unless Perfetto-loadable
    print(f"wrote {out_path} ({n_spans} spans, traced rep untimed)")


def _time_seed_loop(index, qs, k):
    """The pre-engine shape: one AMIHIndex.knn call per query, with the
    probing sequence re-enumerated every call (clearing the cache matches
    the seed implementation, which had no cross-query reuse)."""
    best = float("inf")
    for _ in range(REPEATS):
        with _gc_paused():
            t0 = time.perf_counter()
            for q in qs:
                probing_cache_clear()
                index.knn(q, k)
            best = min(best, time.perf_counter() - t0)
    return best


def run(max_n: int | None = None, nq: int = 64, batches=(1, 8, 64),
        ps=(64, 128), ks=(1, 10, 100), out_json: str | None = None,
        sizes=None, csv_name: str = "amih_vs_scan.csv",
        shards=(1, 8), probe_backends=("host", "device"),
        trace_out: str | None = None):
    max_n = max_n or int(os.environ.get("REPRO_BENCH_MAX_N", 1_000_000))
    if sizes is None:
        sizes = [n for n in (10_000, 100_000, 1_000_000, 10_000_000)
                 if n <= max_n]
    else:  # explicit sizes (bench_check retries a narrowed workload)
        sizes = [n for n in sizes if n <= max_n]
    rows = []

    def emit(backend, p, n, K, batch, n_shards, t, totals, *,
             m_tables=0, t_seed=None, t_scan=None, t_build=0.0,
             devices=None, probe_backend="host"):
        t_ref = t_scan if t_scan is not None else t
        rows.append({
            "backend": backend, "p": p, "n": n, "K": K,
            "batch": batch, "shards": n_shards, "queries": nq,
            "m_tables": m_tables,
            # which probing walk answered the cell: "host" (reference
            # Python walk) or "device" (fused batch walk, one launch per
            # knn_batch call). bench_check keys cells on it, so the two
            # backends gate against their own baselines.
            "probe_backend": probe_backend,
            # distinct placement devices the shards landed on (sharded
            # backends; 1 on a single-device host). bench_check excludes
            # a cell from the gate when this changed between runs.
            "devices": devices,
            "total_s": round(t, 6),
            "ms_per_query": round(1e3 * t / nq, 4),
            "qps": round(nq / max(t, 1e-9), 2),
            "probes": totals.get("probes", 0),
            "verified": totals.get("verified", 0),
            "verify_launches": totals.get("verify_launches", 0),
            # launch economy (device probe path; 0 on host cells): walk /
            # scan-fallback dispatches per sweep and the per-knn_batch
            # walk-launch rate bench_check gates on — O(1) per batch with
            # fused probing, O(z-groups) without
            "walk_launches": totals.get("walk_launches", 0),
            "scan_launches": totals.get("scan_launches", 0),
            "launches_per_batch": totals.get("launches_per_batch", 0),
            # shared-cache effectiveness after the sweep (S1): probing
            # sequence + device schedule hit/miss lifetime counters
            "probing_hits": totals.get("cache_info", {}).get(
                "probing_hits", 0),
            "probing_misses": totals.get("cache_info", {}).get(
                "probing_misses", 0),
            "schedule_hits": totals.get("cache_info", {}).get(
                "schedule_hits", 0),
            "schedule_misses": totals.get("cache_info", {}).get(
                "schedule_misses", 0),
            "fell_back_to_scan": totals.get("fell_back_to_scan", 0),
            "seed_loop_ms_per_query":
                "" if t_seed is None else round(1e3 * t_seed / nq, 4),
            "speedup_vs_seed_loop":
                "" if t_seed is None
                else round(t_seed / max(t, 1e-9), 3),
            "scan_ms_per_query": round(1e3 * t_ref / nq, 4),
            "speedup_vs_scan": round(t_ref / max(t, 1e-9), 2),
            "index_build_s": round(t_build, 3),
        })
        return rows[-1]

    for p in ps:
        for n in sizes:
            db_bits, db = make_db(n, p, seed=0)
            _, qs = make_queries(db_bits, nq, seed=1)
            # query_cache_size=0: the bench measures probing, and its
            # repeated sweeps over one query set would otherwise time the
            # hot-query LRU instead of the algorithm.
            engines, builds = {}, {}
            for pb in probe_backends:
                t_build0 = time.perf_counter()
                engines[pb] = make_engine(
                    "amih", db, p, query_cache_size=0, probe_backend=pb
                )
                builds[pb] = time.perf_counter() - t_build0
            scan = make_engine("linear_scan", db, p)
            ref = engines.get("host", engines[probe_backends[0]])
            if trace_out is not None:
                # once, on the first (smallest) cell — the trace shows
                # the span taxonomy, not the perf numbers
                _capture_trace(ref, qs[: min(len(qs), 8)], ks[0],
                               trace_out)
                trace_out = None
            for K in ks:
                t_seed = _time_seed_loop(ref.index, qs, K)
                t_scan, _ = _time_batched(scan, qs, K, max(batches))
                for pb in probe_backends:
                    for batch in batches:
                        t_amih, totals = _time_batched(
                            engines[pb], qs, K, batch
                        )
                        r = emit("amih", p, n, K, batch, 1, t_amih,
                                 totals, m_tables=ref.index.m,
                                 t_seed=t_seed, t_scan=t_scan,
                                 t_build=builds[pb], probe_backend=pb)
                        print(
                            f"p={p} n={n:>9} K={K:>3} B={batch:>3} "
                            f"amih[{pb}]={r['ms_per_query']:.3f}ms/q "
                            f"seed_loop={r['seed_loop_ms_per_query']:.3f}"
                            f"ms/q scan={r['scan_ms_per_query']:.3f}ms/q "
                            f"({r['speedup_vs_scan']}x)"
                        )
                emit("linear_scan", p, n, K, max(batches), 1, t_scan,
                     {"verified": n * nq}, t_scan=t_scan)
            # sharded cells: the pod-scale backends over S host shards
            # (S=1 is the degenerate single-shard layout; the multi-device
            # mesh path is exercised by tests/test_shard.py)
            for S in shards:
                if S > n:
                    continue
                sh_scan = make_engine("sharded_scan", db, p, num_shards=S)
                sh_amihs = {
                    pb: make_engine("sharded_amih", db, p, num_shards=S,
                                    probe_backend=pb)
                    for pb in probe_backends
                }
                any_sh = next(iter(sh_amihs.values()))
                n_dev = len({str(d) for d in any_sh.plan.devices}) or 1
                for K in ks:
                    t_s, tot_s = _time_batched(sh_scan, qs, K, max(batches))
                    emit("sharded_scan", p, n, K, max(batches), S, t_s,
                         tot_s, devices=n_dev)
                    for pb in probe_backends:
                        t_a, tot_a = _time_batched(
                            sh_amihs[pb], qs, K, max(batches)
                        )
                        r = emit("sharded_amih", p, n, K, max(batches), S,
                                 t_a, tot_a, devices=n_dev,
                                 probe_backend=pb)
                        print(
                            f"p={p} n={n:>9} K={K:>3} S={S:>2} "
                            f"sharded_amih[{pb}]="
                            f"{r['ms_per_query']:.3f}ms/q "
                            f"sharded_scan={1e3 * t_s / nq:.3f}ms/q"
                        )
    path = write_csv(csv_name, rows)
    payload = {
        "bench": "engine",
        "workload": {
            "sizes": sizes, "ps": list(ps), "ks": list(ks),
            "batches": list(batches), "queries": nq,
            "shards": list(shards),
            "probe_backends": list(probe_backends),
            "codes": "synthetic clustered (AQBC-like)",
        },
        "rows": rows,
    }
    out_json = out_json or BENCH_JSON
    with open(out_json, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {path}")
    print(f"wrote {out_json}")
    return rows


def _parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    def positive_int(v):
        iv = int(v)
        if iv < 1:
            raise argparse.ArgumentTypeError(f"must be >= 1, got {v}")
        return iv

    ap.add_argument("--batch", type=positive_int, nargs="+",
                    default=[1, 8, 64],
                    help="batch sizes for knn_batch (axis of the sweep)")
    ap.add_argument("--shards", type=positive_int, nargs="+",
                    default=[1, 8],
                    help="shard counts for the sharded_scan/sharded_amih "
                         "cells (host-mode ShardPlan shards)")
    ap.add_argument("--probe-backend", type=str, nargs="+",
                    default=["host", "device"],
                    choices=["host", "device"],
                    help="probing walks to time for the amih cells "
                         "(axis of the sweep)")
    ap.add_argument("--max-n", type=int, default=None,
                    help="largest DB size (default REPRO_BENCH_MAX_N or 1e6)")
    ap.add_argument("--nq", type=int, default=64, help="queries per cell")
    ap.add_argument("--p", type=int, nargs="+", default=[64, 128])
    ap.add_argument("--k", type=int, nargs="+", default=[1, 10, 100])
    ap.add_argument("--out", type=str, default=None,
                    help="write the JSON payload here instead of "
                         "BENCH_engine.json (used by scripts/bench_check)")
    ap.add_argument("--trace", type=str, default=None, metavar="OUT.json",
                    help="capture ONE traced repetition (outside the "
                         "timed reps) as a Chrome trace at this path")
    return ap.parse_args(argv)


if __name__ == "__main__":
    a = _parse_args()
    run(max_n=a.max_n, nq=a.nq, batches=tuple(sorted(set(a.batch))),
        ps=tuple(a.p), ks=tuple(a.k), out_json=a.out,
        shards=tuple(sorted(set(a.shards))),
        probe_backends=tuple(dict.fromkeys(a.probe_backend)),
        trace_out=a.trace)
