"""The chip benchmark's harness: one cell, one seed, one run.

Everything of one cell is found by name from ``BENCHMARK.json``:

- the cell (``workloads``) names a configuration and a traffic mix;
- the configuration is the JSON file the cell's ``configs`` entry names:
  the engine's backend and ``make_engine`` options, the corpus model and
  size per chip, and the plain reference (``references/<name>.py``);
- the traffic mix is ``traffic/<name>.json``, read by ``Traffic``;
- each end-to-end metric is read by ``end_to_end/<metric>.py`` and each
  per-layer metric by ``layers/<metric>.py``: a module with one function
  ``read(run) -> float | None`` over the ``Run`` record below. A reader
  that finds nothing to read returns None and the metric is left out.

So a later change adds a cell, a configuration, a traffic mix or a metric
by adding files and ``BENCHMARK.json`` entries, and edits none.

A run: generate the corpus and the query pool (a fixed data set; the
seed draws the order of the pool's batches), build the engine through
``make_engine`` (JAX's default device is the first chip), answer every pool
batch once (the warm-up: every shape the window uses), then a closed
loop that issues the pool's batches in turn, each only when the previous
answer is on the host, until ``seconds`` have passed; the window ends
with the batch running at that moment. Then the device's peak memory is
read, the engine freed, the plain reference run over the pool and every
distinct answer of the window compared with it.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import sys
import time
import traceback
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import corpus  # noqa: E402
import exactness  # noqa: E402
import roofline  # noqa: E402
import tracesum  # noqa: E402

BENCH_REL = Path("benchmarks/chip")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    """Import the Python file ``path`` as a module of its own."""
    name = "chipbench_" + "_".join(path.with_suffix("").parts[-2:])
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Traffic:
    """A closed-loop mix: ``batch`` queries per ``knn_batch(q, k)`` call,
    drawn as a pool of ``pool_batches`` distinct batches of stored codes
    with each bit flipped with probability ``query_flip_prob``; the window
    issues the pool's batches in turn."""

    batch: int
    k: int
    pool_batches: int
    query_flip_prob: float
    loop: str = "closed"

    @classmethod
    def from_file(cls, path: Path) -> "Traffic":
        t = cls(**_load_json(path))
        if t.loop != "closed":
            raise ValueError(f"{path}: only closed-loop traffic is built")
        if min(t.batch, t.k, t.pool_batches) < 1:
            raise ValueError(f"{path}: batch, k and pool_batches must be >= 1")
        return t


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: Traffic
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: Path

    @property
    def p(self) -> int:
        return int(self.config["p"])

    @property
    def n(self) -> int:
        return int(self.config["n_per_chip"]) * self.chips


def _reported_in(metric: dict, cell: str, e2e_of_cell: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_of_cell


def load_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json``, with its files."""
    root = Path(root)
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    bench_dir = root / BENCH_REL
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=_load_json(root / cfg["file"]),
        traffic=Traffic.from_file(bench_dir / "traffic" / f"{w['traffic']}.json"),
        end_to_end=e2e,
        per_layer=[m for m in bench["per_layer"]
                   if _reported_in(m, workload, names)],
        bench_dir=bench_dir,
    )


@dataclass
class Batch:
    """One ``knn_batch`` call of the window (host perf-counter seconds)."""

    pool_index: int
    start: float
    end: float
    queries: int

    @property
    def latency(self) -> float:
        return self.end - self.start


@dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    setup_s: float
    window_s: float
    batches: List[Batch]
    peaks: dict
    trace: object = None          # tracesum.TraceSummary of a traced run


def make_corpus(cell: Cell, seed: int):
    """The corpus (n, W) and the pool of query batches, in the order in
    which the window issues them.

    The corpus and the pool's batches are a fixed data set, drawn from
    the configuration's ``corpus.seed`` as a public benchmark fixes its
    base and query sets; ``seed`` draws only the order of the batches. So
    every seed asks for the same work in another order, and runs of
    different seeds differ no more than two runs of one seed."""
    c = cell.config["corpus"]
    if c["mode"] != "clustered":
        raise ValueError(f"unknown corpus mode {c['mode']!r}")
    db = corpus.clustered_codes(int(c["seed"]), cell.n, cell.p,
                                n_clusters=int(c["n_clusters"]),
                                flip_prob=float(c["flip_prob"]))
    t = cell.traffic
    q = corpus.near_queries(int(c["seed"]), db, cell.p,
                            t.pool_batches * t.batch,
                            flip_prob=t.query_flip_prob)
    pool = [q[i * t.batch:(i + 1) * t.batch] for i in range(t.pool_batches)]
    order = corpus.rng_for(seed, 2).permutation(len(pool))
    return db, [pool[i] for i in order]


def build_engine(cell: Cell, db: np.ndarray, devices):
    """The system under test, through ``make_engine``."""
    from repro.core import make_engine

    return make_engine(cell.config["backend"], db, cell.p,
                       **cell.config.get("engine", {}))


class CompileCounter:
    """Counts JAX traces and compile requests (``compiles``: each a backend
    compile or a load from the persistent cache, ``cache_loads`` the
    latter), and keeps the name of what each request was for. JAX's monitoring
    listeners are process-wide and cannot be removed, so one counter per
    process is made (``shared()``) and read by snapshots."""

    _shared = None
    _KINDS = {
        "/jax/core/compile/jaxpr_trace_duration": "traces",
        "/jax/core/compile/backend_compile_duration": "compiles",
    }

    def __init__(self):
        import jax.monitoring as mon

        self.counts = {"traces": 0, "compiles": 0, "cache_loads": 0}
        self.compiled: List[str] = []

        def on_duration(event, duration, fun_name=None, **_):
            kind = self._KINDS.get(event)
            if kind is not None:
                self.counts[kind] += 1
                if kind == "compiles":
                    self.compiled.append(str(fun_name))

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.counts["cache_loads"] += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    @classmethod
    def shared(cls) -> "CompileCounter":
        if cls._shared is None:
            cls._shared = cls()
        return cls._shared

    def snapshot(self) -> dict:
        return dict(self.counts, compiled=len(self.compiled))

    def since(self, snap: dict) -> dict:
        out = {k: self.counts[k] - snap[k] for k in self.counts}
        out["compiled"] = self.compiled[snap["compiled"]:]
        return out


def _digest(ids: np.ndarray, sims: np.ndarray) -> bytes:
    return (np.ascontiguousarray(ids).tobytes()
            + np.ascontiguousarray(sims).tobytes())


def window(engine, pool, k: int, seconds: float, annotate=None):
    """The closed loop: returns (batches, answers, failed queries, window
    seconds). ``answers`` maps a pool index to its distinct answers."""
    import contextlib

    batches: List[Batch] = []
    answers: Dict[int, list] = {}
    seen: Dict[int, set] = {}
    failed = 0
    i = 0
    t_start = time.perf_counter()
    deadline = t_start + seconds
    t1 = t_start
    while t1 < deadline:
        b = i % len(pool)
        ctx = annotate() if annotate else contextlib.nullcontext()
        t0 = time.perf_counter()
        try:
            with ctx:
                ids, sims, _ = engine.knn_batch(pool[b], k)
        except Exception:               # the run goes on, counted failed
            t1 = time.perf_counter()
            log(f"batch {i}: knn_batch raised\n{traceback.format_exc()}")
            failed += len(pool[b])
            batches.append(Batch(b, t0, t1, 0))
            i += 1
            continue
        t1 = time.perf_counter()
        batches.append(Batch(b, t0, t1, len(pool[b])))
        d = _digest(np.asarray(ids), np.asarray(sims))
        if d not in seen.setdefault(b, set()):
            seen[b].add(d)
            answers.setdefault(b, []).append((np.array(ids), np.array(sims)))
        i += 1
    return batches, answers, failed, t1 - t_start


def memory_peak(devices) -> Optional[int]:
    """The peak bytes in use on the fullest device; None on a backend
    that keeps no memory statistics (the CPU)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def read_metrics(specs: List[dict], folder: Path, run: Run) -> Dict[str, dict]:
    out = {}
    for m in specs:
        value = load_module(folder / f"{m['name']}.py").read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_process: float, *, engine_factory: Optional[Callable] = None,
             trace_dir: Optional[Path] = None) -> dict:
    """One run of ``cell``: returns the result line's object.

    ``engine_factory(cell, db, devices)`` stands in for ``build_engine``
    where a test or the control puts something else in the program's
    place; ``t_process`` is the perf-counter second the process started.
    """
    devices = list(devices[: cell.chips])
    t = cell.traffic
    counter = CompileCounter.shared()
    at_start = counter.snapshot()
    t0 = time.perf_counter()
    db, pool = make_corpus(cell, seed)
    t_data = time.perf_counter()
    engine = (engine_factory or build_engine)(cell, db, devices)
    t_build = time.perf_counter()
    for q in pool:
        engine.knn_batch(q, t.k)
    t_warm = time.perf_counter()
    setup_s = t_warm - t_process
    log(json.dumps({"setup": {
        "data_s": t_data - t0, "build_s": t_build - t_data,
        "warm_s": t_warm - t_build, "pool_batches": len(pool),
        "compiles": counter.since(at_start)}}))

    summary = None
    before = counter.snapshot()
    if trace:
        import tempfile

        import jax.profiler as jp

        opts = jp.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        if trace_dir is not None:
            Path(trace_dir).mkdir(exist_ok=True)
        tmp = tempfile.TemporaryDirectory(dir=trace_dir)
        jp.start_trace(tmp.name, profiler_options=opts)
        try:
            batches, answers, failed, window_s = window(
                engine, pool, t.k, seconds,
                annotate=lambda: jp.TraceAnnotation(
                    tracesum.BATCH_ANNOTATION))
        finally:
            jp.stop_trace()
        t_read = time.perf_counter()
        summary = tracesum.read_xspace(tracesum.find_xspace(tmp.name))
        tmp.cleanup()
        log(json.dumps({"trace_read_s": time.perf_counter() - t_read,
                        "trace_batches": len(summary.batches),
                        "trace_devices": summary.devices}))
    else:
        batches, answers, failed, window_s = window(engine, pool, t.k,
                                                    seconds)
    in_window = counter.since(before)
    log(json.dumps({"window": {
        "seconds": window_s, "batches": len(batches),
        "latency_s": [b.latency for b in batches],
        "in_window": in_window}}))

    peak = memory_peak(devices)
    close = getattr(engine, "close", None)
    if close is not None:
        close()
    del engine
    gc.collect()

    t_ref = time.perf_counter()
    ref = load_module(cell.bench_dir / "references"
                      / f"{cell.config['reference']}.py")
    ref_sims = list(ref.topk_sims(np.concatenate(pool), db, cell.p, t.k,
                                  device=devices[0]).reshape(
                                      len(pool), t.batch, -1))
    t_cmp = time.perf_counter()
    numbers = exactness.compare(answers, pool, db, ref_sims, t.k, failed,
                                ref.sims64)
    correct = exactness.verdict(numbers)
    log(json.dumps({"reference_s": t_cmp - t_ref,
                    "compare_s": time.perf_counter() - t_cmp}))

    d0 = devices[0]
    run = Run(cell=cell, setup_s=setup_s, window_s=window_s,
              batches=batches, peaks=_peaks_or_empty(d0), trace=summary)
    specs = cell.per_layer if trace else cell.end_to_end
    folder = cell.bench_dir / ("layers" if trace else "end_to_end")
    result = {
        "correct": bool(correct),
        "attempted": int(sum(len(pool[b.pool_index]) for b in batches)),
        "failed": int(failed),
        "metrics": read_metrics(specs, folder, run),
        "device": {
            "platform": d0.platform,
            "kind": d0.device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak,
        },
    }
    if summary is not None:
        busy = tracesum.busy_ns(summary)
        lo, hi = summary.window
        result["device"]["busy_s"] = float(np.mean(list(busy.values()))) * 1e-9
        result["device"]["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = tracesum.breakdown(summary)
    result["checks"] = exactness.as_lines(numbers)
    return result


def _peaks_or_empty(device) -> dict:
    """The chip's peaks; off the chip (tests on the CPU) there are none,
    and readers that need them return nothing."""
    if device.platform != "tpu":
        return {}
    return roofline.peaks(device.device_kind)
