"""Unified batched SearchEngine: one query API over every exact-KNN
algorithm in the repo.

Every caller (serving, benchmarks, examples, tests) talks to one surface:

    engine = make_engine("amih", db_words, p, verify_backend="pallas")
    ids, sims, stats = engine.knn_batch(q_words, k)   # q_words: (B, W)

Backends (registry below):

  - "linear_scan"  — exhaustive Eq. 3 scan, batched over queries with
                     chunked popcounts (the paper's comparator);
                     ``compute_backend="pallas"`` routes scoring through
                     the streaming device top-K (kernels/ops.scan_topk)
                     over a device-resident DB, with an exact float64
                     host rerank of the preselected candidates.
  - "single_table" — one CSR-sorted table probed in the paper's tuple
                     order (§4); practical for p <= 64.
  - "amih"         — angular multi-index hashing (§5): probing-sequence
                     sharing across same-z queries and grouped candidate
                     verification — one vectorized NumPy popcount or one
                     Pallas ``verify_tuples_grouped`` launch per
                     (z-group, tuple-step) on a padded (B_g, C_max, W)
                     layout (``verify_backend="pallas"``).

All three are EXACT: ``knn_batch`` returns, for every row, results whose
sims match per-query ``linear_scan_knn`` bit-for-bit (up to ties inside
one Hamming tuple — equal sims by construction). ``EngineStats`` carries
per-query counter objects plus aggregated totals, the serving-side cost
accounting of the paper's Eq. 13.
"""

from __future__ import annotations

import abc
from collections import OrderedDict
from dataclasses import dataclass, field, fields as dc_fields, replace
from typing import Any, ClassVar, Dict, List, Optional, Tuple

import numpy as np

from ..obs import trace as _obs
from .amih import AMIHIndex, AMIHStats
from .enumeration import EnumerationCapExceeded
from .linear_scan import (
    sims_against_db,
    sims_batch_against_db,
    sims_for_ids,
    topk_from_sims,
)
from .packing import WORD_DTYPE, n_words, popcount
from .single_table import SearchStats, SingleTableIndex

__all__ = [
    "ENGINES",
    "EngineStats",
    "SearchEngine",
    "available_backends",
    "make_engine",
    "probe_cache_snapshot",
    "register_engine",
]


@dataclass
class EngineStats:
    """Batched-search accounting: one stats object per query row plus
    lazily-aggregated totals.

    ``per_query`` holds one counter object per query row (AMIHStats or
    SearchStats — every backend provides them); ``aggregate()`` sums
    every numeric counter across queries (bools count occurrences), so
    e.g. ``stats.aggregate()["verified"]`` is the batch's total candidate
    verifications. Counters that are per-query maxima (``max_radius``)
    aggregate with max, not sum.

    Sharded backends additionally fill ``shards`` and ``per_shard`` (one
    dict per shard: rows held, candidates contributed/verified, device
    launches issued, and ``"device"`` — the placement device the shard's
    codes live on and its verification ran on) — the serving-side view
    of where a batch's work landed. The cross-host cluster engine
    (repro.cluster) adds ``per_host``: one dict per worker host
    aggregating its rows, shard count, summed launch/probe counters,
    its own ``per_shard``/``cache_info`` sections, and RPC timing — the
    same attribution one level up, so serving dashboards stay honest
    about WHICH HOST work ran on, not just which device. ``cache_hits`` counts query rows
    answered from the engine's hot-query cache without any probing
    (AMIHEngine's LRU). ``cache_info`` snapshots the process-wide shared
    caches after the batch: the (p, z) probing-sequence cache and — on
    the device probe path — the device schedule cache, each with
    occupancy plus lifetime hit/miss counters (see
    ``probe_cache_snapshot``); empty for backends that touch neither.

    Streaming serving (repro.pipeline.stream) fills the queue-side
    counters: ``queue_depth`` is the number of queries still waiting
    behind the batch step this stats object belongs to, and
    ``latency_ms`` holds rolling answered-query latency percentiles
    ({"p50": ..., "p99": ..., "mean": ..., "count": ...}); both stay at
    their defaults for direct ``knn_batch`` calls.
    """

    backend: str
    queries: int = 0
    per_query: List[Optional[object]] = field(default_factory=list)
    shards: int = 0
    per_shard: List[Dict[str, int]] = field(default_factory=list)
    per_host: List[Dict[str, object]] = field(default_factory=list)
    cache_hits: int = 0
    cache_info: Dict[str, int] = field(default_factory=dict)
    queue_depth: int = 0
    latency_ms: Dict[str, float] = field(default_factory=dict)

    _MAX_COUNTERS = frozenset({"max_radius"})

    def aggregate(self) -> Dict[str, int]:
        totals: Dict[str, int] = {}
        for s in self.per_query:
            if s is None:
                continue
            for f in dc_fields(s):
                v = getattr(s, f.name)
                if not isinstance(v, (bool, int, np.bool_, np.integer)):
                    continue
                if f.name in self._MAX_COUNTERS:
                    totals[f.name] = max(totals.get(f.name, 0), int(v))
                else:
                    totals[f.name] = totals.get(f.name, 0) + int(v)
        return totals

    def total(self, counter: str) -> int:
        return self.aggregate().get(counter, 0)


class SearchEngine(abc.ABC):
    """Exact batched angular-KNN engine over packed binary codes.

    Subclasses register under ``name`` and implement ``build`` (index
    construction from a packed (n, W) code array) and ``knn_batch``.
    """

    name: ClassVar[str]

    #: the Tracer handed to ``make_engine(..., tracer=...)``, if any —
    #: kept on the engine so callers can drain/export its spans.
    tracer = None

    @classmethod
    @abc.abstractmethod
    def build(
        cls, db_words: np.ndarray, p: int, **cfg: Any
    ) -> "SearchEngine":
        ...

    @abc.abstractmethod
    def knn_batch(
        self, q_words: np.ndarray, k: int
    ) -> Tuple[np.ndarray, np.ndarray, EngineStats]:
        """Exact batched angular KNN: (B, W) packed queries ->
        (ids (B, k'), sims (B, k'), stats) with k' = min(k, n). A 1-D
        (W,) query is treated as B=1.

        Contract every backend honors:

          - ids are global DB row indices (int64); sims are the exact
            float64 Eq. 3 cosines of those rows — bit-identical to
            per-query ``linear_scan_knn`` up to ties inside one Hamming
            tuple (codes of equal tuple are exactly equidistant; any
            order among them is correct).
          - rows are sorted by descending sim, ascending id within a
            tie, and never contain duplicates.
          - ``stats`` is an ``EngineStats`` with one per-query counter
            object per row (AMIHStats / SearchStats); sharded backends
            also fill the per-shard view (rows, candidates, launches,
            placement device).
        """
        ...

    # ------------------------------------------------------------ helpers
    @property
    @abc.abstractmethod
    def n(self) -> int:
        ...

    def _check_queries(self, q_words: np.ndarray, p: int) -> np.ndarray:
        q = np.atleast_2d(np.asarray(q_words, dtype=WORD_DTYPE))
        if q.ndim != 2 or q.shape[1] != n_words(p):
            raise ValueError(
                f"queries must be (B, {n_words(p)}) packed words for "
                f"p={p}; got shape {np.asarray(q_words).shape}"
            )
        return np.ascontiguousarray(q)


def probe_cache_snapshot() -> Dict[str, int]:
    """Occupancy + lifetime hit/miss counters of the process-wide probing
    caches: the shared (p, z) sequence cache always, plus the device
    schedule/stack cache when the device probe path has been imported.
    Engines stamp this into ``EngineStats.cache_info`` per batch, so the
    benchmark rows can report cache effectiveness per cell."""
    from .probing import _cache_stats

    out: Dict[str, int] = dict(_cache_stats())
    import sys

    mod = sys.modules.get(__package__ + ".probe_device")
    if mod is not None:   # only if already imported: no jax import here
        out.update(mod.schedule_cache_stats())
    return out


ENGINES: Dict[str, type] = {}


def register_engine(cls: type) -> type:
    ENGINES[cls.name] = cls
    return cls


def available_backends() -> List[str]:
    return sorted(ENGINES)


def make_engine(
    backend: str, db_words: np.ndarray, p: int, **cfg: Any
) -> SearchEngine:
    """Build a search engine by backend name (see ``available_backends``).

    ``db_words`` is the packed (n, W) uint32 code array (``pack_bits``),
    ``p`` the code length in bits. ``cfg`` is forwarded to the backend's
    ``build``; unknown keys raise ``TypeError``. The registered backends
    and their main knobs (full details in docs/tuning.md):

      - "linear_scan"   — exhaustive baseline.
                          ``compute_backend`` ("numpy" | "pallas"),
                          ``chunk``.
      - "single_table"  — one CSR table (paper §4, p <= 64).
                          ``enumeration_cap``.
      - "amih"          — angular multi-index hashing (paper §5).
                          ``m``, ``verify_backend`` ("numpy" | "pallas"),
                          ``probe_backend`` ("host" | "device" — the
                          fused probing walk: ONE launch for the whole
                          batch, every z-group stacked into it;
                          ``probe_fused=False`` restores one launch per
                          z-group), ``probe_stream_cap``,
                          ``enumeration_cap``, ``query_cache_size``,
                          ``overlap_verify``, ``device`` (where the
                          index and its launches live; JAX's default
                          device when None).
      - "sharded_scan"  — row-sharded exhaustive scan (repro.shard).
                          ``mesh`` | ``num_shards`` | ``plan``,
                          ``shard_axes``, ``devices``, ``chunk``.
      - "sharded_amih"  — one shard-local AMIH index per slice, each
                          placed on its own device; with
                          ``probe_backend="device"`` the shards on each
                          device fuse into ONE launch per device,
                          dispatched to all devices without blocking.
                          sharding knobs as above plus ``m``,
                          ``verify_backend``, ``probe_backend``,
                          ``probe_fused``, ``enumeration_cap``,
                          ``probe_workers``, ``probe_mode``,
                          ``prime_bound``.
      - "cluster"       — cross-host coordinator over worker processes
                          (repro.cluster): each worker runs an
                          ``inner_backend`` sharded engine over its
                          host-partitioned slice; the monotone k-th
                          cosine floor broadcasts between hosts.
                          ``hosts`` | ``workers`` (address list),
                          ``inner_backend``, ``num_shards``,
                          ``prime_bound``, ``request_timeout``; extra
                          knobs forward to every worker's engine.

    Every backend answers the same batched ``knn_batch(q_words, k)`` and
    returns results bit-identical to ``linear_scan_knn`` (up to ties
    inside one Hamming tuple). The sharded backends live in
    ``repro.shard`` and are registered on first use, so numpy-only
    callers of the host backends never pay the jax import. Engines that
    hold workers ("amih" with ``overlap_verify``, "sharded_amih" with
    ``probe_workers``) expose ``close()``; GC closes them too.

    ``tracer=`` (a ``repro.obs.Tracer``) threads end-to-end tracing
    through: it is installed as the process tracer — the instrumentation
    sites at every layer read one process-wide tracer, since kernel
    launch sites cannot know which engine they serve — and attached to
    the returned engine as ``engine.tracer`` for draining/export.
    Tracing is off unless the tracer is enabled; spans observe, never
    reorder, so results are bit-identical either way.
    """
    tracer = cfg.pop("tracer", None)
    if tracer is not None:
        from ..obs import trace as _obs_trace

        _obs_trace.set_tracer(tracer)
    cls = ENGINES.get(backend)
    if cls is None and backend.startswith("sharded"):
        try:
            from .. import shard  # noqa: F401  (registers them)
        except ImportError:
            pass  # no jax on this host: fall through to the ValueError
        cls = ENGINES.get(backend)
    if cls is None and backend == "cluster":
        from .. import cluster  # noqa: F401  (registers ClusterEngine)

        cls = ENGINES.get(backend)
    if cls is None:
        raise ValueError(
            f"unknown search backend {backend!r}; "
            f"available: {available_backends()}"
        )
    eng = cls.build(db_words, p, **cfg)
    eng.tracer = tracer
    return eng


@register_engine
class LinearScanEngine(SearchEngine):
    """Exhaustive baseline: batched Eq. 3 sims + per-row deterministic
    top-k (identical selection code path to ``linear_scan_knn``).

    ``compute_backend`` selects the scoring path:

      - "numpy"  — chunked host popcounts (default; no jax dependency).
      - "pallas" — the streaming device top-K ``kernels/ops.scan_topk``
        (hamming_scan kernel on TPU, the identical-math XLA reference
        elsewhere) over a device-resident copy of the DB uploaded once.
        The device preselects ``k + slack`` candidates in float32; their
        sims are then recomputed on host in float64 (``sims_for_ids``)
        and re-ranked, so the returned (ids, sims) stay bit-identical to
        ``linear_scan_knn``. Both ``k`` (fetch size) and the batch dim are
        padded to power-of-two buckets so the jitted top-K retraces
        O(log) times per axis at most.

    This engine is also AMIH's degrade-to-scan comparator, so the kernel
    path keeps the exhaustive fallback regime fast on device-rich hosts.
    """

    name = "linear_scan"

    # Device preselect slack: candidates fetched beyond k so float32
    # rounding at the selection boundary cannot evict a true top-k item.
    # Distinct Eq. 3 sims differ by >~1/p^3 (integer cross-multiplication
    # bound), which stays well above float32 resolution for p <= ~192;
    # beyond that, sims can collapse in float32, so the slack grows with p
    # to keep room for a whole collapsed boundary population. Candidates
    # with *identical* float64 sims are genuine ties (any k of them is a
    # correct answer), so only distinct-sim collisions matter.
    @property
    def _topk_slack(self) -> int:
        return 16 + max(0, self.p - 128) // 4

    def __init__(
        self,
        db_words: np.ndarray,
        p: int,
        chunk: int,
        compute_backend: str = "numpy",
    ):
        self.db_words = np.ascontiguousarray(db_words, dtype=WORD_DTYPE)
        self.p = p
        self.chunk = chunk
        self.compute_backend = compute_backend
        self._db_dev = None   # device-resident codes, uploaded on first use

    @classmethod
    def build(
        cls,
        db_words: np.ndarray,
        p: int,
        chunk: int = 1 << 15,
        compute_backend: str = "numpy",
        **cfg: Any,
    ) -> "LinearScanEngine":
        if cfg:
            raise TypeError(f"unknown linear_scan options: {sorted(cfg)}")
        if compute_backend not in ("numpy", "pallas"):
            raise ValueError(
                f"unknown compute_backend {compute_backend!r}"
            )
        return cls(db_words, p, chunk, compute_backend)

    @property
    def n(self) -> int:
        return self.db_words.shape[0]

    # Cap on live sims-matrix elements: query rows are processed in
    # groups of max(1, _SIMS_BUDGET // n) so peak scratch stays ~64 MB
    # float64 regardless of B and N, while each row is still computed
    # and top-k'd whole — bit-identical to per-query linear_scan_knn.
    _SIMS_BUDGET = 1 << 23

    def knn_batch(self, q_words, k):
        q = self._check_queries(q_words, self.p)
        B = q.shape[0]
        k_eff = min(k, self.n)
        with _obs.current().span("engine.knn_batch", cat="engine",
                                 backend=self.name, B=B, k=k_eff):
            return self._knn_batch_traced(q, B, k_eff)

    def _knn_batch_traced(self, q, B, k_eff):
        if self.compute_backend == "pallas" and k_eff > 0:
            ids_out, sims_out = self._knn_batch_device(q, k_eff)
        else:
            ids_out = np.empty((B, k_eff), dtype=np.int64)
            sims_out = np.empty((B, k_eff), dtype=np.float64)
            group = max(1, self._SIMS_BUDGET // max(self.n, 1))
            for lo in range(0, B, group):
                sims = sims_batch_against_db(
                    q[lo : lo + group], self.db_words, chunk=self.chunk
                )
                for i in range(sims.shape[0]):
                    ids_out[lo + i], sims_out[lo + i] = topk_from_sims(
                        sims[i], k_eff
                    )
        # retrieved = codes scored per query: the whole DB, exhaustively.
        stats = EngineStats(
            backend=self.name, queries=B,
            per_query=[SearchStats(retrieved=self.n) for _ in range(B)],
        )
        return ids_out, sims_out, stats

    def _knn_batch_device(self, q, k_eff):
        """Device streaming top-K preselect + exact float64 host rerank.

        Both the fetch size and the batch dim are padded to power-of-two
        buckets (zero query rows score 0.0 everywhere and are sliced off),
        so the jitted ``scan_topk`` retraces O(log) times per axis instead
        of once per distinct (B, k).
        """
        import jax.numpy as jnp

        from ..kernels import ops

        if self._db_dev is None:
            self._db_dev = jnp.asarray(self.db_words)
        B = q.shape[0]
        k_fetch = min(
            self.n, ops.pad_bucket(k_eff + self._topk_slack, minimum=8)
        )
        Bp = ops.pad_bucket(B, minimum=8)
        qp = np.zeros((Bp, q.shape[1]), dtype=q.dtype)
        qp[:B] = q
        _, ids32 = ops.scan_topk(
            jnp.asarray(qp), self._db_dev, k_fetch, use_pallas=ops.on_tpu()
        )
        fetched = np.asarray(ids32)[:B].astype(np.int64)   # (B, k_fetch)
        ids_out = np.empty((B, k_eff), dtype=np.int64)
        sims_out = np.empty((B, k_eff), dtype=np.float64)
        for i in range(B):
            cand = fetched[i]
            sub = sims_for_ids(q[i], self.db_words, cand)  # exact float64
            order = np.lexsort((cand, -sub))[:k_eff]
            ids_out[i] = cand[order]
            sims_out[i] = sub[order]
        return ids_out, sims_out


@register_engine
class SingleTableEngine(SearchEngine):
    """Single hash table (paper §4); exact for p <= 64.

    The raw index has no cost guard: on sparse occupancy a single tuple's
    bucket enumeration is C(z, r1)*C(p-z, r2) — combinatorial. The engine
    caps it (default ``max(8n, 16384)``) and degrades the affected query
    to an exact linear scan (the paper's §5 observation), flagged in
    ``SearchStats.fell_back_to_scan``. Counters accumulated before the
    fallback are kept — they are probes actually performed.
    """

    name = "single_table"

    def __init__(self, index: SingleTableIndex, db_words, enumeration_cap):
        self.index = index
        self.p = index.p
        self.db_words = np.ascontiguousarray(db_words, dtype=WORD_DTYPE)
        self.enumeration_cap = enumeration_cap

    @classmethod
    def build(
        cls,
        db_words: np.ndarray,
        p: int,
        enumeration_cap: Optional[int] = None,
        **cfg: Any,
    ) -> "SingleTableEngine":
        if cfg:
            raise TypeError(f"unknown single_table options: {sorted(cfg)}")
        n = np.asarray(db_words).shape[0]
        if enumeration_cap is None:
            enumeration_cap = max(8 * n, 1 << 14)
        return cls(SingleTableIndex.build(db_words, p), db_words,
                   enumeration_cap)

    @property
    def n(self) -> int:
        return self.index.n

    def knn_batch(self, q_words, k):
        q = self._check_queries(q_words, self.p)
        B = q.shape[0]
        k_eff = min(k, self.n)
        with _obs.current().span("engine.knn_batch", cat="engine",
                                 backend=self.name, B=B, k=k_eff):
            return self._knn_batch_traced(q, B, k_eff)

    def _knn_batch_traced(self, q, B, k_eff):
        zs = popcount(q)
        ids_out = np.empty((B, k_eff), dtype=np.int64)
        sims_out = np.empty((B, k_eff), dtype=np.float64)
        per_query: List[SearchStats] = []
        for i in range(B):
            st = SearchStats()
            if zs[i] == 0:
                # Zero-norm query: cosine is undefined, every code scores
                # exactly 0.0, so any k ids are a correct answer — and the
                # table would enumerate C(p, r2) buckets per tuple trying
                # to find them. Emit the deterministic tie order directly.
                ids_out[i] = np.arange(k_eff, dtype=np.int64)
                sims_out[i] = 0.0
            else:
                try:
                    ids_out[i], sims_out[i] = self.index.knn(
                        q[i], k_eff, stats=st,
                        enumeration_cap=self.enumeration_cap,
                    )
                except EnumerationCapExceeded:
                    # probing has lost to exhaustive verification for
                    # this query.
                    st.fell_back_to_scan = True
                    ids_out[i], sims_out[i] = topk_from_sims(
                        sims_against_db(q[i], self.db_words), k_eff
                    )
            per_query.append(st)
        return ids_out, sims_out, EngineStats(
            backend=self.name, queries=B, per_query=per_query
        )


@register_engine
class AMIHEngine(SearchEngine):
    """Angular multi-index hashing (paper §5): batch-aware probing with
    per-(p, z) probing-sequence sharing and grouped NumPy/Pallas
    verification.

    Each tuple step verifies the fresh candidates of ALL same-z queries in
    one backend call: ``verify_backend="numpy"`` is a single vectorized
    host popcount over the concatenated ragged blocks;
    ``verify_backend="pallas"`` gathers them into a padded
    (B_g, C_max, W) device layout (power-of-two buckets -> bounded jit
    cache) and issues one ``verify_tuples_grouped`` launch per (z-group,
    tuple-step) against the device-resident DB uploaded at build
    (``index.verify_launches`` counts dispatches).

    ``enumeration_cap`` bounds a single substring-tuple's bucket
    enumeration before the query degrades to an exact full scan; the
    default scales with the DB like SingleTableEngine's
    (``max(8n, 16384)``) instead of a fixed constant.

    Hot-query cache: serving traffic repeats query codes (hot documents,
    retried requests), and probing + verification for a repeated packed
    code is fully deterministic — so ``knn_batch`` memoizes per
    (code bytes, k) in a bounded LRU (``query_cache_size`` entries,
    0 disables). Hits skip probing entirely and are counted in
    ``EngineStats.cache_hits`` / ``engine.cache_hits``; the cached stats
    counters are replayed (copied) so per-query accounting stays
    identical to an uncached run.

    ``overlap_verify=True`` pipelines each z-group's tuple loop one step
    deep (repro.pipeline.VerifyOverlap): tuple step t's grouped
    verification runs on a worker thread / the device while the host
    probes step t+1. Results are bit-identical to the sequential loop;
    probe-side counters of a query that finishes at step t may include
    one extra (discarded) probing step — see pipeline/overlap.py.
    """

    name = "amih"

    def __init__(self, index: AMIHIndex, enumeration_cap,
                 query_cache_size: int = 256, overlap_verify: bool = False):
        self.index = index
        self.p = index.p
        self.enumeration_cap = enumeration_cap
        self.query_cache_size = query_cache_size
        self.overlap_verify = overlap_verify
        self._overlap = None   # VerifyOverlap, created on first use
        # (q_words bytes, k) -> (ids row, sims row, AMIHStats); ordered
        # oldest-first so popitem(last=False) evicts the LRU entry.
        self._query_cache: "OrderedDict[Tuple[bytes, int], tuple]" = (
            OrderedDict()
        )
        self.cache_hits = 0

    @classmethod
    def build(
        cls,
        db_words: np.ndarray,
        p: int,
        m: Optional[int] = None,
        verify_backend: str = "numpy",
        enumeration_cap: Optional[int] = None,
        query_cache_size: int = 256,
        overlap_verify: bool = False,
        probe_backend: str = "host",
        probe_stream_cap: int = 1 << 16,
        probe_fused: bool = True,
        device: Optional[Any] = None,
        **cfg: Any,
    ) -> "AMIHEngine":
        if cfg:
            raise TypeError(f"unknown amih options: {sorted(cfg)}")
        n = np.asarray(db_words).shape[0]
        if enumeration_cap is None:
            enumeration_cap = max(8 * n, 1 << 14)
        index = AMIHIndex.build(
            db_words, p, m=m, verify_backend=verify_backend,
            device=device, probe_backend=probe_backend,
            probe_stream_cap=probe_stream_cap,
            probe_fused=probe_fused,
        )
        return cls(index, enumeration_cap, query_cache_size, overlap_verify)

    def _overlap_driver(self):
        """The engine's VerifyOverlap (one worker, lazily created)."""
        if self._overlap is None and self.overlap_verify:
            from ..pipeline.overlap import VerifyOverlap

            self._overlap = VerifyOverlap()
        return self._overlap

    def close(self) -> None:
        """Release the overlap worker thread (idempotent); engines are
        also closed on GC so sweeps that build many pipelined engines
        don't accumulate idle verify workers."""
        overlap, self._overlap = self._overlap, None
        if overlap is not None:
            overlap.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass   # interpreter shutdown: executors may already be gone

    @property
    def n(self) -> int:
        return self.index.n

    def knn_batch(self, q_words, k):
        q = self._check_queries(q_words, self.p)
        B = q.shape[0]
        k_eff = min(k, self.n)
        with _obs.current().span("engine.knn_batch", cat="engine",
                                 backend=self.name, B=B, k=k_eff):
            return self._knn_batch_traced(q, B, k_eff)

    def _knn_batch_traced(self, q, B, k_eff):
        cache = self._query_cache if self.query_cache_size > 0 else None

        # Split rows into cache hits and (deduplicated) misses. Duplicate
        # rows inside one batch do identical probing work, so one compute
        # serves them all — counters are copies of the computed row's,
        # exactly what per-row computation would have produced.
        per_query: List[Optional[AMIHStats]] = [None] * B
        ids_out = np.empty((B, k_eff), dtype=np.int64)
        sims_out = np.empty((B, k_eff), dtype=np.float64)
        hits = 0
        miss_keys: Dict[bytes, List[int]] = {}
        for i in range(B):
            key = q[i].tobytes()
            cached = cache.get((key, k_eff)) if cache is not None else None
            if cached is not None:
                cache.move_to_end((key, k_eff))
                c_ids, c_sims, c_stats = cached
                ids_out[i], sims_out[i] = c_ids, c_sims
                per_query[i] = replace(c_stats)
                hits += 1
            else:
                miss_keys.setdefault(key, []).append(i)

        if miss_keys:
            rows = [idxs[0] for idxs in miss_keys.values()]
            miss_stats = [AMIHStats() for _ in rows]
            m_ids, m_sims = self.index.knn_batch(
                q[rows], k_eff, stats=miss_stats,
                enumeration_cap=self.enumeration_cap,
                overlap=self._overlap_driver(),
            )
            for j, (key, idxs) in enumerate(miss_keys.items()):
                for i in idxs:
                    ids_out[i], sims_out[i] = m_ids[j], m_sims[j]
                    per_query[i] = replace(miss_stats[j])
                if cache is not None:
                    cache[(key, k_eff)] = (
                        m_ids[j].copy(), m_sims[j].copy(), miss_stats[j]
                    )
                    while len(cache) > self.query_cache_size:
                        cache.popitem(last=False)

        self.cache_hits += hits
        return ids_out, sims_out, EngineStats(
            backend=self.name, queries=B, per_query=per_query,
            cache_hits=hits, cache_info=probe_cache_snapshot(),
        )
