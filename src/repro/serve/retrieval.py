"""Retrieval service: the paper's technique as a first-class serving feature.

Pipeline:  encoder LM  ->  mean-pooled hidden state  ->  AQBC binarization
           ->  exact angular KNN through the unified SearchEngine
           (core.engine; backend selected by name — including the
           pod-scale "sharded_scan"/"sharded_amih" backends of
           repro.shard, configured via the mesh/num_shards knobs, and
           the cross-host "cluster" tier of repro.cluster, selected by
           ``RetrievalConfig.cluster``/``hosts``).

This is the production shape of the paper: binary hashing exists to make
billion-item corpora searchable in RAM (paper §6.3.4); the LM zoo supplies
the embeddings; the engine supplies exact sublinear angular search over
the codes, *batched* — queued queries are answered ``search_batch_size``
at a time through one ``knn_batch`` call per step, the multi-index-hashing
serving shape (probing-sequence sharing amortizes across the batch).

``RetrievalService.build_index`` ingests documents (token arrays), encodes,
learns/applies AQBC, packs codes, builds the engine. ``search_batch``
answers a batch of queries in one engine call; ``search`` is the B=1
convenience; ``submit``/``run_queued`` expose the queued serving loop.

Queued serving is asynchronous and streamable (repro.pipeline):
``submit`` is thread-safe and returns a ``Ticket`` (int-compatible qid +
a future resolving to that query's (ids, sims));
``run_queued(stream=True)`` yields one ``StepResult`` per batch step as
it completes, encoding batch i+1 on the device while batch i searches,
with queue-depth and p50/p99 latency counters on each step's
``EngineStats``. ``RetrievalConfig.pipelined=True`` additionally turns
on the engine-level pipelining (AMIH verify/probe overlap,
shard-parallel probing) for the backends that support it.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import EngineStats, SearchEngine, linear_scan_knn, make_engine, pack_bits
from ..core import aqbc
from ..models import Model
from ..models.common import ArchConfig
from ..pipeline.stream import LatencyTracker, StepResult, Ticket, stream_search

__all__ = ["RetrievalConfig", "RetrievalService"]


@dataclass(frozen=True)
class RetrievalConfig:
    code_bits: int = 64
    aqbc_iters: int = 15
    m_tables: Optional[int] = None    # None -> paper's p/log2(n)
    batch_size: int = 32              # encode batch
    # core.engine backend name: "amih", "linear_scan", "single_table",
    # or the pod-scale "sharded_scan" / "sharded_amih" (repro.shard).
    backend: str = "amih"
    # AMIH grouped candidate verification: "numpy" (one vectorized host
    # popcount per z-group/tuple-step) or "pallas" (one
    # verify_tuples_grouped launch per step over the padded
    # (B_g, C_max, W) layout; DB stays device-resident from build).
    verify_backend: str = "numpy"
    # AMIH probing walk: "host" (the reference per-tuple Python walk) or
    # "device" (the fused probe -> bucket-lookup -> verify jitted launch;
    # see core.probe_device). Applies to "amih" and "sharded_amih";
    # probe_stream_cap bounds the precompiled probing stream per (p, z)
    # schedule before the scan fallback takes over. The device walk
    # stacks every z-group into ONE launch per batch — and, sharded, one
    # fused launch per DEVICE, dispatched to all devices without blocking.
    probe_backend: str = "host"
    probe_stream_cap: int = 1 << 16
    # linear_scan scoring: "numpy" (chunked host popcounts) or "pallas"
    # (streaming device top-K via kernels/ops.scan_topk + exact float64
    # host rerank).
    compute_backend: str = "numpy"
    # None -> backend default (max(8n, 16384)): bucket enumerations past
    # this degrade the query to an exact scan.
    enumeration_cap: Optional[int] = None
    search_batch_size: int = 32       # queued queries per knn_batch step
    # Sharded-backend layout knobs (repro.shard.ShardPlan): a mesh shards
    # the sharded_scan DB across devices (shard_axes selects the mesh
    # axes; None = all); num_shards is the host-side shard count when no
    # mesh is given; None -> one shard per local device.
    mesh: Optional[object] = None
    num_shards: Optional[int] = None
    shard_axes: Optional[Tuple[str, ...]] = None
    # Engine-level pipelining (repro.pipeline): "amih" gets the tuple-step
    # verify/probe overlap (overlap_verify), "sharded_amih" gets
    # shard-parallel probing under the shared warm-started bound
    # (probe_workers; None -> one worker per shard; the worker pool is
    # persistent — forked once per engine, released by service.close()).
    # Results stay bit-identical to the sequential engines.
    pipelined: bool = False
    probe_workers: Optional[int] = None
    # Worker flavor of the shard-probe pool: "process" (real CPU
    # parallelism on CPython), "thread" (free-threaded runtimes /
    # GIL-releasing device verification), or "auto" (process where fork
    # exists; the pallas verify backend forces thread either way).
    probe_mode: str = "auto"
    # Explicit per-shard placement devices for the sharded backends
    # (round-robin over shards); None derives placement from the mesh,
    # falling back to the local devices.
    devices: Optional[Tuple[object, ...]] = None
    # Cross-host serving tier (repro.cluster): cluster=True swaps the
    # engine for the "cluster" backend — a coordinator over ``hosts``
    # worker processes, each running ``backend`` (which must then be a
    # sharded backend; any other name serves via sharded_amih workers)
    # over its host-partitioned slice, with the monotone k-th-cosine
    # floor broadcast between hosts. Exact results, same knn_batch API;
    # the queued/streaming serving loop is unchanged on top.
    cluster: bool = False
    hosts: int = 2
    # End-to-end tracing (repro.obs): True installs an enabled Tracer at
    # build_index time (a float in (0, 1] additionally samples top-level
    # spans at that probability). Spans from every layer — engine,
    # AMIH probe/verify, kernel launches, and (cluster=True) the
    # cross-host worker spans — land on ``service.engine.tracer``;
    # export with repro.obs.export.write_chrome_trace. Off by default:
    # the disabled path is a single attribute check per span site.
    trace: object = False

    @property
    def engine(self) -> str:
        """Pre-shard name of ``backend``, kept for callers of the old
        field."""
        return self.backend


@dataclass
class RetrievalService:
    """End-to-end retrieval serving over one encoder LM + one engine.

    Lifecycle: construct with an encoder config/params and a
    ``RetrievalConfig``; ``build_index(doc_tokens)`` encodes the corpus,
    learns AQBC, packs codes and builds the engine; then either

      - ``search_batch(query_tokens, k)`` — one batched ``knn_batch``
        call, returns ``(ids, sims, EngineStats)``; ``search`` is the
        B=1 convenience returning the query's own stats object, or
      - ``submit(query_tokens) -> Ticket`` + ``run_queued(k[, stream])``
        — the queued/streaming serving loop (see the method docstrings).

    ``close()`` releases engine-held workers (the persistent shard-probe
    pool, the verify-overlap thread) — call it when retiring a service
    on a long-lived serving host; GC of the engine does it too.
    """

    cfg: ArchConfig
    params: object
    rcfg: RetrievalConfig = field(default_factory=RetrievalConfig)

    engine: Optional[SearchEngine] = None
    rotation: Optional[jax.Array] = None
    db_words: Optional[np.ndarray] = None
    shift: Optional[np.ndarray] = None   # non-negativity shift, fit at build
    _queue: List[Tuple[Ticket, np.ndarray]] = field(default_factory=list)
    _next_qid: int = 0
    # guards _queue/_next_qid: submit may be called from many request
    # threads while run_queued drains (the streaming serving shape)
    _lock: threading.Lock = field(
        default_factory=threading.Lock, repr=False
    )
    # rolling submit->resolve latency over answered queries (ms)
    _latency: LatencyTracker = field(
        default_factory=LatencyTracker, repr=False
    )
    # jitted pooled-encoder forward, built once on first embed(): a fresh
    # @jax.jit closure per call would retrace+recompile on every batched
    # serving step (embed is the hot path of run_queued)
    _pooled: Optional[object] = field(default=None, repr=False)

    # ------------------------------------------------------------ encoding
    def _pooled_fn(self):
        """The jitted pooled forward (final-norm hidden states, not
        logits), built once and cached on the service."""
        if self._pooled is not None:
            return self._pooled
        from ..models import lm as lm_lib

        @jax.jit
        def pooled(tokens):
            h = lm_lib.embed_tokens(self.cfg, self.params, tokens)
            positions = jnp.arange(tokens.shape[1])
            window = (
                self.cfg.sliding_window if self.cfg.family == "hybrid" else 0
            )
            if self.cfg.first_k_dense:
                h, _ = lm_lib._apply_stack(
                    self.cfg.replace(n_experts=0),
                    self.params["front_layers"], h, positions,
                    window=window, moe=False,
                )
            h, _ = lm_lib._apply_stack(
                self.cfg, self.params["layers"], h, positions,
                window=window, moe=True,
            )
            from ..models.layers import apply_norm

            h = apply_norm(h, self.params["final_norm"], self.cfg.norm)
            return h.mean(axis=1).astype(jnp.float32)

        self._pooled = pooled
        return pooled

    def embed(self, token_batches: np.ndarray) -> np.ndarray:
        """(N, S) int32 tokens -> (N, d_model) float32 mean-pooled states."""
        pooled = self._pooled_fn()
        out = []
        B = self.rcfg.batch_size
        toks = np.asarray(token_batches, np.int32)
        for i in range(0, len(toks), B):
            chunk = toks[i : i + B]
            pad = B - len(chunk)
            if pad:
                chunk = np.pad(chunk, ((0, pad), (0, 0)))
            emb = np.asarray(pooled(jnp.asarray(chunk)))
            out.append(emb[: len(toks[i : i + B])])
        return np.concatenate(out, axis=0)

    def _shifted(self, x: np.ndarray, fit: bool) -> np.ndarray:
        """AQBC assumes non-negative data (SIFT/BoW regime); shift into the
        positive orthant per-dimension. The shift is FIT ON THE CORPUS and
        reused for queries — refitting per query would zero out single
        queries and break angle consistency."""
        if fit:
            self.shift = x.min(axis=0, keepdims=True)
        return np.maximum(x - self.shift, 0.0)

    # ------------------------------------------------------------ indexing
    def build_index(self, doc_tokens: np.ndarray) -> Dict[str, float]:
        x = self._shifted(self.embed(doc_tokens), fit=True)
        model = aqbc.learn(
            x, self.rcfg.code_bits, iters=self.rcfg.aqbc_iters
        )
        self.rotation = model.rotation
        bits = np.asarray(aqbc.encode(jnp.asarray(x), self.rotation))
        self.db_words = pack_bits(bits)
        shard_cfg: Dict[str, object] = {
            "mesh": self.rcfg.mesh,
            "num_shards": self.rcfg.num_shards,
            "shard_axes": self.rcfg.shard_axes,
            "devices": self.rcfg.devices,
        }
        cfg: Dict[str, object] = {}
        if self.rcfg.backend == "amih":
            cfg = {
                "m": self.rcfg.m_tables,
                "verify_backend": self.rcfg.verify_backend,
                "enumeration_cap": self.rcfg.enumeration_cap,
                "overlap_verify": self.rcfg.pipelined,
                "probe_backend": self.rcfg.probe_backend,
                "probe_stream_cap": self.rcfg.probe_stream_cap,
            }
        elif self.rcfg.backend == "linear_scan":
            cfg = {"compute_backend": self.rcfg.compute_backend}
        elif self.rcfg.backend == "single_table":
            cfg = {"enumeration_cap": self.rcfg.enumeration_cap}
        elif self.rcfg.backend == "sharded_scan":
            cfg = shard_cfg
        elif self.rcfg.backend == "sharded_amih":
            cfg = {
                **shard_cfg,
                "m": self.rcfg.m_tables,
                "verify_backend": self.rcfg.verify_backend,
                "enumeration_cap": self.rcfg.enumeration_cap,
                "probe_workers": self.rcfg.probe_workers,
                "probe_mode": self.rcfg.probe_mode,
                "probe_backend": self.rcfg.probe_backend,
                "probe_stream_cap": self.rcfg.probe_stream_cap,
            }
        backend = self.rcfg.backend
        if self.rcfg.cluster:
            # cross-host tier: the coordinator ships each worker its
            # host-partitioned slice; workers run the sharded flavor of
            # the configured backend (anything unsharded serves through
            # sharded_amih workers). Only JSON-serializable knobs cross
            # the wire — mesh/devices placement is re-derived per host.
            inner = backend if backend in ("sharded_amih", "sharded_scan") \
                else "sharded_amih"
            cfg = {
                "hosts": self.rcfg.hosts,
                "inner_backend": inner,
                "num_shards": self.rcfg.num_shards,
            }
            if inner == "sharded_amih":
                cfg.update(
                    m=self.rcfg.m_tables,
                    verify_backend=self.rcfg.verify_backend,
                    enumeration_cap=self.rcfg.enumeration_cap,
                    probe_backend=self.rcfg.probe_backend,
                    probe_stream_cap=self.rcfg.probe_stream_cap,
                )
            backend = "cluster"
        if self.rcfg.trace:
            from ..obs import trace as _obs_trace

            sample = (
                float(self.rcfg.trace)
                if isinstance(self.rcfg.trace, float) else 1.0
            )
            cfg["tracer"] = _obs_trace.Tracer(
                enabled=True, sample=sample, host="coordinator",
            )
        self.engine = make_engine(
            backend, self.db_words, self.rcfg.code_bits, **cfg
        )
        if (self.rcfg.backend == "sharded_amih" and not self.rcfg.cluster
                and self.rcfg.pipelined
                and self.rcfg.probe_workers is None):
            # pipelined default: one probe worker per (non-empty) shard
            self.engine.probe_workers = len(self.engine.indexes)
        index = getattr(self.engine, "index", None)
        return {
            "n_docs": float(len(doc_tokens)),
            "aqbc_objective": float(model.objective_trace[-1]),
            "m_tables": float(getattr(index, "m", 0)),
        }

    # -------------------------------------------------------------- search
    def encode_query(self, query_tokens: np.ndarray) -> np.ndarray:
        x = self.embed(
            query_tokens[None, :] if query_tokens.ndim == 1 else query_tokens
        )
        x = self._shifted(x, fit=False)
        bits = np.asarray(aqbc.encode(jnp.asarray(x), self.rotation))
        return pack_bits(bits)

    def search_batch(
        self, query_tokens: np.ndarray, k: int = 10
    ) -> Tuple[np.ndarray, np.ndarray, EngineStats]:
        """Exact angular KNN for a batch of queries through one
        ``knn_batch`` call. Returns (ids (B, k'), sims (B, k'), stats)."""
        assert self.engine is not None, "call build_index first"
        q_words = self.encode_query(query_tokens)
        return self.engine.knn_batch(q_words, k)

    def search(self, query_tokens: np.ndarray, k: int = 10):
        """Single-query convenience over ``search_batch`` (B=1).

        Returns (ids, sims, stats) where stats is the query's own counter
        object (AMIHStats / SearchStats — every backend provides one).
        """
        ids, sims, stats = self.search_batch(
            query_tokens[None, :] if query_tokens.ndim == 1 else query_tokens,
            k,
        )
        return ids[0], sims[0], stats.per_query[0]

    # ------------------------------------------------------ queued serving
    def submit(self, query_tokens: np.ndarray) -> Ticket:
        """Enqueue a query for the next batched search step (thread-safe).

        Returns a ``Ticket``: an int-compatible qid (old callers keep
        indexing result dicts with it) whose ``future`` resolves to this
        query's (ids, sims) when its batch step completes.
        """
        toks = np.asarray(query_tokens)
        with self._lock:
            ticket = Ticket(self._next_qid)
            self._next_qid += 1
            self._queue.append((ticket, toks))
        return ticket

    def queue_depth(self) -> int:
        """Queries currently waiting for a ``run_queued`` drain."""
        with self._lock:
            return len(self._queue)

    def run_queued(self, k: int = 10, stream: bool = False):
        """Drain the queue, ``search_batch_size`` queries per knn_batch
        step (the serving loop's batched shape).

        ``stream=False`` (default): blocks until the drain completes and
        returns qid -> (ids, sims), as before.

        ``stream=True``: returns an iterator of ``StepResult``s, one per
        batch step, yielded AS EACH STEP COMPLETES — step i+1 encodes on
        the device while step i searches (repro.pipeline.stream). Every
        step's ``EngineStats`` carries ``queue_depth`` and rolling
        p50/p99 ``latency_ms`` over answered queries (measured
        submit -> resolve); each answered ticket's future is resolved
        before its step is yielded.

        Queries submitted after the drain snapshot wait for the next
        ``run_queued`` call. If a step raises, unanswered queries are
        re-queued for a retry; their tickets' CURRENT futures fail with
        the step's exception (a blocked ``ticket.result()`` observes the
        dead drain instead of hanging) and are replaced with fresh ones
        that a successful retry drain resolves.
        """
        if stream:
            return self._run_queued_stream(k)
        out: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        for step in self._run_queued_stream(k):
            out.update(step.results)
        return out

    def _run_queued_stream(self, k: int) -> Iterator[StepResult]:
        assert self.engine is not None, "call build_index first"
        step_size = max(1, self.rcfg.search_batch_size)
        with self._lock:
            pending = self._queue
            self._queue = []
        steps = [
            pending[lo : lo + step_size]
            for lo in range(0, len(pending), step_size)
        ]
        done_steps = 0
        try:
            results = stream_search(
                self.engine,
                [np.stack([t for _, t in batch]) for batch in steps],
                k,
                encode=self.encode_query,
                stamp_latency=False,   # stamped below: submit -> resolve
            )
            for sr in results:
                now = time.perf_counter()
                batch = steps[sr.step]
                for row, (ticket, _) in enumerate(batch):
                    pair = (sr.ids[row], sr.sims[row])
                    sr.results[ticket.qid] = pair
                    self._latency.record(
                        1e3 * (now - ticket.submitted_at)
                    )
                    ticket.future.set_result(pair)
                # serving-level counters: true submit->resolve latency
                # and the queries still waiting behind this step
                sr.stats.latency_ms = self._latency.snapshot()
                sr.stats.queue_depth += self.queue_depth()
                done_steps += 1
                yield sr
        except GeneratorExit:
            # the CONSUMER abandoned the iterator early — nothing failed.
            # Re-queue the unanswered queries with their futures left
            # pending; the next drain resolves them.
            self._requeue(steps[done_steps:])
            raise
        except BaseException as exc:
            # a step actually died: unanswered queries go back to the
            # queue's front for a retry; their current futures FAIL (a
            # waiter blocked in ticket.result() must observe the dead
            # drain, not hang) and are replaced with fresh ones that the
            # retry drain resolves — futures are single-shot.
            requeued = self._requeue(steps[done_steps:])
            for ticket, _ in requeued:
                failed, ticket.future = ticket.future, Future()
                failed.set_exception(exc)
            raise

    def _requeue(self, unanswered_steps):
        """Push un-drained batches back onto the queue's front."""
        requeued = [item for batch in unanswered_steps for item in batch]
        with self._lock:
            self._queue[:0] = requeued
        return requeued

    def search_linear(self, query_tokens: np.ndarray, k: int = 10):
        """Exhaustive baseline over the same codes (cross-check)."""
        q_words = self.encode_query(query_tokens)[0]
        return linear_scan_knn(q_words, self.db_words, k)

    # ------------------------------------------------------------ lifecycle
    def close(self) -> None:
        """Release engine-held workers (persistent shard-probe pool,
        verify-overlap thread). Idempotent; safe before build_index."""
        engine, close = self.engine, getattr(self.engine, "close", None)
        if engine is not None and callable(close):
            close()
