"""The cross-host serving tier (repro.cluster): transport framing in
isolation (round-trips, partial reads, truncation, timeouts), the worker
frame loop in-process, coordinator failure semantics against stub
workers (request timeout -> degraded cluster), and the end-to-end
exactness contract over a REAL spawned localhost fleet — merged cluster
results bit-identical in sims to ``linear_scan_knn`` and bit-identical
in ids to single-host ``sharded_amih`` over the same plan, including
uneven N, K > per-host rows, and a worker SIGKILLed mid-stream (whose
tickets must FAIL promptly, never hang).

The spawned fleet is module-scoped (each worker is a fresh interpreter
importing jax — seconds per process), shared by every exactness test via
``workers=``; the kill test gets its own throwaway fleet.
"""

import json
import socket
import struct
import threading
import time

import numpy as np
import pytest

from repro.cluster import (
    ClusterDegradedError,
    FrameError,
    LocalCluster,
    RequestTimeoutError,
    WorkerDiedError,
    pack_ragged,
    recv_frame,
    send_frame,
    unpack_ragged,
)
from repro.cluster.worker import WorkerServer, stats_from_wire, \
    stats_to_wire
from repro.core import AMIHStats, linear_scan_knn, make_engine, pack_bits
from repro.core.engine import EngineStats
from repro.core.linear_scan import sims_against_db, sims_for_ids
from repro.core.single_table import SearchStats
from repro.data import synthetic_binary_codes, synthetic_queries
from repro.shard import ShardPlan


def _check_exact(ids, sims, qs, db, k_eff):
    """The repo-wide exactness convention: sims bit-identical to the
    scan; ids distinct and really carrying those sims (tie ORDER inside
    one Hamming tuple is the only permitted difference vs the scan)."""
    B = qs.shape[0]
    assert ids.shape == (B, k_eff) and sims.shape == (B, k_eff)
    for i in range(B):
        _, sims_l = linear_scan_knn(qs[i], db, k_eff)
        np.testing.assert_array_equal(sims[i], sims_l)
        np.testing.assert_array_equal(
            sims_for_ids(qs[i], db, ids[i]), sims[i]
        )
        assert len(set(ids[i].tolist())) == k_eff


# ============================================================= transport
def _pair():
    a, b = socket.socketpair()
    return a, b


def test_frame_roundtrip_meta_and_arrays():
    a, b = _pair()
    try:
        arrays = {
            "q": np.arange(12, dtype=np.uint32).reshape(3, 4),
            "floor": np.array([-np.inf, 0.25], dtype=np.float64),
            "empty": np.empty(0, dtype=np.int64),
        }
        send_frame(a, "search", {"req": 7, "k": 10}, arrays)
        kind, meta, got = recv_frame(b)
        assert kind == "search" and meta["req"] == 7 and meta["k"] == 10
        assert set(got) == set(arrays)
        for name, arr in arrays.items():
            assert got[name].dtype == arr.dtype
            np.testing.assert_array_equal(got[name], arr)
        # a bare frame (no meta, no arrays) round-trips too
        send_frame(a, "ping")
        kind, meta, got = recv_frame(b)
        assert kind == "ping" and meta == {} and got == {}
    finally:
        a.close(), b.close()


def test_frame_rejects_non_wire_dtype_before_sending():
    a, b = _pair()
    try:
        with pytest.raises(ValueError, match="non-wire dtype"):
            send_frame(a, "x", arrays={
                "bad": np.zeros(2, dtype=np.float16)
            })
        # nothing hit the wire: the socket would block on recv
        b.setblocking(False)
        with pytest.raises(BlockingIOError):
            b.recv(1)
    finally:
        a.close(), b.close()


def test_frame_partial_reads_and_short_writes():
    """TCP delivers byte dribbles, not frames: a sender trickling one
    byte at a time must still produce one intact frame on the reader."""
    a, b = _pair()
    try:
        payload = {"ids": np.arange(1000, dtype=np.int64)}
        cap = []
        orig = a.sendall

        class Dribble:
            def sendall(self, data):
                cap.append(bytes(data))

        fake = Dribble()
        send_frame(fake, "result", {"req": 1}, payload)
        (frame,) = cap

        def trickle():
            for i in range(0, len(frame), 1):
                orig(frame[i : i + 1])

        t = threading.Thread(target=trickle, daemon=True)
        t.start()
        kind, meta, got = recv_frame(b)
        t.join()
        assert kind == "result" and meta["req"] == 1
        np.testing.assert_array_equal(got["ids"], payload["ids"])
    finally:
        a.close(), b.close()


def test_frame_truncation_and_bad_magic_raise_frame_error():
    a, b = _pair()
    cap = []

    class Cap:
        def sendall(self, data):
            cap.append(bytes(data))

    send_frame(Cap(), "result", {"req": 1},
               {"ids": np.arange(64, dtype=np.int64)})
    (frame,) = cap
    try:
        a.sendall(frame[: len(frame) // 2])
        a.close()   # EOF mid-frame
        with pytest.raises(FrameError, match="mid-frame"):
            recv_frame(b)
    finally:
        b.close()
    a, b = _pair()
    try:
        a.sendall(b"NOPE" + frame[4:])
        with pytest.raises(FrameError, match="magic"):
            recv_frame(b)
    finally:
        a.close(), b.close()


def test_frame_rejects_negative_declared_shape():
    """A negative dim makes np.prod negative, which would slip under the
    MAX_PAYLOAD guard and reach np.frombuffer as a bad count — the
    receiver must reject it as a FrameError up front."""
    a, b = _pair()
    try:
        hdr = json.dumps({
            "kind": "result",
            "arrays": [{"name": "z", "dtype": "int64",
                        "shape": [-1, 1 << 40]}],
        }).encode()
        a.sendall(b"AMRP" + struct.pack(">I", len(hdr)) + hdr)
        with pytest.raises(FrameError, match="negative dimension"):
            recv_frame(b)
    finally:
        a.close(), b.close()


def test_recv_frame_timeout_bounds_idle_wait():
    a, b = _pair()
    try:
        t0 = time.perf_counter()
        with pytest.raises((socket.timeout, TimeoutError)):
            recv_frame(b, timeout=0.2)
        assert time.perf_counter() - t0 < 5.0
        # the socket is reusable after the timeout (deadline cleared)
        send_frame(a, "pong", {"seq": 3})
        kind, meta, _ = recv_frame(b, timeout=5.0)
        assert kind == "pong" and meta["seq"] == 3
    finally:
        a.close(), b.close()


def test_pack_unpack_ragged_roundtrip_and_validation():
    planes = [
        np.array([3, 1, 4], dtype=np.int64),
        np.empty(0, dtype=np.int64),
        np.array([1, 5], dtype=np.int64),
    ]
    flat, lens = pack_ragged(planes, dtype=np.int64)
    assert flat.tolist() == [3, 1, 4, 1, 5]
    assert lens.tolist() == [3, 0, 2]
    back = unpack_ragged(flat, lens)
    assert [p.tolist() for p in back] == [p.tolist() for p in planes]
    flat2, lens2 = pack_ragged([], dtype=np.float64)
    assert flat2.shape == (0,) and lens2.shape == (0,)
    with pytest.raises(FrameError, match="lengths sum"):
        unpack_ragged(flat, np.array([3, 1, 2], dtype=np.int64))


def test_stats_wire_roundtrip_mixed_kinds():
    st = EngineStats(
        backend="sharded_amih", queries=2,
        per_query=[AMIHStats(probes=3, tuples_processed=7), SearchStats()],
        shards=2, per_shard=[{"shard": 0, "rows": 5}],
        cache_info={"hits": 1},
    )
    back = stats_from_wire(stats_to_wire(st))
    assert back.backend == st.backend and back.queries == 2
    assert isinstance(back.per_query[0], AMIHStats)
    assert isinstance(back.per_query[1], SearchStats)
    assert back.per_query[0].tuples_processed == 7
    assert back.per_shard == st.per_shard
    assert back.cache_info == st.cache_info


# ====================================================== worker, in-process
def test_worker_frame_loop_in_process():
    """One WorkerServer driven over raw frames: build -> ready, a bounded
    search returning exact global-id planes, bound frames published when
    queries fill k, and a live remote bound applied without error."""
    p, n, B, k = 64, 600, 4, 5
    db_bits = synthetic_binary_codes(n, p, seed=20)
    db = pack_bits(db_bits)
    qs = pack_bits(synthetic_queries(db_bits, B, seed=21))
    # the worker serves the SECOND half of a 2-host partition: its ids
    # must come back global with no coordinator-side fixup
    plan = ShardPlan.balanced(n, 4)
    sub = plan.host_partition(2)[1]
    srv = WorkerServer("127.0.0.1", 0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    sock = socket.create_connection(srv.addr, timeout=30)
    try:
        send_frame(sock, "build", {
            "host": 1, "p": p, "backend": "sharded_amih",
            "plan": sub.summary(), "cfg": {},
        }, {"db": db[sub.base : sub.base + sub.n]})
        kind, meta, _ = recv_frame(sock, timeout=60)
        assert kind == "ready"
        assert meta["host"] == 1 and meta["n"] == sub.n
        send_frame(sock, "search", {"req": 0, "k": k}, {
            "q": qs, "floor": np.full(B, -np.inf),
        })
        bounds, result = [], None
        while result is None:
            kind, meta, arrays = recv_frame(sock, timeout=60)
            if kind == "bound":
                assert meta["req"] == 0
                bounds.append((int(arrays["qi"][0]),
                               float(arrays["val"][0])))
                # echo it back: a live bound mid-search must be absorbed
                send_frame(sock, "bound", {"req": 0}, {
                    "qi": arrays["qi"].copy(), "val": arrays["val"].copy(),
                })
            elif kind == "result":
                result = (meta, arrays)
        meta, arrays = result
        ids = unpack_ragged(arrays["ids"], arrays["lens"])
        sims = unpack_ragged(arrays["sims"], arrays["lens"])
        slab = db[sub.base : sub.base + sub.n]
        for i in range(B):
            assert sims[i].shape[0] >= k        # full local fill
            _, sims_l = linear_scan_knn(qs[i], slab, k)
            np.testing.assert_array_equal(sims[i][:k], sims_l)
            assert (ids[i] >= sub.base).all()   # global ids
            np.testing.assert_array_equal(
                sims_for_ids(qs[i], db, ids[i]), sims[i]
            )
        # every query filled k local rows -> every query published a
        # bound at least once, and re-publishes only RAISE it (each
        # successive shard can tighten the local k-th)
        assert {qi for qi, _ in bounds} == set(range(B))
        last = {}
        for qi, val in bounds:
            assert val > last.get(qi, -np.inf)
            last[qi] = val
        for i in range(B):
            assert last[i] == sims[i][k - 1]    # final bound = local kth
        st = stats_from_wire(meta["stats"])
        assert st.queries == B and st.shards == sub.num_shards
    finally:
        sock.close()
        srv.close()
        t.join(timeout=10)


def test_worker_survives_malformed_frame_content():
    """A well-framed build whose CONTENT is garbage (missing meta keys)
    must tear down that connection only — the documented failure unit —
    and the server keeps accepting, never dying with the exception."""
    srv = WorkerServer("127.0.0.1", 0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        sock = socket.create_connection(srv.addr, timeout=10)
        try:
            send_frame(sock, "build", {"host": 0})   # no backend/plan/p
            with pytest.raises(FrameError):          # conn torn down
                recv_frame(sock, timeout=10)
        finally:
            sock.close()
        # the process survived: a fresh connection still gets service
        sock = socket.create_connection(srv.addr, timeout=10)
        try:
            send_frame(sock, "ping", {"seq": 9})
            kind, meta, _ = recv_frame(sock, timeout=10)
            assert kind == "pong" and meta["seq"] == 9
        finally:
            sock.close()
    finally:
        srv.close()
        t.join(timeout=10)


# ============================================= coordinator failure semantics
class _StubWorker:
    """Protocol-correct worker that never answers searches: replies
    ready/pong so the build succeeds and heartbeats stay green, then
    swallows every search frame — the pure request-timeout case."""

    def __init__(self):
        self._srv = socket.create_server(("127.0.0.1", 0))
        self.addr = self._srv.getsockname()[:2]
        self.searches = 0
        self._t = threading.Thread(target=self._loop, daemon=True)
        self._t.start()

    def _loop(self):
        try:
            conn, _ = self._srv.accept()
        except OSError:
            return
        try:
            while True:
                kind, meta, _ = recv_frame(conn)
                if kind == "build":
                    send_frame(conn, "ready", {
                        "host": meta.get("host", 0),
                        "n": meta["plan"]["n"],
                        "shards": meta["plan"]["num_shards"],
                    })
                elif kind == "ping":
                    send_frame(conn, "pong", {"seq": meta.get("seq", 0)})
                elif kind == "search":
                    self.searches += 1   # ...and never answer
                elif kind == "close":
                    return
        except (FrameError, OSError):
            pass
        finally:
            conn.close()

    def close(self):
        self._srv.close()
        self._t.join(timeout=5)


def test_heartbeat_clock_restarts_after_slow_build():
    """Regression: last_seen is stamped at socket-connect time, but a
    build (slab transfer + engine construction) can take minutes — the
    coordinator must restart the staleness clock at init, or the first
    heartbeat check marks every worker dead before any ping is sent."""
    from repro.cluster.coordinator import ClusterCoordinator, \
        _WorkerHandle

    a, b = _pair()
    stop = threading.Event()

    def ponger():
        try:
            while not stop.is_set():
                kind, meta, _ = recv_frame(b)
                if kind == "ping":
                    send_frame(b, "pong", {"seq": meta.get("seq", 0)})
        except (FrameError, OSError):
            pass

    t = threading.Thread(target=ponger, daemon=True)
    t.start()
    h = _WorkerHandle(0, ("127.0.0.1", 0), a)
    h.last_seen -= 60.0                # pretend build took a minute
    coord = ClusterCoordinator(
        [h], ShardPlan.balanced(10, 1), heartbeat=0.1
    )
    try:
        time.sleep(1.0)                # ~10 beats: any stale clock trips
        assert h.alive
    finally:
        stop.set()
        coord.close()
        t.join(timeout=5)


class _GarbageResultWorker:
    """Answers the build correctly, then replies to a search with a
    WELL-FRAMED result whose stats rows don't decode (unexpected field
    -> TypeError in stats_from_wire) — the escape path that bypasses
    FrameError/OSError in the coordinator's reader."""

    def __init__(self):
        self._srv = socket.create_server(("127.0.0.1", 0))
        self.addr = self._srv.getsockname()[:2]
        self._t = threading.Thread(target=self._loop, daemon=True)
        self._t.start()

    def _loop(self):
        try:
            conn, _ = self._srv.accept()
        except OSError:
            return
        try:
            while True:
                kind, meta, arrays = recv_frame(conn)
                if kind == "build":
                    send_frame(conn, "ready", {
                        "host": meta.get("host", 0),
                        "n": meta["plan"]["n"],
                        "shards": meta["plan"]["num_shards"],
                    })
                elif kind == "ping":
                    send_frame(conn, "pong", {"seq": meta.get("seq", 0)})
                elif kind == "search":
                    B = arrays["q"].shape[0]
                    send_frame(conn, "result", {
                        "req": meta["req"],
                        "stats": {"per_query": [
                            {"_kind": "AMIHStats", "no_such_counter": 1}
                        ]},
                    }, {
                        "ids": np.zeros(0, dtype=np.int64),
                        "sims": np.zeros(0, dtype=np.float64),
                        "lens": np.zeros(B, dtype=np.int64),
                    })
                elif kind == "close":
                    return
        except (FrameError, OSError):
            pass
        finally:
            conn.close()

    def close(self):
        self._srv.close()
        self._t.join(timeout=5)


def test_corrupt_result_fails_request_fast_not_timeout():
    """A result the reader can't decode must fail the in-flight request
    IMMEDIATELY via _mark_dead, not silently kill the reader thread and
    leave the request to sit out the full request_timeout."""
    p, n = 64, 200
    db = pack_bits(synthetic_binary_codes(n, p, seed=24))
    qs = pack_bits(synthetic_queries(
        synthetic_binary_codes(n, p, seed=24), 2, seed=25))
    stub = _GarbageResultWorker()
    try:
        eng = make_engine(
            "cluster", db, p, workers=[stub.addr],
            request_timeout=60.0, heartbeat=0.4,
        )
        try:
            t0 = time.perf_counter()
            with pytest.raises(WorkerDiedError):
                eng.knn_batch(qs, 3)
            # via the reader's death, NOT the 60 s request timeout
            assert time.perf_counter() - t0 < 20.0
        finally:
            eng.close()
    finally:
        stub.close()


def test_request_timeout_degrades_silent_worker():
    p, n = 64, 200
    db = pack_bits(synthetic_binary_codes(n, p, seed=22))
    qs = pack_bits(synthetic_queries(
        synthetic_binary_codes(n, p, seed=22), 2, seed=23))
    stub = _StubWorker()
    try:
        eng = make_engine(
            "cluster", db, p, workers=[stub.addr],
            request_timeout=1.5, heartbeat=0.4,
        )
        try:
            t0 = time.perf_counter()
            with pytest.raises(RequestTimeoutError, match="timed out"):
                eng.knn_batch(qs, 3)
            assert time.perf_counter() - t0 < 30.0   # bounded, no hang
            assert stub.searches == 1
            # the timed-out handle's socket is CLOSED (not just flagged
            # dead): the stub's serving loop sees EOF and exits, instead
            # of lingering until eng.close() with a parked reader
            stub._t.join(timeout=10.0)
            assert not stub._t.is_alive()
            # the silent worker is OUT: the cluster fails fast now
            # instead of re-timing-out every request
            with pytest.raises(ClusterDegradedError):
                eng.knn_batch(qs, 3)
        finally:
            eng.close()
    finally:
        stub.close()


# ===================================================== e2e: spawned fleet
HOSTS = 3


@pytest.fixture(scope="module")
def fleet():
    """One spawned 3-worker localhost fleet for every e2e test here
    (workers accept a new coordinator per engine, so engines can come
    and go while the processes live for the whole module)."""
    fl = LocalCluster(HOSTS)
    yield fl
    fl.close()


@pytest.fixture(scope="module")
def corpus():
    p, n = 64, 997                     # prime N: uneven shards everywhere
    db_bits = synthetic_binary_codes(n, p, seed=0)
    return p, pack_bits(db_bits), db_bits


@pytest.mark.parametrize("B", [1, 8, 64])
def test_cluster_exact_vs_scan_and_single_host(fleet, corpus, B):
    """The acceptance contract: merged cluster results carry exactly the
    scan's sims AND exactly the ids single-host sharded_amih produces
    over the same plan (the lexsort merge commutes with partitioning)."""
    p, db, db_bits = corpus
    k, S = 10, 5                       # 5 shards over 3 hosts: runs 2/2/1
    qs = pack_bits(synthetic_queries(db_bits, B, seed=B))
    eng = make_engine("cluster", db, p, workers=fleet.addresses,
                      num_shards=S)
    try:
        ids, sims, stats = eng.knn_batch(qs, k)
    finally:
        eng.close()
    _check_exact(ids, sims, qs, db, k)
    single = make_engine("sharded_amih", db, p, num_shards=S)
    ids_1, sims_1, _ = single.knn_batch(qs, k)
    np.testing.assert_array_equal(ids, ids_1)
    np.testing.assert_array_equal(sims, sims_1)
    # per-host attribution covers the whole fleet and all the rows
    assert len(stats.per_host) == HOSTS
    assert sum(h["rows"] for h in stats.per_host) == db.shape[0]
    assert sum(h["shards"] for h in stats.per_host) == S
    assert all(h["rpc_ms"] >= 0 for h in stats.per_host)
    assert [h["platform"] for h in stats.per_host] == ["cpu"] * HOSTS
    assert stats.queries == B and len(stats.per_query) == B


def test_local_worker_runs_on_the_platform_it_is_given(corpus, monkeypatch):
    """LocalCluster gives each worker the CPU in the child itself: a
    parent environment naming an accelerator this host lacks does not
    reach the worker, which runs on the CPU and says so."""
    p, db, db_bits = corpus
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    fl = LocalCluster(1)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    qs = pack_bits(synthetic_queries(db_bits, 4, seed=5))
    try:
        eng = make_engine("cluster", db, p, workers=fl.addresses,
                          num_shards=2)
        try:
            ids, sims, stats = eng.knn_batch(qs, 10)
        finally:
            eng.close()
    finally:
        fl.close()
    _check_exact(ids, sims, qs, db, 10)
    assert [h["platform"] for h in stats.per_host] == ["cpu"]


def test_cluster_k_exceeds_per_host_rows(fleet):
    """K larger than any single host's slice: hosts return short planes
    (and stay silent on the bound channel), the union still covers k."""
    p, n, k = 64, 50, 40               # ~17 rows/host, k=40
    db_bits = synthetic_binary_codes(n, p, seed=2)
    db = pack_bits(db_bits)
    qs = pack_bits(synthetic_queries(db_bits, 4, seed=3))
    eng = make_engine("cluster", db, p, workers=fleet.addresses,
                      num_shards=HOSTS)
    try:
        ids, sims, _ = eng.knn_batch(qs, k)
        _check_exact(ids, sims, qs, db, k)
        # k > n clamps to n (the union is the whole DB)
        ids, sims, _ = eng.knn_batch(qs, 99)
        _check_exact(ids, sims, qs, db, n)
    finally:
        eng.close()


def test_cluster_bound_broadcast_reaches_other_hosts(fleet, corpus):
    """The cross-host floor is not decorative: after a batch, the
    coordinator has rebroadcast raised bounds to peers (bound_frames
    move), and priming never breaks exactness (prime_bound on/off
    agree bit-identically)."""
    p, db, db_bits = corpus
    qs = pack_bits(synthetic_queries(db_bits, 8, seed=40))
    eng = make_engine("cluster", db, p, workers=fleet.addresses,
                      num_shards=6)
    try:
        ids_a, sims_a, stats = eng.knn_batch(qs, 10)
        assert sum(h["bound_frames"] for h in stats.per_host) > 0
    finally:
        eng.close()
    unprimed = make_engine("cluster", db, p, workers=fleet.addresses,
                           num_shards=6, prime_bound=False)
    try:
        ids_b, sims_b, _ = unprimed.knn_batch(qs, 10)
    finally:
        unprimed.close()
    np.testing.assert_array_equal(ids_a, ids_b)
    np.testing.assert_array_equal(sims_a, sims_b)


def test_cluster_exact_when_floor_equals_kth_with_tie_group(fleet):
    """Regression: exactly-tied probing tuples can round 1 ulp apart in
    float64, so a worker's strictly-below stop may fire mid-tie-group
    and drop rows AT the floor. With the primed floor equal to the true
    k-th (the sample covers the whole DB at this n) and two DB rows
    exactly at it, the merge must still produce the scan's sims — the
    coordinator keeps the bound-justifying sample rows in the pool."""
    p, n, k, seed = 128, 186, 6, 1994142471
    db_bits = synthetic_binary_codes(n, p, seed=seed)
    db = pack_bits(db_bits)
    qs = pack_bits(synthetic_queries(db_bits, 8, seed=seed + 1))
    eng = make_engine("cluster", db, p, workers=fleet.addresses,
                      num_shards=3)
    try:
        ids, sims, _ = eng.knn_batch(qs, k)
    finally:
        eng.close()
    _check_exact(ids, sims, qs, db, k)
    # query 6 is the tie witness: its k-th sim repeats at the floor
    scan_sims = np.sort(sims_against_db(qs[6], db))[::-1]
    assert scan_sims[k - 1] == scan_sims[k - 2] or \
        (scan_sims == scan_sims[k - 1]).sum() > 1


def test_killed_worker_fails_tickets_and_degrades_cluster():
    """A worker SIGKILLed mid-stream: the in-flight step's tickets FAIL
    with a ClusterError promptly (no hang), unanswered queries are
    re-queued, and the degraded cluster fast-fails afterwards."""
    from repro.cluster import ClusterError
    from repro.serve.retrieval import RetrievalConfig, RetrievalService

    p, n, B, k = 64, 1200, 12, 5
    db_bits = synthetic_binary_codes(n, p, seed=50)
    db = pack_bits(db_bits)
    qs = pack_bits(synthetic_queries(db_bits, B, seed=51))
    fl = LocalCluster(2)
    eng = None
    try:
        eng = make_engine("cluster", db, p, workers=fl.addresses,
                          num_shards=2, request_timeout=60.0)
        svc = RetrievalService(
            cfg=None, params=None,
            rcfg=RetrievalConfig(search_batch_size=4),  # 12 q -> 3 steps
        )
        svc.engine = eng
        # identity "encoder" over pre-packed codes, gated so steps after
        # the first cannot reach their search until the kill has landed
        # (otherwise the fast steps race the signal and all complete)
        gate = threading.Event()
        calls = [0]

        def encode(toks):
            if calls[0] > 0:
                assert gate.wait(timeout=30.0)
            calls[0] += 1
            return np.asarray(toks)

        svc.encode_query = encode
        tickets = [svc.submit(qs[i]) for i in range(B)]
        futures = [t.future for t in tickets]   # snapshot pre-requeue
        stream = svc.run_queued(k, stream=True)
        first = next(stream)                    # step 0 answered cleanly
        assert len(first.results) == 4
        fl.kill_worker(1)                       # SIGKILL mid-stream
        gate.set()                              # release steps 1, 2
        t0 = time.perf_counter()
        with pytest.raises(ClusterError):
            for _ in stream:
                pass
        assert time.perf_counter() - t0 < 30.0  # failed, didn't hang
        # step 0's tickets resolved; every later ticket's ORIGINAL
        # future fails with the step's ClusterError and the query is
        # back in the queue for a retry drain
        for f in futures[:4]:
            ids, sims = f.result(timeout=1)
            assert ids.shape == (k,)
        failed = [f for f in futures[4:]
                  if isinstance(f.exception(timeout=10), ClusterError)]
        assert len(failed) == B - 4
        assert svc.queue_depth() == B - 4
        # the cluster stays degraded: fail-fast, not retry-and-timeout
        with pytest.raises(ClusterDegradedError):
            eng.knn_batch(qs[:2], k)
    finally:
        if eng is not None:
            eng.close()
        fl.close()


def test_cluster_engine_spawns_and_owns_local_fleet():
    """The no-workers path: build spawns its own LocalCluster and close
    tears it down (the smoke/launcher shape, kept under test here)."""
    p, n = 64, 300
    db_bits = synthetic_binary_codes(n, p, seed=60)
    db = pack_bits(db_bits)
    qs = pack_bits(synthetic_queries(db_bits, 2, seed=61))
    eng = make_engine("cluster", db, p, hosts=2, num_shards=2)
    procs = list(eng._fleet.procs)
    try:
        ids, sims, _ = eng.knn_batch(qs, 3)
        _check_exact(ids, sims, qs, db, 3)
        assert all(pr.is_alive() for pr in procs)
    finally:
        eng.close()
    assert not any(pr.is_alive() for pr in procs)
