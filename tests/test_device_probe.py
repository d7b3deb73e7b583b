"""Device-resident probing walk (core.probe_device + kernels.device_probe).

The fused probe -> bucket-lookup -> verify launch must be bit-identical
to the host reference walk (ids AND sims) and exact vs linear scan (sims
up to in-tuple ties), across every entry point that can select it, in
ONE walk launch per batch: every z-group rides its schedule-stack row of
one ``lax.while_loop``, and the queries it leaves undone take ONE scan
fallback launch. The walk's per-query counters equal the values the
position-map walk it replaced gave on the same data (pinned below).
"""

import numpy as np
import pytest

from repro.core import AMIHIndex, AMIHStats, linear_scan_knn, pack_bits
from repro.core.engine import make_engine
from repro.core.linear_scan import sims_for_ids
from repro.core.probe_device import (
    MAX_OFFSET_BYTES,
    build_device_csr,
    get_schedule,
    schedule_cache_clear,
    schedule_cache_info,
)
from repro.data import synthetic_binary_codes, synthetic_queries
from repro.kernels import ops
from repro.obs.metrics import REGISTRY as _REG


def _make_data(n, p, B, seed=0, clustered=False):
    rng = np.random.default_rng(seed)
    if clustered:
        # queries a few flips away from db rows: small probing radii, so
        # the precompiled stream covers the walk without the scan fallback
        base = rng.integers(0, 2, size=(n, p)).astype(np.uint8)
        picks = rng.integers(0, n, size=B)
        q_bits = base[picks].copy()
        for i in range(B):
            flips = rng.choice(p, size=3, replace=False)
            q_bits[i, flips] ^= 1
        return pack_bits(base), pack_bits(q_bits)
    db_bits = rng.integers(0, 2, size=(n, p)).astype(np.uint8)
    q_bits = rng.integers(0, 2, size=(B, p)).astype(np.uint8)
    return pack_bits(db_bits), pack_bits(q_bits)


def _check_vs_scan(q, db, ids, sims, k):
    """Exactness up to in-tuple ties: the sim multiset matches linear
    scan (1-ulp tolerance — the scan factors sqrt(z)*sqrt(|x|) where the
    tuple path takes one sqrt of the product) and every returned id
    really carries the sim it came with."""
    B = ids.shape[0]
    for b in range(B):
        _, sims_l = linear_scan_knn(q[b], db, k)
        np.testing.assert_allclose(sims[b], sims_l, atol=1e-9)
        np.testing.assert_allclose(
            sims_for_ids(q[b], db, ids[b].astype(np.int64)), sims[b],
            atol=1e-9,
        )


def _clustered(n, p, B, seed):
    """Clustered codes (the cluster model) and queries a few flips from
    stored codes: walks that mostly finish, with some bails at K >= 10."""
    db_bits = synthetic_binary_codes(n, p, seed=seed, mode="clustered")
    q = pack_bits(synthetic_queries(db_bits, B, seed=seed + 1))
    return pack_bits(db_bits), q


def _pair(db, p, **kw):
    host = AMIHIndex.build(db, p, probe_backend="host", **kw)
    dev = AMIHIndex.build(db, p, probe_backend="device", **kw)
    return host, dev


# ------------------------------------------------------------- exactness
@pytest.mark.parametrize(
    "p,B,n,k",
    [(32, 1, 300, 5), (32, 8, 300, 5), (64, 8, 500, 10),
     (64, 64, 500, 10), (128, 8, 300, 7)],
)
def test_device_bit_identical_to_host_and_scan(p, B, n, k):
    db, q = _make_data(n, p, B, seed=p + B)
    host, dev = _pair(db, p)
    ih, sh = host.knn_batch(q, k)
    id_, sd = dev.knn_batch(q, k)
    np.testing.assert_array_equal(ih, id_)
    np.testing.assert_array_equal(sh, sd)
    _check_vs_scan(q, db, id_, sd, k)


def test_zero_norm_queries():
    p, n, k = 64, 400, 6
    db, q = _make_data(n, p, 8, seed=3)
    q[0] = 0                      # zero query: Hamming-order fallback
    q[3] = 0
    host, dev = _pair(db, p)
    ih, sh = host.knn_batch(q, k)
    id_, sd = dev.knn_batch(q, k)
    np.testing.assert_array_equal(ih, id_)
    np.testing.assert_array_equal(sh, sd)
    _check_vs_scan(q, db, id_, sd, k)


def test_k_exceeds_bucket_yields():
    # k = n forces the walk past every bucket the early tuples yield
    p, n = 32, 120
    db, q = _make_data(n, p, 4, seed=11)
    host, dev = _pair(db, p)
    ih, sh = host.knn_batch(q, n)
    id_, sd = dev.knn_batch(q, n)
    np.testing.assert_array_equal(ih, id_)
    np.testing.assert_array_equal(sh, sd)
    _check_vs_scan(q, db, id_, sd, n)


def test_truncated_stream_falls_back_to_scan():
    p, n, k = 64, 400, 5
    db, q = _make_data(n, p, 8, seed=5)
    host = AMIHIndex.build(db, p, probe_backend="host")
    dev = AMIHIndex.build(db, p, probe_backend="device",
                          probe_stream_cap=64)
    before = _REG.value("launches.scan_topk")
    stats = [AMIHStats() for _ in range(q.shape[0])]
    ih, sh = host.knn_batch(q, k)
    id_, sd = dev.knn_batch(q, k, stats=stats)
    np.testing.assert_array_equal(sh, sd)
    _check_vs_scan(q, db, id_, sd, k)
    assert _REG.value("launches.scan_topk") - before == 1
    assert any(st.fell_back_to_scan for st in stats)


def test_bounded_path_matches_host():
    p, n, k = 64, 500, 8
    db, q = _make_data(n, p, 16, seed=21)
    host, dev = _pair(db, p)
    for bound in (-np.inf, 0.4, 1.01):
        bounds = np.full(q.shape[0], bound)
        rh = host.knn_batch_bounded(q, k, stop_below=bounds)
        rd = dev.knn_batch_bounded(q, k, stop_below=bounds)
        for (hi, hs), (di, ds) in zip(rh, rd):
            np.testing.assert_array_equal(hi, di)
            np.testing.assert_array_equal(hs, ds)


def _one_z(q, p):
    """``q`` with every row cut or padded to the popcount of row 0: a
    batch of one z-group."""
    bits = np.unpackbits(q.view(np.uint8), axis=1,
                         bitorder="little")[:, :p].copy()
    z = int(bits[0].sum())
    for row in bits:
        ones = np.flatnonzero(row)
        zeros = np.flatnonzero(row == 0)
        if ones.size > z:
            row[ones[z:]] = 0
        else:
            row[zeros[: z - ones.size]] = 1
    return pack_bits(bits)


# -------------------------------------------------------- launch economy
@pytest.mark.parametrize("one_group", [False, True])
def test_one_walk_launch_per_batch(one_group):
    """Mixed-z batches and single-z batches alike: ONE walk launch, and
    at most ONE scan fallback launch for the bailed queries of every
    group."""
    p, n, k = 64, 2000, 5
    db, q = _make_data(n, p, 32, seed=9, clustered=True)
    if one_group:
        q = _one_z(q, p)
    dev = AMIHIndex.build(db, p, probe_backend="device")
    groups = len(np.unique(np.bitwise_count(q).sum(axis=1)))
    assert (groups == 1) == one_group
    walk0 = _REG.value("launches.device_probe")
    scan0 = _REG.value("launches.scan_topk")
    id_, sd = dev.knn_batch(q, k)
    assert _REG.value("launches.device_probe") - walk0 == 1
    # the cross-group scan fallback fires at most ONCE for the whole
    # batch (covering only bailed queries): O(1) launches per batch total
    assert _REG.value("launches.scan_topk") - scan0 <= 1
    ih, sh = AMIHIndex.build(db, p, probe_backend="host").knn_batch(q, k)
    np.testing.assert_array_equal(ih, id_)
    np.testing.assert_array_equal(sh, sd)


@pytest.mark.parametrize("p,B,one_group", [
    (32, 1, True), (32, 8, False), (64, 8, False), (64, 64, False),
    (128, 8, False), (64, 8, True), (128, 8, True),
])
def test_fused_batch_parity_and_single_launch(p, B, one_group):
    """Mixed-z and single-z batches: the fused walk is ONE launch per
    batch and bit-identical (ids AND sims) to the host walk."""
    n, k = 600, 7
    db, q = _make_data(n, p, B, seed=p + 2 * B)
    if one_group:
        q = _one_z(q, p)
    host = AMIHIndex.build(db, p, probe_backend="host")
    fused = AMIHIndex.build(db, p, probe_backend="device")
    walk0 = _REG.value("launches.device_probe")
    scan0 = _REG.value("launches.scan_topk")
    if_, sf = fused.knn_batch(q, k)
    assert _REG.value("launches.device_probe") - walk0 == 1
    assert _REG.value("launches.scan_topk") - scan0 <= 1
    ih, sh = host.knn_batch(q, k)
    np.testing.assert_array_equal(ih, if_)
    np.testing.assert_array_equal(sh, sf)
    _check_vs_scan(q, db, if_, sf, k)


def test_batched_trace_counts_bounded():
    """Varying z-histograms across batches must NOT retrace the fused
    kernels: the schedule stack pads its group count and stream length
    to power-of-two buckets, so once a set of z values is resident, any
    mix of them traces nothing new."""
    p, n, k = 64, 800, 5
    db, _ = _make_data(n, p, 1, seed=23)
    dev = AMIHIndex.build(db, p, probe_backend="device")
    rng = np.random.default_rng(29)
    support = [28, 30, 32, 34, 36]

    def batch_with_zs(zs):
        bits = np.zeros((len(zs), p), dtype=np.uint8)
        for i, z in enumerate(zs):
            bits[i, rng.choice(p, size=z, replace=False)] = 1
        return pack_bits(bits)

    # warmup: every z of the support enters the stack; this call pays
    # the trace (and any stack growth / commit)
    dev.knn_batch(batch_with_zs(support + support[:3]), k)
    before = _REG.values("traces.")
    scans_before = ops.scan_topk._cache_size()
    for seed in range(5):
        r = np.random.default_rng(100 + seed)
        # a different histogram over the SAME support each batch
        zs = r.choice(support, size=8, p=np.roll(
            [0.4, 0.3, 0.15, 0.1, 0.05], seed
        ))
        dev.knn_batch(batch_with_zs(zs), k)
    after = _REG.values("traces.")
    walk = "traces.device_probe_walk_batched"
    assert after.get(walk, 0) == before.get(walk, 0)
    # the scan fallback pads the BAILED subset to a power-of-two bucket
    # of at least 8 rows, so a batch of 8 traces at most one shape
    assert ops.scan_topk._cache_size() - scans_before <= 1


def test_schedule_cache_shared_across_indexes():
    schedule_cache_clear()
    p = 32
    db1, q = _make_data(200, p, 4, seed=1)
    db2, _ = _make_data(300, p, 4, seed=2)
    a = AMIHIndex.build(db1, p, probe_backend="device")
    b = AMIHIndex.build(db2, p, probe_backend="device")
    a.knn_batch(q, 3)
    entries_after_first = schedule_cache_info()[0]
    b.knn_batch(q, 3)  # same (p, m, widths, z) keys: no new entries
    assert schedule_cache_info()[0] == entries_after_first
    widths = tuple(int(w) for w in a.device_csr["widths"])
    sched = get_schedule(p, a.m, widths, int(
        np.bitwise_count(q[0]).sum()), a.probe_stream_cap)
    assert sched.p == p and sched.s_len > 0


@pytest.mark.parametrize("m", [1, 2])
def test_csr_rejects_oversized_substrings(m):
    # one 64-bit table would need a 2^64-slot offsets array, two 32-bit
    # tables 32 GiB of them: both need sparse offsets
    db, _ = _make_data(100, 64, 1, seed=4)
    idx = AMIHIndex.build(db, 64, m=m)
    with pytest.raises(ValueError, match="substrings.*sparse"):
        build_device_csr(idx)


def test_csr_admits_the_papers_m_at_p64():
    """m = 3 at p = 64 (22-, 21- and 21-bit substrings, the paper's
    m = p / log2 n at n = 2^24): 48 MiB of dense offsets, under the
    bound, and the walk answers exactly through them."""
    p, n, B, k = 64, 3000, 16, 10
    db, q = _clustered(n, p, B, seed=31)
    ih, sh = AMIHIndex.build(db, p, m=3).knn_batch(q, k)
    dev = AMIHIndex.build(db, p, m=3, probe_backend="device")
    csr = dev.device_csr
    assert csr["widths"] == (22, 21, 21)
    assert 4 * 3 * ((1 << 22) + 1) <= MAX_OFFSET_BYTES
    id_, sd = dev.knn_batch(q, k)
    np.testing.assert_array_equal(ih, id_)
    np.testing.assert_array_equal(sh, sd)
    _check_vs_scan(q, db, id_, sd, k)


# ------------------------------------------------------------ entry points
def test_engine_entry_points():
    p, n, B, k = 64, 600, 16, 7
    db, q = _make_data(n, p, B, seed=7)
    ih, sh, _ = make_engine(
        "amih", db, p, m=4, probe_backend="host").knn_batch(q, k)
    id_, sd, _ = make_engine(
        "amih", db, p, m=4, probe_backend="device").knn_batch(q, k)
    np.testing.assert_array_equal(ih, id_)
    np.testing.assert_array_equal(sh, sd)
    # pipelined engine: overlap_verify is a no-op on the device path
    ip, sp, _ = make_engine(
        "amih", db, p, m=4, probe_backend="device", overlap_verify=True,
    ).knn_batch(q, k)
    np.testing.assert_array_equal(ip, id_)
    np.testing.assert_array_equal(sp, sd)


def test_sharded_entry_point_records_backend_and_stands_down():
    p, n, B, k = 64, 600, 16, 7
    db, q = _make_data(n, p, B, seed=13)
    eng_h = make_engine("sharded_amih", db, p, num_shards=3, m=4,
                        probe_backend="host")
    eng_d = make_engine("sharded_amih", db, p, num_shards=3, m=4,
                        probe_backend="device")
    ih, sh, st_h = eng_h.knn_batch(q, k)
    id_, sd, st_d = eng_d.knn_batch(q, k)
    np.testing.assert_array_equal(ih, id_)
    np.testing.assert_array_equal(sh, sd)
    assert all(ps["probe_backend"] == "device" for ps in st_d.per_shard)
    assert all(ps["probe_backend"] == "host" for ps in st_h.per_shard)
    # no host probing loop left: the worker pool never engages
    eng_d.probe_workers = 8
    assert not eng_d._use_parallel(64)


def test_shard_pool_collapses_to_inline_for_device_indexes():
    from repro.pipeline.shardpool import PersistentShardPool, SharedBound

    p, n, B, k = 64, 600, 8, 5
    db, q = _make_data(n, p, B, seed=17)
    eng = make_engine("sharded_amih", db, p, num_shards=3, m=4,
                      probe_backend="device")
    pool = PersistentShardPool(eng.indexes, AMIHStats, max_workers=4,
                               mode="process")
    try:
        assert len(pool.groups) == 1      # stand-down gate: inline path
        out = pool.probe(q, k, SharedBound(B, k))
        assert pool.forks == 0
        assert set(out) == {s for s, _ in eng.indexes}
    finally:
        pool.close()


def test_stats_populated_on_device_path():
    p, n, k = 64, 500, 5
    db, q = _make_data(n, p, 8, seed=19)
    dev = AMIHIndex.build(db, p, probe_backend="device")
    stats = [AMIHStats() for _ in range(q.shape[0])]
    dev.knn_batch(q, k, stats=stats)
    for st in stats:
        assert st.probes > 0
        assert st.verified > 0
        assert st.tuples_processed > 0


# ------------------------------------------------ the K-slot carry walk
@pytest.mark.parametrize("p,m", [(32, None), (64, None), (64, 3),
                                 (128, None)])
@pytest.mark.parametrize("k", [1, 10, 100])
def test_walk_exact_vs_reference_and_host(p, m, k):
    """The fused walk over clustered codes at p in {32, 64, 128} and
    K in {1, 10, 100} (p = 64 also at the paper's m = 3, 22-bit
    substrings): sims equal the float64 scan's, ids carry their sims,
    and ids and sims equal the host walk's. K = 100 bails part of each
    batch to the scan fallback."""
    n, B = 2500, 16
    db, q = _clustered(n, p, B, seed=p + k)
    ih, sh = AMIHIndex.build(db, p, m=m).knn_batch(q, k)
    dev = AMIHIndex.build(db, p, m=m, probe_backend="device")
    stats = [AMIHStats() for _ in range(B)]
    id_, sd = dev.knn_batch(q, k, stats=stats)
    _check_vs_scan(q, db, id_, sd, k)
    np.testing.assert_array_equal(sh, sd)
    np.testing.assert_array_equal(ih, id_)
    assert all(np.unique(row).size == k for row in id_)
    if k == 100:
        assert any(st.fell_back_to_scan for st in stats)


def test_codes_met_through_two_tables_enter_once():
    """Every code sits in the query's own bucket of table 0 and a few
    flips from it in table 1, so the walk meets each code through both
    tables: retrieved counts both meetings, verified and the answer
    each code once."""
    p, n = 32, 64
    rng = np.random.default_rng(37)
    q_bits = rng.integers(0, 2, size=p).astype(np.uint8)
    db_bits = np.repeat(q_bits[None], n, axis=0)
    for row in db_bits:
        row[16 + rng.choice(16, size=rng.integers(1, 3), replace=False)] ^= 1
    db, q = pack_bits(db_bits), pack_bits(q_bits[None])
    dev = AMIHIndex.build(db, p, m=2, probe_backend="device")
    stats = [AMIHStats()]
    id_, sd = dev.knn_batch(q, n, stats=stats)
    np.testing.assert_array_equal(np.sort(id_[0]), np.arange(n))
    assert stats[0].verified == n
    assert stats[0].retrieved > n
    assert not stats[0].fell_back_to_scan
    ih, sh = AMIHIndex.build(db, p, m=2).knn_batch(q, n)
    np.testing.assert_array_equal(ih, id_)
    np.testing.assert_array_equal(sh, sd)


# Per-query counters of the position-map walk (the device path before the
# K-slot carry) on the data of each case, computed once on that walk and
# pinned: (p, K, seed, stream cap) -> verified, tuples_processed,
# max_radius, fell_back_to_scan per query, and the batch's probes,
# retrieved and substring_tuples_probed. n = 3000 clustered codes, B = 16,
# the default m.
PINNED = {
    "p32_k1": ((32, 1, 5, 1 << 16), dict(
        verified=[391, 376, 407, 446, 341, 443, 459, 448, 436, 434, 407,
                  352, 443, 428, 395, 345],
        tuples_processed=[5, 2, 1, 2, 5, 1, 1, 2, 2, 4, 1, 7, 2, 3, 2, 3],
        max_radius=[2, 1, 0, 1, 2, 0, 0, 1, 1, 2, 0, 3, 1, 1, 1, 1],
        fell_back_to_scan=[0] * 16,
        probes=3724, retrieved=7188, substring_tuples_probed=51)),
    "p64_k10": ((64, 10, 6, 1 << 16), dict(
        verified=[3000, 780, 776, 767, 3000, 497, 3000, 486, 759, 3000,
                  809, 702, 3000, 3000, 672, 713],
        tuples_processed=[140, 92, 103, 89, 255, 56, 179, 44, 83, 185, 94,
                          93, 226, 196, 101, 83],
        max_radius=[23, 16, 17, 15, 29, 11, 25, 10, 14, 25, 16, 15, 33, 26,
                    16, 15],
        fell_back_to_scan=[1, 0, 0, 0, 1, 0, 1, 0, 0, 1, 0, 0, 1, 1, 0, 0],
        probes=8388, retrieved=15952, substring_tuples_probed=780)),
    "p64_k100": ((64, 100, 7, 1 << 16), dict(
        verified=[3000] * 16,
        tuples_processed=[364, 377, 330, 376, 372, 369, 385, 364, 364, 365,
                          325, 353, 333, 357, 372, 362],
        max_radius=[37, 37, 41, 38, 37, 35, 37, 37, 37, 33, 42, 33, 41, 38,
                    33, 37],
        fell_back_to_scan=[1] * 16,
        probes=11159, retrieved=20469, substring_tuples_probed=1500)),
    "p128_k10": ((128, 10, 8, 1 << 16), dict(
        verified=[3000, 364, 831, 773, 3000, 3000, 761, 3000, 739, 3000,
                  3000, 3000, 3000, 277, 3000, 3000],
        tuples_processed=[1277, 168, 300, 326, 488, 412, 242, 375, 312, 571,
                          420, 520, 689, 182, 993, 384],
        max_radius=[70, 21, 30, 30, 39, 37, 26, 33, 31, 45, 37, 42, 50, 21,
                    62, 33],
        fell_back_to_scan=[1, 0, 0, 0, 1, 1, 0, 1, 0, 1, 1, 1, 1, 0, 1, 1],
        probes=13577, retrieved=14147, substring_tuples_probed=1463)),
    "p64_truncated": ((64, 10, 6, 64), dict(
        verified=[3000] * 16,
        tuples_processed=[140, 92, 103, 89, 255, 56, 179, 44, 83, 185, 94,
                          93, 226, 196, 101, 83],
        max_radius=[23, 16, 17, 15, 29, 11, 25, 10, 14, 25, 16, 15, 33, 26,
                    16, 15],
        fell_back_to_scan=[1] * 16,
        probes=96, retrieved=356, substring_tuples_probed=96)),
}


@pytest.mark.parametrize("case", sorted(PINNED))
def test_engine_stats_match_the_position_map_walk(case):
    (p, k, seed, cap), want = PINNED[case]
    n, B = 3000, 16
    db, q = _clustered(n, p, B, seed=seed)
    eng = make_engine("amih", db, p, probe_backend="device",
                      probe_stream_cap=cap, query_cache_size=0)
    ids, sims, stats = eng.knn_batch(q, k)
    _check_vs_scan(q, db, ids, sims, k)
    for name in ("verified", "tuples_processed", "max_radius",
                 "fell_back_to_scan"):
        got = [int(getattr(st, name)) for st in stats.per_query]
        assert got == want[name], name
    for name in ("probes", "retrieved", "substring_tuples_probed"):
        assert stats.total(name) == want[name], name


@pytest.mark.parametrize("k", [1, 100])
def test_amih_counters_per_batch(k):
    """The walk's registry counters move once per batch by the batch's
    own numbers: queries, the bailed ones, the walk's candidates and
    distinct codes, and its iterations."""
    p, n, B = 64, 2500, 16
    db, q = _clustered(n, p, B, seed=41)
    eng = make_engine("amih", db, p, probe_backend="device",
                      query_cache_size=0)
    names = ("amih.queries", "amih.bailed_queries", "amih.retrieved",
             "amih.verified", "amih.walk_iters")
    before = {name: _REG.value(name) for name in names}
    _, _, stats = eng.knn_batch(q, k)
    moved = {name: _REG.value(name) - before[name] for name in names}
    bailed = stats.total("fell_back_to_scan")
    assert moved["amih.queries"] == B
    assert moved["amih.bailed_queries"] == bailed
    assert moved["amih.retrieved"] == stats.total("retrieved")
    assert 1 <= moved["amih.walk_iters"]
    # a bailed query's stats count every code (the scan verified all);
    # the counter keeps the walk's own distinct codes
    walked = sum(st.verified for st in stats.per_query
                 if not st.fell_back_to_scan)
    assert walked <= moved["amih.verified"] <= stats.total("retrieved")
    if k == 1:
        assert bailed == 0 and moved["amih.verified"] == walked
    else:
        assert bailed > 0
