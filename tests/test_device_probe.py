"""Device-resident probing walk (core.probe_device + kernels.device_probe).

The fused probe -> bucket-lookup -> verify launch must be bit-identical
to the host reference walk (ids AND sims) and exact vs linear scan (sims
up to in-tuple ties), across every entry point that can select it, in
ONE walk launch per batch: every z-group rides the same schedule-stack
row of one ``lax.while_loop`` (``probe_fused=False`` keeps the PR 6
one-launch-per-z-group shape as the parity oracle).
"""

import numpy as np
import pytest

from repro.core import AMIHIndex, AMIHStats, linear_scan_knn, pack_bits
from repro.core.engine import make_engine
from repro.core.linear_scan import sims_for_ids
from repro.core.probe_device import (
    build_device_csr,
    get_schedule,
    schedule_cache_clear,
    schedule_cache_info,
)
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
    before = _REG.value("launches.device_probe_scan")
    stats = [AMIHStats() for _ in range(q.shape[0])]
    ih, sh = host.knn_batch(q, k)
    id_, sd = dev.knn_batch(q, k, stats=stats)
    np.testing.assert_array_equal(sh, sd)
    _check_vs_scan(q, db, id_, sd, k)
    assert _REG.value("launches.device_probe_scan") > before
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


# -------------------------------------------------------- launch economy
def test_one_walk_launch_per_batch():
    p, n, k = 64, 2000, 5
    db, q = _make_data(n, p, 32, seed=9, clustered=True)
    dev = AMIHIndex.build(db, p, probe_backend="device")
    groups = len(np.unique(np.bitwise_count(q).sum(axis=1)))
    assert groups > 1             # the fusion must actually fuse something
    walk0 = _REG.value("launches.device_probe")
    scan0 = _REG.value("launches.device_probe_scan")
    dev.knn_batch(q, k)
    assert _REG.value("launches.device_probe") - walk0 == 1
    # the cross-group scan fallback fires at most ONCE for the whole
    # batch (covering only bailed queries): O(1) launches per batch total
    assert _REG.value("launches.device_probe_scan") - scan0 <= 1
    # the PR 6 per-z-group shape survives behind probe_fused=False
    grouped = AMIHIndex.build(db, p, probe_backend="device",
                              probe_fused=False)
    walk0 = _REG.value("launches.device_probe")
    grouped.knn_batch(q, k)
    assert _REG.value("launches.device_probe") - walk0 == groups


@pytest.mark.parametrize("p,B", [(32, 1), (32, 8), (64, 8), (64, 64),
                                 (128, 8)])
def test_fused_batch_parity_and_single_launch(p, B):
    """Mixed-z batches: the fused walk is ONE launch per batch and
    bit-identical (ids AND sims) to both the host walk and the PR 6
    per-z-group device path."""
    n, k = 600, 7
    db, q = _make_data(n, p, B, seed=p + 2 * B)
    host = AMIHIndex.build(db, p, probe_backend="host")
    fused = AMIHIndex.build(db, p, probe_backend="device")
    grouped = AMIHIndex.build(db, p, probe_backend="device",
                              probe_fused=False)
    walk0 = _REG.value("launches.device_probe")
    scan0 = _REG.value("launches.device_probe_scan")
    if_, sf = fused.knn_batch(q, k)
    assert _REG.value("launches.device_probe") - walk0 == 1
    assert _REG.value("launches.device_probe_scan") - scan0 <= 1
    ih, sh = host.knn_batch(q, k)
    ig, sg = grouped.knn_batch(q, k)
    np.testing.assert_array_equal(ih, if_)
    np.testing.assert_array_equal(sh, sf)
    np.testing.assert_array_equal(ig, if_)
    np.testing.assert_array_equal(sg, sf)
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
    # the scan fallback pads the BAILED subset to a power-of-two bucket,
    # so at most log2(B) distinct shapes can ever trace
    scan = "traces.device_probe_scan_multi"
    assert after.get(scan, 0) - before.get(scan, 0) <= 3


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


def test_csr_rejects_oversized_substrings():
    # one 64-bit table would need a 2^64-slot offsets array
    db, _ = _make_data(100, 64, 1, seed=4)
    idx = AMIHIndex.build(db, 64, m=1)
    with pytest.raises(ValueError, match="substring"):
        build_device_csr(idx)


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
