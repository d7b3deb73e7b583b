"""The batched verification pipeline (one launch per z-group and tuple
step): grouped-Pallas keys == per-query NumPy tuples across ragged
candidate blocks straddling the power-of-two padding buckets, the jit
cache stays bounded under varied shapes, and AMIH's launch counters match
the one-launch-per-(z-group, tuple-step) contract."""

import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core import AMIHIndex, make_engine, pack_bits
from repro.core.packing import hamming_tuples
from repro.data import synthetic_binary_codes, synthetic_queries
from repro.kernels import ops
from repro.obs.metrics import REGISTRY as _REG


def _random_workload(rng, B, C, p, n=64):
    db = pack_bits((rng.random((n, p)) < 0.4).astype(np.uint8))
    qs = pack_bits((rng.random((B, p)) < 0.4).astype(np.uint8))
    idx = rng.integers(0, n, size=(B, C)).astype(np.int32)
    lengths = rng.integers(0, C + 1, size=B).astype(np.int32)
    lengths[rng.integers(0, B)] = C  # at least one full row
    return db, qs, idx, lengths


# C values straddling every padding-bucket edge the op can hit at test
# sizes: below the minimum bucket (8), and around 8/16/32/64/128 (the
# default kernel block), plus a >1-block shape.
_C_EDGES = [1, 7, 8, 9, 15, 16, 17, 31, 32, 33, 63, 64, 65, 127, 128, 129]


@given(
    B=st.sampled_from([1, 8, 64]),
    ci=st.integers(0, len(_C_EDGES) - 1),
    p=st.sampled_from([32, 64, 128]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=24, deadline=None)
def test_grouped_pallas_matches_per_query_numpy(B, ci, p, seed):
    """keys[b, c] == r10 * (p+1) + r01 from host popcounts for c <
    lengths[b]; -1 (masked padding) beyond — for every ragged shape."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    C = _C_EDGES[ci]
    db, qs, idx, lengths = _random_workload(rng, B, C, p)
    keys = ops.verify_tuples_grouped_op(
        qs, jnp.asarray(db), idx, lengths, p=p, use_pallas=True
    )
    assert keys.shape == (B, C) and keys.dtype == np.int32
    for b in range(B):
        length = int(lengths[b])
        r10, r01 = hamming_tuples(qs[b], db[idx[b, :length]])
        np.testing.assert_array_equal(
            keys[b, :length], r10 * (p + 1) + r01
        )
        assert np.all(keys[b, length:] == -1)


def test_grouped_ref_path_matches_pallas():
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    db, qs, idx, lengths = _random_workload(rng, 8, 33, 96)
    k_pl = ops.verify_tuples_grouped_op(
        qs, jnp.asarray(db), idx, lengths, p=96, use_pallas=True
    )
    k_ref = ops.verify_tuples_grouped_op(
        qs, jnp.asarray(db), idx, lengths, p=96, use_pallas=False
    )
    np.testing.assert_array_equal(k_pl, k_ref)


def test_resolve_counts_the_padded_keys_copied_to_the_host():
    """``PendingKeys.get`` counts the whole padded (B_pad, C_pad) int32
    key block it copies back in ``d2h.bytes``; the empty launch, which
    never reaches the device, counts nothing."""
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    db, qs, idx, lengths = _random_workload(rng, 5, 20, 64)
    before = _REG.value("d2h.bytes")
    keys = ops.verify_tuples_grouped_op(
        qs, jnp.asarray(db), idx, lengths, p=64, use_pallas=False
    )
    assert keys.shape == (5, 20)
    Bp, Cp = ops.pad_bucket(5, minimum=1), ops.pad_bucket(20, minimum=8)
    assert _REG.value("d2h.bytes") - before == Bp * Cp * 4
    before = _REG.value("d2h.bytes")
    ops.verify_tuples_grouped_op(
        qs, jnp.asarray(db), idx[:, :0], lengths, p=64
    )
    assert _REG.value("d2h.bytes") == before


def test_empty_candidate_matrix():
    import jax.numpy as jnp

    db = pack_bits(np.zeros((4, 32), np.uint8))
    keys = ops.verify_tuples_grouped_op(
        pack_bits(np.zeros((3, 32), np.uint8)),
        jnp.asarray(db),
        np.zeros((3, 0), np.int32),
        np.zeros(3, np.int32),
        p=32,
    )
    assert keys.shape == (3, 0)


def test_jit_cache_stays_bounded_across_varied_shapes():
    """100 calls with 100 distinct ragged (B, C) shapes must coalesce
    into the power-of-two padding buckets: the kernel trace count grows
    by at most log2-many entries, not one per shape."""
    import jax.numpy as jnp

    from repro.obs.metrics import REGISTRY

    rng = np.random.default_rng(11)
    p = 64
    db = pack_bits((rng.random((256, p)) < 0.5).astype(np.uint8))
    db_dev = jnp.asarray(db)
    before = REGISTRY.value("traces.verify_tuples_grouped")
    shapes = [(1 + (i % 13), 1 + 2 * i) for i in range(100)]
    assert len(set(shapes)) == 100
    for B, C in shapes:
        qs = pack_bits((rng.random((B, p)) < 0.5).astype(np.uint8))
        idx = rng.integers(0, 256, size=(B, C)).astype(np.int32)
        lengths = np.full(B, C, np.int32)
        ops.verify_tuples_grouped_op(
            qs, db_dev, idx, lengths, p=p, use_pallas=True
        )
    traces = REGISTRY.value("traces.verify_tuples_grouped") - before
    # B buckets {1,2,4,8,16} x C buckets {8,16,32,64,128,256} at most
    assert traces <= 30, traces


def test_amih_one_launch_per_z_group_and_tuple_step():
    """The launch counter contract: batched AMIH verification dispatches
    once per (z-group, tuple-step) with fresh candidates — identical
    launch counts for the numpy and pallas backends, both ≤ what
    query-at-a-time probing would have issued."""
    p, n, B, k = 64, 300, 16, 8
    db_bits = synthetic_binary_codes(n, p, seed=21)
    qs = pack_bits(synthetic_queries(db_bits, B, seed=22))
    db = pack_bits(db_bits)

    eng_np = make_engine("amih", db, p, verify_backend="numpy")
    eng_pl = make_engine("amih", db, p, verify_backend="pallas")
    before = _REG.value("launches.verify_grouped")
    ids_n, sims_n, _ = eng_np.knn_batch(qs, k)
    ids_p, sims_p, _ = eng_pl.knn_batch(qs, k)
    np.testing.assert_array_equal(sims_n, sims_p)

    # device dispatches == the index's own accounting
    assert (
        _REG.value("launches.verify_grouped") - before
        == eng_pl.index.verify_launches
    )
    # grouped == grouped, whatever the backend
    assert eng_pl.index.verify_launches == eng_np.index.verify_launches

    # per-query probing would launch once per (query, step): the grouped
    # batch must not exceed it, and with shared-z queries it must win
    per_query = 0
    for i in range(B):
        idx1 = AMIHIndex.build(db, p, verify_backend="numpy")
        idx1.knn(qs[i], k)
        per_query += idx1.verify_launches
    assert eng_pl.index.verify_launches <= per_query
    zs = {int(z) for z in np.bitwise_count(qs).sum(axis=1)}
    if len(zs) < B:  # at least one shared z-group
        assert eng_pl.index.verify_launches < per_query


def test_amih_device_residency_uploaded_once():
    p, n = 64, 200
    db_bits = synthetic_binary_codes(n, p, seed=23)
    qs = pack_bits(synthetic_queries(db_bits, 4, seed=24))
    db = pack_bits(db_bits)
    idx = AMIHIndex.build(db, p, verify_backend="pallas")
    dev0 = idx._db_dev
    assert dev0 is not None  # uploaded eagerly at build
    idx.knn_batch(qs, 5)
    idx.knn_batch(qs, 3)
    assert idx.db_dev is dev0  # never re-shipped


def test_oversized_step_chunks_instead_of_exploding():
    """A fell-back-to-scan z-group (every block is the whole DB) must
    split across launches once the padded gather exceeds the element
    budget — same results, more dispatches, bounded peak memory."""
    from repro.core import linear_scan_knn

    p, n, B = 64, 512, 4
    rng = np.random.default_rng(25)
    db = pack_bits((rng.random((n, p)) < 0.5).astype(np.uint8))
    qbits = (rng.random(p) < 0.5).astype(np.uint8)
    # same popcount for every query -> one z-group
    qs = pack_bits(np.stack([rng.permutation(qbits) for _ in range(B)]))

    results = []
    launches = []
    for budget in (1 << 24, 256):
        eng = make_engine("amih", db, p, m=1, enumeration_cap=10,
                          verify_backend="pallas")
        eng.index.verify_elem_budget = budget
        ids, sims, stats = eng.knn_batch(qs, 6)
        assert stats.total("fell_back_to_scan") == B
        results.append(sims)
        launches.append(eng.index.verify_launches)
    np.testing.assert_array_equal(results[0], results[1])
    assert launches[1] > launches[0]  # chunked into more dispatches
    for i in range(B):
        _, sims_l = linear_scan_knn(qs[i], db, 6)
        np.testing.assert_array_equal(results[0][i], sims_l)
