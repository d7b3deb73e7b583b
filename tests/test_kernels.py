"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracle
(interpret mode on CPU; the same pallas_call lowers natively on TPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.linear_scan import sims_against_db
from repro.core.packing import pack_bits
from repro.kernels import ops, ref


def _random_codes(rng, n, p):
    return pack_bits((rng.random((n, p)) < 0.5).astype(np.uint8))


# ------------------------------------------------------------ oracle tests
def test_popcount32_exact(rng):
    v = rng.integers(0, 2**32, size=(64,), dtype=np.uint32)
    got = np.asarray(ref.popcount32(jnp.asarray(v)))
    want = np.bitwise_count(v)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("p", [8, 24, 32, 64, 128, 200])
def test_scores_ref_matches_numpy_eq3(rng, p):
    B, N = 4, 100
    q = _random_codes(rng, B, p)
    db = _random_codes(rng, N, p)
    z = np.bitwise_count(q).sum(axis=1)
    got = np.asarray(ref.scores_ref(jnp.asarray(q), jnp.asarray(db), jnp.asarray(z)))
    for b in range(B):
        want = sims_against_db(q[b], db)
        np.testing.assert_allclose(got[b], want, atol=1e-6)


# ---------------------------------------------------- pallas kernel sweeps
@pytest.mark.parametrize("p", [16, 32, 64, 128, 256])
@pytest.mark.parametrize("shape", [(1, 100), (5, 1030), (9, 2048)])
def test_hamming_scan_kernel_sweep(rng, p, shape):
    B, N = shape
    q = jnp.asarray(_random_codes(rng, B, p))
    db = jnp.asarray(_random_codes(rng, N, p))
    got = np.asarray(ops.scan_scores(q, db, use_pallas=True))
    want = np.asarray(ops.scan_scores(q, db, use_pallas=False))
    np.testing.assert_allclose(got, want, atol=1e-6)


def _exact_score_codes(rng, n, p):
    """Codes of weight 0, 1, 4, 16 or 64 (at most p): every product
    |q| |b| is a power of four, so every Eq. 3 score c / sqrt(|q| |b|)
    is a dyadic float that rsqrt and 1 / sqrt both give exactly."""
    weights = [w for w in (0, 1, 4, 16, 64) if w <= p]
    bits = np.zeros((n, p), np.uint8)
    for row, w in zip(bits, rng.choice(weights, n)):
        row[rng.choice(p, w, replace=False)] = 1
    return pack_bits(bits)


@pytest.mark.parametrize("p", [32, 64, 128])
@pytest.mark.parametrize("B", [1, 8, 64])
@pytest.mark.parametrize("N", [3000, 16000])
def test_hamming_scan_kernel_bit_identical(rng, p, B, N):
    # N is no multiple of the kernel's tile (1,024 codes at N = 3000;
    # 8,192 or 16,384 at N = 16000, so several tiles at B = 64)
    q = _exact_score_codes(rng, B, p)
    q[0] = 0                                       # a zero query
    db = _exact_score_codes(rng, N, p)
    db[[5, N - 1]] = 0                             # zero codes
    z = np.bitwise_count(q).sum(axis=1)
    got = np.asarray(ops.scan_scores(jnp.asarray(q), jnp.asarray(db),
                                     use_pallas=True))
    want = np.asarray(ref.scores_ref(jnp.asarray(q), jnp.asarray(db),
                                     jnp.asarray(z)))
    np.testing.assert_array_equal(got, want)
    assert np.all(got[0] == 0.0) and np.all(got[:, [5, N - 1]] == 0.0)


@pytest.mark.parametrize("p", [32, 64, 128])
@pytest.mark.parametrize("n", [64, 1000, 3000])
def test_verify_tuples_kernel_sweep(rng, p, n):
    q = jnp.asarray(_random_codes(rng, 1, p)[0])
    cand = jnp.asarray(_random_codes(rng, n, p))
    r10p, r01p = ops.verify_tuples_op(q, cand, use_pallas=True)
    r10r, r01r = ops.verify_tuples_op(q, cand, use_pallas=False)
    # integer outputs: exact equality, not allclose
    assert np.array_equal(np.asarray(r10p), np.asarray(r10r))
    assert np.array_equal(np.asarray(r01p), np.asarray(r01r))


def test_kernel_degenerate_zero_query(rng):
    p = 64
    q = jnp.zeros((1, 2), jnp.uint32)
    db = jnp.asarray(_random_codes(rng, 256, p))
    got = np.asarray(ops.scan_scores(q, db, use_pallas=True))
    assert np.all(got == 0.0)  # zero query -> sim defined as 0


def test_kernel_zero_codes_in_db(rng):
    p = 32
    q = jnp.asarray(_random_codes(rng, 1, p))
    db_bits = (np.random.default_rng(0).random((128, p)) < 0.5).astype(np.uint8)
    db_bits[7] = 0  # plant an all-zero code
    db = jnp.asarray(pack_bits(db_bits))
    got = np.asarray(ops.scan_scores(q, db, use_pallas=True))
    assert got[0, 7] == 0.0


# ------------------------------------------------------------ streaming topk
@pytest.mark.parametrize("chunk", [64, 1000, 1 << 14])
@pytest.mark.parametrize("k", [1, 10, 100])
def test_scan_topk_streaming_exact(rng, chunk, k):
    p, B, N = 64, 3, 2500
    q = jnp.asarray(_random_codes(rng, B, p))
    db = jnp.asarray(_random_codes(rng, N, p))
    sims, ids = ops.scan_topk(q, db, k, chunk=chunk)
    full = np.asarray(ops.scan_scores(q, db, use_pallas=False))
    for b in range(B):
        want = np.sort(full[b])[::-1][: min(k, N)]
        np.testing.assert_allclose(
            np.sort(np.asarray(sims[b]))[::-1], want, atol=1e-6
        )
        # ids must be consistent with their sims
        np.testing.assert_allclose(
            full[b][np.asarray(ids[b])], np.asarray(sims[b]), atol=1e-6
        )


def _one_stage_merge(best_sims, best_ids, sims, first_id, k):
    """The running top-K merge as one ``lax.top_k`` over the running best
    and the whole chunk: the oracle of the two-stage merge."""
    import jax

    ids = jnp.broadcast_to(
        (first_id + jnp.arange(sims.shape[1], dtype=jnp.int32))[None, :],
        sims.shape,
    )
    all_sims = jnp.concatenate([best_sims, sims], axis=1)
    all_ids = jnp.concatenate([best_ids, ids], axis=1)
    new_sims, pos = jax.lax.top_k(all_sims, k)
    return new_sims, jnp.take_along_axis(all_ids, pos, axis=1)


def _one_stage_scan_topk(q, db, k, chunk, use_pallas, n_valid=None):
    """``scan_topk`` with the one-stage merge in every step."""
    import jax

    B, W = q.shape
    N = db.shape[0]
    k, chunk = min(k, N), min(chunk, N)
    n_chunks = -(-N // chunk)
    dbp = jnp.pad(db, ((0, n_chunks * chunk - N), (0, 0)))
    row_ids = jnp.arange(n_chunks * chunk).reshape(n_chunks, chunk)
    valid = row_ids < N
    if n_valid is not None:
        valid = valid & (row_ids < n_valid)

    def step(carry, inp):
        db_chunk, ok, j = inp
        sims = ops.scan_scores(q, db_chunk, use_pallas=use_pallas)
        sims = jnp.where(ok[None, :], sims, -jnp.inf)
        return _one_stage_merge(*carry, sims, j * chunk, k), None

    init = (jnp.full((B, k), -jnp.inf, jnp.float32),
            jnp.full((B, k), -1, jnp.int32))
    (sims, ids), _ = jax.lax.scan(
        step, init,
        (dbp.reshape(n_chunks, chunk, W), valid,
         jnp.arange(n_chunks, dtype=jnp.int32)),
    )
    return sims, ids


def _weight16_codes(rng, n, levels):
    """64-bit codes with 16 bits set, drawn from ``levels`` fixed
    patterns: Eq. 3 scores are then |q & b| / 16, few values, so equal
    scores straddle the k-th place; and each is a dyadic fraction, which
    the CPU's rsqrt returns exactly in every fusion (it rounds other
    scores by an ulp differently from one compiled program to another)."""
    bits = np.zeros((levels, 64), np.uint8)
    for row in bits:
        row[rng.choice(64, 16, replace=False)] = 1
    return pack_bits(bits)[rng.integers(0, levels, n)]


@pytest.mark.parametrize("k", [1, 10, 100, 128])
@pytest.mark.parametrize("levels", [2, 7, 60, 3000])
@pytest.mark.parametrize("carry_inf", [False, True])
def test_two_stage_merge_matches_one_stage(k, levels, carry_inf):
    rng = np.random.default_rng(k * 1000 + levels)
    B, chunk = 6, 8192
    g = ops.topk_group_width(chunk, k, chunk)
    assert g > 0
    sims = rng.integers(0, levels, (B, chunk)).astype(np.float32) / levels
    sims[rng.random((B, chunk)) < 0.2] = -np.inf      # masked rows
    sims[0] = -np.inf
    if carry_inf:
        best = np.full((B, k), -np.inf, np.float32)
        best_ids = np.full((B, k), -1, np.int32)
    else:
        best = -np.sort(-rng.integers(0, levels, (B, k)) / levels, axis=1)
        best = best.astype(np.float32)
        best_ids = np.sort(rng.choice(4 * chunk, (B, k)), axis=1)
        best_ids = best_ids.astype(np.int32)
    args = (jnp.asarray(best), jnp.asarray(best_ids), jnp.asarray(sims),
            jnp.int32(5 * chunk))
    got = ops._merge_topk_chunk(*args, k, g)
    want = _one_stage_merge(*args, k)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# (k, chunk, N): the first four merge in two stages, the last two in one
_MERGE_CASES = [
    (1, 64, 1000), (10, 512, 3000), (100, 2048, 5000), (128, 4096, 9000),
    (100, 64, 1000), (10, 16, 300),
]


@pytest.mark.parametrize("k,chunk,N", _MERGE_CASES)
@pytest.mark.parametrize("levels", [2, 5, 40])
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_scan_topk_two_stage_matches_one_stage(
    rng, k, chunk, N, levels, masked, use_pallas
):
    two_stage = (k, chunk, N) in _MERGE_CASES[:4]
    assert (ops.topk_group_width(N, k, chunk) > 0) == two_stage
    assert N % chunk != 0
    B = 5
    db = jnp.asarray(_weight16_codes(rng, N, levels))
    q = jnp.asarray(np.concatenate(
        [_weight16_codes(rng, B - 1, levels), _weight16_codes(rng, 1, 1)]
    ))
    n_valid = jnp.int32(N - chunk // 2 - 3) if masked else None
    got = ops.scan_topk(q, db, k, chunk=chunk, use_pallas=use_pallas,
                        n_valid=n_valid)
    want = _one_stage_scan_topk(q, db, k, chunk, use_pallas, n_valid)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


@pytest.mark.parametrize("B", [1, 8, 64, 72])
@pytest.mark.parametrize("chunk,N", [(1000, 2500), (4096, 9000),
                                     (1 << 16, 20000)])
@pytest.mark.parametrize("masked", [False, True])
def test_scan_topk_kernel_matches_one_stage_reference(rng, B, chunk, N,
                                                     masked):
    # the kernel's tiles against the XLA reference scores and the direct
    # merge: chunks that pad to whole tiles, several chunks and one, and
    # 72 queries in two 64-row query blocks
    k = 100
    db = jnp.asarray(_weight16_codes(rng, N, 40))
    q = jnp.asarray(np.concatenate(
        [_weight16_codes(rng, B - 1, 40), np.zeros((1, 2), np.uint32)]
    ))
    n_valid = jnp.int32(N - 777) if masked else None
    got = ops.scan_topk(q, db, k, chunk=chunk, use_pallas=True,
                        n_valid=n_valid)
    want = _one_stage_scan_topk(q, db, k, chunk, False, n_valid)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# ------------------------------------------------- block-max pruned scan
@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("mode", ["clustered", "uniform"])
def test_scan_topk_pruned_exact(rng, use_pallas, mode):
    from repro.data import synthetic_binary_codes, synthetic_queries

    # pruning needs n_blocks >> k: 128 blocks, k=5
    p, B, N, k = 64, 4, 16384, 5
    db_bits = synthetic_binary_codes(N, p, seed=3, mode=mode)
    q_bits = synthetic_queries(db_bits, B, seed=4)
    q = jnp.asarray(pack_bits(q_bits))
    db = jnp.asarray(pack_bits(db_bits))
    sims_p, ids_p, frac = ops.scan_topk_pruned(
        q, db, k, blk=128, use_pallas=use_pallas
    )
    sims_f, ids_f = ops.scan_topk(q, db, k, chunk=512)
    np.testing.assert_allclose(
        np.sort(np.asarray(sims_p), axis=1),
        np.sort(np.asarray(sims_f), axis=1),
        atol=1e-6,
    )
    assert 0.0 < float(frac) <= 1.0
    if mode == "clustered":  # pruning must actually bite on clustered data
        assert float(frac) < 0.5, float(frac)


def test_blockmax_kernel_matches_ref(rng):
    from repro.kernels.blockmax_scan import blockmax_scores

    p, B, N, blk = 96, 3, 2048, 256
    q = jnp.asarray(_random_codes(rng, B, p))
    db = jnp.asarray(_random_codes(rng, N, p))
    z = jnp.asarray(np.bitwise_count(np.asarray(q)).sum(axis=1), jnp.int32)
    got = np.asarray(blockmax_scores(q, z, db, blk_n=blk, interpret=True))
    full = np.asarray(ops.scan_scores(q, db, use_pallas=False))
    want = full.reshape(B, N // blk, blk).max(axis=-1)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_scan_topk_k_ge_n(rng):
    p, B, N = 32, 2, 37
    q = jnp.asarray(_random_codes(rng, B, p))
    db = jnp.asarray(_random_codes(rng, N, p))
    sims, ids = ops.scan_topk(q, db, 50, chunk=16)
    assert sims.shape == (B, N)
    assert set(np.asarray(ids[0]).tolist()) == set(range(N))
