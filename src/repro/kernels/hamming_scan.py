"""Pallas TPU kernel: streaming angular scoring of packed binary codes.

The paper's linear-scan baseline scores every (query, code) pair with
Eq. 3: c = |q & b| (the AND popcount), then the cosine
c / sqrt(|q| |b|). On TPU this is a VPU-integer streaming kernel whose
output, the (B, n) float32 scores, outweighs its input 32x at p = 64.

Layout. The codes enter as word planes, (W, R, 128) uint32: plane w
holds word w of every code, 128 codes to a row (``code_planes`` makes
them from the (n, W) rows in one pass, a chunk of the caller's stream
at a time, each chunk padded with zero codes to whole 1,024-code
granules). A 64-bit code takes 8 bytes of HBM and of VMEM. The queries
enter broadcast over the lanes, (W, B, 128) words and (B, 128)
popcounts, fetched once per call. One call scores one chunk, found in
the planes by a prefetched tile index, so no chunk is copied out.

  grid = (span / BLK_N, B / BLK_Q), span = the chunk's padded length
  per step: BLK_N codes ((W, BLK_N / 128, 128) of the planes) against
  BLK_Q <= 64 query rows, into a (BLK_Q, BLK_N) block of scores.

Each loop iteration loads 8 plane rows (1,024 codes a word, one vreg),
counts the codes' own popcounts |b| once, then for each of the 8 rows
broadcasts it over the sublanes and scores it against every 8-query
group: one (8 queries, 128 codes) vreg per group, the output block's
own tile. With c the AND popcount, r10 = |q| - c and r01 = |b| - c are
integer identities, so the tuple form's num = |q| - r10 = c and
den = |q| (|q| - r10 + r01) = |q| |b| are the same exact integers in
float32, and each score is the same num * rsqrt(den) (0 where den is 0)
as when the kernel counted r10 and r01.

Tile. BLK_Q = min(B rounded up to 8, 64); BLK_N the largest
power-of-two multiple of 1,024 dividing the span with a score block of
at most 2 MiB (8,192 codes at BLK_Q = 64, 65,536 at BLK_Q = 8). VMEM at
BLK_Q = 64, BLK_N = 8,192, W = 2, each block double-buffered: scores
2 x 2 MiB, planes 2 x 64 KiB, query words 2 x 64 KiB, popcounts
2 x 32 KiB: about 4.3 MiB of the 16 MiB scoped default.

Validated on CPU via interpret mode against ref.py; on TPU the same
pallas_call lowers natively.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Codes per tile granule: 8 sublanes of 128 lanes, one vreg of a plane.
ROW_BLOCK = 8 * LANES
# Query rows per grid step, at most.
MAX_BLK_Q = 64
# Bytes of one grid step's score block, at most.
OUT_BLOCK_BYTES = 2 << 20


def _span(chunk: int) -> int:
    """Codes a chunk takes in the planes: ``chunk`` rounded up to whole
    ``ROW_BLOCK`` granules."""
    return -(-chunk // ROW_BLOCK) * ROW_BLOCK


def code_planes(db_words: jax.Array, chunk: int) -> jax.Array:
    """(N, W) code rows -> (W, R, 128) uint32 word planes of the rows'
    chunks of ``chunk`` codes, each padded with zero codes to whole
    granules: code j of chunk c sits at position c * span + j (row
    position // 128, lane position % 128), span = ``_span(chunk)``."""
    N, W = db_words.shape
    n_chunks = -(-N // chunk)
    span = _span(chunk)
    db = jnp.pad(db_words.astype(jnp.uint32), ((0, n_chunks * chunk - N),
                                               (0, 0)))
    if span != chunk:
        db = jnp.pad(db.reshape(n_chunks, chunk, W),
                     ((0, 0), (0, span - chunk), (0, 0)))
    return db.reshape(n_chunks * span, W).T.reshape(W, -1, LANES)


def _tile_codes(n_codes: int, blk_q: int) -> int:
    """Codes per grid step: the largest power-of-two multiple of
    ``ROW_BLOCK`` dividing ``n_codes`` whose (blk_q, BLK_N) float32 score
    block fits ``OUT_BLOCK_BYTES``."""
    blk = ROW_BLOCK
    while n_codes % (2 * blk) == 0 and blk_q * 2 * blk * 4 <= OUT_BLOCK_BYTES:
        blk *= 2
    return blk


def _scores_kernel(first_ref, qb_ref, zb_ref, planes_ref, out_ref, *,
                   n_words: int):
    """One (BLK_Q, BLK_N) block of Eq. 3 cosine scores."""
    del first_ref  # the chunk's first tile: read by the planes' index map
    n_groups = zb_ref.shape[0] // 8

    def rows(i, carry):
        r0 = pl.multiple_of(i * 8, 8)
        words = [planes_ref[w, pl.ds(r0, 8), :] for w in range(n_words)]
        norm = sum(jax.lax.population_count(d) for d in words)
        norm = norm.astype(jnp.int32).astype(jnp.float32)  # |b|, (8, 128)
        groups = [slice(8 * g, 8 * g + 8) for g in range(n_groups)]
        qv = [[qb_ref[w, q, :] for w in range(n_words)] for q in groups]
        zv = [zb_ref[q, :] for q in groups]
        for s in range(8):
            # plane row s over the 8 sublanes: 128 codes for 8 queries
            row = [jnp.broadcast_to(d[s:s + 1, :], (8, LANES)) for d in words]
            b = jnp.broadcast_to(norm[s:s + 1, :], (8, LANES))
            col = pl.multiple_of((r0 + s) * LANES, LANES)
            for q, qw, z in zip(groups, qv, zv):
                c = sum(jax.lax.population_count(qw[w] & row[w])
                        for w in range(n_words))
                den = z * b                              # |q| |b|, exact
                out_ref[q, pl.ds(col, LANES)] = jnp.where(
                    den > 0,
                    c.astype(jnp.int32).astype(jnp.float32)
                    * jax.lax.rsqrt(jnp.maximum(den, 1.0)),
                    0.0,
                )
        return carry

    jax.lax.fori_loop(0, planes_ref.shape[1] // 8, rows, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def hamming_scan_scores(
    q_words: jax.Array,
    planes: jax.Array,
    index: jax.Array,
    *,
    chunk: int,
    interpret: bool,
) -> jax.Array:
    """(B, W) queries x chunk ``index`` (a traced int32 scalar) of the
    word planes ``code_planes(db, chunk)`` -> (B, chunk) float32 Eq. 3
    cosine scores of the codes [index * chunk, (index + 1) * chunk)."""
    B, W = q_words.shape
    assert planes.shape[0] == W and planes.shape[2] == LANES, planes.shape
    span = _span(chunk)
    blk_q = min(-(-B // 8) * 8, MAX_BLK_Q)
    blk_n = _tile_codes(span, blk_q)
    q = q_words.astype(jnp.uint32)
    q = jnp.pad(q, ((0, -(-B // blk_q) * blk_q - B), (0, 0)))
    Bp = q.shape[0]
    qb = jnp.broadcast_to(q.T[:, :, None], (W, Bp, LANES))
    z = jax.lax.population_count(q).sum(axis=1).astype(jnp.float32)
    zb = jnp.broadcast_to(z[:, None], (Bp, LANES))
    first = jnp.asarray(index, jnp.int32) * (span // blk_n)
    scores = pl.pallas_call(
        functools.partial(_scores_kernel, n_words=W),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(span // blk_n, Bp // blk_q),
            in_specs=[
                pl.BlockSpec((W, blk_q, LANES), lambda i, j, f: (0, j, 0)),
                pl.BlockSpec((blk_q, LANES), lambda i, j, f: (j, 0)),
                pl.BlockSpec((W, blk_n // LANES, LANES),
                             lambda i, j, f: (0, f[0] + i, 0)),
            ],
            out_specs=pl.BlockSpec((blk_q, blk_n), lambda i, j, f: (j, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((Bp, span), jnp.float32),
        interpret=interpret,
    )(jnp.reshape(first, (1,)), qb, zb, planes)
    return scores[:B, :chunk]
