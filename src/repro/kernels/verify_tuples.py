"""Pallas TPU kernel: batched Hamming-tuple verification (AMIH hot loop).

After AMIH's bucket probes produce a candidate id list, each candidate's
exact full-code tuple (r_1to0, r_0to1) must be computed to (a) confirm it is
a true (r1, r2)-near neighbor and (b) place it in the emission order
(paper §5.1 "final pruning"). Two shapes are provided:

  - ``verify_tuples``: one query vs one gathered candidate block.
    grid = (N / BLK_N,); candidate block (BLK_N, W) in VMEM; the query's W
    words are scalars broadcast against (1, BLK_N) word rows — all
    intermediates are 2-D VPU tiles; SWAR popcount as in hamming_scan.

  - ``verify_tuples_grouped``: every query of an AMIH z-group at once.
    Candidates are pre-gathered into a padded (B, C, W) layout and the
    grid is 2-D over (query tile, candidate-block): program (i, j)
    verifies the 8 queries of tile i against their candidate block j. A
    per-query length vector masks the C-padding (and whole padded query
    rows) in-kernel: padded slots come back as key = -1. The tuple ->
    Eq. 3 bucket key conversion
    is fused on device — each candidate returns ONE packed int32

        key = r10 * (p + 1) + r01        (p + 1 > any valid r01)

    so a single (B, C) array crosses back to the host bucketer instead of
    two tuple planes.

Outputs are exact int32 tuples/keys, so the test oracle comparison is
equality, not allclose.

This module is the kernel body only. Padding buckets, backend selection,
non-blocking dispatch, and per-device placement/launch accounting (the
mesh-resident sharded path runs one of these launches per shard on that
shard's own device) all live in the wrapper layer, kernels/ops.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import popcount32

DEFAULT_BLK_N = 1024
DEFAULT_BLK_C = 128
# query rows per grouped-verify program: one sublane tile
DEFAULT_BLK_B = 8

# Trace-time counters, keyed by kernel name: the jitted wrappers bump them
# from their Python bodies, which only execute when jax actually traces a
# new (shape, static-arg) signature. Tests assert the jit cache stays
# bounded under the power-of-two padding buckets (see ops.pad_bucket).
TRACE_COUNTS = {"verify_tuples": 0, "verify_tuples_grouped": 0}


def _verify_kernel(q_ref, cand_ref, r10_ref, r01_ref, *, n_words: int):
    blk_n = cand_ref.shape[0]
    r10 = jnp.zeros((1, blk_n), dtype=jnp.int32)
    r01 = jnp.zeros((1, blk_n), dtype=jnp.int32)
    for w in range(n_words):
        qw = q_ref[0, w]                       # scalar uint32
        cw = cand_ref[:, w][None, :]           # (1, BLK_N)
        r10 = r10 + popcount32(qw & ~cw)
        r01 = r01 + popcount32(~qw & cw)
    r10_ref[...] = r10[0]
    r01_ref[...] = r01[0]


def _verify_grouped_kernel(
    q_ref, cand_ref, len_ref, key_ref, *, n_words: int, p: int
):
    """Program (i, j): query tile i vs its j-th candidate block.

    q_ref (BLK_B, W) uint32; cand_ref (BLK_B, BLK_C, W) uint32; len_ref
    (BLK_B, 1) int32 (each query's true candidate count); key_ref
    (BLK_B, BLK_C) int32.
    """
    blk_b, blk_c = key_ref.shape
    r10 = jnp.zeros((blk_b, blk_c), dtype=jnp.int32)
    r01 = jnp.zeros((blk_b, blk_c), dtype=jnp.int32)
    for w in range(n_words):
        qw = q_ref[:, w][:, None]               # (BLK_B, 1) uint32
        cw = cand_ref[:, :, w]                  # (BLK_B, BLK_C)
        r10 = r10 + popcount32(qw & ~cw)
        r01 = r01 + popcount32(~qw & cw)
    key = r10 * jnp.int32(p + 1) + r01
    col = pl.program_id(1) * blk_c + jax.lax.broadcasted_iota(
        jnp.int32, (blk_b, blk_c), 1
    )
    key_ref[...] = jnp.where(col < len_ref[...], key, jnp.int32(-1))


@functools.partial(jax.jit, static_argnames=("p", "blk_c", "interpret"))
def verify_tuples_grouped(
    q_words: jax.Array,
    cand_words: jax.Array,
    lengths: jax.Array,
    *,
    p: int,
    blk_c: int = DEFAULT_BLK_C,
    interpret: bool,
):
    """(B, W), (B, C, W), (B,) -> packed bucket keys (B, C) int32.

    One launch verifies every query of a z-group against its padded
    candidate block: 2-D grid (B / BLK_B, C / blk_c) over tiles of
    ``BLK_B`` query rows (B itself when B < 8; B >= 8 is padded to a
    multiple of 8, so each block's query axis is sublane-aligned).
    Entry (i, c) is ``r10 * (p + 1) + r01`` for candidate c of query i
    when ``c < lengths[i]``, and -1 (masked padding) otherwise.
    C % blk_c == 0.
    """
    TRACE_COUNTS["verify_tuples_grouped"] += 1
    B, W = q_words.shape
    Bc, C, Wd = cand_words.shape
    assert W == Wd and B == Bc and B == lengths.shape[0]
    assert C % blk_c == 0, (C, blk_c)
    blk_b = min(B, DEFAULT_BLK_B)
    Bp = -(-B // blk_b) * blk_b
    pad = Bp - B
    keys = pl.pallas_call(
        functools.partial(_verify_grouped_kernel, n_words=W, p=p),
        grid=(Bp // blk_b, C // blk_c),
        in_specs=[
            pl.BlockSpec((blk_b, W), lambda i, j: (i, 0)),
            pl.BlockSpec((blk_b, blk_c, W), lambda i, j: (i, j, 0)),
            pl.BlockSpec((blk_b, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((blk_b, blk_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, C), jnp.int32),
        interpret=interpret,
    )(
        jnp.pad(q_words.astype(jnp.uint32), ((0, pad), (0, 0))),
        jnp.pad(cand_words.astype(jnp.uint32), ((0, pad), (0, 0), (0, 0))),
        jnp.pad(lengths.astype(jnp.int32), (0, pad))[:, None],
    )
    return keys[:B]


@functools.partial(jax.jit, static_argnames=("blk_n", "interpret"))
def verify_tuples(
    q_words: jax.Array,
    cand_words: jax.Array,
    *,
    blk_n: int = DEFAULT_BLK_N,
    interpret: bool,
):
    """(W,), (N, W) -> (r10, r01), each (N,) int32. N % blk_n == 0."""
    TRACE_COUNTS["verify_tuples"] += 1
    (W,) = q_words.shape
    N, Wd = cand_words.shape
    assert W == Wd
    assert N % blk_n == 0, (N, blk_n)
    grid = (N // blk_n,)
    return pl.pallas_call(
        functools.partial(_verify_kernel, n_words=W),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, W), lambda i: (0, 0)),
            pl.BlockSpec((blk_n, W), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((blk_n,), lambda i: (i,)),
            pl.BlockSpec((blk_n,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N,), jnp.int32),
            jax.ShapeDtypeStruct((N,), jnp.int32),
        ],
        interpret=interpret,
    )(q_words.astype(jnp.uint32)[None, :], cand_words.astype(jnp.uint32))
