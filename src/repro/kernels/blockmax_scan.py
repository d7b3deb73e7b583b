"""Pallas TPU kernel: per-block score maxima for pruned exact scan (R2).

Phase 1 of the block-max pruned exact top-K (§Perf R2):

  maxima[b, j] = max over codes in db block j of sim(q_b, code)

The kernel computes the (B, blk) score tile in VMEM (same SWAR popcount +
Eq. 3 body as hamming_scan) but writes only its row-max — HBM traffic is
the packed codes once plus a (B, n_blocks) f32 matrix (4·B/blk bytes per
code instead of 4·B).

Phase 2 (ops.scan_topk_pruned) uses the exact bound: if mu_k is the k-th
largest block maximum for a query, every block with max < mu_k contains
only items with score < mu_k <= (true k-th best score), so it cannot hold
a top-K item (up to ties, which the >= threshold keeps). Only surviving
blocks are rescored. Exactness is property-tested against the full scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import popcount32

DEFAULT_BLK_N = 2048


def _blockmax_kernel(q_ref, z_ref, db_ref, out_ref, *, n_words: int):
    blk_q = q_ref.shape[0]
    blk_n = db_ref.shape[0]
    r10 = jnp.zeros((blk_q, blk_n), dtype=jnp.int32)
    r01 = jnp.zeros((blk_q, blk_n), dtype=jnp.int32)
    for w in range(n_words):
        qw = q_ref[:, w][:, None]
        dw = db_ref[:, w][None, :]
        r10 = r10 + popcount32(qw & ~dw)
        r01 = r01 + popcount32(~qw & dw)
    z = z_ref[:].astype(jnp.float32)[:, None]
    num = z - r10.astype(jnp.float32)
    den_sq = z * (z - r10.astype(jnp.float32) + r01.astype(jnp.float32))
    inv = jnp.where(
        den_sq > 0, jax.lax.rsqrt(jnp.where(den_sq > 0, den_sq, 1.0)), 0.0
    )
    sims = jnp.where(den_sq > 0, num * inv, 0.0)
    out_ref[...] = sims.max(axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("blk_n", "interpret"))
def blockmax_scores(
    q_words: jax.Array,      # (B, W) uint32
    z_q: jax.Array,          # (B,) int32
    db_words: jax.Array,     # (N, W) uint32, N % blk_n == 0
    *,
    blk_n: int = DEFAULT_BLK_N,
    interpret: bool,
) -> jax.Array:
    """(B, n_blocks) per-block maxima of Eq. 3 scores."""
    B, W = q_words.shape
    N, Wd = db_words.shape
    assert W == Wd and N % blk_n == 0, (W, Wd, N, blk_n)
    n_blocks = N // blk_n
    return pl.pallas_call(
        functools.partial(_blockmax_kernel, n_words=W),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((B, W), lambda j: (0, 0)),
            pl.BlockSpec((B,), lambda j: (0,)),
            pl.BlockSpec((blk_n, W), lambda j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((B, 1), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((B, n_blocks), jnp.float32),
        interpret=interpret,
    )(
        q_words.astype(jnp.uint32),
        z_q.astype(jnp.int32),
        db_words.astype(jnp.uint32),
    )
