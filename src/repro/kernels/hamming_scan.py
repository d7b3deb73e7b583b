"""Pallas TPU kernel: streaming angular scoring of packed binary codes.

The paper's linear-scan baseline and AMIH's candidate-verification hot loop
share one compute shape: XOR/ANDN + popcount between query words and a block
of code words, then the Eq. 3 cosine from the resulting tuple. On TPU this
is a VPU-integer, HBM-bandwidth-bound streaming kernel:

  grid = (N / BLK_N, B / BLK_Q)
  per step: db block (BLK_N, W) and query tile (BLK_Q, W) live in VMEM;
  the W word columns are statically unrolled so all intermediates are 2-D
  (BLK_Q, BLK_N) tiles aligned to the 8x128 VPU lanes; popcount is SWAR.

VMEM budget at defaults (BLK_Q=8, BLK_N=1024, W<=16):
  db 1024*16*4 = 64 KiB, q 8*16*4 = 0.5 KiB, acc 2 * 8*1024*4 = 64 KiB,
  out 8*1024*4 = 32 KiB  << 16 MiB VMEM.

MXU alignment: BLK_N is a multiple of 128 (lane dim), BLK_Q a multiple of 8
(sublane dim). The kernel never touches the MXU — it is bandwidth-bound by
design; its roofline is HBM bytes (16 B/code at p=128), which is why block
sizes favor large BLK_N (sequential HBM reads of the code array).

Validated on CPU via interpret mode against ref.py; on TPU the same
pallas_call lowers natively.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import popcount32

DEFAULT_BLK_N = 1024
DEFAULT_BLK_Q = 8


def _scores_kernel(q_ref, z_ref, db_ref, out_ref, *, n_words: int):
    """One (BLK_Q, BLK_N) tile of Eq.3 cosine scores."""
    blk_q = q_ref.shape[0]
    blk_n = db_ref.shape[0]
    r10 = jnp.zeros((blk_q, blk_n), dtype=jnp.int32)
    r01 = jnp.zeros((blk_q, blk_n), dtype=jnp.int32)
    # Static unroll over words keeps every intermediate a 2-D VPU tile.
    for w in range(n_words):
        qw = q_ref[:, w][:, None]            # (BLK_Q, 1) uint32
        dw = db_ref[:, w][None, :]           # (1, BLK_N) uint32
        r10 = r10 + popcount32(qw & ~dw)
        r01 = r01 + popcount32(~qw & dw)
    z = z_ref[...].astype(jnp.float32)      # (BLK_Q, 1)
    num = z - r10.astype(jnp.float32)
    den_sq = z * (z - r10.astype(jnp.float32) + r01.astype(jnp.float32))
    inv = jnp.where(den_sq > 0, jax.lax.rsqrt(jnp.where(den_sq > 0, den_sq, 1.0)), 0.0)
    out_ref[...] = jnp.where(den_sq > 0, num * inv, 0.0)


@functools.partial(
    jax.jit, static_argnames=("blk_n", "blk_q", "interpret")
)
def hamming_scan_scores(
    q_words: jax.Array,
    z_q: jax.Array,
    db_words: jax.Array,
    *,
    blk_n: int = DEFAULT_BLK_N,
    blk_q: int = DEFAULT_BLK_Q,
    interpret: bool,
) -> jax.Array:
    """(B, W) x (N, W) -> (B, N) float32 Eq.3 cosine scores.

    B and N must be multiples of blk_q / blk_n (ops.py pads & masks).
    """
    B, W = q_words.shape
    N, Wd = db_words.shape
    assert W == Wd, (W, Wd)
    assert B % blk_q == 0 and N % blk_n == 0, (B, N, blk_q, blk_n)
    grid = (N // blk_n, B // blk_q)
    return pl.pallas_call(
        functools.partial(_scores_kernel, n_words=W),
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk_q, W), lambda i, j: (j, 0)),
            pl.BlockSpec((blk_q, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((blk_n, W), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((blk_q, blk_n), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        interpret=interpret,
    )(
        q_words.astype(jnp.uint32),
        z_q.astype(jnp.int32)[:, None],
        db_words.astype(jnp.uint32),
    )
