"""Plain reference for exact angular K-NN over packed binary codes.

Independent of the program under test: it imports nothing of it and takes
nothing it made. The guarantee it stands for: every returned row holds the
K largest cosines of the query against the corpus, each the float64 value

    sim = (z - r10) / (sqrt(z) * sqrt(z - r10 + r01))

(the paper's Eq. 3, with z = |q|, r10 = |q & ~b|, r01 = |~q & b|, and
sim = 0 where z or |b| is 0), so that any K ids of equal sim are a
correct answer.

``topk_sims`` finds each query's K largest sims exactly without a float
on the device. With x = |q & b| and y = |b|, r10 = z - x and r01 = y - x,
so for one query a code's sim is a function of the pair (x, y) alone. The
device counts, for each query, how many codes have each pair: the corpus
is laid out in groups of equal y, and within each block of one group the
device counts the codes of each x with integer popcounts. The host then
reads each pair's float64 sim and takes the K largest, each as often as
codes have it.

``ControlEngine`` is this reference put in the program's place and
computed one precision lower (float32 sims, ranked on the device in
float32): the control that the comparison must refuse.
"""

from __future__ import annotations

import functools

import numpy as np

_Q_BLOCK = 128
_ROW_BLOCK = 1 << 16
_CONTROL_BLOCK = 1 << 20


def popcount_rows(words: np.ndarray) -> np.ndarray:
    return np.bitwise_count(np.asarray(words, dtype=np.uint32)).sum(-1)


def sims64(q: np.ndarray, db_rows: np.ndarray) -> np.ndarray:
    """float64 Eq. 3 sims of ``db_rows`` (m, W) against one query (W,)."""
    q = np.asarray(q, dtype=np.uint32)
    b = np.asarray(db_rows, dtype=np.uint32)
    z = int(np.bitwise_count(q).sum())
    r10 = np.bitwise_count(q & ~b).sum(-1).astype(np.int64)
    r01 = np.bitwise_count(~q & b).sum(-1).astype(np.int64)
    return _eq3(z, r10, r01)


def _eq3(z: int, r10: np.ndarray, r01: np.ndarray) -> np.ndarray:
    if z == 0:
        return np.zeros(np.shape(r10), dtype=np.float64)
    nb = (z - r10 + r01).astype(np.float64)
    num = (z - r10).astype(np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        s = num / (np.sqrt(float(z)) * np.sqrt(nb))
    return np.where(nb == 0, 0.0, s)


def group_blocks(db: np.ndarray, p: int, block: int):
    """The corpus in blocks of ``block`` rows, each block holding codes of
    one popcount y only: returns (blocks (NB, block, W), rows in use per
    block (NB,), the y of each block (NB,))."""
    y = popcount_rows(db).astype(np.uint8)
    order = np.argsort(y, kind="stable")
    counts = np.bincount(y, minlength=p + 1)
    per_group = -(-counts // block)
    out = np.zeros((int(per_group.sum()) * block, db.shape[1]), db.dtype)
    used = np.zeros(int(per_group.sum()), dtype=np.int32)
    block_y = np.repeat(np.arange(p + 1), per_group)
    src = blk = 0
    for g in np.flatnonzero(counts):
        c = int(counts[g])
        out[blk * block: blk * block + c] = db[order[src: src + c]]
        full, rest = divmod(c, block)
        used[blk: blk + full] = block
        if rest:
            used[blk + full] = rest
        src += c
        blk += int(per_group[g])
    return out.reshape(-1, block, db.shape[1]), used, block_y


@functools.lru_cache(maxsize=None)
def _x_counts_fn(p: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def x_counts(q, blocks, used):
        # q (Q, W), blocks (NB, C, W) uint32, used (NB,) -> (NB, Q, p+1):
        # per block and query, how many of its codes have |q & b| = x
        bins = jnp.arange(p + 1, dtype=jnp.int32)

        def one(args):
            b, n_used = args
            x = jax.lax.population_count(q[:, None, :] & b[None, :, :])
            x = x.sum(-1).astype(jnp.int32)
            x = jnp.where(jnp.arange(b.shape[0]) < n_used, x, -1)
            return (x[:, :, None] == bins).sum(axis=1, dtype=jnp.int32)

        return jax.lax.map(one, (blocks, used))

    return x_counts


def topk_sims(queries: np.ndarray, db: np.ndarray, p: int, k: int,
              device=None) -> np.ndarray:
    """(Q, k') float64: each query's k' = min(k, n) largest sims,
    descending, computed exactly as the module docstring says."""
    import jax

    n = db.shape[0]
    k = min(k, n)
    block = min(_ROW_BLOCK, 1 << max(7, (n // (p + 1)).bit_length()))
    blocks, used, block_y = group_blocks(db, p, block)
    blocks_dev = jax.device_put(blocks, device)
    used_dev = jax.device_put(used, device)
    fn = _x_counts_fn(p)
    out = np.empty((queries.shape[0], k), dtype=np.float64)
    for qlo in range(0, queries.shape[0], _Q_BLOCK):
        q = queries[qlo: qlo + _Q_BLOCK]
        nq = len(q)
        if nq < _Q_BLOCK:           # one block shape: one compile
            q = np.concatenate([q, np.zeros((_Q_BLOCK - nq,) + q.shape[1:],
                                            q.dtype)])
        per_block = np.asarray(fn(jax.device_put(q, device), blocks_dev,
                                  used_dev))
        # (Q, y, x): how many codes of popcount y share x bits with q
        counts = np.zeros((_Q_BLOCK, p + 1, p + 1), dtype=np.int64)
        np.add.at(counts, (slice(None), block_y),
                  per_block.transpose(1, 0, 2))
        for j, z in enumerate(popcount_rows(q[:nq])):
            yy, xx = np.nonzero(counts[j])
            s = _eq3(int(z), int(z) - xx, yy - xx)
            order = np.argsort(-s, kind="stable")
            reps = np.minimum(counts[j][yy, xx][order], k)
            out[qlo + j] = np.repeat(s[order], reps)[:k]
    return out


class ControlEngine:
    """The reference in the program's place, one precision lower: sims in
    float32 on the device, the top K taken by those float32 sims, and
    reported as float64 values of the float32 numbers."""

    def __init__(self, db: np.ndarray, p: int, device=None):
        import jax
        import jax.numpy as jnp

        self.n = db.shape[0]
        self.db_dev = jax.device_put(db, device)
        self.device = device

        @functools.partial(jax.jit, static_argnames="k")
        def block(q, db_blk, base, k):
            z = jax.lax.population_count(q).sum(-1).astype(jnp.float32)
            r10 = jax.lax.population_count(
                q[:, None, :] & ~db_blk[None, :, :]).sum(-1)
            r01 = jax.lax.population_count(
                ~q[:, None, :] & db_blk[None, :, :]).sum(-1)
            zc = z[:, None]
            nb = zc - r10.astype(jnp.float32) + r01.astype(jnp.float32)
            s = (zc - r10.astype(jnp.float32)) / (jnp.sqrt(zc) * jnp.sqrt(nb))
            s = jnp.where((nb == 0) | (zc == 0), 0.0, s)
            top, idx = jax.lax.top_k(s, k)
            return top, idx.astype(jnp.int32) + base

        self._block = block

    def knn_batch(self, q, k):
        import jax

        k = min(k, self.n)
        blk = min(_CONTROL_BLOCK, self.n)
        q_dev = jax.device_put(np.asarray(q, dtype=np.uint32), self.device)
        sims, ids = [], []
        for lo in range(0, self.n, blk):
            s, i = self._block(q_dev, self.db_dev[lo: lo + blk],
                               np.int32(lo), k=min(k, blk))
            sims.append(np.asarray(s))
            ids.append(np.asarray(i))
        sims = np.concatenate(sims, axis=1)
        ids = np.concatenate(ids, axis=1)
        order = np.argsort(-sims, axis=1, kind="stable")[:, :k]
        return (np.take_along_axis(ids, order, 1).astype(np.int64),
                np.take_along_axis(sims, order, 1).astype(np.float64),
                None)

    def close(self):
        self.db_dev = None
