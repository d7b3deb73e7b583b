"""Corpus and query generators of the chip benchmark, drawn from the seed.

They draw the statistical model of the program's ``synthetic_binary_codes``
(clustered mode) and ``synthetic_queries``, but are the benchmark's own,
so that the yardstick does not move when the program's generators change:
cluster centres of i.i.d. fair bits, each code its centre with every bit
flipped with probability ``flip_prob``; a query is a stored code with
every bit flipped with probability ``query_flip_prob``.

The corpus is drawn on the device in one jitted call (``jax.random``,
block by block so that no (n, p) array is ever whole) and copied to the
host once; the queries, a few hundred, are drawn on the host.

Packing is LSB-first, as the program expects: bit j of a code lives in
word j // 32 at bit position j % 32.
"""

from __future__ import annotations

import functools

import numpy as np

WORD_BITS = 32
_BLOCK_ROWS = 1 << 18


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any integer seed."""
    return np.random.default_rng([seed % (1 << 64), stream])


def n_words(p: int) -> int:
    return (p + WORD_BITS - 1) // WORD_BITS


def pack_bits(bits: np.ndarray) -> np.ndarray:
    """(n, p) {0,1} -> (n, W) uint32 words, LSB-first."""
    n, p = bits.shape
    W = n_words(p)
    packed = np.packbits(bits.astype(bool), axis=1, bitorder="little")
    out = np.zeros((n, W * 4), dtype=np.uint8)
    out[:, : packed.shape[1]] = packed
    return out.view("<u4").astype(np.uint32, copy=False)


def _pack_device(bits):
    """(m, p) bool -> (m, W) uint32 on the device, LSB-first."""
    import jax.numpy as jnp

    m, p = bits.shape
    W = n_words(p)
    bits = jnp.pad(bits, ((0, 0), (0, W * WORD_BITS - p)))
    shifted = (bits.reshape(m, W, WORD_BITS).astype(jnp.uint32)
               << jnp.arange(WORD_BITS, dtype=jnp.uint32))
    return shifted.sum(axis=-1, dtype=jnp.uint32)


@functools.lru_cache(maxsize=None)
def _clustered_fn(n: int, p: int, n_clusters: int, flip_prob: float):
    import jax
    import jax.numpy as jnp

    block = min(n, _BLOCK_ROWS)
    n_blocks = -(-n // block)

    @jax.jit
    def make(key):
        k_centre, k_assign, k_flip = jax.random.split(key, 3)
        centres = _pack_device(
            jax.random.bernoulli(k_centre, 0.5, (n_clusters, p)))

        def one(i):
            assign = jax.random.randint(jax.random.fold_in(k_assign, i),
                                        (block,), 0, n_clusters)
            flips = jax.random.bernoulli(jax.random.fold_in(k_flip, i),
                                         flip_prob, (block, p))
            # flat: a (rows, W) array pads W to a whole tile on a TPU
            return (centres[assign] ^ _pack_device(flips)).reshape(-1)

        codes = jax.lax.map(one, jnp.arange(n_blocks))
        return codes.reshape(-1)[: n * n_words(p)]

    return make


def clustered_codes(seed: int, n: int, p: int, *, n_clusters: int,
                    flip_prob: float) -> np.ndarray:
    """(n, W) packed clustered codes, drawn on the default device from
    ``seed`` and returned on the host."""
    import jax

    key = jax.random.key(seed % (1 << 32))
    codes = _clustered_fn(n, p, n_clusters, float(flip_prob))(key)
    return np.asarray(codes).reshape(n, n_words(p))


def bernoulli_words(rng, n: int, p: int, prob: float) -> np.ndarray:
    """(n, W) packed words whose first p bits are i.i.d. Bernoulli(prob)."""
    return pack_bits(rng.random((n, p), dtype=np.float32) < prob)


def near_queries(seed: int, db: np.ndarray, p: int, n_queries: int, *,
                 flip_prob: float) -> np.ndarray:
    """(n_queries, W) packed queries: stored codes drawn at random with
    every bit flipped with probability ``flip_prob`` (``synthetic_queries``).
    """
    rng = rng_for(seed, 1)
    rows = rng.integers(0, db.shape[0], n_queries)
    return db[rows] ^ bernoulli_words(rng, n_queries, p, flip_prob)
