"""Public jit'd entry points over the Pallas kernels.

Handles block padding/masking, streaming top-K over DB chunks (bounded
memory — never materializes (B, N) for huge N), and backend selection:
Pallas lowers natively on TPU; everywhere else the same kernel body runs
under ``interpret=True`` (and a pure-XLA reference path is available for
speed on CPU).
"""

from __future__ import annotations

import functools
import threading
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace as _obs
from ..obs.metrics import REGISTRY as _REG
from . import ref
from .hamming_scan import code_planes, hamming_scan_scores
from .verify_tuples import DEFAULT_BLK_C
from .verify_tuples import verify_tuples as _verify_tuples_kernel
from .verify_tuples import verify_tuples_grouped as _verify_grouped_kernel

__all__ = [
    "SCAN_CHUNK",
    "PendingKeys",
    "PendingWalk",
    "carry_width",
    "count_d2h",
    "device_key",
    "device_probe_walk_batched_launch",
    "merge_topk",
    "on_tpu",
    "pad_bucket",
    "scan_scores",
    "scan_topk",
    "topk_group_width",
    "verify_tuples_grouped_launch",
    "verify_tuples_grouped_op",
    "verify_tuples_op",
]

# Host-side launch accounting: bumped once per device dispatch of each op,
# into the process metrics registry under ``launches.<op>`` (and, for a
# placed launch, ``launches.device.<dkey>``).
# AMIH's batched verification asserts exactly one grouped launch per
# (z-group, tuple-step) through this counter (see tests/test_verify_grouped);
# the device probe path asserts one launch per batch through
# "device_probe" (the fused walk); its scan fallback counts under
# "scan_topk", the scan engine's program.

# Guards the per-device jit instances: thread-mode shard probing (forced
# for the pallas verify backend) dispatches launches from several
# threads, and dict check-then-insert is not atomic.
_LAUNCH_LOCK = threading.Lock()


def _bump_launch(op: str, dkey: "str | None" = None) -> None:
    """One device dispatch of ``op``: bump ``launches.<op>`` (and the
    per-device split when the launch was placed)."""
    _REG.counter("launches." + op).add(1)
    if dkey is not None:
        _REG.counter("launches.device." + dkey).add(1)


def count_d2h(*arrays) -> None:
    """Count the bytes of the device arrays among ``arrays`` that the
    host is about to copy back (``d2h.bytes``): every served path's
    device-to-host fetch calls this beside its ``np.asarray``."""
    _REG.counter("d2h.bytes").add(
        sum(int(a.nbytes) for a in arrays if isinstance(a, jax.Array))
    )


def device_key(device) -> str:
    """Stable string key for a placement device (``"default"`` for None —
    the unplaced path that follows jax's default device)."""
    return "default" if device is None else str(device)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pad_bucket(size: int, minimum: int = 8) -> int:
    """Next power of two >= max(size, minimum).

    Dynamic AMIH candidate blocks are padded to these buckets before
    hitting jit, so the trace cache holds at most O(log(max_size)) entries
    per axis instead of one per distinct ragged shape.
    """
    target = max(int(size), minimum, 1)
    return 1 << (target - 1).bit_length()


def _pad_to(x: jax.Array, axis: int, multiple: int, fill=0):
    size = x.shape[axis]
    target = ((size + multiple - 1) // multiple) * multiple
    if target == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad, constant_values=fill)


def scan_scores(
    q_words: jax.Array,
    db_words: jax.Array,
    *,
    use_pallas: bool | None = None,
) -> jax.Array:
    """(B, W), (N, W) -> (B, N) Eq.3 cosine scores (float32).

    use_pallas=None picks the kernel on TPU and interpret-mode Pallas
    elsewhere only for modest sizes (interpret mode is a correctness tool,
    not a fast CPU path); the jnp reference is semantically identical.
    """
    N, _ = db_words.shape
    if use_pallas is None:
        use_pallas = on_tpu()
    if not use_pallas:
        z_q = ref.popcount32(q_words.astype(jnp.uint32)).sum(axis=-1)
        return ref.scores_ref(q_words, db_words, z_q)
    return hamming_scan_scores(
        q_words, code_planes(db_words, N), jnp.int32(0), chunk=N,
        interpret=not on_tpu(),
    )


# Codes per block of scan_topk's streaming merge.
SCAN_CHUNK = 1 << 16


def _sort_work(n: int) -> int:
    """Modelled work of an exact selection over ``n`` columns: a sorting
    network over n padded to a power of two, P log^2 P compare-exchanges
    per row."""
    depth = max(1, (n - 1).bit_length())
    return (1 << depth) * depth * depth


def topk_group_width(n: int, k: int, chunk: int = SCAN_CHUNK) -> int:
    """Group width g of ``scan_topk``'s two-stage merge for a top-``k``
    scan of ``n`` codes in blocks of ``chunk``, or 0 for the direct merge.

    g is the power of two, dividing the block and leaving more than k
    groups, that minimises the modelled work of the sorts (block / g
    group maxima, k g candidates, 2 k to merge with the running best);
    equal work goes to the larger g, whose group maxima and gather are
    smaller. 0 where no g beats one ``lax.top_k`` over the k + block
    columns, as for blocks of at most a few k. ``scan_topk`` calls it at
    trace time; callers call it to count which merge a launch takes.
    """
    k, chunk = min(k, n), min(chunk, n)
    direct = _sort_work(k + chunk)
    best_g, best_work = 0, direct
    g = 2
    while chunk // g > k:
        if chunk % g == 0:
            work = (_sort_work(chunk // g) + _sort_work(k * g)
                    + _sort_work(2 * k))
            if work < direct and work <= best_work:
                best_g, best_work = g, work
        g *= 2
    return best_g


def _desc_key(x: jax.Array) -> jax.Array:
    """float32 -> int32 whose ascending order is ``lax.top_k``'s order of
    the floats, largest first (a total order on the bits)."""
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    return ~jnp.where(i < 0, i ^ 0x7FFFFFFF, i)


def _from_desc_key(key: jax.Array) -> jax.Array:
    i = ~key
    return jax.lax.bitcast_convert_type(
        jnp.where(i < 0, i ^ 0x7FFFFFFF, i), jnp.float32
    )


def _first_k(keys: jax.Array, ids: jax.Array, k: int):
    """The k smallest (key, id) pairs of each row, in order."""
    keys, ids = jax.lax.sort((keys, ids), num_keys=2)
    return keys[:, :k], ids[:, :k]


def _merge_topk_chunk(best_sims, best_ids, sims, first_id, k: int, g: int):
    """Running top-k of ``(best_sims, best_ids)`` (B, k) and one block of
    scores ``sims`` (B, chunk) whose ids run from ``first_id``.

    g = 0: one ``lax.top_k`` over the k + chunk columns. g > 0: the
    exact two-stage selection of ``scan_topk``'s docstring.
    """
    B, chunk = sims.shape
    if g == 0:
        ids = first_id + jnp.arange(chunk, dtype=jnp.int32)
        all_sims = jnp.concatenate([best_sims, sims], axis=1)
        all_ids = jnp.concatenate(
            [best_ids, jnp.broadcast_to(ids[None, :], sims.shape)], axis=1
        )
        new_sims, pos = jax.lax.top_k(all_sims, k)
        return new_sims, jnp.take_along_axis(all_ids, pos, axis=1)
    G = chunk // g
    # group j holds columns j, j + G, ..., j + (g - 1) G: the reshape is
    # free in the scores' (B, chunk) layout, with G on the lanes
    view = sims.reshape(B, g, G)
    gmax = view.max(axis=1)                                    # (B, G)
    # offset in the block of each group's first max: ranks equal maxima
    lead = (jnp.argmax(view == gmax[:, None, :], axis=1) * G
            + jnp.arange(G, dtype=jnp.int32))
    _, lead = _first_k(_desc_key(gmax), lead, k)
    top = jnp.sort(lead % G, axis=1)                           # (B, k)
    # the kept groups' scores, row i of each after row i - 1: ids ascend
    # along the k g columns, so lax.top_k's lower position is the lower id
    cand = jnp.take_along_axis(view, top[:, None, :], axis=2)
    sims, pos = jax.lax.top_k(cand.reshape(B, g * k), k)
    # id of column pos: first_id + (pos // k) G + top[pos % k], the
    # lookup in top as a one-hot select (a gather costs more on a TPU)
    hit = (pos % k)[:, :, None] == jnp.arange(k, dtype=jnp.int32)
    ids = (first_id + (pos // k) * G
           + jnp.where(hit, top[:, None, :], 0).sum(axis=2))
    keys, ids = _first_k(
        jnp.concatenate([_desc_key(best_sims), _desc_key(sims)], axis=1),
        jnp.concatenate([best_ids, ids], axis=1), k,
    )
    return _from_desc_key(keys), ids


@functools.partial(jax.jit, static_argnames=("k", "chunk", "use_pallas"))
def scan_topk(
    q_words: jax.Array,
    db_words: jax.Array,
    k: int,
    *,
    chunk: int = SCAN_CHUNK,
    use_pallas: bool = False,
    n_valid: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Streaming exact angular top-K: (B, W) x (N, W) -> sims, ids (B, k).

    The DB is processed in chunks with a running top-K merge
    (lax.scan carry), so peak memory is O(B * (k + chunk)) beside the
    codes: one chunk's (B, chunk) scores and the merge's sort operands,
    at most k + chunk columns of them. The Pallas path also holds one
    relaid copy of the codes, their word planes (``code_planes``), which
    each step's kernel reads in place at its chunk's offset.
    This is the device-side linear-scan baseline *and* the reranker of the
    distributed retrieval path.

    The merge is exact and takes two stages where that saves work
    (``topk_group_width`` picks g from the static (N, k, chunk); 0 keeps
    one ``lax.top_k`` over the running best and the whole chunk):
    (1) split the chunk into G = chunk / g groups, group j holding the
    ids j, j + G, ..., j + (g - 1) G, and take each group's max and the
    id of its first max; (2) keep the k groups ranked first by (max
    descending, that id ascending), gather their k g scores in id order,
    ``lax.top_k`` them to k, and merge those with the running best.
    Lemma: a score s whose group h is not kept loses to the max of each
    of the k kept groups: each such max is larger than s, or equal to s
    and at a lower id (equal maxima rank by the id of their first max,
    and s, if it equals h's max, lies at or after h's first max). So s
    is not in the top k. The sorts cover chunk / g + k g + 2 k columns
    in place of k + chunk: 6,400 in place of 65,664 at k = 128,
    chunk = 65,536, g = 32.

    Ties: every selection orders by (score descending, id ascending),
    with scores compared as ``lax.top_k`` compares them, so among equal
    float32 scores the lowest ids win, as in the direct merge (whose
    ``lax.top_k`` keeps the lower position, and positions follow ids).
    The two merges return the same sims and ids, bit for bit.

    ``n_valid`` (traced scalar) masks rows >= n_valid to -inf sims: shard
    slices padded to a common row count (ShardPlan's device layout) scan
    without their zero-code pad rows ever entering the top-K.
    """
    B, W = q_words.shape
    N, _ = db_words.shape
    g = topk_group_width(N, k, chunk)
    k = min(k, N)
    chunk = min(chunk, N)
    n_chunks = (N + chunk - 1) // chunk
    padded_n = n_chunks * chunk
    row_ids = jnp.arange(padded_n).reshape(n_chunks, chunk)
    base_valid = row_ids < N
    if n_valid is not None:
        base_valid = base_valid & (row_ids < n_valid)
    chunk_ids = jnp.arange(n_chunks, dtype=jnp.int32)

    if use_pallas:
        # the codes relaid once into word planes; a step's kernel reads
        # its chunk of them in place
        planes = code_planes(db_words, chunk)

        def scores(chunk_idx):
            return hamming_scan_scores(q_words, planes, chunk_idx,
                                       chunk=chunk, interpret=not on_tpu())

        xs = chunk_ids
    else:
        def scores(db_chunk):
            return scan_scores(q_words, db_chunk, use_pallas=False)

        xs = jnp.pad(db_words, ((0, padded_n - N), (0, 0)))
        xs = xs.reshape(n_chunks, chunk, W)

    init_sims = jnp.full((B, k), -jnp.inf, dtype=jnp.float32)
    init_ids = jnp.full((B, k), -1, dtype=jnp.int32)

    def step(carry, inp):
        x, valid, chunk_idx = inp
        sims = jnp.where(valid[None, :], scores(x), -jnp.inf)
        return _merge_topk_chunk(*carry, sims, chunk_idx * chunk, k, g), None

    (sims, ids), _ = jax.lax.scan(
        step, (init_sims, init_ids), (xs, base_valid, chunk_ids)
    )
    return sims, ids


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk(
    sims: jax.Array, ids: jax.Array, k: int
) -> Tuple[jax.Array, jax.Array]:
    """Merge per-shard candidate pools: (B, C) sims/ids -> top-k (B, k).

    C is the concatenation of every shard's local top-K (the O(K)-per-shard
    all-gather layout of the sharded engines); invalid slots carry -inf
    sims so they lose to every real candidate. One lax.top_k, no re-scan.
    """
    k = min(k, sims.shape[1])
    best, pos = jax.lax.top_k(sims, k)
    return best, jnp.take_along_axis(ids, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "blk", "use_pallas"))
def scan_topk_pruned(
    q_words: jax.Array,
    db_words: jax.Array,
    k: int,
    *,
    blk: int = 2048,
    use_pallas: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Block-max pruned EXACT angular top-K (§Perf R2).

    Phase 1: per-block score maxima (blockmax_scan kernel — HBM sees the
    codes once plus a tiny (B, n_blocks) matrix).
    Phase 2: bound mu_k = k-th largest block max per query. A block with
    max < mu_k cannot contain a top-K item: at least k items (one per
    block above the bound) score >= mu_k, so everything in that block is
    beaten. Only surviving blocks are rescored, under ``lax.cond`` so
    pruned blocks skip the scoring work entirely.

    Returns (sims, ids, scanned_fraction) — the last is the measured
    fraction of blocks rescored (pruning power; 1.0 = no pruning).
    Exact for any input; property-tested against scan_topk.
    """
    from .blockmax_scan import blockmax_scores

    B, W = q_words.shape
    N, _ = db_words.shape
    k = min(k, N)
    blk = min(blk, N)
    n_blocks = -(-N // blk)
    padded_n = n_blocks * blk
    dbp = jnp.pad(db_words, ((0, padded_n - N), (0, 0)))
    z_q = ref.popcount32(q_words.astype(jnp.uint32)).sum(axis=-1)

    if use_pallas:
        maxima = blockmax_scores(
            q_words, z_q, dbp, blk_n=blk, interpret=not on_tpu()
        )
        if padded_n != N:  # padded zero-codes score 0.0; mask via re-max
            pass  # zero codes score 0.0 <= any real max; harmless for max
    else:  # jnp oracle path (identical math)
        sims_all = ref.scores_ref(q_words, dbp, z_q)
        valid = jnp.arange(padded_n) < N
        sims_all = jnp.where(valid[None, :], sims_all, -jnp.inf)
        maxima = sims_all.reshape(B, n_blocks, blk).max(axis=-1)

    kk = min(k, n_blocks)
    mu_k = jax.lax.top_k(maxima, kk)[0][:, -1]            # (B,)
    block_needed = (maxima >= mu_k[:, None]).any(axis=0)  # (n_blocks,)

    dbb = dbp.reshape(n_blocks, blk, W)
    base_valid = jnp.arange(padded_n).reshape(n_blocks, blk) < N
    init_sims = jnp.full((B, k), -jnp.inf, dtype=jnp.float32)
    init_ids = jnp.full((B, k), -1, dtype=jnp.int32)

    def rescore(carry, db_blk, valid, j):
        best_sims, best_ids = carry
        sims = ref.scores_ref(q_words, db_blk, z_q)
        sims = jnp.where(valid[None, :], sims, -jnp.inf)
        ids = (j * blk + jnp.arange(blk, dtype=jnp.int32))[None, :]
        ids = jnp.broadcast_to(ids, sims.shape)
        all_sims = jnp.concatenate([best_sims, sims], axis=1)
        all_ids = jnp.concatenate([best_ids, ids], axis=1)
        new_sims, pos = jax.lax.top_k(all_sims, k)
        return new_sims, jnp.take_along_axis(all_ids, pos, axis=1)

    def step(carry, inp):
        db_blk, valid, needed, j = inp
        carry = jax.lax.cond(
            needed,
            lambda c: rescore(c, db_blk, valid, j),
            lambda c: c,
            carry,
        )
        return carry, None

    (sims, ids), _ = jax.lax.scan(
        step,
        (init_sims, init_ids),
        (dbb, base_valid, block_needed,
         jnp.arange(n_blocks, dtype=jnp.int32)),
    )
    return sims, ids, block_needed.mean()


def verify_tuples_op(
    q_words: jax.Array,
    cand_words: jax.Array,
    *,
    use_pallas: bool | None = None,
    blk_n: int = 1024,
):
    """(W,), (N, W) -> exact (r10, r01) int32 tuples for each candidate."""
    N = cand_words.shape[0]
    if use_pallas is None:
        use_pallas = on_tpu()
    if not use_pallas:
        return ref.verify_tuples_ref(q_words, cand_words)
    _bump_launch("verify")
    blk = min(blk_n, max(8, N))
    cp = _pad_to(cand_words, 0, blk)
    with _obs.current().span("launch.verify", cat="kernel", n=N):
        r10, r01 = _verify_tuples_kernel(
            q_words, cp, blk_n=blk, interpret=not on_tpu()
        )
    return r10[:N], r01[:N]


def _gather_verify_grouped_impl(
    q_words: jax.Array,
    db_words: jax.Array,
    cand_idx: jax.Array,
    lengths: jax.Array,
    *,
    p: int,
    blk_c: int,
    use_pallas: bool,
    interpret: bool,
):
    """Device side of the grouped verify: gather candidate rows from the
    resident DB and fuse tuple computation + bucket-key packing into one
    compiled computation (one kernel launch on the Pallas path)."""
    cand = jnp.take(db_words, cand_idx, axis=0)        # (B, C, W) on device
    if use_pallas:
        return _verify_grouped_kernel(
            q_words, cand, lengths, p=p, blk_c=blk_c, interpret=interpret
        )
    return ref.verify_tuples_grouped_ref(q_words, cand, lengths, p)


# Per-device jit instances of the gather+verify: one jitted callable (and
# hence one O(log B * log C) executable cache) per placement device.
# Sharded AMIH engines verify each shard on that shard's own device; a
# single shared jit instance would interleave every device's executables
# in one cache and make per-device trace/launch economy unobservable.
# Keyed by ``device_key`` so tests can inspect which devices compiled.
_DEVICE_JITS: dict = {}


def _gather_verify_grouped_for(device):
    """The jitted gather+verify bound to ``device`` (None -> the default
    device), created on first use and cached for the process lifetime.
    Guarded: thread-mode shard probing dispatches concurrently, and an
    unguarded check-then-insert would build (and trace) duplicate jit
    instances for a not-yet-cached device key."""
    key = device_key(device)
    with _LAUNCH_LOCK:
        fn = _DEVICE_JITS.get(key)
        if fn is None:
            fn = jax.jit(
                _gather_verify_grouped_impl,
                static_argnames=("p", "blk_c", "use_pallas", "interpret"),
            )
            _DEVICE_JITS[key] = fn
    return fn


def _device_fn(device, name: str, make):
    """Per-device jit instance registry shared with the grouped verify:
    one jitted callable per (device, op) pair, keyed ``"<dkey>::<op>"``
    in ``_DEVICE_JITS``, created on first use and reused for the process
    lifetime — sustained serving never rebuilds a jit wrapper per batch."""
    key = f"{device_key(device)}::{name}"
    with _LAUNCH_LOCK:
        fn = _DEVICE_JITS.get(key)
        if fn is None:
            fn = make()
            _DEVICE_JITS[key] = fn
    return fn


def device_jit_cache_info() -> Tuple[str, ...]:
    """Device keys that have a compiled grouped-verify cache (testing).
    Per-device probe-walk instances appear as ``"<dkey>::<op>"``."""
    return tuple(sorted(_DEVICE_JITS))


class PendingKeys:
    """Handle for an in-flight grouped-verify launch.

    Holds the (padded) device array of packed bucket keys without forcing
    a host sync — on accelerator backends the computation dispatches
    asynchronously, so the issuing thread can keep probing the next tuple
    step while the device works. ``get()`` materializes the unpadded
    (B, C) host array (blocking until the launch and transfer complete).
    """

    __slots__ = ("_keys", "_B", "_C", "_dkey")

    def __init__(self, keys, B: int, C: int, dkey: str = "default"):
        self._keys = keys
        self._B = B
        self._C = C
        self._dkey = dkey

    def get(self) -> np.ndarray:
        with _obs.current().span("launch.verify_grouped.resolve",
                                 cat="kernel", device=self._dkey):
            count_d2h(self._keys)
            return np.asarray(self._keys)[: self._B, : self._C]


def verify_tuples_grouped_launch(
    q_words,
    db_words: jax.Array,
    cand_idx,
    lengths,
    *,
    p: int,
    use_pallas: bool | None = None,
    blk_c: int = DEFAULT_BLK_C,
    device=None,
) -> PendingKeys:
    """Non-blocking form of ``verify_tuples_grouped_op``: pads, dispatches
    the jitted gather+verify, and returns a ``PendingKeys`` handle
    WITHOUT synchronizing with the device. Same padding/trace-cache
    contract as the blocking op (which is now ``launch().get()``).

    ``device`` places the launch: the query/index/length inputs are
    committed to it (``jax.device_put``) and the computation compiles and
    runs there — ``db_words`` is expected to already be resident on the
    same device (``AMIHIndex.db_dev`` uploads it once at build). Each
    device gets its own jit instance (``_gather_verify_grouped_for``) and
    its own ``launches.device.<dkey>`` counter; ``device=None`` keeps
    the old default-device behavior."""
    idx = np.ascontiguousarray(np.asarray(cand_idx, dtype=np.int32))
    lens = np.asarray(lengths, dtype=np.int32)
    B, C = idx.shape
    if C == 0 or B == 0:
        return PendingKeys(np.full((B, C), -1, dtype=np.int32), B, C)
    if use_pallas is None:
        use_pallas = on_tpu()
    Bp = pad_bucket(B, minimum=1)
    Cp = pad_bucket(C, minimum=8)
    blk = min(blk_c, Cp)
    idxp = np.zeros((Bp, Cp), dtype=np.int32)
    idxp[:B, :C] = idx
    lensp = np.zeros(Bp, dtype=np.int32)
    lensp[:B] = lens
    if device is not None:
        # placed launch: pad on the host and upload ONCE to the target
        # device — staging through jnp on the default device would
        # re-funnel every shard's launch through device 0, the exact
        # bottleneck per-shard placement exists to remove
        qh = np.asarray(q_words)
        qp_host = np.zeros((Bp,) + qh.shape[1:], dtype=qh.dtype)
        qp_host[:B] = qh
        qp = jax.device_put(qp_host, device)
        idxp = jax.device_put(idxp, device)
        lensp = jax.device_put(lensp, device)
    else:
        qp = _pad_to(jnp.asarray(q_words), 0, Bp)
    dkey = device_key(device)
    _bump_launch("verify_grouped", dkey)
    with _obs.current().span("launch.verify_grouped.dispatch",
                             cat="kernel", device=dkey, B=B, C=C):
        keys = _gather_verify_grouped_for(device)(
            qp,
            db_words,
            jnp.asarray(idxp),
            jnp.asarray(lensp),
            p=p,
            blk_c=blk,
            use_pallas=use_pallas,
            interpret=not on_tpu(),
        )
    return PendingKeys(keys, B, C, dkey)


def _probe_put(arrays, device):
    """Commit per-call probe arrays: one device_put each to the placement
    device, or a plain jnp.asarray on the default device."""
    if device is not None:
        return [jax.device_put(a, device) for a in arrays]
    return [jnp.asarray(a) for a in arrays]


def carry_width(k: int) -> int:
    """Slots of the walk's per-query (position, id) carry for a top-``k``
    search: k rounded up to a multiple of the 128 lanes, so every k up to
    128 shares one compiled walk."""
    return -(-max(int(k), 1) // 128) * 128


class PendingWalk:
    """Handle for an in-flight fused batch-walk launch.

    Like ``PendingKeys``, holds the device output arrays without forcing
    a host sync, so the sharded engine can dispatch every device's fused
    launch back-to-back and only block at the final merge. ``get()``
    copies the (B, kf) carries and the per-query counters to the host."""

    __slots__ = ("_out", "_B", "_dkey", "_res")

    def __init__(self, out, B: int, dkey: str):
        self._out = out
        self._B = B
        self._dkey = dkey
        self._res = None

    def get(self) -> dict:
        if self._res is None:
            with _obs.current().span("launch.device_probe.resolve",
                                     cat="kernel", device=self._dkey):
                count_d2h(*self._out)
                (pos, ids, probes, retrieved, verified, done, cursor,
                 iters) = self._out
                B = self._B
                self._res = {
                    "pos": np.asarray(pos)[:B],
                    "ids": np.asarray(ids)[:B],
                    "probes": np.asarray(probes)[:B],
                    "retrieved": np.asarray(retrieved)[:B],
                    "verified": np.asarray(verified)[:B],
                    "done": np.asarray(done)[:B],
                    "cursor": np.asarray(cursor),
                    "iters": int(iters),
                }
                self._out = None
        return self._res


def device_probe_walk_batched_launch(
    q_words,
    q_sub,
    z_sub,
    pow1,
    pow0,
    gid,
    t_stop,
    k: int,
    *,
    stack,
    csr,
    p: int,
    device=None,
    use_pallas: bool | None = None,
    tile: int | None = None,
    cap: int | None = None,
    walk_budget: int | None = None,
) -> PendingWalk:
    """Dispatch the fused cross-z-group walk: ONE launch for the whole
    batch, every z-group included, and return a ``PendingWalk`` without
    synchronizing (the sharded engine dispatches every device's walk
    before it blocks on any).

    ``stack`` is a ``repro.core.probe_device.ScheduleStack`` (the grow-
    only concatenation of the index's per-z schedules), ``gid`` maps
    each query to its stack row and ``csr`` is the index's committed CSR
    dict; per-call arrays (queries, substring values/popcounts, flip
    tables, per-query stop positions) are padded to a power-of-two batch
    and committed to ``device``. ``walk_budget`` caps the loop
    iterations; still-undone queries are left to the caller's scan
    fallback, exactly as with a truncated schedule."""
    from ..core.probe_device import DEFAULT_PROBE_CAP, DEFAULT_TILE, KMAX
    from . import device_probe

    if use_pallas is None:
        use_pallas = on_tpu()
    tile = DEFAULT_TILE if tile is None else tile
    if tile > DEFAULT_TILE:
        raise ValueError(
            f"tile={tile} exceeds the schedule pad margin {DEFAULT_TILE}"
        )
    cap = pad_bucket(DEFAULT_PROBE_CAP if cap is None else cap, minimum=8)
    qh = np.ascontiguousarray(np.asarray(q_words))
    B = qh.shape[0]
    Bp = pad_bucket(B, minimum=1)
    if walk_budget is None:
        # each iteration verifies <= cap candidates for each of Bp
        # queries; the scan verifies n_pad codes for the bailed ones.
        # Past n_pad / (4 cap Bp) iterations the walk has burned a
        # quarter of a scan's verifications without converging, and on
        # a deep walk the exhaustive scan is the cheaper way to finish.
        walk_budget = max(4, int(csr["n_pad"]) // (4 * cap * Bp))

    def pad_rows(a, fill=0):
        a = np.asarray(a)
        out = np.full((Bp,) + a.shape[1:], fill, dtype=a.dtype)
        out[:B] = a
        return out

    # padded query rows: gid 0 (a real stack row) with t_stop = -1 —
    # born done, never probed, never block the done check
    per_call = _probe_put(
        [
            pad_rows(qh),
            pad_rows(np.asarray(q_sub, dtype=np.int32)),
            pad_rows(np.asarray(z_sub, dtype=np.int32)),
            pad_rows(np.asarray(pow1, dtype=np.int32)),
            pad_rows(np.asarray(pow0, dtype=np.int32)),
            pad_rows(np.asarray(gid, dtype=np.int32)),
            pad_rows(np.asarray(t_stop, dtype=np.int32), fill=-1),
            np.int32(k),
            np.int32(walk_budget),
        ],
        device,
    )
    bundle = stack.device_arrays(device)
    dkey = device_key(device)
    _bump_launch("device_probe", dkey)
    fn = _device_fn(
        device,
        "walk_batched",
        lambda: jax.jit(
            device_probe.device_probe_walk_batched,
            static_argnames=(
                "p", "tile", "cap", "kf", "kmax", "use_pallas", "interpret",
            ),
        ),
    )
    with _obs.current().span("launch.device_probe.dispatch", cat="kernel",
                             device=dkey, B=B):
        out = fn(
            *per_call,
            bundle["g_start"],
            bundle["g_end"],
            bundle["tbl"],
            bundle["step"],
            bundle["idx1"],
            bundle["idx0"],
            bundle["maxi1"],
            bundle["maxi0"],
            bundle["block_start"],
            bundle["widths"],
            csr["sub_masks"],
            csr["offsets"],
            csr["ids"],
            csr["db_pad"],
            bundle["inv_pos"],
            p=p,
            tile=tile,
            cap=cap,
            kf=carry_width(k),
            kmax=KMAX,
            use_pallas=use_pallas,
            interpret=not on_tpu(),
        )
    return PendingWalk(out, B, dkey)


def verify_tuples_grouped_op(
    q_words,
    db_words: jax.Array,
    cand_idx,
    lengths,
    *,
    p: int,
    use_pallas: bool | None = None,
    blk_c: int = DEFAULT_BLK_C,
    device=None,
):
    """Batched AMIH verification: one launch for a whole z-group.

    q_words (B, W) packed queries; db_words (N, W) device-resident codes;
    cand_idx (B, C_max) int32 candidate rows (entries past ``lengths[b]``
    are don't-cares); lengths (B,) int32 true candidate counts. Returns a
    host (B, C_max) int32 array of packed bucket keys
    ``r10 * (p + 1) + r01`` with -1 in every padded slot.

    B and C_max are padded up to power-of-two buckets (``pad_bucket``)
    before the jitted gather+verify, so the trace cache stays
    O(log B * log C) instead of one entry per ragged candidate shape.
    Candidate rows are gathered from ``db_words`` *on device* — the host
    ships only the (B, C_max) index matrix, never the code rows. For
    host/device overlap use ``verify_tuples_grouped_launch`` and resolve
    the returned handle when the keys are actually needed. ``device``
    places the launch on a specific device (see the launch docstring).
    """
    return verify_tuples_grouped_launch(
        q_words, db_words, cand_idx, lengths,
        p=p, use_pallas=use_pallas, blk_c=blk_c, device=device,
    ).get()
