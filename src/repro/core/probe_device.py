"""Device-resident AMIH probing (the ``probe_backend="device"`` path).

The host probing loop in ``amih.py`` walks the (p, z) tuple sequence one
step at a time: enumerate substring probes, look buckets up in host CSR
tables, verify fresh candidates, emit. Every step is a host round-trip.
This module compiles the whole walk into ONE jitted launch per batch:

1.  **Schedule** (``DeviceSchedule``): the probing sequence depends only
    on (p, z) — not the query — so the entire walk is precomputed as flat
    device arrays. Each *stream entry* is one bucket probe: a table id,
    the walk step it belongs to, and the index combination that flips
    ``a`` one-bits and ``b`` zero-bits of the query substring (Prop. 4's
    T_{r1,r2,m} cover, deduplicated across steps by the same staircase
    the host path uses). The combination is stored as canonical indices
    into the query's *sorted* bit positions (ones first, then zeros), so
    one schedule serves every query: per-query validity is just
    ``max_index < z_s`` (resp. ``< w_s - z_s``) and the probed set per
    query is exactly the host path's.

2.  **CSR** (``build_device_csr``): each ``_SubTable``'s buckets become a
    dense offsets table (``offsets[s, v] .. offsets[s, v + 1]`` bounds
    bucket ``v`` of table ``s``) plus one shared sorted-ids matrix,
    committed next to the padded codes — bucket lookup on device is two
    gathers. Dense offsets cost 4 (2^w + 1) bytes a table, so their
    total is bounded (``MAX_OFFSET_BYTES``).

3.  **Walk kernel** (``kernels/device_probe.py``): a ``lax.while_loop``
    consumes the stream in tiles, expands bucket ranges into candidate
    slots (at most ``cap`` per query per iteration — oversized buckets
    are resumed across iterations), gathers + popcount-verifies the
    candidates (Pallas kernel on TPU, XLA reference elsewhere), and
    merges each code's exact walk position into a per-query carry of the
    kf smallest (position, id) pairs met so far, each code once (from
    the stream block that met it first: ``DeviceSchedule.block_start``).
    Early termination is the paper's Prop. 2 bound in walk-position
    space: a query is done when at least k codes have position <= the
    last *completed* step (pigeonhole: those are final) or the walk has
    passed its ``stop_below`` position.

4.  **Extraction** (host): the final top-K of query ``qi`` is the first
    k of its carry; sims are read from the host float64 ``sims64`` table
    at those positions, so emitted sims never round-trip through float32
    and results are bit-identical to the host path and
    ``linear_scan_knn`` (including in-tuple ties: ascending id within a
    position, walk order across positions).

If the schedule could not be fully built (a probe needs more than
``KMAX`` flips, or the stream would exceed ``stream_cap`` entries — the
device analogue of the host enumeration-cap guard), or the walk ran out
of its iteration budget, the queries still not done are answered by the
exhaustive scan (``engine.device_scan_knn``, the scan engine's own
program and float64 rescore), each code placed at its walk position
through its Hamming tuple: ONE more launch for the whole batch.
"""

from __future__ import annotations

import math
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..obs import trace as _obs
from ..obs.metrics import REGISTRY as _REG
from .enumeration import combination_indices
from .packing import extract_substring, hamming_tuples, popcount
from .probing import probing_prefix
from .tuples import rhat, sim_value

__all__ = [
    "DEFAULT_PROBE_CAP",
    "DEFAULT_STREAM_CAP",
    "DeviceSchedule",
    "KMAX",
    "MAX_OFFSET_BYTES",
    "POS_INF",
    "ScheduleStack",
    "build_device_csr",
    "dispatch_groups_device",
    "get_schedule",
    "get_schedule_stack",
    "resolve_groups_device",
    "run_groups_device",
    "schedule_cache_clear",
    "schedule_cache_info",
    "schedule_cache_stats",
]

# Max flips per substring probe the schedule encodes (index columns per
# side). Probes needing more truncate the schedule -> scan fallback; with
# the paper's m ~ p/log2(n) splits, rsub = floor(r/m) stays tiny and real
# walks never get near this.
KMAX = 8

# "Never met" sentinel of the walk's position carry (int32 max).
POS_INF = np.int32(0x7FFFFFFF)

# Bound on the dense CSR offsets, 4 * (2^wmax + 1) bytes per table:
# 128 MiB, the size of a 2^24-code share of 64-bit codes, so the offsets
# never outgrow the codes they index. It admits the paper's m = 3 at
# p = 64 (22-bit substrings, 48 MiB); 32-bit substrings (m = 2) would
# need 32 GiB of dense offsets, i.e. sparse ones.
MAX_OFFSET_BYTES = 1 << 27

# Stream entries consumed per while_loop iteration (also the schedule's
# pad margin, so a tile slice never needs clamping).
DEFAULT_TILE = 1024

# Candidate slots expanded per query per iteration: the walk kernel's
# peak gather is (B_pad, cap, W) words.
DEFAULT_PROBE_CAP = 2048

# Default bound on schedule stream entries per (p, z); the `AMIHIndex`
# field ``probe_stream_cap`` overrides it per index.
DEFAULT_STREAM_CAP = 1 << 16

# ``block_start`` of a (table, a, b) block the stream does not hold.
NO_BLOCK = np.int32(1 << 30)


@dataclass
class DeviceSchedule:
    """Precomputed device walk for one (p, m, widths, z, stream_cap).

    Host-side numpy arrays, stacked onto the device by ``ScheduleStack``.
    Instances are shared process-wide through ``get_schedule`` — treat
    every array as read-only.
    """

    p: int
    m: int
    widths: Tuple[int, ...]
    z: int
    stream_cap: int
    # ---- full walk metadata (all L valid tuples, in walk order)
    L: int = 0
    r1s: np.ndarray = field(default=None, repr=False)      # (L,) int32
    r2s: np.ndarray = field(default=None, repr=False)      # (L,) int32
    sims64: np.ndarray = field(default=None, repr=False)   # (L,) float64
    cum_maxrad: np.ndarray = field(default=None, repr=False)  # (L,) int32
    inv_pos: np.ndarray = field(default=None, repr=False)  # ((p+1)^2,) int32
    # ---- probe stream (built_steps walk steps flattened; padded to P)
    s_len: int = 0          # real stream entries
    built_steps: int = 0    # walk steps fully encoded in the stream
    complete: bool = False  # built_steps == L
    tbl: np.ndarray = field(default=None, repr=False)      # (P,) int32
    step_ext: np.ndarray = field(default=None, repr=False)  # (P+1,) int32
    idx1: np.ndarray = field(default=None, repr=False)     # (P, KMAX) int32
    idx0: np.ndarray = field(default=None, repr=False)     # (P, KMAX) int32
    maxi1: np.ndarray = field(default=None, repr=False)    # (P,) int32
    maxi0: np.ndarray = field(default=None, repr=False)    # (P,) int32
    cum_subtuples: np.ndarray = field(default=None, repr=False)
    # stream entry where the block of (table s, a one-flips, b zero-flips)
    # starts, NO_BLOCK where the stream holds no such block:
    # (m, wmax+1, wmax+1) int32
    block_start: np.ndarray = field(default=None, repr=False)


def _build_schedule(
    p: int, m: int, widths: Tuple[int, ...], z: int, stream_cap: int
) -> DeviceSchedule:
    from ..kernels import ops

    sched = DeviceSchedule(p=p, m=m, widths=widths, z=z,
                           stream_cap=stream_cap)
    L = (z + 1) * (p - z + 1)
    walk = probing_prefix(p, z, L)
    assert len(walk) == L, "probing sequence shorter than tuple count"
    r1s = np.fromiter((t[0] for t in walk), dtype=np.int32, count=L)
    r2s = np.fromiter((t[1] for t in walk), dtype=np.int32, count=L)
    sched.L = L
    sched.r1s, sched.r2s = r1s, r2s
    sched.sims64 = np.fromiter(
        (sim_value(p, z, r1, r2) for (r1, r2) in walk),
        dtype=np.float64, count=L,
    )
    sched.cum_maxrad = np.maximum.accumulate(r1s + r2s).astype(np.int32)
    inv_pos = np.full((p + 1) * (p + 1), POS_INF, dtype=np.int32)
    inv_pos[r1s.astype(np.int64) * (p + 1) + r2s] = np.arange(
        L, dtype=np.int32
    )
    sched.inv_pos = inv_pos

    wmax = max(widths)
    cover: List[Dict[int, int]] = [{} for _ in range(m)]
    tbl_l: List[np.ndarray] = []
    step_l: List[np.ndarray] = []
    idx1_l: List[np.ndarray] = []
    idx0_l: List[np.ndarray] = []
    maxi1_l: List[np.ndarray] = []
    maxi0_l: List[np.ndarray] = []
    probe_counts: List[int] = []
    block_start = np.full((m, wmax + 1, wmax + 1), NO_BLOCK, dtype=np.int32)
    total = 0
    built = 0
    complete = False
    for t, (r1, r2) in enumerate(walk):
        rsub = (r1 + r2) // m
        # collect this step's new probes WITHOUT committing the cover:
        # a step is all-or-nothing, so an abort leaves the stream ending
        # exactly at a completed step boundary
        new_probes: List[Tuple[int, int, int]] = []
        cnt = 0
        abort = False
        for s in range(m):
            w = widths[s]
            cov = cover[s]
            for a in range(min(r1, w, rsub) + 1):
                bmax = min(r2, w, rsub - a)
                for b in range(cov.get(a, -1) + 1, bmax + 1):
                    if a > KMAX or b > KMAX:
                        abort = True
                        break
                    cnt += math.comb(w, a) * math.comb(w, b)
                    new_probes.append((s, a, b))
                if abort:
                    break
            if abort:
                break
        if abort or total + cnt > stream_cap:
            break
        entry = total
        for (s, a, b) in new_probes:
            cov = cover[s]
            cov[a] = max(cov.get(a, -1), b)
            w = widths[s]
            c1 = combination_indices(w, a)
            c0 = combination_indices(w, b)
            C1, C0 = len(c1), len(c0)
            i1 = np.full((C1, KMAX), wmax, dtype=np.int32)
            if a:
                i1[:, :a] = c1
            i0 = np.full((C0, KMAX), wmax, dtype=np.int32)
            if b:
                i0[:, :b] = c0
            m1 = (
                c1[:, -1].astype(np.int32)
                if a else np.full(C1, -1, dtype=np.int32)
            )
            m0 = (
                c0[:, -1].astype(np.int32)
                if b else np.full(C0, -1, dtype=np.int32)
            )
            e = C1 * C0
            block_start[s, a, b] = entry
            entry += e
            tbl_l.append(np.full(e, s, dtype=np.int32))
            step_l.append(np.full(e, t, dtype=np.int32))
            idx1_l.append(np.repeat(i1, C0, axis=0))
            idx0_l.append(np.tile(i0, (C1, 1)))
            maxi1_l.append(np.repeat(m1, C0))
            maxi0_l.append(np.tile(m0, C1))
        total += cnt
        probe_counts.append(len(new_probes))
        built = t + 1
    else:
        complete = True

    s_len = total
    P = ops.pad_bucket(s_len + DEFAULT_TILE, minimum=DEFAULT_TILE)

    def cat(parts, pad_shape, pad_val):
        out = np.full(pad_shape, pad_val, dtype=np.int32)
        if parts:
            body = np.concatenate(parts, axis=0)
            out[: len(body)] = body
        return out

    sched.s_len = s_len
    sched.built_steps = built
    sched.complete = complete
    sched.tbl = cat(tbl_l, (P,), 0)
    steps = cat(step_l, (P + 1,), built)
    sched.step_ext = steps
    sched.idx1 = cat(idx1_l, (P, KMAX), wmax)
    sched.idx0 = cat(idx0_l, (P, KMAX), wmax)
    # padded entries carry an impossible max index so they can never be
    # valid for any query (belt and braces next to the in-stream mask)
    sched.maxi1 = cat(maxi1_l, (P,), 1 << 30)
    sched.maxi0 = cat(maxi0_l, (P,), 1 << 30)
    sched.cum_subtuples = np.concatenate(
        ([0], np.cumsum(probe_counts, dtype=np.int64))
    )
    sched.block_start = block_start
    return sched


_SCHED_CACHE: "OrderedDict[tuple, DeviceSchedule]" = OrderedDict()
_SCHED_CACHE_MAX = 32
_SCHED_LOCK = threading.RLock()
_SCHED_HITS = 0
_SCHED_MISSES = 0


def get_schedule(
    p: int, m: int, widths: Tuple[int, ...], z: int, stream_cap: int
) -> DeviceSchedule:
    """Process-wide LRU of device walk schedules — like the probing-prefix
    cache, one (p, m, widths, z) schedule serves every index and shard."""
    global _SCHED_HITS, _SCHED_MISSES
    key = (p, m, tuple(widths), z, stream_cap)
    with _SCHED_LOCK:
        sched = _SCHED_CACHE.get(key)
        if sched is not None:
            _SCHED_CACHE.move_to_end(key)
            _SCHED_HITS += 1
            return sched
        _SCHED_MISSES += 1
    built = _build_schedule(p, m, tuple(widths), z, stream_cap)
    with _SCHED_LOCK:
        sched = _SCHED_CACHE.setdefault(key, built)
        _SCHED_CACHE.move_to_end(key)
        while len(_SCHED_CACHE) > _SCHED_CACHE_MAX:
            _SCHED_CACHE.popitem(last=False)
        return sched


def schedule_cache_clear() -> None:
    with _SCHED_LOCK:
        _SCHED_CACHE.clear()
    with _STACK_LOCK:
        _STACK_CACHE.clear()


def schedule_cache_info() -> Tuple[int, int]:
    """(entries, total stream entries) of the schedule cache."""
    with _SCHED_LOCK:
        return (
            len(_SCHED_CACHE),
            sum(s.s_len for s in _SCHED_CACHE.values()),
        )


def schedule_cache_stats() -> Dict[str, int]:
    """Process-wide schedule-cache health: entries/stream size plus the
    cumulative ``get_schedule`` hit/miss counts (threaded into
    ``EngineStats.cache_info`` and recorded in bench rows, so a cache
    regression shows up as a miss-rate jump instead of a latency mystery)."""
    with _SCHED_LOCK:
        entries, stream = (
            len(_SCHED_CACHE),
            sum(s.s_len for s in _SCHED_CACHE.values()),
        )
        hits, misses = _SCHED_HITS, _SCHED_MISSES
    return {
        "schedule_entries": entries,
        "schedule_stream": stream,
        "schedule_hits": hits,
        "schedule_misses": misses,
    }


# ----------------------------------------------------------------- stack
class ScheduleStack:
    """Grow-only concatenation of every z-schedule of one
    (p, m, widths, stream_cap) config — the batched form of
    ``DeviceSchedule`` the fused cross-z-group walk indexes by row.

    Each new z appends one *segment* of ``s_len + DEFAULT_TILE`` entries
    to the flat stream arrays (the stream itself plus a tile of inert
    pad entries, so a frozen group's cursor can over-advance by one tile
    without reading a neighbor's stream); per-row ``g_start``/``g_end``
    bound the real entries and the inverse-position tables stack one row
    per z. Host capacity grows by power-of-two buckets and the committed
    per-device bundle is re-uploaded only when the version changes, so
    the jit trace cache sees O(log) distinct stream lengths and steady-
    state serving re-commits nothing.
    """

    def __init__(self, p: int, m: int, widths: Tuple[int, ...],
                 stream_cap: int):
        self.p = p
        self.m = m
        self.widths = tuple(widths)
        self.stream_cap = stream_cap
        self.wmax = max(widths)
        self.rows: Dict[int, int] = {}          # z -> row index
        self.scheds: List[DeviceSchedule] = []  # one per row
        self.g_start: List[int] = []
        self.g_end: List[int] = []
        self.version = 0
        self._used = 0
        self._cap = 0
        self.tbl = np.zeros(0, dtype=np.int32)
        self.step = np.zeros(0, dtype=np.int32)
        self.idx1 = np.zeros((0, KMAX), dtype=np.int32)
        self.idx0 = np.zeros((0, KMAX), dtype=np.int32)
        self.maxi1 = np.zeros(0, dtype=np.int32)
        self.maxi0 = np.zeros(0, dtype=np.int32)
        self._dev: Dict[str, tuple] = {}        # dkey -> (version, bundle)
        self._lock = threading.RLock()

    def _grow(self, need: int) -> None:
        from ..kernels import ops

        cap = ops.pad_bucket(need, minimum=4 * DEFAULT_TILE)
        for name in ("tbl", "step", "idx1", "idx0", "maxi1", "maxi0"):
            old = getattr(self, name)
            shape = (cap,) + old.shape[1:]
            new = np.zeros(shape, dtype=np.int32)
            new[: len(old)] = old
            setattr(self, name, new)
        self._cap = cap

    def row(self, z: int) -> int:
        """The stack row for popcount ``z``, appending (and versioning)
        on first sight. Thread-safe; rows never move once assigned."""
        with self._lock:
            r = self.rows.get(z)
            if r is not None:
                return r
        sched = get_schedule(self.p, self.m, self.widths, z,
                             self.stream_cap)
        with self._lock:
            r = self.rows.get(z)
            if r is not None:
                return r
            seg = sched.s_len + DEFAULT_TILE
            start = self._used
            if start + seg > self._cap:
                self._grow(start + seg)
            # the schedule's own pad entries (step=built, maxi=1<<30)
            # fill the segment margin, so a cursor parked past g_end
            # still reads its group's completed-step count
            self.tbl[start : start + seg] = sched.tbl[:seg]
            self.step[start : start + seg] = sched.step_ext[:seg]
            self.idx1[start : start + seg] = sched.idx1[:seg]
            self.idx0[start : start + seg] = sched.idx0[:seg]
            self.maxi1[start : start + seg] = sched.maxi1[:seg]
            self.maxi0[start : start + seg] = sched.maxi0[:seg]
            self._used = start + seg
            self.scheds.append(sched)
            self.g_start.append(start)
            self.g_end.append(start + sched.s_len)
            r = len(self.scheds) - 1
            self.rows[z] = r
            self.version += 1
            return r

    def device_arrays(self, device) -> dict:
        """The committed jnp bundle for ``device`` at the current
        version (row-count and capacity padded to power-of-two buckets;
        re-uploaded only after a new z grew the stack)."""
        from ..kernels import ops

        key = ops.device_key(device)
        with self._lock:
            cur = self._dev.get(key)
            if cur is not None and cur[0] == self.version:
                return cur[1]
            G = len(self.scheds)
            G_pad = ops.pad_bucket(G, minimum=1)
            g_start = np.zeros(G_pad, dtype=np.int32)
            g_start[:G] = self.g_start
            g_end = np.zeros(G_pad, dtype=np.int32)
            g_end[:G] = self.g_end
            pp2 = (self.p + 1) * (self.p + 1)
            inv = np.full((G_pad, pp2), POS_INF, dtype=np.int32)
            blocks = np.full(
                (G_pad, self.m * (self.wmax + 1) ** 2), NO_BLOCK,
                dtype=np.int32,
            )
            for i, s in enumerate(self.scheds):
                inv[i] = s.inv_pos
                blocks[i] = s.block_start.reshape(-1)

            import jax
            import jax.numpy as jnp

            put = (
                (lambda a: jax.device_put(a, device))
                if device is not None
                else jnp.asarray
            )
            bundle = {
                "g_start": put(g_start),
                "g_end": put(g_end),
                "tbl": put(self.tbl),
                "step": put(self.step),
                "idx1": put(self.idx1),
                "idx0": put(self.idx0),
                "maxi1": put(self.maxi1),
                "maxi0": put(self.maxi0),
                "inv_pos": put(inv),
                "block_start": put(blocks),
                "widths": put(np.asarray(self.widths, dtype=np.int32)),
            }
            self._dev[key] = (self.version, bundle)
            return bundle


_STACK_CACHE: "OrderedDict[tuple, ScheduleStack]" = OrderedDict()
_STACK_CACHE_MAX = 8
_STACK_LOCK = threading.RLock()


def get_schedule_stack(
    p: int, m: int, widths: Tuple[int, ...], stream_cap: int
) -> ScheduleStack:
    """Process-wide LRU of schedule stacks: one grow-only stack per
    (p, m, widths, stream_cap) config serves every index and shard,
    exactly like ``get_schedule`` one level down."""
    key = (p, m, tuple(widths), stream_cap)
    with _STACK_LOCK:
        stack = _STACK_CACHE.get(key)
        if stack is None:
            stack = ScheduleStack(p, m, tuple(widths), stream_cap)
            _STACK_CACHE[key] = stack
        _STACK_CACHE.move_to_end(key)
        while len(_STACK_CACHE) > _STACK_CACHE_MAX:
            _STACK_CACHE.popitem(last=False)
        return stack


# ------------------------------------------------------------------- CSR
def build_device_csr(index) -> dict:
    """Device-resident CSR of every ``_SubTable``, committed to
    ``index.device``.

    ``offsets`` is dense over bucket values — (m, 2^wmax + 1) int32, so a
    bucket lookup is two gathers with no per-table searchsorted on device
    — and its bytes are bounded by ``MAX_OFFSET_BYTES``; ``ids`` is the
    per-table sorted id rows padded to ``n_pad`` with the out-of-bounds
    marker ``n_pad``; ``db_pad`` zero-pads the packed codes to ``n_pad``
    rows for static gather shapes; ``sub_masks`` (m, W) uint32 holds the
    bits of each table's substring in the code words.
    """
    from ..kernels import ops

    widths = [t.width for t in index.tables]
    wmax = max(widths)
    m = index.m
    offset_bytes = 4 * m * ((1 << wmax) + 1)
    if offset_bytes > MAX_OFFSET_BYTES:
        raise ValueError(
            f"probe_backend='device' keeps dense CSR offsets, "
            f"4 * (2^w + 1) bytes per table: {m} tables of {wmax}-bit "
            f"substrings need {offset_bytes / 2**20:.0f} MiB, over the "
            f"{MAX_OFFSET_BYTES >> 20} MiB bound; substrings this wide "
            f"need sparse bucket offsets, which the device walk does not "
            f"have"
        )
    n = index.n
    n_pad = ops.pad_bucket(n, minimum=8)
    W = index.db_words.shape[1]
    offsets = np.full((m, (1 << wmax) + 1), n, dtype=np.int32)
    ids = np.full((m, n_pad), n_pad, dtype=np.int32)
    bit = np.arange(W * 32)
    masks = np.zeros((m, W), dtype=np.uint32)
    for s, table in enumerate(index.tables):
        w = table.width
        offsets[s, : (1 << w) + 1] = np.searchsorted(
            table.sorted_vals, np.arange((1 << w) + 1), side="left"
        ).astype(np.int32)
        ids[s, :n] = table.sorted_ids
        inside = ((bit >= table.lo) & (bit < table.hi)).reshape(W, 32)
        masks[s] = (inside.astype(np.uint64)
                    << np.arange(32, dtype=np.uint64)).sum(axis=1)
    db_pad = np.zeros((n_pad, W), dtype=index.db_words.dtype)
    db_pad[:n] = index.db_words

    import jax
    import jax.numpy as jnp

    put = (
        (lambda a: jax.device_put(a, index.device))
        if index.device is not None
        else jnp.asarray
    )
    return {
        "offsets": put(offsets),
        "ids": put(ids),
        "db_pad": put(db_pad),
        "sub_masks": put(masks),
        "n": n,
        "n_pad": n_pad,
        "wmax": wmax,
        "widths": tuple(widths),
    }


def _pow_arrays(
    q_sub: np.ndarray, z_sub: np.ndarray, widths: Tuple[int, ...], wmax: int
):
    """Per-query flip values for the canonical index combinations.

    ``pow1[b, s, i]`` is the bit value of the i-th one-position of query
    b's substring s (ascending position; 0 for i >= z_s and for the KMAX
    padding column i == wmax); ``pow0`` likewise over zero-positions. The
    schedule's index combinations OR these into the XOR mask, so each
    valid stream entry reproduces exactly one host bucket value.
    """
    Bg, m = q_sub.shape
    pow1 = np.zeros((Bg, m, wmax + 1), dtype=np.int32)
    pow0 = np.zeros((Bg, m, wmax + 1), dtype=np.int32)
    for s in range(m):
        w = widths[s]
        bits = (q_sub[:, s, None] >> np.arange(w, dtype=np.uint32)) & 1
        order1 = np.argsort(1 - bits, axis=1, kind="stable")
        order0 = np.argsort(bits, axis=1, kind="stable")
        col = np.arange(w)
        z_s = z_sub[:, s : s + 1].astype(np.int64)
        pow1[:, s, :w] = np.where(col < z_s, 1 << order1, 0)
        pow0[:, s, :w] = np.where(col < (w - z_s), 1 << order0, 0)
    return pow1, pow0


# ---------------------------------------------------------------- driver
def _extract(pos, ids, ts, k, sims64):
    """The first k of one query's (position, id) pairs, sorted by
    (position, id), that lie at or before walk position ``ts``:
    (out_ids local int64, out_pos int64, out_sims float64)."""
    take = min(k, int(np.searchsorted(pos, ts, side="right")))
    out_pos = pos[:take].astype(np.int64)
    return ids[:take].astype(np.int64), out_pos, sims64[out_pos]


def _record_stats(st, sched, verified, out_pos, probes, retrieved,
                  scanned, r_hat):
    st.probes += int(probes)
    st.retrieved += int(retrieved)
    st.verified += int(verified)
    t_last = int(out_pos[-1]) if out_pos.size else -1
    st.tuples_processed += t_last + 1
    if t_last >= 0:
        st.max_radius = max(st.max_radius, int(sched.cum_maxrad[t_last]))
        if st.max_radius > r_hat:
            st.exceeded_rhat = True
        st.substring_tuples_probed += int(
            sched.cum_subtuples[min(t_last + 1, sched.built_steps)]
        )
    if scanned:
        st.fell_back_to_scan = True


def _query_substrings(index, q_words):
    """(q_sub uint32, z_sub int32) substring values/popcounts for a
    whole (possibly mixed-z) query batch."""
    q_sub = np.stack(
        [
            np.asarray(extract_substring(q_words, t.lo, t.hi))
            for t in index.tables
        ],
        axis=1,
    ).astype(np.uint32)
    z_sub = np.bitwise_count(q_sub).astype(np.int32)
    return q_sub, z_sub


class _PendingGroups:
    """In-flight fused batch probe: the non-blocking half of
    ``run_groups_device``. Holds the launch handle plus the host-side
    context ``resolve_groups_device`` needs for extraction."""

    __slots__ = ("q_words", "k", "zs", "gid", "t_stop", "stack", "handle")

    def __init__(self, q_words, k, zs, gid, t_stop, stack, handle):
        self.q_words = q_words
        self.k = k
        self.zs = zs
        self.gid = gid
        self.t_stop = t_stop
        self.stack = stack
        self.handle = handle


def dispatch_groups_device(
    index,
    q_words: np.ndarray,
    k: int,
    stop_below: Optional[np.ndarray] = None,
) -> _PendingGroups:
    """Dispatch ONE fused walk launch for the whole batch — every
    z-group rides the same ``lax.while_loop`` via its schedule-stack row
    — and return without blocking. The sharded engine calls this once
    per device back-to-back (async multi-device dispatch); single-index
    callers go through ``run_groups_device``."""
    from ..kernels import ops

    B = q_words.shape[0]
    with _obs.current().span("amih.prep", cat="amih", B=B):
        csr = index.device_csr
        widths = csr["widths"]
        stack = get_schedule_stack(
            index.p, index.m, widths, index.probe_stream_cap
        )
        zs = popcount(q_words)
        gid = np.empty(B, dtype=np.int32)
        t_stop = np.empty(B, dtype=np.int32)
        for z in np.unique(zs):
            r = stack.row(int(z))
            sel = zs == z
            gid[sel] = r
            sched = stack.scheds[r]
            if stop_below is None:
                t_stop[sel] = sched.L - 1
            else:
                # snapshot of the live bounds: bounds only ever rise, so a
                # stale (lower) value is always still a valid lower bound
                t_stop[sel] = (
                    np.searchsorted(
                        -sched.sims64, -stop_below[sel], side="right"
                    )
                    - 1
                ).astype(np.int32)
        q_sub, z_sub = _query_substrings(index, q_words)
        pow1, pow0 = _pow_arrays(q_sub, z_sub, widths, csr["wmax"])

    handle = ops.device_probe_walk_batched_launch(
        q_words,
        q_sub.astype(np.int32),
        z_sub,
        pow1,
        pow0,
        gid,
        t_stop,
        k,
        stack=stack,
        csr=csr,
        p=index.p,
        device=index.device,
    )
    index.verify_launches += 1
    return _PendingGroups(q_words, k, zs, gid, t_stop, stack, handle)


def _scan_fallback(index, pending, rows, res):
    """Answer the bailed queries ``rows`` by the exhaustive device scan
    and write their (position, id) pairs into ``res`` in the walk's
    order: each code's walk position is its Hamming tuple's position in
    the query's schedule (one inverse-position lookup per result)."""
    from .engine import device_scan_knn

    csr = index.device_csr
    n, n_pad = csr["n"], csr["n_pad"]
    k = pending.k
    q = pending.q_words[rows]
    ids, _ = device_scan_knn(
        q, index.db_words, csr["db_pad"], k, index.p,
        n_valid=None if n == n_pad else np.int32(n),
        device=index.device,
    )
    for j, qi in enumerate(rows):
        sched = pending.stack.scheds[pending.gid[qi]]
        r10, r01 = hamming_tuples(q[j], index.db_words[ids[j]])
        pos = sched.inv_pos[r10 * (index.p + 1) + r01]
        order = np.lexsort((ids[j], pos))
        res["pos"][qi] = POS_INF
        res["pos"][qi, :k] = pos[order]
        res["ids"][qi, :k] = ids[j, order]


def resolve_groups_device(index, pending: _PendingGroups, stats,
                          on_done=None):
    """Block on a dispatched fused walk, finish any bailed queries with
    ONE exhaustive scan launch, and extract results — the whole batch
    costs two launches at most. Returns finished ``_QueryState``s with
    the host loop's result contract (LOCAL ids; float64 sims)."""
    from .amih import _QueryState

    q_words = pending.q_words
    k = pending.k
    stack = pending.stack
    B = q_words.shape[0]
    n = index.device_csr["n"]
    res = pending.handle.get()
    undone = np.flatnonzero(~res["done"])
    _REG.counter("amih.queries").add(B)
    _REG.counter("amih.bailed_queries").add(int(undone.size))
    _REG.counter("amih.retrieved").add(int(res["retrieved"].sum()))
    _REG.counter("amih.verified").add(int(res["verified"].sum()))
    _REG.counter("amih.walk_iters").add(res["iters"])
    scanned = np.zeros(B, dtype=bool)
    if undone.size:
        # truncated schedules / budget bails: finish every straggler of
        # every group with ONE exhaustive scan launch — positions are
        # exact, so results are unchanged, and the batch total stays at
        # two launches
        with _obs.current().span("amih.fallback", cat="amih",
                                 B=int(undone.size)):
            res["pos"] = res["pos"].copy()
            res["ids"] = res["ids"].copy()
            _scan_fallback(index, pending, undone, res)
        scanned[undone] = True
        index.verify_launches += 1

    states: List[_QueryState] = []
    with _obs.current().span("amih.extract", cat="amih", B=B):
        for qi in range(B):
            sched = stack.scheds[pending.gid[qi]]
            out_ids, out_pos, out_sims = _extract(
                res["pos"][qi], res["ids"][qi], int(pending.t_stop[qi]), k,
                sched.sims64,
            )
            st = None if stats is None else stats[qi]
            if st is not None:
                _record_stats(
                    st, sched, n if scanned[qi] else res["verified"][qi],
                    out_pos, res["probes"][qi], res["retrieved"][qi],
                    bool(scanned[qi]), rhat(int(pending.zs[qi])),
                )
            state = _QueryState(
                qi=qi,
                q_words=q_words[qi],
                q_subs=[],
                z_subs=[],
                seen=np.empty(0, dtype=bool),
                cover=[],
                pending={},
                out_ids=out_ids,
                out_sims=out_sims,
                stats=st,
                scanned=bool(scanned[qi]),
                done=out_ids.size >= k,
            )
            states.append(state)
            if on_done is not None and state.done:
                on_done(
                    qi,
                    out_ids + index.id_offset,
                    np.asarray(out_sims, dtype=np.float64),
                )
    return states


def run_groups_device(
    index,
    q_words: np.ndarray,
    k: int,
    stats,
    stop_below: Optional[np.ndarray] = None,
    on_done=None,
):
    """Device-path replacement for ``AMIHIndex._run_groups``: ONE fused
    walk launch (plus at most one scan-fallback launch) for the whole
    batch, then host extraction. Returns finished ``_QueryState``s with
    the same result contract as the host loop (LOCAL ids; float64
    sims)."""
    if q_words.shape[0] == 0:
        return []
    pending = dispatch_groups_device(index, q_words, k, stop_below)
    return resolve_groups_device(index, pending, stats, on_done=on_done)
