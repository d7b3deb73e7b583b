"""Fused device-resident AMIH probing walk (paper §4–§5, one launch).

``device_probe_walk`` compiles the whole probe -> bucket-lookup ->
verify -> top-K pipeline of one z-group into a single jitted
``lax.while_loop``: each iteration consumes a tile of the precomputed
probe stream (repro.core.probe_device.DeviceSchedule), expands the CSR
bucket ranges into at most ``cap`` candidate slots per query, gathers
the candidate codes from the device-resident padded DB, popcount-
verifies them (the ``verify_tuples_grouped`` Pallas kernel on TPU, the
XLA reference elsewhere), and scatter-mins each candidate's exact walk
position into a per-query (B, n_pad) position map. Rediscoveries
scatter the same position, so deduplication costs nothing.

Early termination is Prop. 2's k-th-cosine bound translated to walk
positions: after the entries of walk step t are all consumed, every
code with position <= t is guaranteed present in the map (pigeonhole
over the Prop. 4 cover), so a query is done once at least ``k``
positions <= min(t, t_stop) are mapped, or the walk has passed
``t_stop`` (the per-query stop-below bound; the full walk length when
unbounded). The check runs every ``check_every`` iterations (it scans
the position map), and the loop also yields after ``budget``
iterations: past that point one exhaustive ``device_probe_scan``
launch is cheaper than continuing to grind tile-by-tile through a
combinatorially deep walk — the device analogue of the host path's
enumeration-cap scan fallback.

Oversized buckets are split across iterations: when even a single
stream entry exceeds ``cap`` candidates for some query, the iteration
takes ``cap`` of them and resumes the same entry at offset ``off``
next round, so device memory stays bounded by (B, cap, W) regardless
of bucket skew.

``device_probe_scan`` is the fallback for truncated schedules (stream
cap or KMAX abort — the device analogue of the host enumeration-cap
guard): one launch verifies EVERY code against the still-undone
queries in chunks of a ``lax.map``, yielding the complete position
map. Either way a z-group costs O(1) launches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.metrics import REGISTRY
from . import ref
from .verify_tuples import DEFAULT_BLK_C, verify_tuples_grouped

POS_INF = jnp.int32(0x7FFFFFFF)

# Trace-time counters ``traces.<kernel>`` in the metrics registry (as in
# verify_tuples): bumped only when jax traces a new (shape, static-arg)
# signature, so tests can assert the power-of-two padding keeps the jit
# cache bounded.


def _verify(q_words, gathered, totals, *, p, cap, use_pallas, interpret):
    """Packed bucket keys of the gathered (B, cap, W) candidates:
    Pallas kernel natively on TPU, XLA reference elsewhere."""
    if use_pallas:
        return verify_tuples_grouped(
            q_words, gathered, totals,
            p=p, blk_c=min(DEFAULT_BLK_C, cap), interpret=interpret,
        )
    return ref.verify_tuples_grouped_ref(q_words, gathered, totals, p)


@functools.partial(
    jax.jit,
    static_argnames=(
        "p", "tile", "cap", "kmax", "check_every", "use_pallas", "interpret"
    ),
)
def device_probe_walk(
    q_words,      # (B, W) uint32 packed queries
    q_sub,        # (B, m) int32 query substring values
    z_sub,        # (B, m) int32 substring popcounts
    pow1,         # (B, m, wmax+1) int32 one-position bit values
    pow0,         # (B, m, wmax+1) int32 zero-position bit values
    t_stop,       # (B,) int32 last walk position to consider (<0: done)
    k_arr,        # () int32 results wanted per query
    s_len,        # () int32 real stream entries
    budget,       # () int32 max iterations before the scan fallback
    tbl,          # (P,) int32 stream: table id per entry
    step_ext,     # (P+1,) int32 stream: walk step per entry (ext: built)
    idx1,         # (P, kmax) int32 one-side combination indices
    idx0,         # (P, kmax) int32 zero-side combination indices
    maxi1,        # (P,) int32 largest one-side index (-1: none)
    maxi0,        # (P,) int32 largest zero-side index (-1: none)
    widths,       # (m,) int32 substring widths
    offsets,      # (m, 2^wmax + 1) int32 dense CSR bucket offsets
    bucket_ids,   # (m, n_pad) int32 CSR sorted ids (pad: n_pad)
    db_pad,       # (n_pad, W) uint32 zero-padded packed codes
    inv_pos,      # ((p+1)^2,) int32 packed key -> walk position
    *,
    p: int,
    tile: int,
    cap: int,
    kmax: int,
    check_every: int,
    use_pallas: bool,
    interpret: bool,
):
    """One fused launch: walk the probe stream to completion or until
    every query terminates early. Returns (posmap (B, n_pad) int32,
    probes (B,) int32, retrieved (B,) int32, done (B,) bool,
    cursor () int32, iters () int32)."""
    REGISTRY.counter("traces.device_probe_walk").add(1)
    B = q_words.shape[0]
    n_pad = db_pad.shape[0]
    V = offsets.shape[1]
    wp1 = pow1.shape[2]
    col = jnp.arange(tile, dtype=jnp.int32)
    slot = jnp.arange(cap, dtype=jnp.int32)
    brow = jnp.arange(B, dtype=jnp.int32)[:, None]
    pow1f = pow1.reshape(B, -1)
    pow0f = pow0.reshape(B, -1)
    offsf = offsets.reshape(-1)
    idsf = bucket_ids.reshape(-1)

    posmap0 = jnp.full((B, n_pad), POS_INF, dtype=jnp.int32)
    zeros_b = jnp.zeros((B,), dtype=jnp.int32)
    carry0 = (
        jnp.int32(0),              # cursor: next stream entry
        jnp.int32(0),              # off: resume offset into entry cursor
        t_stop < 0,                # done
        posmap0,
        zeros_b,                   # probes (bucket lookups) per query
        zeros_b,                   # retrieved candidates per query
        jnp.int32(0),              # iterations
    )

    def cond(c):
        cursor, _, done, _, _, _, it = c
        return (cursor < s_len) & ~done.all() & (it < budget)

    def body(c):
        cursor, off, done, posmap, probes, retrieved, it = c
        # -- tile of stream entries (P >= s_len + tile: never clamps)
        t_tbl = lax.dynamic_slice(tbl, (cursor,), (tile,))
        t_idx1 = lax.dynamic_slice(idx1, (cursor, 0), (tile, kmax))
        t_idx0 = lax.dynamic_slice(idx0, (cursor, 0), (tile, kmax))
        t_m1 = lax.dynamic_slice(maxi1, (cursor,), (tile,))
        t_m0 = lax.dynamic_slice(maxi0, (cursor,), (tile,))
        in_stream = (cursor + col) < s_len
        # -- per-query validity: the canonical combination only names
        #    actual one/zero positions of THIS query's substring
        zq = jnp.take(z_sub, t_tbl, axis=1)              # (B, tile)
        wd = jnp.take(widths, t_tbl)                     # (tile,)
        valid = (
            in_stream[None, :]
            & (~done)[:, None]
            & (t_m1[None, :] < zq)
            & (t_m0[None, :] < (wd[None, :] - zq))
        )
        # -- bucket value: XOR the OR-ed flip bits into the substring
        mask = jnp.zeros((B, tile), dtype=jnp.int32)
        for j in range(kmax):
            mask = (
                mask
                | jnp.take(pow1f, t_tbl * wp1 + t_idx1[:, j], axis=1)
                | jnp.take(pow0f, t_tbl * wp1 + t_idx0[:, j], axis=1)
            )
        vals = jnp.clip(jnp.take(q_sub, t_tbl, axis=1) ^ mask, 0, V - 2)
        foff = t_tbl[None, :] * V + vals
        lo = jnp.take(offsf, foff)
        hi = jnp.take(offsf, foff + 1)
        sizes = jnp.where(valid, hi - lo, 0)
        # -- greedy prefix of entries whose total fits cap (per query);
        #    entry `cursor` may resume mid-bucket at offset `off`
        adj = jnp.maximum(
            sizes - jnp.where(col == 0, off, 0)[None, :], 0
        )
        csum = jnp.cumsum(adj, axis=1)
        fits = csum.max(axis=0) <= cap          # monotone: a prefix
        n_take = fits.sum().astype(jnp.int32)
        partial = n_take == 0                   # entry 0 alone overflows
        take_sizes = jnp.where(col[None, :] < n_take, adj, 0)
        take_sizes = jnp.where(
            partial,
            jnp.where(col[None, :] == 0, jnp.minimum(adj, cap), 0),
            take_sizes,
        )
        starts = jnp.cumsum(take_sizes, axis=1) - take_sizes
        totals = take_sizes.sum(axis=1)         # (B,) <= cap
        # -- expand ranges to slots: mark each entry's first slot with
        #    its tile index + 1, running-max fills the rest
        marks = jnp.zeros((B, cap), dtype=jnp.int32).at[
            brow, starts
        ].max((col[None, :] + 1) * (take_sizes > 0), mode="drop")
        ent = jnp.maximum(lax.cummax(marks, axis=1) - 1, 0)
        within = slot[None, :] - jnp.take_along_axis(starts, ent, axis=1)
        base = (
            jnp.take_along_axis(lo, ent, axis=1)
            + jnp.where(ent == 0, off, 0)
            + within
        )
        vslot = slot[None, :] < totals[:, None]
        tt = t_tbl[ent]                         # (B, cap)
        cand = jnp.take(idsf, tt * n_pad + jnp.clip(base, 0, n_pad - 1))
        cand = jnp.where(vslot, cand, n_pad)    # n_pad: dropped below
        gathered = jnp.take(
            db_pad, jnp.minimum(cand, n_pad - 1), axis=0
        )                                        # (B, cap, W)
        keys = _verify(
            q_words, gathered, totals,
            p=p, cap=cap, use_pallas=use_pallas, interpret=interpret,
        )
        pos = jnp.where(
            keys >= 0,
            jnp.take(inv_pos, jnp.maximum(keys, 0)),
            POS_INF,
        )
        # idempotent dedup: a rediscovered candidate scatters its same
        # exact position; out-of-range cand (pad slots, CSR pad) drops
        posmap = posmap.at[brow, cand].min(pos, mode="drop")
        # -- cost counters (resumed entry 0 counts once, at off == 0)
        probes = probes + jnp.where(
            partial,
            (valid[:, 0] & (off == 0)).astype(jnp.int32),
            (
                valid
                & (col[None, :] < n_take)
                & ~((col[None, :] == 0) & (off > 0))
            ).sum(axis=1).astype(jnp.int32),
        )
        retrieved = retrieved + totals
        cursor2 = jnp.where(partial, cursor, cursor + n_take)
        off2 = jnp.where(partial, off + cap, jnp.int32(0))
        it2 = it + 1

        def check(d):
            # last fully completed walk step: every code at a position
            # <= T_comp is in the map (pigeonhole over Prop. 4's cover)
            T_comp = jnp.take(step_ext, cursor2) - 1
            eff = jnp.minimum(T_comp, t_stop)
            cnt = (posmap <= eff[:, None]).sum(axis=1)
            return d | (cnt >= k_arr) | (T_comp >= t_stop)

        done2 = lax.cond(
            ((it2 % check_every) == 0) | (cursor2 >= s_len),
            check,
            lambda d: d,
            done,
        )
        return (cursor2, off2, done2, posmap, probes, retrieved, it2)

    cursor, _, done, posmap, probes, retrieved, iters = lax.while_loop(
        cond, body, carry0
    )
    return posmap, probes, retrieved, done, cursor, iters


@functools.partial(
    jax.jit, static_argnames=("p", "chunk", "use_pallas", "interpret")
)
def device_probe_scan(
    q_words,      # (B, W) uint32 packed queries
    db_pad,       # (n_pad, W) uint32 zero-padded packed codes
    inv_pos,      # ((p+1)^2,) int32 packed key -> walk position
    n_valid,      # () int32 real code count (pad rows -> POS_INF)
    *,
    p: int,
    chunk: int,
    use_pallas: bool,
    interpret: bool,
):
    """Exhaustive position map: verify EVERY code against every query in
    one launch (``lax.map`` over row chunks keeps peak memory at
    (B, chunk, W)). Returns (B, n_pad) int32 exact walk positions —
    the fused form of the host enumeration-cap scan fallback."""
    REGISTRY.counter("traces.device_probe_scan").add(1)
    B, W = q_words.shape
    n_pad = db_pad.shape[0]
    assert n_pad % chunk == 0, (n_pad, chunk)
    lens = jnp.full((B,), chunk, dtype=jnp.int32)

    def one(args):
        ci, db_chunk = args
        gathered = jnp.broadcast_to(db_chunk[None], (B, chunk, W))
        keys = _verify(
            q_words, gathered, lens,
            p=p, cap=chunk, use_pallas=use_pallas, interpret=interpret,
        )
        rowid = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
        return jnp.where(
            (keys >= 0) & (rowid[None, :] < n_valid),
            jnp.take(inv_pos, jnp.maximum(keys, 0)),
            POS_INF,
        )

    parts = lax.map(
        one,
        (
            jnp.arange(n_pad // chunk, dtype=jnp.int32),
            db_pad.reshape(n_pad // chunk, chunk, W),
        ),
    )
    return jnp.transpose(parts, (1, 0, 2)).reshape(B, n_pad)


def device_probe_walk_batched(
    posmap_in,    # (B, n_pad) int32 scratch (donated; contents ignored)
    q_words,      # (B, W) uint32 packed queries (mixed z-groups)
    q_sub,        # (B, m) int32 query substring values
    z_sub,        # (B, m) int32 substring popcounts
    pow1,         # (B, m, wmax+1) int32 one-position bit values
    pow0,         # (B, m, wmax+1) int32 zero-position bit values
    gid,          # (B,) int32 schedule-stack row per query
    t_stop,       # (B,) int32 last walk position to consider (<0: done)
    k_arr,        # () int32 results wanted per query
    budget,       # () int32 max iterations before the scan fallback
    g_start,      # (G,) int32 segment start per stack row (pad: 0)
    g_end,        # (G,) int32 segment start + s_len per row (pad: 0)
    tbl,          # (Pt,) int32 concatenated streams: table id per entry
    step_flat,    # (Pt,) int32 walk step per entry (segment pad: built)
    idx1,         # (Pt, kmax) int32 one-side combination indices
    idx0,         # (Pt, kmax) int32 zero-side combination indices
    maxi1,        # (Pt,) int32 largest one-side index (-1: none)
    maxi0,        # (Pt,) int32 largest zero-side index (-1: none)
    widths,       # (m,) int32 substring widths
    offsets,      # (m, 2^wmax + 1) int32 dense CSR bucket offsets
    bucket_ids,   # (m, n_pad) int32 CSR sorted ids (pad: n_pad)
    db_pad,       # (n_pad, W) uint32 zero-padded packed codes
    inv_pos,      # (G, (p+1)^2) int32 packed key -> walk position per row
    *,
    p: int,
    tile: int,
    cap: int,
    kmax: int,
    check_every: int,
    use_pallas: bool,
    interpret: bool,
):
    """Cross-z-group fused walk: ONE launch per batch, not per z-group.

    Every query carries a ``gid`` row into the concatenated schedule
    stack (``repro.core.probe_device.ScheduleStack``); the carry holds
    one absolute stream cursor and mid-bucket resume offset PER GROUP
    plus per-query done flags, so each group consumes its own stream at
    its own pace while every group's queries share each iteration's
    lookup/verify work. Per-group tile consumption is the per-z-group
    kernel's, computed with a segment scatter-max over that group's
    queries — cursor trajectories (and hence results and counters) are
    identical to running ``device_probe_walk`` once per group.

    A group advances only while it has an undone query and stream left
    (``active``); exhausted groups freeze and their unfinished queries
    fall through to the fused multi-group scan. Returns (posmap
    (B, n_pad) int32, probes (B,) int32, retrieved (B,) int32, done
    (B,) bool, cursor (G,) int32, iters () int32)."""
    REGISTRY.counter("traces.device_probe_walk_batched").add(1)
    B = q_words.shape[0]
    G = g_start.shape[0]
    n_pad = db_pad.shape[0]
    Pt = tbl.shape[0]
    V = offsets.shape[1]
    wp1 = pow1.shape[2]
    pp2 = inv_pos.shape[1]
    col = jnp.arange(tile, dtype=jnp.int32)
    slot = jnp.arange(cap, dtype=jnp.int32)
    brow = jnp.arange(B, dtype=jnp.int32)[:, None]
    pow1f = pow1.reshape(B, -1)
    pow0f = pow0.reshape(B, -1)
    offsf = offsets.reshape(-1)
    idsf = bucket_ids.reshape(-1)
    inv_posf = inv_pos.reshape(-1)
    g_end_q = jnp.take(g_end, gid)             # (B,)

    posmap0 = jnp.full_like(posmap_in, POS_INF)
    zeros_b = jnp.zeros((B,), dtype=jnp.int32)
    carry0 = (
        g_start,                       # (G,) cursor: next stream entry
        jnp.zeros((G,), jnp.int32),    # (G,) off: mid-bucket resume
        t_stop < 0,                    # (B,) done
        posmap0,
        zeros_b,                       # probes (bucket lookups) per query
        zeros_b,                       # retrieved candidates per query
        jnp.int32(0),                  # iterations
    )

    def group_active(cursor, done):
        g_undone = jnp.zeros((G,), bool).at[gid].max(~done, mode="drop")
        return g_undone & (cursor < g_end)

    def cond(c):
        cursor, _, done, _, _, _, it = c
        return group_active(cursor, done).any() & (it < budget)

    def body(c):
        cursor, off, done, posmap, probes, retrieved, it = c
        active_g = group_active(cursor, done)
        curq = jnp.take(cursor, gid)           # (B,)
        offq = jnp.take(off, gid)              # (B,)
        # -- per-query tile of the group's stream (absolute indices;
        #    clamped for gather safety — out-of-segment entries are
        #    masked by in_stream, so their values never matter)
        raw = curq[:, None] + col[None, :]     # (B, tile)
        tidx = jnp.minimum(raw, Pt - 1)
        t_tbl = jnp.take(tbl, tidx)            # (B, tile)
        t_m1 = jnp.take(maxi1, tidx)
        t_m0 = jnp.take(maxi0, tidx)
        in_stream = raw < g_end_q[:, None]
        zq = jnp.take_along_axis(z_sub, t_tbl, axis=1)
        wd = jnp.take(widths, t_tbl)           # (B, tile)
        valid = (
            in_stream
            & (~done)[:, None]
            & (t_m1 < zq)
            & (t_m0 < (wd - zq))
        )
        # -- bucket value: XOR the OR-ed flip bits into the substring
        mask = jnp.zeros((B, tile), dtype=jnp.int32)
        for j in range(kmax):
            i1 = jnp.take(idx1[:, j], tidx)
            i0 = jnp.take(idx0[:, j], tidx)
            mask = (
                mask
                | jnp.take_along_axis(pow1f, t_tbl * wp1 + i1, axis=1)
                | jnp.take_along_axis(pow0f, t_tbl * wp1 + i0, axis=1)
            )
        vals = jnp.clip(
            jnp.take_along_axis(q_sub, t_tbl, axis=1) ^ mask, 0, V - 2
        )
        foff = t_tbl * V + vals
        lo = jnp.take(offsf, foff)
        hi = jnp.take(offsf, foff + 1)
        sizes = jnp.where(valid, hi - lo, 0)
        # -- greedy per-group prefix of entries whose total fits cap:
        #    the group's limit is the max over ITS queries (segment
        #    scatter-max), exactly the per-z-group kernel's csum.max
        adj = jnp.maximum(
            sizes - jnp.where(col == 0, offq[:, None], 0), 0
        )
        csum = jnp.cumsum(adj, axis=1)
        gmax = jnp.zeros((G, tile), dtype=jnp.int32).at[gid].max(
            csum, mode="drop"
        )
        fits_g = gmax <= cap                    # monotone: a prefix
        n_take_g = fits_g.sum(axis=1).astype(jnp.int32)   # (G,)
        partial_g = n_take_g == 0               # entry 0 alone overflows
        n_take_q = jnp.take(n_take_g, gid)      # (B,)
        partial_q = jnp.take(partial_g, gid)    # (B,)
        take_sizes = jnp.where(col[None, :] < n_take_q[:, None], adj, 0)
        take_sizes = jnp.where(
            partial_q[:, None],
            jnp.where(col[None, :] == 0, jnp.minimum(adj, cap), 0),
            take_sizes,
        )
        starts = jnp.cumsum(take_sizes, axis=1) - take_sizes
        totals = take_sizes.sum(axis=1)         # (B,) <= cap
        # -- expand ranges to slots: mark each entry's first slot with
        #    its tile index + 1, running-max fills the rest
        marks = jnp.zeros((B, cap), dtype=jnp.int32).at[
            brow, starts
        ].max((col[None, :] + 1) * (take_sizes > 0), mode="drop")
        ent = jnp.maximum(lax.cummax(marks, axis=1) - 1, 0)
        within = slot[None, :] - jnp.take_along_axis(starts, ent, axis=1)
        base = (
            jnp.take_along_axis(lo, ent, axis=1)
            + jnp.where(ent == 0, offq[:, None], 0)
            + within
        )
        vslot = slot[None, :] < totals[:, None]
        tt = jnp.take_along_axis(t_tbl, ent, axis=1)      # (B, cap)
        cand = jnp.take(idsf, tt * n_pad + jnp.clip(base, 0, n_pad - 1))
        cand = jnp.where(vslot, cand, n_pad)    # n_pad: dropped below
        gathered = jnp.take(
            db_pad, jnp.minimum(cand, n_pad - 1), axis=0
        )                                        # (B, cap, W)
        keys = _verify(
            q_words, gathered, totals,
            p=p, cap=cap, use_pallas=use_pallas, interpret=interpret,
        )
        pos = jnp.where(
            keys >= 0,
            jnp.take(inv_posf, gid[:, None] * pp2 + jnp.maximum(keys, 0)),
            POS_INF,
        )
        posmap = posmap.at[brow, cand].min(pos, mode="drop")
        # -- cost counters (resumed entry 0 counts once, at off == 0)
        probes = probes + jnp.where(
            partial_q,
            (valid[:, 0] & (offq == 0)).astype(jnp.int32),
            (
                valid
                & (col[None, :] < n_take_q[:, None])
                & ~((col[None, :] == 0) & (offq > 0)[:, None])
            ).sum(axis=1).astype(jnp.int32),
        )
        retrieved = retrieved + totals
        # frozen groups (all queries done, or stream exhausted) keep
        # their cursor/off: they did no work this iteration
        adv = active_g & ~partial_g
        cursor2 = jnp.where(adv, cursor + n_take_g, cursor)
        off2 = jnp.where(
            active_g,
            jnp.where(partial_g, off + cap, jnp.int32(0)),
            off,
        )
        it2 = it + 1

        def check(d):
            # last fully completed walk step OF THE QUERY'S GROUP: every
            # code at a position <= T_comp is in the map (pigeonhole)
            cq = jnp.minimum(jnp.take(cursor2, gid), Pt - 1)
            T_comp = jnp.take(step_flat, cq) - 1
            eff = jnp.minimum(T_comp, t_stop)
            cnt = (posmap <= eff[:, None]).sum(axis=1)
            return d | (cnt >= k_arr) | (T_comp >= t_stop)

        done2 = lax.cond(
            ((it2 % check_every) == 0)
            | ~group_active(cursor2, done).any(),
            check,
            lambda d: d,
            done,
        )
        return (cursor2, off2, done2, posmap, probes, retrieved, it2)

    cursor, _, done, posmap, probes, retrieved, iters = lax.while_loop(
        cond, body, carry0
    )
    return posmap, probes, retrieved, done, cursor, iters


def device_probe_scan_multi(
    q_words,      # (B, W) uint32 packed queries (mixed z-groups)
    gid,          # (B,) int32 schedule-stack row per query
    db_pad,       # (n_pad, W) uint32 zero-padded packed codes
    inv_pos,      # (G, (p+1)^2) int32 packed key -> walk position per row
    n_valid,      # () int32 real code count (pad rows -> POS_INF)
    *,
    p: int,
    chunk: int,
    use_pallas: bool,
    interpret: bool,
):
    """Cross-z-group exhaustive position map: ``device_probe_scan`` with
    a per-query ``gid`` row into the stacked inverse-position tables, so
    ONE launch finishes the bailed queries of EVERY group in the batch.
    Returns (B, n_pad) int32 exact walk positions."""
    REGISTRY.counter("traces.device_probe_scan_multi").add(1)
    B, W = q_words.shape
    n_pad = db_pad.shape[0]
    pp2 = inv_pos.shape[1]
    inv_posf = inv_pos.reshape(-1)
    assert n_pad % chunk == 0, (n_pad, chunk)
    lens = jnp.full((B,), chunk, dtype=jnp.int32)

    def one(args):
        ci, db_chunk = args
        gathered = jnp.broadcast_to(db_chunk[None], (B, chunk, W))
        keys = _verify(
            q_words, gathered, lens,
            p=p, cap=chunk, use_pallas=use_pallas, interpret=interpret,
        )
        rowid = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
        return jnp.where(
            (keys >= 0) & (rowid[None, :] < n_valid),
            jnp.take(
                inv_posf, gid[:, None] * pp2 + jnp.maximum(keys, 0)
            ),
            POS_INF,
        )

    parts = lax.map(
        one,
        (
            jnp.arange(n_pad // chunk, dtype=jnp.int32),
            db_pad.reshape(n_pad // chunk, chunk, W),
        ),
    )
    return jnp.transpose(parts, (1, 0, 2)).reshape(B, n_pad)
