"""Fused device-resident AMIH probing walk (paper §4–§5, one launch).

``device_probe_walk_batched`` compiles the whole probe -> bucket-lookup
-> verify -> top-K pipeline of a query batch into a single jitted
``lax.while_loop``: each iteration consumes a tile of the precomputed
probe stream (repro.core.probe_device.ScheduleStack) per z-group,
expands the CSR bucket ranges into at most ``cap`` candidate slots per
query, gathers the candidate codes from the device-resident padded DB,
popcount-verifies them (the ``verify_tuples_grouped`` Pallas kernel on
TPU, the XLA reference elsewhere), and merges each new candidate's exact
walk position into a per-query carry of its ``kf`` smallest (position,
id) pairs so far.

Each code enters the carry once. A code held in several tables is
reached once per table, each time from a different block of the stream
(one block per (table, one-flips a, zero-flips b)); the code's (a, b) in
every table is a function of the code and the query, so the walk looks
up where each of its blocks starts and keeps the code only from the
earliest. The walk consumes each group's stream as a prefix, so that
block has always been probed when a later one is. The same rule counts
the distinct codes verified, per query.

Early termination is Prop. 2's k-th-cosine bound translated to walk
positions: after the entries of walk step t are all consumed, every
code with position <= t has been met (pigeonhole over the Prop. 4
cover), so a query is done once at least ``k`` positions <= min(t,
t_stop) are in its carry, or the walk has passed ``t_stop`` (the
per-query stop-below bound; the full walk length when unbounded). The
loop also yields after ``budget`` iterations: past that point the
exhaustive scan is cheaper than grinding tile-by-tile through a
combinatorially deep walk — the device analogue of the host path's
enumeration-cap scan fallback.

Oversized buckets are split across iterations: when even a single
stream entry exceeds ``cap`` candidates for some query, the iteration
takes ``cap`` of them and resumes the same entry at offset ``off``
next round, so device memory stays bounded by (B, cap, W) regardless
of bucket skew.
"""

from __future__ import annotations

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


def device_probe_walk_batched(
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
    block_start,  # (G, m (wmax+1)^2) int32 first entry of each (s, a, b)
    widths,       # (m,) int32 substring widths
    sub_masks,    # (m, W) uint32 bits of each substring in the code words
    offsets,      # (m, 2^wmax + 1) int32 dense CSR bucket offsets
    bucket_ids,   # (m, n_pad) int32 CSR sorted ids (pad: n_pad)
    db_pad,       # (n_pad, W) uint32 zero-padded packed codes
    inv_pos,      # (G, (p+1)^2) int32 packed key -> walk position per row
    *,
    p: int,
    tile: int,
    cap: int,
    kf: int,
    kmax: int,
    use_pallas: bool,
    interpret: bool,
):
    """Cross-z-group fused walk: ONE launch per batch.

    Every query carries a ``gid`` row into the concatenated schedule
    stack (``repro.core.probe_device.ScheduleStack``); the carry holds
    one absolute stream cursor and mid-bucket resume offset PER GROUP
    plus per-query done flags, so each group consumes its own stream at
    its own pace while every group's queries share each iteration's
    lookup/verify work. A group's tile consumption is computed with a
    segment scatter-max over that group's queries, so its cursor moves
    exactly as it would in a launch of that group alone.

    A group advances only while it has an undone query and stream left
    (``active``); exhausted groups freeze and their unfinished queries
    fall through to the scan fallback. Returns (pos (B, kf) int32 and
    ids (B, kf) int32: each query's kf smallest (position, id) pairs in
    that order, POS_INF past the codes met; probes, retrieved and
    verified (B,) int32: bucket lookups, candidates pulled and distinct
    codes verified; done (B,) bool, cursor (G,) int32, iters () int32).
    """
    REGISTRY.counter("traces.device_probe_walk_batched").add(1)
    B = q_words.shape[0]
    G = g_start.shape[0]
    n_pad = db_pad.shape[0]
    Pt = tbl.shape[0]
    V = offsets.shape[1]
    m = offsets.shape[0]
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
    bstartf = block_start.reshape(-1)
    g_end_q = jnp.take(g_end, gid)             # (B,)
    # per-table masked query words, for the candidates' flips per table
    q_in = q_words[:, None, None, :] & sub_masks[None, None]  # (B,1,m,W)
    q_out = ~q_words[:, None, None, :] & sub_masks[None, None]
    blk_row = (gid[:, None, None] * m
               + jnp.arange(m, dtype=jnp.int32)[None, None, :])

    zeros_b = jnp.zeros((B,), dtype=jnp.int32)
    carry0 = (
        g_start,                       # (G,) cursor: next stream entry
        jnp.zeros((G,), jnp.int32),    # (G,) off: mid-bucket resume
        t_stop < 0,                    # (B,) done
        jnp.full((B, kf), POS_INF, jnp.int32),   # best positions
        jnp.full((B, kf), n_pad, jnp.int32),     # their ids
        zeros_b,                       # probes (bucket lookups) per query
        zeros_b,                       # retrieved candidates per query
        zeros_b,                       # distinct codes verified per query
        jnp.int32(0),                  # iterations
    )

    def group_active(cursor, done):
        g_undone = jnp.zeros((G,), bool).at[gid].max(~done, mode="drop")
        return g_undone & (cursor < g_end)

    def cond(c):
        cursor, done, it = c[0], c[2], c[-1]
        return group_active(cursor, done).any() & (it < budget)

    def body(c):
        (cursor, off, done, best_pos, best_id,
         probes, retrieved, verified, it) = c
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
        #    scatter-max)
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
        cand = jnp.where(vslot, cand, n_pad)
        gathered = jnp.take(
            db_pad, jnp.minimum(cand, n_pad - 1), axis=0
        )                                        # (B, cap, W)
        keys = _verify(
            q_words, gathered, totals,
            p=p, cap=cap, use_pallas=use_pallas, interpret=interpret,
        )
        # -- keep each code from the earliest block that holds it: its
        #    one-flips a and zero-flips b in every table name the block
        #    its bucket there lies in; the table whose block starts
        #    first is the one the walk met it through first
        g = gathered[:, :, None, :]
        fa = ref.popcount32(q_in & ~g).sum(axis=-1)       # (B, cap, m)
        fb = ref.popcount32(q_out & g).sum(axis=-1)
        first = jnp.argmin(
            jnp.take(bstartf, (blk_row * wp1 + fa) * wp1 + fb), axis=2
        )
        fresh = vslot & (keys >= 0) & (first == tt)
        pos = jnp.where(
            fresh,
            jnp.take(inv_posf, gid[:, None] * pp2 + jnp.maximum(keys, 0)),
            POS_INF,
        )
        best_pos, best_id = lax.sort(
            (jnp.concatenate([best_pos, pos], axis=1),
             jnp.concatenate([best_id, cand], axis=1)),
            num_keys=2,
        )
        best_pos, best_id = best_pos[:, :kf], best_id[:, :kf]
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
        verified = verified + fresh.sum(axis=1).astype(jnp.int32)
        # frozen groups (all queries done, or stream exhausted) keep
        # their cursor/off: they did no work this iteration
        adv = active_g & ~partial_g
        cursor2 = jnp.where(adv, cursor + n_take_g, cursor)
        off2 = jnp.where(
            active_g,
            jnp.where(partial_g, off + cap, jnp.int32(0)),
            off,
        )
        # last fully completed walk step OF THE QUERY'S GROUP: every
        # code at a position <= T_comp has been met (pigeonhole)
        cq = jnp.minimum(jnp.take(cursor2, gid), Pt - 1)
        T_comp = jnp.take(step_flat, cq) - 1
        eff = jnp.minimum(T_comp, t_stop)
        cnt = (best_pos <= eff[:, None]).sum(axis=1)
        done2 = done | (cnt >= k_arr) | (T_comp >= t_stop)
        return (cursor2, off2, done2, best_pos, best_id,
                probes, retrieved, verified, it + 1)

    (cursor, _, done, best_pos, best_id,
     probes, retrieved, verified, iters) = lax.while_loop(
        cond, body, carry0
    )
    return best_pos, best_id, probes, retrieved, verified, done, cursor, iters
