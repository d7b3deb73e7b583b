"""Device-sharded angular search: DB rows split over mesh axes.

The 10^9+-code regime (paper §6, SIFT-1B) does not fit one accelerator's
HBM; production deployments shard the packed code array row-wise across
the ``data`` axis (and across pods via the ``pod`` axis). A query
broadcast to all shards runs the streaming scan kernels locally, keeps a
local top-K, and the K-sized partials are all-gathered (K * devices
values, tiny) — one all-gather of O(K) per query batch, no code movement.

Two merge shapes:

  - ``sharded_scan_topk``: gather + re-select the global top-K on device
    (float32 end to end) — the retrieval-step / dry-run path.
  - ``sharded_scan_candidates``: gather WITHOUT the final re-selection,
    returning every shard's top-``k_fetch`` (global ids, -1 in invalid
    slots). The sharded engine reranks this pool on host in exact float64
    so its results stay bit-identical to ``linear_scan_knn``; pad rows of
    a ShardPlan layout are masked on device (``scan_topk n_valid``), so
    uneven N never leaks zero-code pads into the pool.

This module is pure pjit/shard_map JAX and is exercised both by tests
(with 8 fake CPU devices in a subprocess) and by the production-mesh
dry-run (``retrieval_step``). It covers the *scan* side of mesh
residency; the sharded AMIH engine reaches the same placement without
shard_map — each shard's index commits its codes to the plan-assigned
device (``ShardPlan.devices``) and issues per-device verify launches
through kernels/ops.py (host-driven, since probing is a host-side table
walk).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import ops
from .plan import ShardPlan, resolve_mesh_axes

__all__ = [
    "make_retrieval_step",
    "sharded_scan_candidates",
    "sharded_scan_topk",
]


def _shard_index(mesh: Mesh, axes) -> jax.Array:
    """Linear shard index of the executing device (row-major over axes)."""
    idx = jnp.int32(0)
    for ax in axes:
        idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
    return idx


def _local_topk_then_merge(q_words, db_shard, shard_offset, k, chunk, axes):
    """Per-shard body: local streaming top-K then cross-shard merge."""
    sims, ids = ops.scan_topk(q_words, db_shard, k, chunk=chunk)
    ids = ids + shard_offset            # local -> global ids
    # all-gather the K-sized partials along the DB-sharding axes
    all_sims = sims
    all_ids = ids
    for ax in axes:
        all_sims = jax.lax.all_gather(all_sims, ax, axis=1, tiled=True)
        all_ids = jax.lax.all_gather(all_ids, ax, axis=1, tiled=True)
    return ops.merge_topk(all_sims, all_ids, k)


def sharded_scan_topk(
    mesh: Mesh,
    q_words: jax.Array,
    db_words: jax.Array,
    k: int,
    *,
    chunk: int = 1 << 14,
    shard_axes: Optional[Tuple[str, ...]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact global angular top-K with the DB row-sharded over the mesh.

    q_words: (B, W) replicated; db_words: (N, W) sharded on rows.
    Returns (sims, ids) (B, k) replicated. N must divide evenly by the
    number of DB shards (pad the DB with zero codes otherwise — zero codes
    score 0.0 and are filtered by id >= 0 semantics upstream).

    shard_axes defaults to EVERY mesh axis (§Perf iteration R1): the scan
    is embarrassingly row-parallel, so the original pod/data-only layout
    left the 16-wide 'model' axis idle — 16x redundant per-device work.
    """
    db_axes, n_shards = resolve_mesh_axes(mesh, shard_axes)
    N = db_words.shape[0]
    assert N % n_shards == 0, (N, n_shards)
    shard_rows = N // n_shards

    def body(q, db_shard):
        offset = (_shard_index(mesh, db_axes) * shard_rows).astype(jnp.int32)
        return _local_topk_then_merge(q, db_shard, offset, k, chunk, db_axes)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(db_axes)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return fn(q_words, db_words)


def sharded_scan_candidates(
    mesh: Mesh,
    q_words: jax.Array,
    db_padded: jax.Array,
    plan: ShardPlan,
    k_fetch: int,
    *,
    chunk: int = 1 << 14,
) -> Tuple[jax.Array, jax.Array]:
    """Per-shard top-``k_fetch`` pools, gathered but NOT re-selected.

    ``db_padded`` is the plan's device layout ((S * rows_padded, W),
    sharded on rows over ``plan.axis_names``); each shard's scan masks
    its pad rows via the plan's per-shard ``counts`` and maps local rows
    to global ids via ``starts``. Returns replicated
    (sims (B, S * k_fetch) float32, gids (B, S * k_fetch) int32) with
    sim = -inf / gid = -1 in invalid slots — the host-rerank candidate
    pool of the sharded_scan engine.
    """
    axes, n_shards = resolve_mesh_axes(mesh, plan.axis_names or None)
    if n_shards != plan.num_shards:
        raise ValueError(
            f"plan has {plan.num_shards} shards but mesh axes {axes} "
            f"give {n_shards}"
        )
    starts = jnp.asarray(plan.starts, dtype=jnp.int32)
    counts = jnp.asarray(plan.counts, dtype=jnp.int32)

    def body(q, db_shard, starts_arr, counts_arr):
        idx = _shard_index(mesh, axes)
        sims, ids = ops.scan_topk(
            q, db_shard, k_fetch, chunk=chunk, n_valid=counts_arr[idx]
        )
        gids = jnp.where(sims > -jnp.inf, ids + starts_arr[idx], -1)
        for ax in axes:
            sims = jax.lax.all_gather(sims, ax, axis=1, tiled=True)
            gids = jax.lax.all_gather(gids, ax, axis=1, tiled=True)
        return sims, gids

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(axes), P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return fn(q_words, db_padded, starts, counts)


def make_retrieval_step(
    mesh: Mesh,
    k: int,
    chunk: int = 1 << 14,
    shard_axes: Optional[Tuple[str, ...]] = None,
):
    """jit-able retrieval step for serving + the production dry-run."""
    if shard_axes is None:
        shard_axes = tuple(mesh.axis_names)

    @functools.partial(jax.jit, static_argnums=())
    def retrieval_step(q_words, db_words):
        return sharded_scan_topk(
            mesh, q_words, db_words, k, chunk=chunk, shard_axes=shard_axes
        )

    in_shardings = (
        NamedSharding(mesh, P()),
        NamedSharding(mesh, P(shard_axes)),
    )
    return retrieval_step, in_shardings
