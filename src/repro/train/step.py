"""jit'd train/serve step construction — the single source of truth used by
the Trainer, the serving engine, the benchmarks AND the production dry-run
(launch/dryrun.py lowers exactly these functions, so what is dry-run is
what runs).

``make_train_step``: loss -> grad -> (optional scan-microbatched
accumulation) -> (optional int8 compressed data-parallel mean) -> AdamW.
Gradients are mean-reduced over the batch axes implicitly by pjit (the
batch is sharded over pod/data; XLA inserts the reduce-scatter/all-reduce);
the explicit shard_map compression path replaces that collective with the
int8 error-feedback one.

``make_serve_step``: one decode token against a seq_len KV cache, the
function lowered for the decode_* / long_* dry-run shapes.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import Model, input_specs
from ..models.common import ArchConfig, ShapeConfig
from ..models.sharding import DEFAULT_RULES, Rules, sharding_context
from ..optim import OptimConfig, apply_updates, init_state, state_specs
from ..launch import shardings as sh
from ..launch.mesh import auto_axes

__all__ = ["TrainConfig", "make_train_step", "make_serve_step"]


@dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1            # grad-accumulation steps (lax.scan)
    grad_compression: str = "none"   # none | int8 (error-feedback DP mean)
    compression_block: int = 256


def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def _tree_scale(a, s):
    return jax.tree.map(lambda x: x * s, a)


def make_train_step(
    cfg: ArchConfig,
    ocfg: OptimConfig,
    tcfg: TrainConfig = TrainConfig(),
    mesh: Optional[Mesh] = None,
    rules: Optional[Rules] = None,
    log: Optional[list] = None,
    opt_rules: Optional[Rules] = None,
):
    """Build (train_step, specs) for one architecture.

    Returns a dict with:
      step:            jit'd (params, opt_state, batch) -> (params, opt_state, metrics)
      param_specs:     ShapeDtypeStruct tree
      opt_specs:       ShapeDtypeStruct tree
      in_shardings:    (params, opt, batch) NamedSharding trees (mesh != None)
      out_shardings:   (params, opt, None)
      init:            (key) -> (params, opt_state) materializer
    """
    model = Model(cfg)
    mesh = None if mesh is None else auto_axes(mesh)
    rules = dict(rules or DEFAULT_RULES)

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        if tcfg.microbatches <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return grads, metrics
        nm = tcfg.microbatches

        def split(x):
            b = x.shape[0]
            assert b % nm == 0, (b, nm)
            return x.reshape((nm, b // nm) + x.shape[1:])

        mbs = jax.tree.map(split, batch)

        def body(acc, mb):
            (loss, metrics), grads = grad_fn(params, mb)
            return _tree_add(acc, grads), metrics

        zero = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        acc, metrics = jax.lax.scan(body, zero, mbs)
        grads = _tree_scale(acc, 1.0 / nm)
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return grads, metrics

    def train_step(params, opt_state, batch):
        if mesh is not None:
            ctx = sharding_context(mesh, rules, log)
        else:
            from contextlib import nullcontext

            ctx = nullcontext()
        with ctx:
            grads, metrics = compute_grads(params, batch)
            new_params, new_opt, om = apply_updates(
                ocfg, params, grads, opt_state
            )
        metrics = dict(metrics)
        metrics.update(om)
        return new_params, new_opt, metrics

    out: Dict[str, Any] = {
        "param_specs": model.param_specs(),
        "opt_specs": state_specs(ocfg, model.param_specs()),
    }

    def init(key):
        params = model.init_params(key)
        return params, init_state(ocfg, params)

    out["init"] = init

    if mesh is None:
        out["step"] = jax.jit(train_step, donate_argnums=(0, 1))
        return out

    pshard = sh.param_shardings(cfg, mesh, rules, log)
    # optimizer-only rules (ZeRO-style): moments may shard over the data
    # axes even where the live params do not — XLA inserts the
    # reduce-scatter(grads)/all-gather(updates) pair around the update
    oshard = sh.opt_shardings(ocfg, cfg, mesh, opt_rules or rules, log)
    out["in_shardings"] = (pshard, oshard)
    out["out_shardings"] = (pshard, oshard, None)

    def batch_shardings(batch_specs):
        return sh.batch_shardings(cfg, mesh, rules, batch_specs, log)

    out["batch_shardings"] = batch_shardings
    out["step"] = jax.jit(
        train_step,
        in_shardings=(pshard, oshard, None),  # batch shardings set at lower
        out_shardings=(pshard, oshard, None),
        donate_argnums=(0, 1),
    )

    def lower_for(shape: ShapeConfig):
        """Lower against ShapeDtypeStructs (the dry-run entry point)."""
        bspecs = input_specs(cfg, shape)
        bshard = batch_shardings(bspecs)
        specs_sharded = {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=bshard[k])
            for k, v in bspecs.items()
        }
        fn = jax.jit(
            train_step,
            in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard, None),
            donate_argnums=(0, 1),
        )
        return fn.lower(out["param_specs"], out["opt_specs"], specs_sharded)

    out["lower_for"] = lower_for
    return out


def make_serve_step(
    cfg: ArchConfig,
    mesh: Optional[Mesh] = None,
    rules: Optional[Rules] = None,
    log: Optional[list] = None,
):
    """Build the single-token decode step (the decode-shape dry-run target).

    Returns dict with:
      step:       (params, cache, tokens, pos) -> (logits, cache)
      lower_for:  (shape) -> lowered (mesh != None); cache sized to
                  shape.seq_len, batch = shape.global_batch
    """
    model = Model(cfg)
    mesh = None if mesh is None else auto_axes(mesh)
    rules = dict(rules or DEFAULT_RULES)

    def serve_step(params, cache, tokens, pos):
        if mesh is not None:
            ctx = sharding_context(mesh, rules, log)
        else:
            from contextlib import nullcontext

            ctx = nullcontext()
        with ctx:
            logits, new_cache = model.decode_step(params, cache, tokens, pos)
        return logits, new_cache

    out: Dict[str, Any] = {"param_specs": model.param_specs()}
    if mesh is None:
        out["step"] = jax.jit(serve_step, donate_argnums=(1,))
        return out

    pshard = sh.param_shardings(cfg, mesh, rules, log)

    def lower_for(shape: ShapeConfig):
        B, S = shape.global_batch, shape.seq_len
        cache_tpl = model.cache_template(B, S)
        cshard = sh.cache_shardings(cfg, mesh, rules, cache_tpl, log)
        cache_specs = jax.tree.map(
            lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
            cache_tpl,
            cshard,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
        tok_shard = sh.batch_shardings(
            cfg, mesh, rules,
            {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)}, log,
        )["tokens"]
        tok_spec = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=tok_shard)
        pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
        fn = jax.jit(
            serve_step,
            in_shardings=(pshard, cshard, tok_shard, None),
            out_shardings=(None, cshard),
            donate_argnums=(1,),
        )
        return fn.lower(out["param_specs"], cache_specs, tok_spec, pos_spec)

    out["lower_for"] = lower_for

    def lower_prefill(shape: ShapeConfig):
        """Lower the full-sequence prefill (prefill_* shapes)."""
        bspecs = input_specs(cfg, shape)
        bshard = sh.batch_shardings(cfg, mesh, rules, bspecs, log)
        specs_sharded = {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=bshard[k])
            for k, v in bspecs.items()
        }

        def prefill_fn(params, batch):
            with sharding_context(mesh, rules, log):
                return model.prefill(params, batch)

        fn = jax.jit(
            prefill_fn, in_shardings=(pshard, bshard), out_shardings=None
        )
        return fn.lower(out["param_specs"], specs_sharded)

    out["lower_prefill"] = lower_prefill
    return out


def make_dp_compressed_train_step(
    cfg: ArchConfig,
    ocfg: OptimConfig,
    mesh: Mesh,
    block: int = 256,
):
    """Data-parallel train step with int8 error-feedback gradient all-reduce.

    shard_map over the data axes: params replicated, batch row-sharded,
    per-shard grads compressed to int8 (+ carried residual) before the
    cross-shard mean — the explicit form of the distributed-optimization
    trick. The returned step threads ``residuals`` (f32 pytree, one per
    param) alongside the optimizer state.

    Scope: DP axes only (params replicated across them). Composing with TP
    keeps the pjit path (make_train_step), where XLA owns the collective.
    """
    model = Model(cfg)
    from ..optim.compression import compressed_psum_mean

    dp_axes = tuple(n for n in ("pod", "data") if n in mesh.axis_names)

    def local_step(params, opt_state, residuals, batch):
        def loss_fn(p):
            loss, metrics = model.loss(p, batch)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params
        )
        mean_grads, new_res = compressed_psum_mean(
            grads, residuals, dp_axes, block
        )
        new_params, new_opt, om = apply_updates(
            ocfg, params, mean_grads, opt_state
        )
        metrics = dict(metrics)
        metrics.update(om)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, dp_axes), metrics)
        return new_params, new_opt, new_res, metrics

    rep = P()
    batch_spec = {"tokens": P(dp_axes)}
    if cfg.family == "vlm":
        batch_spec["vision_embeds"] = P(dp_axes)
    if cfg.family == "encdec":
        batch_spec["enc_frames"] = P(dp_axes)

    fn = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(rep, rep, rep, batch_spec),
        out_specs=(rep, rep, rep, rep),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0, 1, 2))
