"""Unified LM: parameter templates, forward/loss, prefill, decode.

One module serves all 10 assigned architectures:
  dense / moe           decoder-only transformer (GQA/MQA, SwiGLU/GeGLU)
  ssm                   Mamba2 (SSD) stack, attention-free
  hybrid                Hymba-style parallel attn+SSM heads
  vlm                   decoder LM with stubbed patch-embedding inputs
  encdec                Whisper-style (see encdec.py; shares templates)

Parameters are described by a *template* pytree of ``PSpec`` records
(shape, logical axes, dtype kind, init kind). ``init_params`` materializes
it; ``param_specs`` turns it into ShapeDtypeStructs for the allocation-free
dry-run; ``logical_axes`` feeds the sharding rules. Per-layer parameters are
stacked on a leading "layers" axis and executed with lax.scan (+remat).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import ssm as ssm_lib
from .blocks import (
    AttnCache,
    LayerCache,
    block_decode,
    block_forward,
)
from .common import ArchConfig
from .sharding import shard_hint


class PSpec(NamedTuple):
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    kind: str = "p"        # p = param dtype, f = float32
    init: str = "normal"   # normal | out | zeros | ones | ssm_special


# ------------------------------------------------------------- templates
def _norm_t(cfg) -> Dict[str, PSpec]:
    d = cfg.d_model
    t = {"scale": PSpec((d,), ("embed",), "p", "ones")}
    if cfg.norm == "layernorm":
        t["bias"] = PSpec((d,), ("embed",), "p", "zeros")
    return t


def _attn_t(cfg) -> Dict[str, PSpec]:
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads_padded, cfg.n_kv_heads, cfg.head_dim_
    return {
        "wq": PSpec((d, hq, dh), ("embed", "q_heads", "head_dim")),
        "wk": PSpec((d, hkv, dh), ("embed", "kv_heads", "head_dim")),
        "wv": PSpec((d, hkv, dh), ("embed", "kv_heads", "head_dim")),
        "wo": PSpec((hq, dh, d), ("q_heads", "head_dim", "embed"), "p", "out"),
    }


def _mlp_t(cfg, d_ff: int) -> Dict[str, PSpec]:
    d = cfg.d_model
    t = {
        "wi": PSpec((d, d_ff), ("embed", "mlp")),
        "wo": PSpec((d_ff, d), ("mlp", "embed"), "p", "out"),
    }
    if cfg.activation in ("swiglu", "geglu"):
        t["wi_gate"] = PSpec((d, d_ff), ("embed", "mlp"))
    return t


def _moe_t(cfg) -> Dict[str, PSpec]:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_ff
    t = {
        "router": PSpec((d, e), ("embed", "experts"), "f"),
        "w_up": PSpec((e, d, f), ("experts", "embed", "mlp")),
        "w_down": PSpec((e, f, d), ("experts", "mlp", "embed"), "p", "out"),
    }
    if cfg.activation in ("swiglu", "geglu"):
        t["w_gate"] = PSpec((e, d, f), ("experts", "embed", "mlp"))
    return t


def _ssm_t(cfg) -> Dict[str, PSpec]:
    out = {}
    for name, (shape, axes, kind) in ssm_lib.ssm_param_shapes(cfg).items():
        init = "ssm_special" if name in ("A_log", "dt_bias", "D_skip") else (
            "out" if name == "out_proj" else
            "ones" if name == "norm_scale" else
            "zeros" if name == "conv_b" else "normal"
        )
        out[name] = PSpec(shape, axes, kind, init)
    return out


def layer_template(cfg: ArchConfig, moe: bool, cross_attn: bool = False):
    """Template for one layer (unstacked). Nested subdicts per sublayer."""
    t: Dict[str, Any] = {"ln1": _norm_t(cfg)}
    if cfg.family != "ssm":
        t["attn"] = _attn_t(cfg)
    if cfg.family in ("ssm", "hybrid"):
        t["ssm"] = _ssm_t(cfg)
    if cfg.family == "hybrid":
        d = cfg.d_model
        t["fuse_attn"] = PSpec((d,), ("embed",), "p", "ones")
        t["fuse_ssm"] = PSpec((d,), ("embed",), "p", "ones")
    if cross_attn:
        t["lnx"] = _norm_t(cfg)
        t["xattn"] = _attn_t(cfg)
    if moe and cfg.is_moe:
        t["ln2"] = _norm_t(cfg)
        t["moe"] = _moe_t(cfg)
        if cfg.moe_dense_residual_ff:
            t["moe_dense"] = _mlp_t(cfg, cfg.moe_dense_residual_ff)
    elif cfg.d_ff > 0:
        t["ln2"] = _norm_t(cfg)
        t["mlp"] = _mlp_t(cfg, cfg.d_ff)
    return t


def _stack(template, n: int):
    return jax.tree.map(
        lambda s: PSpec((n,) + s.shape, ("layers",) + s.axes, s.kind, s.init),
        template,
        is_leaf=lambda x: isinstance(x, PSpec),
    )


def model_template(cfg: ArchConfig):
    d, v = cfg.d_model, cfg.vocab_size
    t: Dict[str, Any] = {
        "embed": PSpec((v, d), ("vocab", "embed")),
        "final_norm": _norm_t(cfg),
    }
    if not cfg.tie_embeddings:
        t["unembed"] = PSpec((d, v), ("embed", "vocab"), "p", "out")
    n_main = cfg.n_layers - cfg.first_k_dense
    if cfg.first_k_dense:
        dense_cfg_t = layer_template(cfg.replace(n_experts=0), moe=False)
        t["front_layers"] = _stack(dense_cfg_t, cfg.first_k_dense)
    t["layers"] = _stack(layer_template(cfg, moe=True), n_main)
    if cfg.family == "vlm":
        t["vision_adapter"] = PSpec((d, d), ("embed", None))
    if cfg.family == "encdec":
        t["enc_layers"] = _stack(
            layer_template(cfg, moe=False), cfg.n_encoder_layers
        )
        t["enc_norm"] = _norm_t(cfg)
        # decoder layers get cross-attention
        t["layers"] = _stack(
            layer_template(cfg, moe=True, cross_attn=True), cfg.n_layers
        )
    return t


# -------------------------------------------------------- materialization
def _is_pspec(x):
    return isinstance(x, PSpec)


def count_params(cfg: ArchConfig, active_only: bool = False) -> int:
    total = 0
    for path, spec in jax.tree.flatten_with_path(
        model_template(cfg), is_leaf=_is_pspec
    )[0]:
        n = math.prod(spec.shape)
        if active_only and cfg.is_moe:
            name = getattr(path[-1], "key", str(path[-1]))
            if name in ("w_up", "w_down", "w_gate"):  # routed experts
                n = n * cfg.experts_per_token // cfg.n_experts
        total += n
    return total


def param_specs(cfg: ArchConfig):
    """Pytree of ShapeDtypeStruct mirroring init_params (no allocation)."""
    pdt = cfg.pdtype()

    def to_sds(s: PSpec):
        return jax.ShapeDtypeStruct(
            s.shape, jnp.float32 if s.kind == "f" else pdt
        )

    return jax.tree.map(to_sds, model_template(cfg), is_leaf=_is_pspec)


def logical_axes(cfg: ArchConfig):
    return jax.tree.map(
        lambda s: s.axes, model_template(cfg), is_leaf=_is_pspec
    )


def init_params(cfg: ArchConfig, key: jax.Array):
    """Materialize real parameters (smoke tests / examples / training)."""
    pdt = cfg.pdtype()
    flat, treedef = jax.tree.flatten_with_path(
        model_template(cfg), is_leaf=_is_pspec
    )
    keys = jax.random.split(key, len(flat))
    leaves = []
    for (path, spec), k in zip(flat, keys):
        dt = jnp.float32 if spec.kind == "f" else pdt
        name = str(path[-1])
        if spec.init == "zeros":
            leaves.append(jnp.zeros(spec.shape, dt))
        elif spec.init == "ones":
            leaves.append(jnp.ones(spec.shape, dt))
        elif spec.init == "ssm_special":
            h = spec.shape[-1]
            if "A_log" in name:
                base = jnp.log(jnp.linspace(1.0, 16.0, h))
                leaves.append(jnp.broadcast_to(base, spec.shape).astype(dt))
            elif "dt_bias" in name:
                dt0 = jnp.exp(
                    jnp.linspace(jnp.log(1e-3), jnp.log(1e-1), h)
                )
                base = dt0 + jnp.log(-jnp.expm1(-dt0))
                leaves.append(jnp.broadcast_to(base, spec.shape).astype(dt))
            else:  # D_skip
                leaves.append(jnp.ones(spec.shape, dt))
        else:
            std = 0.02
            if spec.init == "out":
                std = 0.02 / math.sqrt(2 * cfg.n_layers)
            leaves.append(
                (jax.random.normal(k, spec.shape, jnp.float32) * std).astype(dt)
            )
    return jax.tree.unflatten(treedef, leaves)


# ----------------------------------------------------------- embed / head
def embed_tokens(cfg, params, tokens):
    h = params["embed"][tokens].astype(cfg.cdtype())
    if cfg.tie_embeddings:  # gemma-style scaled embedding
        h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
    return h


def lm_head(cfg, params, h):
    if cfg.tie_embeddings:
        w = params["embed"].astype(cfg.cdtype()).T
    else:
        w = params["unembed"].astype(cfg.cdtype())
    logits = (h @ w).astype(jnp.float32)
    return shard_hint(logits, "batch", "seq", "vocab")


# -------------------------------------------------------------- the stack
def _remat_policy(cfg):
    if cfg.remat == "none":
        return None
    if cfg.remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


def _apply_stack(cfg, stack_params, h, positions, *, window, moe):
    """lax.scan over stacked layers with remat. Returns (h, aux_sums)."""

    def body(carry, lp):
        x, aux, _ = block_forward(
            cfg, lp, carry, positions, window=window, moe_layer=moe
        )
        return x, aux

    policy = _remat_policy(cfg)
    if cfg.remat != "none":
        body = jax.checkpoint(body, policy=policy, prevent_cse=True)
    h, auxes = jax.lax.scan(body, h, stack_params)
    aux = jax.tree.map(jnp.sum, auxes) if auxes else {}
    return h, aux


def forward_hidden(cfg: ArchConfig, params, batch) -> Tuple[jax.Array, Dict]:
    """Forward up to (and including) the final norm.

    Returns (h (B, S_text, d) with vision positions already stripped, aux).
    """
    tokens = batch["tokens"]
    h = embed_tokens(cfg, params, tokens)
    if cfg.family == "vlm":
        vis = batch["vision_embeds"].astype(h.dtype) @ params[
            "vision_adapter"
        ].astype(h.dtype)
        h = jnp.concatenate([vis, h], axis=1)
    h = shard_hint(h, "batch", "seq", "embed")
    S = h.shape[1]
    positions = jnp.arange(S)
    window = cfg.sliding_window if cfg.family == "hybrid" else 0

    aux = {}
    if cfg.first_k_dense:
        h, aux0 = _apply_stack(
            cfg.replace(n_experts=0), params["front_layers"], h, positions,
            window=window, moe=False,
        )
    h, aux = _apply_stack(
        cfg, params["layers"], h, positions, window=window, moe=True
    )
    from .layers import apply_norm

    h = apply_norm(h, params["final_norm"], cfg.norm)
    if cfg.family == "vlm":  # score text positions only
        h = h[:, batch["vision_embeds"].shape[1]:]
    return h, aux


def forward(cfg: ArchConfig, params, batch) -> Tuple[jax.Array, Dict]:
    """Training/scoring forward: returns (logits (B,S,V) f32, aux)."""
    h, aux = forward_hidden(cfg, params, batch)
    return lm_head(cfg, params, h), aux


def _unembed_weights(cfg, params):
    if cfg.tie_embeddings:
        return params["embed"].astype(cfg.cdtype()).T
    return params["unembed"].astype(cfg.cdtype())


def _chunked_ce(cfg, params, h, targets):
    """Blocked cross-entropy (+z-loss): the (tokens, vocab) logits tensor
    only ever exists at (ce_chunk, vocab) and is rematerialized in the
    backward pass — §Perf iteration K4. Returns (ce_sum, z_sum, count)."""
    B, S, d = h.shape
    w = _unembed_weights(cfg, params)
    T = B * S
    hc = h.reshape(T, d)
    yc = targets.reshape(T)
    Tc = min(cfg.ce_chunk, T)
    n = -(-T // Tc)
    pad = n * Tc - T
    if pad:
        hc = jnp.pad(hc, ((0, pad), (0, 0)))
        yc = jnp.pad(yc, (0, pad), constant_values=-1)
    hc = hc.reshape(n, Tc, d)
    yc = yc.reshape(n, Tc)

    def body(carry, inp):
        ce_sum, z_sum, cnt = carry
        h_i, y_i = inp
        lg = (h_i @ w).astype(jnp.float32)        # (Tc, V) — the only copy
        lg = shard_hint(lg, "batch", "vocab")
        lz = jax.scipy.special.logsumexp(lg, axis=-1)
        y_safe = jnp.maximum(y_i, 0)
        ll = jnp.take_along_axis(lg, y_safe[:, None], axis=-1)[:, 0]
        m = (y_i >= 0).astype(jnp.float32)
        return (
            ce_sum + ((lz - ll) * m).sum(),
            z_sum + ((lz**2) * m).sum(),
            cnt + m.sum(),
        ), None

    body = jax.checkpoint(body, prevent_cse=True)
    (ce_sum, z_sum, cnt), _ = jax.lax.scan(
        body, (jnp.float32(0), jnp.float32(0), jnp.float32(0)), (hc, yc)
    )
    return ce_sum, z_sum, cnt


def loss_fn(cfg: ArchConfig, params, batch):
    """Next-token CE (+ MoE aux, z-loss). Returns (loss, metrics).

    ce_chunk > 0 uses the blocked-CE path (identical math, bounded logits
    residency); ce_chunk == 0 materializes full logits (legacy/oracle)."""
    with jax.named_scope("ce_loss"):
        targets = batch["tokens"][:, 1:]
        if cfg.ce_chunk:
            h, aux = forward_hidden(cfg, params, batch)
            ce_sum, z_sum, cnt = _chunked_ce(cfg, params, h[:, :-1], targets)
            denom = jnp.maximum(cnt, 1.0)
            ce = ce_sum / denom
            zloss = 1e-4 * z_sum / denom
        else:
            logits, aux = forward(cfg, params, batch)
            lg = logits[:, :-1]
            logz = jax.scipy.special.logsumexp(lg, axis=-1)
            ll = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
            mask = (targets >= 0).astype(jnp.float32)
            denom = jnp.maximum(mask.sum(), 1.0)
            ce = ((logz - ll) * mask).sum() / denom
            zloss = 1e-4 * ((logz**2) * mask).sum() / denom
    total = ce + zloss
    metrics = {"ce": ce, "zloss": zloss}
    if "load_balance_loss" in aux:
        lb = 0.01 * aux["load_balance_loss"] / cfg.n_layers
        rz = 1e-3 * aux["router_z_loss"] / cfg.n_layers
        total = total + lb + rz
        metrics.update(
            moe_lb=lb, moe_rz=rz,
            dropped_fraction=aux["dropped_fraction"] / cfg.n_layers,
        )
    metrics["loss"] = total
    return total, metrics


# ------------------------------------------------------------------ cache
def cache_template(cfg: ArchConfig, batch: int, max_seq: int):
    """Pytree of ShapeDtypeStruct for the decode cache (+logical axes).

    Sliding-window attention (hybrid family) gets a *ring buffer* of
    ``sliding_window`` slots instead of a max_seq-sized cache: O(window)
    memory makes long_500k decode feasible (21.5 GB -> 84 MB for hymba).
    """
    hkv, dh = cfg.n_kv_heads, cfg.head_dim_
    cdt = cfg.cdtype()
    n_main = cfg.n_layers - cfg.first_k_dense
    kv_len = max_seq
    if cfg.family == "hybrid" and cfg.sliding_window:
        kv_len = min(max_seq, cfg.sliding_window)

    def attn_cache(n):
        return AttnCache(
            k=jax.ShapeDtypeStruct((n, batch, kv_len, hkv, dh), cdt),
            v=jax.ShapeDtypeStruct((n, batch, kv_len, hkv, dh), cdt),
        )

    def ssm_cache(n):
        H, P, N, d_inner, conv_dim, _ = ssm_lib.ssm_dims(cfg)
        return ssm_lib.SSMState(
            conv=jax.ShapeDtypeStruct(
                (n, batch, cfg.conv_width - 1, conv_dim), cdt
            ),
            ssm=jax.ShapeDtypeStruct((n, batch, H, P, N), jnp.float32),
        )

    if cfg.family == "ssm":
        layers = LayerCache(attn=None, ssm=ssm_cache(n_main))
    elif cfg.family == "hybrid":
        layers = LayerCache(attn=attn_cache(n_main), ssm=ssm_cache(n_main))
    else:
        layers = LayerCache(attn=attn_cache(n_main), ssm=None)
    cache = {"layers": layers}
    if cfg.first_k_dense:
        cache["front_layers"] = LayerCache(
            attn=attn_cache(cfg.first_k_dense), ssm=None
        )
    return cache


CACHE_AXES = {
    "attn_k": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
    "attn_v": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
    "ssm_conv": ("layers", "batch", "conv_width", "ssm_inner"),
    "ssm_ssm": ("layers", "batch", "ssm_heads", "head_dim", "ssm_state"),
}


def init_cache(cfg: ArchConfig, batch: int, max_seq: int):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_template(cfg, batch, max_seq)
    )


# ---------------------------------------------------------------- decode
def decode_step(cfg: ArchConfig, params, cache, tokens, pos):
    """One decode step. tokens (B, 1) int32, pos scalar int32.

    Returns (logits (B, V) f32, new_cache).
    """
    h = embed_tokens(cfg, params, tokens)
    window = cfg.sliding_window if cfg.family == "hybrid" else 0

    def scan_decode(stack_params, stack_cache, h, sub_cfg):
        def body(x, inp):
            lp, lc = inp
            x, new_lc = block_decode(sub_cfg, lp, x, lc, pos, window=window)
            return x, new_lc

        h, new_cache = jax.lax.scan(body, h, (stack_params, stack_cache))
        return h, new_cache

    new_cache = dict(cache)
    if cfg.first_k_dense:
        h, nc = scan_decode(
            params["front_layers"], cache["front_layers"], h,
            cfg.replace(n_experts=0),
        )
        new_cache["front_layers"] = nc
    h, nc = scan_decode(params["layers"], cache["layers"], h, cfg)
    new_cache["layers"] = nc

    from .layers import apply_norm

    h = apply_norm(h, params["final_norm"], cfg.norm)
    logits = lm_head(cfg, params, h)[:, 0]
    return logits, new_cache


# --------------------------------------------------------------- prefill
def prefill(cfg: ArchConfig, params, batch, max_seq: Optional[int] = None):
    """Full-prompt pass that also builds the decode cache.

    Returns (logits at last position (B, V), cache at prompt length).
    Cache buffers sized to the prompt; serve/engine pads to max_seq.
    """
    tokens = batch["tokens"]
    h = embed_tokens(cfg, params, tokens)
    if cfg.family == "vlm":
        vis = batch["vision_embeds"].astype(h.dtype) @ params[
            "vision_adapter"
        ].astype(h.dtype)
        h = jnp.concatenate([vis, h], axis=1)
    S = h.shape[1]
    positions = jnp.arange(S)
    window = cfg.sliding_window if cfg.family == "hybrid" else 0

    def scan_prefill(stack_params, h, sub_cfg, moe):
        def body(x, lp):
            x, aux, lc = block_forward(
                sub_cfg, lp, x, positions, window=window,
                build_cache=True, moe_layer=moe,
            )
            return x, lc

        return jax.lax.scan(body, h, stack_params)

    cache = {}
    if cfg.first_k_dense:
        h, lc = scan_prefill(
            params["front_layers"], h, cfg.replace(n_experts=0), False
        )
        cache["front_layers"] = lc
    h, lc = scan_prefill(params["layers"], h, cfg, True)
    cache["layers"] = lc

    from .layers import apply_norm

    h = apply_norm(h, params["final_norm"], cfg.norm)
    logits = lm_head(cfg, params, h[:, -1:, :])[:, 0]
    return logits, cache
