"""Pallas TPU kernel: fused flash attention (forward).

§Perf iteration L2: the pure-JAX blocked attention materializes its
(q_blk, Hq, kv_blk) score tensors to HBM — at llava-next prefill_32k that
scope is 55% of all modeled HBM traffic (1.85e14 B/device). This kernel
keeps scores, softmax state and the output accumulator in VMEM; HBM sees
only q/k/v reads and the output write.

Layout & tiling
  grid = (B, Hq, nq, nk)   — nk is minor-most: TPU grids execute
  sequentially, so VMEM scratch (m, l, acc) persists and accumulates
  across the kv sweep of one (b, h, iq) tile, flash-v2 style.
  q tile (q_blk, D) and kv tiles (kv_blk, D) in VMEM; D = head_dim.
  MXU alignment: q_blk, kv_blk multiples of 128 recommended; D is the
  contraction dim (128 for every assigned arch except gemma's 256 and
  whisper/hymba's 64 — all MXU-friendly).
  GQA: kv BlockSpecs index head h // (Hq // Hkv) — no KV duplication.

VMEM budget at defaults (q_blk=512, kv_blk=1024, D=128, f32 scratch):
  q 256 KiB + k,v 2x512 KiB + acc 256 KiB + m,l 2x2 KiB + s 2 MiB << 16 MiB.

Masking: causal and sliding-window masks are applied from absolute
positions; fully-masked kv tiles are skipped with @pl.when (the dominant
saving for causal prefill: ~2x fewer tiles).

Backward is served by the pure-JAX oracle path (layers.blocked_attention)
— the forward kernel is the serving-path / prefill hot spot; a fused
backward is recorded as future work in EXPERIMENTS.md.

Validated in interpret mode against models.layers._blocked_attention_impl
(tests/test_kernels_attention.py); lowers natively on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_Q_BLK = 512
DEFAULT_KV_BLK = 1024


def _flash_kernel(
    q_ref, k_ref, v_ref, vl_ref, o_ref, m_scr, l_scr, acc_scr,
    *, q_blk: int, kv_blk: int, causal: bool, window: int, sk: int,
    dynamic_len: bool,
):
    ik = pl.program_id(3)
    iq = pl.program_id(2)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = iq * q_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, 1), 0)
    k_pos = ik * kv_blk + jax.lax.broadcasted_iota(jnp.int32, (1, kv_blk), 1)

    # tile-level skip: tiles entirely above the causal diagonal, outside
    # the sliding window, or past the valid keys never touch the MXU.
    # With dynamic_len the static bound sk stays a conservative skip.
    first_q = iq * q_blk
    last_q = first_q + q_blk - 1
    first_k = ik * kv_blk
    last_k = first_k + kv_blk - 1
    live = first_k < sk
    if causal:
        live &= first_k <= last_q
    if window > 0 and not dynamic_len:
        live &= last_k >= first_q - window + 1

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)          # (q_blk, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (kv_blk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        d = q.shape[-1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (d ** -0.5)                              # (q_blk, kv_blk)
        ok = k_pos < sk
        if dynamic_len:
            # decode: only slots [0, vl) hold keys; with a window, only
            # the last ``window`` of them participate
            vl = vl_ref[0]
            ok = ok & (k_pos < vl)
            if window > 0:
                ok = ok & (k_pos >= vl - window)
        if causal:
            ok = ok & (q_pos >= k_pos)
        if window > 0 and not dynamic_len:
            ok = ok & (q_pos - k_pos < window)
        ok = jnp.broadcast_to(ok, (q_blk, kv_blk))
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]                          # (q_blk, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_blk", "kv_blk", "interpret"),
)
def flash_attention(
    q: jax.Array,           # (B, Sq, Hq, D)
    k: jax.Array,           # (B, Sk, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_blk: int = DEFAULT_Q_BLK,
    kv_blk: int = DEFAULT_KV_BLK,
    interpret: bool,
    valid_len: jax.Array | None = None,
) -> jax.Array:
    """Fused flash attention forward. Returns (B, Sq, Hq, D) in q.dtype.

    ``valid_len`` (scalar int32) enables flash-DECODE semantics: only key
    slots [0, valid_len) participate (with ``window``: only the trailing
    ``window`` of them) — the single-pass fused read of a partially-filled
    KV cache. Used by models.layers.decode_attention on TPU.
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    q_blk = min(q_blk, Sq)
    kv_blk = min(kv_blk, Sk)
    nq = -(-Sq // q_blk)
    nk = -(-Sk // kv_blk)
    Sq_p, Sk_p = nq * q_blk, nk * kv_blk
    # head-major layout so a (b, h) tile is a contiguous (S, D) slab
    qt = jnp.moveaxis(q, 2, 1)                       # (B, Hq, Sq, D)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if Sq_p != Sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, Sq_p - Sq), (0, 0)))
    if Sk_p != Sk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, Sk_p - Sk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, Sk_p - Sk), (0, 0)))

    dynamic_len = valid_len is not None
    vl = jnp.full((1,), Sk, jnp.int32) if valid_len is None else (
        jnp.asarray(valid_len, jnp.int32).reshape(1)
    )
    kernel = functools.partial(
        _flash_kernel,
        q_blk=q_blk, kv_blk=kv_blk, causal=causal, window=window, sk=Sk,
        dynamic_len=dynamic_len,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, q_blk, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec(
                (1, 1, kv_blk, D), lambda b, h, i, j: (b, h // G, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, kv_blk, D), lambda b, h, i, j: (b, h // G, j, 0)
            ),
            pl.BlockSpec((1,), lambda b, h, i, j: (0,)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, q_blk, D), lambda b, h, i, j: (b, h, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk, 1), jnp.float32),     # m: running max
            pltpu.VMEM((q_blk, 1), jnp.float32),     # l: running sum
            pltpu.VMEM((q_blk, D), jnp.float32),     # acc: output accum
        ],
        interpret=interpret,
    )(qt, kt, vt, vl)
    return jnp.moveaxis(out[:, :, :Sq], 1, 2)        # (B, Sq, Hq, D)
