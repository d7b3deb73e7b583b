"""HLO parser validation: on scan-free modules, XLA's own cost_analysis is
correct — the structural parser must agree on FLOPs; with scans, the parser
must scale by trip count while cost_analysis does not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import analyze, model_flops, parse_hlo_costs
from repro.roofline.hlo_parse import _parse_op_line, _shape_bytes


# ------------------------------------------------------------- line parser
def test_parse_op_line_simple():
    op = _parse_op_line(
        "  %dot.1 = f32[16,1024,2048]{2,1,0} dot(%a, %b), "
        "lhs_batch_dims={0}, lhs_contracting_dims={2}, rhs_batch_dims={0}, "
        "rhs_contracting_dims={1}"
    )
    assert op.kind == "dot"
    assert op.out_shapes == [("f32", (16, 1024, 2048))]
    assert op.operand_names == ["a", "b"]


def test_parse_op_line_tuple_output():
    op = _parse_op_line(
        "  ROOT %t = (s32[], f32[4,8]{1,0}) tuple(%x, %y)"
    )
    assert op.kind == "tuple"
    assert op.out_shapes == [("s32", ()), ("f32", (4, 8))]


def test_shape_bytes():
    assert _shape_bytes("bf16", (10, 10)) == 200
    assert _shape_bytes("f32", ()) == 4
    assert _shape_bytes("pred", (8,)) == 8


# ----------------------------------------------- agreement with XLA (no scan)
def test_parser_matches_cost_analysis_scanfree():
    def f(a, b, c):
        return jnp.tanh(a @ b) @ c

    a = jnp.zeros((128, 256), jnp.float32)
    b = jnp.zeros((256, 512), jnp.float32)
    c = jnp.zeros((512, 64), jnp.float32)
    compiled = jax.jit(f).lower(a, b, c).compile()
    ca = compiled.cost_analysis()
    costs = parse_hlo_costs(compiled.as_text())
    want_flops = 2 * 128 * 256 * 512 + 2 * 128 * 512 * 64
    assert costs.flops == pytest.approx(want_flops, rel=0.01)
    assert ca["flops"] == pytest.approx(want_flops, rel=0.05)


def test_parser_scales_scan_bodies_by_trip_count():
    def f(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None

        h, _ = jax.lax.scan(body, x, None, length=17)
        return h

    x = jnp.zeros((64, 64), jnp.float32)
    w = jnp.zeros((64, 64), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    costs = parse_hlo_costs(compiled.as_text())
    want = 17 * 2 * 64 * 64 * 64
    assert costs.flops == pytest.approx(want, rel=0.01)
    assert 17 in costs.trip_counts
    # XLA's own counter misses the scaling (this is WHY the parser exists)
    ca = compiled.cost_analysis()
    assert ca["flops"] < want / 2


def test_parser_nested_scans():
    def f(x, w):
        def inner(h, _):
            return jnp.tanh(h @ w), None

        def outer(h, _):
            h, _ = jax.lax.scan(inner, h, None, length=3)
            return h, None

        h, _ = jax.lax.scan(outer, x, None, length=5)
        return h

    x = jnp.zeros((32, 32), jnp.float32)
    w = jnp.zeros((32, 32), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    costs = parse_hlo_costs(compiled.as_text())
    want = 5 * 3 * 2 * 32 * 32 * 32
    assert costs.flops == pytest.approx(want, rel=0.02)


def test_parser_counts_collectives():
    import subprocess, sys, textwrap

    # collectives need >1 device: subprocess with fake devices
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        import sys
        sys.path.insert(0, "src")
        from repro.roofline import parse_hlo_costs

        mesh = jax.make_mesh((8,), ("d",))
        def f(x):
            return jax.lax.psum(x, "d")
        fn = jax.shard_map(f, mesh=mesh, in_specs=P("d"), out_specs=P())
        x = jax.ShapeDtypeStruct((800, 4), jnp.float32)
        compiled = jax.jit(fn).lower(x).compile()
        costs = parse_hlo_costs(compiled.as_text())
        total = costs.total_collective_bytes
        # per-device operand: (100, 4) f32 = 1600 B
        assert total >= 1600, costs.collective_bytes
        assert "all-reduce" in costs.collective_bytes, costs.collective_bytes
        print("OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd="/root/repo",
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout


# --------------------------------------------------------------- model flops
def test_model_flops_conventions():
    from repro.configs import get_config
    from repro.models.common import SHAPES

    cfg = get_config("llama3_8b")
    N = cfg.param_count()
    tr = model_flops(cfg, SHAPES["train_4k"])
    assert tr == pytest.approx(6.0 * N * 256 * 4096)
    pf = model_flops(cfg, SHAPES["prefill_32k"])
    assert pf == pytest.approx(2.0 * N * 32 * 32768)
    dec = model_flops(cfg, SHAPES["decode_32k"])
    assert dec > 2.0 * N * 128  # includes the KV-cache attention term

    moe = get_config("kimi_k2_1t_a32b")
    assert moe.active_param_count() < 0.1 * moe.param_count()
    assert model_flops(moe, SHAPES["train_4k"]) == pytest.approx(
        6.0 * moe.active_param_count() * 256 * 4096
    )


def test_analyze_dominant_term():
    from repro.configs import get_config
    from repro.models.common import SHAPES
    from repro.roofline.hlo_parse import HloCosts

    cfg = get_config("llama3_8b")
    costs = HloCosts(flops=1e12, hbm_bytes=1e13, collective_bytes={"all-reduce": 1e9})
    rep = analyze(
        cfg, SHAPES["train_4k"], "single", 256, "", 1e9, costs=costs
    )
    assert rep.dominant == "memory"
    assert rep.memory_s == pytest.approx(1e13 / 819e9)
    assert rep.compute_s == pytest.approx(1e12 / 197e12)
    assert rep.collective_s == pytest.approx(1e9 / 50e9)
    assert rep.step_s == rep.memory_s
    assert rep.fits
