"""Each AMIH cell's walk and scan fallback fit one v5e chip at the cell's
own size.

Nothing runs: the fused walk (``device_probe_walk_batched``) and its
fallback (``scan_topk``, the scan engine's program) are compiled for a
v5e chip described by topology (no chip is attached) at the cell's
corpus size, batch and K, with the device CSR of the cell's m. The walk
carries (B, kf) (position, id) pairs per query, so nothing of size
(B, n) appears in its arguments or its temporaries. The schedule stack
is taken at its largest for a batch: one row per query, each row a full
stream.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_bench_util import ROOT, harness

_BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
AMIH_CELLS = [
    w["name"] for w in _BENCH["workloads"]
    if harness.load_cell(ROOT, w["name"]).config["backend"] == "amih"
]
HBM = 15.75 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these compiles out of it
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # pragma: no cover - depends on the install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


def _walk_args(cell, sharding):
    """Shapes of the walk's arguments at the cell's size."""
    from repro.core.amih import default_num_tables
    from repro.core.packing import substring_spans
    from repro.core.probe_device import (DEFAULT_STREAM_CAP, DEFAULT_TILE,
                                         KMAX)
    from repro.kernels import ops

    p, n = cell.p, cell.n
    m = cell.config["engine"].get("m") or default_num_tables(p, n)
    wmax = max(hi - lo for lo, hi in substring_spans(p, m))
    W = -(-p // 32)
    Bp = ops.pad_bucket(cell.traffic.batch, minimum=1)
    n_pad = ops.pad_bucket(n)
    G = Bp
    Pt = ops.pad_bucket(G * (DEFAULT_STREAM_CAP + DEFAULT_TILE))

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return [
        s((Bp, W), jnp.uint32), s((Bp, m)), s((Bp, m)),
        s((Bp, m, wmax + 1)), s((Bp, m, wmax + 1)), s((Bp,)), s((Bp,)),
        s(()), s(()), s((G,)), s((G,)), s((Pt,)), s((Pt,)),
        s((Pt, KMAX)), s((Pt, KMAX)), s((Pt,)), s((Pt,)),
        s((G, m * (wmax + 1) ** 2)), s((m,)), s((m, W), jnp.uint32),
        s((m, (1 << wmax) + 1)), s((m, n_pad)), s((n_pad, W), jnp.uint32),
        s((G, (p + 1) ** 2)),
    ], n_pad, Bp


@pytest.mark.parametrize("workload", AMIH_CELLS)
def test_walk_and_fallback_fit_one_chip(one_chip, workload, monkeypatch):
    from repro.core.engine import topk_slack
    from repro.core.probe_device import (DEFAULT_PROBE_CAP, DEFAULT_TILE,
                                         KMAX)
    from repro.kernels import device_probe, ops

    # the kernels' interpret mode follows the default backend, the CPU
    # here: steer them to the chip's path for these compiles
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cell = harness.load_cell(ROOT, workload)
    args, n_pad, Bp = _walk_args(cell, one_chip)
    walk = jax.jit(device_probe.device_probe_walk_batched,
                   static_argnames=("p", "tile", "cap", "kf", "kmax",
                                    "use_pallas", "interpret"))
    compiled = walk.lower(
        *args, p=cell.p, tile=DEFAULT_TILE, cap=DEFAULT_PROBE_CAP,
        kf=ops.carry_width(cell.traffic.k), kmax=KMAX, use_pallas=True,
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM
    # no (B, n) array among the walk's temporaries: they stay below
    # one int32 per query and code
    assert mem.temp_size_in_bytes < 4 * Bp * n_pad
    # every query of the batch bailed: the fallback at its widest
    W = -(-cell.p // 32)
    q = jax.ShapeDtypeStruct((ops.pad_bucket(Bp), W), jnp.uint32,
                             sharding=one_chip)
    db = jax.ShapeDtypeStruct((n_pad, W), jnp.uint32, sharding=one_chip)
    k_fetch = ops.pad_bucket(cell.traffic.k + topk_slack(cell.p))
    scan = ops.scan_topk.lower(q, db, k_fetch, use_pallas=True).compile()
    assert "tpu_custom_call" in scan.as_text()
    mem = scan.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM
