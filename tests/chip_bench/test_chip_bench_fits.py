"""Each scan cell's program fits one v5e chip at the cell's own size.

Nothing runs: the program's ``scan_topk``, the call a scan cell's window
drives, is compiled for a v5e chip described by topology (no chip is
attached) at the cell's batch, fetch size and corpus size. The Pallas
scan reads the codes in a tile-padded copy 64 times their size, so a
corpus that fits in HBM can still be refused by the chip's compiler:
2^25 codes of 64 bits ask for 16 GiB. Such a cell would fail every run.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_bench_util import ROOT, harness

_BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SCAN_CELLS = [
    w["name"] for w in _BENCH["workloads"]
    if harness.load_cell(ROOT, w["name"]).config["backend"] == "linear_scan"
]


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


@pytest.mark.parametrize("workload", SCAN_CELLS)
def test_scan_program_fits_one_chip(one_chip, workload, monkeypatch):
    from repro.kernels import ops

    # the kernel's interpret mode follows the default backend, the CPU
    # here: steer it to the chip's path for this compile
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cell = harness.load_cell(ROOT, workload)
    W = -(-cell.p // 32)
    t = cell.traffic
    q = jax.ShapeDtypeStruct((ops.pad_bucket(t.batch), W), jnp.uint32,
                             sharding=one_chip)
    db = jax.ShapeDtypeStruct((cell.n, W), jnp.uint32, sharding=one_chip)
    # the engine fetches k plus a slack of at least 16, in a power of two
    k_fetch = ops.pad_bucket(t.k + 16)
    compiled = ops.scan_topk.lower(q, db, k_fetch, use_pallas=True).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the compile itself refuses a program over the chip's HBM; the
    # reading says how close the cell comes
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15.75 * 2**30
