"""The main-path kernels compile for a TPU v5e chip at real widths.

Nothing runs: each case lowers and compiles for a v5e chip described by
topology (JAX's TPU compiler is installed; no chip is attached) and
asserts that the Pallas kernel survived as a ``tpu_custom_call``. This is
the chip compiler's own check of what interpret mode cannot see: block
shapes that break the (8, 128) tiling rule, or VMEM overuse.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import pack_bits
from repro.core.engine import make_engine
from repro.data import synthetic_binary_codes, synthetic_queries
from repro.kernels import device_probe, ops
from repro.kernels.verify_tuples import verify_tuples_grouped


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep this module's compiles out
    # of the cache, and give later modules in this process theirs back
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # pragma: no cover - depends on the install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


def _spec(x, sharding):
    x = np.asarray(x)
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("W", [2, 4])
def test_verify_tuples_grouped_compiles(one_chip, W):
    B, C = 64, 2048
    args = (
        jax.ShapeDtypeStruct((B, W), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((B, C, W), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip),
    )
    _assert_kernel(
        verify_tuples_grouped.lower(*args, p=32 * W, interpret=False)
        .compile()
    )


@pytest.mark.parametrize("W", [2, 4])
def test_scan_topk_pallas_compiles(one_chip, W, monkeypatch):
    # scan_scores picks interpret mode from the default backend, which
    # is the CPU here: steer it to the chip's path for this compile
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    B, N = 64, 1 << 20
    q = jax.ShapeDtypeStruct((B, W), jnp.uint32, sharding=one_chip)
    db = jax.ShapeDtypeStruct((N, W), jnp.uint32, sharding=one_chip)
    _assert_kernel(ops.scan_topk.lower(q, db, 128, use_pallas=True).compile())


@pytest.fixture(scope="module")
def walk_calls():
    """The exact arguments of the fused walk and of its scan fallback,
    caught from a device-probe search over a small index on the CPU."""
    calls = {}
    real_fn, real_scan = ops._device_fn, ops.scan_topk

    def catching(device, name, make):
        fn = real_fn(device, name, make)

        def call(*args, **kw):
            calls.setdefault(name, (args, kw))
            return fn(*args, **kw)

        return call

    def scan(*args, **kw):
        calls.setdefault("scan_fallback", (args, kw))
        return real_scan(*args, **kw)

    p, n, B = 64, 1 << 12, 8
    db_bits = synthetic_binary_codes(n, p, seed=0)
    q = pack_bits(synthetic_queries(db_bits, B, seed=1))
    eng = make_engine("amih", pack_bits(db_bits), p, m=3,
                      probe_backend="device", query_cache_size=0)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_device_fn", catching)
        mp.setattr(ops, "scan_topk", scan)
        eng.knn_batch(q, 100)      # K=100 bails part of the batch to the scan
    assert set(calls) == {"walk_batched", "scan_fallback"}, sorted(calls)
    return calls


def test_device_probe_pallas_compiles(one_chip, walk_calls):
    args, kw = walk_calls["walk_batched"]
    kw = dict(kw, use_pallas=True, interpret=False)
    specs = [_spec(a, one_chip) for a in args]
    fn = jax.jit(device_probe.device_probe_walk_batched,
                 static_argnames=("p", "tile", "cap", "kf", "kmax",
                                  "use_pallas", "interpret"))
    _assert_kernel(fn.lower(*specs, **kw).compile())


def test_scan_fallback_pallas_compiles(one_chip, walk_calls, monkeypatch):
    # scan_scores picks interpret mode from the default backend, which
    # is the CPU here: steer it to the chip's path for this compile
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    (q, db, k), kw = walk_calls["scan_fallback"]
    kw = dict(kw, use_pallas=True)
    if "n_valid" in kw:
        kw["n_valid"] = _spec(kw["n_valid"], one_chip)
    _assert_kernel(
        ops.scan_topk.lower(_spec(q, one_chip), _spec(db, one_chip), k,
                            **kw).compile()
    )


def test_scan_topk_full_size_temporaries_under_1gib(one_chip, monkeypatch):
    # the benchmark cells' scan at 2^24 codes: the codes' word planes and
    # one chunk's scores, not the tile-padded code copy (8.59 GB) the
    # kernel's former (1024, W) code blocks made XLA write
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    B, N, W = 64, 1 << 24, 2
    q = jax.ShapeDtypeStruct((B, W), jnp.uint32, sharding=one_chip)
    db = jax.ShapeDtypeStruct((N, W), jnp.uint32, sharding=one_chip)
    compiled = ops.scan_topk.lower(q, db, 128, use_pallas=True).compile()
    _assert_kernel(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
