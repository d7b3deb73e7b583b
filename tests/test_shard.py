"""The sharded search subsystem (repro.shard): ShardPlan layout contract,
host- and mesh-mode engine exactness vs ``linear_scan_knn`` (uneven N,
K > per-shard rows, B in {1, 8, 64}), cross-shard early termination,
per-shard EngineStats, and the Optional-annotation regression of the old
``core.distributed`` module (multi-device cases run in subprocesses with
8 fake CPU devices, the tests/test_distributed.py pattern)."""

import json
import subprocess
import sys
import textwrap
import typing

import numpy as np
import pytest

from repro.core import linear_scan_knn, make_engine, pack_bits
from repro.core.linear_scan import sims_against_db
from repro.data import synthetic_binary_codes, synthetic_queries
from repro.shard import ShardPlan


def _run(code: str, devices: int = 8) -> str:
    prelude = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        import numpy as np
    """)
    out = subprocess.run(
        [sys.executable, "-c", prelude + textwrap.dedent(code)],
        capture_output=True, text=True, cwd="/root/repo", timeout=560,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def _check_exact(ids, sims, qs, db, k_eff):
    """Sharded results == per-query linear scan, up to in-tuple ties."""
    B = qs.shape[0]
    assert ids.shape == (B, k_eff) and sims.shape == (B, k_eff)
    for i in range(B):
        _, sims_l = linear_scan_knn(qs[i], db, k_eff)
        np.testing.assert_array_equal(sims[i], sims_l)
        all_sims = sims_against_db(qs[i], db)
        np.testing.assert_array_equal(all_sims[ids[i]], sims[i])
        assert len(set(ids[i].tolist())) == k_eff  # shards are disjoint


# --------------------------------------------------------------- ShardPlan
def test_plan_balanced_remainder():
    plan = ShardPlan.balanced(10, 8)
    assert plan.counts == (2, 2, 1, 1, 1, 1, 1, 1)   # differ by <= 1
    assert plan.starts == (0, 2, 4, 5, 6, 7, 8, 9)
    assert plan.rows_padded == 2
    assert plan.num_shards == 8
    # slices tile [0, n) exactly
    rows = np.concatenate(
        [np.arange(plan.n)[plan.shard_slice(s)] for s in range(8)]
    )
    np.testing.assert_array_equal(rows, np.arange(10))
    assert plan.global_ids(3, np.arange(plan.counts[3])).tolist() == [5]


def test_plan_summary_roundtrip_is_json():
    plan = ShardPlan.balanced(1001, 7, axis_names=("pod", "data"))
    wire = json.dumps(plan.summary())          # serializable by contract
    assert ShardPlan.from_summary(json.loads(wire)) == plan
    s = plan.summary()
    assert s["num_shards"] == 7 and s["rows_padded"] == 143


def test_plan_padded_layout_masks_remainder():
    db = 1 + np.arange(10 * 3, dtype=np.uint32).reshape(10, 3)
    plan = ShardPlan.balanced(10, 4)           # counts (3, 3, 2, 2)
    padded = plan.padded_layout(db)
    assert padded.shape == (12, 3)
    for s in range(4):
        lo = s * plan.rows_padded
        np.testing.assert_array_equal(
            padded[lo : lo + plan.counts[s]], db[plan.shard_slice(s)]
        )
    # the two remainder slots (shards 2 and 3) are zero codes
    assert not padded[2 * 3 + 2].any() and not padded[3 * 3 + 2].any()


def test_plan_validation():
    with pytest.raises(ValueError, match="num_shards"):
        ShardPlan.balanced(10, 0)
    with pytest.raises(ValueError, match="counts sum"):
        ShardPlan(n=5, starts=(0, 2), counts=(2, 2))
    with pytest.raises(ValueError, match="base"):
        ShardPlan(n=4, starts=(3, 5), counts=(2, 2))   # base defaults to 0


def test_plan_host_partition_global_ids_and_local_slices():
    """host_partition: contiguous shard runs differing by <= 1 shard,
    GLOBAL starts with per-host base, local shard_slice, and sub-plan
    summaries that round-trip base over the wire."""
    plan = ShardPlan.balanced(103, 8, axis_names=("pod",))
    subs = plan.host_partition(3)
    assert [s.num_shards for s in subs] == [3, 3, 2]   # differ by <= 1
    assert sum(s.n for s in subs) == plan.n
    # contiguous coverage: each host's base is where the previous ended
    assert subs[0].base == 0
    for prev, cur in zip(subs, subs[1:]):
        assert cur.base == prev.base + prev.n
    covered = []
    for sub in subs:
        assert sub.axis_names == plan.axis_names
        assert sub.devices == ()                       # placement dropped
        for s in range(sub.num_shards):
            # starts are GLOBAL: global_ids needs no per-host fixup
            lo = sub.starts[s]
            np.testing.assert_array_equal(
                sub.global_ids(s, np.arange(sub.counts[s])),
                np.arange(lo, lo + sub.counts[s]),
            )
            # shard_slice is LOCAL to the host's row slab
            sl = sub.shard_slice(s)
            assert sl.start == lo - sub.base
            covered.extend(range(lo, lo + sub.counts[s]))
    assert covered == list(range(plan.n))              # exact tiling
    # wire round-trip keeps base (the "base" key appears iff nonzero)
    for sub in subs:
        wire = json.loads(json.dumps(sub.summary()))
        assert ("base" in wire) == (sub.base != 0)
        assert ShardPlan.from_summary(wire) == sub
    # degenerate and invalid host counts
    assert plan.host_partition(1) == [plan]
    assert plan.host_partition(8)[7].num_shards == 1
    with pytest.raises(ValueError, match="num_hosts"):
        plan.host_partition(0)
    with pytest.raises(ValueError, match="at least one shard"):
        plan.host_partition(9)


# ------------------------------------------- host-mode engines (1 device)
@pytest.mark.parametrize("backend", ["sharded_scan", "sharded_amih"])
@pytest.mark.parametrize("B", [1, 8, 64])
def test_sharded_exact_uneven_n(backend, B):
    p, n, k, S = 64, 997, 10, 8                # N not divisible by shards
    db_bits = synthetic_binary_codes(n, p, seed=0)
    db = pack_bits(db_bits)
    qs = pack_bits(synthetic_queries(db_bits, B, seed=1))
    eng = make_engine(backend, db, p, num_shards=S)
    ids, sims, stats = eng.knn_batch(qs, k)
    _check_exact(ids, sims, qs, db, k)
    assert stats.backend == backend and stats.queries == B
    assert stats.shards == S and len(stats.per_shard) == S
    assert sum(d["rows"] for d in stats.per_shard) == n


@pytest.mark.parametrize("backend", ["sharded_scan", "sharded_amih"])
def test_sharded_k_exceeds_shard_rows(backend):
    # K > every shard's row count: each shard must surface its whole slice
    p, n, k, S = 64, 50, 40, 8                 # ~6 rows/shard, k=40
    db_bits = synthetic_binary_codes(n, p, seed=2)
    db = pack_bits(db_bits)
    qs = pack_bits(synthetic_queries(db_bits, 4, seed=3))
    eng = make_engine(backend, db, p, num_shards=S)
    ids, sims, _ = eng.knn_batch(qs, k)
    _check_exact(ids, sims, qs, db, k)
    # k > n clamps too
    ids, sims, _ = eng.knn_batch(qs, 99)
    _check_exact(ids, sims, qs, db, n)


def test_sharded_more_shards_than_rows():
    p, n = 64, 5
    db_bits = synthetic_binary_codes(n, p, seed=4)
    db = pack_bits(db_bits)
    qs = pack_bits(synthetic_queries(db_bits, 2, seed=5))
    for backend in ("sharded_scan", "sharded_amih"):
        ids, sims, _ = make_engine(backend, db, p, num_shards=8).knn_batch(
            qs, 3
        )
        _check_exact(ids, sims, qs, db, 3)


def test_sharded_amih_early_termination_bounds_global_kth():
    """Later shards stop probing once the pooled k-th cosine bounds them:
    their tuples_processed collapses vs an unbounded per-shard run, and
    per_shard counts the early-stopped queries."""
    p, n, B, k, S = 64, 2000, 8, 5, 8
    db_bits = synthetic_binary_codes(n, p, seed=6)
    db = pack_bits(db_bits)
    qs = pack_bits(synthetic_queries(db_bits, B, seed=7))
    eng = make_engine("sharded_amih", db, p, num_shards=S)
    ids, sims, stats = eng.knn_batch(qs, k)
    _check_exact(ids, sims, qs, db, k)
    assert any(d["early_stopped"] > 0 for d in stats.per_shard[1:])
    # an unbounded run of the last shard does strictly more tuple work
    _, last_index = eng.indexes[-1]
    bounded_tuples = stats.per_shard[-1]["tuples_processed"]
    from repro.core import AMIHStats

    free_stats = [AMIHStats() for _ in range(B)]
    last_index.knn_batch(qs, k, stats=free_stats)
    unbounded_tuples = sum(s.tuples_processed for s in free_stats)
    assert bounded_tuples < unbounded_tuples


def test_sharded_amih_ids_are_global():
    p, n, S = 64, 300, 4
    db_bits = synthetic_binary_codes(n, p, seed=8)
    db = pack_bits(db_bits)
    eng = make_engine("sharded_amih", db, p, num_shards=S)
    for s, index in eng.indexes:
        assert index.id_offset == eng.plan.starts[s]
    # a query equal to a code in the LAST shard must return its global id
    target = n - 3
    q = db[target : target + 1]
    ids, sims, _ = eng.knn_batch(q, 1)
    assert ids[0, 0] == target
    assert sims[0, 0] == sims_against_db(q[0], db)[target]


def test_sharded_scan_per_shard_candidate_counters():
    p, n, S = 64, 640, 4
    db_bits = synthetic_binary_codes(n, p, seed=9)
    db = pack_bits(db_bits)
    qs = pack_bits(synthetic_queries(db_bits, 8, seed=10))
    eng = make_engine("sharded_scan", db, p, num_shards=S)
    _, _, stats = eng.knn_batch(qs, 7)
    assert [d["shard"] for d in stats.per_shard] == list(range(S))
    assert all(d["launches"] == 1 for d in stats.per_shard)
    assert sum(d["candidates"] for d in stats.per_shard) > 0
    assert eng.shard_launches == S
    eng.knn_batch(qs, 7)
    assert eng.shard_launches == 2 * S


def test_plan_knob_passes_through_make_engine():
    import jax

    p, n = 64, 100
    db_bits = synthetic_binary_codes(n, p, seed=11)
    db = pack_bits(db_bits)
    plan = ShardPlan.balanced(n, 3)
    eng = make_engine("sharded_scan", db, p, plan=plan)
    # layout passes through untouched; an UNPLACED caller plan (e.g. a
    # from_summary restore) is placed on the local devices like every
    # other path, so it never silently reverts to the device-0 funnel
    assert eng.plan == plan and eng.plan.counts == plan.counts
    assert len(eng.plan.devices) == plan.num_shards
    assert eng.plan.devices[0] == jax.devices()[0]
    # an already-placed caller plan is trusted as-is (identity kept)
    placed = plan.place(jax.devices())
    assert make_engine("sharded_scan", db, p, plan=placed).plan is placed
    with pytest.raises(ValueError, match="plan covers"):
        make_engine("sharded_scan", db, p, plan=ShardPlan.balanced(n + 1, 3))


# --------------------------------------------------------- device placement
def test_plan_place_round_robin_and_validation():
    plan = ShardPlan.balanced(10, 4)
    assert plan.devices == () and plan.device_for(0) is None
    placed = plan.place(["d0", "d1", "d2"])      # fewer devices than shards
    assert placed.devices == ("d0", "d1", "d2", "d0")
    assert placed.device_for(3) == "d0"
    assert placed.counts == plan.counts          # layout untouched
    assert placed == plan                        # devices excluded from eq
    assert placed.place(None).devices == ()      # clearing
    wide = plan.place(["a", "b", "c", "d", "e"])  # extra devices idle
    assert wide.devices == ("a", "b", "c", "d")
    # summaries carry the placement as strings, and round-trip unplaced
    # — an EXPLICIT drop now: warning by default, error under strict=
    s = placed.summary()
    assert s["devices"] == ["d0", "d1", "d2", "d0"]
    with pytest.warns(UserWarning, match="drops device placements"):
        restored = ShardPlan.from_summary(json.loads(json.dumps(s)))
    assert restored.devices == () and restored == plan
    with pytest.raises(ValueError, match="drops device placements"):
        ShardPlan.from_summary(s, strict=True)
    with pytest.raises(ValueError, match="devices maps"):
        ShardPlan(n=10, starts=placed.starts, counts=placed.counts,
                  devices=("d0",))


def test_host_engines_record_placement_single_device():
    """On a 1-device host every shard lands on that device — recorded in
    the plan and in each per_shard stats dict."""
    import jax

    p, n, S = 64, 400, 4
    db_bits = synthetic_binary_codes(n, p, seed=30)
    db = pack_bits(db_bits)
    qs = pack_bits(synthetic_queries(db_bits, 4, seed=31))
    dev = str(jax.devices()[0])
    for backend in ("sharded_scan", "sharded_amih"):
        eng = make_engine(backend, db, p, num_shards=S)
        assert [str(d) for d in eng.plan.devices] == [dev] * S
        _, _, stats = eng.knn_batch(qs, 5)
        assert [d["device"] for d in stats.per_shard] == [dev] * S


def test_sharded_amih_verify_runs_on_assigned_devices_mesh():
    """The tentpole contract on 8 fake devices: each shard's db_dev is
    committed to its plan device, grouped-verify launches split across
    the devices (per-device launch counters move, the default-device
    counter does not), and results stay exact."""
    _run("""
        from repro.core import make_engine, linear_scan_knn, pack_bits
        from repro.data import synthetic_binary_codes, synthetic_queries
        from repro.kernels import ops
        from repro.launch.mesh import make_mesh
        from repro.obs.metrics import REGISTRY as _REG

        p, n, B, k = 64, 1499, 8, 7
        db_bits = synthetic_binary_codes(n, p, seed=0)
        db = pack_bits(db_bits)
        qs = pack_bits(synthetic_queries(db_bits, B, seed=1))
        mesh = make_mesh((4, 2), ("data", "model"))
        eng = make_engine("sharded_amih", db, p, mesh=mesh,
                          verify_backend="pallas")
        assert eng.plan.num_shards == 8
        assert len({str(d) for d in eng.plan.devices}) == 8
        for s, ix in eng.indexes:
            (got,) = ix.db_dev.devices()
            assert got == eng.plan.device_for(s), (s, got)
        before = _REG.values("launches.device.")
        ids, sims, st = eng.knn_batch(qs, k)
        for i in range(B):
            _, sims_l = linear_scan_knn(qs[i], db, k)
            np.testing.assert_array_equal(sims[i], sims_l)
        delta = {name[len("launches.device."):]: c - before.get(name, 0)
                 for name, c in _REG.values("launches.device.").items()}
        active = {d for d, c in delta.items() if c > 0}
        assert len(active) == 8 and "default" not in active, delta
        # stats record the placement and the per-shard launch counts
        # measured where the verifies ran
        for d in st.per_shard:
            assert d["device"].startswith("TFRT_CPU_")
            assert delta[d["device"]] >= d["launches"] > 0
        # one jit instance per device
        assert len(ops.device_jit_cache_info()) >= 8
        print("OK")
    """)


def test_sharded_amih_uneven_device_counts_mesh():
    """Placement stays exact when shards != devices: an explicit device
    list wraps round-robin (8 shards, 3 devices) and leaves extras idle
    (5 shards, 8 devices)."""
    _run("""
        from repro.core import make_engine, linear_scan_knn, pack_bits
        from repro.data import synthetic_binary_codes, synthetic_queries

        p, n, B, k = 64, 997, 4, 9
        db_bits = synthetic_binary_codes(n, p, seed=2)
        db = pack_bits(db_bits)
        qs = pack_bits(synthetic_queries(db_bits, B, seed=3))
        devs = jax.devices()
        few = make_engine("sharded_amih", db, p, num_shards=8,
                          devices=devs[:3], verify_backend="pallas")
        assert [str(d) for d in few.plan.devices] == \\
            [str(devs[s % 3]) for s in range(8)]
        many = make_engine("sharded_amih", db, p, num_shards=5,
                           devices=devs, verify_backend="pallas")
        assert [str(d) for d in many.plan.devices] == \\
            [str(d) for d in devs[:5]]
        for eng in (few, many):
            ids, sims, _ = eng.knn_batch(qs, k)
            for i in range(B):
                _, sims_l = linear_scan_knn(qs[i], db, k)
                np.testing.assert_array_equal(sims[i], sims_l)
        print("OK")
    """)


def test_sharded_amih_fused_one_launch_per_device():
    """PR 7 tentpole on 8 fake devices: 16 shards, 2 per device, fuse
    into ONE walk launch per device per batch (each device's two shards
    stacked into a super index), per-device launch counters move by
    exactly the fused dispatches, stats attribute the shared launch to
    the group's lead shard only, and results stay exact."""
    _run("""
        from repro.core import make_engine, linear_scan_knn, pack_bits
        from repro.data import synthetic_binary_codes, synthetic_queries
        from repro.obs.metrics import REGISTRY as _REG

        p, n, B, k = 64, 4000, 16, 5
        db_bits = synthetic_binary_codes(n, p, seed=4)
        db = pack_bits(db_bits)
        qs = pack_bits(synthetic_queries(db_bits, B, seed=5))
        eng = make_engine("sharded_amih", db, p, num_shards=16,
                          probe_backend="device")
        assert len({str(d) for d in eng.plan.devices}) == 8
        before = _REG.values("launches.device.")
        walk0 = _REG.value("launches.device_probe")
        ids, sims, st = eng.knn_batch(qs, k)
        # ONE fused walk launch per device, not one per shard
        assert _REG.value("launches.device_probe") - walk0 == 8
        delta = {name[len("launches.device."):]: c - before.get(name, 0)
                 for name, c in _REG.values("launches.device.").items()}
        active = {d for d, c in delta.items() if c > 0}
        assert len(active) == 8 and "default" not in active, delta
        # walk (+ at most one scan-fallback) per device
        assert all(1 <= delta[d] <= 2 for d in active), delta
        # S6 attribution: every shard reports the shared per-device
        # launch id; only the lead shard of each device group carries
        # the launch count, so the sum equals real dispatches
        lids = [d["launch_id"] for d in st.per_shard]
        assert len(set(lids)) == 8 and len(lids) == 16
        assert all(d["fused_shards"] == 2 for d in st.per_shard)
        leads = [d for d in st.per_shard if d["launches"] > 0]
        assert len(leads) == 8
        assert sum(d["launches"] for d in st.per_shard) == \\
            sum(delta[d] for d in active)
        for i in range(B):
            _, sims_l = linear_scan_knn(qs[i], db, k)
            np.testing.assert_array_equal(sims[i], sims_l)
        # second batch: super indexes cached, still 8 walk launches
        walk0 = _REG.value("launches.device_probe")
        ids2, sims2, _ = eng.knn_batch(qs, k)
        assert _REG.value("launches.device_probe") - walk0 == 8
        np.testing.assert_array_equal(ids2, ids)
        print("OK")
    """)


# ------------------------------------------------- deprecated shim
def test_core_distributed_shim_warns_and_reexports():
    """core.distributed is a DeprecationWarning shim now; its re-exports
    must keep resolving for old imports."""
    import importlib
    import warnings

    import repro.core.distributed as legacy

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        legacy = importlib.reload(legacy)
    assert any(
        issubclass(w.category, DeprecationWarning)
        and "repro.shard" in str(w.message)
        for w in caught
    )
    from repro.shard import ShardPlan as new_plan

    assert legacy.ShardPlan is new_plan
    for name in ("make_retrieval_step", "sharded_scan_candidates",
                 "sharded_scan_topk"):
        assert callable(getattr(legacy, name))


# ------------------------------------------------- annotation regression
def test_distributed_annotations_resolve():
    """Regression: ``shard_axes: Optional[...]`` used to reference an
    un-imported Optional (hidden by ``from __future__ import
    annotations`` until something resolved the hints)."""
    from repro.core import distributed as legacy
    from repro.shard import distributed as shard_dist

    for fn in (
        shard_dist.sharded_scan_topk,
        shard_dist.make_retrieval_step,
        legacy.sharded_scan_topk,            # the shim re-export
    ):
        hints = typing.get_type_hints(fn)
        assert "shard_axes" in hints


# ---------------------------------------------- mesh mode (8 fake devices)
def test_sharded_engines_match_linear_scan_on_mesh():
    _run("""
        from repro.core import make_engine, linear_scan_knn, pack_bits
        from repro.data import synthetic_binary_codes, synthetic_queries
        from repro.launch.mesh import make_mesh, make_search_mesh

        p, n, k = 64, 4093, 25               # prime N: uneven everywhere
        db_bits = synthetic_binary_codes(n, p, seed=0)
        db = pack_bits(db_bits)
        mesh = make_mesh((4, 2), ("data", "model"))
        eng = make_engine("sharded_scan", db, p, mesh=mesh, chunk=256)
        assert eng.plan.num_shards == 8
        amih = make_engine("sharded_amih", db, p, mesh=mesh)
        for B in (1, 8, 64):
            qs = pack_bits(synthetic_queries(db_bits, B, seed=B))
            for e in (eng, amih):
                ids, sims, stats = e.knn_batch(qs, k)
                assert stats.shards == 8
                for i in range(B):
                    ids_l, sims_l = linear_scan_knn(qs[i], db, k)
                    np.testing.assert_array_equal(sims[i], sims_l)

        # K > per-shard rows (512 rows/shard, K pool spans shards)
        small = pack_bits(db_bits[:40])
        eng_s = make_engine("sharded_scan", small, p, mesh=mesh, chunk=8)
        qs = pack_bits(synthetic_queries(db_bits, 4, seed=99))
        ids, sims, _ = eng_s.knn_batch(qs, 30)
        for i in range(4):
            _, sims_l = linear_scan_knn(qs[i], small, 30)
            np.testing.assert_array_equal(sims[i], sims_l)

        # the 1-D search mesh helper spans all fake devices
        smesh = make_search_mesh()
        eng_m = make_engine("sharded_scan", db, p, mesh=smesh, chunk=256)
        assert eng_m.plan.num_shards == 8
        ids, sims, _ = eng_m.knn_batch(qs[:2], 10)
        for i in range(2):
            _, sims_l = linear_scan_knn(qs[i], db, 10)
            np.testing.assert_array_equal(sims[i], sims_l)
        print("OK")
    """)
