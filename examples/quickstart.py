"""Quickstart: the paper's algorithm end to end in two minutes on CPU.

1. build a synthetic binary dataset (AQBC-like clustered codes),
2. build a search engine by backend name (the unified SearchEngine API),
3. run exact angular KNN as ONE batched query call and verify against the
   linear-scan backend,
4. print the paper-style cost accounting (probes / verifications /
   grouped-verify launches).

Compute knobs (PR 2):

  - AMIH verifies candidates in one grouped call per (z-group, tuple
    step): ``verify_backend="numpy"`` is a single vectorized host
    popcount over all same-z queries; ``verify_backend="pallas"`` gathers
    the blocks into a padded (B_g, C_max, W) device layout and issues one
    ``verify_tuples_grouped`` kernel launch per step against the
    device-resident DB (uploaded once at build).
    ``engine.index.verify_launches`` counts dispatches.
  - The exhaustive baseline takes ``compute_backend="pallas"``: scoring
    runs through the streaming device top-K (kernels/ops.scan_topk) and
    the preselected candidates are re-ranked on host in float64, so
    results stay bit-identical to the numpy path.

Run:  PYTHONPATH=src python examples/quickstart.py
(REPRO_EXAMPLE_N overrides the DB size — the examples smoke test runs
this headless on a small n)
"""

import os
import time

import numpy as np

from repro.core import make_engine, pack_bits
from repro.data import synthetic_binary_codes, synthetic_queries


def main():
    p, n, k, B = 64, int(os.environ.get("REPRO_EXAMPLE_N", 200_000)), 10, 5
    print(f"dataset: n={n:,} codes x {p} bits, {B} queries in one batch")
    db_bits = synthetic_binary_codes(n, p, seed=0)
    db = pack_bits(db_bits)
    qs = pack_bits(synthetic_queries(db_bits, B, seed=1))

    t0 = time.perf_counter()
    # verify_backend="pallas" puts grouped candidate verification on
    # device (one launch per z-group and tuple step): the Pallas kernel
    # on a TPU, the same math as plain XLA elsewhere. "numpy" (the
    # default) does one vectorized host popcount per step instead — the
    # right choice off-TPU, where a launch per step buys nothing.
    amih = make_engine("amih", db, p)
    print(f"indexed in {time.perf_counter() - t0:.2f}s "
          f"(m={amih.index.m} tables, paper's m = p/log2 n; "
          f"enumeration_cap={amih.enumeration_cap:,} = max(8n, 16384))")
    scan = make_engine("linear_scan", db, p)

    t0 = time.perf_counter()
    ids, sims, stats = amih.knn_batch(qs, k)
    t_amih = time.perf_counter() - t0

    t0 = time.perf_counter()
    ids_l, sims_l, _ = scan.knn_batch(qs, k)
    t_scan = time.perf_counter() - t0

    assert np.array_equal(sims, sims_l), "exactness violated!"
    agg = stats.aggregate()
    for i, s in enumerate(stats.per_query):
        print(f"q{i}: top-{k} sims {np.round(sims[i, :3], 4)}..., "
              f"probes={s.probes} verified={s.verified} "
              f"({s.verified / n:.2%} of db)")
    print(f"batch of {B}: AMIH {1e3 * t_amih:6.2f}ms vs scan "
          f"{1e3 * t_scan:7.2f}ms ({t_scan / max(t_amih, 1e-9):6.1f}x) | "
          f"total probes={agg['probes']} verified={agg['verified']} in "
          f"{amih.index.verify_launches} grouped verify calls")

    # the kernel-backed exhaustive baseline: device top-K preselect
    # (scan_topk; DB uploaded once, resident thereafter) + exact float64
    # host rerank — bit-identical sims, device does the heavy scan.
    scan_dev = make_engine("linear_scan", db, p, compute_backend="pallas")
    scan_dev.knn_batch(qs[:1], k)   # warm: jit compile + DB upload
    t0 = time.perf_counter()
    _, sims_d, _ = scan_dev.knn_batch(qs, k)
    t_dev = time.perf_counter() - t0
    assert np.array_equal(sims_d, sims_l), "device path exactness violated!"
    print(f"kernel-backed scan (compute_backend='pallas'): "
          f"{1e3 * t_dev:7.2f}ms, sims bit-identical")

    # pod-scale sharded backends (repro.shard): the DB row-partitioned by
    # a ShardPlan — per-shard global-id offsets, balanced remainder — and
    # served through the SAME knn_batch API. Here the plan is host-mode
    # (num_shards); on a multi-device host pass a mesh instead:
    #   from repro.launch.mesh import make_search_mesh
    #   make_engine("sharded_amih", db, p, mesh=make_search_mesh())
    from repro.shard import ShardPlan

    plan = ShardPlan.balanced(n, 8)
    print(f"shard plan: {plan.summary()}")
    sharded = make_engine("sharded_amih", db, p, plan=plan)
    t0 = time.perf_counter()
    _, sims_s, st_s = sharded.knn_batch(qs, k)
    t_sh = time.perf_counter() - t0
    assert np.array_equal(sims_s, sims_l), "sharded exactness violated!"
    early = sum(d["early_stopped"] for d in st_s.per_shard)
    print(f"sharded_amih over {st_s.shards} shards: {1e3 * t_sh:6.2f}ms, "
          f"sims bit-identical; {early} per-shard searches stopped early "
          f"(global k-th cosine bound)")
    print("all queries exact — engine('amih') == engine('linear_scan') == "
          "engine('sharded_amih'), orders faster.")


if __name__ == "__main__":
    main()
