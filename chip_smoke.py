#!/usr/bin/env python3
"""Exact AMIH search on TPU chips, driven through ``make_engine``.

    python chip_smoke.py              # one chip: the single-device phases
    python chip_smoke.py --chips 4    # four chips: the sharded phases only

One chip: for p = 64 (m = 4) and p = 128 (m = 8), n = 2^20 clustered
codes and B = 64 queries near them (all drawn from ``--seed``), answered
for K in {1, 10, 100} by
  - ``linear_scan`` with ``compute_backend="pallas"`` (device top-K),
  - ``amih`` with ``probe_backend="device"`` (fused walk + scan fallback),
  - ``amih`` with ``verify_backend="pallas"`` (host walk, device verify).
Four chips: ``sharded_amih`` (device walk, one launch per chip) and
``sharded_scan`` (shard_map + all-gather on a 4-device mesh) over
n = 4 x 2^20 codes at p = 64.

Every answer is checked against ``linear_scan_knn``, the float64 numpy
reference: each row's sorted sims equal the reference's, and each
returned id's sim, recomputed with ``sims_for_ids``, equals the reported
one (ties inside one Hamming tuple may order ids either way). Each phase
prints one JSON line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The script exits non-zero, printing no such line, when JAX finds no TPU
or any phase is wrong. It runs in one process and starts no other.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

N_PER_CHIP = 1 << 20
BATCH = 64
KS = (1, 10, 100)
WIDTHS = ((64, 4), (128, 8))      # (p, m): 16-bit substrings either way


class PhaseError(AssertionError):
    """A phase returned a wrong answer or did not run where it should."""


def emit(line: str) -> None:
    print(line, flush=True)


def require_tpu(chips: int):
    """The TPU devices to run on; exits before any work when there are
    fewer than ``chips`` of them (JAX falls back to its CPU silently)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform "
            f"{devs[0].platform!r}); refusing to run"
        )
    if len(devs) < chips:
        raise SystemExit(
            f"chip_smoke: {chips} chips asked for, {len(devs)} found"
        )
    return devs


def make_corpus(n: int, p: int, batch: int, seed: int):
    """Packed clustered codes, packed queries near them, and each query's
    reference top-max(KS) sims (descending) from ``linear_scan_knn``."""
    from repro.core import linear_scan_knn, pack_bits
    from repro.data import synthetic_binary_codes, synthetic_queries

    db_bits = synthetic_binary_codes(n, p, seed=seed, mode="clustered")
    q = pack_bits(synthetic_queries(db_bits, batch, seed=seed + 1))
    db = pack_bits(db_bits)
    ref = [linear_scan_knn(q[b], db, max(KS))[1] for b in range(batch)]
    return db, q, ref


def check_exact(q, db, ids, sims, ref, k: int) -> None:
    """Raise ``PhaseError`` unless every row holds the reference's sims."""
    from repro.core.linear_scan import sims_for_ids

    k_eff = min(k, db.shape[0])
    if ids.shape != (q.shape[0], k_eff) or sims.shape != ids.shape:
        raise PhaseError(f"result shape {ids.shape}/{sims.shape}")
    for b in range(q.shape[0]):
        if not np.array_equal(np.sort(sims[b])[::-1], ref[b][:k_eff]):
            raise PhaseError(f"row {b}: sims differ from the scan")
        if np.unique(ids[b]).size != k_eff:
            raise PhaseError(f"row {b}: duplicate ids")
        if not np.array_equal(sims_for_ids(q[b], db, ids[b]), sims[b]):
            raise PhaseError(f"row {b}: an id does not carry its sim")


def on_platform(array, platform: str) -> None:
    """Raise unless the device-resident ``array`` lives on ``platform``."""
    where = {d.platform for d in array.devices()}
    if where != {platform}:
        raise PhaseError(f"resident array on {where}, not {platform!r}")


def run_phase(name, engine, q, db, ref, ks, *, launches=(),
              row=None, out=emit):
    """Answer every K twice (compile included, then warm), check both
    answers, and print one line per K. ``launches`` names registry
    counters each answer must move."""
    from repro.obs.metrics import REGISTRY

    for k in ks:
        before = REGISTRY.values("launches.")
        t0 = time.perf_counter()
        ids, sims, stats = engine.knn_batch(q, k)
        cold = time.perf_counter() - t0
        moved = {
            key: v - before.get(key, 0)
            for key, v in REGISTRY.values("launches.").items()
            if v != before.get(key, 0)
        }
        check_exact(q, db, ids, sims, ref, k)
        for counter in launches:
            if not moved.get(counter):
                raise PhaseError(f"{name} K={k}: {counter} did not move")
        t0 = time.perf_counter()
        ids, sims, _ = engine.knn_batch(q, k)
        warm = time.perf_counter() - t0
        check_exact(q, db, ids, sims, ref, k)
        out(json.dumps({
            "phase": name, **(row or {}), "B": q.shape[0], "K": k,
            "seconds": cold, "warm_seconds": warm, "launches": moved,
            "fell_back": stats.total("fell_back_to_scan"), "exact": True,
        }))


def single_chip_phases(device, n, batch, seed, *, widths=WIDTHS, ks=KS,
                       out=emit):
    """The one-chip phases: every engine over one corpus on ``device``.
    The AMIH indexes are placed there, so their launches count under
    ``launches.device.<device>`` as well as under the kernel's name."""
    from repro.core import make_engine
    from repro.kernels import ops

    on_device = "launches.device." + ops.device_key(device)
    for p, m in widths:
        db, q, ref = make_corpus(n, p, batch, seed)
        row = {"p": p, "m": m, "n": n}
        eng = make_engine("linear_scan", db, p, compute_backend="pallas")
        run_phase("linear_scan_pallas", eng, q, db, ref, ks, row=row,
                  out=out)
        on_platform(eng._db_dev, device.platform)
        del eng
        eng = make_engine("amih", db, p, m=m, probe_backend="device",
                          device=device, query_cache_size=0)
        on_platform(eng.index.device_csr["db_pad"], device.platform)
        run_phase("amih_device_walk", eng, q, db, ref, ks, row=row,
                  launches=("launches.device_probe", on_device), out=out)
        del eng
        eng = make_engine("amih", db, p, m=m, verify_backend="pallas",
                          device=device, query_cache_size=0)
        on_platform(eng.index.db_dev, device.platform)
        run_phase("amih_pallas_verify", eng, q, db, ref, ks, row=row,
                  launches=("launches.verify_grouped", on_device), out=out)
        del eng


def sharded_phases(devices, n_per_chip, batch, seed, *, ks=KS, out=emit):
    """The four-chip phases: one corpus row-sharded over ``devices``."""
    from repro.core import make_engine
    from repro.kernels import ops
    from repro.launch.mesh import make_search_mesh

    p, m = WIDTHS[0]
    chips = len(devices)
    n = chips * n_per_chip
    db, q, ref = make_corpus(n, p, batch, seed)
    row = {"p": p, "m": m, "n": n, "chips": chips}
    keys = {"launches.device." + ops.device_key(d) for d in devices}
    eng = make_engine("sharded_amih", db, p, m=m, num_shards=chips,
                      devices=devices, probe_backend="device")
    run_phase("sharded_amih_device_walk", eng, q, db, ref, ks, row=row,
              launches=sorted(keys), out=out)
    eng.close()
    eng = make_engine("sharded_scan", db, p,
                      mesh=make_search_mesh(chips))
    run_phase("sharded_scan_mesh", eng, q, db, ref, ks, row=row, out=out)
    if set(eng._db_dev.devices()) != set(devices):
        raise PhaseError(f"sharded_scan rows on {eng._db_dev.devices()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    from repro.compile_cache import enable_compile_cache
    from repro.kernels import ops

    cache = Path(enable_compile_cache(ROOT))

    def cache_line(when):
        entries = len(list(cache.iterdir())) if cache.is_dir() else 0
        print(json.dumps({"compile_cache": str(cache), when: entries}),
              flush=True)

    cache_line("entries_before")
    try:
        if not ops.on_tpu():
            raise PhaseError("the kernels would not take their TPU path")
        if args.chips == 4:
            sharded_phases(devs[:4], N_PER_CHIP, BATCH, args.seed)
        else:
            single_chip_phases(devs[0], N_PER_CHIP, BATCH, args.seed)
    except PhaseError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    cache_line("entries_after")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
