"""chip_smoke.py on the CPU: its phase logic at a tiny size, its checks,
and its refusal to run where JAX finds no TPU."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import make_engine

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()


def test_single_chip_phases_exact_with_launches_counted():
    import jax

    from repro.kernels import ops

    device = jax.devices()[0]
    on_device = "launches.device." + ops.device_key(device)
    lines = []
    smoke.single_chip_phases(device, 1 << 12, 8, 0, widths=((64, 4),),
                             ks=(1, 100), out=lines.append)
    rows = [json.loads(line) for line in lines]
    assert [(r["phase"], r["K"]) for r in rows] == [
        (phase, k)
        for phase in ("linear_scan_pallas", "amih_device_walk",
                      "amih_pallas_verify")
        for k in (1, 100)
    ]
    assert all(r["exact"] and r["p"] == 64 and r["B"] == 8 for r in rows)
    walk = [r for r in rows if r["phase"] == "amih_device_walk"]
    assert [r["launches"]["launches.device_probe"] for r in walk] == [1, 1]
    # K=100 over 4096 codes bails every query to the scan fallback
    assert walk[1]["fell_back"] == 8
    assert walk[1]["launches"]["launches.device_probe_scan"] == 1
    assert [r["launches"][on_device] for r in walk] == [1, 2]
    verify = [r for r in rows if r["phase"] == "amih_pallas_verify"]
    assert all(r["launches"]["launches.verify_grouped"] >= 1 for r in verify)
    assert all(r["launches"][on_device] == r["launches"]
               ["launches.verify_grouped"] for r in verify)
    assert all("launches.device.default" not in r["launches"] for r in rows)


def test_sharded_phases_on_four_virtual_devices():
    """The ``--chips 4`` phases at a tiny size on four CPU devices: one
    walk launch per device, the mesh scan, both exact."""
    code = f"""
import importlib.util, json
spec = importlib.util.spec_from_file_location(
    "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
import jax
lines = []
smoke.sharded_phases(jax.devices()[:4], 1 << 10, 8, 0, ks=(10,),
                     out=lines.append)
print(json.dumps([json.loads(line) for line in lines]))
"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src")}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    assert [r["phase"] for r in rows] == ["sharded_amih_device_walk",
                                          "sharded_scan_mesh"]
    assert all(r["exact"] and r["n"] == 4 << 10 and r["chips"] == 4
               for r in rows)
    per_device = {key: v for key, v in rows[0]["launches"].items()
                  if key.startswith("launches.device.")}
    assert len(per_device) == 4 and "launches.device.default" not in per_device


@pytest.fixture(scope="module")
def small():
    db, q, ref = smoke.make_corpus(512, 64, 4, seed=3)
    ids, sims, _ = make_engine("linear_scan", db, 64).knn_batch(q, 10)
    return db, q, ref, ids, sims


def test_check_exact_accepts_the_scan(small):
    db, q, ref, ids, sims = small
    smoke.check_exact(q, db, ids, sims, ref, 10)


@pytest.mark.parametrize("corrupt", ["sim_ulp", "id_swap", "duplicate_id",
                                     "short_row"])
def test_check_exact_rejects_wrong_answers(small, corrupt):
    db, q, ref, ids, sims = small
    ids, sims = ids.copy(), sims.copy()
    if corrupt == "sim_ulp":
        sims[1, -1] = np.nextafter(sims[1, -1], 2.0)
    elif corrupt == "id_swap":
        ids[2, 0] = np.setdiff1d(np.arange(db.shape[0]), ids[2])[0]
    elif corrupt == "duplicate_id":
        ids[0, -1] = ids[0, 0]
        sims[0, -1] = sims[0, 0]
    else:
        ids, sims = ids[:, :-1], sims[:, :-1]
    with pytest.raises(smoke.PhaseError):
        smoke.check_exact(q, db, ids, sims, ref, 10)


def test_phase_fails_when_its_launch_counter_stays_still(small):
    db, q, ref, _, _ = small
    eng = make_engine("linear_scan", db, 64)
    with pytest.raises(smoke.PhaseError, match="did not move"):
        smoke.run_phase("scan", eng, q, db, ref, (10,),
                        launches=("launches.device_probe",),
                        out=lambda line: None)


@pytest.mark.parametrize("alone", [False, True])
def test_refuses_to_run_without_a_tpu(tmp_path, alone):
    script = ROOT / "chip_smoke.py"
    if alone:   # a directory holding the script and nothing of the repo
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    out = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        cwd=tmp_path, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        timeout=300,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr
