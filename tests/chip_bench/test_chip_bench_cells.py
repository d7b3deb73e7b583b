"""Every cell of BENCHMARK.json run end to end on the CPU at a tiny corpus
through the harness, and a cell that a later change would add by files
alone."""

from __future__ import annotations

import json
import shutil

import numpy as np
import pytest

from chip_bench_util import ROOT, harness, run_tiny, tiny_cell

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(workload):
    cell = tiny_cell(workload)
    res = run_tiny(cell)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["attempted"] % cell.traffic.batch == 0
    names = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == names
    assert "setup_s" in names and len(names) >= 2
    for m in res["metrics"].values():
        assert m["value"] > 0
    dev = res["device"]
    assert dev["platform"] == "cpu" and dev["count"] == cell.chips
    assert "kind" in dev and "memory_peak_bytes" in dev
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_per_layer_metrics(workload, tmp_path):
    cell = tiny_cell(workload)
    res = run_tiny(cell, trace=True, tmp_path=tmp_path)
    assert res["correct"] is True
    # rooflines need the chip's peaks: off the chip they are left out
    want = {m["name"] for m in cell.per_layer
            if not m["name"].endswith("_roofline")}
    assert want <= set(res["metrics"])
    assert 0 <= res["metrics"]["device_idle_pct"]["value"] <= 100
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    bd = res["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert list(tmp_path.iterdir()) == []        # the trace was removed


def test_seeds_draw_the_same_batches_in_another_order():
    cell = tiny_cell("scan64.k100")
    db1, pool1 = harness.make_corpus(cell, 1)
    seeds = [2**31 + s for s in range(6)]
    pools = [harness.make_corpus(cell, s)[1] for s in seeds]
    assert np.array_equal(db1, harness.make_corpus(cell, seeds[0])[0])

    def batches(pool):
        return [b.tobytes() for b in pool]
    assert any(batches(p) != batches(pool1) for p in pools)
    for p in pools:
        assert sorted(batches(p)) == sorted(batches(pool1))
    assert batches(pool1) == batches(harness.make_corpus(cell, 1)[1])


def test_corpus_is_the_cluster_model():
    import corpus

    args = dict(n_clusters=4, flip_prob=0.08)
    db = corpus.clustered_codes(3, 1 << 14, 64, **args)
    assert db.shape == (1 << 14, 2) and db.dtype == np.uint32
    assert np.array_equal(db, corpus.clustered_codes(3, 1 << 14, 64, **args))
    assert not np.array_equal(db, corpus.clustered_codes(4, 1 << 14, 64,
                                                         **args))
    bits = np.unpackbits(db.view(np.uint8), axis=1, bitorder="little")
    dist = np.abs(bits[1:].astype(int) - bits[0]).mean(1)
    # one cluster in four lies near code 0, about 2 x 8% of bits away
    same = dist < 0.3
    assert 0.15 < same.mean() < 0.35
    assert np.median(dist[same]) == pytest.approx(2 * 0.08 * 0.92, abs=0.03)
    assert np.median(dist[~same]) == pytest.approx(0.5, abs=0.1)


ADDED_CONFIG = {
    "source": "test configuration", "backend": "amih", "p": 32,
    "n_per_chip": 2048,
    "corpus": {"mode": "clustered", "n_clusters": 16, "flip_prob": 0.1,
               "seed": 7},
    "engine": {"m": 2, "probe_backend": "device", "query_cache_size": 0},
    "reference": "exact_angular_knn",
}
ADDED_TRAFFIC = {"loop": "closed", "batch": 8, "k": 5, "pool_batches": 2,
                 "query_flip_prob": 0.05}
ADDED_READER = '''
def read(run):
    return float(len(run.batches))
'''


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A new configuration, traffic mix, cell and per-layer metric, added
    as files and BENCHMARK.json entries in a copy of the benchmark."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(ROOT / "benchmarks" / "chip", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench_dir / "configs" / "amih32.json").write_text(
        json.dumps(ADDED_CONFIG))
    (bench_dir / "traffic" / "b8k5.json").write_text(json.dumps(ADDED_TRAFFIC))
    (bench_dir / "layers" / "batches_run.py").write_text(ADDED_READER)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "amih32", "source": "test", "why": "test",
        "file": "benchmarks/chip/configs/amih32.json", "reduced": []})
    bench["workloads"].append({
        "name": "amih32.b8k5", "config": "amih32", "traffic": "b8k5",
        "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "batches_run", "unit": "batches", "better": "higher",
        "source": "host_clock", "layer": "engine", "moves": "qps",
        "workloads": ["amih32.b8k5"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = tiny_cell("amih32.b8k5", root=tmp_path, n=2048)
    assert cell.p == 32 and cell.traffic.k == 5
    assert [m["name"] for m in cell.per_layer] == ["batches_run"]
    res = run_tiny(cell, trace=True, tmp_path=tmp_path / "tr")
    assert res["correct"] is True
    assert res["metrics"]["batches_run"]["value"] >= 1
    assert res["metrics"]["batches_run"]["unit"] == "batches"
