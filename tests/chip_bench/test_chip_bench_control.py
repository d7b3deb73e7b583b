"""The comparison that decides ``correct`` refuses the control and every
fault a cell can have; the command refuses to run without a TPU."""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from chip_bench_util import ROOT, harness, run_tiny, tiny_cell


def _reference():
    cell = tiny_cell("scan64.k100")
    return harness.load_module(cell.bench_dir / "references"
                               / f"{cell.config['reference']}.py")


def test_control_one_precision_lower_is_not_correct():
    ref = _reference()
    cell = tiny_cell("scan64.k100")
    res = run_tiny(cell, engine_factory=lambda c, db, devs:
                   ref.ControlEngine(db, c.p, devs[0]))
    assert res["correct"] is False
    assert res["checks"]["wrong_sims"]["value"] > 0


class Faulty:
    """The engine under test with one fault planted where it answers."""

    def __init__(self, engine, fault):
        self.engine, self.fault, self.last = engine, fault, None

    def knn_batch(self, q, k):
        ids, sims, stats = self.engine.knn_batch(q, k)
        ids, sims = ids.copy(), sims.copy()
        if self.fault == "state_unchanged":      # the last answer again
            out, self.last = self.last or (ids, sims), (ids, sims)
            ids, sims = out
        elif self.fault == "half_batch":         # the rest stands in
            h = len(q) // 2
            ids[h:], sims[h:] = ids[: len(q) - h], sims[: len(q) - h]
        elif self.fault == "answer_altered":     # one id, where produced
            ids[0, -1] = (ids[0, -1] + 1) % self.engine.n
        return ids, sims, stats

    def close(self):
        getattr(self.engine, "close", lambda: None)()


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_faults_are_not_correct(fault):
    def factory(cell, db, devices):
        return Faulty(harness.build_engine(cell, db, devices), fault)

    res = run_tiny(tiny_cell("scan64.k100"), engine_factory=factory)
    assert res["correct"] is False
    assert sum(c["value"] for c in res["checks"].values()) > 0


def test_an_engine_that_raises_counts_failed():
    class Broken:
        def knn_batch(self, q, k):
            raise RuntimeError("device lost")

    calls = []

    def factory(cell, db, devices):
        engine = harness.build_engine(cell, db, devices)

        class WarmThenBreak:
            def knn_batch(self, q, k):
                calls.append(1)
                if len(calls) > cell.traffic.pool_batches:
                    return Broken().knn_batch(q, k)
                return engine.knn_batch(q, k)
        return WarmThenBreak()

    res = run_tiny(tiny_cell("scan64.k100"), engine_factory=factory)
    assert res["correct"] is False and res["failed"] > 0
    assert res["checks"]["unanswered"]["value"] == res["failed"]


def test_reference_matches_a_plain_scan():
    """The counted top K equals sorting every float64 sim."""
    ref = _reference()
    rng = np.random.default_rng(5)
    db = rng.integers(0, 2**32, (3000, 2), dtype=np.uint32)
    db[:50] = db[50:100]                              # exact ties
    db[7] = 0                                         # a code with no bit
    q = np.concatenate([db[:3] ^ np.uint32(5), np.zeros((1, 2), np.uint32)])
    got = ref.topk_sims(q, db, 64, 20)
    for i in range(len(q)):
        want = np.sort(ref.sims64(q[i], db))[::-1][:20]
        assert np.array_equal(got[i], want)


@pytest.mark.parametrize("k", [1, 10, 100])
def test_reference_on_the_cluster_model(k):
    """Clustered codes put many codes on few sims near the top: the counts
    still give every sim as often as codes have it."""
    import corpus

    ref = _reference()
    db = corpus.clustered_codes(11, 1 << 13, 64, n_clusters=16,
                                flip_prob=0.08)
    q = corpus.near_queries(11, db, 64, 40, flip_prob=0.05)
    got = ref.topk_sims(q, db, 64, k)
    for i in range(len(q)):
        want = np.sort(ref.sims64(q[i], db))[::-1][:k]
        assert np.array_equal(got[i], want)


def test_corpus_blocks_hold_one_popcount_each():
    ref = _reference()
    rng = np.random.default_rng(2)
    db = rng.integers(0, 2**32, (5000, 2), dtype=np.uint32)
    blocks, used, block_y = ref.group_blocks(db, 64, 128)
    assert used.sum() == len(db) and (used > 0).all()
    rows = []
    for b, u, y in zip(blocks, used, block_y):
        assert (ref.popcount_rows(b[:u]) == y).all()
        assert not b[u:].any()
        rows.extend(map(bytes, b[:u]))
    assert sorted(rows) == sorted(map(bytes, db))


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "scan64.k100",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
