"""Shared helpers of the chip benchmark's tests: the harness driven on the
CPU at a tiny corpus, past its look for a chip."""

from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

TINY_N = 4096
SEED = 2**31 + 977        # larger than 32 signed bits, as the driver's are


def tiny_cell(workload: str, root: Path = ROOT, n: int = TINY_N):
    cell = harness.load_cell(root, workload)
    cell.config["n_per_chip"] = n
    return cell


def cpu_devices(chips: int):
    """The CPU device, repeated to stand for the cell's chips."""
    import jax

    return [jax.devices()[0]] * chips


def run_tiny(cell, *, trace=False, seconds=0.3, engine_factory=None,
             tmp_path=None, seed=SEED):
    return harness.run_cell(cell, seed, seconds, trace,
                            cpu_devices(cell.chips), time.perf_counter(),
                            engine_factory=engine_factory,
                            trace_dir=tmp_path)
