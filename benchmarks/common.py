"""Shared benchmark utilities: timing, dataset prep, CSV emission."""

from __future__ import annotations

import csv
import os
import time
from pathlib import Path
from typing import Callable, Dict, List

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import AMIHIndex, pack_bits
from repro.data import synthetic_binary_codes, synthetic_queries

# every benchmark CLI imports this module before it compiles anything
enable_compile_cache(Path(__file__).resolve().parents[1])

ART_DIR = os.environ.get("REPRO_BENCH_DIR", "artifacts/bench")


def timer(fn: Callable, *args, repeat: int = 3, **kw) -> float:
    """Median wall seconds of fn(*args)."""
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn(*args, **kw)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def write_csv(name: str, rows: List[Dict], field_order=None):
    os.makedirs(ART_DIR, exist_ok=True)
    path = os.path.join(ART_DIR, name)
    if not rows:
        return path
    fields = field_order or list(rows[0].keys())
    with open(path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=fields)
        w.writeheader()
        for r in rows:
            w.writerow(r)
    return path


def make_db(n: int, p: int, seed: int = 0, mode: str = "clustered"):
    bits = synthetic_binary_codes(n, p, seed=seed, mode=mode)
    return bits, pack_bits(bits)


def make_queries(db_bits: np.ndarray, nq: int, seed: int = 1):
    qbits = synthetic_queries(db_bits, nq, seed=seed)
    return qbits, pack_bits(qbits)
