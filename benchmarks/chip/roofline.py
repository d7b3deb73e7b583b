"""Least work of the search problem, and the chip's published peaks.

The byte counts are of the problem, not of today's kernel, so a share
stays right when a later change replaces the kernel: an exact top-K over
n codes of p bits reads every code once (n * p / 8 bytes) and writes K
results of an int32 id and a float32 sim per query (B * K * 8 bytes).
The scan's popcounts run on the vector unit, which has no published
peak rate, so every share here is bound by HBM bytes.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a kind not in the table is
    an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}")
    return table[device_kind]


def scan_least_bytes(n: int, p: int, queries: int, k: int) -> int:
    """Bytes an exhaustive top-K of ``queries`` queries must move: the
    codes read once and the results written."""
    return n * p // 8 + queries * min(k, n) * 8


def roofline_pct(least_bytes: float, seconds: float,
                 hbm_bytes_per_s: float) -> float:
    """Share of the HBM roofline in %: the least time the bytes take at
    peak bandwidth over the time measured."""
    return 100.0 * (least_bytes / hbm_bytes_per_s) / seconds
