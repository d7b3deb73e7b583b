"""Bytes the AMIH walk reads and writes per batch, counted from the
program's own counters, so the count follows the work the program did.

Each candidate the walk pulls from a bucket is a 4-byte id read from the
device CSR (``amih.retrieved``), each distinct code it verifies is a
p / 8-byte code read once (``amih.verified``), and each query writes K
(position, id) pairs of 8 bytes. The counters are process-wide, so they
are taken per ``knn_batch`` call (``engine.batches``). The bytes are the
least the walk's own candidates need: a re-read of a code met through a
second table, and the bucket offsets, are left out.
"""

from __future__ import annotations


def walk_bytes_per_batch(retrieved: int, verified: int, batches: int,
                         p: int, queries: int, k: int) -> float:
    """Bytes per batch of a walk that pulled ``retrieved`` ids and
    verified ``verified`` distinct codes over ``batches`` batches of
    ``queries`` queries at top-``k``."""
    return (4 * retrieved + p // 8 * verified) / batches + queries * k * 8
