"""The comparison that decides ``correct``.

Every answer the measured window produced is held against the plain
reference (the exactness check of the bring-up smoke, copied):

- ``bad_shape``: answers whose ids or sims are not (B, min(K, n));
- ``wrong_sims``: rows whose sims, sorted, differ from the reference's K
  largest sims (exact float64 equality);
- ``dup_ids``: rows that hold an id twice;
- ``id_not_sim``: returned ids that are out of range or whose float64
  sim, recomputed by the reference, differs from the one reported;
- ``unanswered``: queries of the window that got no answer.

Ties inside one Hamming tuple may come back in either order, which the
sorted comparison allows. Each number has the limit 0: an exact search
either returns the reference's sims or is wrong.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

LIMITS = {
    "bad_shape": 0,
    "wrong_sims": 0,
    "dup_ids": 0,
    "id_not_sim": 0,
    "unanswered": 0,
}


def compare(answers: Dict[int, List[Tuple[np.ndarray, np.ndarray]]],
            pool: List[np.ndarray], db: np.ndarray, ref_sims: List[np.ndarray],
            k: int, unanswered: int, sims_of) -> Dict[str, int]:
    """Count the faults of ``answers``: pool batch index -> the distinct
    (ids, sims) answers the window got for it. ``ref_sims[b]`` is the
    reference's (B, k') descending sims for pool batch ``b``; ``sims_of(q,
    rows)`` is the reference's float64 sim of ``rows`` against ``q``."""
    n = db.shape[0]
    k_eff = min(k, n)
    out = dict.fromkeys(LIMITS, 0)
    out["unanswered"] = int(unanswered)
    for b, distinct in answers.items():
        q = pool[b]
        for ids, sims in distinct:
            ids = np.asarray(ids)
            sims = np.asarray(sims)
            if ids.shape != (q.shape[0], k_eff) or sims.shape != ids.shape:
                out["bad_shape"] += 1
                continue
            got = -np.sort(-sims.astype(np.float64), axis=1)
            out["wrong_sims"] += int(
                (got != ref_sims[b]).any(axis=1).sum())
            for i in range(q.shape[0]):
                row = ids[i]
                if np.unique(row).size != row.size:
                    out["dup_ids"] += 1
                ok = (row >= 0) & (row < n)
                out["id_not_sim"] += int((~ok).sum())
                if ok.any():
                    re = sims_of(q[i], db[row[ok]])
                    out["id_not_sim"] += int((re != sims[i][ok]).sum())
    return out


def verdict(numbers: Dict[str, int]) -> bool:
    return all(numbers[name] <= limit for name, limit in LIMITS.items())


def as_lines(numbers: Dict[str, int]) -> Dict[str, Dict[str, int]]:
    return {name: {"value": numbers[name], "limit": LIMITS[name]}
            for name in LIMITS}
