"""Reduction of a JAX profiler trace to the benchmark's per-layer numbers.

``read_xspace`` loads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps four things, every time in nanoseconds on the profiler's clock:

- per device, the intervals in which an XLA operation ran (a TPU device
  plane's "XLA Ops" line);
- per device, the executions of each compiled program (its "XLA Modules"
  line), named by the jitted function ("jit_scan_topk");
- the benchmark's own host annotations around each ``knn_batch`` call;
- every other event of the thread that made those calls, to say what
  the host was doing while the device sat idle.

On the CPU backend there are no device planes: operations run on the
client's host threads, carrying ``hlo_op`` and ``hlo_module`` stats. They
are read as the operations of one device, so that the reduction can be
checked on a trace recorded without a chip.

Busy time is the union of operation intervals, so overlapping operations
count once; idle share is 1 - busy / window, where the window runs from
the start of the first annotated batch to the end of the last.
"""

from __future__ import annotations

import re
import warnings
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

BATCH_ANNOTATION = "bench.knn_batch"
_MODULE_ID = re.compile(r"\(\d+\)$")
# "%fusion.19 = (f32[64,128]..., s32[64,128]...) fusion(...), kind=..." ->
# "fusion.19 fusion": the op's name and its opcode, without the shapes
_HLO_TEXT = re.compile(r"^%?([^\s=]+) = .*?[\]}) ]([a-z][a-z0-9\-]*)\(")

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)


@dataclass
class TraceSummary:
    ops: Dict[str, List[Event]] = field(default_factory=dict)
    modules: Dict[str, List[Event]] = field(default_factory=dict)
    batches: List[Tuple[float, float]] = field(default_factory=list)
    host: List[Event] = field(default_factory=list)

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)

    @property
    def window(self) -> Tuple[float, float]:
        return self.batches[0][0], self.batches[-1][1]


def op_name(text: str) -> str:
    """A short name for an "XLA Ops" event, whose name is the op's HLO."""
    m = _HLO_TEXT.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text[:80]


def find_xspace(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _stats(event) -> dict:
    # the stats' builtin type warns when read (JAX 0.9); nothing to act on
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(event.stats)


def read_xspace(path: Path) -> TraceSummary:
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(str(path))
    out = TraceSummary()
    cpu_ops: List[Event] = []
    cpu_runs: Dict[Tuple[str, object], List[float]] = {}
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if line.name == "XLA Ops":
                    out.ops.setdefault(plane.name, []).extend(
                        (op_name(n), s, t) for n, s, t in evs)
                else:
                    out.modules.setdefault(plane.name, []).extend(
                        (_MODULE_ID.sub("", n), s, t) for n, s, t in evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns, e)
                       for e in line.events]
                if any(n == BATCH_ANNOTATION for n, _, _, _ in evs):
                    # the caller's thread: its annotations, and what it
                    # did inside them (dispatches, copies, waits)
                    for n, s, t, _ in evs:
                        if n == BATCH_ANNOTATION:
                            out.batches.append((s, t))
                        elif t > s:
                            out.host.append((n, s, t))
                elif line.name.startswith("tf_XLA"):
                    for n, s, t, e in evs:
                        st = _stats(e)
                        if "hlo_module" in st:
                            cpu_ops.append((n, s, t))
                            key = (st["hlo_module"], st.get("run_id"))
                            run = cpu_runs.setdefault(key, [s, t])
                            run[0], run[1] = min(run[0], s), max(run[1], t)
    if not out.ops and cpu_ops:
        out.ops["/host:CPU"] = cpu_ops
        out.modules["/host:CPU"] = [(m, s, t) for (m, _), (s, t)
                                    in cpu_runs.items()]
    out.batches.sort()
    return out


def merged(events: List[Event], lo: float, hi: float):
    """Union of the events' intervals clipped to [lo, hi], as sorted
    disjoint (starts, ends) arrays."""
    if not events:
        return np.zeros(0), np.zeros(0)
    iv = np.array([(s, t) for _, s, t in events], dtype=np.float64)
    iv = np.clip(iv, lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if iv.size == 0:
        return np.zeros(0), np.zeros(0)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), dtype=bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    group_end = np.maximum.reduceat(iv[:, 1], np.flatnonzero(new))
    return starts, group_end


def covered(starts, ends, lo: float, hi: float) -> float:
    """Length of [lo, hi] that the disjoint intervals cover."""
    if len(starts) == 0 or hi <= lo:
        return 0.0
    return float(np.clip(np.minimum(ends, hi) - np.maximum(starts, lo),
                         0, None).sum())


def busy_ns(summary: TraceSummary) -> Dict[str, float]:
    """Per device, the nanoseconds of the window in which it ran an op."""
    lo, hi = summary.window
    out = {}
    for dev, evs in summary.ops.items():
        s, t = merged(evs, lo, hi)
        out[dev] = float((t - s).sum())
    return out


def idle_pct(summary: TraceSummary) -> float:
    """Idle share of the window in %, averaged over the devices."""
    lo, hi = summary.window
    busy = busy_ns(summary)
    return 100.0 * float(np.mean([1.0 - b / (hi - lo) for b in busy.values()]))


def program_ns(summary: TraceSummary, program: str) -> Dict[str, float]:
    """Per device, the nanoseconds of the window spent in executions of
    compiled programs whose name contains ``program``."""
    lo, hi = summary.window
    out = {}
    for dev, evs in summary.modules.items():
        s, t = merged([e for e in evs if program in e[0]], lo, hi)
        out[dev] = float((t - s).sum())
    return out


def program_ms_per_batch(summary: TraceSummary, program: str):
    """Device time of ``program`` per annotated batch in ms, averaged over
    the devices; None when no execution of it is in the window."""
    per_dev = program_ns(summary, program)
    if not summary.batches or not any(per_dev.values()):
        return None
    return 1e-6 * float(np.mean(list(per_dev.values()))) / len(
        summary.batches)


def host_ns_per_batch(summary: TraceSummary) -> List[float]:
    """Per annotated batch, its length minus the part of it in which any
    of the devices ran an op."""
    lo, hi = summary.window
    all_ops = [e for evs in summary.ops.values() for e in evs]
    s, t = merged(all_ops, lo, hi)
    return [(b - a) - covered(s, t, a, b) for a, b in summary.batches]


def leaf_ops(events: List[Event]) -> List[Event]:
    """The events that hold no other: a while loop's event spans the ops
    of its body on the same line, and would count their time twice."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or not (nxt[1] < e[2] and nxt[2] <= e[2]):
            out.append(e)
    return out


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    """The device operations that took most time (summed over devices)
    and the device's idle time by what the host was doing meanwhile."""
    lo, hi = summary.window
    op_time: Dict[str, float] = defaultdict(float)
    for evs in summary.ops.values():
        for name, s, t in leaf_ops(evs):
            op_time[name] += max(0.0, min(t, hi) - max(s, lo))
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]

    s, t = merged([e for evs in summary.ops.values() for e in evs], lo, hi)
    gap_lo = np.concatenate([[lo], t])
    gap_hi = np.concatenate([s, [hi]])
    host = sorted(summary.host, key=lambda e: e[1])
    host_starts = np.array([e[1] for e in host])
    longest = max((e[2] - e[1] for e in host), default=0.0)
    idle: Dict[str, float] = defaultdict(float)
    for a, b in zip(gap_lo, gap_hi):
        if b <= a:
            continue
        first = np.searchsorted(host_starts, a - longest)
        last = np.searchsorted(host_starts, b)
        near = [e for e in host[first:last] if e[2] > a]
        # cut the gap at every host event's edge; each piece goes to the
        # innermost (shortest) event that covers it
        cuts = sorted({a, b} | {x for e in near for x in e[1:] if a < x < b})
        for x, y in zip(cuts[:-1], cuts[1:]):
            mid = (x + y) / 2
            cover = [e for e in near if e[1] <= mid < e[2]]
            if cover:
                name = min(cover, key=lambda e: e[2] - e[1])[0]
            elif any(p <= mid < q for p, q in summary.batches):
                name = "host code in knn_batch"
            else:
                name = "between batches"
            idle[name] += y - x
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[n, v * 1e-9] for n, v in ops],
        "idle_gaps": [[n, float(v) * 1e-9] for n, v in gaps],
    }
