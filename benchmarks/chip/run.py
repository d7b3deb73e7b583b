#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload scan64.k100 --seed 7 \\
        --seconds 30 --trace 0

From the root of a checkout. The cell is found in ``BENCHMARK.json``;
see ``harness.py`` for what a run does. Progress goes to standard error;
the last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number the
comparison with the plain reference counted, beside its limit.

Refuses to run, with a non-zero exit and no result, unless JAX finds a
TPU and at least as many chips as the cell asks for. One process drives
every chip of the cell; it starts no other. JAX's persistent compilation
cache is ``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR``
names another (``repro.compile_cache``).
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))


def require_chips(chips: int):
    """The cell's TPU devices; exits before any work without them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"run.py: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); refusing to run")
    if len(devs) < chips:
        raise SystemExit(f"run.py: the cell asks for {chips} chips, "
                         f"JAX found {len(devs)}")
    return devs[:chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    from repro.compile_cache import enable_compile_cache

    cell = harness.load_cell(ROOT, args.workload)
    devices = require_chips(cell.chips)
    enable_compile_cache(ROOT)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices, T_PROCESS,
                              trace_dir=ROOT / ".bench_trace")
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
