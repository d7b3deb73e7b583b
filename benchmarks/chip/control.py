#!/usr/bin/env python3
"""Read the control of a cell on the chip: the plain reference put in the
program's place, computed one precision lower (float32 sims), driven
through the cell's own window and comparison on several seeds in one
process. Each seed prints one JSON line with the numbers compared; every
one of them has to come out not correct.

    python3 benchmarks/chip/control.py --workload scan64.k100 \\
        --seeds 11 12 13 --seconds 5

The control's engine runs on the first chip only, so a cell of four
chips would read its control on one, over the cell's whole corpus. The
benchmark's own runs never run this.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    import harness
    from repro.compile_cache import enable_compile_cache

    cell = harness.load_cell(run.ROOT, args.workload)
    devices = run.require_chips(1)
    enable_compile_cache(run.ROOT)
    ref = harness.load_module(cell.bench_dir / "references"
                              / f"{cell.config['reference']}.py")
    refused = 0
    for seed in args.seeds:
        res = harness.run_cell(
            cell, seed, args.seconds, False, devices,
            time.perf_counter(),
            engine_factory=lambda c, db, devs: ref.ControlEngine(
                db, c.p, devs[0]))
        refused += not res["correct"]
        print(json.dumps({"control": args.workload, "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"]}), flush=True)
    print(json.dumps({"control": args.workload, "seeds": len(args.seeds),
                      "refused": refused,
                      "seconds": time.perf_counter() - T_PROCESS}))
    return 0 if refused == len(args.seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
