import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x shape) cell on
the production meshes, with allocation-free ShapeDtypeStruct inputs.

MUST be run as its own process (python -m repro.launch.dryrun ...): the two
lines above pin 512 placeholder devices BEFORE any jax import — smoke tests
and benches must never see them.

Per cell this produces:
  - proof of shardability: .lower().compile() succeeds on the 16x16
    single-pod mesh and the 2x16x16 multi-pod mesh,
  - compiled.memory_analysis(): per-device bytes (feasibility),
  - compiled.cost_analysis(): XLA's raw counters (recorded; while-bodies
    are counted once there — see roofline.hlo_parse for the corrected
    numbers),
  - the parsed, trip-count-scaled roofline terms (roofline.analysis).

Cells:   10 assigned archs x their 4 shapes (minus recorded long_500k
skips) + the paper's retrieval_step (sharded angular scan) as its own cell.

Usage:
  python -m repro.launch.dryrun --arch llama3_8b --shape train_4k \
      --mesh single --out artifacts/dryrun
  python -m repro.launch.dryrun --all --mesh both --out artifacts/dryrun
  python -m repro.launch.dryrun --report artifacts/dryrun   # md table
"""

import argparse
import json
import subprocess
import sys
import time
import traceback


def _cell(arch: str, shape_name: str, mesh_name: str, out_dir: str,
          save_hlo: bool, rules_json: str = "", opt: str = "f32",
          cfg_overrides: str = "", opt_rules_json: str = "",
          profile: str = "baseline") -> dict:
    """Lower+compile one cell in THIS process. Returns the report dict."""
    import jax

    from repro.configs import get_config
    from repro.launch.mesh import make_production_mesh
    from repro.models.common import SHAPES, shape_applicable
    from repro.optim import OptimConfig
    from repro.roofline import analyze, parse_hlo_costs
    from repro.train.step import TrainConfig, make_serve_step, make_train_step

    t_start = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    chips = mesh.size

    if arch == "retrieval":
        rep = _retrieval_cell(mesh, mesh_name, chips)
    else:
        cfg = get_config(arch)
        if profile == "optimized":
            from repro.configs.profiles import (
                optimized_opt_rules,
                optimized_overrides,
            )

            cfg = cfg.replace(**optimized_overrides(arch))
            if not opt_rules_json:
                opt_rules_json = json.dumps(
                    {"embed": list(optimized_opt_rules()["embed"])}
                )
        if cfg_overrides:
            cfg = cfg.replace(**json.loads(cfg_overrides))
        shape = SHAPES[shape_name]
        ok, why = shape_applicable(cfg, shape)
        if not ok:
            return {
                "arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skip", "reason": why,
            }
        rules = json.loads(rules_json) if rules_json else None
        if rules:
            rules = {k: tuple(v) if isinstance(v, list) else v
                     for k, v in rules.items()}
        log: list = []
        ocfg = OptimConfig(quantized_moments=(opt == "int8"))
        opt_rules = None
        if opt_rules_json:
            from repro.models.sharding import DEFAULT_RULES

            opt_rules = dict(DEFAULT_RULES)
            opt_rules.update({
                k: tuple(v) if isinstance(v, list) else v
                for k, v in json.loads(opt_rules_json).items()
            })
        if shape.kind == "train":
            built = make_train_step(
                cfg, ocfg, TrainConfig(), mesh=mesh, rules=rules,
                log=log, opt_rules=opt_rules,
            )
            lowered = built["lower_for"](shape)
        elif shape.kind == "prefill":
            built = make_serve_step(cfg, mesh=mesh, rules=rules, log=log)
            lowered = built["lower_prefill"](shape)
        else:  # decode
            built = make_serve_step(cfg, mesh=mesh, rules=rules, log=log)
            lowered = built["lower_for"](shape)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        hlo = compiled.as_text()
        costs = parse_hlo_costs(hlo)
        per_dev_bytes = (
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes
        )
        report = analyze(
            cfg, shape, mesh_name, chips, hlo,
            bytes_per_device=per_dev_bytes, costs=costs,
        )
        rep = json.loads(report.to_json())
        rep.update(
            status="ok",
            xla_flops_raw=float(ca.get("flops", 0.0)),
            xla_bytes_raw=float(ca.get("bytes accessed", 0.0)),
            memory_analysis={
                "argument": ma.argument_size_in_bytes,
                "output": ma.output_size_in_bytes,
                "temp": ma.temp_size_in_bytes,
                "alias": ma.alias_size_in_bytes,
            },
            sharding_log=log[:200],
            collective_op_counts=costs.collective_ops,
        )
        if save_hlo:
            os.makedirs(out_dir, exist_ok=True)
            with open(
                os.path.join(out_dir, f"{mesh_name}_{arch}_{shape_name}.hlo.txt"),
                "w",
            ) as f:
                f.write(hlo)
    rep["compile_wall_s"] = round(time.time() - t_start, 2)
    return rep


def _retrieval_cell(mesh, mesh_name: str, chips: int) -> dict:
    """The paper's technique on the mesh: sharded angular scan + top-K."""
    import jax
    import jax.numpy as jnp

    from repro.shard import make_retrieval_step
    from repro.roofline.hlo_parse import parse_hlo_costs

    # 2^30 codes x 128 bits (SIFT-1B class), sharded over pod+data axes
    N, W, B, K = 1 << 30, 4, 256, 100
    step, in_shardings = make_retrieval_step(mesh, K)
    q = jax.ShapeDtypeStruct((B, W), jnp.uint32, sharding=in_shardings[0])
    db = jax.ShapeDtypeStruct((N, W), jnp.uint32, sharding=in_shardings[1])
    lowered = jax.jit(step, in_shardings=in_shardings).lower(q, db)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    costs = parse_hlo_costs(hlo)
    hbm_s = costs.hbm_bytes / 819e9
    coll_s = costs.total_collective_bytes / 50e9
    comp_s = costs.flops / 197e12
    terms = {"compute": comp_s, "memory": hbm_s, "collective": coll_s}
    return {
        "arch": "retrieval", "shape": f"scan_n{N}_k{K}", "mesh": mesh_name,
        "chips": chips, "status": "ok",
        "device_flops": costs.flops,
        "device_hbm_bytes": costs.hbm_bytes,
        "device_collective_bytes": costs.total_collective_bytes,
        "collective_breakdown": costs.collective_bytes,
        "compute_s": comp_s, "memory_s": hbm_s, "collective_s": coll_s,
        "dominant": max(terms, key=terms.get),
        "bytes_per_device": ma.argument_size_in_bytes
        + ma.temp_size_in_bytes,
        "note": "paper technique: sharded XOR/popcount scan + all-gather(K) merge",
    }


# --------------------------------------------------------------- sweeping
def _all_cells():
    from repro.configs import ARCH_IDS
    from repro.models.common import SHAPES

    cells = [(a, s) for a in ARCH_IDS for s in SHAPES]
    cells.append(("retrieval", "scan"))
    return cells


def run_all(mesh_names, out_dir: str, save_hlo: bool, jobs: int = 2,
            profile: str = "baseline"):
    """Sweep every cell, one subprocess per cell (isolation: a failing or
    OOMing cell never kills the sweep; memory is returned to the OS)."""
    os.makedirs(out_dir, exist_ok=True)
    procs = []
    todo = [
        (arch, shape, mesh)
        for mesh in mesh_names
        for arch, shape in _all_cells()
    ]
    results = {}

    def launch(arch, shape, mesh):
        out_file = os.path.join(out_dir, f"{mesh}_{arch}_{shape}.json")
        if os.path.exists(out_file):
            return None
        cmd = [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", arch, "--shape", shape, "--mesh", mesh,
            "--out", out_dir, "--profile", profile,
        ]
        if save_hlo:
            cmd.append("--save-hlo")
        return subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT
        )

    running = []
    while todo or running:
        while todo and len(running) < jobs:
            arch, shape, mesh = todo.pop(0)
            p = launch(arch, shape, mesh)
            if p is not None:
                running.append((arch, shape, mesh, p, time.time()))
                print(f"[launch] {mesh}/{arch}/{shape}")
        still = []
        for arch, shape, mesh, p, t0 in running:
            if p.poll() is None:
                if time.time() - t0 > 1800:
                    p.kill()
                    print(f"[timeout] {mesh}/{arch}/{shape}")
                else:
                    still.append((arch, shape, mesh, p, t0))
            else:
                dt = time.time() - t0
                tag = "ok" if p.returncode == 0 else f"FAIL rc={p.returncode}"
                print(f"[done {dt:5.1f}s] {mesh}/{arch}/{shape}: {tag}")
                if p.returncode != 0:
                    out = p.stdout.read().decode(errors="replace")
                    with open(
                        os.path.join(out_dir, f"{mesh}_{arch}_{shape}.err"),
                        "w",
                    ) as f:
                        f.write(out)
        running = still
        time.sleep(1.0)
    return results


# ---------------------------------------------------------------- report
def report(out_dir: str):
    rows = []
    for name in sorted(os.listdir(out_dir)):
        if not name.endswith(".json"):
            continue
        with open(os.path.join(out_dir, name)) as f:
            rows.append(json.load(f))
    ok = [r for r in rows if r.get("status") == "ok"]
    skipped = [r for r in rows if r.get("status") == "skip"]
    print(f"| arch | shape | mesh | dominant | compute_s | memory_s | "
          f"collective_s | step_s | useful | GiB/dev |")
    print("|---|---|---|---|---|---|---|---|---|---|")
    for r in sorted(ok, key=lambda r: (r["mesh"], r["arch"], r["shape"])):
        print(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | {r['dominant']} "
            f"| {r.get('compute_s', 0):.4f} | {r.get('memory_s', 0):.4f} "
            f"| {r.get('collective_s', 0):.4f} "
            f"| {r.get('step_s', max(r.get('compute_s',0), r.get('memory_s',0), r.get('collective_s',0))):.4f} "
            f"| {r.get('useful_ratio', 0):.3f} "
            f"| {r.get('bytes_per_device', 0)/2**30:.2f} |"
        )
    for r in skipped:
        print(f"| {r['arch']} | {r['shape']} | {r['mesh']} | SKIP: "
              f"{r['reason'][:60]} | | | | | | |")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--rules-json", default="", help="sharding rule overrides")
    ap.add_argument("--opt", default="f32", choices=["f32", "int8"],
                    help="optimizer moment precision (train shapes)")
    ap.add_argument("--cfg-json", default="",
                    help="ArchConfig field overrides, e.g. "
                         '\'{"remat": "dots", "kv_chunk": 4096}\'')
    ap.add_argument("--opt-rules-json", default="",
                    help="optimizer-state-only sharding rule overrides "
                         "(ZeRO-style), merged over the defaults")
    ap.add_argument("--profile", default="baseline",
                    choices=["baseline", "optimized"],
                    help="baseline = paper-faithful configs; optimized = "
                         "the §Perf-winning overrides (configs/profiles.py)")
    ap.add_argument("--report", metavar="DIR")
    args = ap.parse_args()

    if args.report:
        report(args.report)
        return
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        run_all(meshes, args.out, args.save_hlo, args.jobs, args.profile)
        return
    assert args.arch and (args.shape or args.arch == "retrieval")
    for mesh in meshes:
        try:
            rep = _cell(
                args.arch, args.shape or "scan", mesh, args.out,
                args.save_hlo, args.rules_json, args.opt, args.cfg_json,
                args.opt_rules_json, args.profile,
            )
        except Exception:
            rep = {
                "arch": args.arch, "shape": args.shape, "mesh": mesh,
                "status": "error", "traceback": traceback.format_exc(),
            }
        os.makedirs(args.out, exist_ok=True)
        out_file = os.path.join(
            args.out, f"{mesh}_{args.arch}_{args.shape or 'scan'}.json"
        )
        with open(out_file, "w") as f:
            json.dump(rep, f, indent=1)
        brief = {
            k: rep.get(k)
            for k in ("arch", "shape", "mesh", "status", "dominant",
                      "compute_s", "memory_s", "collective_s",
                      "useful_ratio", "compile_wall_s", "reason")
            if k in rep
        }
        print(json.dumps(brief))
        if rep["status"] == "error":
            print(rep["traceback"], file=sys.stderr)
            sys.exit(1)


if __name__ == "__main__":
    main()
