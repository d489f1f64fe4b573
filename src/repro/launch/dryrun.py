import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch × shape × mesh) cell.

The two lines above run before ANY other import (jax locks the device count
on first init).  For each cell we build the production mesh, jit the real
step function (train_step incl. optimizer for train shapes; full-sequence
forward for prefill; decode_step for decode shapes), lower against
ShapeDtypeStruct inputs (zero allocation), compile, and record:

  - memory_analysis()    (proves the cell fits per-device HBM)
  - cost_analysis()      (FLOPs / bytes for §Roofline)
  - collective bytes     (parsed from the post-SPMD HLO, scan-aware)

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--out results.jsonl]

--all runs each cell in a fresh subprocess (compile caches don't accumulate;
one failing cell doesn't kill the sweep) and appends JSONL records.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback


def _record(arch: str, shape_name: str, mesh_kind: str, rules_override=None,
            cfg_override=None) -> dict:
    import dataclasses

    import jax
    from repro.configs import SHAPES, get_config, shapes_for
    from repro.dist.sharding import RuleReport, sharding_rules
    from repro.launch.hlo_analysis import analyze_collectives
    from repro.launch.mesh import make_production_mesh
    from repro.models import get_model
    from repro.models.api import input_specs
    from repro.optim.optimizer import make_optimizer
    from repro.train.state import abstract_state
    from repro.train.step import jit_decode_step, jit_forward, jit_train_step

    cfg = get_config(arch)
    if cfg_override:
        cfg = dataclasses.replace(cfg, **cfg_override)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.devices.size
    report = RuleReport()
    rules = sharding_rules(cfg, mesh, shape)
    if rules_override:
        rules.update({k: tuple(v) for k, v in rules_override.items()})
    api = get_model(cfg)
    specs = input_specs(cfg, shape)

    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            opt = make_optimizer(cfg)
            fn, st_sh, bt_sh = jit_train_step(api, opt, mesh, shape, rules=rules,
                                              report=report)
            args = (abstract_state(api, opt), specs)
        elif shape.kind == "prefill":
            fn, p_sh, bt_sh = jit_forward(api, mesh, shape, rules=rules, report=report)
            from repro.models.layers import abstract_params

            args = (abstract_params(api.schema), specs)
        else:  # decode
            fn, p_sh, bt_sh = jit_decode_step(api, mesh, shape, rules=rules,
                                              report=report)
            from repro.models.layers import abstract_params

            args = (abstract_params(api.schema), specs)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    trip = max(cfg.num_layers, cfg.num_encoder_layers)
    from repro.launch.hlo_analysis import analyze_hlo

    hc = analyze_hlo(compiled.as_text(), default_trip_count=trip)
    coll, diag = dict(hc.collectives), hc.diag

    print(f"=== {arch} × {shape_name} × {mesh_kind} ({n_dev} chips) ===")
    print(f"memory_analysis: args={mem.argument_size_in_bytes/1e9:.3f} GB "
          f"out={mem.output_size_in_bytes/1e9:.3f} GB "
          f"temp={mem.temp_size_in_bytes/1e9:.3f} GB per device")
    print(f"cost_analysis: flops={cost.get('flops', 0):.3e} "
          f"bytes={cost.get('bytes accessed', 0):.3e}")
    print(f"collectives (per-device bytes/step): "
          f"{ {k: f'{v:.3e}' for k, v in coll.items()} }")

    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "n_devices": int(n_dev),
        "kind": shape.kind,
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "peak_bytes": int(mem.argument_size_in_bytes + mem.temp_size_in_bytes
                          + mem.output_size_in_bytes),
        "hlo_flops_raw": float(cost.get("flops", 0.0)),
        "hlo_bytes_raw": float(cost.get("bytes accessed", 0.0)),
        "hlo_dot_flops": float(hc.dot_flops),  # trip-aware, per device
        "hlo_bytes_written": float(hc.bytes_written),  # trip-aware, per device
        "collective_bytes": {k: float(v) for k, v in coll.items()},
        "collective_bytes_total": float(sum(coll.values())),
        "collective_diag": diag,
        "scan_trip_count": trip,
        "n_params": int(cfg.n_params()),
        "dropped_rules": [list(map(str, d)) for d in report.dropped],
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "rules_override": rules_override or {},
        "cfg_override": cfg_override or {},
    }


def run_cell(arch, shape_name, mesh_kind, out_path=None, rules_override=None,
             cfg_override=None):
    rec = _record(arch, shape_name, mesh_kind, rules_override, cfg_override)
    if out_path:
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
    return rec


def enumerate_cells(mesh_kinds):
    from repro.configs import ASSIGNED_ARCHS, get_config, shapes_for

    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape in shapes_for(cfg):
            for mk in mesh_kinds:
                yield arch, shape.name, mk


def _done_cells(out_path):
    done = set()
    try:
        with open(out_path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    done.add((r["arch"], r["shape"], r["mesh"]))
                except Exception:
                    pass
    except FileNotFoundError:
        pass
    return done


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/results/dryrun.jsonl")
    ap.add_argument("--timeout", type=int, default=3600)
    ap.add_argument("--rules-override", default=None,
                    help="JSON dict of logical-axis rule overrides (hillclimb)")
    ap.add_argument("--cfg-override", default=None,
                    help="JSON dict of ModelConfig field overrides (hillclimb)")
    args = ap.parse_args()

    if args.all:
        mesh_kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        done = _done_cells(args.out)
        cells = [c for c in enumerate_cells(mesh_kinds) if c not in done]
        print(f"dry-run sweep: {len(cells)} cells to go ({len(done)} done)")
        failures = []
        for arch, shape, mk in cells:
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                   "--shape", shape, "--mesh", mk, "--out", args.out]
            print(f"--> {arch} × {shape} × {mk}", flush=True)
            try:
                r = subprocess.run(cmd, timeout=args.timeout, capture_output=True,
                                   text=True)
                if r.returncode != 0:
                    failures.append((arch, shape, mk, r.stderr[-2000:]))
                    print(f"FAILED: {arch} × {shape} × {mk}\n{r.stderr[-2000:]}",
                          flush=True)
                else:
                    print(r.stdout.strip().splitlines()[-3] if r.stdout.strip() else "",
                          flush=True)
            except subprocess.TimeoutExpired:
                failures.append((arch, shape, mk, "timeout"))
                print(f"TIMEOUT: {arch} × {shape} × {mk}", flush=True)
        print(f"sweep done: {len(cells) - len(failures)} ok, {len(failures)} failed")
        for f in failures:
            print("FAIL:", f[0], f[1], f[2])
        sys.exit(1 if failures else 0)
    else:
        assert args.arch and args.shape
        override = json.loads(args.rules_override) if args.rules_override else None
        cfg_over = json.loads(args.cfg_override) if args.cfg_override else None
        run_cell(args.arch, args.shape, args.mesh, args.out, override, cfg_over)


if __name__ == "__main__":
    main()
