"""Readings that the limits of a cell's comparison are set from.

    python3 benchmarks/chip/calibrate.py --workload qwen2-l4.fg \
        --program-seeds 101,102,...  --control-seeds 201,202,203
    python3 benchmarks/chip/calibrate.py --workload qwen2-l28.tp2x2 \
        --faults half_batch,exchange_left_out --fault-seeds 301,302,303

In one process, on the cell's chips and at its own sizes: for each program
seed, one short run of the cell (the program's first steps against the
reference, as every run compares them); for each fault named and each
fault seed, the same with that fault planted in the foreground's step
(``chipbench.faults``); for each control seed, the reference computed with
float8 matmul operands (the control: one step below the configuration's
bfloat16) against the float32 reference.  Prints one JSON line per
reading, then for each number the largest sound program reading (the
lower end of its limit) and, by control and by fault, the smallest reading
(an upper end where it reads three times the lower or more for the
control, ten times for a fault).  Each control or fault reading is also
held to the cell's limits as they stand, and marked ``"correct": false``
where it fails one of them, as every control has to.  The runs go seed by
seed, and each seed's float32 reference runs once for all of them.  The
benchmark's own runs never run the control or a fault.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def control_readings(plan, role: int) -> dict:
    """Numbers compared for ``role`` with the fp8 reference in the
    program's place."""
    from chipbench import check, harness

    ctl = harness.reference_readings(plan, role, prec="fp8", keep_m=True)
    ref = harness.reference_readings(plan, role, other_m=ctl.pop("m0"))
    return {k: v for k, (v, _) in check.compare(
        ctl, ref, "" if role == 0 else f"bg{role - 1}.").items()}


def within_limits(readings: dict, limits: dict) -> bool:
    return all(v <= limits[k] for k, v in readings.items())


def once_a_seed(reference_readings):
    """``harness.reference_readings`` with the f32 reference of a (seed,
    role) run once and kept (the last seed's only): the program's runs of
    one seed, sound or with a fault planted, and the control are compared
    with the same three reference steps, which are the same whichever run
    asks (the two first gradients' difference is summed on one chip, so it
    agrees with the sharded sum to rounding)."""
    import jax

    from chipbench import reference

    kept = {}

    def readings(plan, role, prec="f32", other_m=None, keep_m=False):
        if prec != "f32":
            return reference_readings(plan, role, prec, other_m, keep_m)
        key = (plan.seed, role)
        if key not in kept:
            kept.clear()
            kept[key] = reference_readings(plan, role, keep_m=True)
        out = dict(kept[key])
        m = out.pop("m0")
        if other_m is not None:  # as ``reference.run`` takes it
            scale = 1.0 / (1.0 - plan.role_train(role)[0]["b1"])
            out["grad_diff_norms"] = {
                p: scale * float(reference._diff_norm(a, b))
                for p, a, b in zip(out["grad_norms"], jax.tree.leaves(m),
                                   jax.tree.leaves(other_m))}
        if keep_m:
            out["m0"] = m
        return out

    return readings


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--faults", default="",
                    help="plant each of these faults (chipbench.faults."
                         "FAULTS) in turn in the fault seeds' foreground step")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args()
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from chipbench import faults, harness, spec

    seeds = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    cell = spec.cell(args.workload)
    tr = cell.traffic
    devs = jax.devices()
    program, control = seeds(args.program_seeds), seeds(args.control_seeds)
    planted = seeds(args.fault_seeds)
    lower, upper = {}, {}
    harness.reference_readings = once_a_seed(harness.reference_readings)
    # seed by seed, so that each seed's f32 reference runs once
    for seed in dict.fromkeys(program + planted + control):
        runs = ([None] if seed in program else []) + (
            [f for f in args.faults.split(",") if f] if seed in planted
            else [])
        for fault in runs:
            with (faults.planted(fault, cell.config["num_hidden_layers"],
                                 tr["data"], tr["model"])
                  if fault else contextlib.nullcontext()):
                out = harness.measure(args.workload, seed, args.seconds,
                                      False, time.perf_counter())
            print(json.dumps({"program_seed": seed, "fault": fault,
                              "correct": out["correct"],
                              "checks": out["checks"],
                              "at_s": time.perf_counter() - T_START}),
                  flush=True)
            for k, (v, _) in out["checks"].items():
                if fault:
                    upper.setdefault(fault, {})
                    upper[fault][k] = min(upper[fault].get(k, v), v)
                else:
                    lower[k] = max(lower.get(k, v), v)
        if seed not in control:
            continue
        plan = harness.Plan(config=cell.config, traffic=tr, seed=seed,
                            seconds=0, trace=False, devices=devs[:cell.chips])
        for role in range(1 + len(tr["tenants"])):
            got = control_readings(plan, role)
            print(json.dumps({"control_seed": seed, "role": role,
                              "readings": got,
                              "correct": within_limits(got, cell.limits),
                              "at_s": time.perf_counter() - T_START}),
                  flush=True)
            upper.setdefault("fp8", {})
            for k, v in got.items():
                upper["fp8"][k] = min(upper["fp8"].get(k, v), v)
    print(json.dumps({"lower": lower, "upper": upper}), flush=True)


if __name__ == "__main__":
    main()
