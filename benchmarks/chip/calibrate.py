"""Readings that the limits of a cell's comparison are set from.

    python3 benchmarks/chip/calibrate.py --workload qwen2-l4.fg \
        --program-seeds 101,102,...  --control-seeds 201,202,203
    python3 benchmarks/chip/calibrate.py --workload qwen2-l28.fsdp4 \
        --fault half_batch --program-seeds 301,302,303

In one process, on the cell's chips and at its own sizes: for each program
seed, one short run of the cell (the program's first steps against the
reference, as every run compares them); for each control seed, the
reference computed with float8 matmul operands (the control: one step
below the configuration's bfloat16) against the float32 reference.  Prints
one JSON line per reading, then for each number the largest program
reading (the lower end of its limit) and the smallest control reading (the
upper end).  Each control reading is also held to the cell's limits as
they stand, and marked ``"correct": false`` where it fails one of them, as
every control has to.  With ``--fault``, the program seeds run with that
fault planted in the foreground's step (``chipbench.faults``), and the
summary gives each number's smallest reading under the fault (an upper end
where it reads ten times the lower or more).  The benchmark's own runs
never run the control or a fault.
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


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--fault", default=None,
                    help="plant this fault (chipbench.faults.FAULTS) in "
                         "the program seeds' foreground step")
    args = ap.parse_args()
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from chipbench import faults, harness, spec

    seeds = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    cell = spec.cell(args.workload)
    lower, upper = {}, {}
    planted = (faults.planted(args.fault, cell.config["num_hidden_layers"])
               if args.fault else contextlib.nullcontext())
    with planted:
        for seed in seeds(args.program_seeds):
            out = harness.measure(args.workload, seed, args.seconds, False,
                                  time.perf_counter())
            print(json.dumps({"program_seed": seed, "fault": args.fault,
                              "correct": out["correct"],
                              "checks": out["checks"]}), flush=True)
            for k, (v, _) in out["checks"].items():
                if args.fault:
                    upper[k] = min(upper.get(k, v), v)
                else:
                    lower[k] = max(lower.get(k, v), v)
    devs = jax.devices()
    for seed in seeds(args.control_seeds):
        plan = harness.Plan(config=cell.config, traffic=cell.traffic,
                            seed=seed, seconds=0, trace=False,
                            devices=devs[:cell.chips])
        for role in range(1 + len(cell.traffic["tenants"])):
            got = control_readings(plan, role)
            print(json.dumps({"control_seed": seed, "role": role,
                              "readings": got,
                              "correct": within_limits(got, cell.limits)}),
                  flush=True)
            for k, v in got.items():
                upper[k] = min(upper.get(k, v), v)
    print(json.dumps({"lower": lower, "upper": upper}), flush=True)


if __name__ == "__main__":
    main()
