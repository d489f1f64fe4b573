"""Collocation benchmarks: paper Fig 12 (analytic) + the executable path.

Default mode — paper Fig 12: pairwise collocation of synthetic kernels under
priorities.  High-priority kernel throughput (% of isolated) when collocated
with a low-priority kernel, across (execution latency × compute intensity)
grids.  Model: the non-preemptive device admits one low-priority kernel
whenever the high-priority queue idles; the hp kernel then waits for the lp
tail: wait ≈ lp_latency / 2 weighted by lp occupancy (intensity).  Paper
finding: priorities are effective EXCEPT for short hp kernels under long lp
kernels.

``--smoke`` — the executable gap-collocation path (paper §5 end-to-end):
plans VGG-16 on the process devices (forcing 8 host devices when the
process has not already initialized jax), carves the plan into disjoint
fg/bg submeshes, dispatches REAL jitted background training steps
(``repro.train.step.jit_train_step`` on a tiny LM) into the plan's gaps
through the ``Collocator``, and gates on the paper's §5 QoS bound: measured
foreground slowdown ≤ 1.33 with background throughput > 0.  ``--record``
appends the measurement to BENCH_collocation.json.
"""
from __future__ import annotations

import os
import sys

LATENCIES = (50e-6, 200e-6, 1e-3, 5e-3)  # kernel execution latencies
INTENSITIES = (0.25, 1.0)  # lp compute intensity (SM occupancy share)

BENCH_FILE = os.path.join(os.path.dirname(__file__), "..", "BENCH_collocation.json")
QOS_SLOWDOWN_BOUND = 1.33  # paper §5: fg slowdown the QoS loop must hold


def hp_throughput(hp_lat: float, lp_lat: float, lp_intensity: float) -> float:
    """Fraction of isolated throughput for the high-priority kernel."""
    # expected blocking per hp kernel: probability the device just accepted a
    # lp kernel (grows with lp occupancy) × residual lp time
    p_block = 0.5 * lp_intensity
    wait = p_block * 0.5 * lp_lat
    return hp_lat / (hp_lat + wait)


def run():
    rows = []
    worst = 1.0
    cells = []
    for hp in LATENCIES:
        for lp in LATENCIES:
            for inten in INTENSITIES:
                f = hp_throughput(hp, lp, inten)
                worst = min(worst, f)
                cells.append(f"hp{hp*1e6:.0f}us/lp{lp*1e6:.0f}us/i{inten}:{f*100:.0f}%")
    short_hp_long_lp = hp_throughput(LATENCIES[0], LATENCIES[-1], 1.0)
    long_hp_short_lp = hp_throughput(LATENCIES[-1], LATENCIES[0], 1.0)
    rows.append({
        "name": "fig12/collocation_matrix",
        "us_per_call": 0.0,
        "derived": (f"worst={worst*100:.0f}% "
                    f"short-hp-long-lp={short_hp_long_lp*100:.0f}% "
                    f"long-hp-short-lp={long_hp_short_lp*100:.0f}% "
                    "(paper: priorities fail only for short hp under long lp)"),
    })
    rows.append({
        "name": "fig12/full_grid",
        "us_per_call": 0.0,
        "derived": " ".join(cells),
    })
    return rows


# ---------------------------------------------------------------------------
# Executable path (--smoke): real jitted bg steps into real plan gaps
# ---------------------------------------------------------------------------


def smoke(record: bool = False, iterations: int = 4) -> int:
    """Run the executable collocation path end-to-end; returns a shell exit
    code — nonzero when the measured fg slowdown breaks the paper's §5 QoS
    bound (1.33×) or background throughput is zero."""
    if "jax" not in sys.modules:
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
        )
    import jax

    import _bench_util

    from repro.configs.vgg16 import CONFIG as VCFG
    from repro.core.costmodel import A100
    from repro.core.multiplex import Collocator, MultiplexConfig
    from repro.core.plan import pow2_floor
    from repro.core.planner import plan
    from repro.models.graph import build_vgg_graph
    from repro.configs import get_config
    from repro.train.step import bg_step_factory

    n_dev = len(jax.devices())
    if n_dev < 2:
        print("smoke needs >1 device (set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
              file=sys.stderr)
        return 1
    G = pow2_floor(n_dev)
    fg_plan = plan(build_vgg_graph(VCFG, 32), G, amp_limit=1.5, hw=A100)
    assert fg_plan.gaps(), "smoke plan has no gaps to collocate into"
    col = Collocator(fg_plan, MultiplexConfig(max_inflight=2))

    # submesh invariants: every bg submesh is device-disjoint from the
    # stage's fg submesh (the executable-collocation correctness condition)
    split = col.submeshes()
    fg_devs = list(split.fg_mesh.devices.flat)
    for si, (rng, mesh) in split.bg.items():
        lo, hi = split.stage_fg_range[si]
        stage_fg_ids = {d.id for d in fg_devs[lo:hi]}
        bg_ids = {d.id for d in mesh.devices.flat}
        assert not (stage_fg_ids & bg_ids), (si, stage_fg_ids, bg_ids)

    # fg stages: compute sized proportionally to the planned stage duration
    # (shared with bench_cluster_throughput so the two smokes are comparable)
    make_fg_stage_fn = _bench_util.proportional_fg_stage_fn(fg_plan)

    # bg: an actual jitted LM training step, sharded on the gap submesh
    res = col.run_executable(
        make_fg_stage_fn, bg_step_factory(get_config("qwen2-1.5b").reduced(),
                                          batch=4, seq=8),
        iterations=iterations,
    )
    ok = res.fg_slowdown <= QOS_SLOWDOWN_BOUND and res.bg_steps_per_iter > 0
    print(f"smoke collocation vgg16@{G} on {n_dev} host devices: {res.row()} "
          f"fg_iter={res.fg_iter_time*1e3:.1f}ms "
          f"(iso {res.fg_iter_time_isolated*1e3:.1f}ms) "
          f"gate<= {QOS_SLOWDOWN_BOUND}: {'ok' if ok else 'FAIL'}")

    if record:
        entry = {
            "date": _bench_util.utc_now_iso(),
            "commit": _bench_util.git_sha(),
            "config": f"vgg16@{G}-bg-qwen2-smoke",
            "devices": n_dev,
            "iterations": iterations,
            "fg_iter_time_s": res.fg_iter_time,
            "fg_iter_time_isolated_s": res.fg_iter_time_isolated,
            "fg_slowdown": res.fg_slowdown,
            "bg_steps_per_iter": res.bg_steps_per_iter,
            "bg_throughput_steps_per_s": res.bg_throughput,
            # every collocated iteration as (wall_s, bg_steps): the learning
            # phase may run slower than the gated steady state — keep the
            # tradeoff visible in the record
            "collocated_iters": [[t, n] for t, n in res.iter_details],
            "banned_ops": list(res.banned_ops),
            "qos_bound": QOS_SLOWDOWN_BOUND,
            "gate_ok": ok,
        }
        _bench_util.append_record(BENCH_FILE, entry)

    if not ok:
        print(
            f"FAIL: fg_slowdown={res.fg_slowdown:.3f} "
            f"(bound {QOS_SLOWDOWN_BOUND}) "
            f"bg_steps/iter={res.bg_steps_per_iter:.1f}",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="executable collocation on forced host devices (CI)")
    ap.add_argument("--record", action="store_true",
                    help="with --smoke: append to BENCH_collocation.json")
    ap.add_argument("--iterations", type=int, default=4)
    args = ap.parse_args()
    if args.smoke:
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
        sys.exit(smoke(record=args.record, iterations=args.iterations))
    else:
        for r in run():
            print(r["name"], "::", r["derived"])
