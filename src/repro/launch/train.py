"""Training entrypoint.

  python -m repro.launch.train --arch qwen2-1.5b [--layers N] [--steps N]
      [--lr LR] [--batch N --seq N] [--data N --model N] [--ckpt-dir DIR]
      [--bg-arch qwen2-1.5b [--bg-arch minicpm-2b ...]] [--smoke]

Trains the registered config at its published widths; ``--layers`` cuts
its depth, and is the only cut.  ``--smoke`` trains the arch's reduced CPU
toy instead.  --bg-arch (repeatable) enables DeepPool multiplexing: each
background job's steps are paced into the foreground plan's gaps on its own
disjoint submesh — the first --bg-arch is the highest-priority tenant and
gets the largest chunk.  Where no device is left over for a tenant, it
runs on the foreground's own devices (same-device).

``run(args)`` is the in-process entry (``chip_smoke.py`` calls it with
``build_parser().parse_args([...])``); ``main()`` adds the persistent
compile cache and the command line.
"""
from __future__ import annotations

import argparse
import itertools

STRAGGLERS_SHOWN = 8  # straggler steps printed after the run's summary


def _bg_submeshes(fg_devices: int, amp_limit: float, hw, cfg, n: int):
    """Per-tenant plan-gap submeshes disjoint from the fg training mesh.

    The production plan assumes 256 devices, so the foreground graph is
    re-planned at the host device count and its per-stage free device
    ranges — clipped to clear the fg mesh's prefix [0, fg_devices) — are
    packed into up to ``n`` disjoint chunks (``pack_ranges``, largest chunk
    to the first --bg-arch).  Falls back to the raw spare devices when the
    host plan leaves no usable gap.

    Returns (meshes, dropped): ``meshes`` has ``n`` entries where tenants
    beyond the packable chunk count get None (plain same-device jit
    fallback), and ``dropped`` lists those tenant indices explicitly — the
    caller must surface them (log + CollocationResult.rejected_tenants),
    never silently vanish a requested tenant.
    """
    import jax

    from repro.configs import TRAIN_4K
    from repro.core.plan import pack_ranges
    from repro.core.planner import plan as make_plan
    from repro.launch.mesh import submesh_from_range
    from repro.models.graph import build_lm_graph

    n_dev = len(jax.devices())
    if n_dev <= fg_devices:
        return [None] * n, list(range(n))
    host_plan = make_plan(build_lm_graph(cfg, TRAIN_4K), n_dev, amp_limit, hw)
    free = []
    for si in range(len(host_plan.stages())):
        for lo, hi in host_plan.free_device_ranges(si):
            lo = max(lo, fg_devices)
            if hi - lo > 0:
                free.append((lo, hi))
    if not free:
        free = [(fg_devices, n_dev)]
    chunks = pack_ranges(free, n)
    meshes = [submesh_from_range(lo, hi) for lo, hi in chunks]
    dropped = list(range(len(meshes), n))
    return meshes + [None] * (n - len(meshes)), dropped


def _register_bg_jobs(coord, archs, meshes, fg_mesh, *, smoke=False,
                      layers=None):
    """Register each --bg-arch as a background Job WITH its step factory.

    The factory (not just the built step fn) goes through
    ``Job.step_fn_factory`` — ``background_tenants()`` rosters only jobs
    carrying a factory, so registering bare ``Job(..., [])`` shells (the
    old behavior) made coordinator-driven ``collocate()``/admission
    silently see zero tenants.  The factory's ``signature`` feeds the
    executable-cache key, scoping compiled steps per (arch, batch, seed).

    Every tenant trains ``run_config(arch, smoke=, layers=)`` through
    ``bg_step_factory`` at seq 32 and 2 samples per device.  A tenant with
    a gap submesh trains there; one without (mesh None) trains on
    ``fg_mesh``'s devices (same-device) and gets a factory that ignores the
    mesh argument but still carries a distinct signature.  Returns the
    per-tenant zero-arg bg step fns for the train loop's paced slot, in CLI
    (priority) order, and each tenant's (placement, device ids).
    """
    from repro.configs import run_config
    from repro.core.coordinator import Job
    from repro.train.step import bg_step_factory

    bg_fns, placements = [], []
    for i, (bg_arch, bg_mesh) in enumerate(zip(archs, meshes)):
        factory = bg_step_factory(run_config(bg_arch, smoke=smoke, layers=layers),
                                  seq=32, seed=1 + i, per_device_batch=2)
        placement = "disjoint" if bg_mesh is not None else "same-device"
        mesh = bg_mesh if bg_mesh is not None else fg_mesh
        fn = factory(mesh)
        if bg_mesh is None:
            same_device = lambda mesh, fn=fn: fn  # noqa: E731
            same_device.signature = f"{factory.signature}-samedev"
            factory = same_device
        ids = sorted(d.id for d in mesh.devices.flat)
        print(f"bg tenant {i} ({bg_arch}) {placement} on devices {ids} "
              f"(batch 2/device)")
        bg_fns.append(fn)
        placements.append((placement, ids))
        # register the tenant with the coordinator (priority: CLI order,
        # first --bg-arch highest) so collocate()/re-plans/admission
        # actually roster it
        coord.submit_background(
            Job(f"bg{i}-{bg_arch}", "background", [],
                priority=len(archs) - i, step_fn_factory=factory)
        )
    return bg_fns, placements


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="python -m repro.launch.train")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="train the arch's reduced CPU toy, not its "
                         "published widths")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to N layers (widths stay published)")
    ap.add_argument("--bg-layers", type=int, default=None,
                    help="depth of the --bg-arch tenants (default: --layers)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--lr", type=float, default=None,
                    help="peak learning rate (default: the arch's schedule)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--bg-arch", action="append", default=None,
                    help="background tenant arch; repeat for multiple "
                         "tenants (first = highest priority)")
    ap.add_argument("--amp-limit", type=float, default=2.0)
    ap.add_argument("--hb-timeout", type=float, default=10.0,
                    help="heartbeat timeout (s) before a silent worker is "
                         "declared failed by the live control plane")
    ap.add_argument("--admit-every", type=int, default=5,
                    help="re-sweep tenant admission every N steps "
                         "(continuous admission; 0 disables)")
    return ap


def run(args):
    """Train as ``args`` (from ``build_parser``) says; returns the
    ``TrainReport`` with ``fg_devices`` and ``bg_tenants`` filled in."""
    import dataclasses

    import jax

    from repro.configs import TRAIN_4K, get_config, run_config
    from repro.core.coordinator import ClusterCoordinator, Job
    from repro.launch.mesh import make_mesh
    from repro.models.attention import count_paths
    from repro.models.graph import build_lm_graph
    from repro.models.transformer import count_loss_paths
    from repro.train.loop import TrainConfig, train

    cfg = get_config(args.arch)
    run_cfg = run_config(args.arch, smoke=args.smoke, layers=args.layers)
    shape = dataclasses.replace(
        TRAIN_4K, seq_len=args.seq, global_batch=args.batch, name="cli"
    )
    # the fg mesh takes the first data*model devices, positionally — the
    # same contract ``_bg_submeshes`` carves the tenants' ranges by
    n_fg = args.data * args.model
    mesh = make_mesh(args.data, args.model, devices=jax.devices()[:n_fg])

    # burst-parallel plan for the FULL config (what production would run)
    coord = ClusterCoordinator(256)
    plan = coord.submit_foreground(
        Job(args.arch, "foreground", build_lm_graph(cfg, TRAIN_4K),
            amp_limit=args.amp_limit)
    )
    print(plan.summary())

    bg_fn = None
    placements = []
    if args.bg_arch:
        archs = list(args.bg_arch)
        meshes, dropped = _bg_submeshes(n_fg, args.amp_limit, coord.hw, cfg,
                                        len(archs))
        if dropped:
            # a requested tenant must never vanish silently: say exactly
            # which --bg-arch lost its gap submesh and what happens instead
            print(
                "no gap submesh for bg tenant(s) "
                + ", ".join(f"{i} ({archs[i]})" for i in dropped)
                + f" — the plan's gaps packed only {len(archs) - len(dropped)}"
                f" chunk(s); these run same-device, on the fg devices"
            )
        bg_layers = args.bg_layers if args.bg_layers is not None else args.layers
        with count_paths() as bg_paths, count_loss_paths() as bg_loss_paths:
            bg_fns, placements = _register_bg_jobs(
                coord, archs, meshes, mesh, smoke=args.smoke, layers=bg_layers)
        if len(bg_fns) == 1:
            bg_fn = bg_fns[0]
        else:
            # round-robin the tenants through the train loop's single paced
            # bg slot, highest priority first within each cycle
            cycle = itertools.cycle(bg_fns)

            def bg_fn():
                next(cycle)()

    # live control plane: this single-process entrypoint co-hosts both
    # sides — the worker beats over the transport, the CoordinatorLoop
    # consumes them, so a stalled worker is detected from live beats
    # (handle_failure + re-plan + reconfig event) instead of only via the
    # fail-stop exception path.  Multi-host runs swap the fake pair for
    # KVStoreTransport over the jax.distributed KV store.
    from repro.dist.faults import HeartbeatMonitor
    from repro.dist.transport import CoordinatorLoop, fake_transport_pair

    worker_end, coord_end = fake_transport_pair()
    hb = HeartbeatMonitor(n_workers=1, timeout=args.hb_timeout)
    control_loop = CoordinatorLoop(coord_end, hb, coordinator=coord)

    tc = TrainConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                     bg_step_fn=bg_fn, coordinator=coord, heartbeat=hb,
                     transport=worker_end, control_loop=control_loop,
                     admit_every=max(0, args.admit_every),
                     peak_lr=args.lr)
    report = train(run_cfg, shape, mesh, tc)
    report.fg_devices = [d.id for d in mesh.devices.flat]
    report.bg_tenants = placements
    if args.bg_arch:
        report.attention_paths["bg"] = dict(bg_paths)
        report.loss_paths["bg"] = dict(bg_loss_paths)
    print("attention paths: " + format_paths(report.attention_paths))
    print("loss paths: " + format_paths(report.loss_paths))
    print(
        f"done: steps={report.steps_done} loss {report.losses[0]:.3f} -> "
        f"{report.losses[-1]:.3f} restarts={report.restarts} "
        f"bg_steps={report.bg_steps} "
        f"mitigations={len(report.mitigations)} "
        f"compile={report.compile_s:.1f} s "
        f"mean_step={1e3 * sum(report.step_times) / len(report.step_times):.1f} ms"
    )
    print_stragglers(report.mitigations)
    return report


def format_paths(paths: dict) -> str:
    """``TrainReport.attention_paths`` or ``.loss_paths`` on one line:
    'fg flash×1; bg full×1'."""
    return "; ".join(
        f"{role} " + " ".join(f"{p}×{n}" for p, n in sorted(c.items()))
        for role, c in paths.items())


def print_stragglers(log):
    """One line per straggler step of ``log`` (a ``MitigationLog``), the
    first ``STRAGGLERS_SHOWN``: where its time went, from dispatch to the
    loss being ready."""
    slow = [e for e in log.events if e["kind"] == "straggler"]
    for e in slow[:STRAGGLERS_SHOWN]:
        print(f"straggler: step {e['step']} {1e3 * e['dt']:.1f} ms = "
              f"dispatch {1e3 * e['dispatch_s']:.1f} + bg dispatch "
              f"{1e3 * e['bg_dispatch_s']:.1f} + loss wait "
              f"{1e3 * e['loss_wait_s']:.1f} ms")
    if len(slow) > STRAGGLERS_SHOWN:
        print(f"straggler: {len(slow) - STRAGGLERS_SHOWN} more not shown")


def main():
    args = build_parser().parse_args()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
