"""Real fg/bg multiplexed execution on disjoint submeshes: the foreground
plan's jitted stages run on their device prefix while REAL background LM
training steps from TWO prioritized tenants are paced into the plan's gap
submeshes through the Collocator (the executable multi-tenant path of
paper §5 — the cluster-throughput setting).

    PYTHONPATH=src python examples/multiplex_demo.py

Forces 8 host devices so the gap submeshes are real device subsets.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, "src")


def main():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.vgg16 import CONFIG as VCFG
    from repro.core.costmodel import A100
    from repro.core.multiplex import BgTenant, Collocator, MultiplexConfig
    from repro.core.planner import plan
    from repro.models.graph import build_vgg_graph
    from repro.configs import get_config
    from repro.train.step import bg_step_factory

    bg_cfg = get_config("qwen2-1.5b").reduced()

    # foreground plan (VGG-16 @ 8 devices, the paper's setting)
    fg_plan = plan(build_vgg_graph(VCFG, 32), 8, amp_limit=1.5, hw=A100)
    print(fg_plan.summary())

    # two prioritized background tenants: each gap's free device ranges are
    # packed largest-chunk-to-highest-priority, every tenant training a REAL
    # tiny LM on its own disjoint submesh with a private state replica.
    # Each tenant's step is sized to its own chunk width (per-device batch)
    # instead of one global gap-minimum quantum.
    losses = []
    tenants = [
        BgTenant("bg-hi", 2, bg_step_factory(bg_cfg, seq=8, seed=0,
                                             on_loss=losses.append,
                                             per_device_batch=2)),
        BgTenant("bg-lo", 1, bg_step_factory(bg_cfg, seq=8, seed=1,
                                             on_loss=losses.append,
                                             per_device_batch=2)),
    ]
    col = Collocator(fg_plan, MultiplexConfig(max_inflight=2),
                     tenants=tenants)
    # admission control: sweep candidate tenant counts through predict()
    # BEFORE compiling anything — the argmax-cluster-throughput roster under
    # the paper's 1.33x QoS bound is what actually runs
    decision = col.admit()
    print("admission:", decision.row())
    print("tenant schedule (stage, tenant, bg steps):",
          col.schedule_tenants())
    split = col.submeshes()
    for si, slots in sorted(split.bg_tenants.items()):
        carve = " ".join(
            f"{tenants[i].job}=[{rng[0]},{rng[1]})"
            for i, entry in enumerate(slots) if entry is not None
            for rng, _m in (entry,)
        )
        print(f"  stage {si}: fg devices {split.stage_fg_range[si]} "
              f"bg {carve}")

    # foreground stages: stand-in compute kernels on the stage's submesh
    def make_fg_stage_fn(stage, mesh):
        x = jax.device_put(jnp.full((256, 256), 0.01, jnp.float32),
                           NamedSharding(mesh, P(None, None)))

        @jax.jit
        def f(x):
            for _ in range(8):
                x = jnp.tanh(x @ x) * 0.1 + 0.01
            return x

        return lambda: f(x)

    res = col.run_executable(make_fg_stage_fn, iterations=5,
                             tenants=list(decision.admitted))
    print(res.row())
    print(f"fg iter {res.fg_iter_time*1e3:.1f} ms "
          f"(isolated {res.fg_iter_time_isolated*1e3:.1f} ms) "
          f"jain_fairness={res.jain_fairness():.3f}")
    for t in res.tenants:
        print(f"  {t.row()} (weight {t.weight:g}, deficit {t.deficit:.1f})")
    n_submeshes = sum(
        sum(1 for e in s if e is not None) for s in split.bg_tenants.values()
    )
    print(f"{len(losses)} real bg train steps dispatched across "
          f"{n_submeshes} tenant gap submeshes (independent model replicas; "
          f"includes one warmup step per replica)")
    # per-stage calibration: fit the per-gap-op inflation vector from the
    # measured result and show the (device-free) prediction tracking it
    model = col.calibrate([res])
    pred = col.predict()
    print(f"calibrated gap_inflation={model.gap_inflation:.3f} "
          f"per-stage={dict(model.gap_inflation_stages)} -> "
          f"predict fg_slowdown={pred.fg_slowdown:.3f} "
          f"(measured {max(res.fg_slowdown, 1.0):.3f})")


if __name__ == "__main__":
    main()
