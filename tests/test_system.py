"""End-to-end system behaviour: training loop with faults, coordinator
elasticity, multiplexed background work, loss goes down."""
import dataclasses
import os

import jax
import pytest

from repro.configs import TRAIN_4K, get_config
from repro.core.coordinator import ClusterCoordinator, Job
from repro.launch.mesh import make_mesh
from repro.models.graph import build_lm_graph, build_vgg_graph
from repro.train.loop import TrainConfig, train

SMOKE_SHAPE = dataclasses.replace(TRAIN_4K, seq_len=64, global_batch=4, name="smoke")


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(1, 1)


def test_train_loss_decreases(mesh):
    cfg = get_config("qwen2-1.5b").reduced()
    report = train(cfg, SMOKE_SHAPE, mesh, TrainConfig(steps=25, seed=0))
    assert report.steps_done == 25
    first = sum(report.losses[:5]) / 5
    last = sum(report.losses[-5:]) / 5
    assert last < first, (first, last)


def test_train_restart_from_checkpoint(mesh, tmp_path):
    """Inject a failure mid-run; the loop restores the checkpoint and
    completes all steps."""
    cfg = get_config("llama3-8b").reduced()
    ckpt_dir = str(tmp_path / "ck")
    fired = {"done": False}

    def injector(step):
        if step == 12 and not fired["done"]:
            fired["done"] = True
            raise RuntimeError("injected device failure")

    tc = TrainConfig(steps=15, ckpt_dir=ckpt_dir, ckpt_every=5)
    report = train(cfg, SMOKE_SHAPE, mesh, tc, fault_injector=injector)
    assert report.steps_done >= 15
    assert report.restarts >= 1
    assert report.mitigations.count("failure") == 1
    from repro.checkpoint.ckpt import latest_step

    assert latest_step(ckpt_dir) == 15


def test_train_with_multiplexed_background(mesh):
    cfg = get_config("qwen2-1.5b").reduced()
    counter = {"n": 0}

    def bg():
        counter["n"] += 1

    tc = TrainConfig(steps=6, bg_step_fn=bg)
    report = train(cfg, SMOKE_SHAPE, mesh, tc)
    assert report.bg_steps == counter["n"] > 0


def test_coordinator_elastic_replan():
    coord = ClusterCoordinator(16)
    job = Job("fg", "foreground", build_lm_graph(get_config("llama3-8b"), TRAIN_4K),
              amp_limit=2.0)
    p16 = coord.submit_foreground(job)
    assert p16.num_gpus == 16
    p15 = coord.handle_failure(0)  # 15 healthy -> plan all 15 survivors
    assert p15.num_gpus == 15
    assert p15.total_time >= p16.total_time - 1e-12
    p17 = coord.handle_join([16, 17])  # 17 healthy -> plan all 17
    assert p17.num_gpus == 17
    assert p17.total_time <= p15.total_time + 1e-12


def test_coordinator_collocation_sim():
    coord = ClusterCoordinator(8)
    from repro.configs.vgg16 import CONFIG as VCFG

    coord.submit_foreground(Job("fg", "foreground", build_vgg_graph(VCFG, 32)))
    res = coord.simulate_collocation()
    assert res.fg_slowdown < 1.2
    assert res.cluster_throughput > 0.0


def test_register_bg_jobs_rosters_tenants_with_factories(mesh):
    """Regression: launch/train.py used to register background jobs as bare
    Job(..., []) shells without step_fn_factory — background_tenants()
    skips factory-less jobs, so coordinator-driven collocation/admission
    silently saw ZERO tenants.  The registration helper must attach the
    factory (and a cache-key signature) to every job it submits."""
    from repro.configs.vgg16 import CONFIG as VCFG
    from repro.launch.train import _register_bg_jobs

    coord = ClusterCoordinator(8)
    coord.submit_foreground(
        Job("fg", "foreground", build_vgg_graph(VCFG, 32), amp_limit=1.5)
    )
    bg_fns, placements = _register_bg_jobs(coord, ["qwen2-1.5b"], [None],
                                           mesh, smoke=True)
    assert len(bg_fns) == 1 and callable(bg_fns[0])
    # no gap submesh: the tenant trains on the fg mesh's devices
    assert placements == [("same-device", [d.id for d in mesh.devices.flat])]
    tenants = coord.background_tenants()  # no default factory passed
    assert len(tenants) == 1
    t = tenants[0]
    assert t.job == "bg0-qwen2-1.5b" and t.step_fn_factory is not None
    # the factory carries a distinct cache signature (arch/depth/batch/seed
    # scoped) so two tenants never share a compiled step through the cache
    assert t.cache_signature == "qwen2-1.5b-smoke-L2-pdb2-s32-r1-samedev"
    # the rostered factory is the paced slot's step fn for any mesh
    assert t.step_fn_factory(None) is bg_fns[0]
    bg_fns[0]()


def test_bg_step_compiles_only_at_set_up(mesh):
    """A bg tenant's step is compiled when its factory builds it, and the
    steps the fg loop dispatches (under the fg's ``with mesh``) compile
    nothing: a compile there would be timed as part of an fg step."""
    from repro.configs import run_config
    from repro.train.step import bg_step_factory

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _dur, **_kw: compiles.append(name)
        if name.endswith("backend_compile_duration") else None)
    step = bg_step_factory(run_config("qwen2-1.5b", smoke=True), seq=32,
                           per_device_batch=2)(make_mesh(1, 1))
    n_set_up = len(compiles)
    assert n_set_up > 0
    step()
    with mesh:
        step()
        step()
    assert len(compiles) == n_set_up
