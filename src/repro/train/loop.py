"""Fault-tolerant training loop.

Integrates every substrate: data pipeline, jitted train step, async
checkpointing with restart, straggler detection (EMA deadlines), elastic
re-plan hooks, and the DeepPool multiplexer (background steps dispatched
into burst-plan gaps with pacing + the slowdown feedback loop).

The loop runs in *mesh generations*: with ``apply_reconfig`` set, a
reconfiguration event the coordinator pushed back (a re-plan after a
failure or join) is not just logged — at the next epoch boundary the
worker re-carves its mesh onto the surviving pool
(``launch.mesh.remesh_for_pool``), re-shards the training state onto the
new carving, and resumes.  The jitted step for each carving goes through
an ``ExecutableCache`` (the coordinator's, when wired), so churning back
to a previously-seen pool reuses the compiled step instead of re-jitting.

Each phase of a step (``train.data``, ``train.dispatch``,
``train.bg_dispatch``, ``train.loss_wait``, ``train.control``, and where
they run ``train.compile``, ``train.readmit`` and ``train.ckpt``) is a host
span with the step as its ``step`` stat, seen only by an active profiler.
The spans tile a step without nesting, so that a gap in the device's work
is named after the one phase the host was in.  A straggler step's log
entry splits its time into dispatch, background dispatch and loss wait.

On a real cluster this runs once per host; in this repo it runs end-to-end
on CPU at smoke scale (examples/train_lm.py) and under forced host-device
counts in the integration tests.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.checkpoint import ckpt as ckpt_lib
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.multiplex import (
    Collocator,
    ExecutableCache,
    MultiplexConfig,
    QoSMonitor,
)
from repro.data.pipeline import SyntheticLMData
from repro.dist.faults import HeartbeatMonitor, MitigationLog, StepTimer
from repro.dist.transport import WorkerClient
from repro.launch.mesh import remesh_for_pool
from repro.models import attention as attn_lib
from repro.models.api import get_model
from repro.models.transformer import count_loss_paths
from repro.optim.optimizer import make_optimizer
from repro.train.state import init_sharded_state
from repro.train.step import jit_train_step


@dataclass
class TrainConfig:
    steps: int = 20
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 10
    keep: int = 3
    seed: int = 0
    log_every: int = 5
    max_failures: int = 3
    straggler_factor: float = 3.0
    bg_step_fn: Optional[Callable] = None  # multiplexed background work
    multiplex: MultiplexConfig = field(default_factory=MultiplexConfig)
    # elastic re-planning: when set, failures are reported to the
    # coordinator (which re-plans the foreground job on the surviving
    # power-of-two subset) and each step beats the heartbeat monitor
    coordinator: Optional[Any] = None  # ClusterCoordinator
    heartbeat: Optional[HeartbeatMonitor] = None
    worker_id: int = 0
    # live control plane: with `transport` set, beats go over the wire
    # (WorkerClient) instead of directly into `heartbeat`, and the worker
    # applies reconfiguration events the coordinator pushes back; with
    # `control_loop` set (single-process runs host the coordinator side
    # in the same loop), every step pumps the consumption path so
    # HeartbeatMonitor.failed()/stragglers() drive handle_failure +
    # MitigationLog from live beats.  `admit_every` > 0 re-sweeps tenant
    # admission (coordinator.readmit) every that-many steps — the
    # continuous-admission epoch cadence
    transport: Optional[Any] = None  # worker-side Transport endpoint
    control_loop: Optional[Any] = None  # CoordinatorLoop (co-hosted)
    admit_every: int = 0
    # applied reconfiguration: re-carve this worker's mesh onto the
    # surviving pool at the epoch boundary after a replan event arrives
    # (instead of logging the event and continuing on the stale mesh)
    apply_reconfig: bool = False
    # coordinator election: with `lease` set (CoordinatorLease), the
    # co-hosted control loop only pumps while this worker holds the lease;
    # on acquiring it (failover), the loop bootstraps coordinator state
    # from the topic log before its first pump
    lease: Optional[Any] = None
    peak_lr: Optional[float] = None  # None: the arch's default schedule peak


@dataclass
class TrainReport:
    steps_done: int = 0
    restarts: int = 0
    losses: list = field(default_factory=list)
    step_times: list = field(default_factory=list)
    mitigations: MitigationLog = field(default_factory=MitigationLog)
    bg_steps: int = 0
    remeshes: int = 0  # applied reconfigurations (mesh actually re-carved)
    compile_s: float = 0.0  # compiling the fg step, kept out of step_times
    # filled by launch.train.run: fg mesh device ids, and per --bg-arch
    # tenant its (placement, device ids), placement 'disjoint'|'same-device'
    fg_devices: list = field(default_factory=list)
    bg_tenants: list = field(default_factory=list)
    # per program ('fg'; 'bg', filled by launch.train.run) the attention
    # paths its trace took: {path: attend calls}, one call per scanned stack
    attention_paths: dict = field(default_factory=dict)
    # and likewise the loss head's: {'fused' | 'plain': head_xent calls}
    loss_paths: dict = field(default_factory=dict)


def _mesh_identity(mesh) -> tuple:
    return (tuple(d.id for d in mesh.devices.flat), tuple(mesh.devices.shape))


def train(
    cfg: ModelConfig,
    shape: ShapeConfig,
    mesh,
    tc: TrainConfig,
    fault_injector: Optional[Callable[[int], None]] = None,
) -> TrainReport:
    """Run `tc.steps` steps with checkpoint/restart + straggler monitoring.
    `fault_injector(step)` may raise to simulate failures (tests)."""
    api = get_model(cfg)
    opt = make_optimizer(cfg, total_steps=tc.steps, peak_lr=tc.peak_lr)
    report = TrainReport()
    timer = StepTimer(deadline_factor=tc.straggler_factor)
    monitor = QoSMonitor()
    # compiled fg steps per mesh carving: re-carving back onto a pool seen
    # before (join after failure) reuses the jitted step through the same
    # bounded LRU the bg tenants use (the coordinator's, when wired)
    exec_cache: ExecutableCache = (
        tc.coordinator.exec_cache if tc.coordinator is not None
        else ExecutableCache()
    )

    worker_client = (WorkerClient(tc.transport, tc.worker_id)
                     if tc.transport is not None else None)
    if tc.control_loop is not None and tc.control_loop.log is None:
        tc.control_loop.log = report.mitigations

    failures = 0
    step = 0
    flagged_stragglers: set = set()
    admitted: Optional[tuple] = None
    state = None
    data_state: Optional[dict] = None
    pending_reconfig: Optional[dict] = None
    first_generation = True

    while True:  # one iteration per mesh generation (re-carved on reconfig)
        with mesh:
            key = ExecutableCache.key(
                f"fg-train-{cfg.name}-{shape.name}-s{tc.seed}", mesh
            )
            step_fn, st_sh, bt_sh = exec_cache.get_or_build(
                key, lambda: jit_train_step(api, opt, mesh, shape)
            )

            def fresh_state():
                return init_sharded_state(tc.seed, api, opt, st_sh)

            data = SyntheticLMData(cfg, shape.global_batch, shape.seq_len,
                                   seed=tc.seed, shardings=bt_sh)
            compiled = False
            if first_generation:
                first_generation = False
                if tc.ckpt_dir and ckpt_lib.latest_step(tc.ckpt_dir) is not None:
                    state, meta = ckpt_lib.restore(tc.ckpt_dir, fresh_state(),
                                                   shardings=st_sh)
                    step = meta["step"]
                    data.restore(meta.get("data",
                                          {"seed": tc.seed, "step": step}))
                    report.restarts += 1
                else:
                    state = fresh_state()
            else:
                # new carving: re-shard the live state + resume the data
                # cursor exactly where the previous generation stopped
                state = jax.device_put(state, st_sh)
                if data_state is not None:
                    data.restore(data_state)

            while step < tc.steps:
                try:
                    at = step  # the step being trained, as the spans name it
                    if fault_injector is not None:
                        fault_injector(step)
                    with TraceAnnotation("train.data", step=at):
                        batch = next(data)
                    if not compiled:
                        # compile before the clock starts: the jitted call
                        # reuses this executable (a cached carving compiles
                        # in no time)
                        with TraceAnnotation("train.compile", step=at), \
                                attn_lib.count_paths() as paths, \
                                count_loss_paths() as loss_paths:
                            t0 = time.perf_counter()
                            step_fn.lower(state, batch).compile()
                            report.compile_s += time.perf_counter() - t0
                        report.attention_paths.setdefault("fg", dict(paths))
                        report.loss_paths.setdefault("fg", dict(loss_paths))
                        compiled = True
                    t0 = time.perf_counter()
                    with TraceAnnotation("train.dispatch", step=at):
                        state, metrics = step_fn(state, batch)
                    t_fg = time.perf_counter()
                    # multiplexing: dispatch paced background steps while the
                    # foreground step is in flight (async dispatch)
                    if tc.bg_step_fn is not None:
                        n_bg = tc.multiplex.max_inflight
                        with TraceAnnotation("train.bg_dispatch", step=at,
                                             n=n_bg):
                            for _ in range(n_bg):
                                tc.bg_step_fn()
                                report.bg_steps += 1
                    t_bg = time.perf_counter()
                    with TraceAnnotation("train.loss_wait", step=at):
                        loss = float(jax.block_until_ready(metrics["loss"]))
                    t1 = time.perf_counter()
                    dt = t1 - t0
                    timer.record(dt)
                    if timer.is_straggler_step(dt):
                        report.mitigations.log(
                            "straggler", step=step, dt=dt,
                            dispatch_s=t_fg - t0, bg_dispatch_s=t_bg - t_fg,
                            loss_wait_s=t1 - t_bg)
                    report.losses.append(loss)
                    report.step_times.append(dt)
                    step += 1
                    report.steps_done += 1
                    with TraceAnnotation("train.control", step=at):
                        if worker_client is not None:
                            # live path: the beat goes over the transport;
                            # the co-hosted CoordinatorLoop (or a remote
                            # coordinator) consumes it — detection,
                            # handle_failure, straggler logging and
                            # continuous admission all happen on the
                            # consumption side, not here
                            worker_client.beat(step)
                        elif tc.heartbeat is not None:
                            tc.heartbeat.beat(tc.worker_id, step)
                        if tc.control_loop is not None:
                            if tc.lease is not None:
                                # election-gated coordination: pump only
                                # while holding the lease; a fresh
                                # acquisition (failover) bootstraps from the
                                # topic log so mitigations the dead holder
                                # already fired are adopted, never re-fired
                                if tc.lease.tick():
                                    if tc.lease.acquired:
                                        tc.control_loop.bootstrap_from_log()
                                    tc.control_loop.pump()
                            else:
                                tc.control_loop.pump()
                        elif tc.heartbeat is not None:
                            # legacy in-process path (no transport):
                            # classify stragglers directly off the monitor
                            lagging = set(tc.heartbeat.stragglers())
                            for w in sorted(lagging - flagged_stragglers):
                                report.mitigations.log("straggler_worker",
                                                       step=step, worker=w)
                            # recovered ones re-arm
                            flagged_stragglers = lagging
                        if worker_client is not None:
                            # epoch-boundary reconfiguration: apply re-plans
                            # the coordinator pushed back since the last step
                            for ev in worker_client.poll_reconfig():
                                report.mitigations.log(
                                    "reconfig", step=step,
                                    **{k: v for k, v in ev.items()
                                       if k != "kind"}
                                )
                                if (tc.apply_reconfig
                                        and ev.get("action") == "replan"
                                        and ev.get("devices")):
                                    pending_reconfig = ev  # latest event wins
                    if (tc.admit_every > 0 and tc.coordinator is not None
                            and step % tc.admit_every == 0):
                        # continuous admission: re-sweep the tenant roster at
                        # the epoch cadence (churn events re-sweep via the
                        # control loop); log only when the admitted set
                        # changed
                        with TraceAnnotation("train.readmit", step=at):
                            decision = tc.coordinator.readmit(reason="epoch")
                        if decision is not None:
                            now = tuple(t.job for t in decision.admitted)
                            if admitted is not None and now != admitted:
                                report.mitigations.log(
                                    "admission", step=step,
                                    admitted=list(now),
                                    rejected=[t.job
                                              for t in decision.rejected],
                                )
                            admitted = now
                    if tc.ckpt_dir and step % tc.ckpt_every == 0:
                        with TraceAnnotation("train.ckpt", step=at):
                            ckpt_lib.save(tc.ckpt_dir, state, step,
                                          keep=tc.keep,
                                          extra_meta={"data": data.state()},
                                          async_=False)
                    if pending_reconfig is not None:
                        break  # epoch boundary: re-carve before next step
                except (RuntimeError, ValueError, FloatingPointError) as e:
                    failures += 1
                    report.mitigations.log("failure", step=step,
                                           err=repr(e)[:200])
                    if failures > tc.max_failures:
                        raise
                    # fail-stop semantics (paper §3.2): a wired coordinator
                    # treats a step failure as loss of this worker's device.
                    # Report it once — repeats of the same worker would only
                    # re-run an identical planner search.
                    if (tc.coordinator is not None
                            and tc.worker_id in tc.coordinator.healthy):
                        new_plan = tc.coordinator.handle_failure(tc.worker_id)
                        if new_plan is not None:
                            report.mitigations.log("replan", step=step,
                                                   gpus=new_plan.num_gpus)
                    # restart from last checkpoint (or fresh if none)
                    if tc.ckpt_dir and \
                            ckpt_lib.latest_step(tc.ckpt_dir) is not None:
                        state, meta = ckpt_lib.restore(
                            tc.ckpt_dir, fresh_state(), shardings=st_sh
                        )
                        step = meta["step"]
                        data.restore(meta.get("data", {"seed": tc.seed,
                                                       "step": step}))
                    else:
                        state = fresh_state()
                        step = 0
                    report.restarts += 1
            data_state = data.state()
            data.close()
        if step >= tc.steps or pending_reconfig is None:
            break
        # -- applied reconfig: re-carve onto the surviving pool -------------
        ev, pending_reconfig = pending_reconfig, None
        new_mesh = remesh_for_pool(ev["devices"])
        if _mesh_identity(new_mesh) == _mesh_identity(mesh):
            continue  # this host's carving is unchanged (event logged above)
        mesh = new_mesh
        report.remeshes += 1
        report.mitigations.log(
            "reconfig_applied", step=step, gpus=ev.get("gpus"),
            mesh_devices=len(new_mesh.devices.flat),
            reason=ev.get("reason"),
        )
    return report
