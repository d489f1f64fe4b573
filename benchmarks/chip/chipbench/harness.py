"""One run of one cell: drive ``repro.launch.train.run`` and measure it.

The program has no step hook and no seed argument of its own, so the
harness reaches into ``repro.train.loop``, ``repro.train.step``,
``repro.train.state`` and ``repro.models.api`` for the length of one run
(``Patches``), and restores them after:

- ``loop.train`` is wrapped: its ``fault_injector(step)`` argument, called
  at the start of every step, becomes the step-start stamp that opens,
  closes and traces the window, and ends the loop by lowering
  ``TrainConfig.steps`` (the optimizer's schedule was fixed at the start);
- ``init_sharded_state`` makes the weights from ``--seed`` (``inputs``),
  ``SyntheticLMData._make_host_batch`` makes each step's batch from it on
  the host (the pipeline's prefetch thread, queue and ``device_put`` stay
  the program's), and the tenant's ``make_batch`` a seeded batch: inputs
  and weights come from the benchmark, so the reference can make the same
  ones itself;
- ``jit_train_step`` is wrapped so that each compiled step's first calls
  are observed (the first gradient, from Adam's moment, and the change of
  the weights after three updates), and each compiled step's peak memory
  over its arguments is recorded.

One ``run()`` call does it all: set-up builds the state and compiles, the
first ``warmup_steps`` steps run, and the same loop, state and step go on
into the window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import check, inputs, peaks, reference, spec, trace

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration")
TRACE_STEPS = 4  # steps profiled after the window closes (--trace 1)
# the window holds at least this many steps, so that a quantile of its step
# intervals has two to go by however short ``--seconds`` is
MIN_STEPS = 2


class NoChip(RuntimeError):
    """The cell's chips are not there: no result may be printed."""


def _set(obj, name, value, saved):
    saved.append((obj, name, getattr(obj, name)))
    setattr(obj, name, value)


class Observer:
    """Records what a compiled training step's first calls leave: Adam's
    first moment after one update, and the change of the weights after
    three."""

    def __init__(self, model, role: int, words, shapes, b1: float):
        self.words, self.b1 = words, b1
        self.calls, self.times = 0, []
        self.last = self.m0 = self.delta = None
        # the compiled step's peak over its own arguments, by the compiler
        self.extra_bytes = 0
        self._delta = reference.delta_norms_fn(model, role, shapes)

    def after(self, out):
        state, metrics = out
        k = self.calls
        self.calls += 1
        self.times.append(time.perf_counter())
        self.last = metrics["loss"]
        if k == 0:  # Adam's first moment after one update: (1 - b1) g
            self.m0 = jax.device_get(state["opt"]["m"])
        if k == 2:  # the weights as step 4 takes them
            self.delta = self._delta(state["params"], self.words)
        return out

    def readings(self, paths) -> dict:
        g = [float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))
             / (1 - self.b1) for x in jax.tree.leaves(self.m0)]
        d = np.asarray(jax.device_get(self.delta))
        return {"grad_norms": dict(zip(paths, g)),
                "delta_norms": dict(zip(paths, map(float, d))),
                "m0": self.m0}


class _Compiled:
    def __init__(self, compiled, obs):
        self._c, self._obs = compiled, obs

    def __call__(self, *a):
        return self._obs.after(self._c(*a))


class _Lowered:
    def __init__(self, lowered, obs):
        self._l, self._obs = lowered, obs

    def compile(self, *a, **k):
        compiled = self._l.compile(*a, **k)
        mem = compiled.memory_analysis()
        if mem is not None:
            self._obs.extra_bytes = max(
                self._obs.extra_bytes,
                mem.peak_memory_in_bytes - mem.argument_size_in_bytes)
        return _Compiled(compiled, self._obs)


class _Jitted:
    def __init__(self, fn, obs):
        self._fn, self._obs = fn, obs

    def __call__(self, *a):
        return self._obs.after(self._fn(*a))

    def lower(self, *a, **k):
        return _Lowered(self._fn.lower(*a, **k), self._obs)


@dataclasses.dataclass
class Plan:
    """What one run does, from the cell's files."""

    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list

    @property
    def words(self):
        return inputs.seed_words(self.seed)

    @property
    def model(self):
        """The configuration's reference module (``spec.reference``)."""
        return spec.reference(self.config)

    def role_shapes(self, role: int) -> dict:
        """The parameter layout of ``role``, by the reference module."""
        return self.model.param_shapes(self.role_config(role))

    def role_config(self, role: int) -> dict:
        if role == 0:
            return self.config
        t = self.traffic["tenants"][role - 1]
        return dict(self.config, num_hidden_layers=t["layers"])

    def role_train(self, role: int) -> tuple:
        """(training recipe, schedule length) of ``role``."""
        train = self.config["train"]
        if role == 0:
            return train, self.traffic["schedule_steps"]
        t = self.traffic["tenants"][role - 1]
        return dict(train, peak_lr=t["peak_lr"]), t["schedule_steps"]


class Run:
    """State of one run: stamps, window, observers, trace."""

    def __init__(self, plan: Plan, t_start: float):
        self.plan, self.t_start = plan, t_start
        self.w0 = plan.traffic["warmup_steps"]
        self.stamps, self.observers = {}, {}
        self.tc = None
        self.t_w0 = self.t_end = None
        self.closed_at = None  # first step after the window
        self.compiles, self.compiles_at_w0, self.window_compiles = [], 0, None
        self.trace_dir = self.trace_from = self.trace_to = None
        self.step_ann = self.window_ann = None
        self.tenant_role = 0
        self.schedule_ran_out = False
        self._plus_one = jax.jit(lambda x: x + 1)

    # -- window ----------------------------------------------------------
    def drain(self):
        """Wait until every chip of the cell has run all it was given."""
        jax.block_until_ready([o.last for o in self.observers.values()
                               if o.last is not None])
        jax.block_until_ready([self._plus_one(jax.device_put(np.float32(0), d))
                               for d in self.plan.devices])

    def stamp(self, step: int):
        now = time.perf_counter()
        self.stamps[step] = now
        if step == 1:
            self.drain()  # compiles the drain programs in set-up
        if step == self.w0:
            self.t_w0, self.compiles_at_w0 = now, len(self.compiles)
        elif self.closed_at is None and step >= self.w0 + MIN_STEPS and \
                now - self.t_w0 >= self.plan.seconds:
            self.drain()
            self.t_end, self.closed_at = time.perf_counter(), step
            self.window_compiles = len(self.compiles) - self.compiles_at_w0
            if self.plan.trace:
                self._start_trace(step)
            else:
                self.tc.steps = step + 1
        elif self.trace_from is not None and self.trace_to is None:
            self._step_span(None)
            if step == self.trace_from + TRACE_STEPS:
                self._stop_trace(step)
                self.tc.steps = step + 1
        if self.trace_from is not None and self.trace_to is None:
            self._step_span(step)
        if self.closed_at is None and step + 1 >= self.tc.steps:
            self.schedule_ran_out = True

    def _step_span(self, step):
        if self.step_ann is not None:
            self.step_ann.__exit__(None, None, None)
            self.step_ann = None
        if step is not None:
            self.step_ann = jax.profiler.TraceAnnotation(trace.STEP_SPAN,
                                                         step=step)
            self.step_ann.__enter__()

    def _start_trace(self, step):
        self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.trace_from = step
        self.window_ann = jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
        self.window_ann.__enter__()

    def _stop_trace(self, step):
        self.drain()
        self._step_span(None)
        self.window_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.trace_to = step

    # -- patched program pieces -------------------------------------------
    def init_state(self, seed, api, optimizer, shardings):
        role = int(seed)
        if role > 0:
            self.tenant_role = role
        shapes = self.plan.role_shapes(role)
        abstract = api.abstract()
        prog = {p: tuple(s.shape) for p, s in zip(
            inputs.leaf_paths(abstract), jax.tree.leaves(abstract))}
        ours = {p: tuple(s.shape) for p, s in zip(
            inputs.leaf_paths(shapes), jax.tree.leaves(shapes))}
        if prog != ours:
            raise ValueError(f"the program's parameters are not the layout "
                             f"of the configuration: {prog} vs {ours}")

        def make(words):
            params = inputs.make_params(self.plan.model, words, role, shapes)
            return {"params": params, "opt": optimizer.init(params),
                    "step": jnp.zeros((), jnp.int32)}

        return jax.jit(make, out_shardings=shardings)(self.plan.words)

    def tenant_batch(self, rng, cfg, batch, seq):
        t = self.plan.traffic["tenants"][self.tenant_role - 1]
        if (batch, seq) != (t["batch"], t["seq"]):
            raise ValueError(f"the tenant's batch is {batch}x{seq}, the "
                             f"traffic's {t['batch']}x{t['seq']}")
        return inputs.make_tokens(self.plan.words, self.tenant_role, 0, batch,
                                  seq, cfg.vocab_size,
                                  self.plan.traffic["token_power"])

    def observed(self, jit_train_step, role_of):
        def wrapped(api, optimizer, mesh, shape, *a, **k):
            fn, st_sh, bt_sh = jit_train_step(api, optimizer, mesh, shape,
                                              *a, **k)
            role = role_of()
            train, _ = self.plan.role_train(role)
            obs = Observer(self.plan.model, role, self.plan.words,
                           self.plan.role_shapes(role), train["b1"])
            self.observers[role] = obs
            return _Jitted(fn, obs), st_sh, bt_sh
        return wrapped

    def host_batch(self, data, step):
        """The foreground's batch of ``step``, in the pipeline's thread."""
        tr = self.plan.traffic
        return inputs.host_batch(self.plan.words, data.cursor.seed, step,
                                 data.batch, data.seq,
                                 self.plan.config["vocab_size"],
                                 tr["token_power"])

    def train(self, orig_train):
        def wrapped(cfg, shape, mesh, tc, fault_injector=None):
            self.tc = dataclasses.replace(tc)
            return orig_train(cfg, shape, mesh, self.tc,
                              fault_injector=self.stamp)
        return wrapped

    @contextlib.contextmanager
    def patches(self):
        from repro.data import pipeline
        from repro.models import api as api_mod
        from repro.train import loop, state, step

        saved = []
        tenants = iter(range(1, 1 + len(self.plan.traffic["tenants"])))
        try:
            _set(loop, "train", self.train(loop.train), saved)
            _set(pipeline.SyntheticLMData, "_make_host_batch",
                 lambda data, k: self.host_batch(data, k), saved)
            _set(loop, "init_sharded_state", self.init_state, saved)
            _set(state, "init_sharded_state", self.init_state, saved)
            _set(api_mod, "make_batch", self.tenant_batch, saved)
            _set(loop, "jit_train_step",
                 self.observed(loop.jit_train_step, lambda: 0), saved)
            _set(step, "jit_train_step",
                 self.observed(step.jit_train_step, lambda: next(tenants)),
                 saved)
            listener = lambda name, *a, **k: (  # noqa: E731
                self.compiles.append(name) if name in COMPILE_EVENTS else None)
            jax.monitoring.register_event_duration_secs_listener(listener)
            saved.append(None)
            yield
        finally:
            for item in reversed(saved):
                if item is None:
                    jax.monitoring.unregister_event_duration_listener(listener)
                else:
                    setattr(*item)


def train_args(plan: Plan, extra=()) -> list:
    c, tr = plan.config, plan.traffic
    argv = ["--arch", c["arch"], "--layers", c["num_hidden_layers"],
            "--seq", tr["seq"], "--batch", tr["batch"],
            "--steps", tr["schedule_steps"], "--lr", c["train"]["peak_lr"],
            "--data", tr["data"], "--model", tr["model"]]
    layers = {t["layers"] for t in tr["tenants"]}
    if len(layers) > 1:
        raise ValueError("the program gives every tenant one depth")
    for _ in tr["tenants"]:
        argv += ["--bg-arch", c["arch"]]
    if layers:
        argv += ["--bg-layers", layers.pop()]
    return [str(a) for a in (*argv, *extra)]


def _quantile(xs, q):
    return statistics.quantiles(xs, n=100, method="inclusive")[q - 1]


def measure(name: str, seed: int, seconds: float, trace_on: bool,
            t_start: float, *, require_tpu: bool = True, config=None,
            traffic=None, extra_args=()) -> dict:
    """Run cell ``name`` once; returns the result object.  The tests pass
    a small ``config`` and ``traffic``, ``extra_args`` for the program
    (``--smoke``) and ``require_tpu=False``."""
    cell = spec.cell(name)
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {devs[0].platform} devices only")
    if len(devs) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips, JAX sees "
                     f"{len(devs)}")
    if require_tpu:
        from repro.launch.compile_cache import use_compile_cache

        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    plan = Plan(config=config or cell.config, traffic=traffic or cell.traffic,
                seed=seed, seconds=seconds, trace=trace_on,
                devices=devs[:cell.chips])
    run = Run(plan, t_start)
    from repro.launch import train as launch_train

    args = launch_train.build_parser().parse_args(train_args(plan,
                                                             extra_args))
    with run.patches(), contextlib.redirect_stdout(sys.stderr):
        report = launch_train.run(args)
    return finish(cell, plan, run, report)


def finish(cell, plan: Plan, run: Run, report) -> dict:
    tr, c = plan.traffic, plan.config
    if run.closed_at is None:
        raise RuntimeError(
            f"the window never closed: {report.steps_done} steps of a "
            f"{tr['schedule_steps']}-step schedule ran out first")
    w0, k = run.w0, run.closed_at
    window_s = run.t_end - run.t_w0
    steps = k - w0
    tokens = tr["batch"] * tr["seq"]
    intervals = [run.stamps[i + 1] - run.stamps[i] for i in range(w0, k)]
    bg_tokens = sum(tr["tenants"][r - 1]["batch"] * tr["tenants"][r - 1]["seq"]
                    for r, o in run.observers.items() if r > 0
                    for t in o.times if run.t_w0 <= t < run.stamps[k])
    e2e = {
        "fg_tokens_per_s": (steps * tokens / window_s, "tokens/s"),
        "fg_step_p90_ms": (1e3 * _quantile(intervals, 90), "ms"),
        "bg_tokens_per_s": (bg_tokens / window_s, "tokens/s"),
        "setup_s": (run.t_w0 - run.t_start, "s"),
    }
    # the allocator's peak counts live arrays, not a running program's
    # temporaries: add the most that a step program holds over its arguments
    device = {"platform": plan.devices[0].platform,
              "kind": plan.devices[0].device_kind,
              "count": len(plan.devices),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in plan.devices) + max(
                      o.extra_bytes for o in run.observers.values())}
    failed = report.restarts + report.mitigations.count("failure")
    rec = {
        "compile_s": report.compile_s,
        "window_s": window_s,
        "window_steps": steps,
        "step_times": report.step_times[w0:k],
        "tokens_per_step": tokens,
        "chips": len(plan.devices),
        "flops_per_token": plan.model.train_flops_per_token(c, tr["seq"]),
        "peak_flops": (peaks.peak(plan.devices[0].device_kind)["bf16_flops"]
                       if plan.devices[0].platform == "tpu" else None),
        # bg steps the loop dispatches per fg step
        "max_inflight": (run.tc.multiplex.max_inflight if tr["tenants"]
                         else 0),
        "trace": None,
    }
    breakdown = None
    if run.trace_dir is not None:
        [path] = glob.glob(os.path.join(run.trace_dir, "**", "*.xplane.pb"),
                           recursive=True)
        red = trace.reduce(trace.load(path), n_devices=len(plan.devices))
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        red["steps"] = run.trace_to - run.trace_from
        rec["trace"] = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = trace.breakdown(red)

    # the program's readings, then everything it holds on the chips goes
    prog = {r: o.readings(inputs.leaf_paths(plan.role_shapes(r)))
            for r, o in run.observers.items()}
    # the loop's config holds the tenants' steps, and they their states
    del report, run.observers
    run.tc = None
    gc.collect()
    nums = {"window_compiles": (run.window_compiles, "compiles in window"),
            "failed_steps": (failed, "restarts and failures")}
    for role, readings in sorted(prog.items()):
        ref = reference_readings(plan, role, other_m=readings.pop("m0"))
        nums.update(check.compare(readings, ref,
                                  "" if role == 0 else f"bg{role - 1}."))
    checks = {}
    for key, (value, where) in nums.items():
        limit = cell.limits[key]
        checks[key] = {"value": value, "limit": limit, "at": where}
    correct = (not run.schedule_ran_out and all(
        v["value"] <= v["limit"] for v in checks.values()))
    for key, v in checks.items():
        print(f"check {key}: {v['value']!r} (limit {v['limit']!r}; "
              f"{v['at']})", file=sys.stderr)

    if plan.trace:
        metrics = {}
        for m in cell.per_layer:
            value = read_metric(m["name"], rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        names = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {n: {"value": v, "unit": names[n]}
                   for n, (v, _) in e2e.items() if n in names}
    out = {"correct": bool(correct), "attempted": steps, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: [v["value"], v["limit"]] for k, v in checks.items()}
    return out


def reference_readings(plan: Plan, role: int, prec: str = "f32",
                       other_m=None, keep_m: bool = False) -> dict:
    """The reference module's three steps for ``role``.  On several chips
    its f32 weights and moments are sharded over them by their last
    dimension, and its batch by rows, wherever the chips divide it."""
    c = plan.role_config(role)
    train, total = plan.role_train(role)
    tr = plan.traffic
    rows = tr["batch"] if role == 0 else tr["tenants"][role - 1]["batch"]
    shardings = batch_sharding = None
    n = len(plan.devices)
    if n > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(plan.devices), ("d",))
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, P(*([None] * (s.ndim - 1)), "d")
                                    if s.ndim and s.shape[-1] % n == 0
                                    else P()),
            plan.role_shapes(role))
        batch_sharding = NamedSharding(mesh, P("d") if rows % n == 0 else P())
    if role == 0:
        batches = lambda k: jax.device_put(inputs.host_batch(  # noqa: E731
            plan.words, 0, k, tr["batch"], tr["seq"], c["vocab_size"],
            tr["token_power"]), batch_sharding)
    else:
        t = tr["tenants"][role - 1]
        fixed = jax.jit(lambda: inputs.make_tokens(
            plan.words, role, 0, t["batch"], t["seq"], c["vocab_size"],
            tr["token_power"]), out_shardings=batch_sharding)()
        batches = lambda k: fixed  # noqa: E731
    return reference.run(plan.model, c, train, total, plan.words, role,
                         batches, shardings=shardings, prec=prec,
                         other_m=other_m, keep_m=keep_m)


def read_metric(name: str, rec: dict) -> Optional[float]:
    value = spec.load_module(spec.reader_path(name)).read(rec)
    return None if value is None or not math.isfinite(value) else float(value)
