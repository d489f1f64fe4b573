"""The training program's own marks: named scopes in the compiled step,
host spans around each phase of the loop, and the phase split of a
straggler step."""
import collections
import dataclasses
import glob
import os
import re
import time

import jax
import pytest

from repro.configs import TRAIN_4K, run_config
from repro.launch.mesh import make_mesh
from repro.train.loop import TrainConfig, train

SMOKE_SHAPE = dataclasses.replace(TRAIN_4K, seq_len=64, global_batch=2,
                                  name="smoke")
SCOPES = ("embed", "attention", "mlp", "loss_head", "optimizer")
PHASES = ("train.data", "train.dispatch", "train.loss_wait", "train.control")


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(1, 1)


def _in_scope(path, scope):
    return re.search(r"(?:^|[/(])%s(?=[/)]|$)" % scope, path) is not None


def test_the_compiled_step_carries_every_scope_backward_included(mesh):
    from repro.models.api import get_model, make_batch
    from repro.optim.optimizer import make_optimizer
    from repro.train.state import init_sharded_state
    from repro.train.step import jit_train_step

    cfg = run_config("qwen2-1.5b", smoke=True)
    api, opt = get_model(cfg), make_optimizer(cfg)
    with mesh:
        fn, st_sh, bt_sh = jit_train_step(api, opt, mesh, SMOKE_SHAPE)
        state = init_sharded_state(0, api, opt, st_sh)
        batch = jax.device_put(make_batch(jax.random.PRNGKey(0), cfg, 2, 64),
                               bt_sh)
        hlo = fn.lower(state, batch).compile().as_text()
    assert hlo.startswith("HloModule jit_step_with_fsdp,")
    paths = re.findall(r'op_name="([^"]+)"', hlo)
    for scope in SCOPES:
        assert any(_in_scope(p, scope) for p in paths), scope
    # the backward pass: the layer scan's recompute and transpose, and the
    # loss head's and the embedding's transposes
    backward = [p for p in paths if "transpose(" in p]
    for scope in ("embed", "attention", "mlp", "loss_head"):
        assert any(_in_scope(p, scope) for p in backward), scope


def test_the_bg_step_program_has_a_name_of_its_own(mesh):
    from repro.train.step import bg_step_factory

    names = []

    def listener(name, _secs, **kw):
        if name.endswith("backend_compile_duration"):
            names.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        bg_step_factory(run_config("qwen2-1.5b", smoke=True), seq=32,
                        per_device_batch=2)(mesh)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    # the fg program is jit(step_with_fsdp): the prefix stays shared
    assert "jit(step_with_fsdp_bg)" in names, names


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("train."):
                    spans.append((ev.start_ns, ev.end_ns, ev.name,
                                  dict(ev.stats)))
    return sorted(spans)


def test_train_marks_each_phase_of_each_step_with_a_span(mesh, tmp_path):
    cfg = run_config("qwen2-1.5b", smoke=True)
    tc = TrainConfig(steps=3, bg_step_fn=lambda: None)
    with jax.profiler.trace(str(tmp_path)):
        report = train(cfg, SMOKE_SHAPE, mesh, tc)
    assert report.steps_done == 3
    spans = _host_spans(str(tmp_path))
    steps = collections.defaultdict(list)
    for _, _, name, stats in spans:
        steps[name].append(stats["step"])
    for name in PHASES + ("train.bg_dispatch",):
        assert steps[name] == [0, 1, 2], (name, steps)
    assert steps["train.compile"] == [0]
    assert {s["n"] for *_, n, s in spans if n == "train.bg_dispatch"} == {
        tc.multiplex.max_inflight}
    # the phases tile a step: no span holds another
    for (_, end, *_), (start, *_) in zip(spans, spans[1:]):
        assert end <= start


def test_a_straggler_step_logs_where_its_time_went(mesh):
    cfg = run_config("qwen2-1.5b", smoke=True)
    calls = []

    def slow_bg():
        calls.append(None)
        # two tenant steps a step (max_inflight): the first of step 10
        if len(calls) == 2 * 10 + 1:
            time.sleep(1.0)

    tc = TrainConfig(steps=12, bg_step_fn=slow_bg)
    report = train(cfg, SMOKE_SHAPE, mesh, tc)
    [slow] = [e for e in report.mitigations.events
              if e["kind"] == "straggler" and e["step"] == 10]
    assert slow["bg_dispatch_s"] >= 1.0
    assert slow["dispatch_s"] + slow["bg_dispatch_s"] + slow[
        "loss_wait_s"] == pytest.approx(slow["dt"])
    assert slow["dt"] == pytest.approx(report.step_times[10])


def test_run_prints_a_handful_of_stragglers(capsys):
    from repro.dist.faults import MitigationLog
    from repro.launch.train import STRAGGLERS_SHOWN, print_stragglers

    log = MitigationLog()
    for k in range(STRAGGLERS_SHOWN + 3):
        log.log("straggler", step=k, dt=0.6, dispatch_s=0.001,
                bg_dispatch_s=0.0, loss_wait_s=0.599)
    log.log("failure", step=99, err="x")
    print_stragglers(log)
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == STRAGGLERS_SHOWN + 1
    assert lines[0] == ("straggler: step 0 600.0 ms = dispatch 1.0 + bg "
                        "dispatch 0.0 + loss wait 599.0 ms")
    assert lines[-1] == "straggler: 3 more not shown"
