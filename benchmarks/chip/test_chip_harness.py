"""The harness on the CPU: its data layer, its refusal to run without a
chip, and the comparison that decides ``correct`` against planted faults
and against the float8 control, at a reduced size."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import calibrate  # noqa: E402
from chipbench import faults, harness, spec  # noqa: E402

BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_its_files(name):
    cell = spec.cell(name)
    assert cell.chips in (1, 4)
    assert {"hidden_size", "num_hidden_layers", "train", "arch"} <= set(
        cell.config)
    assert {"seq", "batch", "warmup_steps", "schedule_steps",
            "tenants"} <= set(cell.traffic)
    assert {"window_compiles", "failed_steps", "grad_gap", "grad_diff",
            "update_gap"} <= set(cell.limits)
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_resolves_to_a_reader(name):
    path = spec.reader_path(name)
    mod_spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    assert callable(mod.read)


def test_names_and_units_keep_to_the_allowed_characters():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    assert all(spec.NAME_RE.match(n) for n in names), names
    units = [m["unit"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert all(spec.UNIT_RE.match(u) for u in units), units
    for root, _, files in os.walk(HERE):
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), ROOT)
            assert all(spec.NAME_RE.match(p) for p in rel.split("/")
                       if p != "__pycache__"), rel


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            pass
    return False


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _run_py(ROOT)
    assert p.returncode != 0 and not _has_result(p.stdout), p.stderr[-2000:]
    assert "no TPU" in p.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0 and not _has_result(p.stdout), p.stderr[-2000:]


@pytest.mark.parametrize("seconds", [0.0, 1e-9])
def test_a_window_shorter_than_one_step_still_holds_two_intervals(seconds):
    """However short ``--seconds`` is against a step, the window closes
    only once it holds two step intervals, so that their 90th percentile
    has two to go by."""
    import types

    import jax

    cell = spec.cell(CELLS[0])
    plan = harness.Plan(config=cell.config, traffic=cell.traffic, seed=1,
                        seconds=seconds, trace=False,
                        devices=jax.devices()[:1])
    run = harness.Run(plan, time.perf_counter())
    run.tc = types.SimpleNamespace(steps=1000)
    step = 0
    while run.closed_at is None:
        run.stamp(step)
        time.sleep(1e-3)
        step += 1
    w0 = cell.traffic["warmup_steps"]
    assert run.closed_at == w0 + 2 and run.tc.steps == w0 + 3
    intervals = [run.stamps[i + 1] - run.stamps[i]
                 for i in range(w0, run.closed_at)]
    assert len(intervals) == 2
    assert harness._quantile(intervals, 90) >= min(intervals)


# -- the comparison, on the program's reduced toy at seq 64 ----------------

TENANT_CELL = "qwen2-l4.samedev-bg"


def _small(name):
    from repro.configs import run_config

    cell = spec.cell(name)
    m = run_config(cell.config["arch"], smoke=True)
    config = dict(cell.config, hidden_size=m.d_model,
                  intermediate_size=m.d_ff, num_attention_heads=m.num_heads,
                  num_key_value_heads=m.num_kv_heads, head_dim=m.d_head,
                  num_hidden_layers=m.num_layers, vocab_size=m.vocab_size)
    return config, dict(cell.traffic, seq=64)


def _measure(name, seed):
    config, traffic = _small(name)
    return harness.measure(name, seed, 0.3, False, time.perf_counter(),
                           require_tpu=False, config=config, traffic=traffic,
                           extra_args=["--smoke"])


@pytest.fixture
def faulty_step(monkeypatch):
    """Plant ``fault`` in the program's train step of the models with
    ``layers`` layers (the foreground toy has 2, the tenant 1)."""
    from repro.train import step

    def plant(fault, layers):
        monkeypatch.setattr(step, "make_train_step", faults.faulty(
            step.make_train_step, fault, layers))
    return plant


def test_a_sound_run_is_correct():
    out = _measure("qwen2-l4.fg", 2**33 + 11)
    assert out["correct"], out["checks"]
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]


def test_the_tenants_training_is_compared_too():
    # the limits are the chip-size cell's; at this toy size only the fg
    # role's sound readings are known to lie under them
    out = _measure(TENANT_CELL, 2**33 + 11)
    assert out["metrics"]["bg_tokens_per_s"]["value"] > 0
    assert sorted(k for k in out["checks"] if k.startswith("bg0.")) == [
        "bg0.grad_diff", "bg0.grad_gap", "bg0.update_gap"]
    assert all(v <= lim for k, (v, lim) in out["checks"].items()
               if not k.startswith("bg0.")), out["checks"]


@pytest.mark.parametrize("fault, name, layers, caught_by", [
    ("state_unchanged", "qwen2-l4.fg", 2, "update_gap"),
    ("update_doubled", "qwen2-l4.fg", 2, "update_gap"),
    ("state_unchanged", TENANT_CELL, 1, "bg0.update_gap"),
    ("update_doubled", TENANT_CELL, 1, "bg0.update_gap"),
])
def test_a_planted_fault_makes_the_run_incorrect(faulty_step, fault, name,
                                                 layers, caught_by):
    faulty_step(fault, layers)
    out = _measure(name, 2**33 + 12)
    assert not out["correct"]
    value, limit = out["checks"][caught_by]
    # a step that moves no weight, or moves them twice, reads about 1
    assert value > 0.5 > limit, out["checks"]


@pytest.mark.parametrize("name, role", [("qwen2-l4.fg", 0),
                                        ("minicpm.fg", 0),
                                        (TENANT_CELL, 1),
                                        ("qwen2-l28.fsdp4", 0),
                                        ("qwen2-l28.tp2x2", 0)])
def test_the_float8_control_fails_the_cells_limits(name, role):
    import jax

    config, traffic = _small(name)
    cell = spec.cell(name)
    plan = harness.Plan(config=config, traffic=traffic, seed=2**33 + 13,
                        seconds=0, trace=False, devices=jax.devices()[:1])
    got = calibrate.control_readings(plan, role)
    assert not calibrate.within_limits(got, cell.limits), got
