"""The four-chip cell on four host devices at a reduced size: the
reference's state and batch sharded over the chips, a sound run that is
correct, and the faults only a batch of several rows over several chips
can have, each of which the comparison has to fail.  All of it runs in one
child process, as JAX fixes its device count when it starts."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")
CELL = "qwen2-l28.fsdp4"
FAULTS = ("state_unchanged", "half_batch", "exchange_left_out")

CHILD = """
import json, sys, time
sys.path[:0] = [{here!r}, {src!r}]
import jax
from chipbench import faults, harness, reference, spec
from repro.configs import run_config

cell = spec.cell({cell!r})
m = run_config(cell.config["arch"], smoke=True)
config = dict(cell.config, hidden_size=m.d_model, intermediate_size=m.d_ff,
              num_attention_heads=m.num_heads,
              num_key_value_heads=m.num_kv_heads, head_dim=m.d_head,
              num_hidden_layers=m.num_layers, vocab_size=m.vocab_size)
traffic = dict(cell.traffic, seq=64)
out = {{"devices": len(jax.devices())}}

from repro.train import loop
program_train = loop.train
def mesh_spy(cfg, shape, mesh, tc, **k):
    out["program_mesh"] = dict(mesh.shape)
    return program_train(cfg, shape, mesh, tc, **k)
loop.train = mesh_spy

seen = {{}}
sound_run = reference.run
def spy(model, c, t, total, words, role, batches, shardings=None, **k):
    rows = batches(0)["tokens"].addressable_shards
    seen["batch_rows"] = sorted(s.data.shape[0] for s in rows)
    seen["batch_devices"] = len({{s.device for s in rows}})
    seen["weights_by_last_dim"] = sorted({{
        (s.spec[-1] if len(s.spec) else None) == "d"
        for s in jax.tree.leaves(shardings)}})
    return sound_run(model, c, t, total, words, role, batches,
                     shardings=shardings, **k)
reference.run = spy

def measure(seed):
    return harness.measure({cell!r}, seed, 0.3, False, time.perf_counter(),
                           require_tpu=False, config=config, traffic=traffic,
                           extra_args=["--smoke"])

out["sound"] = measure(2**33 + 31)
out["shards"] = dict(seen)
for fault in {faults!r}:
    with faults.planted(fault, config["num_hidden_layers"], traffic["data"],
                        traffic["model"]):
        out[fault] = measure(2**33 + 32)

if {agreement!r}:
    plans = [harness.Plan(config=config, traffic=traffic, seed=2**33 + 33,
                          seconds=0, trace=False, devices=jax.devices()[:n])
             for n in (4, 1)]
    four, one = [harness.reference_readings(p, 0) for p in plans]
    out["agreement"] = {{k: {{leaf: [four[k][leaf], one[k][leaf]]
                              for leaf in one[k]}}
                         for k in ("grad_norms", "delta_norms")}}
print("RESULT " + json.dumps(out, default=str))
"""


def run_on_four_devices(cell, faults, agreement):
    """The child above for ``cell``: a sound run, one run with each of
    ``faults`` planted, and with ``agreement`` the sharded reference
    against one device's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = textwrap.dedent(CHILD.format(here=HERE, src=SRC, cell=cell,
                                        faults=faults, agreement=agreement))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    [line] = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


@pytest.fixture(scope="module")
def four_devices():
    return run_on_four_devices(CELL, FAULTS, agreement=True)


def test_the_reference_shards_its_state_and_batch_over_four_chips(
        four_devices):
    assert four_devices["devices"] == 4
    # one row of the batch a chip, every weight and moment split four ways
    assert four_devices["shards"] == {"batch_rows": [1, 1, 1, 1],
                                      "batch_devices": 4,
                                      "weights_by_last_dim": [True]}


def test_the_sharded_reference_agrees_with_one_chips(four_devices):
    # the same float32 sums in another order: gradient norms agree to
    # ~1e-6 of a leaf's norm; after three Adam steps the change of a leaf
    # to ~1e-4 (Adam divides round-off by the root of a small moment); the
    # cell's limits on these gaps are 0.013 and 0.004
    for kind, tol in (("grad_norms", 1e-5), ("delta_norms", 1e-3)):
        got = four_devices["agreement"][kind]
        med = sorted(b for _, b in got.values())[len(got) // 2]
        gaps = {leaf: abs(a - b) / max(b, med)
                for leaf, (a, b) in got.items()}
        assert max(gaps.values()) < tol, (kind, gaps)


# The limits are the chip-size cell's.  At this toy size three Adam steps
# of a 2-layer model read update_gap ~0.006, over the 0.004 set from the
# chip's 0.0004-0.0006: the toy's runs are held to the other numbers.
TOY_HELD = ("window_compiles", "failed_steps", "grad_gap", "grad_diff")


def test_a_sound_run_on_four_chips_is_correct(four_devices):
    out = four_devices["sound"]
    assert all(out["checks"][k][0] <= out["checks"][k][1] for k in TOY_HELD), \
        out["checks"]
    assert out["device"]["count"] == 4
    assert four_devices["program_mesh"] == {"data": 4, "model": 1}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_on_four_chips_makes_the_run_incorrect(four_devices, fault):
    out = four_devices[fault]
    assert not out["correct"], out["checks"]
    # each fault moves the first gradient itself, far past its limit
    value, limit = out["checks"]["grad_diff"]
    assert value > 5 * limit, out["checks"]
