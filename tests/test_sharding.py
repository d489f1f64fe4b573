"""Sharding rules: divisibility guard, per-arch layouts, hypothesis props."""
import jax
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 must collect without hypothesis
    from _prop import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import DECODE_32K, LONG_500K, TRAIN_4K, get_config
from repro.dist.sharding import RuleReport, pspec, sharding_rules
from repro.launch.mesh import largest_pow2_mesh, make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(1, 1)  # rules logic is mesh-shape driven; use axis names


class FakeMesh:
    """Mesh stand-in (axis names + sizes) — pspec only reads these."""

    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.devices = np.zeros(tuple(axes.values()))


M = FakeMesh(data=16, model=16)
MP = FakeMesh(pod=2, data=16, model=16)


def test_divisibility_guard_drops():
    rep = RuleReport()
    # 36 heads on a 16-way axis -> dropped
    spec = pspec(("embed", "heads", "head_dim"), (2304, 36, 64),
                 {"embed": ("data",), "heads": ("model",), "head_dim": ()}, M, rep)
    assert spec == P("data")  # trailing None trimmed
    assert rep.dropped == [("heads", 36, 16)]


def test_divisible_keeps():
    spec = pspec(("embed", "heads", "head_dim"), (8192, 64, 128),
                 {"embed": ("data",), "heads": ("model",), "head_dim": ()}, M)
    assert spec == P("data", "model")


def test_no_axis_reuse():
    # same mesh axis can't shard two dims of one array
    spec = pspec(("mlp", "mlp"), (256, 256), {"mlp": ("model",)}, M)
    assert spec == P("model")  # second dim dropped (trailing None trimmed)


def test_rules_minicpm_attention_replicated():
    cfg = get_config("minicpm-2b")
    rules = sharding_rules(cfg, M, TRAIN_4K)
    assert rules["heads"] == () and rules["kv_heads"] == ()
    assert rules["mlp"] == ("model",)  # 5760 % 16 == 0


def test_rules_moe_modes():
    qwen = get_config("qwen3-moe-30b-a3b")
    r = sharding_rules(qwen, M, TRAIN_4K)
    assert r["expert"] == ("model",) and r["moe_mlp"] == ()
    grok = get_config("grok-1-314b")
    r = sharding_rules(grok, M, TRAIN_4K)
    assert r["expert"] == () and r["moe_mlp"] == ("model",)


def test_rules_decode_kv_fallbacks():
    qwen72 = get_config("qwen2-72b")  # kv=8 not divisible by 16
    r = sharding_rules(qwen72, M, DECODE_32K)
    assert r["act_kv_seq"] == ("model",)
    # long context (batch=1): sequence shards over DP axes
    zamba = get_config("zamba2-2.7b")
    r = sharding_rules(zamba, MP, LONG_500K)
    assert r["act_kv_seq"] == ("pod", "data")
    assert r["act_batch"] == ()


def test_rules_serving_drops_fsdp_for_small_models():
    small = get_config("qwen2-1.5b")
    assert sharding_rules(small, M, DECODE_32K)["embed"] == ()
    big = get_config("grok-1-314b")
    assert sharding_rules(big, M, DECODE_32K)["embed"] == ("data",)


def test_largest_pow2_mesh():
    m = largest_pow2_mesh(1)
    assert m.devices.size == 1


@settings(max_examples=60, deadline=None)
@given(
    st.lists(st.integers(1, 4096), min_size=1, max_size=4),
    st.sampled_from(["embed", "heads", "mlp", "vocab", "norm"]),
)
def test_property_pspec_always_valid(dims, axis):
    """The guard guarantees: every sharded dim is divisible by its axes."""
    rules = {"embed": ("data",), "heads": ("model",), "mlp": ("model",),
             "vocab": ("model",), "norm": ()}
    axes = tuple(axis for _ in dims)
    spec = pspec(axes, tuple(dims), rules, M)
    sizes = {"data": 16, "model": 16}
    for dim, s in zip(dims, tuple(spec) + (None,) * (len(dims) - len(spec))):
        if s is None:
            continue
        parts = s if isinstance(s, tuple) else (s,)
        total = int(np.prod([sizes[a] for a in parts]))
        assert dim % total == 0


def test_mesh_builders_use_auto_axes():
    """JAX 0.9's ``jax.make_mesh`` defaults to Explicit axes, which every
    ``with_sharding_constraint`` in the models rejects: each builder in
    launch/mesh.py must hand back all-Auto axes.  The production meshes
    need 256/512 devices, so they are built in a subprocess."""
    import os
    import subprocess
    import sys
    import textwrap

    from jax.sharding import AxisType

    from repro.launch.mesh import submesh_from_range

    for m in (make_mesh(1, 1), make_mesh(1, 1, pod=1), largest_pow2_mesh(1),
              submesh_from_range(0, 1)):
        assert m.axis_types == (AxisType.Auto,) * len(m.axis_names), m
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    code = textwrap.dedent("""
        from jax.sharding import AxisType
        from repro.launch.mesh import make_mesh, make_production_mesh
        for m in (make_production_mesh(), make_production_mesh(multi_pod=True),
                  make_mesh(4, 2, pod=2)):
            assert m.axis_types == (AxisType.Auto,) * len(m.axis_names), m
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]
