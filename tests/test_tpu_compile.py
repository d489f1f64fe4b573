"""Compiles for a described (not attached) TPU v5e: the Pallas kernels at
the widths of the configs that use them, and the training steps of
``chip_smoke.py`` with their memory.  Interpret mode cannot show what the
chip's compiler refuses (tiling, fast-memory limits, programs that do not
fit); these compiles can.  Nothing runs, so they say nothing of results or
times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, so a module-level
call would break every other pytest worker.
"""
import dataclasses
import importlib.util
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _flash(s):
    from repro.kernels.flash_attention import flash_attention

    # qwen2-1.5b: 12 q heads, 2 kv heads, d_head 128, seq 4096
    B, S, H, KV, D = 1, 4096, 12, 2, 128
    return jax.jit(flash_attention).lower(
        _sds(s, (B, S, H, D)), _sds(s, (B, S, KV, D)), _sds(s, (B, S, KV, D)))


def _ssd(s):
    from repro.kernels.ssd_scan import ssd_scan

    # zamba2-2.7b: d_inner 5120 in 80 heads of 64, ssm state 64
    B, S, H, P, N = 1, 4096, 80, 64, 64
    return jax.jit(ssd_scan).lower(
        _sds(s, (B, S, H, P)), _sds(s, (B, S, H), jnp.float32),
        _sds(s, (H,), jnp.float32), _sds(s, (B, S, N)), _sds(s, (B, S, N)))


def _wkv(s):
    from repro.kernels.wkv6 import wkv6

    # rwkv6-1.6b: d_model 2048 in 32 heads of 64
    B, S, H, K = 1, 4096, 32, 64
    return jax.jit(wkv6).lower(*(_sds(s, (B, S, H, K)) for _ in range(4)),
                               _sds(s, (H, K), jnp.float32))


@pytest.mark.parametrize("lower", [_flash, _ssd, _wkv],
                         ids=["flash_attention", "ssd_scan", "wkv6"])
def test_kernel_compiles_for_v5e(one_chip, lower):
    compiled = lower(one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
           "flash_attention_bwd_dkv")


def _kernel_calls(hlo: str) -> set:
    """The Pallas kernels the compiled program calls, by instruction name."""
    return {k for k in KERNELS if f"%{k}" in hlo}


@pytest.mark.parametrize("H,KV,D", [(36, 36, 64), (12, 2, 128)],
                         ids=["minicpm-2b", "qwen2-1.5b"])
def test_flash_attention_grad_compiles_for_v5e(one_chip, H, KV, D):
    """The forward and both backward kernels at the benchmark's widths
    (head width 64 is a whole-dimension block), seq 4096."""
    from repro.kernels.flash_attention import flash_attention

    B, S = 1, 4096

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v).astype(jnp.float32))

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        _sds(one_chip, (B, S, H, D)), _sds(one_chip, (B, S, KV, D)),
        _sds(one_chip, (B, S, KV, D))).compile().as_text()
    assert _kernel_calls(hlo) == set(KERNELS)


def _step_bytes(topo, cfg, seq, batch, carving=(1, 1)):
    """Bytes one compiled training step holds on each chip of a
    (data, model) ``carving`` of the described chips: its arguments,
    temporaries and outputs, less the donated outputs that reuse argument
    buffers."""
    from repro.configs import TRAIN_4K
    from repro.models.api import get_model, input_specs
    from repro.optim.optimizer import make_optimizer
    from repro.train.state import abstract_state
    from repro.train.step import jit_train_step

    n = carving[0] * carving[1]
    mesh = Mesh(np.array(topo.devices[:n]).reshape(carving), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    shape = dataclasses.replace(TRAIN_4K, seq_len=seq, global_batch=batch,
                                name="chip")
    api = get_model(cfg)
    opt = make_optimizer(cfg)
    fn, _, _ = jit_train_step(api, opt, mesh, shape)
    compiled = fn.lower(abstract_state(api, opt), input_specs(cfg, shape)) \
        .compile()
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes), compiled


def _smoke_bytes(topo, cs, layers):
    """fg step at ``layers`` plus the same-device tenant's step, counted
    as if both programs' temporaries were live at once."""
    from repro.configs import run_config

    fg, _ = _step_bytes(topo, run_config(cs.ARCH, layers=layers), cs.SEQ, 1)
    bg, _ = _step_bytes(topo, run_config(cs.ARCH, layers=cs.BG_LAYERS), 32, 2)
    return fg, bg


def test_chip_smoke_train_step_fits_one_chip(topo):
    cs = _chip_smoke()
    fg, bg = _smoke_bytes(topo, cs, cs.LAYERS)
    assert fg + bg <= cs.HBM_BYTES - cs.HEADROOM_BYTES, (fg, bg)


def test_chip_smoke_depth_is_the_deepest_that_fits(topo):
    cs = _chip_smoke()
    fg, bg = _smoke_bytes(topo, cs, cs.LAYERS + 1)
    assert fg + bg > cs.HBM_BYTES - cs.HEADROOM_BYTES, (fg, bg)


@pytest.mark.parametrize("carving", [(4, 1), (2, 2)], ids=["data4", "data2-model2"])
def test_chip_smoke_full_depth_fits_each_of_four_chips(topo, carving):
    """``chip_smoke.py --four-chips`` trains all layers at seq 4096, batch 4,
    on both carvings of the 2x2 host: each chip's share must fit."""
    from repro.configs import run_config

    cs = _chip_smoke()
    cfg = run_config(cs.ARCH)
    per_chip, _ = _step_bytes(topo, cfg, cs.SEQ, cs.FOUR_CHIP_BATCH, carving)
    assert per_chip <= cs.HBM_BYTES, per_chip


def test_full_depth_step_runs_the_kernel_in_shard_map_on_four_chips(
        topo, monkeypatch):
    """On a TPU backend the (4,1) full-depth step takes the flash kernel,
    inside ``shard_map`` (GSPMD cannot partition a custom call), and each
    chip's share still fits."""
    from repro.configs import run_config
    from repro.models import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cs = _chip_smoke()
    with attention.count_paths() as paths:
        per_chip, compiled = _step_bytes(topo, run_config(cs.ARCH), cs.SEQ,
                                         cs.FOUR_CHIP_BATCH, (4, 1))
    assert dict(paths) == {"flash": 1}
    assert _kernel_calls(compiled.as_text()) == set(KERNELS)
    assert per_chip <= cs.HBM_BYTES, per_chip
