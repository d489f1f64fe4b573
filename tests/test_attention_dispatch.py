"""Which path ``models.attention.attend`` takes, and the count of it.

The backend check is forced to TPU by monkeypatching; the paths are then
only traced (``jax.eval_shape``), as the kernel cannot run on the CPU.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)


def _qkv(Sq, Skv=None, H=4, KV=2, d=64, dtype=jnp.bfloat16):
    Skv = Sq if Skv is None else Skv
    return (jax.ShapeDtypeStruct((1, Sq, H, d), dtype),
            jax.ShapeDtypeStruct((1, Skv, KV, d), dtype),
            jax.ShapeDtypeStruct((1, Skv, KV, d), dtype))


def _paths(*args, **kw):
    with attention.count_paths() as counts:
        jax.eval_shape(lambda q, k, v: attention.attend(q, k, v, **kw), *args)
    return dict(counts)


@pytest.mark.parametrize("Sq,Skv,kw,path", [
    (4096, None, {}, "flash"),
    (4096, None, {"window": 1024}, "flash"),
    (4096, None, {"causal": False}, "flash"),
    (32, None, {}, "full"),
    (4096, None, {"softcap": 30.0}, "blocked"),
    (4096, 1024, {"causal": False}, "blocked"),  # cross-attention
    (1024, 4096, {"causal": False}, "full"),
], ids=["seq4096", "window", "noncausal", "seq32", "softcap", "cross",
        "cross-short"])
def test_attend_takes_the_kernel_only_where_it_can_run(on_tpu, Sq, Skv, kw,
                                                       path):
    assert _paths(*_qkv(Sq, Skv), **kw) == {path: 1}


def test_attend_on_cpu_never_takes_the_kernel():
    assert _paths(*_qkv(4096)) == {"blocked": 1}
    assert _paths(*_qkv(32)) == {"full": 1}


def test_counters_nest_and_close():
    """Counts are taken as a program traces: each call here traces anew."""
    with attention.count_paths() as outer:
        with attention.count_paths() as inner:
            _paths(*_qkv(32))
        _paths(*_qkv(4096))
    _paths(*_qkv(32))
    assert dict(inner) == {"full": 1}
    assert dict(outer) == {"full": 1, "blocked": 1}


def test_one_traced_call_stands_for_the_layer_scan(on_tpu):
    """The model's layers run under ``lax.scan``: every layer of a step
    traces ``attend`` once."""
    from repro.configs import TRAIN_4K, run_config
    from repro.models.api import get_model, input_specs

    cfg = run_config("qwen2-1.5b", smoke=True, layers=3)
    api = get_model(cfg)
    for seq, path in [(4096, "flash"), (32, "full")]:
        shape = dataclasses.replace(TRAIN_4K, seq_len=seq, global_batch=1)
        with attention.count_paths() as counts:
            jax.eval_shape(jax.grad(lambda p, b: api.loss(p, b)[0]),
                           api.abstract(), input_specs(cfg, shape))
        assert dict(counts) == {path: 1}, seq


@pytest.mark.parametrize("Sq,kw", [(2048, {}), (2048, {"window": 300}),
                                   (2048, {"softcap": 30.0}), (32, {}),
                                   (2560, {"causal": False})],
                         ids=["blocked", "window", "softcap", "full",
                              "blocked-noncausal"])
def test_attend_on_cpu_is_the_xla_path_bit_for_bit(Sq, kw):
    """With nothing forced, the CPU output is what ``blocked_attention``
    (from 2048 positions) or ``full_attention`` give, bit for bit."""
    ks = jax.random.split(jax.random.PRNGKey(Sq), 3)
    q, k, v = (jax.random.normal(kk, s.shape, jnp.float32).astype(s.dtype)
               for kk, s in zip(ks, _qkv(Sq)))
    got = jax.jit(lambda q, k, v: attention.attend(q, k, v, **kw))(q, k, v)
    xla = attention.blocked_attention if Sq >= 2048 else \
        attention.full_attention
    want = jax.jit(lambda q, k, v: xla(q, k, v, **kw))(q, k, v)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))


@pytest.fixture(scope="module")
def smoke_run():
    """A short ``launch.train.run`` with a tenant: its report and stdout."""
    import contextlib
    import io

    from repro.launch.train import build_parser, run

    args = build_parser().parse_args(
        ["--arch", "qwen2-1.5b", "--smoke", "--steps", "2", "--seq", "64",
         "--batch", "1", "--bg-arch", "qwen2-1.5b"])
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        report = run(args)
    return report, out.getvalue()


@pytest.mark.parametrize("field,prefix,paths", [
    ("attention_paths", "attention paths", {"fg": {"full": 1}, "bg": {"full": 1}}),
    ("loss_paths", "loss paths", {"fg": {"fused": 1}, "bg": {"fused": 1}}),
], ids=["attention", "loss"])
def test_run_reports_and_prints_the_paths(smoke_run, field, prefix, paths):
    """``launch.train.run`` prints the paths of the fg step and of its
    tenants once, and keeps them on ``TrainReport``: attention by
    ``attend``'s path, and the loss head ``fused`` (its gradients formed in
    the forward pass) in both training steps."""
    from repro.launch.train import format_paths

    report, out = smoke_run
    assert getattr(report, field) == paths
    assert [ln for ln in out.splitlines() if ln.startswith(prefix + ":")] == \
        [f"{prefix}: {format_paths(paths)}"]
    assert format_paths({"fg": {"flash": 1}, "bg": {"full": 2, "blocked": 1}}
                        ) == "fg flash×1; bg blocked×1 full×2"
