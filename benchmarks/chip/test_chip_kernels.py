"""The readers of single device ops (``attention_kernel_ms``,
``collective_exposed_ms``), on traces built by hand."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness, trace  # noqa: E402
from test_chip_trace import WINDOW, _reduce  # noqa: E402

STEP = trace.STEP_PROGRAM


def _rec(ops, inflight=0, n_devices=1):
    """Two traced steps: the fg program (id 7) at 100-400 and 500-800 us,
    with ``inflight`` tenant runs (id 9) after each; ``ops`` as given.
    (A tenant is told from the fg program by its runs a step, so a test
    with tenants gives it two.)"""
    mods = []
    for t in (100, 500):
        mods.append((STEP, t, 300, 7))
        mods += [(STEP, t + 300 + 30 * k, 30, 9) for k in range(inflight)]
    red = _reduce({trace.OPS_LINE: ops, trace.MODULES_LINE: mods}, WINDOW,
                  n_devices)
    red["steps"] = 2
    return {"trace": red, "max_inflight": inflight}


def test_attention_kernel_ms_sums_the_kernels_inside_the_fg_program():
    ops = [("%flash_attention_fwd.1 = bf16[12,4096,128] custom-call()", 110, 20, 7),
           ("flash_attention_fwd.2", 140, 20, 7),  # the recompute
           ("flash_attention_bwd_dq.1", 200, 30, 7),
           ("flash_attention_bwd_dkv.1", 240, 40, 7),
           ("fusion.3", 300, 50, 7),
           ("flash_attention_fwd.1", 510, 20, 7),
           ("flash_attention_bwd_dkv.1", 600, 40, 7),
           ("flash_attention_fwd_like.1", 650, 10, 7),  # not a kernel
           # in a tenant's run, not the fg program's
           ("flash_attention_fwd.1", 401, 10, 9)]
    rec = _rec(ops, inflight=2)
    got = harness.read_metric("attention_kernel_ms", rec)
    assert got == pytest.approx((20 + 20 + 30 + 40 + 20 + 40) / 2 / 1000)


@pytest.mark.parametrize("n_devices", [1, 2])
def test_attention_kernel_ms_is_none_without_the_kernels(n_devices):
    rec = _rec([("fusion.1", 110, 20, 7), ("while.4", 200, 30, 7)],
               n_devices=n_devices)
    assert harness.read_metric("attention_kernel_ms", rec) is None
    assert harness.read_metric("attention_kernel_ms", {"trace": None}) is None


def test_attention_kernel_ms_is_the_mean_over_chips():
    rec = _rec([("flash_attention_fwd.1", 110, 40, 7)], n_devices=2)
    assert harness.read_metric("attention_kernel_ms", rec) == \
        pytest.approx(40 / 2 / 1000)


def test_collective_exposed_ms_counts_collectives_inside_the_fg_program():
    ops = [  # the layer loop's while op spans the ops it runs
        ("%while.3 = (s32[]) while(s32[] %p), body=%body.1", 110, 280, 7),
        ("%all-gather.1 = bf16[1536,8960] all-gather(bf16[384,8960] %a)",
         120, 30, 7),
        ("fusion.1", 150, 50, 7),
        ("%async-collective-start.2 = (bf16[384,12,128]) fusion(%b)",
         200, 2, 7),
        ("%async-collective-done.2 = bf16[1536,12,128] fusion(%c)",
         202, 8, 7),
        ("%slice-start.4 = (bf16[152064,384]) async-start(%d)", 210, 5, 7),
        ("reduce-scatter.4", 510, 20, 7),
        # between the fg program's runs
        ("all-reduce.9", 420, 30, 7)]
    for n in (1, 2):
        rec = _rec(ops, n_devices=n)
        got = harness.read_metric("collective_exposed_ms", rec)
        assert got == pytest.approx((30 + 2 + 8 + 20) / 2 / 1000), n


def test_collective_exposed_ms_is_none_without_collectives():
    rec = _rec([("fusion.1", 110, 20, 7), ("%while.4 = while()", 200, 30, 7),
                ("all-reduce.9", 420, 30, 7)])
    assert harness.read_metric("collective_exposed_ms", rec) is None
    assert harness.read_metric("collective_exposed_ms", {"trace": None}) \
        is None
