"""The readers of the training loop's host spans (``chipbench.spans``,
``data_idle_ms`` and ``sync_idle_ms``), on traces built by hand."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness, spans, trace  # noqa: E402

US = 1_000_000  # picoseconds in a microsecond
STEP = "jit_step_with_fsdp"


def _stats(stats, ids):
    return "".join(f"stats {{ metadata_id: {ids[k]} int64_value: {v} }} "
                   for k, v in stats.items())


def _events(evs, ids, stat_ids):
    return "".join(f"events {{ metadata_id: {ids[n]} offset_ps: {s * US} "
                   f"duration_ps: {u * US} " + _stats(st, stat_ids) + "} "
                   for n, s, u, st in evs)


def _meta(ids):
    return "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }} ' for n, i in ids.items())


def _rec(ops, modules, host, steps):
    """One chip's ``ops`` and ``modules`` ([(name, start_us, dur_us)]) and
    the host's main thread (``host``: [(name, start_us, dur_us, {stat:
    int})]) under a window of 0-2000 us, reduced as the harness does."""
    from jax.profiler import ProfileData

    stat_ids = {"program_id": 1, "step": 2}
    smeta = "".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }} ' for n, i in stat_ids.items())
    ids = {n: i + 1 for i, n in enumerate(sorted(
        {e[0] for e in ops + modules}))}
    with_pid = [(n, s, u, {"program_id": 7 if n == STEP else 9})
                for n, s, u in modules]
    dev = (f'planes {{ id: 1 name: "/device:TPU:0" '
           f'lines {{ id: 1 name: "{trace.OPS_LINE}" timestamp_ns: 0 '
           f'{_events([(*o, {}) for o in ops], ids, stat_ids)}}} '
           f'lines {{ id: 2 name: "{trace.MODULES_LINE}" timestamp_ns: 0 '
           f'{_events(with_pid, ids, stat_ids)}}} {_meta(ids)}{smeta}}}')
    host = [(trace.WINDOW_SPAN, 0, 2000, {})] + list(host)
    hids = {n: i + 1 for i, n in enumerate(sorted({h[0] for h in host}))}
    text = dev + (f'\nplanes {{ id: 99 name: "/host:CPU" lines {{ id: 1 '
                  f'name: "python3" timestamp_ns: 0 '
                  f'{_events(host, hids, stat_ids)}}} {_meta(hids)}'
                  f'{smeta}}}')
    red = trace.reduce(ProfileData.from_text_proto(text), n_devices=1)
    red["steps"] = steps
    return {"trace": red, "max_inflight": 0}


def _two_steps(host_spans):
    # two fg program runs, 100-400 and 700-1000, with a bubble between
    # ops inside the first (200-300) that is the device's own, and a
    # tenant's program at 1000-1100 right after the second
    ops = [("fusion.1", 100, 100), ("fusion.2", 300, 100),
           ("fusion.3", 700, 300), ("fusion.9", 1000, 100)]
    mods = [(STEP, 100, 300), (STEP, 700, 300), (STEP + "_bg", 1000, 100)]
    host = [(n, s, u, {"step": k}) for k, (n, s, u) in enumerate(host_spans)]
    return _rec(ops, mods, host, steps=2)


def test_idle_inside_a_program_run_is_not_attributed():
    # idle outside the runs: 0-100, 400-700, 1100-2000; the loss wait
    # covers 150-450 (only 400-450 of it idle), control 450-500, the data
    # 500-650, dispatch 650-700; 0-100 and 1100-2000 fall under no span
    rec = _two_steps([("train.loss_wait", 150, 300),
                      ("train.control", 450, 50), ("train.data", 500, 150),
                      ("train.dispatch", 650, 50)])
    assert spans.idle_by_span(rec["trace"]) == pytest.approx({
        "train.loss_wait": 0.025, "train.control": 0.025,
        "train.data": 0.075, "train.dispatch": 0.025, None: 0.5})
    assert harness.read_metric("sync_idle_ms", rec) == pytest.approx(0.05)
    assert harness.read_metric("data_idle_ms", rec) == pytest.approx(0.075)


def test_readmit_counts_as_sync_idle():
    rec = _two_steps([("train.readmit", 400, 100)])
    assert harness.read_metric("sync_idle_ms", rec) == pytest.approx(0.05)
    assert harness.read_metric("data_idle_ms", rec) == 0.0


def test_a_program_without_the_spans_reads_nothing():
    # the loop before it marked its phases: no reading, and no error
    rec = _two_steps([("np.asarray(jax.Array)", 400, 50)])
    for name in ("data_idle_ms", "sync_idle_ms"):
        assert harness.read_metric(name, rec) is None, name
        assert harness.read_metric(name, {"trace": None,
                                          "max_inflight": 0}) is None
