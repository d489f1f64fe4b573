"""The trace reduction and the peaks table, on a trace built by hand."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import peaks, trace  # noqa: E402

US = 1_000_000  # picoseconds in a microsecond


def _xspace(device_lines, host_events, n_devices=1):
    """A text XSpace: each chip gets ``device_lines`` ({line: [(name,
    start_us, dur_us, program_id)]}), the host one thread of
    ``host_events`` ([(name, start_us, dur_us)])."""
    names = sorted({ev[0] for evs in device_lines.values() for ev in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    planes = []
    for d in range(n_devices):
        lines = []
        for li, (line, evs) in enumerate(device_lines.items()):
            body = "".join(
                f"events {{ metadata_id: {ids[n]} offset_ps: {s * US} "
                f"duration_ps: {u * US} stats {{ metadata_id: 1 "
                f"int64_value: {pid} }} }} " for n, s, u, pid in evs)
            lines.append(f'lines {{ id: {li + 1} name: "{line}" '
                         f"timestamp_ns: 0 {body}}}")
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }} ' for n, i in ids.items())
        planes.append(f'planes {{ id: {d + 1} name: "/device:TPU:{d}" '
                      + " ".join(lines) + f" {meta}"
                      + 'stat_metadata { key: 1 value { id: 1 '
                      'name: "program_id" } } }')
    hnames = sorted({n for n, *_ in host_events})
    hids = {n: i + 1 for i, n in enumerate(hnames)}
    hbody = "".join(f"events {{ metadata_id: {hids[n]} offset_ps: {s * US} "
                    f"duration_ps: {u * US} }} " for n, s, u in host_events)
    hmeta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }} ' for n, i in hids.items())
    planes.append(f'planes {{ id: 99 name: "/host:CPU" lines {{ id: 1 '
                  f'name: "python3" timestamp_ns: 0 {hbody}}} {hmeta}}}')
    return "\n".join(planes)


def _reduce(device_lines, host_events, n_devices=1):
    from jax.profiler import ProfileData

    pd = ProfileData.from_text_proto(_xspace(device_lines, host_events,
                                             n_devices))
    return trace.reduce(pd, n_devices=n_devices)


WINDOW = [(trace.WINDOW_SPAN, 100, 1000), ("PjitFunction(step)", 550, 100)]


def test_busy_is_the_union_of_op_intervals_and_idle_the_rest():
    # ops 100-400 and 300-500 overlap (union 400 us); 700-900 adds 200;
    # an op before the window is left out, one across its end is clipped
    ops = [("fusion.1", 100, 300, 1), ("fusion.2", 300, 200, 1),
           ("fusion.3", 700, 200, 1), ("fusion.0", 0, 50, 1),
           ("fusion.4", 1000, 300, 1)]
    red = _reduce({trace.OPS_LINE: ops}, WINDOW)
    assert red["window_s"] == pytest.approx(1000e-6)
    assert red["busy_s"] == pytest.approx(700e-6)  # 400 + 200 + 100
    gaps = trace.gaps(red["devices"][0]["ops"], red["lo"], red["hi"])
    assert [(round(e - s)) for s, e in gaps] == [200_000, 100_000]
    b = trace.breakdown(red)
    assert b["idle_gaps"][0] == ["PjitFunction(step)", pytest.approx(200e-6)]
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(300e-6)]


def test_idle_share_is_the_mean_over_chips():
    ops = [("fusion.1", 100, 500, 1)]
    red = _reduce({trace.OPS_LINE: ops}, WINDOW, n_devices=2)
    assert red["busy_s"] == pytest.approx(500e-6)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.5)


def test_exposed_collective_time_counts_only_what_no_compute_covers():
    # the all-gather at 100-300 is covered by a fusion for 150 of its 200
    # us; the reduce-scatter at 600-700 runs alone
    ops = [("all-gather.1", 100, 200, 1), ("fusion.1", 50, 200, 1),
           ("reduce-scatter.2", 600, 100, 1), ("fusion.2", 800, 100, 1)]
    red = _reduce({trace.OPS_LINE: ops}, WINDOW)
    assert trace.exposed(red["devices"][0]["ops"]) == pytest.approx(150_000)


def test_two_programs_of_one_name_are_told_apart_by_program_id():
    # per traced step: the fg program once (id 7), the bg program twice
    # (id 9), both named jit_step_with_fsdp; a feed program runs too
    mods = []
    for k in range(3):
        t = 100 + 300 * k
        mods += [("jit_step_with_fsdp", t, 200, 7),
                 ("jit_step_with_fsdp", t + 200, 30, 9),
                 ("jit_step_with_fsdp", t + 240, 30, 9),
                 ("jit_fn", t + 280, 5, 3)]
    red = _reduce({trace.OPS_LINE: [("fusion.1", 100, 10, 7)],
                   trace.MODULES_LINE: mods}, WINDOW)
    red["steps"] = 3
    progs = trace.step_programs(red, inflight=2)
    assert len(progs["fg"]) == 3 and len(progs["bg"]) == 6
    assert {round(e - s) for s, e in progs["fg"]} == {200_000}
    assert {round(e - s) for s, e in progs["bg"]} == {30_000}


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="traced_window"):
        _reduce({trace.OPS_LINE: [("fusion.1", 0, 10, 1)]},
                [("other", 0, 10)])


def test_a_device_kind_missing_from_the_peaks_table_is_an_error():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")
