"""The trace reduction and the peaks table, on a synthesized profile."""
from types import SimpleNamespace as NS

import pytest

from bench import harness as H
from bench import trace_reduce as TR


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in
                                lines.items()])


def profile():
    """Window [100, 1100) ns.  Device ops: [100,300), [250,400) overlap,
    [600,700), and [1000,1200) which the window cuts at 1100.  Host:
    wait_arrival over [400,600), serve_call over [650,1100) with a
    nested device_to_host over [800,1000)."""
    host = plane("/host:CPU", {"python": [
        ev("traced_window", 100, 1000), ev("wait_arrival", 400, 200),
        ev("serve_call", 650, 450), ev("device_to_host", 800, 200)]})
    dev = plane("/device:TPU:0", {
        "XLA Ops": [ev("fusion.1", 100, 200), ev("all-to-all.3", 250, 150),
                    ev("fusion.2", 600, 100), ev("copy.4", 1000, 200)],
        "XLA Modules": [ev("jit_decode_step(7)", 100, 300),
                        ev("jit_decode_step(8)", 600, 100),
                        ev("jit__lambda_(9)", 1000, 200)]})
    return [host, dev]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    red = TR.reduce_profile(profile(), H.LABELS)
    assert red.window_s == pytest.approx(1000e-9)
    # [100,400) + [600,700) + [1000,1100)
    assert red.busy_s == pytest.approx(500e-9)


def test_programs_are_summed_by_name_without_run_ids():
    red = TR.reduce_profile(profile(), H.LABELS)
    assert red.programs["jit_decode_step"] == (pytest.approx(400e-9), 2)
    assert red.programs["jit__lambda_"] == (pytest.approx(100e-9), 1)
    sec, runs = red.program_seconds(lambda n: "decode" in n)
    assert (sec, runs) == (pytest.approx(400e-9), 2)


def test_idle_gaps_go_to_the_innermost_host_label():
    red = TR.reduce_profile(profile(), H.LABELS)
    # gaps: [400,600) wait_arrival; [700,1000): serve_call over [700,800)
    # and device_to_host (inner) over [800,1000)
    assert red.idle == {"wait_arrival": pytest.approx(200e-9),
                        "serve_call": pytest.approx(100e-9),
                        "device_to_host": pytest.approx(200e-9)}
    assert sum(red.idle.values()) == pytest.approx(
        red.window_s - red.busy_s)


def test_uncovered_gaps_are_other_and_extra_spans_take_part():
    red = TR.reduce_profile(profile(), ("serve_call",),
                            extra_labels=[(850.0, 900.0, "commit")])
    assert red.idle["other"] == pytest.approx(200e-9)
    assert red.idle["commit"] == pytest.approx(50e-9)
    assert red.idle["serve_call"] == pytest.approx(250e-9)


def test_collective_time_with_nothing_beside_it():
    red = TR.reduce_profile(profile(), H.LABELS)
    # all-to-all [250,400) overlaps fusion.1 up to 300
    assert red.collective_alone_s == pytest.approx(100e-9)


def test_breakdown_lists_the_largest_first():
    b = TR.reduce_profile(profile(), H.LABELS).breakdown(top=2)
    assert [n for n, _ in b["device_ops"]] == ["fusion.1", "all-to-all.3"]
    assert len(b["idle_gaps"]) == 2
    assert b["idle_gaps"][0][0] == "wait_arrival"


def test_a_trace_without_the_window_or_a_device_is_refused():
    host, dev = profile()
    with pytest.raises(ValueError, match="traced_window"):
        TR.reduce_profile([dev], H.LABELS)
    with pytest.raises(ValueError, match="device plane"):
        TR.reduce_profile([host], H.LABELS)


def test_helpers():
    assert TR.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert TR.gaps_in([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert TR.merge([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert TR.covered([(0, 1), (2, 3)], 0.5, 2.5) == 1.0
    assert TR.program_name("jit_lookup(123)") == "jit_lookup"
    assert TR.op_name("%fusion.76 = bf16[8] fusion(bf16[8] %p)") == "fusion.76"


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_raise():
    assert H.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert H.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        H.peaks_for("TPU v9 imaginary")
