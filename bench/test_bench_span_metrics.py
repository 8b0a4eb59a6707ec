"""The readers of the engine's nested spans, on a synthesized run: the
device idle under ``plan`` and its children per decode step, and the
dedup map's round inside the commit."""
from types import SimpleNamespace as NS

import pytest

from bench import harness as H
from bench import trace_reduce as TR
from bench.drivers import serve

step_idle_us = H.metric_reader("serve.step_idle_us")
dedup_round_ms = H.metric_reader("serve.dedup_round_ms")


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in
                                lines.items()])


def reduced():
    """Window [0, 6000) ns; device ops leave the gaps [1000,1200),
    [3000,3500) and [5000,5800).  One batch's spans, already on the
    profile's clock: ``plan`` over [900,5500) holding ``prefill``, two
    ``token_sync`` and a ``dispatch``; then ``commit``."""
    host = plane("/host:CPU", {"python": [ev("traced_window", 0, 6000)]})
    dev = plane("/device:TPU:0", {"XLA Ops": [
        ev("fusion.1", 0, 1000), ev("fusion.2", 1200, 1800),
        ev("fusion.3", 3500, 1500), ev("fusion.4", 5800, 200)]})
    spans = [(900.0, 5500.0, "plan"), (950.0, 1100.0, "prefill"),
             (1100.0, 3100.0, "token_sync"), (3100.0, 3300.0, "dispatch"),
             (3300.0, 5100.0, "token_sync"), (5500.0, 5700.0, "commit")]
    return TR.reduce_profile([host, dev], H.LABELS, extra_labels=spans)


def driver(spans=(), calls=(), new_tokens=2, t0_ns=0):
    d = serve.Driver.__new__(serve.Driver)
    d.spans = [(s, e, n, {}) for s, e, n in spans]
    d.calls = [serve.Call(s, e, 1, 128) for s, e in calls]
    d.new_tokens = new_tokens
    d.t0_ns = t0_ns
    return d


def run(trace=None, **kw):
    return NS(trace=trace, driver=driver(**kw), traced_ns=(0, 10_000),
              config={}, peaks={})


def test_step_idle_sums_plan_and_the_spans_nested_in_it():
    red = reduced()
    # prefill 100, token_sync 100 + 300 + 100, dispatch 200, plan's own
    # 400; commit's 200 and the uncovered 100 are not the plan's
    assert red.idle == {"prefill": pytest.approx(100e-9),
                        "token_sync": pytest.approx(500e-9),
                        "dispatch": pytest.approx(200e-9),
                        "plan": pytest.approx(400e-9),
                        "commit": pytest.approx(200e-9),
                        "other": pytest.approx(100e-9)}
    # 1200 ns over one traced call of 2 decode steps
    got = step_idle_us(run(red, calls=[(0, 6000)]))
    assert got == pytest.approx(0.6)


def test_step_idle_counts_missing_names_as_zero():
    """A program with no nested spans puts the idle under ``plan`` alone;
    the metric reads the same quantity there."""
    red = TR.Reduced(window_s=1.0, busy_s=0.9,
                     idle={"plan": 0.01, "commit": 0.001})
    got = step_idle_us(run(red, calls=[(0, 10), (20, 30)], new_tokens=4))
    assert got == pytest.approx(1e6 * 0.01 / 8)
    empty = TR.Reduced(window_s=1.0, busy_s=1.0)
    assert step_idle_us(run(empty, calls=[(0, 10)])) == 0.0


def test_step_idle_without_a_trace_or_a_traced_call_is_none():
    assert step_idle_us(run(None, calls=[(0, 10)])) is None
    # the only call lies outside the traced stretch
    assert step_idle_us(run(reduced(), calls=[(0, 20_000)])) is None


def test_dedup_round_counts_only_the_rounds_inside_a_commit():
    ms = 1_000_000
    spans = [(-5 * ms, -1 * ms, "commit"), (-4 * ms, -2 * ms, "dedup_round"),
             (1 * ms, 20 * ms, "commit"), (5 * ms, 13 * ms, "dedup_round"),
             (22 * ms, 23 * ms, "dedup_round"),      # route's lookup
             (30 * ms, 50 * ms, "commit"), (31 * ms, 35 * ms, "dedup_round")]
    # the round before the window's start is not read
    assert dedup_round_ms(run(spans=spans)) == pytest.approx(6.0)


def test_dedup_round_without_the_span_is_none():
    assert dedup_round_ms(run(spans=[(0, 10, "commit")])) is None
    assert dedup_round_ms(run(spans=[(0, 10, "dedup_round")])) is None
