"""NVTrace observability stack: histogram correctness, span/event
accounting, compile-stall attribution, and the trim-backoff counters.

The histogram tests pin the quantile *bound* the module promises
(``oracle <= quantile(q) <= oracle * growth`` for in-range data) against
a sorted-array oracle, the overflow-bucket contract, and merge
associativity — the property that makes cross-shard snapshot merging
order-independent.  The span tests exercise the innermost-span charging
rule against a real ``StagedIO`` instruction stream and cross-validate
the listener's totals against a ``PersistTrace`` on the same stream via
``FaultsTee``.  The serving tests pin where the engine's spans sit, how
they link, and that their profiler annotations share the profile's
clock.
"""
import json
import math

import numpy as np
import pytest

from repro.analysis.trace import PersistTrace
from repro.obs.compile import CompileTracker
from repro.obs.metrics import Histogram, MetricsRegistry, log_bounds
from repro.obs.spans import FaultsTee, PersistListener, Tracer
from repro.persistence.manifest import StagedIO
from repro.serving.engine import RequestLog


def _oracle(sorted_vals, q):
    """The exact q-quantile under the histogram's rank convention."""
    n = len(sorted_vals)
    return sorted_vals[min(max(1, math.ceil(q * n)), n) - 1]


# --------------------------------------------------------------------- #
# histogram correctness                                                  #
# --------------------------------------------------------------------- #
def test_log_bounds_cover_and_validate():
    assert log_bounds(1.0, 8.0, 2.0) == (1.0, 2.0, 4.0, 8.0)
    b = log_bounds(0.5, 1e6, 1.25)
    assert b[0] == 0.5 and b[-1] >= 1e6 and b[-2] < 1e6
    for lo, hi, g in ((0.0, 1.0, 2.0), (2.0, 1.0, 2.0), (1.0, 2.0, 1.0)):
        with pytest.raises(ValueError, match="need lo > 0"):
            log_bounds(lo, hi, g)


def test_quantile_bounded_by_oracle_across_buckets():
    """For in-range data the quantile never under-reports and never
    over-reports by more than one bucket ratio — including values that
    land exactly on bucket edges."""
    h = Histogram(lo=1.0, hi=1e4, growth=1.3)
    rng = np.random.default_rng(0)
    vals = np.concatenate([
        rng.uniform(1.0, 1e4, 400),
        np.asarray(h.bounds[:8]),            # exact edges
        np.asarray(h.bounds[:8]) * 1.0001,   # just past the edges
    ])
    for v in vals:
        h.record(float(v))
    s = np.sort(vals)
    for q in (0.01, 0.1, 0.5, 0.9, 0.99, 0.999, 1.0):
        oracle = _oracle(s, q)
        assert oracle <= h.quantile(q) <= oracle * h.growth


def test_quantile_overflow_returns_observed_max_and_empty_is_nan():
    h = Histogram(lo=1.0, hi=10.0, growth=2.0)
    assert math.isnan(h.quantile(0.5))
    for v in (5.0, 100.0, 200.0):
        h.record(v)
    assert h.quantile(0.3) == 8.0      # rank 1 -> bucket (4, 8]
    assert h.quantile(0.5) == 200.0    # rank 2: overflow -> observed max
    assert h.quantile(1.0) == 200.0
    assert h.min == 5.0 and h.max == 200.0


def test_merge_is_associative_and_rejects_layout_mismatch():
    rng = np.random.default_rng(1)
    chunks = [rng.uniform(0.5, 5e4, 100) for _ in range(3)]

    def hist_of(*datasets):
        h = Histogram(lo=1.0, hi=1e4, growth=1.5)
        for d in datasets:
            for v in d:
                h.record(float(v))
        return h

    parts = [hist_of(c) for c in chunks]
    left = hist_of()                   # (a + b) + c
    left.merge(parts[0]); left.merge(parts[1]); left.merge(parts[2])
    ab = hist_of(); ab.merge(parts[1]); ab.merge(parts[2])
    right = hist_of(); right.merge(parts[0]); right.merge(ab)
    direct = hist_of(*chunks)
    for h in (left, right):
        assert h.counts == direct.counts
        assert h.sum == pytest.approx(direct.sum)
        assert (h.min, h.max) == (direct.min, direct.max)
    with pytest.raises(ValueError, match="different"):
        left.merge(Histogram(lo=1.0, hi=1e4, growth=2.0))


def test_merge_snapshot_order_independent():
    """Cross-shard folding: three shard snapshots merged in any order
    give the same registry state (counters/histograms add, and the
    quantiles of the merged histogram match a direct recording)."""
    rng = np.random.default_rng(2)
    shard_vals = [rng.uniform(1.0, 1e3, 50) for _ in range(3)]
    snaps = []
    for i, vals in enumerate(shard_vals):
        reg = MetricsRegistry()
        reg.counter("ops_total", layer="log").inc(10 * (i + 1))
        h = reg.histogram("lat_us", lo=1.0, hi=1e3, growth=1.25)
        for v in vals:
            h.record(float(v))
        snaps.append(json.loads(json.dumps(reg.snapshot())))
    merged = []
    for order in ((0, 1, 2), (2, 0, 1), (1, 2, 0)):
        reg = MetricsRegistry()
        for i in order:
            reg.merge_snapshot(snaps[i])
        merged.append(reg)
    base = merged[0]
    assert base.counter("ops_total", layer="log").value == 60
    h0 = base.histogram("lat_us", lo=1.0, hi=1e3, growth=1.25)
    assert h0.count == 150
    for reg in merged[1:]:
        h = reg.histogram("lat_us", lo=1.0, hi=1e3, growth=1.25)
        assert h.counts == h0.counts             # exact: integer adds
        assert (h.min, h.max) == (h0.min, h0.max)
        assert h.sum == pytest.approx(h0.sum)    # float adds reassociate
        assert reg.counter("ops_total", layer="log").value == 60
    s = np.sort(np.concatenate(shard_vals))
    for q in (0.5, 0.99):
        assert _oracle(s, q) <= h0.quantile(q) <= _oracle(s, q) * h0.growth


def test_registry_kind_conflict_and_monotone_counter():
    reg = MetricsRegistry()
    reg.counter("x_total").inc()
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    with pytest.raises(ValueError, match="monotone"):
        reg.counter("x_total").inc(-1)
    gen = reg.gen
    reg.reset()
    assert reg.gen == gen + 1 and reg.entries() == []


def test_prometheus_export_shape():
    reg = MetricsRegistry()
    reg.counter("ops_total", layer="log").inc(3)
    h = reg.histogram("lat_us", lo=1.0, hi=4.0, growth=2.0)
    for v in (0.5, 3.0, 99.0):
        h.record(v)
    text = reg.to_prometheus()
    assert "# TYPE ops_total counter" in text
    assert '# TYPE lat_us histogram' in text
    assert 'ops_total{layer="log"} 3' in text
    assert 'lat_us_bucket{le="+Inf"} 3' in text
    assert "lat_us_count 3" in text


# --------------------------------------------------------------------- #
# snapshot round-trip (hypothesis when available)                        #
# --------------------------------------------------------------------- #
def _roundtrip(counter_n, gauge_v, hist_vals):
    reg = MetricsRegistry()
    reg.counter("c_total", layer="log").inc(counter_n)
    reg.gauge("g", shard="0").set(gauge_v)
    h = reg.histogram("h_us", lo=1.0, hi=1e5, growth=1.5, phase="commit")
    for v in hist_vals:
        h.record(v)
    snap = json.loads(json.dumps(reg.snapshot()))   # the wire format
    twin = MetricsRegistry.from_snapshot(snap)
    assert twin.snapshot() == reg.snapshot()
    twin.merge_snapshot(snap)                        # self-merge doubles
    assert twin.counter("c_total", layer="log").value == 2 * counter_n
    h2 = twin.histogram("h_us", lo=1.0, hi=1e5, growth=1.5, phase="commit")
    assert h2.count == 2 * len(hist_vals)
    assert twin.gauge("g", shard="0").value == gauge_v


try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @settings(max_examples=25, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(0, 10 ** 9),
           st.floats(-1e6, 1e6, allow_nan=False),
           st.lists(st.floats(0.0, 1e9, allow_nan=False,
                              allow_infinity=False), max_size=60))
    def test_snapshot_roundtrip_property(counter_n, gauge_v, hist_vals):
        """snapshot -> JSON text -> from_snapshot is exact for every
        metric kind, including empty and overflow-heavy histograms."""
        _roundtrip(counter_n, gauge_v, hist_vals)

except ImportError:        # hypothesis optional: keep fixed probes
    def test_snapshot_roundtrip_property():
        _roundtrip(7, -3.5, [0.0, 1.0, 17.3, 1e9])
        _roundtrip(0, 0.0, [])


# --------------------------------------------------------------------- #
# spans + persistence-event listener                                     #
# --------------------------------------------------------------------- #
def test_innermost_span_gets_the_instruction_bill(tmp_path):
    """The paper's asymmetry as the tracer reports it: a traversal-style
    span persists nothing, the commit span pays every instruction; a
    nested span takes the bill from its parent while it is innermost."""
    reg = MetricsRegistry()
    tr = Tracer(registry=reg)
    io = StagedIO(tmp_path / "log")
    PersistListener(tracer=tr, registry=reg).attach(io)
    with tr.span("plan"):
        pass                                     # traversal: free
    with tr.span("commit") as commit:
        io.write("a.tmp", b"x")
        with tr.span("flush_fence") as inner:
            io.flush("a.tmp")
            io.fence()
        io.publish("a.tmp", "a")
    assert commit.counts == {"write": 1, "publish": 1}
    assert inner.counts == {"flush": 1, "fence": 1}
    recs = tr.records()
    assert [r["span"] for r in recs] == ["plan", "flush_fence", "commit"]
    assert recs[0]["counts"] == {} and recs[0]["dur_us"] >= 0
    assert [r["depth"] for r in recs] == [0, 1, 0]
    assert [r["parent"] for r in recs] == [None, commit.id, None]
    assert len({r["id"] for r in recs}) == 3
    assert tr.totals == {"write": 1, "flush": 1, "fence": 1, "publish": 1}
    assert tr.span_counts == tr.totals           # every event was in-span
    assert reg.counter("persist_events_total", kind="fence").value == 1
    assert reg.histogram("span_us", phase="commit").count == 1


def test_disabled_tracer_is_a_noop(tmp_path):
    reg = MetricsRegistry()
    tr = Tracer(registry=reg, enabled=False)
    with tr.span("commit") as s:
        assert s is None
    assert tr.records() == [] and reg.entries() == []


def test_tracer_survives_registry_reset(tmp_path):
    """The gen-keyed handle caches re-resolve after reset(): post-reset
    spans/events land in the *new* registry entries, not orphans."""
    reg = MetricsRegistry()
    tr = Tracer(registry=reg)
    io = StagedIO(tmp_path / "log")
    PersistListener(tracer=tr, registry=reg).attach(io)
    with tr.span("commit"):
        io.write("a", b"x")
    reg.reset()
    with tr.span("commit"):
        io.write("b", b"y")
    assert reg.histogram("span_us", phase="commit").count == 1
    assert reg.counter("persist_events_total", kind="write").value == 1


def test_faults_tee_cross_validates_listener_against_trace(tmp_path):
    """One instruction stream, two sinks: the listener's totals (and the
    tracer's) must equal the PersistTrace's per-kind event counts."""
    reg = MetricsRegistry()
    tr = Tracer(registry=reg)
    listener = PersistListener(tracer=tr, registry=reg)
    trace = PersistTrace()
    io = StagedIO(tmp_path / "log")
    FaultsTee(trace, listener).attach(io)
    with tr.span("workload"):
        for i in range(5):
            io.write(f"f{i}.tmp", b"v")
            io.flush(f"f{i}.tmp")
        io.fence()
        for i in range(5):
            io.publish(f"f{i}.tmp", f"f{i}")
        io.unlink("f0")
    by_kind = {}
    for ev in trace.events:
        by_kind[ev.kind] = by_kind.get(ev.kind, 0) + 1
    assert by_kind == {"write": 5, "flush": 5, "fence": 1,
                       "publish": 5, "trim": 1}
    assert listener.totals == by_kind
    assert tr.totals == by_kind and tr.span_counts == by_kind
    # the trace side kept its CrashPlan site numbering too
    assert [s.kind for s in trace.sites].count("publish") == 5


# --------------------------------------------------------------------- #
# the serving engine's spans                                             #
# --------------------------------------------------------------------- #
N_NEW = 3
TRAVERSAL = ("prefill", "token_sync", "dispatch")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A tiny engine that served one call of three batches: rids 0-1 and
    2 at prompt length 8, rids 3-4 at 12.  Returns the engine, the
    requests, its registry and the span records of that call."""
    import jax

    from repro.configs.registry import get_arch, tiny
    from repro.models.model import build_model
    from repro.serving.engine import ServeEngine
    cfg = tiny(get_arch("qwen3-1.7b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reg = MetricsRegistry()
    eng = ServeEngine(model, params, max_len=32, batch_size=2,
                      log_dir=tmp_path_factory.mktemp("log"), registry=reg)
    rng = np.random.default_rng(0)
    reqs = {rid: rng.integers(0, cfg.vocab, size=S).astype(np.int32)
            for rid, S in enumerate((8, 8, 8, 12, 12))}
    eng.serve(reqs, n_new=N_NEW)
    return eng, reqs, reg, eng.tracer.records()


def _children(recs):
    kids = {}
    for r in recs:
        kids.setdefault(r["parent"], []).append(r)
    return kids


def _root_phases(recs):
    kids = _children(recs)
    (root,) = [r for r in recs if r["span"] == "serve"]
    return root, kids, {name: [r for r in kids[root["id"]]
                               if r["span"] == name]
                        for name in ("route", "plan", "commit")}


def test_serve_spans_nest_where_the_work_happens(served):
    _, _, _, recs = served
    root, kids, ph = _root_phases(recs)
    assert root["parent"] is None and root["depth"] == 0
    assert sorted(r["span"] for r in kids[root["id"]]) == \
        ["commit"] * 3 + ["plan"] * 3 + ["route"]
    (route,) = ph["route"]
    assert [(r["span"], r["meta"]) for r in kids[route["id"]]] == \
        [("dedup_round", {"op": "lookup"})]
    for p in ph["plan"]:
        assert [r["span"] for r in kids[p["id"]]] == \
            ["prefill"] + ["token_sync", "dispatch"] * N_NEW
    for c in ph["commit"]:
        inner = {r["span"]: r for r in kids[c["id"]]}
        assert len(kids[c["id"]]) == 2 and set(inner) == {"dedup_round",
                                                          "flush_fence"}
        assert inner["dedup_round"]["meta"] == {"op": "update"}


def test_plan_and_commit_carry_the_batch_rids(served):
    _, _, _, recs = served
    _, _, ph = _root_phases(recs)
    batches = [[0, 1], [2], [3, 4]]
    assert [p["meta"]["rids"] for p in ph["plan"]] == batches
    assert [p["meta"]["n"] for p in ph["plan"]] == [2, 1, 2]
    assert [c["meta"]["rids"] for c in ph["commit"]] == batches


def test_traversal_spans_persist_nothing(served):
    """The paper's asymmetry inside the decode loop: the traversal's
    spans charge no persistence instruction, the commit pays."""
    _, _, _, recs = served
    trav = [r for r in recs if r["span"] in TRAVERSAL]
    assert len(trav) == 3 * (1 + 2 * N_NEW)
    assert all(r["counts"] == {} for r in trav)
    assert all(r["counts"].get("write") == 1
               for r in recs if r["span"] == "commit")


def test_every_parent_is_an_enclosing_span(served):
    _, _, _, recs = served
    by_id = {r["id"]: r for r in recs}
    assert len(by_id) == len(recs)
    for r in recs:
        if r["parent"] is None:
            assert r["depth"] == 0
            continue
        p = by_id[r["parent"]]
        assert p["depth"] == r["depth"] - 1
        assert p["t_us"] <= r["t_us"]
        assert (r["t_us"] + r["dur_us"]
                <= p["t_us"] + p["dur_us"] + 1e-6)


def test_request_latency_runs_from_the_call_entry(served):
    """A rid of a later batch waits for the batches before it: its
    latency covers every plan and commit up to its own."""
    _, reqs, reg, recs = served
    _, _, ph = _root_phases(recs)
    h = reg.histogram("serve_request_us", lo=1.0, hi=1e8, growth=1.25)
    assert h.count == len(reqs)
    work = [p["dur_us"] + c["dur_us"]
            for p, c in zip(ph["plan"], ph["commit"])]
    assert h.min >= work[0]
    assert h.max >= sum(work)


def test_span_annotations_share_the_profile_clock(served, tmp_path):
    """Every span is also a profiler annotation of its name; moved by the
    offset of a ``traced_window`` annotation opened as the benchmark
    opens it, each ring record starts within 200 us of its annotation."""
    import glob
    import time

    import jax
    eng, reqs, _, _ = served
    jax.profiler.start_trace(str(tmp_path))
    window = jax.profiler.TraceAnnotation("traced_window")
    window.__enter__()
    start_ns = time.perf_counter_ns()
    try:
        eng.serve({rid + 100: p for rid, p in reqs.items()}, n_new=N_NEW)
    finally:
        stop_ns = time.perf_counter_ns()
        window.__exit__(None, None, None)
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    host = {}
    for p in jax.profiler.ProfileData.from_file(path).planes:
        if not p.name.startswith("/device"):
            for line in p.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(float(e.start_ns))
    (win,) = host["traced_window"]
    off = win - start_ns
    tr = eng.tracer
    recs = [r for r in tr.records()
            if start_ns <= tr.epoch_ns + r["t_us"] * 1e3 <= stop_ns]
    assert {r["span"] for r in recs} == {
        "serve", "route", "dedup_round", "plan", "commit", "flush_fence",
        *TRAVERSAL}
    gaps = [min(abs(s - (tr.epoch_ns + r["t_us"] * 1e3 + off))
                for s in host[r["span"]]) for r in recs]
    assert max(gaps) < 200e3


# --------------------------------------------------------------------- #
# compile-stall attribution                                              #
# --------------------------------------------------------------------- #
def test_compile_tracker_first_call_per_shape_sig():
    reg = MetricsRegistry()
    trk = CompileTracker(registry=reg)
    calls = []
    fn = trk.instrument("sharded.update", "cfg=(2,128,64)",
                        lambda x: (calls.append(1), x * 2)[1])
    a = np.zeros(3, np.int32)
    assert fn(a) is not None and fn(a) is not None and len(calls) == 2
    assert len(trk.events) == 1                  # warm second call
    fn(np.zeros(4, np.int32))                    # new shape -> new stall
    assert len(trk.events) == 2
    assert all(ev.trigger == "steady" for ev in trk.events)
    with trk.reason("resplit_width_change"):
        with trk.reason("capacity_ladder"):      # innermost reason wins
            fn(np.zeros(5, np.int32))
        fn(np.zeros(6, np.int32))
    st = trk.stats()
    assert st["steady"]["events"] == 2
    assert st["capacity_ladder"]["events"] == 1
    assert st["resplit_width_change"]["events"] == 1
    assert all(v["stall_us"] >= 0 for v in st.values())
    assert reg.counter("compile_events_total", site="sharded.update",
                       trigger="capacity_ladder").value == 1


def test_compile_tracker_first_seen_and_disabled():
    trk = CompileTracker(registry=MetricsRegistry())
    assert trk.first_seen("site", "k") is True
    assert trk.first_seen("site", "k") is False
    trk.enabled = False
    fn = trk.instrument("site2", "k", lambda x: x)
    fn(np.zeros(2))
    assert trk.events == []                      # disabled: no recording
    trk.reset()
    assert trk.first_seen("site", "k") is True   # reset clears the cache


# --------------------------------------------------------------------- #
# trim backoff: retry and heal paths, counted on the registry            #
# --------------------------------------------------------------------- #
def _plant_torn(root):
    root.mkdir(parents=True, exist_ok=True)
    (root / "log_000000.json").write_text('{"7": [1, 2')   # mid-write


def test_trim_backoff_counts_retries_and_gives_up_gracefully(
        tmp_path, monkeypatch):
    """Every failed unlink burns one (jittered) backoff attempt and one
    retry counter; exhausting the budget leaves the record torn without
    failing the restart."""
    root = tmp_path / "log"
    _plant_torn(root)
    monkeypatch.setattr(RequestLog, "_backoff", lambda self, attempt: None)
    monkeypatch.setattr(
        StagedIO, "unlink",
        lambda self, rel: (_ for _ in ()).throw(OSError("busy")))
    reg = MetricsRegistry()
    log = RequestLog(root, registry=reg)
    assert reg.counter("serving_trim_retries_total").value == \
        RequestLog._TRIM_RETRIES
    assert reg.counter("serving_trims_total").value == 0
    assert "log_000000.json" in log._torn        # still pending, not lost
    assert not log.is_committed([7]).any()


def test_trim_backoff_heal_path_recovers_the_record(tmp_path, monkeypatch):
    """A writer that lands the payload during the grace interval heals
    the record: it is folded, counted as a heal, and never trimmed."""
    root = tmp_path / "log"
    _plant_torn(root)

    def finish_write(self, attempt):             # the "slow writer" lands
        (root / "log_000000.json").write_text('{"7": [1, 2, 3]}')

    monkeypatch.setattr(RequestLog, "_backoff", finish_write)
    reg = MetricsRegistry()
    log = RequestLog(root, registry=reg)
    assert reg.counter("serving_trim_heals_total").value == 1
    assert reg.counter("serving_trims_total").value == 0
    assert log.is_committed([7]).all()
    assert log.committed()[7] == [1, 2, 3]
    assert (root / "log_000000.json").exists()


def test_backoff_is_bounded_and_jittered():
    import time as _time
    log_cls = RequestLog
    sleeps = []
    real_sleep = _time.sleep
    try:
        _time.sleep = sleeps.append
        inst = object.__new__(log_cls)           # no __init__: just _rng
        import random
        inst._rng = random.Random(0)
        for k in range(8):
            inst._backoff(k)
    finally:
        _time.sleep = real_sleep
    assert len(sleeps) == 8
    for k, s in enumerate(sleeps):
        cap = min(log_cls._TRIM_BACKOFF_S * (1 << k),
                  log_cls._TRIM_BACKOFF_MAX_S)
        assert cap / 2 <= s <= cap               # jitter in [0.5, 1.0)
    assert max(sleeps) <= log_cls._TRIM_BACKOFF_MAX_S
