"""Reduce a profiler trace of one traced window to device numbers.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes.  What is read:

* device planes: ``/device:TPU:<n>`` (SparseCore planes left out); on each,
  the ``XLA Ops`` line (one event per operation run) and the
  ``XLA Modules`` line (one event per program run);
* host annotations: events of the host plane whose names are the
  harness's ``jax.profiler.TraceAnnotation`` labels, and the
  ``traced_window`` annotation that bounds the window.

What comes out (``Reduced``):

* ``window_s``: the length of ``traced_window``;
* ``busy_s``: per device, the union of its operation intervals inside
  the window, averaged over the devices used;
* ``programs``: per program name, summed device seconds and run count,
  over all devices;
* ``ops``: per operation (its HLO instruction name, e.g. ``fusion.76``;
  a loop's own event spans its body's), summed device seconds;
* ``idle``: per label, seconds in which no operation ran on the device,
  each gap split over the innermost host label that covers it
  (``other`` where none does), averaged over the devices;
* ``collective_alone_s``: seconds of collective operations with no other
  operation beside them, averaged over the devices.

Device and host events share one clock in the profile; the window's own
annotation is the anchor that moves host spans timed by
``perf_counter_ns`` onto it (``reduce_trace``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "traced_window"
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "all_to_all")
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    programs: Dict[str, Tuple[float, int]] = field(default_factory=dict)
    ops: Dict[str, float] = field(default_factory=dict)
    idle: Dict[str, float] = field(default_factory=dict)
    collective_alone_s: float = 0.0
    n_devices: int = 1

    def program_seconds(self, match) -> Tuple[float, int]:
        """Summed device seconds and runs of the programs ``match`` takes."""
        s = n = 0
        for name, (sec, runs) in self.programs.items():
            if match(name):
                s += sec
                n += runs
        return s, n

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def program_name(raw: str) -> str:
    """A program's name without the run id the profiler appends."""
    return _SUFFIX.sub("", raw).strip()


def op_name(raw: str) -> str:
    """An operation's HLO instruction name, without its text."""
    return raw.split(" = ", 1)[0].lstrip("%").strip()


def union_length(intervals: Sequence[Tuple[float, float]]) -> float:
    """Total length covered by [start, end) intervals."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def merge(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Disjoint sorted intervals covering the same time."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: Sequence[Tuple[float, float]], s: float,
            e: float) -> float:
    """How much of [s, e) the merged intervals cover."""
    i = bisect.bisect_right(merged, (s, float("inf"))) - 1
    total = 0.0
    for a, b in merged[max(i, 0):]:
        if a >= e:
            break
        total += max(0.0, min(b, e) - max(a, s))
    return total


def gaps_in(intervals: Sequence[Tuple[float, float]], lo: float,
            hi: float) -> List[Tuple[float, float]]:
    """The parts of [lo, hi) that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def label_segments(labels: Sequence[Tuple[float, float, str]]
                   ) -> List[Tuple[float, float, str]]:
    """Cut the time line at every label's ends; each piece takes the
    innermost (latest-starting) label open over it."""
    pts = sorted({x for s, e, _ in labels for x in (s, e)})
    starts = sorted(labels)
    out, active, i = [], [], 0
    for a, b in zip(pts, pts[1:]):
        while i < len(starts) and starts[i][0] <= a:
            active.append(starts[i])
            i += 1
        active = [x for x in active if x[1] > a]
        if active:
            out.append((a, b, max(active)[2]))
    return out


def attribute(gaps: Sequence[Tuple[float, float]],
              labels: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Split each gap over the innermost label that covers each part of
    it; uncovered parts go to ``other``."""
    out: Dict[str, float] = {}
    segs = label_segments(labels)
    j = 0
    for gs, ge in sorted(gaps):
        covered = 0.0
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < ge:
            a, b, name = segs[k]
            part = min(b, ge) - max(a, gs)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            k += 1
        if ge - gs - covered > 0:
            out["other"] = out.get("other", 0.0) + (ge - gs - covered)
    return out


def _device_planes(planes):
    out = []
    for p in planes:
        if re.fullmatch(r"/device:TPU:\d+", p.name):
            out.append(p)
    return out


def _events(line):
    return [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             e.name) for e in line.events]


def reduce_profile(planes, labels: Sequence[str],
                   extra_labels: Sequence[Tuple[float, float, str]] = (),
                   devices: Optional[int] = None) -> Reduced:
    """Reduce parsed planes (each with ``.name`` and ``.lines``, each line
    with ``.name`` and ``.events`` of ``name``, ``start_ns`` and
    ``duration_ns``).  ``extra_labels`` are host spans already on the
    profile's clock."""
    host = [(s, e, n) for p in planes if not p.name.startswith("/device")
            for ln in p.lines for s, e, n in _events(ln)]
    win = [(s, e) for s, e, n in host if n == WINDOW]
    if not win:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    lo, hi = win[0]
    marks = [(s, e, n) for s, e, n in host if n in labels]
    marks += list(extra_labels)
    devs = _device_planes(planes)
    if devices is not None:
        devs = devs[:devices]
    if not devs:
        raise ValueError("the trace has no TPU device plane")
    red = Reduced(window_s=(hi - lo) / 1e9, busy_s=0.0, n_devices=len(devs))
    busy = alone = 0.0
    for p in devs:
        lines = {ln.name: ln for ln in p.lines}
        if "XLA Ops" not in lines:
            raise ValueError(f"device plane {p.name} has no 'XLA Ops' line; "
                             f"it has {sorted(lines)}")
        ops = [(max(s, lo), min(e, hi), n)
               for s, e, n in _events(lines["XLA Ops"]) if e > lo and s < hi]
        spans = [(s, e) for s, e, _ in ops]
        busy += union_length(spans)
        for s, e, n in ops:
            n = op_name(n)
            red.ops[n] = red.ops.get(n, 0.0) + (e - s) / 1e9
        for name, sec in attribute(gaps_in(spans, lo, hi), marks).items():
            red.idle[name] = red.idle.get(name, 0.0) + sec / 1e9
        is_coll = [any(c in n.lower() for c in COLLECTIVES)
                   for _, _, n in ops]
        coll = [(s, e) for (s, e, _), c in zip(ops, is_coll) if c]
        if coll:
            other = merge([(s, e) for (s, e, _), c in zip(ops, is_coll)
                           if not c])
            alone += sum((e - s) - covered(other, s, e) for s, e in coll)
        for s, e, n in _events(lines.get("XLA Modules", _Empty())):
            if e <= lo or s >= hi:
                continue
            name = program_name(n)
            sec, runs = red.programs.get(name, (0.0, 0))
            red.programs[name] = (sec + (min(e, hi) - max(s, lo)) / 1e9,
                                  runs + 1)
    k = len(devs)
    red.busy_s = busy / k / 1e9
    red.collective_alone_s = alone / k / 1e9
    red.idle = {n: s / k for n, s in red.idle.items()}
    return red


class _Empty:
    events = ()


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_trace(trace_dir: str, labels: Sequence[str],
                 host_spans: Sequence[Tuple[int, int, str]] = (),
                 window_perf_ns: Optional[int] = None,
                 devices: Optional[int] = None) -> Reduced:
    """Read the newest profile under ``trace_dir`` and reduce it.

    ``host_spans`` are (start, end, name) in ``perf_counter_ns``; with
    ``window_perf_ns``, the ``perf_counter_ns`` at which the window's
    annotation opened, they are moved onto the profile's clock and take
    part in attributing idle gaps."""
    import jax
    pd = jax.profiler.ProfileData.from_file(find_xplane(trace_dir))
    planes = list(pd.planes)
    extra = []
    if host_spans and window_perf_ns is not None:
        host = [(float(e.start_ns), e.name) for p in planes
                if not p.name.startswith("/device") for ln in p.lines
                for e in ln.events if e.name == WINDOW]
        if host:
            off = host[0][0] - window_perf_ns
            extra = [(s + off, e + off, n) for s, e, n in host_spans]
    return reduce_profile(planes, labels, extra, devices)
