"""Run one cell of BENCHMARK.json once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start to the first timed operation)
makes the weights or tables on the device from ``--seed``, draws the
traffic and runs every shape the window uses once.  The window then
drives the cell's traffic for ``--seconds``; nothing should compile in
it, and the count of compiles there is printed.  After the window the
device's peak memory is read, the program's state is freed, and what
the window produced is compared with the plain reference: each number
compared is printed beside its limit as the last lines of standard
error, and under ``checks`` at the end of the result line.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` a profile of a few seconds of the window is reduced to the
cell's per-layer metrics, ``busy_s``/``window_s`` and ``breakdown``.
The last line of standard output is the JSON result.  With no TPU, or
fewer chips than the cell asks for, the run exits with code 3 and
prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import harness as H  # noqa: E402

TRACE_AFTER = 0.3     # the traced part starts after this share of the window
TRACE_SECONDS = 8.0   # and lasts this long, to the next call boundary


class TraceWindow:
    """Profiles one stretch of the window, opened and closed only at call
    boundaries so that every call in it is whole."""

    def __init__(self, trace_dir: str, start_after_s: float,
                 length_s: float):
        self.dir = trace_dir
        self.start_after_s = start_after_s
        self.length_s = length_s
        self.start_ns = self.stop_ns = None
        self._ann = None

    @property
    def open(self) -> bool:
        return self.start_ns is not None and self.stop_ns is None

    def at_boundary(self, elapsed_s: float) -> None:
        import jax
        if self.start_ns is None and elapsed_s >= self.start_after_s:
            jax.profiler.start_trace(self.dir)
            self._ann = jax.profiler.TraceAnnotation("traced_window")
            self._ann.__enter__()
            self.start_ns = time.perf_counter_ns()
        elif self.open and (time.perf_counter_ns() - self.start_ns
                            >= self.length_s * 1e9):
            self.close()

    def close(self) -> None:
        if not self.open:
            return
        import jax
        self.stop_ns = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()


class RunView:
    """What a per-layer metric reader is handed."""

    def __init__(self, cell, driver, trace, peaks, tw):
        self.cell = cell
        self.config = cell.config
        self.driver = driver
        self.trace = trace            # trace_reduce.Reduced
        self.peaks = peaks
        self.traced_ns = (tw.start_ns, tw.stop_ns)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = H.find_cell(args.workload)
    try:
        devices = H.require_chips(cell.chips)
    except H.NoChip as e:
        H.log(f"bench: {e}")
        return 3
    kind = devices[0].device_kind
    H.log(f"device: platform={devices[0].platform} kind={kind!r} "
          f"count={len(devices)}")
    peaks = H.peaks_for(kind)
    H.log(f"compile cache: {H.enable_compile_cache()}")

    driver = H.driver_module(cell.config["driver"]).Driver(
        cell, args.seed, devices, args.seconds)
    driver.setup()
    setup_s = time.perf_counter() - T0
    H.log(f"setup_s: {setup_s!r}")

    tw = None
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        tw = TraceWindow(trace_dir, TRACE_AFTER * args.seconds,
                         min(TRACE_SECONDS, 0.5 * args.seconds))
    counter = H.CompileCounter()
    try:
        with counter:
            driver.window(args.seconds, tw)
    finally:
        if tw is not None:
            tw.close()
    H.log(f"compiles in window: {counter.compiles} "
          f"(persistent-cache loads: {counter.cache_loads})")

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": H.memory_peak(devices)}
    reduced = None
    if args.trace:
        from bench.trace_reduce import reduce_trace
        if tw.start_ns is None:
            raise RuntimeError("the window ended before its traced part "
                               "began; give it more --seconds")
        reduced = reduce_trace(trace_dir, H.LABELS,
                               driver.host_spans(tw.start_ns, tw.stop_ns),
                               tw.start_ns, devices=len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        for name, (sec, runs) in sorted(reduced.programs.items(),
                                        key=lambda kv: -kv[1][0])[:12]:
            H.log(f"program {name}: {sec!r} s in {runs} runs")

    outcome = driver.check()

    metrics = {}
    breakdown = None
    if args.trace:
        view = RunView(cell, driver, reduced, peaks, tw)
        for m in cell.per_layer():
            value = H.metric_reader(m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = reduced.breakdown()
    else:
        values = dict(outcome.end_to_end, setup_s=setup_s)
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    for c in outcome.checks:
        H.log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}")
    print(H.result_line(outcome, metrics, device, breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
