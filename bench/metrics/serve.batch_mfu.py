"""Model FLOPs of every batch served in the window (prefill and each
decode step that chooses a token) over the summed wall time of the
engine's ``plan`` spans, over the chip's bf16 peak, in %."""
from bench.readers import model_flops


def read(run):
    d = run.driver
    plans = d.window_spans("plan")
    wall = sum(e - s for s, e, _ in plans) / 1e9
    calls = [c for c in d.calls if c.start_ns >= d.t0_ns]
    if wall <= 0 or len(plans) != len(calls):
        return None
    flops = sum(model_flops(run, c) for c in calls)
    return 100.0 * flops / wall / run.peaks["bf16_flops_per_s"]
