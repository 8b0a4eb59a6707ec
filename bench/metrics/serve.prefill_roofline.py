"""Least time of every prefill in the traced stretch (its FLOPs, or its
bytes where larger) over the device time of the prefill programs, in %."""
from bench.readers import PREFILL, prefill_least_s, share, traced_calls


def read(run):
    if run.trace is None:
        return None
    sec, _ = run.trace.program_seconds(PREFILL.search)
    return share(prefill_least_s(run, traced_calls(run)), sec)
