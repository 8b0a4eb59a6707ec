"""Least time of every decode step in the traced stretch (weights and
the K/V cache read once, or its FLOPs where larger) over the device time
of the decode programs, in %."""
from bench.readers import DECODE, decode_least_s, share, traced_calls


def read(run):
    if run.trace is None:
        return None
    sec, _ = run.trace.program_seconds(DECODE.search)
    return share(decode_least_s(run, traced_calls(run)), sec)
