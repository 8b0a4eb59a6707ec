"""Least time of the traced rounds' lookups (their algorithmic bytes at
the HBM peak, see bench/counts.py) over the device time of the lookup
programs summed over the chips, in %."""
from bench.readers import LOOKUP, map_round_bytes, share


def read(run):
    if run.trace is None:
        return None
    sec, _ = run.trace.program_seconds(LOOKUP.search)
    least = map_round_bytes(run, "lookup") / run.peaks["hbm_bytes_per_s"]
    return share(least, sec)
