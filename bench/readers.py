"""Arithmetic the per-layer metric readers share.

Each reader in ``bench/metrics/`` is handed a ``run`` (see ``RunView``
in ``run.py``): the cell's configuration, its driver's records, the
reduced trace of the traced stretch and the device's peaks.  A reader
that finds nothing to read returns None, and its metric is left out.
"""
from __future__ import annotations

import re

import numpy as np

from bench import counts as C

DECODE = re.compile(r"decode_step")
PREFILL = re.compile(r"lambda")
UPDATE = re.compile(r"update_parallel|update_local")
LOOKUP = re.compile(r"lookup")


def traced_calls(run):
    lo, hi = run.traced_ns
    return [c for c in run.driver.calls if c.start_ns >= lo
            and c.end_ns <= hi]


def decode_least_s(run, calls) -> float:
    """Least seconds of every decode step the calls ran: a call of batch
    B and prompt length S runs ``new_tokens`` steps at S, S+1, ..."""
    cfg, pk, n = run.config, run.peaks, run.driver.new_tokens
    return sum(C.least_seconds(C.decode_flops(cfg, c.batch, c.length + i),
                               C.decode_bytes(cfg, c.batch, c.length + i), pk)
               for c in calls for i in range(n))


def prefill_least_s(run, calls) -> float:
    cfg, pk = run.config, run.peaks
    return sum(C.least_seconds(C.prefill_flops(cfg, c.batch, c.length),
                               C.prefill_bytes(cfg, c.batch, c.length), pk)
               for c in calls)


def model_flops(run, call) -> float:
    """The work a served batch needs: its prefill and the decode steps
    whose logits choose a token (the step after the last token is not
    counted)."""
    cfg, n = run.config, run.driver.new_tokens
    return (C.prefill_flops(cfg, call.batch, call.length)
            + sum(C.decode_flops(cfg, call.batch, call.length + i)
                  for i in range(n - 1)))


def share(least_s: float, device_s: float):
    """A roofline share in percent, or None with no device time."""
    if device_s <= 0:
        return None
    return 100.0 * least_s / device_s


def map_round_bytes(run, kind: str) -> float:
    """Least bytes of the update or lookup programs of the traced rounds."""
    d = run.driver
    total = 0.0
    for r, _, _, fl in d.window_rounds(*run.traced_ns):
        ok, found, _ = d.results[r]
        rd = d.rounds.round(r)
        if kind == "lookup":
            total += C.lookup_bytes(found.size, d.visits)
        elif rd.keys.size:
            ins = rd.ops == 0
            n_ok = int(ok.sum())
            total += C.update_bytes(rd.keys.size, d.visits, fl - n_ok,
                                    int((ok & ins).sum()),
                                    int((ok & ~ins).sum()))
    return total


def device_idle(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def mean(xs):
    return float(np.mean(xs)) if len(xs) else None
