"""The one traffic generator: every mix is a file of parameters.

Open loop (``"kind": "open_loop"``), for independent users:
``rate_per_s`` arrivals a second over the window.  The requests come in
blocks of the fewest requests that hold the mix's ``shares`` of prompt
``lengths`` exactly (20 for shares of 0.5, 0.35 and 0.15), and the
window holds the whole number of blocks nearest ``rate * seconds``.
Every block has the same prompt lengths and the same gaps between
arrivals (the means of the exponential distribution of that rate over
as many equal slices of probability, so the mean gap is ``1 / rate``
exactly); the run's seed draws their order within each block, and the
prompts' tokens.  So every seed offers the same work at the same
offered load, in another order, and no seed can crowd the long prompts
of the whole window together.  Arrival offsets are timed from the
window's start (as ``make_schedule`` in ``src/repro/obs/loadgen.py``
does: seeded, no clock).

Closed loop (``"kind": "closed_loop"``), for callers that wait for each
answer: rounds of the configuration's ``round_ops`` operations, of which
the share ``update_share`` are updates (inserts and deletes, half each)
and the rest lookups, over keys uniform in ``1..key_range``.  A pool of
``POOL_ROUNDS`` rounds is drawn from the seed before the window; round r
takes pool round ``r % POOL_ROUNDS`` with every key shifted by
``(r // POOL_ROUNDS) * KEY_SHIFT`` (mod the key range), so no two rounds
of a run repeat and a round costs the host three vector operations.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


POOL_ROUNDS = 64     # closed-loop rounds drawn before the window
KEY_SHIFT = 7919     # added to a pool round's keys on each reuse


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


# --------------------------------------------------------------------- #
# open loop                                                              #
# --------------------------------------------------------------------- #
@dataclass
class Requests:
    arrival_s: np.ndarray        # float64[n], offsets from the window start
    lengths: np.ndarray          # int64[n] prompt lengths
    prompts: list                # n int32 arrays of token ids
    new_tokens: int


def exact_counts(n: int, shares) -> np.ndarray:
    """Split n in the given shares by largest remainder."""
    shares = np.asarray(shares, np.float64)
    raw = n * shares / shares.sum()
    out = np.floor(raw).astype(np.int64)
    for i in np.argsort(-(raw - out), kind="stable")[:n - out.sum()]:
        out[i] += 1
    return out


def block_size(shares) -> int:
    """The fewest requests, up to 1000, that hold every share a whole
    number of times."""
    for b in range(1, 1001):
        if all(abs(b * s - round(b * s)) < 1e-9 for s in shares):
            return b
    raise ValueError(f"no block of up to 1000 requests holds the shares "
                     f"{list(shares)} exactly")


def exponential_slices(b: int, rate: float) -> np.ndarray:
    """The mean of an exponential of ``rate`` over each of ``b`` equal
    slices of probability; their mean is ``1 / rate``."""
    u = 1.0 - np.arange(b + 1) / b            # survival at the slice edges
    with np.errstate(divide="ignore", invalid="ignore"):
        ulu = np.where(u > 0, u * np.log(u), 0.0)
    # the integral of -log(1 - q) over a slice is [u - u log u] between
    # its edges
    return ((u[:-1] - ulu[:-1]) - (u[1:] - ulu[1:])) * b / rate


def open_loop(traffic: dict, seed: int, seconds: float,
              vocab: int) -> Requests:
    rate = float(traffic["rate_per_s"])
    b = block_size(traffic["shares"])
    blocks = max(1, int(round(rate * seconds / b)))
    gaps = exponential_slices(b, rate)
    lens = np.repeat(np.asarray(traffic["lengths"], np.int64),
                     exact_counts(b, traffic["shares"]))
    order = _rng(seed, 0)
    gaps = np.concatenate([order.permutation(gaps) for _ in range(blocks)])
    lens = np.concatenate([order.permutation(lens) for _ in range(blocks)])
    arrival = np.cumsum(gaps)
    rng = _rng(seed, 1)
    prompts = [rng.integers(0, vocab, size=int(s)).astype(np.int32)
               for s in lens]
    return Requests(arrival, lens, prompts, int(traffic["new_tokens"]))


# --------------------------------------------------------------------- #
# closed loop                                                            #
# --------------------------------------------------------------------- #
@dataclass
class Round:
    ops: np.ndarray      # int32[u]  0 insert, 1 delete
    keys: np.ndarray     # int32[u]
    vals: np.ndarray     # int32[u]
    lookups: np.ndarray  # int32[l]


class MapRounds:
    """Round r of a closed-loop mix, for any r >= 0."""

    def __init__(self, traffic: dict, seed: int, key_range: int,
                 round_ops: int):
        self.key_range = int(key_range)
        self.shift = KEY_SHIFT
        self.updates = int(round(round_ops * float(traffic["update_share"])))
        self.lookups = int(round_ops) - self.updates
        rng = _rng(seed, 2)
        P = POOL_ROUNDS
        u, l = self.updates, self.lookups
        half = np.repeat(np.asarray([0, 1], np.int32), [u - u // 2, u // 2])
        self._ops = np.stack([rng.permutation(half) for _ in range(P)]) \
            if u else np.zeros((P, 0), np.int32)
        self._keys = rng.integers(0, key_range, size=(P, u)).astype(np.int64)
        self._vals = rng.integers(0, 1 << 30, size=(P, u)).astype(np.int32)
        self._look = rng.integers(0, key_range, size=(P, l)).astype(np.int64)

    @property
    def ops_per_round(self) -> int:
        return self.updates + self.lookups

    def _k(self, pool: np.ndarray, r: int) -> np.ndarray:
        off = (r // pool.shape[0]) * self.shift
        return ((pool[r % pool.shape[0]] + off) % self.key_range
                + 1).astype(np.int32)

    def round(self, r: int) -> Round:
        P = self._ops.shape[0]
        return Round(self._ops[r % P], self._k(self._keys, r),
                     self._vals[r % P], self._k(self._look, r))


def prefill(seed: int, key_range: int, n: int):
    """The keys and values loaded before the window: ``n`` distinct keys
    of ``1..key_range``, in a seeded order."""
    rng = _rng(seed, 3)
    keys = (rng.permutation(key_range)[:n] + 1).astype(np.int32)
    vals = rng.integers(0, 1 << 30, size=n).astype(np.int32)
    return keys, vals
