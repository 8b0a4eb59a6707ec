"""The durable hash map: closed-loop rounds into ``update_parallel`` and
``lookup`` of ``repro.core.batched``.

Set-up makes an empty table of the configuration's nodes and buckets on
the device, loads the prefilled keys through ``update_parallel`` in
rounds of the window's update size, measures the mean chain length, and
runs ``WARM_ROUNDS`` rounds of the traffic.

A round hands the device its updates and lookups, runs one
``update_parallel`` and then one ``lookup`` on the state it returns, and
brings the per-op ``ok`` flags, the lookup results and the round's
``coalesced_flushes`` back to the host; its latency runs from the first
hand-over to the last result on the host.  The next round starts then.

Checks, against the plain reference replaying every round from set-up
on: per-op ``ok`` flags, lookup results, the summed coalesced flushes,
and the final content of the node pool (keys, liveness, live values).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import harness as H
from bench import traffic as T
from bench.reference.hash_map import DenseMap

WARM_ROUNDS = 3      # rounds of the traffic run in set-up


class Driver:
    """Single-chip driver; :class:`bench.drivers.sharded_map.Driver`
    replaces the four program calls below."""

    def __init__(self, cell, seed: int, devices, seconds: float):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.devices = devices
        self.seconds = float(seconds)
        cfg = self.config
        self.key_range = int(cfg["key_range"])
        self.rounds = T.MapRounds(self.traffic, self.seed, self.key_range,
                                  int(cfg["round_ops"]))
        self.records = []        # per round: (start_ns, end_ns, flushes)
        self.results = []        # per round: (ok, found, vals) on the host

    # ---- the program ---------------------------------------------------- #
    def make_state(self):
        from repro.core import batched as B
        return B.make_state(int(self.config["nodes"]),
                            int(self.config["buckets"]))

    def update(self, state, ops, ks, vs):
        """Dispatch one update round; device arrays in, device arrays out:
        (state', ok, coalesced flushes)."""
        from repro.core import batched as B
        state, ok, stats = B.update_parallel(state, ops, ks, vs,
                                             int(self.config["buckets"]))
        return state, ok, stats.coalesced_flushes

    def lookup(self, state, ks):
        from repro.core import batched as B
        return B.lookup(state, ks, int(self.config["buckets"]))

    def put(self, *arrays):
        import jax
        return [jax.device_put(a, self.devices[0]) for a in arrays]

    def mean_chain(self, state) -> float:
        from repro.core import batched as B
        _, mean = B.chain_stats(state, int(self.config["buckets"]))
        return float(mean)

    def pool(self, state):
        """(keys, live, vals) of the used nodes, on the host."""
        import jax
        st = jax.device_get(state)
        c = int(st.cursor)
        return st.key[1:c], st.live[1:c], st.val[1:c]

    # ---- set-up ---------------------------------------------------------- #
    def setup(self) -> None:
        import jax
        cfg = self.config
        self.state = self.make_state()
        keys, vals = T.prefill(self.seed, self.key_range, int(cfg["prefill"]))
        u = self.rounds.updates or int(cfg["round_ops"]) // 2
        self.prefill_ok = []
        for i in range(0, keys.size, u):
            k, v = keys[i:i + u], vals[i:i + u]
            if k.size < u:      # the last load round: pad with repeats
                k = np.concatenate([k, np.full(u - k.size, k[-1], k.dtype)])
                v = np.concatenate([v, np.full(u - v.size, v[-1], v.dtype)])
            ops = np.zeros(u, np.int32)
            self.state, ok, _ = self.update(self.state, *self.put(ops, k, v))
            self.prefill_ok.append((k, v, ok))
        self.prefill_ok = [(k, v, np.asarray(ok))
                           for k, v, ok in self.prefill_ok]
        self.visits = 0.5 * self.mean_chain(self.state)
        H.log(f"map: {keys.size} keys loaded; mean chain "
              f"{2 * self.visits:.4f}")
        self.next_round = 0
        for _ in range(WARM_ROUNDS):
            self._round(None)
        jax.block_until_ready(self.state)

    # ---- the window ------------------------------------------------------ #
    def _round(self, record):
        import jax
        r = self.next_round
        self.next_round += 1
        with jax.profiler.TraceAnnotation("generate"):
            rd = self.rounds.round(r)
        t0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation("host_to_device"):
            if rd.keys.size:
                ops, ks, vs = self.put(rd.ops, rd.keys, rd.vals)
            lk, = self.put(rd.lookups)
        with jax.profiler.TraceAnnotation("map_round"):
            ok = fl = None
            if rd.keys.size:
                self.state, ok, fl = self.update(self.state, ops, ks, vs)
            found, vals = self.lookup(self.state, lk)
        with jax.profiler.TraceAnnotation("device_to_host"):
            ok = np.asarray(ok) if ok is not None else np.zeros(0, bool)
            fl = int(fl) if fl is not None else 0
            found, vals = np.asarray(found), np.asarray(vals)
        t1 = time.perf_counter_ns()
        self.results.append((ok, found, vals))
        if record is not None:
            record.append((t0, t1, fl))

    def window(self, seconds: float, tw) -> None:
        self.first_window_round = self.next_round
        t0 = time.perf_counter_ns()
        self.t0_ns = t0
        end = t0 + int(seconds * 1e9)
        while time.perf_counter_ns() < end:
            if tw is not None:
                tw.at_boundary((time.perf_counter_ns() - t0) / 1e9)
            self._round(self.records)
        self.t1_ns = self.records[-1][1]
        H.log(f"map: {len(self.records)} rounds in the window")

    def host_spans(self, lo_ns: int, hi_ns: int):
        return []

    def window_rounds(self, lo_ns=None, hi_ns=None):
        """(round index, start, end, flushes) of window rounds inside
        [lo_ns, hi_ns]."""
        out = []
        for i, (s, e, fl) in enumerate(self.records):
            if (lo_ns is None or s >= lo_ns) and (hi_ns is None or e <= hi_ns):
                out.append((self.first_window_round + i, s, e, fl))
        return out

    # ---- checks ---------------------------------------------------------- #
    def check(self) -> H.Outcome:
        lat_ms = np.asarray([(e - s) / 1e6 for s, e, _ in self.records])
        ops = len(self.records) * self.rounds.ops_per_round
        e2e = {"map_ops_s": ops / ((self.t1_ns - self.t0_ns) / 1e9),
               "map_p95_ms": float(np.percentile(lat_ms, 95))}
        keys, live, vals = self.pool(self.state)
        self.state = None
        gc.collect()

        t = time.perf_counter()
        ref = DenseMap(self.key_range)
        ok_bad = look_bad = 0
        for k, v, ok in self.prefill_ok:
            ok_bad += int((ref.update(np.zeros(k.size, np.int32), k, v)
                           != ok).sum())
        flushes_before = ref.flushes
        first = self.first_window_round
        for r, (ok, found, got) in enumerate(self.results):
            rd = self.rounds.round(r)
            if rd.keys.size:
                ok_bad += int((ref.update(rd.ops, rd.keys, rd.vals)
                               != ok).sum())
            if r == first - 1:
                flushes_before = ref.flushes
            f_want, v_want = ref.lookup(rd.lookups)
            look_bad += int(((f_want != found) | (v_want != got)).sum())
        program_flushes = sum(fl for _, _, fl in self.records)
        flush_gap = abs(program_flushes - (ref.flushes - flushes_before))
        content_bad = ref.content_mismatches(keys, live, vals)
        H.log(f"map: reference replayed {len(self.results)} rounds in "
              f"{time.perf_counter() - t:.1f} s")
        n_ops = len(self.results) * self.rounds.ops_per_round
        return H.Outcome(
            attempted=n_ops, failed=ok_bad + look_bad,
            checks=[H.Check("ok_mismatch", ok_bad, 0),
                    H.Check("lookup_mismatch", look_bad, 0),
                    H.Check("flush_mismatch", flush_gap, 0),
                    H.Check("content_mismatch", content_bad, 0)],
            end_to_end=e2e)
