"""Plain reference of the durable hash map, in numpy, dense by key.

The map holds int32 keys in ``1..key_range`` with int32 values.  Its
semantics, as ``BENCHMARK.json``'s hash-map configurations state them:

* a round of updates is applied in batch order (its linearization
  order); an insert succeeds iff its key is absent or deleted, and sets
  the value; a delete succeeds iff its key is present;
* a lookup after the round sees every update of the round;
* persistence accounting: a successful insert of a key that never held
  a node flushes twice (node, then bucket head), every other successful
  update flushes once.

Arrays indexed by the key stand in for the node pool; keys that appear
more than once in a round are replayed one at a time, in order.
"""
from __future__ import annotations

import numpy as np

OP_INSERT, OP_DELETE = 0, 1


class DenseMap:
    def __init__(self, key_range: int):
        n = key_range + 1
        self.live = np.zeros(n, np.bool_)
        self.val = np.zeros(n, np.int32)
        self.node = np.zeros(n, np.bool_)   # the key has held a node
        self.flushes = 0

    def update(self, ops: np.ndarray, ks: np.ndarray,
               vs: np.ndarray) -> np.ndarray:
        """Apply one round; returns the per-op success flags."""
        order = np.argsort(ks)
        same = ks[order][1:] == ks[order][:-1]
        repeated = np.zeros(ks.size, np.bool_)
        repeated[order[1:][same]] = True
        repeated[order[:-1][same]] = True
        ok = np.zeros(ks.size, np.bool_)

        once = ~repeated
        k, ins, v = ks[once], ops[once] == OP_INSERT, vs[once]
        done = ins != self.live[k]          # insert iff absent, delete iff live
        fresh = done & ins & ~self.node[k]
        ok[once] = done
        self.flushes += int(done.sum()) + int(fresh.sum())
        self.node[k[fresh]] = True
        self.live[k] = ins                  # after any op: its own code
        self.val[k[done & ins]] = v[done & ins]

        for i in np.flatnonzero(repeated):  # batch order
            key, insert = ks[i], ops[i] == OP_INSERT
            if insert == self.live[key]:
                continue
            ok[i] = True
            self.flushes += 1 if (not insert or self.node[key]) else 2
            if insert:
                self.node[key] = True
                self.val[key] = vs[i]
            self.live[key] = insert
        return ok

    def lookup(self, ks: np.ndarray):
        found = self.live[ks]
        return found, np.where(found, self.val[ks], 0).astype(np.int32)

    def content_mismatches(self, keys: np.ndarray, live: np.ndarray,
                           vals: np.ndarray) -> int:
        """How far a node pool (the keys, liveness and values of its used
        nodes) departs from this map: keys with no node or two, nodes of
        keys never inserted, and wrong liveness or live values."""
        keys = np.asarray(keys, np.int64)
        inside = (keys >= 1) & (keys < self.live.size)
        bad = int((~inside).sum())
        keys = keys[inside]
        live = np.asarray(live, np.bool_)[inside]
        vals = np.asarray(vals)[inside]
        seen = np.bincount(keys, minlength=self.live.size)
        bad += int((seen > 1).sum()) + int((seen.astype(bool)
                                            != self.node).sum())
        bad += int((live != self.live[keys]).sum())
        bad += int((live & (vals != self.val[keys])).sum())
        return bad
