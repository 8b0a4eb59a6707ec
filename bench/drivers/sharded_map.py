"""The durable hash map partitioned by bucket range over the chips:
the hash-map rounds through ``ShardedDurableMap.update`` and ``.lookup``
of ``repro.core.sharded``, whose routing sort and ``all_to_all`` send
each op to the chip that owns its bucket.

The rounds, the reference and the checks are the hash map's; a round's
updates and lookups go in as host arrays (the map's own API pads them
and places them on the mesh), and the checks add ``foreign_ops``: ops a
shard was asked to commit outside its own bucket range, which must be 0.
"""
from __future__ import annotations

import numpy as np

from bench import harness as H
from bench.drivers import hash_map


class Driver(hash_map.Driver):
    foreign = 0

    def make_state(self):
        import jax
        from repro.core.sharded import ShardedDurableMap
        mesh = jax.make_mesh((len(self.devices),), ("shards",),
                             devices=self.devices)
        self.map = ShardedDurableMap(
            capacity=int(self.config["nodes"]),
            n_buckets=int(self.config["buckets"]), mesh=mesh)
        return self.map

    def update(self, state, ops, ks, vs):
        ok, stats = self.map.update(ops, ks, vs)
        self.foreign += int(np.sum(np.asarray(stats.foreign_ops)))
        return state, ok, int(np.sum(np.asarray(stats.coalesced_flushes)))

    def lookup(self, state, ks):
        return self.map.lookup(ks)

    def put(self, *arrays):
        return list(arrays)

    def mean_chain(self, state) -> float:
        return float(self.map.chain_stats()[1])

    def pool(self, state):
        import jax
        st = jax.device_get(self.map.state)
        parts = [[], [], []]
        for s in range(self.map.n_shards):
            c = int(st.cursor[s])
            for out, f in zip(parts, (st.key, st.live, st.val)):
                out.append(f[s, 1:c])
        self.map = None
        return tuple(np.concatenate(p) for p in parts)

    def check(self) -> H.Outcome:
        out = super().check()
        out.checks.append(H.Check("foreign_ops", self.foreign, 0))
        return out
