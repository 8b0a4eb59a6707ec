"""Sharded durable map: bucket-range partitioning of the plan/commit engine.

The NVTraverse split is naturally shard-local.  The *plan* phase (the
journey) is embarrassingly parallel — it reads a snapshot and does zero
persistence work — and the *commit* phase (the destination) only ever
touches one bucket chain, so partitioning the node pool and the bucket
heads by **bucket range** keeps every flush and fence inside the shard
that owns the bucket.  Nothing crosses a shard boundary at commit time;
recovery is per-shard independent.

Layout (``ShardedState``): the single-device :class:`HashMapState` gains
a leading shard axis.  Shard ``s`` of ``S`` owns global buckets
``[s·nb_local, (s+1)·nb_local)`` where ``nb_local = n_buckets / S``, and
a private node pool with its own bump cursor.  Because ``nb_local``
divides ``n_buckets``, the local bucket of a key equals its global
bucket mod ``nb_local`` — the unmodified single-device engine
(:func:`repro.core.batched.update_parallel` with ``n_buckets=nb_local``)
places every key in the *same global bucket* it would occupy unsharded,
so the gathered sharded map is a bucket-permutation-equivalent of the
single-device map (identical per-key values and liveness; node ids
differ only by per-shard allocation order).

Routing: ops enter data-parallel (each shard holds a contiguous slice of
the batch), are grouped by owner shard (``owner = global_bucket //
nb_local``) with a stable sort so batch order survives inside each
group, and are exchanged with one ``all_to_all`` whose per-(src, dst)
block is padded to the slice length — static shapes, no host round-trip.
The flattened receive buffer is src-major, i.e. *global batch order*, so
each shard's local plan/commit round composes duplicate-key ops exactly
as the single-device engine would; padding slots ride along as
``valid=False`` ops, which the engine treats as fully transparent.

Accounting: per-shard ``CommitStats`` come back stacked
(:class:`ShardCommitStats`) so the O(1)-flushes / 2-fences-per-update
law still holds globally — per-op flush/fence sums equal the
single-device engine's bit for bit, and the coalesced batch cost is
``2 × max over shards of the largest same-bucket conflict group``
(shards fence concurrently).  ``bucket_flushes`` is the locality proof:
stacked to a global array it must be nonzero only inside each shard's
own range, and ``foreign_ops`` counts ops a shard received for buckets
outside its range (always 0 unless routing is broken).

Re-splittable ranges (the migration layer): ``splits`` generalizes the
even partition to *arbitrary* contiguous boundaries — shard ``s`` owns
global buckets ``[splits[s], splits[s+1])`` — by handing the engine the
range base (``update_parallel(..., nb_global=n_buckets, base=…)``), so
a key's local bucket is ``global_bucket - base`` instead of the mod
trick.  :meth:`ShardedDurableMap.rebalance` re-splits a live map under
a skewed load: it opens a fresh map on the new boundaries and drains
the old one into it in bounded global-bucket-range rounds — each round
one ordinary routed ``update`` batch, so every migrated key commits
with the same O(1) flushes + 2 fences *in its new owner shard* and the
per-round ``bucket_flushes``/``foreign_ops`` counters prove it.
:meth:`ShardedDurableMap.migrate_to` is the general form (new capacity
and/or bucket count and/or boundaries) the membership index's growth
path runs on.
"""
from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import batched
from ..obs.compile import get_tracker
from ..obs.metrics import get_registry

AXIS = "shards"


class ShardedState(NamedTuple):
    """:class:`~repro.core.batched.HashMapState` with a leading shard
    axis; row ``s`` is shard ``s``'s private node pool + bucket heads."""
    key: jax.Array          # int32[S, cap_local]
    val: jax.Array          # int32[S, cap_local]
    nxt: jax.Array          # int32[S, cap_local]
    live: jax.Array         # bool[S, cap_local]
    head: jax.Array         # int32[S, nb_local]
    cursor: jax.Array       # int32[S]  per-shard bump allocator
    flushes: jax.Array      # int32[S]  per-shard persistence accounting
    fences: jax.Array       # int32[S]


class ShardCommitStats(NamedTuple):
    """Per-shard :class:`~repro.core.batched.CommitStats`, stacked.

    All fields except ``bucket_flushes`` are ``int32[S]`` (one entry per
    shard); ``bucket_flushes`` is the global ``int32[n_buckets]`` array
    (shard rows concatenated in bucket-range order, so index ``b`` *is*
    global bucket ``b``).  ``foreign_ops[s]`` counts valid ops shard
    ``s`` received whose global bucket is outside its own range — the
    routing invariant says it is always 0.
    """
    ops_committed: jax.Array
    conflict_groups: jax.Array
    max_group: jax.Array
    coalesced_flushes: jax.Array
    coalesced_fences: jax.Array
    foreign_ops: jax.Array
    bucket_flushes: jax.Array

    @property
    def total_ops_committed(self) -> int:
        return int(jnp.sum(self.ops_committed))

    @property
    def total_coalesced_flushes(self) -> int:
        return int(jnp.sum(self.coalesced_flushes))

    @property
    def global_coalesced_fences(self) -> int:
        """Shards commit concurrently, so their fences overlap: the batch
        needs ``2 × (largest same-bucket group on any shard)`` fences."""
        return int(jnp.max(self.coalesced_fences))


def _state_specs() -> ShardedState:
    two = P(AXIS, None)
    one = P(AXIS)
    return ShardedState(key=two, val=two, nxt=two, live=two, head=two,
                        cursor=one, flushes=one, fences=one)


def items_of_state(state: batched.HashMapState) -> dict:
    """``{key: (live, val)}`` over every allocated node of a
    single-device map — the engine allocates at most one node per key,
    so this is the map's abstract content (dead nodes included)."""
    st = jax.device_get(state)
    c = int(st.cursor)
    return {int(k): (bool(l), int(v))
            for k, l, v in zip(st.key[1:c], st.live[1:c], st.val[1:c])}


# --------------------------------------------------------------------- #
# shard-local bodies, compiled once per (mesh, n_shards, n_buckets)      #
# --------------------------------------------------------------------- #
def _route(owner: jax.Array, valid: jax.Array, S: int):
    """Send-buffer layout for one all-to-all: group this shard's ops by
    owner (stable sort, so batch order survives within each group) and
    place group ``d`` at block ``d`` of a ``[S, L0]`` buffer."""
    L0 = owner.shape[0]
    owner = jnp.where(valid, owner, 0)           # pads ride to shard 0
    sort_idx = jnp.argsort(owner)                # stable: ties keep order
    so = owner[sort_idx]
    counts = jnp.zeros(S, jnp.int32).at[owner].add(1)
    starts = jnp.cumsum(counts) - counts
    flat = so * L0 + (jnp.arange(L0, dtype=jnp.int32) - starts[so])
    return sort_idx, flat


def _a2a(x: jax.Array, S: int) -> jax.Array:
    """Exchange a ``[S·L0]`` or ``[S·L0, W]`` dest-major buffer; the
    result, flattened src-major, is this shard's slice of the batch in
    global order (block ``d`` of ``S·L0`` rows goes to shard ``d``)."""
    shp = x.shape
    return jax.lax.all_to_all(
        x.reshape(S, -1), AXIS, 0, 0, tiled=True).reshape(shp)


def _send_packed(fields, sort_idx, flat, S: int):
    """Route a whole op payload with ONE all_to_all: the fields stack as
    int32 columns of a ``[S·L0, W]`` buffer (one collective per commit
    round instead of one per field — the latency floor of a real
    multi-device deployment is per-collective, not per-byte)."""
    cols = jnp.stack([f.astype(jnp.int32) for f in fields], axis=1)
    buf = jnp.zeros((cols.shape[0] * S, cols.shape[1]), jnp.int32)
    recv = _a2a(buf.at[flat].set(cols[sort_idx]), S)
    return [recv[:, i] for i in range(len(fields))]


def _squeeze(state: ShardedState) -> batched.HashMapState:
    return batched.HashMapState(*(f[0] for f in state))


@lru_cache(maxsize=None)
def _build_fns(mesh, S: int, n_buckets: int, nb_max: int):
    """The jitted shard_map update/lookup closures for one map config —
    cached so every :class:`ShardedDurableMap` instance with the same
    (mesh, shards, buckets, max range width) shares compiles.  The split
    boundaries themselves are *traced operands* (``bounds`` replicated,
    ``base``/``size`` per-shard), so a rebalanced map re-uses the same
    compile."""

    def update_local(state, ops, ks, vs, valid, bounds, base, size):
        st = _squeeze(state)
        base_me, size_me = base[0], size[0]
        owner = (jnp.searchsorted(
            bounds, batched.bucket_of(ks, n_buckets), side="right")
            .astype(jnp.int32) - 1)
        sort_idx, flat = _route(owner, valid, S)
        r_ops, r_ks, r_vs, r_valid_i = _send_packed(
            [ops, ks, vs, valid], sort_idx, flat, S)
        r_valid = r_valid_i.astype(jnp.bool_)
        # routing invariant instrumentation: a shard must never be asked
        # to commit (flush/fence) a bucket outside its own range
        g = batched.bucket_of(r_ks, n_buckets) - base_me
        foreign = jnp.sum(
            r_valid & ((g < 0) | (g >= size_me))).astype(jnp.int32)
        st2, ok_r, stats = batched.update_parallel(
            st, r_ops, r_ks, r_vs, nb_max, valid=r_valid,
            nb_global=n_buckets, base=base_me)
        # hand each op's result back to the shard that holds its slot
        ok = jnp.zeros(ops.shape[0], jnp.bool_).at[sort_idx].set(
            _a2a(ok_r, S)[flat])
        sstats = ShardCommitStats(
            ops_committed=stats.ops_committed[None],
            conflict_groups=stats.conflict_groups[None],
            max_group=stats.max_group[None],
            coalesced_flushes=stats.coalesced_flushes[None],
            coalesced_fences=stats.coalesced_fences[None],
            foreign_ops=foreign[None],
            bucket_flushes=stats.bucket_flushes,
        )
        return ShardedState(*(f[None] for f in st2)), ok, sstats

    def lookup_local(state, ks, valid, bounds, base):
        st = _squeeze(state)
        owner = (jnp.searchsorted(
            bounds, batched.bucket_of(ks, n_buckets), side="right")
            .astype(jnp.int32) - 1)
        sort_idx, flat = _route(owner, valid, S)
        r_ks, = _send_packed([ks], sort_idx, flat, S)
        # probe, not lookup: exists (node present, live or dead) rides
        # along for free — the growth path's exact fits check needs it
        r_exists, r_live, r_vals = batched.probe(
            st, r_ks, nb_max, nb_global=n_buckets, base=base[0])
        # one packed collective for the answers too
        back = _a2a(jnp.stack([r_exists.astype(jnp.int32),
                               r_live.astype(jnp.int32), r_vals],
                              axis=1), S)[flat]
        n = ks.shape[0]
        exists = jnp.zeros(n, jnp.bool_).at[sort_idx].set(
            back[:, 0].astype(jnp.bool_))
        found = jnp.zeros(n, jnp.bool_).at[sort_idx].set(
            back[:, 1].astype(jnp.bool_))
        vals = jnp.zeros(n, jnp.int32).at[sort_idx].set(back[:, 2])
        return exists, found, vals

    sspec = _state_specs()
    ospec = ShardCommitStats(*([P(AXIS)] * 7))
    # check_vma=False: every output here is explicitly sharded, so there
    # is no replication to check.
    update_fn = jax.jit(jax.shard_map(
        update_local, mesh=mesh,
        in_specs=(sspec, P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(None),
                  P(AXIS), P(AXIS)),
        out_specs=(sspec, P(AXIS), ospec), check_vma=False))
    lookup_fn = jax.jit(jax.shard_map(
        lookup_local, mesh=mesh,
        in_specs=(sspec, P(AXIS), P(AXIS), P(None), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS), P(AXIS)), check_vma=False))
    return update_fn, lookup_fn


class RebalanceReport(NamedTuple):
    """What a re-split / migration actually did — and the proof it kept
    persistence local to the *new* owner ranges."""
    rounds: int
    migrated: int               # live keys drained into the new map
    foreign_ops: int            # Σ over rounds/shards (must be 0)
    bucket_flushes: np.ndarray  # int32[n_buckets_new] Σ over rounds
    splits_old: Tuple[int, ...]
    splits_new: Tuple[int, ...]
    chain_before: Tuple[int, float]
    chain_after: Tuple[int, float]


def even_splits(n_buckets: int, n_shards: int) -> Tuple[int, ...]:
    """The default contiguous-range boundaries: ``n_shards`` equal
    ranges (requires divisibility, like the original static split).

    >>> even_splits(64, 4)
    (0, 16, 32, 48, 64)
    """
    if n_buckets % n_shards:
        raise ValueError(
            f"n_buckets={n_buckets} not divisible by n_shards={n_shards}"
            " (pass explicit splits= for uneven ranges)")
    w = n_buckets // n_shards
    return tuple(s * w for s in range(n_shards)) + (n_buckets,)


class ShardedDurableMap:
    """Bucket-range-sharded durable map running the plan/commit engine
    per shard under ``shard_map``.

    ``capacity`` is the *total* node budget (split evenly; each shard
    reserves its own null node 0, so the usable total is
    ``S·(ceil(capacity/S) - 1)``).  ``splits`` (optional, ``S+1``
    strictly increasing boundaries with ``splits[0]=0`` and
    ``splits[-1]=n_buckets``) assigns shard ``s`` the contiguous global
    bucket range ``[splits[s], splits[s+1])``; the default is the even
    partition (then ``n_buckets`` must be divisible by the shard
    count).  Requires ``n_shards`` jax devices — force host devices for
    CPU work with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """

    def __init__(self, n_shards: Optional[int] = None, *,
                 capacity: int = 1 << 16, n_buckets: int = 1024,
                 mesh=None, splits: Optional[Sequence[int]] = None):
        if mesh is None:
            from ..launch.mesh import make_map_mesh
            mesh = make_map_mesh(n_shards or jax.device_count())
        self.mesh = mesh
        self.n_shards = int(np.prod(list(mesh.shape.values())))
        if n_shards is not None and n_shards != self.n_shards:
            raise ValueError(
                f"n_shards={n_shards} does not match the given mesh "
                f"({self.n_shards} devices); pass one or the other")
        if splits is None:
            splits = even_splits(n_buckets, self.n_shards)
        self.splits = tuple(int(b) for b in splits)
        if (len(self.splits) != self.n_shards + 1
                or self.splits[0] != 0 or self.splits[-1] != n_buckets
                or any(a >= b for a, b in zip(self.splits,
                                              self.splits[1:]))):
            raise ValueError(
                f"splits={splits} must be {self.n_shards + 1} strictly "
                f"increasing boundaries from 0 to {n_buckets}")
        self.n_buckets = n_buckets
        self.sizes = tuple(b - a for a, b in zip(self.splits,
                                                 self.splits[1:]))
        self.nb_max = max(self.sizes)       # head width (ranges padded)
        self.nb_local = self.nb_max         # back-compat alias
        self.capacity = capacity
        self.cap_local = -(-capacity // self.n_shards)
        S, C, NBM = self.n_shards, self.cap_local, self.nb_max
        state = ShardedState(
            key=jnp.zeros((S, C), jnp.int32),
            val=jnp.zeros((S, C), jnp.int32),
            nxt=jnp.full((S, C), batched.NIL, jnp.int32),
            live=jnp.zeros((S, C), jnp.bool_),
            head=jnp.full((S, NBM), batched.NIL, jnp.int32),
            cursor=jnp.ones(S, jnp.int32),
            flushes=jnp.zeros(S, jnp.int32),
            fences=jnp.zeros(S, jnp.int32),
        )
        self.state = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(
                mesh, P(AXIS, *([None] * (x.ndim - 1))))), state)
        self._bounds = jnp.asarray(self.splits, jnp.int32)
        shard1 = NamedSharding(mesh, P(AXIS))
        self._base = jax.device_put(
            jnp.asarray(self.splits[:-1], jnp.int32), shard1)
        self._size = jax.device_put(
            jnp.asarray(self.sizes, jnp.int32), shard1)
        self._update_fn, self._lookup_fn = _build_fns(
            mesh, S, n_buckets, NBM)
        # NVTrace compile seam: a (mesh, S, n_buckets, nb_max) miss above
        # is only *built* here — the XLA compile stall lands on the first
        # call per argument-shape signature, which the tracker times and
        # attributes to the active reason (re-split width change,
        # capacity-ladder step, or "steady" cold start)
        trk = get_tracker()
        cfg = f"S={S},nb={n_buckets},nb_max={NBM}"
        self._update_fn = trk.instrument("sharded.update", cfg,
                                         self._update_fn)
        self._lookup_fn = trk.instrument("sharded.lookup", cfg,
                                         self._lookup_fn)
        self._metrics = get_registry()

    # ---------------- host API --------------------------------------- #
    def _pad(self, *arrs: np.ndarray):
        """Pad the batch so each shard's slice is the same power-of-two
        length (static all-to-all shapes, retraces capped at one per
        log2 size); pad slots are ``valid=False`` and fully transparent
        to the engine."""
        n = arrs[0].shape[0]
        per = -(-max(n, 1) // self.n_shards)
        per = 1 << (per - 1).bit_length()
        total = per * self.n_shards
        out = [jnp.asarray(np.concatenate(
            [a, np.zeros(total - n, a.dtype)])) for a in arrs]
        valid = jnp.asarray(np.arange(total) < n)
        return out, valid

    def update(self, ops, ks, vs) -> Tuple[np.ndarray, ShardCommitStats]:
        """One mixed plan/commit round over the whole map: route each op
        to its owner shard, commit per shard, return per-op ``ok`` in
        batch order plus the stacked per-shard stats (``bucket_flushes``
        re-assembled on the global bucket axis from the per-range rows)."""
        ops = np.asarray(ops, np.int32)
        ks = np.asarray(ks, np.int32)
        vs = np.asarray(vs, np.int32)
        n = ks.shape[0]
        if n == 0:
            return np.zeros(0, np.bool_), None
        (ops_p, ks_p, vs_p), valid = self._pad(ops, ks, vs)
        self.state, ok, stats = self._update_fn(
            self.state, ops_p, ks_p, vs_p, valid,
            self._bounds, self._base, self._size)
        bf = np.asarray(stats.bucket_flushes).reshape(
            self.n_shards, self.nb_max)
        stats = stats._replace(bucket_flushes=np.concatenate(
            [bf[s, :w] for s, w in enumerate(self.sizes)]))
        self._export_stats(stats)
        return np.asarray(ok)[:n], stats

    def _export_stats(self, stats: ShardCommitStats) -> None:
        """Mirror one round's commit accounting onto the NVTrace
        registry (the satellite that gives `CommitStats` sums, foreign
        ops and per-shard load one read path): cumulative flush/fence
        totals, the routing invariant, and per-shard committed-op load."""
        m = self._metrics
        committed = np.asarray(stats.ops_committed)
        m.counter("map_commit_ops_total").inc(int(committed.sum()))
        m.counter("map_commit_flushes_total").inc(
            int(np.asarray(stats.coalesced_flushes).sum()))
        m.counter("map_commit_fences_total").inc(
            int(np.asarray(stats.coalesced_fences).max(initial=0)))
        m.counter("map_foreign_ops_total").inc(
            int(np.asarray(stats.foreign_ops).sum()))
        for s in range(self.n_shards):
            m.counter("map_shard_ops_total", shard=str(s)).inc(
                int(committed[s]))

    def owners_of(self, ks) -> np.ndarray:
        """Owner shard of each key under the current split (host-side
        routing twin — used by the index's exact per-shard fits check)."""
        b = batched.bucket_of_np(np.asarray(ks, np.int32), self.n_buckets)
        return (np.searchsorted(np.asarray(self.splits), b,
                                side="right") - 1).astype(np.int32)

    def insert(self, ks, vs):
        ks = np.asarray(ks, np.int32)
        return self.update(np.full(ks.shape, batched.OP_INSERT, np.int32),
                           ks, vs)

    def delete(self, ks):
        ks = np.asarray(ks, np.int32)
        return self.update(np.full(ks.shape, batched.OP_DELETE, np.int32),
                           ks, np.zeros_like(ks))

    def lookup(self, ks) -> Tuple[np.ndarray, np.ndarray]:
        """Batched lookup (the journey — no persistence work on any
        shard): returns ``(found bool[n], vals int32[n])``.  Exactly
        :func:`repro.core.batched.lookup`'s contract: a not-found key's
        val is 0, even when a dead node still holds its last value."""
        _, found, vals = self.probe(ks)
        return found, np.where(found, vals, 0).astype(np.int32)

    def probe(self, ks) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Node-level probe across shards (zero persistence work):
        ``(exists, live, vals)``, where ``exists`` is True iff the key
        holds a node at all — dead included.  The exact fit check of
        the index growth path keys off ``exists``: a removed member's
        node is resurrected in place, never re-allocated."""
        ks = np.asarray(ks, np.int32)
        n = ks.shape[0]
        if n == 0:
            z = np.zeros(0, np.bool_)
            return z, z, np.zeros(0, np.int32)
        (ks_p,), valid = self._pad(ks)
        exists, found, vals = self._lookup_fn(self.state, ks_p, valid,
                                              self._bounds, self._base)
        return (np.asarray(exists)[:n], np.asarray(found)[:n],
                np.asarray(vals)[:n])

    def items(self) -> dict:
        """Gathered abstract content ``{key: (live, val)}`` — the
        bucket-permutation-invariant view used by the state-identity
        checks against the single-device engine.  Keys are disjoint
        across shards (bucket ranges partition the hash space), so the
        union over per-shard views is exact."""
        st = jax.device_get(self.state)
        out = {}
        for s in range(self.n_shards):
            out.update(items_of_state(
                batched.HashMapState(*(f[s] for f in st))))
        return out

    @property
    def flushes(self) -> int:
        """Aggregate per-op flush accounting (sums the per-shard
        counters; equals the single-device engine's on the same ops)."""
        return int(np.sum(jax.device_get(self.state.flushes)))

    @property
    def fences(self) -> int:
        return int(np.sum(jax.device_get(self.state.fences)))

    @property
    def cursor_max(self) -> int:
        """Fullest shard's bump cursor — the growth trigger (a batch of
        fresh inserts could in the worst case all hash to one shard)."""
        return int(np.max(jax.device_get(self.state.cursor)))

    @property
    def cursors(self) -> np.ndarray:
        """Per-shard bump cursors (``int64[S]``) — the exact per-shard
        fits checks (index growth, live rebalance reserve) compare these
        against per-shard allocation demand."""
        return np.asarray(jax.device_get(self.state.cursor), np.int64)

    def fresh_demand(self, ks) -> np.ndarray:
        """Per-shard allocation demand (``int64[S]``) of a batch of
        distinct insert keys: only keys without a node (live or dead —
        a removed key's node is resurrected in place) allocate, each in
        its owner shard.  The exact half of the index growth check."""
        ks = np.asarray(ks, np.int32)
        exists, _, _ = self.probe(ks)
        return np.bincount(self.owners_of(ks[~exists]),
                           minlength=self.n_shards).astype(np.int64)

    def load_state(self, arrays: dict) -> None:
        """Adopt a host snapshot (field name → stacked ``[S, …]`` numpy
        array, as ``jax.device_get(self.state)`` produces) as this map's
        state, re-sharded onto the mesh — the rebalance journal's
        recovery path.  The arrays must match this map's geometry."""
        st = ShardedState(**{f: jnp.asarray(arrays[f])
                             for f in ShardedState._fields})
        self.state = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(
                self.mesh, P(AXIS, *([None] * (x.ndim - 1))))), st)

    def chain_stats(self) -> Tuple[int, float]:
        """Global (max, mean) chain length over all shards' buckets
        (each shard contributes only its *owned* range — the padding
        rows of an uneven split hold no chains and are excluded)."""
        st = jax.device_get(self.state)
        mx, total = 0, 0.0
        for s, w in enumerate(self.sizes):
            local = batched.HashMapState(*(f[s] for f in st))
            local = local._replace(head=local.head[:w])
            m, mean = batched.chain_stats(
                jax.tree_util.tree_map(jnp.asarray, local), w)
            mx = max(mx, int(m))
            total += float(mean) * w
        return mx, total / self.n_buckets

    # ---------------- migration over the mesh -------------------------- #
    def migrate_to(self, *, capacity: Optional[int] = None,
                   n_buckets: Optional[int] = None,
                   splits: Optional[Sequence[int]] = None,
                   buckets_per_round: Optional[int] = None,
                   ) -> Tuple["ShardedDurableMap", RebalanceReport]:
        """Drain this map into a fresh one — new boundaries and/or a
        larger pool and/or a different global bucket count — in bounded
        rounds of ``buckets_per_round`` *old* global buckets each.

        Every round is one ordinary routed ``update`` on the new map:
        the drained keys ride the same all_to_all to their new owner
        shards and commit through the unmodified plan/commit engine, so
        each migrated key pays O(1) flushes + 2 fences in its new owner
        range and nothing anywhere else — the per-round stats are summed
        into the report as the proof (``foreign_ops == 0``;
        ``bucket_flushes`` nonzero only where the new split says).
        Returns ``(new_map, report)``; the old map is left frozen (do
        not write it again)."""
        nb_new = n_buckets or self.n_buckets
        if splits is None:
            if nb_new == self.n_buckets:
                splits = self.splits
            elif nb_new % self.n_buckets == 0:
                # bucket-count growth keeps the split *shape*: scale the
                # boundaries so each shard keeps its share of the space
                f = nb_new // self.n_buckets
                splits = tuple(b * f for b in self.splits)
            else:
                # never silently fall back to the even partition: that
                # would undo a load-weighted rebalance behind the
                # caller's back (or fail on divisibility mid-migration)
                raise ValueError(
                    f"n_buckets={nb_new} is not a multiple of the "
                    f"current {self.n_buckets}; pass splits= explicitly "
                    f"to re-shape the ranges")
        # compile attribution: a geometry change here is what buys the
        # recompile — a capacity/bucket step is the ladder, a pure
        # boundary move is the re-split width change the ROADMAP taxes
        reason = ("capacity_ladder" if (capacity or n_buckets)
                  else "resplit_width_change")
        with get_tracker().reason(reason):
            new = ShardedDurableMap(
                self.n_shards, capacity=capacity or self.capacity,
                n_buckets=nb_new, mesh=self.mesh, splits=splits)
        bpr = buckets_per_round or max(1, self.n_buckets // 8)
        chain_before = self.chain_stats()
        host = jax.device_get(self.state)
        # per-shard host views in drain_range's dict form (one shared
        # chain-walk implementation with the single-device migration)
        from .migrate import drain_range
        shard_host = [{f: getattr(host, f)[s] for f in host._fields}
                      for s in range(self.n_shards)]
        rounds = migrated = foreign = 0
        bf_total = np.zeros(new.n_buckets, np.int64)
        with get_tracker().reason(reason):  # drain pays the first calls
            for lo in range(0, self.n_buckets, bpr):
                hi = min(lo + bpr, self.n_buckets)
                parts = []
                for s in range(self.n_shards):  # split order = global
                    a = max(lo, self.splits[s])  # bucket-ascending order
                    b = min(hi, self.splits[s + 1])
                    if a < b:
                        parts.append(drain_range(
                            shard_host[s], a - self.splits[s],
                            b - self.splits[s]))
                ks = np.concatenate([p[0] for p in parts])
                vs = np.concatenate([p[1] for p in parts])
                rounds += 1
                if not ks.size:
                    continue
                ok, stats = new.insert(ks, vs)
                if not ok.all():
                    raise RuntimeError(
                        f"rebalance drain overflowed the new pool at "
                        f"global bucket {lo} (capacity {new.capacity})")
                migrated += int(ks.size)
                foreign += int(np.sum(np.asarray(stats.foreign_ops)))
                bf_total += np.asarray(stats.bucket_flushes)
        m = get_registry()
        m.counter("map_drain_rounds_total").inc(rounds)
        m.counter("map_drained_keys_total").inc(migrated)
        return new, RebalanceReport(
            rounds=rounds, migrated=migrated, foreign_ops=foreign,
            bucket_flushes=bf_total.astype(np.int32),
            splits_old=self.splits, splits_new=new.splits,
            chain_before=chain_before, chain_after=new.chain_stats())

    def rebalance(self, splits: Sequence[int], *,
                  buckets_per_round: Optional[int] = None
                  ) -> RebalanceReport:
        """Re-split the bucket ranges in place: migrate every chain to
        its owner under the new boundaries (see :meth:`migrate_to`) and
        adopt the rebalanced state.  The public handle survives — only
        the split (and the node placement that proves it) changes."""
        new, report = self.migrate_to(splits=splits,
                                      buckets_per_round=buckets_per_round)
        self.__dict__.update(new.__dict__)
        return report
