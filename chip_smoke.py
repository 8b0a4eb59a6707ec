#!/usr/bin/env python3
"""Bring-up smoke run of the serving and durable-map path on a TPU chip.

    python chip_smoke.py [--seed 0]     # one chip: serve + hash + ordered
    python chip_smoke.py --chips 4      # only the 4-shard map vs one device

Phases, in order; each prints its wall seconds, which are set-up times
and not metrics:

  device       require a TPU; print its kind and the device count
  serve        qwen3-1.7b at its published widths in bf16, random weights
               from ``--seed``: 16 requests (8 of 512 tokens, 8 of 128),
               batch 8, 32 new tokens, a crash after the first committed
               batch and recovery on a second engine over the same log;
               exactly-once checks and one decode step against a prefill
               over the extended prompt
  hash_map     16M nodes / 4M buckets: 8M distinct keys in 64k-op
               ``update_parallel`` batches, one 50%-update mixed batch
               (64k updates, 64k lookups), 64k more lookups, all against
               the dict oracle
  ordered_map  4M nodes: 3.75M keys in 256k-op batches, one 50%-update
               mixed batch, ``range_query`` and ``top_k`` against the
               dict oracle

With ``--chips 4`` only the sharded phase runs: a 4-shard
``ShardedDurableMap`` against the single-device engine on the mixed
stream of ``benchmarks/sharded_worker.py``.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Any failed
check raises, so the run exits non-zero without that line; so does a run
that finds no TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SERVE_ARCH = "qwen3-1.7b"
SERVE_PROMPTS = (512,) * 8 + (128,) * 8
SERVE_BATCH = 8
SERVE_NEW = 32
HASH_NODES, HASH_BUCKETS, HASH_KEYS, HASH_BATCH = 1 << 24, 1 << 22, \
    1 << 23, 1 << 16
# the ordered engine rebuilds its volatile towers on the host before each
# batch: wider batches mean fewer rebuilds
ORDERED_NODES, ORDERED_KEYS, ORDERED_BATCH = 1 << 22, 15 << 18, 1 << 18


def check(cond, msg: str) -> None:
    """A failed check raises (``assert`` would vanish under ``-O``)."""
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


@contextlib.contextmanager
def phase(name: str):
    """Print the phase's wall seconds and device 0's peak memory so far."""
    t0 = time.perf_counter()
    yield
    secs = time.perf_counter() - t0
    import jax
    mem = jax.devices()[0].memory_stats() or {}
    print(f"phase {name}: {secs:.3f} s (set-up wall time, not a metric); "
          f"peak_bytes_in_use={mem.get('peak_bytes_in_use')}", flush=True)


# --------------------------------------------------------------------- #
# serve                                                                  #
# --------------------------------------------------------------------- #
def decode_vs_prefill(model, params, prompts: np.ndarray, max_len: int):
    """One decode step after a prefill of ``prompts[:, :-1]`` against a
    prefill over the whole prompt.  Returns the largest absolute logit
    difference over the largest reference logit."""
    import jax
    import jax.numpy as jnp
    V = model.cfg.vocab
    prefill = jax.jit(lambda p, b: model.prefill(p, b, max_len))
    S = prompts.shape[1] - 1
    logits, caches = prefill(params, {"tokens": jnp.asarray(prompts[:, :S])})
    dec, _ = jax.jit(model.decode_step)(
        params, jnp.asarray(prompts[:, S]), caches, jnp.int32(S))
    ref, _ = prefill(params, {"tokens": jnp.asarray(prompts)})
    logits, dec, ref = (np.asarray(x[:, -1, :V], np.float32)
                        for x in (logits, dec, ref))
    for name, x in (("prefill", logits), ("decode", dec), ("reference", ref)):
        check(np.isfinite(x).all(), f"{name} logits are not all finite")
    return float(np.abs(dec - ref).max() / np.abs(ref).max())


def serve_phase(arch: str, prompt_lens, *, batch: int, n_new: int,
                seed: int, tol: float) -> None:
    import jax
    from repro.launch.serve import load_model, make_requests, serve_requests
    model, params = load_model(arch, seed)
    cfg = model.cfg
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"serve: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab} params={n_params} dtype={cfg.param_dtype}",
          flush=True)
    requests = make_requests(cfg, prompt_lens, seed)
    rids = sorted(requests)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_log_") as log_dir:
        before, _ = serve_requests(model, params, requests, n_new=n_new,
                                   batch_size=batch, log_dir=log_dir,
                                   crash_after=1)
        check(len(before) == batch,
              f"{len(before)} results committed before the crash, "
              f"expected one batch of {batch}")
        after, eng = serve_requests(model, params, requests, n_new=n_new,
                                    batch_size=batch, log_dir=log_dir)
        check(sorted(after) == rids, "not every rid is committed")
        check(all(after[r] == before[r] for r in before),
              "a result committed before the crash changed")
        check(bool(np.all(eng.took_effect(rids))),
              "took_effect is not true for every rid")
    toks = np.asarray([after[r] for r in rids])
    check(toks.shape == (len(rids), n_new), f"token shape {toks.shape}")
    check(bool(((toks >= 0) & (toks < cfg.vocab)).all()),
          "a generated token is outside [0, vocab)")
    short = min(prompt_lens)
    prompts = np.stack([requests[r] for r in rids
                        if requests[r].shape[0] == short][:batch])
    err = decode_vs_prefill(model, params, prompts,
                            max(prompt_lens) + n_new)
    print(f"serve: decode-vs-prefill max|diff|/max|ref| = {err!r} "
          f"(limit {tol})", flush=True)
    check(err <= tol, f"decode logits differ from prefill by {err}")


# --------------------------------------------------------------------- #
# durable maps                                                           #
# --------------------------------------------------------------------- #
def _distinct_keys(rng, n: int) -> np.ndarray:
    """``n`` distinct int32 keys in [1, 2**31 - 1)."""
    ks = rng.choice((1 << 31) - 2, size=n, replace=False) + 1
    return ks.astype(np.int32)


def _draw(rng, present: np.ndarray, n: int) -> np.ndarray:
    """``n`` keys, half of them from ``present`` and half fresh."""
    old = present[rng.integers(0, present.size, size=n - n // 2)]
    new = rng.integers(1, (1 << 31) - 1, size=n // 2)
    return rng.permutation(np.concatenate([old, new])).astype(np.int32)


def _mixed_batch(rng, present: np.ndarray, n: int):
    """A 50%-update batch: ``n`` inserts and deletes and ``n`` lookups,
    over loaded and fresh keys alike."""
    ops = rng.integers(0, 2, size=n).astype(np.int32)
    vs = rng.integers(0, 1 << 30, size=n).astype(np.int32)
    return ops, _draw(rng, present, n), vs, _draw(rng, present, n)


def _expect_lookup(items: dict, ks):
    found = np.asarray([items.get(int(k), (False, 0))[0] for k in ks])
    vals = np.asarray([items[int(k)][1] if f else 0
                       for k, f in zip(ks, found)], np.int32)
    return found, vals


def hash_phase(*, capacity: int, n_buckets: int, n_keys: int, batch: int,
               seed: int) -> None:
    import jax.numpy as jnp
    from repro.core import batched as B
    from repro.core.ordered import oracle_apply
    from repro.core.sharded import items_of_state
    rng = np.random.default_rng(seed)
    keys = _distinct_keys(rng, n_keys)
    vals = rng.integers(0, 1 << 30, size=n_keys).astype(np.int32)
    items: dict = {}
    state = B.make_state(capacity, n_buckets)
    oks = []
    ins = jnp.full(batch, B.OP_INSERT, jnp.int32)
    for i in range(0, n_keys, batch):
        state, ok, _ = B.update_parallel(
            state, ins, jnp.asarray(keys[i:i + batch]),
            jnp.asarray(vals[i:i + batch]), n_buckets)
        oks.append(ok)
    want = oracle_apply(items, np.zeros(n_keys, np.int32), keys, vals,
                        capacity)
    check(np.array_equal(np.concatenate([np.asarray(o) for o in oks]), want),
          "hash load: per-op ok differs from the oracle")

    ops, ks, vs, look = _mixed_batch(rng, keys, batch)
    state, ok, _ = B.update_parallel(state, jnp.asarray(ops),
                                     jnp.asarray(ks), jnp.asarray(vs),
                                     n_buckets)
    want = oracle_apply(items, ops, ks, vs, capacity)
    check(np.array_equal(np.asarray(ok), want),
          "hash mixed batch: per-op ok differs from the oracle")
    for q in (look, _draw(rng, keys, batch)):
        found, got = B.lookup(state, jnp.asarray(q), n_buckets)
        f_want, v_want = _expect_lookup(items, q)
        check(np.array_equal(np.asarray(found), f_want)
              and np.array_equal(np.asarray(got), v_want),
              "hash lookups differ from the oracle")
    check(items_of_state(state) == items,
          "hash map content differs from the oracle")
    print(f"hash_map: {len(items)} keys, {n_buckets} buckets, "
          f"{capacity} nodes", flush=True)


def ordered_phase(*, capacity: int, n_keys: int, batch: int, seed: int,
                  max_items: int = 4096, n_top: int = 1024) -> None:
    import jax.numpy as jnp
    from repro.core import ordered as O
    rng = np.random.default_rng(seed + 1)
    keys = _distinct_keys(rng, n_keys)
    vals = rng.integers(0, 1 << 30, size=n_keys).astype(np.int32)
    items: dict = {}
    state = O.make_ordered(capacity)
    oks = []
    for i in range(0, n_keys, batch):
        ks = keys[i:i + batch]
        state, ok, _ = O.update_parallel_ordered(
            state, np.zeros(ks.size, np.int32), ks, vals[i:i + batch])
        oks.append(ok)
    want = O.oracle_apply(items, np.zeros(n_keys, np.int32), keys, vals,
                          capacity)
    check(np.array_equal(np.concatenate([np.asarray(o) for o in oks]), want),
          "ordered load: per-op ok differs from the oracle")

    ops, ks, vs, look = _mixed_batch(rng, keys, batch)
    state, ok, _ = O.update_parallel_ordered(state, ops, ks, vs)
    want = O.oracle_apply(items, ops, ks, vs, capacity)
    check(np.array_equal(np.asarray(ok), want),
          "ordered mixed batch: per-op ok differs from the oracle")
    towers = O.build_towers(state)
    found, got = O.lookup_ordered(state, jnp.asarray(look), towers)
    f_want, v_want = _expect_lookup(items, look)
    check(np.array_equal(np.asarray(found), f_want)
          and np.array_equal(np.asarray(got), v_want),
          "ordered lookups differ from the oracle")

    live = np.sort(np.asarray([k for k, (lv, _) in items.items() if lv],
                              np.int64))
    # ranges of one key, of a quarter and all of max_items, and one the
    # output truncates
    for width in (1, max_items // 4, max_items, 2 * max_items):
        i = int(rng.integers(0, live.size - width))
        lo, hi = int(live[i]), int(live[i + width - 1])
        total, rk, rv = O.range_query(state, lo, hi, max_items, towers)
        want = O.oracle_range(items, lo, hi)
        n = min(len(want), max_items)
        check(int(total) == len(want)
              and np.array_equal(np.asarray(rk)[:n], [k for k, _ in want][:n])
              and np.array_equal(np.asarray(rv)[:n], [v for _, v in want][:n]),
              f"range_query [{lo}, {hi}] differs from the oracle")
    count, tk, tv = O.top_k(state, n_top)
    top = live[-n_top:]
    check(int(count) == n_top and np.array_equal(np.asarray(tk), top)
          and np.array_equal(np.asarray(tv), [items[int(x)][1] for x in top]),
          "top_k differs from the oracle")
    O.check_sorted(state)
    check(O.live_items(state) == {k: v for k, (lv, v) in items.items()
                                  if lv},
          "ordered map content differs from the oracle")
    print(f"ordered_map: {live.size} live keys, {capacity} nodes",
          flush=True)


def sharded_phase(n_shards: int, seed: int) -> None:
    """The mixed stream of ``benchmarks/sharded_worker.py`` through an
    ``n_shards``-shard map and the single-device engine: per-key state,
    per-op ok flags, lookups, flush/fence totals and per-bucket flushes
    must agree, no shard may receive a foreign op, and the shards must
    sit on ``n_shards`` distinct devices."""
    import jax
    import jax.numpy as jnp
    from benchmarks.run import (NVT_MIXED_SEED, NVT_NB, NVT_PREPOP,
                                NVT_RATIOS, nvt_mixed_point)
    from repro.core import batched as B
    from repro.core.ordered import oracle_apply
    from repro.core.sharded import ShardedDurableMap, items_of_state
    cap = 1 << 16
    pre = np.arange(1, NVT_PREPOP + 1, dtype=np.int32)
    st_pre, _, _ = B.update_parallel(
        B.make_state(cap, NVT_NB), jnp.zeros(pre.size, jnp.int32),
        jnp.asarray(pre), jnp.asarray(pre), NVT_NB)
    rng = np.random.default_rng(NVT_MIXED_SEED + seed)
    for ratio in NVT_RATIOS:
        upd_ops, upd_ks, upd_vs, look = nvt_mixed_point(rng, ratio)
        items: dict = {}
        oracle_apply(items, np.zeros(pre.size, np.int32), pre, pre)
        want_ok = oracle_apply(items, upd_ops, upd_ks, upd_vs)
        m = ShardedDurableMap(n_shards, capacity=cap, n_buckets=NVT_NB)
        devs = {s.device for s in m.state.key.addressable_shards}
        check(len(devs) == n_shards and devs <= set(jax.devices()),
              f"shards sit on {len(devs)} distinct devices, "
              f"expected {n_shards}")
        m.insert(pre, pre)
        st = st_pre
        if upd_ops.size:
            st, ok_s, stats_s = B.update_parallel(
                st, jnp.asarray(upd_ops), jnp.asarray(upd_ks),
                jnp.asarray(upd_vs), NVT_NB)
            ok_m, stats_m = m.update(upd_ops, upd_ks, upd_vs)
            check(np.array_equal(np.asarray(ok_s), ok_m)
                  and np.array_equal(ok_m, want_ok),
                  f"ratio {ratio}: per-op ok differs")
            foreign = int(np.sum(np.asarray(stats_m.foreign_ops)))
            check(foreign == 0, f"ratio {ratio}: {foreign} foreign ops")
            check(np.array_equal(np.asarray(stats_s.bucket_flushes),
                                 np.asarray(stats_m.bucket_flushes)),
                  f"ratio {ratio}: per-bucket flushes differ")
        f_s, v_s = B.lookup(st, jnp.asarray(look), NVT_NB)
        f_m, v_m = m.lookup(look)
        check(np.array_equal(np.asarray(f_s), f_m)
              and np.array_equal(np.asarray(v_s), v_m),
              f"ratio {ratio}: lookups differ")
        check(items_of_state(st) == m.items() == items,
              f"ratio {ratio}: per-key state differs")
        check(int(st.flushes) == m.flushes and int(st.fences) == m.fences,
              f"ratio {ratio}: flush/fence totals differ")
        print(f"sharded_map: ratio {ratio}%: {n_shards} shards on "
              f"{len(devs)} devices agree with one device, foreign_ops=0",
              flush=True)


# --------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    with phase("device"):
        import jax
        devices = jax.devices()
        if devices[0].platform != "tpu":
            sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                     f"{devices[0].platform!r}); this script runs only on "
                     "the chip")
        check(len(devices) >= args.chips,
              f"--chips {args.chips} but JAX sees {len(devices)} devices")
        print(f"device: kind={devices[0].device_kind!r} "
              f"count={len(devices)}", flush=True)
        from repro.launch.compile_cache import enable_compile_cache
        print(f"device: compile cache at {enable_compile_cache()}",
              flush=True)

    if args.chips == 4:
        with phase("sharded_map"):
            sharded_phase(4, args.seed)
    else:
        with phase("serve"):
            serve_phase(SERVE_ARCH, SERVE_PROMPTS, batch=SERVE_BATCH,
                        n_new=SERVE_NEW, seed=args.seed, tol=5e-2)
        with phase("hash_map"):
            hash_phase(capacity=HASH_NODES, n_buckets=HASH_BUCKETS,
                       n_keys=HASH_KEYS, batch=HASH_BATCH, seed=args.seed)
        with phase("ordered_map"):
            ordered_phase(capacity=ORDERED_NODES, n_keys=ORDERED_KEYS,
                          batch=ORDERED_BATCH, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
