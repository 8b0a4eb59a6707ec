"""Benchmark harness (deliverable d): one function per paper figure plus
framework benches.  Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run [--only fig5a,...]
"""
from __future__ import annotations

import argparse
import sys
import time

# bench_nvt workload shape, shared with benchmarks/sharded_worker.py so
# the sharded section always mirrors the single-device mixed section
NVT_NB = 1024
NVT_N_OPS = 20_000
NVT_PREPOP = 10_000
NVT_MIXED_SEED = 1
NVT_RATIOS = (0, 20, 50)


def nvt_mixed_point(rng, ratio):
    """One mixed-workload point: updates (inserts with fresh + duplicate
    keys interleaved with deletes of mostly-present keys), the rest
    lookups.  The single draw sequence both bench sections consume —
    callers must draw points in NVT_RATIOS order from a fresh
    ``default_rng(NVT_MIXED_SEED)`` for the sections to coincide.
    Returns numpy ``(upd_ops, upd_ks, upd_vs, look_ks)``."""
    import numpy as np
    n_upd = NVT_N_OPS * ratio // 100
    n_look = NVT_N_OPS - n_upd
    upd_ops = rng.integers(0, 2, size=n_upd).astype(np.int32)
    upd_ks = rng.integers(1, 2 * NVT_PREPOP, size=n_upd).astype(np.int32)
    look_ks = rng.integers(1, 2 * NVT_PREPOP, size=n_look).astype(np.int32)
    return upd_ops, upd_ks, upd_ks * 3, look_ks


def _load_report(out_json):
    """Existing bench report, or {} — a truncated file (e.g. an
    interrupted earlier run) self-heals instead of wedging every
    subsequent bench run."""
    import json
    from pathlib import Path
    try:
        return json.loads(Path(out_json).read_text())
    except (FileNotFoundError, json.JSONDecodeError):
        return {}


def bench_paper_figures(rows, only=None):
    from benchmarks.paper_figures import ALL_FIGURES
    for fn in ALL_FIGURES:
        name = fn.__name__.split("_")[0]
        if only and name not in only:
            continue
        t0 = time.time()
        fn(rows)
        print(f"# {fn.__name__} done in {time.time()-t0:.1f}s",
              file=sys.stderr)


def bench_batched_hashmap(rows):
    """Wall-clock throughput of the jitted durable hash map (CPU)."""
    import jax.numpy as jnp
    from repro.core import batched as B
    NB = 1024
    st0 = B.make_state(1 << 16, NB)
    ks = jnp.arange(1, 20_001)
    B.insert(st0, ks, ks, NB)[0].cursor.block_until_ready()   # compile
    t0 = time.perf_counter()
    st, _ = B.insert(st0, ks, ks, NB)
    st.cursor.block_until_ready()
    t_insert = (time.perf_counter() - t0) / 20_000 * 1e6
    q = jnp.arange(1, 50_001)
    B.lookup(st, q, NB)[0].block_until_ready()   # compile
    t0 = time.perf_counter()
    for _ in range(5):
        B.lookup(st, q, NB)[0].block_until_ready()
    t_lookup = (time.perf_counter() - t0) / (5 * 50_000) * 1e6
    rows.append(("batched_hashmap,insert", t_insert,
                 f"fences_per_op={float(st.fences)/20_000:.2f}"))
    rows.append(("batched_hashmap,lookup", t_lookup,
                 "fences_per_op=0.00"))


def bench_nvt(rows, out_json="BENCH_nvt.json"):
    """The PR's headline comparison, machine-readable.

    (a) sequential-scan vs plan/commit insert engines on a 20k-op batch —
        identical per-op fence accounting, coalesced batch fences
        reported alongside;
    (b) nvt_probe Pallas kernel (streamed bucket tiles, interpret mode on
        CPU) vs the XLA reference on a table larger than the old
        whole-table-in-VMEM cap (2 MB), with a bit-exactness check;
    (c) paper-style mixed workloads (§5): 20k-op batches at 0/20/50%
        update ratio (updates split evenly between inserts and deletes,
        the rest lookups) against a pre-populated map — sequential mixed
        oracle (``apply`` + ``lookup``) vs one ``update_parallel`` round
        + the same lookup, with a bit-identical state/ok check.
    """
    import json
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import batched as B
    from repro.kernels.nvt_probe.ops import nvt_probe
    from repro.kernels.nvt_probe.ref import tiles_from_keys

    NB, N_OPS = NVT_NB, NVT_N_OPS
    st0 = B.make_state(1 << 16, NB)
    ks = jnp.arange(1, N_OPS + 1)

    def timed(fn, reps=3):
        fn()                                   # compile (excluded)
        best = float("inf")
        for _ in range(reps):                  # best-of-reps: robust to
            t0 = time.perf_counter()           # scheduler/GC noise
            out = fn()
            best = min(best, time.perf_counter() - t0)
        return out, best

    (st_scan, _), t_scan = timed(
        lambda: jax.block_until_ready(B.insert(st0, ks, ks, NB)))
    (st_par, _, stats), t_par = timed(
        lambda: jax.block_until_ready(B.insert_parallel(st0, ks, ks, NB)))
    state_equal = all(
        bool(jnp.array_equal(getattr(st_scan, f), getattr(st_par, f)))
        for f in st_scan._fields)

    # (b) streamed probe on a 4 MB table (old single-tile cap: 2 MB)
    PNB, CAP, Q, BLOCK_NB = 4096, 256, 256, 512
    rng = np.random.default_rng(0)
    keys = rng.choice(np.arange(1, 1 << 20), size=PNB * CAP // 4,
                      replace=False).astype(np.int32)
    kt, vt = tiles_from_keys(keys, PNB, CAP)
    queries = jnp.asarray(rng.integers(1, 1 << 20, size=Q).astype(np.int32))
    (fx, vx), t_xla = timed(lambda: jax.block_until_ready(
        nvt_probe(kt, vt, queries, impl="xla")))
    (fp, vp), t_pal = timed(lambda: jax.block_until_ready(
        nvt_probe(kt, vt, queries, impl="pallas", interpret=True,
                  block_q=128, block_nb=BLOCK_NB)))
    bit_exact = bool(jnp.array_equal(fx, fp) and jnp.array_equal(vx, vp))

    # (c) mixed workloads at paper update ratios over a pre-populated map
    rng_m = np.random.default_rng(NVT_MIXED_SEED)
    PREPOP = NVT_PREPOP
    pre_ks = jnp.arange(1, PREPOP + 1)
    st_pre, _, _ = B.update_parallel(
        st0, jnp.zeros(PREPOP, jnp.int32), pre_ks, pre_ks, NB)
    jax.block_until_ready(st_pre)
    mixed = {}
    for ratio in NVT_RATIOS:
        upd_ops, upd_ks, upd_vs, look_ks = map(
            jnp.asarray, nvt_mixed_point(rng_m, ratio))
        n_upd = int(upd_ops.shape[0])
        n_look = int(look_ks.shape[0])

        def scan_side():
            st = st_pre
            if n_upd:
                st, ok = B.apply(st, upd_ops, upd_ks, upd_vs, NB)
            else:
                ok = jnp.zeros(0, jnp.bool_)
            return jax.block_until_ready(
                (st, ok, B.lookup(st, look_ks, NB)))

        def par_side():
            st = st_pre
            if n_upd:
                st, ok, stats = B.update_parallel(st, upd_ops, upd_ks,
                                                  upd_vs, NB)
            else:
                ok, stats = jnp.zeros(0, jnp.bool_), None
            return jax.block_until_ready(
                (st, ok, B.lookup(st, look_ks, NB))), stats

        (st_s, ok_s, look_s), t_s = timed(scan_side, reps=5)
        ((st_m, ok_m, look_m), stats_m), t_m = timed(par_side, reps=5)
        ident = all(
            bool(jnp.array_equal(getattr(st_s, f), getattr(st_m, f)))
            for f in st_s._fields) and bool(jnp.array_equal(ok_s, ok_m)) \
            and all(bool(jnp.array_equal(a, b))
                    for a, b in zip(look_s, look_m))
        # chain shape after the round: the baseline future resize/rehash
        # work compares against (load factor = live keys per bucket)
        max_chain, mean_chain = B.chain_stats(st_m, NB)
        mixed[str(ratio)] = {
            "update_ratio": ratio,
            "batch_ops": N_OPS,
            "n_updates": n_upd,
            "n_lookups": n_look,
            "scan_us_per_op": t_s / N_OPS * 1e6,
            "parallel_us_per_op": t_m / N_OPS * 1e6,
            "speedup": t_s / t_m,
            "state_identical": ident,
            "coalesced_fences": (int(stats_m.coalesced_fences)
                                 if stats_m is not None else 0),
            "chain_stats": {
                "max_chain": int(max_chain),
                "mean_chain": float(mean_chain),
                "load_factor": int(st_m.live.sum()) / NB,
            },
        }

    # merge (don't rewrite): a partial run must not discard sections
    # other benches own, e.g. the sharded section of --only sharded
    report = _load_report(out_json)
    report.update({
        "insert": {
            "batch_ops": N_OPS,
            "n_buckets": NB,
            "scan_us_per_op": t_scan / N_OPS * 1e6,
            "parallel_us_per_op": t_par / N_OPS * 1e6,
            "speedup": t_scan / t_par,
            "state_identical": state_equal,
            "fences_scan": int(st_scan.fences),
            "fences_parallel": int(st_par.fences),
            "fences_per_op": float(st_par.fences) / N_OPS,
            "coalesced_fences": int(stats.coalesced_fences),
            "coalesced_flushes": int(stats.coalesced_flushes),
            "max_conflict_group": int(stats.max_group),
        },
        "mixed": mixed,
        "probe": {
            "n_buckets": PNB,
            "bucket_cap": CAP,
            "table_bytes": int(PNB * CAP * 4),
            "old_vmem_cap_bytes": 2 * 1024 * 1024,
            "block_nb": BLOCK_NB,
            "queries": Q,
            "xla_us_per_query": t_xla / Q * 1e6,
            "pallas_interpret_us_per_query": t_pal / Q * 1e6,
            "bit_exact": bit_exact,
        },
    })
    with open(out_json, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {out_json}", file=sys.stderr)
    ins = report["insert"]
    rows.append(("nvt,insert_scan", ins["scan_us_per_op"],
                 f"fences_per_op={ins['fences_per_op']:.2f}"))
    rows.append(("nvt,insert_parallel", ins["parallel_us_per_op"],
                 f"speedup={ins['speedup']:.1f}x;"
                 f"coalesced_fences={ins['coalesced_fences']}"))
    for ratio, m in mixed.items():
        rows.append((f"nvt,mixed_{ratio}pct_parallel",
                     m["parallel_us_per_op"],
                     f"speedup={m['speedup']:.1f}x;"
                     f"state_identical={m['state_identical']}"))
    rows.append(("nvt,probe_xla", report["probe"]["xla_us_per_query"],
                 f"table_mb={PNB*CAP*4/2**20:.0f}"))
    rows.append(("nvt,probe_pallas_interpret",
                 report["probe"]["pallas_interpret_us_per_query"],
                 f"bit_exact={bit_exact}"))


def bench_nvt_ordered(rows, out_json="BENCH_nvt.json"):
    """OrderedNVT: the plan/commit engine on the sorted bottom list.

    (a) mixed insert/delete batch over a pre-populated ordered map —
        sequential scan oracle (:func:`repro.core.ordered.apply_ordered`,
        one head-to-predecessor walk per op) vs one
        ``update_parallel_ordered`` round descending the volatile
        towers, with a bit-identical state/ok/accounting check *and* a
        pure-dict+sorted oracle content check;
    (b) volatile tower (re)build cost — the Property 2 reconstruction
        the recovery path pays;
    (c) ordered reads on a seeded zipf workload: ``range_query`` (every
        answer checked against the sorted-dict oracle) and ``top_k``
        us/query.

    The batch here is sized so the O(n²)-walk scan oracle stays a
    few-second bench; the 20k-op acceptance identity runs in
    ``tests/test_ordered.py`` (slow lane).
    """
    import json
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import ordered as O

    CAP = 1 << 13
    PREPOP = 2_000
    N_OPS = 4_000
    KEYSPACE = 40_000
    rng = np.random.default_rng(NVT_MIXED_SEED)
    pre = np.sort(rng.choice(np.arange(1, KEYSPACE), PREPOP,
                             replace=False)).astype(np.int32)
    st0 = O.make_ordered(CAP)
    st0, ok0, _ = O.update_parallel_ordered(
        st0, np.zeros(PREPOP, np.int32), pre, pre * 3)
    assert bool(np.asarray(ok0).all())
    model: dict = {}
    O.oracle_apply(model, np.zeros(PREPOP, np.int32), pre, pre * 3,
                   capacity=CAP)
    jax.block_until_ready(st0)

    # (a) one mixed batch: ~half hits (deletes/duplicate inserts), half
    # fresh keys — duplicate-key groups and shared predecessors included
    ops = rng.integers(0, 2, N_OPS).astype(np.int32)
    ks = np.where(rng.random(N_OPS) < 0.5,
                  rng.choice(pre, N_OPS),
                  rng.integers(1, KEYSPACE, N_OPS)).astype(np.int32)
    vs = rng.integers(0, 10_000, N_OPS).astype(np.int32)

    def timed(fn, reps=3):
        fn()                                   # compile (excluded)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            best = min(best, time.perf_counter() - t0)
        return out, best

    towers0, t_towers = timed(lambda: O.build_towers(st0))
    (st_s, ok_s), t_scan = timed(lambda: jax.block_until_ready(
        O.apply_ordered(st0, jnp.asarray(ops), jnp.asarray(ks),
                        jnp.asarray(vs))), reps=2)
    (st_p, ok_p, stats), t_par = timed(lambda: jax.block_until_ready(
        O.update_parallel_ordered(st0, ops, ks, vs, towers=towers0)))
    ident = all(
        bool(jnp.array_equal(getattr(st_s, f), getattr(st_p, f)))
        for f in st_s._fields) and bool(jnp.array_equal(ok_s, ok_p))
    ok_m = O.oracle_apply(model, ops, ks, vs, capacity=CAP)
    dict_ident = (O.items_host(st_p) == model
                  and bool(np.array_equal(np.asarray(ok_p),
                                          np.asarray(ok_m, bool))))

    # (c) ordered reads over the post-batch state, seeded zipf spans
    towers = O.build_towers(st_p)
    spans = []
    for _ in range(64):
        lo = int((rng.zipf(1.3) * 37) % KEYSPACE)
        spans.append((lo, lo + int(rng.integers(50, 2_000))))
    range_ident = True
    for lo, hi in spans:
        want = O.oracle_range(model, lo, hi)
        total, rk, rv = O.range_query(st_p, lo, hi, 1024, towers)
        got = list(zip(np.asarray(rk)[:len(want)].tolist(),
                       np.asarray(rv)[:len(want)].tolist()))
        range_ident &= (int(total) == len(want) and got == want)

    def range_all():
        for lo, hi in spans:
            out = O.range_query(st_p, lo, hi, 1024, towers)
        return jax.block_until_ready(out)

    _, t_range = timed(range_all)
    cnt, tk_keys, tk_vals = O.top_k(st_p, 128)
    alive = sorted(O.live_items(st_p))
    topk_ident = (np.asarray(tk_keys)[:int(cnt)].tolist()
                  == alive[-int(cnt):])
    _, t_topk = timed(lambda: jax.block_until_ready(
        O.top_k(st_p, 128)))

    report = _load_report(out_json)
    report["ordered"] = {
        "capacity": CAP,
        "prepop": PREPOP,
        "batch_ops": N_OPS,
        "scan_us_per_op": t_scan / N_OPS * 1e6,
        "parallel_us_per_op": t_par / N_OPS * 1e6,
        "speedup": t_scan / t_par,
        "state_identical": bool(ident),
        "dict_oracle_identical": bool(dict_ident),
        "fences_scan": int(st_s.fences),
        "fences_parallel": int(st_p.fences),
        "coalesced_fences": int(stats.coalesced_fences),
        "max_conflict_group": int(stats.max_group),
        "conflict_groups": int(stats.conflict_groups),
        "tower_build_us": t_towers * 1e6,
        "range": {
            "queries": len(spans),
            "max_items": 1024,
            "us_per_query": t_range / len(spans) * 1e6,
            "identical": bool(range_ident),
        },
        "top_k": {
            "k": 128,
            "us_per_call": t_topk * 1e6,
            "identical": bool(topk_ident),
        },
    }
    with open(out_json, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {out_json}", file=sys.stderr)
    o = report["ordered"]
    rows.append(("ordered,mixed_scan", o["scan_us_per_op"],
                 f"batch={N_OPS}"))
    rows.append(("ordered,mixed_parallel", o["parallel_us_per_op"],
                 f"speedup={o['speedup']:.1f}x;"
                 f"state_identical={o['state_identical']};"
                 f"dict_oracle_identical={o['dict_oracle_identical']}"))
    rows.append(("ordered,range_query", o["range"]["us_per_query"],
                 f"identical={o['range']['identical']}"))
    rows.append(("ordered,top_k", o["top_k"]["us_per_call"],
                 f"identical={o['top_k']['identical']}"))


def bench_nvt_migrate(rows, out_json="BENCH_nvt.json"):
    """Online-growth section: a map seeded at capacity C absorbs 8C
    inserts under live mixed traffic, growing itself through the bounded
    migration rounds of :mod:`repro.core.migrate` — per point we record
    migrations run, amortized rounds per op, wall time per op,
    chain/load-factor shape before and after growth, and a per-key
    content-identity check against a python-dict oracle driven through
    the same stream.  Points: update ratio 0/20/50% × uniform vs skewed
    (zipf) update keys.  Merged under ``out_json["migrate"]``."""
    import json
    import numpy as np
    from repro.core.migrate import MigratingMap

    C, NB0, BATCH = 2048, 64, 512
    TOTAL = 8 * C
    migrate = {}
    for dist in ("uniform", "skewed"):
        for ratio in NVT_RATIOS:
            rng = np.random.default_rng(NVT_MIXED_SEED + ratio)
            m = MigratingMap(capacity=C, n_buckets=NB0,
                             rounds_per_update=2)
            model = {}
            next_key = 1
            chain0 = None
            t_map = 0.0       # time in m.update() only — the dict
            inserted = 0      # oracle + chain sampling stay untimed so
            n_ops = 0         # us_per_op is comparable to the sections
            while inserted < TOTAL:       # that time bare engine calls
                n_upd = BATCH * ratio // 100
                n_ins = BATCH - n_upd
                n_ops += BATCH
                ks_ins = np.arange(next_key, next_key + n_ins,
                                   dtype=np.int32)
                next_key += n_ins
                inserted += n_ins
                seen = max(1, next_key - 1)
                if dist == "uniform":
                    ks_upd = rng.integers(
                        1, seen + 1, size=n_upd).astype(np.int32)
                else:
                    ks_upd = (rng.zipf(1.3, size=n_upd)
                              % seen + 1).astype(np.int32)
                ops = np.concatenate([
                    np.zeros(n_ins, np.int32),
                    rng.integers(0, 2, size=n_upd).astype(np.int32)])
                ks = np.concatenate([ks_ins, ks_upd])
                vs = (ks * 3).astype(np.int32)
                t0 = time.perf_counter()
                ok = m.update(ops, ks, vs)
                t_map += time.perf_counter() - t0
                for o, k, v, okk in zip(ops, ks, vs, ok):
                    k = int(k)
                    if o == 0:
                        if bool(okk):
                            model[k] = int(v)
                    elif bool(okk):
                        del model[k]
                if m.migrations_completed == 0 and not m.migrating:
                    # keep the newest pre-growth shape: the last sample
                    # before the first migration is the seed table at
                    # its fullest — the "before" of the chain comparison
                    from repro.core import batched as B
                    mx0, mean0 = B.chain_stats(m.state, m.n_buckets)
                    chain0 = (int(mx0), float(mean0),
                              len(model) / m.n_buckets)
            from repro.core import batched as B
            items = m.items()
            live = {k for k, (l, _) in items.items() if l}
            ident = live == set(model) and all(
                items[k][1] == v for k, v in model.items())
            mx1, mean1 = B.chain_stats(m.state, m.n_buckets)
            migrate[f"{dist}_{ratio}"] = {
                "distribution": dist,
                "update_ratio": ratio,
                "seed_capacity": C,
                "inserts_absorbed": TOTAL,
                "final_capacity": m.capacity,
                "final_n_buckets": m.n_buckets,
                "migrations": m.migrations_completed,
                "rounds": m.rounds_total,
                "rounds_per_op": m.rounds_total / n_ops,
                "pulls": m.pulls_total,
                "us_per_op": t_map / n_ops * 1e6,
                "state_identical": bool(ident),
                "chain_stats_before": {
                    "max_chain": chain0[0],
                    "mean_chain": chain0[1],
                    "load_factor": chain0[2],
                } if chain0 else None,
                "chain_stats_after": {
                    "max_chain": int(mx1),
                    "mean_chain": float(mean1),
                    "load_factor": len(live) / m.n_buckets,
                },
            }
    report = _load_report(out_json)
    report["migrate"] = {
        "seed_capacity": C,
        "seed_n_buckets": NB0,
        "growth_factor": 8,
        "note": "us_per_op includes jit compiles for newly reached "
                "capacities; the first point pays most of them",
        "points": migrate,
    }
    with open(out_json, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# merged migrate section into {out_json}", file=sys.stderr)
    for name, p in migrate.items():
        rows.append((f"nvt,migrate_{name}", p["us_per_op"],
                     f"migrations={p['migrations']};"
                     f"rounds_per_op={p['rounds_per_op']:.4f};"
                     f"state_identical={p['state_identical']}"))


def _run_worker(module: str, n_dev: int) -> dict:
    """Run one forced-host-device bench worker subprocess (the
    ``--xla_force_host_platform_device_count`` flag must land before
    jax initializes, and this process's jax is already up) and parse
    its single-JSON-document stdout.

    Only on the CPU backend: on an accelerator this process holds the
    chip, and a child that needs a device would fail or hang."""
    import json
    import os
    import subprocess

    import jax
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{module} runs on forced host devices in a child process, "
            f"but this process holds the {jax.default_backend()} backend; "
            "a chip serves one process at a time.  Run this section with "
            "JAX_PLATFORMS=cpu; the multi-chip path is "
            "`python chip_smoke.py --chips 4`.")
    env = dict(os.environ)
    env["PYTHONPATH"] = ("src" + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else "src")
    proc = subprocess.run(
        [sys.executable, "-m", module, str(n_dev)],
        capture_output=True, text=True, env=env)
    if proc.returncode != 0:
        print(proc.stderr, file=sys.stderr)
        raise RuntimeError(f"{module} ({n_dev} devices) failed")
    return json.loads(proc.stdout)


def bench_nvt_sharded(rows, out_json="BENCH_nvt.json",
                      device_counts=(1, 2, 4, 8)):
    """Sharded durable map vs the single-device plan/commit engine on
    1/2/4/8 forced host devices (same mixed-workload points as the
    single-device section).  Each device count runs in a subprocess —
    ``XLA_FLAGS=--xla_force_host_platform_device_count`` must land
    before jax initializes, and this process's jax is already up.
    Results (state-identity check, per-point timing, chain_stats,
    persistence-locality counters) merge into ``out_json["sharded"]``.
    """
    import json

    sharded = {}
    for n_dev in device_counts:
        print(f"# sharded worker: {n_dev} host devices...",
              file=sys.stderr)
        sharded[str(n_dev)] = _run_worker("benchmarks.sharded_worker",
                                          n_dev)
    report = _load_report(out_json)
    report["sharded"] = sharded
    with open(out_json, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# merged sharded section into {out_json}", file=sys.stderr)
    for n_dev, res in sharded.items():
        p = res["points"]["50"]
        rows.append((f"nvt,sharded_{n_dev}dev_mixed50",
                     p["sharded_us_per_op"],
                     f"vs_single={p['single_us_per_op']:.3f}us;"
                     f"state_identical={res['state_identical']};"
                     f"max_chain={p['chain_stats']['max_chain']}"))


def bench_nvt_rebalance_live(rows, out_json="BENCH_nvt.json",
                             device_counts=(2, 4)):
    """Live cross-shard rebalancing under a zipf-skewed mixed stream
    (benchmarks/rebalance_worker.py per forced-host-device count): the
    auto policy must trigger at least one re-split under live traffic,
    final per-key content must match a never-rebalanced map + a dict
    oracle, every flush must stay in its owner range, and the final
    per-shard imbalance must not exceed the trigger imbalance.  Results
    merge into ``out_json["rebalance_live"]``."""
    import json

    section = {}
    for n_dev in device_counts:
        print(f"# rebalance_live worker: {n_dev} host devices...",
              file=sys.stderr)
        section[str(n_dev)] = _run_worker("benchmarks.rebalance_worker",
                                          n_dev)
    report = _load_report(out_json)
    report["rebalance_live"] = {
        "note": "us_per_op includes the shard_map recompiles a re-split "
                "forces (new max range width) plus drain rounds, "
                "amortized over a short stream; plain_us_per_op is the "
                "never-rebalanced floor on the same traffic",
        **section,
    }
    with open(out_json, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# merged rebalance_live section into {out_json}",
          file=sys.stderr)
    for n_dev, p in section.items():
        rows.append((f"nvt,rebalance_live_{n_dev}dev", p["us_per_op"],
                     f"rebalances={p['rebalances']};"
                     f"imbalance={p['trigger_imbalance']:.2f}"
                     f"->{p['final_imbalance']:.2f};"
                     f"state_identical={p['state_identical']}"))


def bench_nvt_restart(rows, out_json="BENCH_nvt.json",
                      sizes=(1_000, 10_000, 100_000)):
    """Serving-restart latency: O(1) with snapshots vs O(history).

    For each size we build a request log with that many committed rids
    (batched records, a 512-rid retention window evicting in the same
    records), in two variants: no snapshots (restart replays every
    record) and periodic truncating snapshots via
    :meth:`repro.serving.engine.RequestLog.snapshot` (restart seeds
    from the newest snapshot and replays only the suffix — the builds
    end on a snapshot boundary, so the suffix is empty).  Restart time
    is best-of-3 ``RequestLog(root)`` construction after a warmup
    restart (jit/compile excluded — steady-state restart is what a
    serving fleet pays).  ``flat_ratio_snap`` (largest/smallest
    snapshot-restart time) is the O(1) claim; ``records_parsed`` makes
    the replayed-suffix length machine-checkable, and
    ``took_effect_no_replay`` asserts a recovering client's probe
    parses zero additional records.  Merged under
    ``out_json["restart"]``."""
    import json
    import tempfile
    from pathlib import Path
    from repro.serving.engine import RequestLog

    BATCH, RETAIN, SNAP_EVERY = 50, 512, 10     # rids/record, window,
    points = {}                                  # commits per snapshot
    with tempfile.TemporaryDirectory() as d:
        for n in sizes:
            n_commits = n // BATCH
            assert n_commits % SNAP_EVERY == 0   # end on a snap boundary
            pt = {"committed_rids": n, "records_written": n_commits}
            for variant in ("nosnap", "snap"):
                root = Path(d) / f"{variant}_{n}"
                log = RequestLog(root)
                rid = 0
                for c in range(n_commits):
                    log.commit({rid + i: [rid + i] for i in range(BATCH)},
                               evict=log.expired_rids(RETAIN))
                    rid += BATCH
                    if variant == "snap" and (c + 1) % SNAP_EVERY == 0:
                        log.snapshot()
                RequestLog(root)                 # warmup (jit compiles)
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    fresh = RequestLog(root)
                    best = min(best, time.perf_counter() - t0)
                pt[f"{variant}_restart_ms"] = best * 1e3
                pt[f"{variant}_records_parsed"] = fresh.records_parsed
                # detectable recovery: the probe answers from the map,
                # no further record parsing
                parsed0 = fresh.records_parsed
                alive = bool(fresh.took_effect([rid - 1])[0])
                evicted = bool(fresh.took_effect([0])[0])
                pt[f"{variant}_took_effect_no_replay"] = (
                    alive and not evicted
                    and fresh.records_parsed == parsed0)
            points[str(n)] = pt
    snap_ms = [points[str(n)]["snap_restart_ms"] for n in sizes]
    nosnap_ms = [points[str(n)]["nosnap_restart_ms"] for n in sizes]
    section = {
        "batch_rids_per_record": BATCH,
        "retain": RETAIN,
        "snap_every_commits": SNAP_EVERY,
        "points": points,
        "flat_ratio_snap": max(snap_ms) / min(snap_ms),
        "growth_ratio_nosnap": nosnap_ms[-1] / nosnap_ms[0],
        "took_effect_no_replay": all(
            points[str(n)][f"{v}_took_effect_no_replay"]
            for n in sizes for v in ("nosnap", "snap")),
    }
    report = _load_report(out_json)
    report["restart"] = section
    with open(out_json, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# merged restart section into {out_json}", file=sys.stderr)
    for n in sizes:
        pt = points[str(n)]
        rows.append((f"nvt,restart_snap_{n}",
                     pt["snap_restart_ms"] * 1e3,
                     f"records_parsed={pt['snap_records_parsed']};"
                     f"nosnap_ms={pt['nosnap_restart_ms']:.1f}"))
    rows.append(("nvt,restart_flat_ratio",
                 section["flat_ratio_snap"],
                 f"nosnap_growth={section['growth_ratio_nosnap']:.1f}x;"
                 f"took_effect_no_replay="
                 f"{section['took_effect_no_replay']}"))


def bench_nvt_obs(rows, out_json="BENCH_nvt.json",
                  snap_path="OBS_metrics.json"):
    """NVTrace observability section: what the instrumentation *sees*
    and what it *costs*, merged under ``out_json["obs"]``.

    Four sub-reports:

    * ``serving`` — a tiny qwen2-family :class:`ServeEngine` on a fresh
      registry serves a measured request wave (after a warmup wave that
      absorbs the jit compiles); ``serve_request_us`` yields p50/p99,
      the ``span_us{phase=...}`` histograms yield the per-phase (route /
      plan / commit / flush_fence / publish / snapshot) breakdown, and
      the span persistence counts exhibit the paper's asymmetry at
      runtime: the traversal phases (``route``/``plan``) charge **zero**
      persistence instructions, the commit/snapshot phases pay all of
      them (``traversal_free_persistence``).
    * ``consistency`` — the same RequestLog workload runs once under a
      :class:`repro.obs.spans.FaultsTee` feeding both a ``PersistTrace``
      and the span listener; the tracer's lifetime totals, the
      per-finished-span sums, and the trace's per-kind event counts must
      agree exactly (the two observability layers cross-validate on an
      identical run).
    * ``overhead`` — a mixed 50%-update serving point (alternating
      single-rid ``commit`` / ``took_effect`` probe) timed best-of
      interleaved with ``obs=True`` vs ``obs=False``; the enabled /
      disabled us/op ratio is the instrumentation tax CI bounds at 5%.
    * ``compile`` — ``benchmarks/obs_worker.py`` on 2 forced host
      devices: the zipf-skewed rebalance_live stream plus one explicit
      capacity step, with every first-call XLA stall attributed to its
      trigger (re-split width change vs capacity ladder vs steady).

    The measured serving registry is also dumped to ``snap_path`` — the
    artifact the CI obs lane uploads and ``tools/metrics_dump.py``
    smoke-reads."""
    import json
    import tempfile
    from collections import Counter
    from pathlib import Path

    import jax
    import numpy as np

    from repro.analysis.trace import PersistTrace
    from repro.configs.registry import get_arch, tiny
    from repro.models.model import build_model
    from repro.obs.metrics import MetricsRegistry
    from repro.obs.spans import FaultsTee, Tracer
    from repro.serving.engine import RequestLog, ServeEngine

    PHASES = ("route", "plan", "commit", "flush_fence", "publish",
              "snapshot")
    TRAVERSAL, PERSISTING = ("route", "plan"), ("commit", "snapshot")

    # ---- serving latency + per-phase breakdown ----------------------
    cfg = tiny(get_arch("qwen2-7b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def wave(base, n=24):
        return {base + i: rng.integers(0, cfg.vocab, size=12)
                .astype(np.int32) for i in range(n)}

    reg = MetricsRegistry()
    with tempfile.TemporaryDirectory() as d:
        eng = ServeEngine(model, params, max_len=32, log_dir=d,
                          batch_size=4, retain=64, snapshot_every=4,
                          registry=reg)
        eng.serve(wave(10_000, n=8), n_new=4)     # warmup: jit compiles
        reg.reset()                               # measure steady-state
        eng.serve(wave(0), n_new=4)
        lat = reg.histogram("serve_request_us", lo=1.0, hi=1e8,
                            growth=1.25)
        phases = {}
        for ph in PHASES:
            h = reg.histogram("span_us", lo=0.1, hi=1e8, growth=1.25,
                              phase=ph)
            if h.count:
                phases[ph] = {"count": h.count,
                              "p50_us": h.quantile(0.5),
                              "p99_us": h.quantile(0.99)}
        by_phase = {ph: 0 for ph in PHASES}
        for r in eng.tracer.records():
            by_phase[r["span"]] = (by_phase.get(r["span"], 0)
                                   + sum(r["counts"].values()))
        serving = {
            "requests": lat.count,
            "p50_us": lat.quantile(0.5),
            "p99_us": lat.quantile(0.99),
            "phases": phases,
            "persist_events_by_phase": by_phase,
            # the paper's claim, live: traversal phases persist nothing
            "traversal_free_persistence": (
                all(by_phase[p] == 0 for p in TRAVERSAL)
                and sum(by_phase[p] for p in PERSISTING) > 0),
        }
        reg.dump_json(snap_path)

    # ---- span counts vs PersistTrace on an identical run ------------
    reg2 = MetricsRegistry()
    tracer = Tracer(registry=reg2)
    with tempfile.TemporaryDirectory() as d:
        log = RequestLog(d, registry=reg2, tracer=tracer)
        trace = PersistTrace()
        FaultsTee(trace, log.io.faults).attach(log.io)
        rid = 0
        with tracer.span("workload"):
            for b in range(8):
                log.commit({rid + i: [rid + i] for i in range(4)},
                           evict=log.expired_rids(16))
                rid += 4
                if (b + 1) % 3 == 0:
                    log.snapshot()
        by_kind = dict(Counter(e.kind for e in trace.events))
    consistency = {
        "trace_events": by_kind,
        "tracer_totals": dict(tracer.totals),
        "span_counts": dict(tracer.span_counts),
        "span_trace_consistent": (tracer.totals == by_kind
                                  and tracer.span_counts == by_kind),
    }

    # ---- instrumentation overhead, mixed 50%-update point -----------
    # Paired interleaved measurement: the same op runs back-to-back on
    # an obs=True and an obs=False log (order alternating per op class
    # to cancel fs-commit batching effects), and the estimate is the
    # *median of per-pair differences* — commit latency on a real fs is
    # noisy enough that independently-timed runs cannot resolve a
    # few-percent delta, but paired differences can.
    STEPS, BATCH, TRIALS = 900, 4, 3

    def overhead_trial():
        lr = np.random.default_rng(7)
        with tempfile.TemporaryDirectory() as da, \
                tempfile.TemporaryDirectory() as db:
            logs = {True: RequestLog(da, registry=MetricsRegistry(),
                                     obs=True),
                    False: RequestLog(db, registry=MetricsRegistry(),
                                      obs=False)}
            for log in logs.values():
                log.commit({-1: [0]})             # warm the io path
            diff = {"c": [], "p": []}
            base = {"c": [], "p": []}
            rid = 0
            seen = {"c": 0, "p": 0}
            for step in range(STEPS):
                cls = "c" if step % 2 == 0 else "p"
                order = ((True, False) if seen[cls] % 2 == 0
                         else (False, True))
                seen[cls] += 1
                t = {}
                if cls == "c":                    # 50% updates...
                    batch = {rid + j: [rid + j] for j in range(BATCH)}
                    rid += BATCH
                    for obs in order:
                        t0 = time.perf_counter_ns()
                        logs[obs].commit(batch)
                        t[obs] = time.perf_counter_ns() - t0
                else:                             # ...50% probes
                    probes = [int(x)
                              for x in lr.integers(0, rid, size=BATCH)]
                    for obs in order:
                        t0 = time.perf_counter_ns()
                        logs[obs].took_effect(probes)
                        t[obs] = time.perf_counter_ns() - t0
                diff[cls].append(t[True] - t[False])
                base[cls].append(t[False])
            off_us = (np.median(base["c"]) + np.median(base["p"])) \
                / 2 / 1e3
            delta_us = (np.median(diff["c"]) + np.median(diff["p"])) \
                / 2 / 1e3
            return off_us, delta_us

    trials = sorted((overhead_trial() for _ in range(TRIALS)),
                    key=lambda t: t[1] / t[0])
    off_us, delta_us = trials[TRIALS // 2]        # median trial
    overhead = {
        "ops": STEPS, "batch": BATCH, "trials": TRIALS,
        "disabled_us_per_op": off_us,
        "enabled_us_per_op": off_us + delta_us,
        "delta_us_per_op": delta_us,
        "ratio": 1 + delta_us / off_us,
    }

    # ---- compile-stall attribution (2 forced host devices) ----------
    print("# obs worker: 2 host devices...", file=sys.stderr)
    compile_rep = _run_worker("benchmarks.obs_worker", 2)
    compile_rep["by_trigger"] = compile_rep.pop("compile")

    report = _load_report(out_json)
    report["obs"] = {"serving": serving, "consistency": consistency,
                     "overhead": overhead, "compile": compile_rep,
                     "metrics_snapshot": snap_path}
    with open(out_json, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# merged obs section into {out_json}", file=sys.stderr)
    rows.append(("nvt,obs_serve_p50", serving["p50_us"],
                 f"p99={serving['p99_us']:.0f}us;"
                 f"traversal_free={serving['traversal_free_persistence']}"))
    rows.append(("nvt,obs_overhead_ratio", overhead["ratio"],
                 f"enabled={overhead['enabled_us_per_op']:.1f}us;"
                 f"disabled={overhead['disabled_us_per_op']:.1f}us"))
    for trig, st in sorted(compile_rep["by_trigger"].items()):
        rows.append((f"nvt,obs_compile_{trig}", st["stall_us"],
                     f"events={st['events']}"))


def bench_checkpoint(rows):
    """NVTraverse commit vs fence-per-write baseline (paper insight at
    framework scale) on a ~25M-param pytree."""
    import tempfile
    import jax.numpy as jnp
    from repro.persistence.checkpoint import CheckpointManager
    tree = {"p": {f"l{i}": jnp.zeros((256, 1024)) for i in range(24)}}
    FSYNC_US = 1000.0     # nominal NVMe fsync
    for policy in ("nvtraverse", "izraelevitz"):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, policy=policy)
            t0 = time.time()
            mgr.save(1, tree)
            tree2 = dict(tree)
            tree2["p"] = dict(tree["p"])
            tree2["p"]["l0"] = tree["p"]["l0"] + 1
            mgr.save(2, tree2)            # delta commit
            wall = (time.time() - t0) / 2 * 1e6
            c = mgr.io.counters
            derived = (f"fences={c.fences};modeled_us="
                       f"{wall + c.fences * FSYNC_US:.0f}")
            rows.append((f"checkpoint,{policy}", wall, derived))


def bench_kernels(rows):
    """Kernel microbenches: XLA-path wall time (CPU); the Pallas kernels
    are TPU-targeted and validated in interpret mode (tests/test_kernels)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.ssd_scan.ops import ssd_scan
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (4, 512, 8, 64), jnp.float32)
    k = jax.random.normal(ks[1], (4, 512, 4, 64), jnp.float32)
    v = jax.random.normal(ks[2], (4, 512, 4, 64), jnp.float32)
    flash_attention(q, k, v, impl="xla").block_until_ready()
    t0 = time.time()
    for _ in range(3):
        flash_attention(q, k, v, impl="xla").block_until_ready()
    rows.append(("kernel,attention_ref_xla_cpu", (time.time()-t0)/3*1e6,
                 "pallas_validated=interpret"))
    xh = jax.random.normal(ks[3], (2, 1024, 8, 64), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (2, 1024, 8)))
    A = -jnp.ones((8,))
    Bm = jax.random.normal(ks[3], (2, 1024, 64)) * 0.5
    Cm = jax.random.normal(ks[4], (2, 1024, 64)) * 0.5
    ssd_scan(xh, dt, A, Bm, Cm, impl="xla").block_until_ready()
    t0 = time.time()
    for _ in range(3):
        ssd_scan(xh, dt, A, Bm, Cm, impl="xla").block_until_ready()
    rows.append(("kernel,ssd_scan_ref_xla_cpu", (time.time()-t0)/3*1e6,
                 "pallas_validated=interpret"))


def bench_roofline(rows):
    """Roofline terms per (arch × shape) cell from the dry-run artifacts
    (baseline + optimized-defaults matrices when present)."""
    from pathlib import Path
    try:
        from repro.roofline.analysis import load_table
    except Exception as e:    # dry-run not executed yet
        print(f"# roofline skipped: {e}", file=sys.stderr)
        return
    for tag, d in (("base", "benchmarks/results/dryrun"),
                   ("opt", "benchmarks/results/dryrun_opt")):
        if not Path(d).exists():
            continue
        table, _ = load_table(d)
        for r in table:
            dom_t = max(r["t_compute_s"], r["t_memory_s"],
                        r["t_collective_s"])
            rows.append((f"roofline_{tag},{r['arch']},{r['shape']}",
                         dom_t * 1e6,
                         f"dominant={r['dominant']};frac="
                         f"{r['roofline_fraction']:.3f}"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig5a,fig5b,fig5c,fig5d,fig5e,fig5f,"
                         "fig6,hashmap,batched,nvt,ordered,migrate,"
                         "sharded,rebalance_live,restart,obs,ckpt,"
                         "kernels,roofline")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    rows = []
    if only is None or any(o.startswith("fig") for o in only):
        bench_paper_figures(rows, only)
    if only is None or only & {"hashmap", "batched"}:
        bench_batched_hashmap(rows)
    if only is None or only & {"nvt", "batched"}:
        bench_nvt(rows)
    if only is None or "ordered" in only:
        bench_nvt_ordered(rows)
    if only is None or "migrate" in only:
        bench_nvt_migrate(rows)
    if only is None or "sharded" in only:
        bench_nvt_sharded(rows)
    if only is None or "rebalance_live" in only:
        bench_nvt_rebalance_live(rows)
    if only is None or "restart" in only:
        bench_nvt_restart(rows)
    if only is None or "obs" in only:
        bench_nvt_obs(rows)
    if only is None or "ckpt" in only:
        bench_checkpoint(rows)
    if only is None or "kernels" in only:
        bench_kernels(rows)
    if only is None or "roofline" in only:
        bench_roofline(rows)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")


if __name__ == "__main__":
    main()
