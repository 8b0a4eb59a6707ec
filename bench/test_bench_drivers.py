"""Rehearsals of every cell on the CPU at a tiny size, with the program
whole and with it broken underneath: each fault a cell can have must
turn ``correct`` false."""
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import harness as H
from bench import tiny

ROOT = H.ROOT
SEED = 2**31 + 17


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    env.pop("BENCH_RUN", None)
    env.update(extra)
    return env


# --------------------------------------------------------------------- #
# serve                                                                  #
# --------------------------------------------------------------------- #
SERVE = "serve-qwen3-1.7b-over"


def test_serve_cell_is_correct_with_nothing_compiled_in_the_window():
    """Above the knee: what is sent before the close is served and
    checked, and the backlog left at the close is not sent."""
    d = tiny.tiny_driver(SERVE, SEED, 1.0)
    d.setup()
    with H.CompileCounter() as counter:
        d.window(1.0, None)
    out = d.check()
    assert out.correct, [(c.name, c.value) for c in out.checks]
    assert out.failed == 0 and 0 < out.attempted == d.sent.sum() < 400
    assert counter.compiles == 0
    assert out.end_to_end["gen_tok_s"] > 0
    assert "req_p80_ms" not in out.end_to_end
    # every call sent counts: none is sent after the close
    assert out.end_to_end["gen_tok_s"] == pytest.approx(
        sum(c.batch for c in d.calls) * d.new_tokens
        / (d.t_last - d.t0_ns / 1e9), rel=1e-3)


def test_a_tail_cell_serves_every_request_due_in_the_window():
    """A cell judged on ``req_p80_ms`` serves the requests still waiting
    at the close after it, so that its tail is the tail of all of them."""
    cell = tiny.tiny_cell(SERVE)
    cell.traffic["rate_per_s"] = 20.0
    cell.spec = dict(cell.spec, end_to_end=[
        {"name": "req_p80_ms", "workloads": [SERVE]}])
    out = tiny.run_tiny(cell, SEED, seconds=1.0)
    assert out.correct, [(c.name, c.value) for c in out.checks]
    assert out.failed == 0 and out.attempted == 20
    assert out.compiles == 0
    assert out.end_to_end["req_p80_ms"] > 0


def _serve_faulty(monkeypatch, fault):
    from repro.models.model import Model
    from repro.serving.engine import ServeEngine
    if fault == "state_unchanged":
        step = Model.decode_step

        def stale(self, params, tokens, caches, pos):
            logits, _ = step(self, params, tokens, caches, pos)
            return logits, caches
        monkeypatch.setattr(Model, "decode_step", stale)
    elif fault == "half_batch":
        serve = ServeEngine.serve

        def half(self, requests, n_new=8, **kw):
            keep = dict(list(requests.items())[:max(1, len(requests) // 2)])
            if len(requests) == 1 and n_new > 2:
                keep = {}
            return serve(self, keep, n_new, **kw)
        monkeypatch.setattr(ServeEngine, "serve", half)
    elif fault == "token_altered":
        greedy = ServeEngine._greedy_batch

        def altered(self, prompts, n_new):
            gen = greedy(self, prompts, n_new)
            return (gen + 1) % self.model.cfg.vocab
        monkeypatch.setattr(ServeEngine, "_greedy_batch", altered)
    return tiny.run_tiny(SERVE, SEED, seconds=1.0)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_serve_faults_turn_correct_false(monkeypatch, fault):
    out = _serve_faulty(monkeypatch, fault)
    assert not out.correct, [(c.name, c.value) for c in out.checks]


# --------------------------------------------------------------------- #
# hash map                                                               #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("cell", ["hash-50u", "hash-read"])
def test_hash_cells_are_correct_with_nothing_compiled_in_the_window(cell):
    out = tiny.run_tiny(tiny.map_cell(tiny.MAP_MIXES[cell]), SEED,
                        seconds=0.5)
    assert out.correct, [(c.name, c.value) for c in out.checks]
    assert out.failed == 0 and out.attempted > 0
    assert out.compiles == 0
    assert out.end_to_end["map_ops_s"] > 0


def _map_faulty(monkeypatch, fault, cell):
    import jax.numpy as jnp
    from repro.core import batched as B
    if fault == "state_unchanged":
        upd = B.update_parallel

        def unchanged(state, *a, **k):
            _, ok, stats = upd(state, *a, **k)
            return state, ok, stats
        monkeypatch.setattr(B, "update_parallel", unchanged)
    elif fault == "half_batch":
        upd, look = B.update_parallel, B.lookup

        def half_update(state, ops, ks, vs, *a, **k):
            n = ks.shape[0] // 2
            state, ok, stats = upd(state, ops[:n], ks[:n], vs[:n], *a, **k)
            return state, jnp.concatenate([ok, jnp.zeros(
                ks.shape[0] - n, bool)]), stats

        def half_lookup(state, ks, *a, **k):
            n = ks.shape[0] // 2
            f, v = look(state, ks[:n], *a, **k)
            pad = ks.shape[0] - n
            return (jnp.concatenate([f, jnp.zeros(pad, bool)]),
                    jnp.concatenate([v, jnp.zeros(pad, v.dtype)]))
        monkeypatch.setattr(B, "update_parallel", half_update)
        monkeypatch.setattr(B, "lookup", half_lookup)
    elif fault == "answer_altered":
        look = B.lookup

        def altered(state, ks, *a, **k):
            f, v = look(state, ks, *a, **k)
            return f.at[0].set(~f[0]), v
        monkeypatch.setattr(B, "lookup", altered)
    return tiny.run_tiny(tiny.map_cell(tiny.MAP_MIXES[cell]), SEED,
                         seconds=0.3)


@pytest.mark.parametrize("cell,fault", [
    ("hash-50u", "state_unchanged"), ("hash-50u", "half_batch"),
    ("hash-50u", "answer_altered"), ("hash-read", "half_batch"),
    ("hash-read", "answer_altered")])
def test_hash_faults_turn_correct_false(monkeypatch, cell, fault):
    out = _map_faulty(monkeypatch, fault, cell)
    assert not out.correct, [(c.name, c.value) for c in out.checks]


# --------------------------------------------------------------------- #
# sharded map: four virtual CPU devices, in a process of its own        #
# --------------------------------------------------------------------- #
SHARDED = """
import sys
from bench import tiny
fault = sys.argv[1]
if fault == "exchange_left_out":
    from repro.core import sharded
    sharded._a2a = lambda x, S: x
out = tiny.run_tiny(tiny.map_cell("50u", chips=4), {seed}, seconds=0.3)
print("CORRECT" if out.correct else "WRONG",
      [(c.name, c.value) for c in out.checks], out.compiles)
"""


@pytest.mark.parametrize("fault,want", [("none", "CORRECT 0"),
                                        ("exchange_left_out", "WRONG")])
def test_sharded_cell_on_four_virtual_devices(fault, want):
    r = subprocess.run(
        [sys.executable, "-c", SHARDED.format(seed=SEED), fault],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stderr[-3000:]
    line = r.stdout.strip().splitlines()[-1]
    assert line.startswith(want.split()[0]), line
    if want == "CORRECT 0":
        assert line.endswith(" 0"), line      # no compile in the window


# --------------------------------------------------------------------- #
# the command                                                            #
# --------------------------------------------------------------------- #
def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SERVE,
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300, env=_env())
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_traffic_is_the_same_work_for_every_seed():
    """Every block of arrivals holds the mix's lengths and gaps; the seed
    draws their order within each block, and the tokens."""
    from bench import traffic as T
    tr = tiny.tiny_cell(SERVE).traffic
    b = T.block_size(tr["shares"])
    assert b == 20
    a = T.open_loop(tr, 1, 10.0, 512)
    z = T.open_loop(tr, 2**31 + 5, 10.0, 512)
    assert a.arrival_s.size == round(tr["rate_per_s"] * 10.0 / b) * b
    assert np.all(np.diff(a.arrival_s) > 0)
    for x in (a, z):
        gaps = np.diff(x.arrival_s, prepend=0.0).reshape(-1, b)
        lens = x.lengths.reshape(-1, b)
        for g, s in zip(gaps, lens):
            np.testing.assert_allclose(np.sort(g), np.sort(gaps[0]))
            np.testing.assert_array_equal(np.sort(s), np.sort(lens[0]))
        assert sorted(lens[0].tolist()) == sorted(
            [s for s, k in zip(tr["lengths"], [10, 7, 3]) for _ in range(k)])
    # the gaps are the exponential's means over equal slices: their mean
    # is exactly one over the rate
    assert np.mean(np.diff(a.arrival_s, prepend=0.0)) == \
        pytest.approx(1 / tr["rate_per_s"], rel=1e-9)
    assert not np.array_equal(a.lengths, z.lengths)
    assert not np.array_equal(a.arrival_s, z.arrival_s)
    assert not all(np.array_equal(x, y)
                   for x, y in zip(a.prompts, z.prompts))
    c = T.open_loop(tr, 1, 10.0, 512)
    np.testing.assert_array_equal(a.arrival_s, c.arrival_s)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, c.prompts))
