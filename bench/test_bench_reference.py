"""The plain references against the program at a tiny size, on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import tiny
from bench.drivers import serve as S
from bench.reference import hash_map as RH
from bench.reference import qwen3 as RQ


@pytest.fixture(scope="module")
def cfg():
    return tiny.tiny_cell("serve-qwen3-1.7b-over").config


def test_weights_are_the_same_whether_made_whole_or_layer_by_layer(cfg):
    key = RQ.seed_key(2**31 + 3)           # seeds past 32 bits are fine
    p = jax.jit(lambda k: S.make_params(cfg, k, cfg["vocab_size"]))(key)
    for i in range(cfg["num_hidden_layers"]):
        w = RQ.layer_weights(cfg, key, i)
        np.testing.assert_array_equal(p["blocks"]["mlp"]["w_down"][i],
                                      w["down"])
        np.testing.assert_array_equal(p["blocks"]["attn"]["k_norm"][i],
                                      w["k_norm"])
    np.testing.assert_array_equal(p["embed"], RQ.embed_weights(cfg, key))


def test_engine_prefill_then_decode_agrees_with_the_reference(cfg):
    """The program's prefill, then its decode steps through the cache,
    against the reference's whole-sequence forward (program in float32
    over the same bf16-valued weights, so the tolerance is float32's)."""
    from repro.models.model import build_model
    seed = 5
    arch = S.program_arch(dict(cfg, torch_dtype="float32"))
    model = build_model(arch)
    key = RQ.seed_key(seed)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          S.make_params(cfg, key, cfg["vocab_size"]))
    rng = np.random.default_rng(0)
    B, P, N = 2, 12, 5
    prompts = rng.integers(0, cfg["vocab_size"], (B, P)).astype(np.int32)
    follow = rng.integers(0, cfg["vocab_size"], (B, N)).astype(np.int32)
    logits, caches = model.prefill(params, {"tokens": jnp.asarray(prompts)},
                                   P + N)
    got = [np.asarray(logits[:, -1])]
    for i in range(N - 1):
        lg, caches = model.decode_step(params, jnp.asarray(follow[:, i]),
                                       caches, jnp.int32(P + i))
        got.append(np.asarray(lg[:, 0]))
    got = np.stack(got, axis=1)                       # [B, N, V]
    want = RQ.Reference(cfg, seed).served_logits(list(prompts), list(follow))
    for b in range(B):
        np.testing.assert_allclose(got[b], want[b], rtol=2e-4, atol=2e-3)


def test_widest_gap():
    lg = [np.asarray([[0.0, 3.0, 1.0], [5.0, 4.5, 0.0]])]
    assert RQ.widest_gap(lg, [np.asarray([1, 0])]) == 0.0
    assert RQ.widest_gap(lg, [np.asarray([2, 1])]) == 2.0


def test_fp8_rounding_is_coarser_than_bf16():
    x = jnp.linspace(-3.0, 3.0, 1001)
    err8 = jnp.abs(RQ.fp8_round(x) - x).max()
    err16 = jnp.abs(x.astype(jnp.bfloat16).astype(jnp.float32) - x).max()
    assert float(err8) > 4 * float(err16) > 0


def test_map_reference_agrees_with_the_program_sequential_oracle():
    """Rounds with repeated keys, against ``batched.apply``, the
    program's one-op-at-a-time mixed engine."""
    from repro.core import batched as B
    R, nb = 200, 16
    ref = RH.DenseMap(R)
    state = B.make_state(512, nb)
    rng = np.random.default_rng(1)
    flushes = 0
    for _ in range(6):
        ops = rng.integers(0, 2, 300).astype(np.int32)
        ks = rng.integers(1, R + 1, 300).astype(np.int32)
        vs = rng.integers(0, 1000, 300).astype(np.int32)
        before = int(state.flushes)
        state, ok = B.apply(state, jnp.asarray(ops), jnp.asarray(ks),
                            jnp.asarray(vs), nb)
        flushes += int(state.flushes) - before
        np.testing.assert_array_equal(ref.update(ops, ks, vs),
                                      np.asarray(ok))
        look = rng.integers(1, R + 1, 100).astype(np.int32)
        f, v = B.lookup(state, jnp.asarray(look), nb)
        fr, vr = ref.lookup(look)
        np.testing.assert_array_equal(fr, np.asarray(f))
        np.testing.assert_array_equal(vr, np.asarray(v))
    assert ref.flushes == flushes
    c = int(state.cursor)
    assert ref.content_mismatches(np.asarray(state.key[1:c]),
                                  np.asarray(state.live[1:c]),
                                  np.asarray(state.val[1:c])) == 0
    # a pool that lost a node, or holds a wrong value, is caught
    keys = np.asarray(state.key[1:c])
    live = np.asarray(state.live[1:c])
    vals = np.asarray(state.val[1:c]).copy()
    assert ref.content_mismatches(keys[1:], live[1:], vals[1:]) > 0
    vals[np.flatnonzero(live)[0]] += 1
    assert ref.content_mismatches(keys, live, vals) == 1
