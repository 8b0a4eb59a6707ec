"""Decode attention: one new token against the K/V cache, grouped-query.

The decode branch of ``self_attention`` reads the cache as stored,
contracts the query heads in groups against it and against the new
token's own K/V, and returns that K/V for the stack to write at its
position (``transformer.write_kv``).  These tests hold it to the
repeated-cache ``attention_scores`` it replaces, and guard the decode
step at qwen3-1.7b's widths against a copy of the cache repeated up to
every query head, against a layer's cache written inside the layer
loop, and against a decode that does not update its donated cache in
place.
"""
import functools
import re

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch, tiny
from repro.models.layers import (attention_scores, attn_params, qkv,
                                 self_attention)
from repro.models.model import build_model
from repro.models.transformer import write_kv
from repro.serving.engine import ServeEngine

TOL = {"float32": 1e-5, "bfloat16": 2e-2}


def _repeated_cache_decode(p, x, cache, pos, window, *, cfg):
    """The decode branch as it was: the new token written into the cache,
    then ``attention_scores`` over the cache repeated up to H heads."""
    positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    q, k, v = qkv(p, x, cfg, positions)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, pos, 1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, pos, 1)
    kpos = jnp.arange(ck.shape[1])
    m = kpos <= pos
    if window is not None:
        m = m & jnp.where(window > 0, kpos > pos - window, True)
    out = attention_scores(q, ck, cv, m[None, None, None, :])
    B, Sq, H, dh = out.shape
    y = jnp.einsum("bsn,nd->bsd", out.reshape(B, Sq, H * dh),
                   p["wo"].astype(x.dtype))
    return y, {"k": ck, "v": cv}


def _grouped_decode(p, x, cache, pos, window, *, cfg):
    """The decode branch, then the stack's one-position write of the new
    K/V into the cache (as a stack of one layer)."""
    positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    y, new = self_attention(p, x, cfg, positions=positions, mode="decode",
                            window=window, cache=cache, cache_pos=pos)
    stacked = jax.tree.map(lambda a: a[None], (cache, new))
    written = write_kv(*stacked, mode="decode", cache_pos=pos)
    return y, new, jax.tree.map(lambda a: a[0], written)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("windowed", [False, True],
                         ids=["full", "traced_window"])
@pytest.mark.parametrize("G", [1, 2, 4])
def test_grouped_decode_matches_repeated_cache(G, windowed, dtype):
    K, Bt, S_max, pos = 2, 3, 24, 13          # the new token mid-buffer
    cfg = tiny(get_arch("qwen3-1.7b"), n_heads=K * G, n_kv_heads=K,
               param_dtype=dtype, compute_dtype=dtype)
    dt = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(G), 4)
    p = attn_params(ks[0], cfg, dt)
    x = jax.random.normal(ks[1], (Bt, 1, cfg.d_model), jnp.float32).astype(dt)
    # every slot filled, ``pos`` and those past it too: the mask must
    # drop them, and the new token's K/V must stand in for slot ``pos``
    shape = (Bt, S_max, K, cfg.head_dim)
    cache = {"k": jax.random.normal(ks[2], shape, jnp.float32).astype(dt),
             "v": jax.random.normal(ks[3], shape, jnp.float32).astype(dt)}
    # with a window it is traced, and cuts the attended span to 5
    window = jnp.int32(5) if windowed else None
    run = lambda f: jax.jit(functools.partial(f, cfg=cfg))(
        p, x, cache, jnp.int32(pos), window)
    y, new, written = run(_grouped_decode)
    y_ref, cache_ref = run(_repeated_cache_decode)
    assert y.shape == (Bt, 1, cfg.d_model) and y.dtype == dt
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=TOL[dtype], atol=TOL[dtype])
    for name in ("k", "v"):
        # the layer returns the new token's K/V alone ...
        assert new[name].shape == (Bt, 1, K, cfg.head_dim)
        # ... and the cache after its one-position write is the reference's
        np.testing.assert_array_equal(np.asarray(written[name], np.float32),
                                      np.asarray(cache_ref[name], np.float32))


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its
    equations' parameters (scan, remat, pjit, cond and while bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        yield from _nested_equations(eqn)


def _nested_equations(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(sub, jax.extend.core.ClosedJaxpr):
                sub = sub.jaxpr
            if isinstance(sub, jax.extend.core.Jaxpr):
                yield from _equations(sub)


def _sizes(eqns):
    return [int(np.prod(o.aval.shape)) for e in eqns
            for o in e.outvars if hasattr(o.aval, "shape")]


@pytest.fixture(scope="module")
def qwen3_decode():
    """qwen3-1.7b's decode step at full widths, batch 8 and 2,112
    positions, from shapes: (cfg, model, argument shapes, jaxpr)."""
    cfg = get_arch("qwen3-1.7b")
    model = build_model(cfg)
    B, S = 8, 2112
    args = (jax.eval_shape(model.init, jax.random.PRNGKey(0)),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.eval_shape(lambda: model.init_caches(B, S)),
            jax.ShapeDtypeStruct((), jnp.int32))
    return cfg, model, args, jax.make_jaxpr(model.decode_step)(*args)


def test_qwen3_decode_step_never_repeats_the_cache(qwen3_decode):
    """No equation yields a tensor with as many elements as the cache
    repeated to every query head ([B, S, K, G, dh] = [B, S, H, dh])."""
    cfg, _, (_, tokens, caches, _), jaxpr = qwen3_decode
    (B,), (_, _, S, K, dh) = tokens.shape, caches["k"].shape
    H = cfg.n_heads
    assert H > K
    sizes = _sizes(_equations(jaxpr.jaxpr))
    # the walk reaches the scanned layer body: the scores of every query
    # head over the cache
    assert B * H * S in sizes
    assert B * S * H * dh not in sizes


def test_qwen3_decode_layer_loop_writes_no_layer_cache(qwen3_decode):
    """The scanned layer body reads each layer's cache and yields no tensor
    as large as it ([B, S, K, dh]): the new K/V leave the loop as
    [L, B, 1, K, dh] and are written after it, one position."""
    _, _, (_, tokens, caches, _), jaxpr = qwen3_decode
    L, B, S, K, dh = caches["k"].shape
    scans = [e for e in _equations(jaxpr.jaxpr)
             if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == L
    body = _sizes(_nested_equations(scans[0]))
    assert body and max(body) < B * S * K * dh
    assert [tuple(o.aval.shape) for o in scans[0].outvars
            if o.aval.ndim == 5] == [(L, B, 1, K, dh)] * 2


def test_serve_engine_decode_donates_the_cache(qwen3_decode, tmp_path):
    """The engine's jitted decode aliases both cache leaves to its
    outputs, so the one-position write lands in place."""
    _, model, (params, tokens, caches, pos), _ = qwen3_decode
    eng = ServeEngine(model, params, max_len=caches["k"].shape[2],
                      log_dir=tmp_path, batch_size=tokens.shape[0])
    text = eng._decode.lower(params, tokens, caches, pos).as_text()
    main = next(l for l in text.splitlines() if "func.func public @main" in l)
    aliased = re.findall(r"tensor<([0-9x]+)x(\w+)> \{tf\.aliasing_output",
                         main)
    shape = "x".join(map(str, caches["k"].shape))
    assert aliased == [(shape, "bf16")] * 2


def test_donated_decode_steps_past_a_window_match_teacher_forcing(tmp_path):
    """Greedy tokens served through the engine's donated decode, many
    steps past a sliding window's edge, equal teacher forcing over the
    same sequence."""
    cfg = tiny(get_arch("gemma3-27b"), local_per_global=1, local_window=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    S, n_new, max_len = 3, 8, 16              # decodes at positions 3..10
    rng = np.random.default_rng(0)
    reqs = {i: rng.integers(0, cfg.vocab, size=S).astype(np.int32)
            for i in range(2)}
    out = ServeEngine(model, params, max_len=max_len, log_dir=tmp_path,
                      batch_size=2).serve(reqs, n_new=n_new)
    prefill = jax.jit(lambda p, b: model.prefill(p, b, max_len))
    seqs = np.stack([np.concatenate([reqs[r], out[r]]) for r in sorted(reqs)])
    for i in range(n_new):
        logits, _ = prefill(params, {"tokens": jnp.asarray(seqs[:, :S + i])})
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(logits[:, -1], axis=-1)), seqs[:, S + i])
