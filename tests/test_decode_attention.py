"""Decode attention: one new token against the K/V cache, grouped-query.

The decode branch of ``self_attention`` contracts the query heads in
groups against the cache as stored; these tests hold it to the repeated-
cache ``attention_scores`` it replaces, and guard against a copy of the
cache repeated up to every query head coming back into the step.
"""
import functools

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch, tiny
from repro.models.layers import (attention_scores, attn_params, qkv,
                                 self_attention)
from repro.models.model import build_model

TOL = {"float32": 1e-5, "bfloat16": 2e-2}


def _repeated_cache_decode(p, x, cache, pos, window, *, cfg):
    """The decode branch as it was: ``attention_scores`` over the cache
    repeated up to H heads, with the same mask."""
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
    positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    return self_attention(p, x, cfg, positions=positions, mode="decode",
                          window=window, cache=cache, cache_pos=pos)


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
    # every slot filled, those past ``pos`` too: the mask must drop them
    shape = (Bt, S_max, K, cfg.head_dim)
    cache = {"k": jax.random.normal(ks[2], shape, jnp.float32).astype(dt),
             "v": jax.random.normal(ks[3], shape, jnp.float32).astype(dt)}
    # with a window it is traced, and cuts the attended span to 5
    window = jnp.int32(5) if windowed else None
    run = lambda f: jax.jit(functools.partial(f, cfg=cfg))(
        p, x, cache, jnp.int32(pos), window)
    y, new = run(_grouped_decode)
    y_ref, new_ref = run(_repeated_cache_decode)
    assert y.shape == (Bt, 1, cfg.d_model) and y.dtype == dt
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=TOL[dtype], atol=TOL[dtype])
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(new[name], np.float32),
                                      np.asarray(new_ref[name], np.float32))


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its
    equations' parameters (scan, remat, pjit, cond and while bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _equations(sub)


def test_qwen3_decode_step_never_repeats_the_cache():
    """Traced at full widths, batch 8 and 2,112 positions, from shapes:
    no equation yields a tensor with as many elements as the cache
    repeated to every query head ([B, S, K, G, dh] = [B, S, H, dh])."""
    cfg = get_arch("qwen3-1.7b")
    model = build_model(cfg)
    B, S = 8, 2112
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    assert H > K
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: model.init_caches(B, S))
    jaxpr = jax.make_jaxpr(model.decode_step)(
        params, jax.ShapeDtypeStruct((B,), jnp.int32), caches,
        jax.ShapeDtypeStruct((), jnp.int32))
    sizes = [int(np.prod(o.aval.shape)) for e in _equations(jaxpr.jaxpr)
             for o in e.outvars if hasattr(o.aval, "shape")]
    # the walk reaches the scanned layer body: one layer's cache update
    assert B * S * K * dh in sizes
    assert B * S * H * dh not in sizes
