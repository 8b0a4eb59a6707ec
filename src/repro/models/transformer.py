"""Layer stacks for every assigned family, built around ``lax.scan`` over
stacked per-layer parameters (small HLO, remat-friendly).

  * dense / vlm:  [attn → mlp] × L, optional local:global window pattern
    (gemma3) expressed as a *traced* window size inside one scanned block;
  * moe:          [attn → moe_ffn (+shared/+dense-residual)] × L;
  * ssm:          [mamba2 SSD] × L;
  * hybrid:       mamba2 backbone with a tied shared-attention block every
    k-th layer (zamba2) — the shared block's per-invocation KV caches ride
    in the scan carry;
  * encdec:       bidirectional encoder stack + causal decoder stack with
    cross-attention (whisper).

Remat: ``cfg.remat == "block"`` checkpoints each scanned block — the
standard activation policy for long stacks (§Perf iterates on it).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .layers import (attn_params, cross_attention, cross_kv, mlp,
                     mlp_params, rms_norm, self_attention)
from .mamba2 import SSMCache, init_ssm_cache, mamba_block, mamba_params
from .moe import moe_ffn, moe_params


# --------------------------------------------------------------------- #
# single blocks                                                           #
# --------------------------------------------------------------------- #
def _sp(x, cfg, mode):
    """Sequence-parallel residual stream (Megatron-SP as a GSPMD
    constraint): shard the sequence dim of the per-block activations over
    the model axis, turning the two TP all-reduces per layer into
    reduce-scatter + all-gather pairs at half the volume (§Perf)."""
    if not getattr(cfg, "sp", False) or mode == "decode":
        return x
    from ..sharding.constraints import batch_axes, constrain
    return constrain(x, batch_axes(), "model", None)


def dense_block(p, x, cfg, *, positions, mode, window=None,
                cache=None, cache_pos=None):
    x = _sp(x, cfg, mode)
    h, new_cache = self_attention(p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps),
                                  cfg, positions=positions, mode=mode,
                                  window=window, cache=cache,
                                  cache_pos=cache_pos)
    x = x + _sp(h, cfg, mode)
    x = x + _sp(mlp(p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg.act),
                cfg, mode)
    return x, new_cache


def moe_block(p, x, cfg, *, positions, mode, cache=None, cache_pos=None):
    x = _sp(x, cfg, mode)
    h, new_cache = self_attention(p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps),
                                  cfg, positions=positions, mode=mode,
                                  cache=cache, cache_pos=cache_pos)
    x = x + _sp(h, cfg, mode)
    y, aux = moe_ffn(p["moe"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
    return x + _sp(y, cfg, mode), new_cache, aux


def encdec_block(p, x, cfg, *, positions, mode, cache=None, cache_pos=None,
                 enc_out=None, xa_cache=None):
    h, new_cache = self_attention(p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps),
                                  cfg, positions=positions, mode=mode,
                                  cache=cache, cache_pos=cache_pos)
    x = x + h
    h, xa_kv = cross_attention(p["xattn"], rms_norm(x, p["ln_x"], cfg.norm_eps),
                               cfg, kv=enc_out, kv_cache=xa_cache)
    x = x + h
    x = x + mlp(p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg.act)
    return x, new_cache, xa_kv


# --------------------------------------------------------------------- #
# parameter builders                                                     #
# --------------------------------------------------------------------- #
def dense_block_params(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {"ln1": jnp.zeros((cfg.d_model,), dtype),
            "ln2": jnp.zeros((cfg.d_model,), dtype),
            "attn": attn_params(k1, cfg, dtype),
            "mlp": mlp_params(k2, cfg.d_model, cfg.d_ff, dtype, cfg.act,
                              fused=getattr(cfg, "fused_gate_up", False))}


def moe_block_params(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {"ln1": jnp.zeros((cfg.d_model,), dtype),
            "ln2": jnp.zeros((cfg.d_model,), dtype),
            "attn": attn_params(k1, cfg, dtype),
            "moe": moe_params(k2, cfg, dtype)}


def encdec_block_params(key, cfg, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": jnp.zeros((cfg.d_model,), dtype),
            "ln2": jnp.zeros((cfg.d_model,), dtype),
            "ln_x": jnp.zeros((cfg.d_model,), dtype),
            "attn": attn_params(k1, cfg, dtype),
            "xattn": attn_params(k2, cfg, dtype),
            "mlp": mlp_params(k3, cfg.d_model, cfg.d_ff, dtype, cfg.act,
                              fused=getattr(cfg, "fused_gate_up", False))}


def stacked_params(key, n: int, builder, cfg, dtype):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: builder(k, cfg, dtype))(keys)


# --------------------------------------------------------------------- #
# scanned stacks                                                          #
# --------------------------------------------------------------------- #
def unrolled_scan(f, init, xs, *, length: int):
    """lax.scan-compatible Python unrolling.

    Needed for honest compiled-cost accounting: XLA's cost analysis counts
    a while-loop body ONCE regardless of trip count, so the dry-run lowers
    stacks unrolled (``cfg.scan_layers=False``) when producing the roofline
    FLOPs/bytes; real training keeps ``lax.scan`` for compile time.
    """
    carry = init
    ys = []
    for i in range(length):
        x = jax.tree.map(lambda a: a[i], xs)
        carry, y = f(carry, x)
        ys.append(y)
    if all(l is None for l in jax.tree.leaves(ys[0], is_leaf=lambda v: v is None)):
        return carry, None
    stacked = jax.tree.map(lambda *e: jnp.stack(e), *ys)
    return carry, stacked


def _scan(cfg, f, init, xs, length: int):
    if cfg.scan_layers:
        return jax.lax.scan(f, init, xs)
    return unrolled_scan(f, init, xs, length=length)


def _maybe_remat(fn, cfg):
    if cfg.remat == "block":
        # save only the scanned-block boundaries; recompute inside the block
        # during backward — the standard long-stack activation policy
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def _layer_window(cfg, idx):
    """Traced sliding-window size for layer ``idx`` (0 = full attention)."""
    if not cfg.local_per_global:
        return None
    period = cfg.local_per_global + 1
    is_global = (idx % period) == (period - 1)
    return jnp.where(is_global, 0, cfg.local_window)


def write_kv(caches, new, *, mode, cache_pos, layer=None):
    """Fold the attention caches a stack's layers returned into the
    stacked {'k','v'} caches ([L,B,S,K,dh]) they read.

    In decode each layer returns only the new token's K/V ([B,1,K,dh],
    [L,B,1,K,dh] as a scan's output) and that one position is written at
    ``cache_pos``: in place when the caches are donated, and no layer's
    cache is copied.  Otherwise ``new`` is a filled cache that replaces
    the stack's whole, or layer ``layer``'s.
    """
    if layer is None and mode != "decode":
        return new
    if layer is not None:
        new = jax.tree.map(lambda a: a[None], new)
    start = (0 if layer is None else layer, 0,
             cache_pos if mode == "decode" else 0, 0, 0)
    return jax.tree.map(
        lambda buf, upd: jax.lax.dynamic_update_slice(buf, upd, start),
        caches, new)


def dense_stack(params, x, cfg, *, positions, mode, caches=None,
                cache_pos=None):
    """params: stacked [L, ...]; caches: stacked {'k','v'} or None."""
    L = cfg.n_layers

    def body(carry, inp):
        x = carry
        lp, idx, cache = inp
        window = _layer_window(cfg, idx)
        y, new_cache = dense_block(lp, x, cfg, positions=positions,
                                   mode=mode, window=window, cache=cache,
                                   cache_pos=cache_pos)
        return y, new_cache

    body = _maybe_remat(body, cfg)
    xs = (params, jnp.arange(L), caches)
    x, new_caches = _scan(cfg, body, x, xs, L)
    return x, write_kv(caches, new_caches, mode=mode, cache_pos=cache_pos)


def moe_stack(params, x, cfg, *, positions, mode, caches=None,
              cache_pos=None):
    L = cfg.n_layers

    def body(carry, inp):
        x, aux = carry
        lp, cache = inp
        y, new_cache, a = moe_block(lp, x, cfg, positions=positions,
                                    mode=mode, cache=cache,
                                    cache_pos=cache_pos)
        return (y, aux + a), new_cache

    body = _maybe_remat(body, cfg)
    (x, aux), new_caches = _scan(
        cfg, body, (x, jnp.float32(0.0)), (params, caches), L)
    new_caches = write_kv(caches, new_caches, mode=mode, cache_pos=cache_pos)
    return x, new_caches, aux / L


def ssm_stack(params, x, cfg, *, caches=None):
    def body(carry, inp):
        x = carry
        lp, cache = inp
        y, new_cache = mamba_block(lp, rms_norm(x, lp["ln"], cfg.norm_eps),
                                   cfg, cache=cache)
        x = x + y
        return x, new_cache

    body = _maybe_remat(body, cfg)
    x, new_caches = _scan(cfg, body, x, (params, caches), cfg.n_layers)
    return x, new_caches


def hybrid_stack(params, x, cfg, *, positions, mode, caches=None,
                 cache_pos=None):
    """zamba2: mamba backbone + tied shared attn block every k-th layer.

    ``params = {"mamba": stacked[L], "shared": dense_block_params}``;
    ``caches = {"ssm": stacked[L] SSMCache, "attn": {'k','v'} [n_inv, ...]}``.
    The shared block's caches are carried (written at the invocation index
    by `write_kv`, one position in decode) because its parameters are tied
    across invocations but its KV history is not.
    """
    L, k = cfg.n_layers, cfg.shared_attn_every
    shared = params["shared"]

    def body(carry, inp):
        x, attn_caches = carry
        lp, idx, ssm_cache = inp
        h, new_ssm = mamba_block(lp, rms_norm(x, lp["ln"], cfg.norm_eps),
                                 cfg, cache=ssm_cache)
        x = x + h

        def with_shared(x, attn_caches):
            inv = idx // k
            if attn_caches is None:
                y, _ = dense_block(shared, x, cfg, positions=positions,
                                   mode=mode, cache=None,
                                   cache_pos=cache_pos)
                return y, attn_caches
            cache = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, inv, 0,
                                                       keepdims=False),
                attn_caches)
            y, new_cache = dense_block(shared, x, cfg, positions=positions,
                                       mode=mode, cache=cache,
                                       cache_pos=cache_pos)
            attn_caches = write_kv(attn_caches, new_cache, mode=mode,
                                   cache_pos=cache_pos, layer=inv)
            return y, attn_caches

        is_shared = (idx % k) == (k - 1)
        if attn_caches is None:
            x = jax.lax.cond(is_shared,
                             lambda x: with_shared(x, None)[0],
                             lambda x: x, x)
            return (x, attn_caches), new_ssm
        x, attn_caches = jax.lax.cond(
            is_shared, with_shared, lambda x, c: (x, c), x, attn_caches)
        return (x, attn_caches), new_ssm

    body = _maybe_remat(body, cfg)
    ssm_caches = caches["ssm"] if caches is not None else None
    attn_caches = caches["attn"] if caches is not None else None
    (x, new_attn), new_ssm = _scan(
        cfg, body, (x, attn_caches),
        (params["mamba"], jnp.arange(L), ssm_caches), L)
    new_caches = (None if caches is None
                  else {"ssm": new_ssm, "attn": new_attn})
    return x, new_caches


def encoder_stack(params, x, cfg):
    def body(x, lp):
        y, _ = dense_block(lp, x, cfg, positions=None, mode="bidir")
        return y, None

    body = _maybe_remat(body, cfg)
    x, _ = _scan(cfg, body, x, params, cfg.enc_layers)
    return x


def decoder_stack(params, x, cfg, *, positions, mode, enc_out=None,
                  xa_caches=None, caches=None, cache_pos=None):
    """Whisper decoder: self-attn + cross-attn blocks.

    During train/prefill ``enc_out`` is given and per-layer cross KV is
    computed in-scan; during decode the precomputed ``xa_caches`` [L,...]
    are read, and returned as given.
    """
    decode = mode == "decode"

    def body(x, inp):
        lp, cache, xa_cache = inp
        y, new_cache, xa_kv = encdec_block(
            lp, x, cfg, positions=positions, mode=mode, cache=cache,
            cache_pos=cache_pos, enc_out=enc_out, xa_cache=xa_cache)
        return y, (new_cache, None if decode else xa_kv)

    body = _maybe_remat(body, cfg)
    x, (new_caches, xa_kvs) = _scan(
        cfg, body, x, (params, caches, xa_caches), cfg.n_layers)
    new_caches = write_kv(caches, new_caches, mode=mode, cache_pos=cache_pos)
    return x, new_caches, xa_caches if decode else xa_kvs


def precompute_cross_caches(params, enc_out, cfg):
    """[L]-stacked cross-attention KV from encoder output."""
    return jax.vmap(lambda lp: cross_kv(lp["xattn"], enc_out, cfg))(params)


def init_attn_caches(cfg, n_layers, batch, max_len, dtype):
    K, dh = cfg.n_kv_heads, cfg.head_dim
    shape = (n_layers, batch, max_len, K, dh)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_ssm_caches(cfg, n_layers, batch, dtype):
    one = init_ssm_cache(cfg, batch, dtype)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n_layers,) + a.shape), one)
