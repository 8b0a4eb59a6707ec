"""Neural net layers: norms, rotary embeddings, attention (GQA / qk-norm /
bias / sliding-window / cross), MLPs — pure JAX, param-dict style.

All ``apply`` functions take a params dict and are shape-polymorphic over
batch/sequence.  Attention supports three modes:

  * ``causal``  — train/prefill self-attention (optionally sliding-window);
  * ``bidir``   — encoder self-attention;
  * ``decode``  — one query token against a persistent KV cache.

The XLA einsum path here is the dry-run/compile reference; the Pallas
flash-attention kernel (kernels/flash_attention) is numerically validated
against `attention_scores` semantics and can be swapped in via ops.py.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def head_rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    """qk-norm: RMS over the head dim of [..., heads, head_dim]."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


# --------------------------------------------------------------------- #
# rotary position embeddings                                             #
# --------------------------------------------------------------------- #
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Apply RoPE. x: [B, S, H, dh]; positions: [B, S] (int32)."""
    if theta <= 0.0:
        return x
    dh = x.shape[-1]
    half = dh // 2
    freq = jnp.exp(-jnp.log(theta) *
                   jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # [B,S,half]
    sin = jnp.sin(ang)[:, :, None, :]
    cos = jnp.cos(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- #
# attention                                                              #
# --------------------------------------------------------------------- #
def _proj(x, w, b=None):
    y = jnp.einsum("bsd,dn->bsn", x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def qkv(p: dict, x: jax.Array, cfg, positions: Optional[jax.Array],
        *, use_rope: bool = True):
    """Project to q/k/v with GQA layout [B,S,H,dh] / [B,S,K,dh].

    With ``cfg.fused_qkv`` the three projections are ONE matmul — in
    backward this turns three [B,S,D] model-axis all-reduces (dx from each
    projection's transpose) into one (§Perf fusion iteration)."""
    B, S, _ = x.shape
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if "wqkv" in p:
        u = _proj(x, p["wqkv"], p.get("bqkv"))
        q, k, v = jnp.split(u, [H * dh, (H + K) * dh], axis=-1)
        q = q.reshape(B, S, H, dh)
        k = k.reshape(B, S, K, dh)
        v = v.reshape(B, S, K, dh)
    else:
        q = _proj(x, p["wq"], p.get("bq")).reshape(B, S, H, dh)
        k = _proj(x, p["wk"], p.get("bk")).reshape(B, S, K, dh)
        v = _proj(x, p["wv"], p.get("bv")).reshape(B, S, K, dh)
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    if use_rope and positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_scores(q: jax.Array, k: jax.Array, v: jax.Array,
                     mask: Optional[jax.Array]) -> jax.Array:
    """GQA attention.  q: [B,Sq,H,dh], k/v: [B,Sk,K,dh], mask broadcastable
    to [B,1,Sq,Sk] (True = attend).  Returns [B,Sq,H,dh].

    KV heads are repeated up to H so there is ONE head axis, explicitly
    constrained over the "model" mesh axis — GSPMD then keeps the [Sq,Sk]
    score tensor sharded H-ways instead of inventing a mixed K/G layout
    (the 8.6 GB/buffer failure mode recorded in EXPERIMENTS.md §Perf #0).
    Per device the repeat materializes only the local heads' copies.
    """
    from ..sharding.constraints import batch_axes, constrain
    B, Sq, H, dh = q.shape
    K = k.shape[2]
    ba = batch_axes()
    if H != K:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    q = constrain(q, ba, None, "model", None)
    k = constrain(k, ba, None, "model", None)
    v = constrain(v, ba, None, "model", None)
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k) / jnp.sqrt(dh).astype(q.dtype)
    scores = scores.astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    scores = constrain(scores, ba, "model", None, None)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", w, v)
    return out


def attention_decode(q: jax.Array, ck: jax.Array, cv: jax.Array,
                     mask: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """One query token against the K/V cache and its own K/V, grouped-query.

    q: [B,1,H,dh]; ck/cv: [B,S,K,dh] as stored, read and never written;
    mask broadcastable to [B,K,G,1,S] (True = attend), G = H // K; k/v:
    [B,1,K,dh], the new token's own, always attended.  Returns [B,1,H,dh].

    The same products, mask and float32 softmax as `attention_scores`
    over the cache with the new token written at its position, but the
    H query heads are split into K groups of G and contracted against
    the cache as it is stored: the cache is read once, never repeated up
    to H heads nor copied to hold the new token.  The softmax runs over
    the cache's scores and the new token's together; both value sums
    accumulate in float32 and are rounded once.  On a mesh the K axis is
    constrained over "model".
    """
    from ..sharding.constraints import batch_axes, constrain
    B, Sq, H, dh = q.shape
    K = ck.shape[2]
    ba = batch_axes()
    qg = constrain(q.reshape(B, Sq, K, H // K, dh),
                   ba, None, "model", None, None)
    ck = constrain(ck, ba, None, "model", None)
    cv = constrain(cv, ba, None, "model", None)
    scale = jnp.sqrt(dh).astype(q.dtype)
    s_cache = jnp.einsum("bqkgd,bskd->bkgqs", qg, ck) / scale
    s_cache = jnp.where(mask, s_cache.astype(jnp.float32), NEG_INF)
    s_new = (jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / scale).astype(
        jnp.float32)
    scores = jnp.concatenate([s_cache, s_new], axis=-1)
    scores = constrain(scores, ba, "model", None, None, None)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    S = ck.shape[1]
    out = (jnp.einsum("bkgqs,bskd->bqkgd", w[..., :S], cv,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bkgqs,bskd->bqkgd", w[..., S:], v,
                        preferred_element_type=jnp.float32))
    return out.astype(q.dtype).reshape(B, Sq, H, dh)


def attention_blocked(q, k, v, *, causal: bool, window, chunk: int = 1024):
    """Online-softmax attention, scanned over KV chunks (XLA flash).

    Peak score materialization drops from O(Sq·Sk) to O(Sq·chunk) — the
    §Perf memory-term optimization for the 32k prefill cells; numerics
    match the naive path (same f32 softmax).  q/k/v: [B,S,H,dh] with KV
    already repeated to H (caller).  window may be traced.
    """
    from ..sharding.constraints import batch_axes, constrain
    B, Sq, H, dh = q.shape
    Sk = k.shape[1]
    chunk = min(chunk, Sk)
    pad = (-Sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = (Sk + pad) // chunk
    ba = batch_axes()
    scale = 1.0 / (dh ** 0.5)
    qpos = jnp.arange(Sq)[:, None]
    kc = jnp.moveaxis(k.reshape(B, n_chunks, chunk, H, dh), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, n_chunks, chunk, H, dh), 1, 0)

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, j = inp
        kb = constrain(kb, ba, None, "model", None)
        vb = constrain(vb, ba, None, "model", None)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kb).astype(jnp.float32) * scale
        kpos = j * chunk + jnp.arange(chunk)[None, :]
        mask = kpos < Sk                       # padding
        if causal:
            mask = mask & (kpos <= qpos)
        if window is not None:
            w = jnp.asarray(window)
            mask = mask & jnp.where(w > 0, kpos > qpos - w, True)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(q.dtype), vb).astype(jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (kc, vc, jnp.arange(n_chunks)))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).astype(q.dtype)
    return out.transpose(0, 2, 1, 3)           # [B,Sq,H,dh]


def causal_mask(Sq: int, Sk: int, q_offset, window: int = 0):
    """[1,1,Sq,Sk] boolean mask; window>0 = sliding-window causal."""
    qpos = jnp.arange(Sq)[:, None] + q_offset
    kpos = jnp.arange(Sk)[None, :]
    m = kpos <= qpos
    if window:
        m = m & (kpos > qpos - window)
    return m[None, None]


def self_attention(p: dict, x: jax.Array, cfg, *, positions,
                   mode: str = "causal", window=0,
                   cache: Optional[dict] = None, cache_pos=None):
    """Self-attention for all modes; returns (out, new_cache).

    ``window`` may be a traced scalar (0 = full attention) so that the
    gemma3 local/global pattern compiles as ONE scanned block.

    ``cache`` (a {'k','v'} buffer of length S_max) is only read in decode
    mode: positions below ``cache_pos`` are attended together with the
    new token, whose own {'k','v'} ([B,1,K,dh]) is returned for the stack
    to write at ``cache_pos`` (`transformer.write_kv`).  In causal
    mode a provided cache buffer is *filled* from position 0 (prefill)
    and the attention itself runs over the current tokens only.
    """
    B, S, _ = x.shape
    q, k, v = qkv(p, x, cfg, positions)
    if mode == "decode":
        # one new token (S == 1) against the persistent cache, which is
        # read as given: the caller writes the returned K/V at cache_pos
        assert cache is not None
        kpos = jnp.arange(cache["k"].shape[1])
        m = kpos < cache_pos
        if window is not None:
            w_active = jnp.asarray(window)
            m = m & jnp.where(w_active > 0, kpos > cache_pos - w_active, True)
        out = attention_decode(q, cache["k"], cache["v"],
                               m[None, None, None, None, :], k, v)
        new_cache = {"k": k, "v": v}
    elif mode == "bidir":
        out = attention_scores(q, k, v, None)
        new_cache = None
    elif getattr(cfg, "attn_impl", "naive") == "blocked":
        # §Perf: XLA online-softmax flash — O(Sq·chunk) score footprint
        from ..sharding.constraints import batch_axes, constrain
        H, K = q.shape[2], k.shape[2]
        kk = jnp.repeat(k, H // K, axis=2) if H != K else k
        vv = jnp.repeat(v, H // K, axis=2) if H != K else v
        ba = batch_axes()
        qq = constrain(q, ba, None, "model", None)
        out = attention_blocked(qq, kk, vv, causal=True, window=window,
                                chunk=getattr(cfg, "attn_chunk", 1024))
        if cache is not None:
            new_cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, 1),
                "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, 1),
            }
        else:
            new_cache = None
    else:  # causal train/prefill
        qpos = jnp.arange(S)[:, None]
        kpos = jnp.arange(S)[None, :]
        m = kpos <= qpos
        if window is not None:
            w_active = jnp.asarray(window)
            m = m & jnp.where(w_active > 0, kpos > qpos - w_active, True)
        mask = m[None, None]
        out = attention_scores(q, k, v, mask)
        if cache is not None:   # prefill: fill the decode buffer
            new_cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, 1),
                "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, 1),
            }
        else:
            new_cache = None
    B, Sq, H, dh = out.shape
    y = jnp.einsum("bsn,nd->bsd", out.reshape(B, Sq, H * dh),
                   p["wo"].astype(x.dtype))
    return y, new_cache


def cross_attention(p: dict, x: jax.Array, cfg, *, kv=None, kv_cache=None):
    """Decoder cross-attention over encoder output.

    ``kv``: encoder activations [B,Se,D] (prefill/train) — projected here;
    ``kv_cache``: precomputed {"k","v"} (decode).
    """
    B, S, _ = x.shape
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _proj(x, p["wq"], p.get("bq")).reshape(B, S, H, dh)
    if kv_cache is None:
        Se = kv.shape[1]
        k = _proj(kv, p["wk"]).reshape(B, Se, K, dh)
        v = _proj(kv, p["wv"]).reshape(B, Se, K, dh)
    else:
        k, v = kv_cache["k"], kv_cache["v"]
    out = attention_scores(q, k, v, None)
    y = jnp.einsum("bsn,nd->bsd", out.reshape(B, S, H * dh),
                   p["wo"].astype(x.dtype))
    return y, {"k": k, "v": v}


def cross_kv(p: dict, kv: jax.Array, cfg) -> dict:
    """Precompute the cross-attention KV cache from encoder output."""
    B, Se, _ = kv.shape
    K, dh = cfg.n_kv_heads, cfg.head_dim
    return {"k": _proj(kv, p["wk"]).reshape(B, Se, K, dh),
            "v": _proj(kv, p["wv"]).reshape(B, Se, K, dh)}


# --------------------------------------------------------------------- #
# MLPs                                                                   #
# --------------------------------------------------------------------- #
def mlp(p: dict, x: jax.Array, act: str = "silu") -> jax.Array:
    if act == "gelu":
        h = jax.nn.gelu(_proj(x, p["w_up"]))
    elif "w_gate_up" in p:
        gu = _proj(x, p["w_gate_up"])
        g, u = jnp.split(gu, 2, axis=-1)
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.silu(_proj(x, p["w_gate"])) * _proj(x, p["w_up"])
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))


# --------------------------------------------------------------------- #
# initializers                                                           #
# --------------------------------------------------------------------- #
def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) > 1 else shape[0]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def attn_params(key, cfg, dtype):
    H, K, dh, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    ks = jax.random.split(key, 4)
    if getattr(cfg, "fused_qkv", False):
        p = {
            "wqkv": dense_init(ks[0], (D, (H + 2 * K) * dh), dtype),
            "wo": dense_init(ks[3], (H * dh, D), dtype,
                             scale=(H * dh) ** -0.5),
        }
        if cfg.qkv_bias:
            p["bqkv"] = jnp.zeros(((H + 2 * K) * dh,), dtype)
        if cfg.qk_norm:
            p.update(q_norm=jnp.zeros((dh,), dtype),
                     k_norm=jnp.zeros((dh,), dtype))
        return p
    p = {
        "wq": dense_init(ks[0], (D, H * dh), dtype),
        "wk": dense_init(ks[1], (D, K * dh), dtype),
        "wv": dense_init(ks[2], (D, K * dh), dtype),
        "wo": dense_init(ks[3], (H * dh, D), dtype, scale=(H * dh) ** -0.5),
    }
    if cfg.qkv_bias:
        p.update(bq=jnp.zeros((H * dh,), dtype),
                 bk=jnp.zeros((K * dh,), dtype),
                 bv=jnp.zeros((K * dh,), dtype))
    if cfg.qk_norm:
        p.update(q_norm=jnp.zeros((dh,), dtype),
                 k_norm=jnp.zeros((dh,), dtype))
    return p


def mlp_params(key, d_model, d_ff, dtype, act="silu", fused=False):
    ks = jax.random.split(key, 3)
    if act != "gelu" and fused:
        return {"w_gate_up": dense_init(ks[0], (d_model, 2 * d_ff), dtype),
                "w_down": dense_init(ks[2], (d_ff, d_model), dtype,
                                     scale=d_ff ** -0.5)}
    p = {"w_up": dense_init(ks[1], (d_model, d_ff), dtype),
         "w_down": dense_init(ks[2], (d_ff, d_model), dtype,
                              scale=d_ff ** -0.5)}
    if act != "gelu":
        p["w_gate"] = dense_init(ks[0], (d_model, d_ff), dtype)
    return p
