"""Plain float32 reference of a Qwen3 dense decoder, and the seeded weights.

The weights of a served model are made here, from the run's seed, so
that the reference can make them again after the program's state is
freed: it takes nothing the program made.  Every leaf is drawn in
float32 and rounded to bfloat16, the type the configuration serves.

    layer i, leaf j:  normal(fold_in(fold_in(fold_in(key, 2), i), j))
    embedding:        normal(fold_in(key, 0))
    final norm:       normal(fold_in(key, 1)),  an offset, see below
    matrices and the embedding: std ``initializer_range`` of the
                      configuration (0.02 for Qwen3, as its own
                      initialisation draws them)
    norm weights:     1 + delta, delta ~ 0.1 * normal (stored as delta),
                      so that a norm's weight does work in the comparison

The forward pass follows the Qwen3 description (hf:Qwen/Qwen3-1.7B):
pre-norm RMSNorm blocks, grouped-query attention with RMSNorm on each
query and key head before rotary embedding (rotate-half form, base
``rope_theta``), causal softmax attention scaled by 1/sqrt(head_dim), a
SiLU-gated MLP, a final RMSNorm and logits through the tied embedding.
Every matrix product runs at ``Precision.HIGHEST`` in float32.

``quant`` (optional) rounds both operands of every matrix product
before it runs: the control passes float8 rounding to show that a
lower precision fails the comparison.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NORM_SPREAD = 0.1
HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: dict):
    """(D, F, H, K, dh, V, L) of a configuration file's sizes."""
    return (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["vocab_size"], cfg["num_hidden_layers"])


def seed_key(seed: int):
    """The weights' key: any whole number up to 2**64 - 1."""
    return jax.random.PRNGKey(int(seed))


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def layer_shapes(cfg: dict) -> dict:
    """Leaf -> (shape, std) of one layer, matrices as (in, out)."""
    D, F, H, K, dh, _, _ = dims(cfg)
    s = cfg["initializer_range"]
    return {"ln1": ((D,), NORM_SPREAD), "ln2": ((D,), NORM_SPREAD),
            "q": ((D, H * dh), s), "k": ((D, K * dh), s),
            "v": ((D, K * dh), s), "o": ((H * dh, D), s),
            "q_norm": ((dh,), NORM_SPREAD), "k_norm": ((dh,), NORM_SPREAD),
            "gate": ((D, F), s), "up": ((D, F), s), "down": ((F, D), s)}


def layer_weights(cfg: dict, key, i) -> dict:
    """Layer ``i``'s bfloat16 leaves (``i`` may be traced, for a vmap)."""
    k = jax.random.fold_in(jax.random.fold_in(key, 2), i)
    return {name: _normal(jax.random.fold_in(k, j), shape, std)
            for j, (name, (shape, std))
            in enumerate(layer_shapes(cfg).items())}


def embed_weights(cfg: dict, key):
    D, _, _, _, _, V, _ = dims(cfg)
    return _normal(jax.random.fold_in(key, 0), (V, D),
                   cfg["initializer_range"])


def final_norm_weights(cfg: dict, key):
    D = cfg["hidden_size"]
    return _normal(jax.random.fold_in(key, 1), (D,), NORM_SPREAD)


# --------------------------------------------------------------------- #
# forward                                                                #
# --------------------------------------------------------------------- #
def _mm(a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, delta, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + delta.astype(jnp.float32))


def _rope(x, theta):
    """Rotate-half rotary embedding; x: [S, heads, dh], positions 0..S-1."""
    S, _, dh = x.shape
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    half = dh // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def layer_forward(cfg: dict, h, w: dict, quant=None):
    """One decoder layer over a whole sequence; h: float32 [S, D]."""
    D, F, H, K, dh, _, _ = dims(cfg)
    eps = cfg["rms_norm_eps"]
    S = h.shape[0]
    f32 = {n: a.astype(jnp.float32) for n, a in w.items()}
    x = _rms(h, f32["ln1"], eps)
    q = _mm(x, f32["q"], quant).reshape(S, H, dh)
    k = _mm(x, f32["k"], quant).reshape(S, K, dh)
    v = _mm(x, f32["v"], quant).reshape(S, K, dh)
    q = _rope(_rms(q, f32["q_norm"], eps), cfg["rope_theta"])
    k = _rope(_rms(k, f32["k_norm"], eps), cfg["rope_theta"])
    group = H // K                       # query head h reads kv head h//group
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    if quant is not None:
        q, k, v = quant(q), quant(k), quant(v)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / np.sqrt(dh)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    if quant is not None:
        p = quant(p)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    h = h + _mm(a.reshape(S, H * dh), f32["o"], quant)
    x = _rms(h, f32["ln2"], eps)
    g = jax.nn.silu(_mm(x, f32["gate"], quant)) * _mm(x, f32["up"], quant)
    return h + _mm(g, f32["down"], quant)


def logits_at(cfg: dict, h, final_delta, embed, quant=None):
    """Logits of the rows of h (float32 [n, D]) through the tied embedding."""
    x = _rms(h, final_delta.astype(jnp.float32), cfg["rms_norm_eps"])
    return _mm(x, embed.astype(jnp.float32).T, quant)


def fp8_round(x):
    """Per-tensor scaled rounding to float8_e4m3fn and back to float32."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


QUANTS = {None: None, "fp8": fp8_round}


class Reference:
    """Teacher-forced reference over prompts and their served tokens.

    ``served_logits`` takes prompts and the tokens served after each and
    returns, per request, the logits at every position that chose a
    served token.  Sequences are padded to one length (causal: padding
    after a sequence changes none of its positions) and run one layer at
    a time, each layer's weights made again from the seed, so the
    reference holds one layer's weights and the sample's activations."""

    def __init__(self, cfg: dict, seed: int, quant=None):
        self.cfg = cfg
        self.key = seed_key(seed)
        self.quant = QUANTS[quant]
        self._layer = jax.jit(
            lambda h, w: layer_forward(cfg, h, w, self.quant))
        self._weights = jax.jit(functools.partial(layer_weights, cfg))
        self._emb_full = jax.jit(functools.partial(embed_weights, cfg))
        self._final = jax.jit(functools.partial(final_norm_weights, cfg))
        self._logits = jax.jit(
            lambda h, fd, e: logits_at(cfg, h, fd, e, self.quant))

    def hidden(self, tokens: np.ndarray):
        """Final hidden states (before the final norm) of padded token
        rows [n, S]: float32 [n, S, D]."""
        emb = self._emb_full(self.key)
        hs = [emb[jnp.asarray(t)].astype(jnp.float32) for t in tokens]
        del emb
        L = self.cfg["num_hidden_layers"]
        for i in range(L):
            w = self._weights(self.key, i)
            hs = [self._layer(h, w) for h in hs]
            del w
        return hs

    def served_logits(self, prompts, served):
        """For each (prompt, served tokens) pair, float32 logits [n_new, V]
        at the positions that chose the served tokens."""
        S = max(len(p) + len(t) for p, t in zip(prompts, served))
        rows = np.zeros((len(prompts), S), np.int32)
        for r, (p, t) in enumerate(zip(prompts, served)):
            seq = np.concatenate([p, t])
            rows[r, :seq.size] = seq
        hs = self.hidden(rows)
        emb = self._emb_full(self.key)
        fd = self._final(self.key)
        out = []
        for h, p, t in zip(hs, prompts, served):
            pos = np.arange(len(p) - 1, len(p) - 1 + len(t))
            out.append(np.asarray(self._logits(h[jnp.asarray(pos)], fd,
                                               emb)))
        return out


def widest_gap(ref_logits, chosen) -> float:
    """The widest gap, over every position of every sequence, by which
    the reference's logit of the chosen token lies below its best."""
    gap = 0.0
    for lg, t in zip(ref_logits, chosen):
        t = np.asarray(t)
        best = lg.max(axis=-1)
        gap = max(gap, float((best - lg[np.arange(t.size), t]).max()))
    return gap
