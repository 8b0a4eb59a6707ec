"""Flash attention Pallas TPU kernel: blocked online-softmax.

Grid: (batch*q_heads, Sq/block_q, Sk/block_k), KV-block dim innermost and
sequential ("arbitrary") so the running max/sum/accumulator live in VMEM
scratch across KV iterations.  BlockSpecs stream one (block_q × d) Q tile
and one (block_k × d) KV tile into VMEM per step; the MXU sees
[block_q, d] @ [d, block_k] and [block_q, block_k] @ [block_k, d] GEMMs
with d and blocks multiples of 128.

GQA is handled by the KV index_map (``kv_head = q_head // group``): no
repeated KV is ever materialized.  Causal and sliding-window masks are
applied against absolute positions; KV blocks entirely outside the visible
window are skipped via ``pl.when`` (their loads still happen — block
skipping at the grid level is a §Perf iteration for the TPU timeline, but
the FLOP accounting already excludes the masked MACs on the real MXU since
the whole tile is predicated off).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale: float, causal: bool, window: int,
            block_q: int, block_k: int, n_k: int):
    i = pl.program_id(1)          # q block
    j = pl.program_id(2)          # kv block

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_start = i * block_q
    k_start = j * block_k

    # skip KV blocks fully in the future (causal) or past the window
    visible = True
    if causal:
        visible = k_start <= q_start + block_q - 1
    if window > 0:
        visible = visible & (k_start + block_k - 1 >
                             q_start - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                             # [bq, bk]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask = mask & (kpos <= qpos)
        if window > 0:
            mask = mask & (kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_sc[...]                        # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                    # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)           # [bq, 1]
        l_sc[...] = l_sc[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(j == n_k - 1)
    def _finalize():
        l = l_sc[...]
        l = jnp.where(l == 0.0, 1.0, l)           # fully-masked rows
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q: [BH, Sq, d]; k/v: [BK, Sk, d] with BH % BK == 0 (GQA groups).

    Returns [BH, Sq, d] attention output.
    """
    BH, Sq, d = q.shape
    BK, Sk, _ = k.shape
    assert BH % BK == 0
    group = BH // BK
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    n_q, n_k = Sq // block_q, Sk // block_k
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k)

    return pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j, group=group: (b // group, j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j, group=group: (b // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),      # running max
            pltpu.VMEM((block_q, 1), jnp.float32),      # running sum
            pltpu.VMEM((block_q, d), jnp.float32),      # output accum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
