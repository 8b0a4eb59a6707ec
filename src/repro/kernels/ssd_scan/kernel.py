"""Mamba2 SSD chunk-scan Pallas TPU kernel.

The TPU-native formulation of the selective scan (DESIGN.md §2 hardware
adaptation): instead of the CUDA per-timestep recurrence, each Q-token
chunk is computed as dense [Q,Q]/[Q,N]/[Q,P] GEMMs on the MXU, and only a
tiny [P,N] state crosses chunks.

Grid: (batch*heads, n_chunks), chunk dim sequential ("arbitrary") — the
carried state lives in a VMEM scratch accumulator.  Per program the VMEM
working set is x[Q,P], dA/dt[Q], B/C[Q,N], L[Q,Q], state[P,N]; with
Q=P=N=128 everything is MXU-aligned.

Inputs (pre-arranged by ops.py):
  xh  [BH, C, Q, P]   head channels
  dt  [BH, C, Q]      softplus(dt + bias)
  dA  [BH, C, Q]      dt * A  (A negative, per head)
  Bm  [BH, C, Q, N]   input projection (group-broadcast per head)
  Cm  [BH, C, Q, N]   output projection
Output:
  y   [BH, C, Q, P]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _column(row, eye):
    """[1, Q] row -> [Q, 1] column without a transpose: keep the diagonal
    of the row broadcast down the sublanes, then reduce across lanes."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _kernel(x_ref, dt_ref, dA_ref, b_ref, c_ref, y_ref, state_sc, *,
            chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_sc[...] = jnp.zeros_like(state_sc)

    x = x_ref[0, 0].astype(jnp.float32)        # [Q, P]
    dt = dt_ref[0, 0].astype(jnp.float32)      # [1, Q]
    dA = dA_ref[0, 0].astype(jnp.float32)      # [1, Q]
    Bm = b_ref[0, 0].astype(jnp.float32)       # [Q, N]
    Cm = c_ref[0, 0].astype(jnp.float32)       # [Q, N]

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = row >= col
    eye = row == col
    # inclusive cumsum as a masked lane reduction, kept in both layouts
    cum_c = jnp.sum(jnp.where(tri, dA, 0.0), axis=1, keepdims=True)  # [Q,1]
    cum_r = jnp.sum(jnp.where(eye, cum_c, 0.0), axis=0,
                    keepdims=True)                                   # [1,Q]
    total = jnp.sum(dA, axis=1, keepdims=True)                       # [1,1]
    # intra-chunk: masked decay kernel L[i,j] = exp(cum_i - cum_j), j <= i
    L = jnp.where(tri, jnp.exp(cum_c - cum_r), 0.0)     # [Q, Q]
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    scores = cb * L * dt                       # [Q, Q]
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk contribution from the carried state
    state = state_sc[...]                      # [P, N]
    y_inter = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y = y + y_inter * jnp.exp(cum_c)

    # state update: S <- exp(cum_last) * S + X^T diag(w) B,  w = dt*decay
    w = jnp.exp(total - cum_c) * _column(dt, eye)        # [Q,1]
    s_local = jax.lax.dot_general(x * w, Bm, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    state_sc[...] = state * jnp.exp(total) + s_local
    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd_scan_kernel(xh, dt, dA, Bm, Cm, *, interpret: bool = False):
    """xh: [BH, C, Q, P]; dt/dA: [BH, C, Q]; Bm/Cm: [BH, C, Q, N]."""
    BH, C, Q, P = xh.shape
    N = Bm.shape[-1]
    kernel = functools.partial(_kernel, chunk=Q)
    # dt/dA travel as [BH, C, 1, Q]: a (1, Q) row block is tile-legal
    # (its sublane dim equals the array's), a (1, 1, Q) block is not
    dt = dt.reshape(BH, C, 1, Q)
    dA = dA.reshape(BH, C, 1, Q)
    return pl.pallas_call(
        kernel,
        grid=(BH, C),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda b, c: (b, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, C, Q, P), xh.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xh, dt, dA, Bm, Cm)
