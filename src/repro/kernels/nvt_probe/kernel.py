"""NVTraverse batched hash-probe Pallas TPU kernel — the paper's hot loop.

The paper's traversal is pointer-chasing over bucket chains; its entire
point is that the journey does *zero* persistence work.  The TPU-native
adaptation (DESIGN.md §2): pointer-chasing gathers are hostile to the VPU,
so buckets are laid out as dense fixed-capacity rows ("bucket tiles") and
the journey becomes a vectorized key-compare over a VMEM-resident tile —
same read-only semantics, MXU/VPU-friendly layout.  The critical phase
(CAS + flush + fence) stays on the host commit path (core/batched.py);
this kernel is the read side of the split the paper formalizes.

Inputs:
  keys_tile [n_buckets, cap] int32 — bucket rows (0 = empty slot)
  vals_tile [n_buckets, cap] int32
  queries   [Q] int32
Outputs:
  found [Q] int32 (0/1), vals [Q] int32

Grid: ``(Q/block_q, n_buckets/block_nb)`` — the second dimension
*streams* bucket-tile blocks through VMEM, so the table no longer has to
fit on chip (the old kernel pinned the whole table, capping it at ~2 MB).
The bucket axis is the innermost (sequential) grid dimension and the
output block index depends only on the query-block index, so the output
stays resident in VMEM across the sweep and accumulates.

Per (query-block, bucket-tile) step the whole query block is processed at
once — hash all queries, mask those whose bucket falls outside this tile,
gather their bucket rows, and compare — no scalar per-query loop.  The
chip has no vector gather, so the row gather is a one-hot matmul on the
MXU, exact because each int32 word moves as four bf16 byte planes.
Each query's bucket lives in exactly one tile, so sum-accumulation
across tiles is exact (bit-identical to ``probe_ref``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mix32(x):
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return (x ^ (x >> 16)).astype(jnp.uint32)


def _bytes_bf16(x, p: int):
    """Byte ``p`` of int32 ``x`` as bf16 — 0..255 is exact in bf16."""
    return ((x >> (8 * p)) & 0xFF).astype(jnp.float32).astype(jnp.bfloat16)


def _gather_rows(onehot, tile):
    """``onehot @ tile`` for an int32 tile, exact: each byte plane is a
    bf16 MXU matmul with one nonzero term per output, reassembled into
    the int32 row (zero where the one-hot row is empty)."""
    out = jnp.zeros((onehot.shape[0], tile.shape[1]), jnp.int32)
    for p in range(4):
        plane = jax.lax.dot_general(
            onehot, _bytes_bf16(tile, p), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out = out | (plane.astype(jnp.int32) << (8 * p))
    return out


def _kernel(keys_ref, vals_ref, q_ref, found_ref, val_ref, *,
            n_buckets: int, block_nb: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        found_ref[...] = jnp.zeros_like(found_ref)
        val_ref[...] = jnp.zeros_like(val_ref)

    qs = q_ref[...]                                    # [block_q, 1]
    b = (_mix32(qs) % jnp.uint32(n_buckets)).astype(jnp.int32)
    local = b - j * block_nb
    in_tile = (local >= 0) & (local < block_nb)        # bucket in this tile?
    # the row gather is a one-hot matmul: the chip has no vector gather
    onehot = (local == jax.lax.broadcasted_iota(
        jnp.int32, (qs.shape[0], block_nb), 1)).astype(jnp.bfloat16)
    rows_k = _gather_rows(onehot, keys_ref[...])       # [block_q, cap]
    rows_v = _gather_rows(onehot, vals_ref[...])
    hit = (rows_k == qs) & in_tile                     # vectorized compare
    found_ref[...] += jnp.max(hit.astype(jnp.int32), axis=1, keepdims=True)
    val_ref[...] += jnp.sum(jnp.where(hit, rows_v, 0), axis=1,
                            keepdims=True)


def nvt_probe_kernel(keys_tile, vals_tile, queries, *, block_q: int = 128,
                     block_nb: int = 512, interpret: bool = False):
    NB, cap = keys_tile.shape
    Q = queries.shape[0]
    block_q = min(block_q, Q)
    assert Q % block_q == 0
    block_nb = min(block_nb, NB)
    pad_nb = (-NB) % block_nb
    if pad_nb:
        # padded rows are empty buckets no query hashes to (b < NB always)
        keys_tile = jnp.pad(keys_tile, ((0, pad_nb), (0, 0)))
        vals_tile = jnp.pad(vals_tile, ((0, pad_nb), (0, 0)))
    n_tiles = (NB + pad_nb) // block_nb
    kernel = functools.partial(_kernel, n_buckets=NB, block_nb=block_nb)
    # queries and results travel as [Q, 1] columns: a query block is a
    # sublane-aligned (block_q, 1) tile that broadcasts against the
    # [block_q, cap] rows without a relayout
    found, vals = pl.pallas_call(
        kernel,
        grid=(Q // block_q, n_tiles),
        in_specs=[
            pl.BlockSpec((block_nb, cap), lambda i, j: (j, 0)),  # streamed
            pl.BlockSpec((block_nb, cap), lambda i, j: (j, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),  # VMEM-resident
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),  # across sweep
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, 1), jnp.int32),
            jax.ShapeDtypeStruct((Q, 1), jnp.int32),
        ],
        interpret=interpret,
    )(keys_tile, vals_tile, queries.reshape(Q, 1))
    return found[:, 0], vals[:, 0]
