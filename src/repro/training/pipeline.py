"""GPipe-style pipeline parallelism over a "stage" mesh axis (optional
strategy; DESIGN.md §5).

The model's layer stack is split into S contiguous stage groups; each
stage's devices hold only their group's parameters (true PP memory
scaling).  Microbatches stream through stages with ``jax.lax.ppermute``
boundary rotation inside ``shard_map`` — the classic GPipe schedule with
S-1 bubble slots, expressed JAX-natively (no torch.distributed-style
point-to-point emulation; the permute IS the pipe).

This module is deliberately self-contained (a stack of dense blocks) —
it demonstrates and tests the schedule; wiring arbitrary families through
PP is a config-level extension (the production mesh for the assigned
cells has no stage axis, per the brief).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def mlp_block(p, x):
    h = jax.nn.silu(x @ p["w1"]) * (x @ p["w2"])
    return x + h @ p["w3"]


def init_pipeline_params(key, *, n_stages: int, layers_per_stage: int,
                         d_model: int, d_ff: int):
    """[S, Lps, ...] — leading dim sharded over the stage axis."""
    def one(k):
        k1, k2, k3 = jax.random.split(k, 3)
        s = d_model ** -0.5
        # small output scale keeps the normalization-free demo stack stable
        return {"w1": jax.random.normal(k1, (d_model, d_ff)) * s,
                "w2": jax.random.normal(k2, (d_model, d_ff)) * s,
                "w3": jax.random.normal(k3, (d_ff, d_model))
                      * 0.1 * d_ff ** -0.5}
    keys = jax.random.split(key, n_stages * layers_per_stage)
    stacked = jax.vmap(one)(keys)
    return jax.tree.map(
        lambda a: a.reshape((n_stages, layers_per_stage) + a.shape[1:]),
        stacked)


def gpipe_forward(params, x_mb, *, n_stages: int, axis: str = "stage"):
    """Run M microbatches through the pipe inside shard_map.

    ``params``: this stage's [Lps, ...] group (already sharded-in);
    ``x_mb``: [M, B/M, T, D] microbatches (replicated over the stage axis).
    Returns [M, B/M, T, D] outputs (valid on the LAST stage).
    """
    stage = jax.lax.axis_index(axis)
    M = x_mb.shape[0]

    def stage_apply(x):
        def body(x, lp):
            return mlp_block(lp, x), None
        y, _ = jax.lax.scan(body, x, params)
        return y

    def step(carry, t):
        buf = carry           # [B/M, T, D] the slot flowing through me
        # inject a fresh microbatch at stage 0 while the schedule fills
        inject = jnp.where(t < M, t, M - 1)
        buf = jnp.where(stage == 0, x_mb[inject], buf)
        out = stage_apply(buf)
        # rotate stage s -> s+1 (last stage's output exits the pipe)
        nxt = jax.lax.ppermute(
            out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
        # the last stage banks its finished microbatch index t-(S-1)
        return nxt, out

    T_total = M + n_stages - 1            # GPipe bubble: S-1 extra ticks
    _, outs = jax.lax.scan(step, jnp.zeros_like(x_mb[0]),
                           jnp.arange(T_total))
    # on the last stage, outs[t] for t in [S-1, S-1+M) are the results;
    # zero elsewhere + psum replicates them across the pipe
    take = jax.lax.dynamic_slice_in_dim(outs, n_stages - 1, M, axis=0)
    take = jnp.where(stage == n_stages - 1, take, 0.0)
    return jax.lax.psum(take, axis)


def make_gpipe_fn(mesh: Mesh, *, n_stages: int, axis: str = "stage"):
    """shard_map-wrapped pipeline forward on ``mesh`` (must carry
    ``axis``)."""
    pspec = P(axis)                       # params: stage dim sharded
    xspec = P(None, "data", None, None) if "data" in mesh.axis_names \
        else P()

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: pspec, {"w1": 0, "w2": 0, "w3": 0}),
                  xspec),
        out_specs=xspec, check_vma=False)
    def fn(params, x_mb):
        params = jax.tree.map(lambda a: a[0], params)  # my stage's group
        return gpipe_forward(params, x_mb, n_stages=n_stages, axis=axis)

    return fn
