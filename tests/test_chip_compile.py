"""Compile rehearsals for a described TPU v5e chip — no chip needed.

The TPU compiler ships with JAX and compiles for a topology that is
described rather than attached.  Each test compiles one program of the
main path at its real size and so catches what the chip's compiler
refuses and interpret mode accepts (tile-illegal blocks, vector gathers,
programs that do not fit).  Nothing runs: these tests say nothing about
results or speed.

The topology is described inside the module fixture, never at import:
only one process at a time may load the TPU library, and a module that
decided at import whether its tests exist would give parallel test
workers different collections.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_arch
from repro.core import batched as B
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.nvt_probe.ops import nvt_probe
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.models.model import build_model

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host, with the persistent compile
    cache off: a described-chip compile is written to the cache but
    cannot be read back without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _is_kernel(compiled) -> bool:
    """The Pallas kernel reached the chip's compiler as a Mosaic custom
    call — not interpret mode and not the ``ref.py`` path."""
    return "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    q = _spec(one_chip, (16, 2048, 128), jnp.bfloat16)
    c = _compile(lambda q, k, v: flash_attention_kernel(q, k, v), q, q, q)
    assert _is_kernel(c)


def test_nvt_probe_compiles(one_chip):
    table = _spec(one_chip, (1 << 16, 32), jnp.int32)
    queries = _spec(one_chip, (4096,), jnp.int32)
    c = _compile(lambda k, v, q: nvt_probe(k, v, q), table, table, queries)
    assert _is_kernel(c)


def test_ssd_scan_compiles(one_chip):
    cfg = get_arch("mamba2-370m")
    Bt, S = 1, 2048
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    args = (_spec(one_chip, (Bt, S, H, P), jnp.bfloat16),
            _spec(one_chip, (Bt, S, H), jnp.float32),
            _spec(one_chip, (H,), jnp.float32),
            _spec(one_chip, (Bt, S, N), jnp.bfloat16),
            _spec(one_chip, (Bt, S, N), jnp.bfloat16))
    c = _compile(lambda *a: ssd_scan(*a, chunk=cfg.ssm_chunk), *args)
    assert _is_kernel(c)


def test_qwen3_decode_step_compiles_at_full_width(one_chip):
    model = build_model(get_arch("qwen3-1.7b"))
    batch, max_len = 8, 544
    params = _shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    caches = _shapes(jax.eval_shape(lambda: model.init_caches(batch,
                                                              max_len)),
                     one_chip)
    c = _compile(model.decode_step, params,
                 _spec(one_chip, (batch,), jnp.int32), caches,
                 _spec(one_chip, (), jnp.int32))
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


def test_hash_lookup_compiles_at_16m_nodes(one_chip):
    nodes, buckets = 1 << 24, 1 << 22
    state = B.HashMapState(
        key=_spec(one_chip, (nodes,), jnp.int32),
        val=_spec(one_chip, (nodes,), jnp.int32),
        nxt=_spec(one_chip, (nodes,), jnp.int32),
        live=_spec(one_chip, (nodes,), jnp.bool_),
        head=_spec(one_chip, (buckets,), jnp.int32),
        cursor=_spec(one_chip, (), jnp.int32),
        flushes=_spec(one_chip, (), jnp.int32),
        fences=_spec(one_chip, (), jnp.int32))
    c = _compile(lambda st, ks: B.lookup(st, ks, buckets), state,
                 _spec(one_chip, (1 << 16,), jnp.int32))
    assert c.memory_analysis().argument_size_in_bytes > 13 * nodes
