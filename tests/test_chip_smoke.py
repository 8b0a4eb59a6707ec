"""``chip_smoke.py`` off the chip: it refuses the CPU, each of its phases
passes at a tiny size, and its import path leaves ``XLA_FLAGS`` alone.
Also the one compile-cache helper every entry point calls."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)


def _python(*args, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=str(ROOT / "src"), **env)
    return subprocess.run([sys.executable, *args], cwd=ROOT, env=full,
                          capture_output=True, text=True, timeout=600)


def test_refuses_to_run_without_a_tpu():
    r = _python("chip_smoke.py")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_serve_phase_tiny():
    cs.serve_phase("tiny:qwen3-1.7b", (24,) * 4 + (8,) * 4, batch=4,
                   n_new=4, seed=0, tol=1e-3)


def test_hash_phase_tiny():
    cs.hash_phase(capacity=1 << 12, n_buckets=1 << 8, n_keys=2048,
                  batch=256, seed=0)


def test_ordered_phase_tiny():
    cs.ordered_phase(capacity=1 << 12, n_keys=3072, batch=1024, seed=0,
                     max_items=256, n_top=128)


def test_sharded_phase_on_four_devices():
    r = _python("-c", "import chip_smoke; chip_smoke.sharded_phase(4, 0)",
                XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.count("4 shards on 4 devices") == 3


def test_chip_path_imports_leave_xla_flags_alone():
    code = (
        "import os, sys\n"
        "import chip_smoke, benchmarks.run\n"
        "import repro.launch.serve, repro.launch.compile_cache\n"
        "import repro.core.batched, repro.core.ordered, repro.core.sharded\n"
        "assert 'repro.launch.dryrun' not in sys.modules\n"
        "assert 'XLA_FLAGS' not in os.environ, os.environ['XLA_FLAGS']\n")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env=dict(env, JAX_PLATFORMS="cpu",
                                PYTHONPATH=str(ROOT / "src")),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]


def test_compile_cache_follows_env_else_checkout(monkeypatch, tmp_path):
    from repro.launch.compile_cache import enable_compile_cache
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == old
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
