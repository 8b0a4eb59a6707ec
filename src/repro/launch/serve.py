"""Serving driver: batched requests against any assigned arch (reduced or
full config) with the durable request log.

    PYTHONPATH=src python -m repro.launch.serve --arch tiny:qwen2-7b \
        --requests 8 --new-tokens 8 [--crash-after 1]

:func:`load_model`, :func:`make_requests` and :func:`serve_requests` are
the path ``chip_smoke.py`` drives at full width.
"""
from __future__ import annotations

import argparse
import json
import tempfile
from typing import Dict, Optional, Sequence

import jax
import numpy as np

from ..configs.registry import parse_arch
from ..models.model import build_model
from ..serving.engine import ServeEngine
from .compile_cache import enable_compile_cache


def load_model(arch: str, seed: int):
    """``(model, params)`` for ``arch`` (``tiny:<name>`` for the reduced
    config); the weights are random, made on the device from ``seed``."""
    model = build_model(parse_arch(arch))
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    return model, params


def make_requests(cfg, prompt_lens: Sequence[int],
                  seed: int) -> Dict[int, np.ndarray]:
    """One request per entry of ``prompt_lens``: rid -> random prompt."""
    rng = np.random.default_rng(seed)
    return {i: rng.integers(0, cfg.vocab, size=n).astype(np.int32)
            for i, n in enumerate(prompt_lens)}


def serve_requests(model, params, requests: Dict[int, np.ndarray], *,
                   n_new: int, batch_size: int, log_dir: str,
                   crash_after: Optional[int] = None):
    """Serve ``requests`` through a fresh :class:`ServeEngine` on the
    request log in ``log_dir``; returns ``(committed results, engine)``.
    ``crash_after`` crashes the log after that many committed batches;
    a second call on the same ``log_dir`` recovers."""
    cfg = model.cfg
    max_len = max(int(p.shape[0]) for p in requests.values()) + n_new + (
        cfg.vis_tokens if cfg.family == "vlm" else 0)
    eng = ServeEngine(model, params, max_len=max_len, log_dir=log_dir,
                      batch_size=batch_size)
    out = eng.serve(requests, n_new=n_new, crash_after_batches=crash_after)
    return out, eng


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny:qwen2-7b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--log-dir", default=None)
    ap.add_argument("--crash-after", type=int, default=None,
                    help="crash after N committed batches (test recovery)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    model, params = load_model(args.arch, args.seed)
    requests = make_requests(model.cfg, [args.prompt_len] * args.requests,
                             args.seed)
    log_dir = args.log_dir or tempfile.mkdtemp(prefix="serve_log_")
    out, _ = serve_requests(model, params, requests,
                            n_new=args.new_tokens,
                            batch_size=args.batch_size, log_dir=log_dir,
                            crash_after=args.crash_after)
    print(json.dumps({"arch": model.cfg.name, "committed": len(out),
                      "log_dir": log_dir,
                      "sample": {str(k): out[k] for k in list(out)[:3]}},
                     indent=1))
    if args.crash_after is not None:
        print("crashed after", args.crash_after,
              "batches; re-run with --log-dir", log_dir, "to recover")


if __name__ == "__main__":
    main()
