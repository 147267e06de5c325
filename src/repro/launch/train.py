"""End-to-end training driver (CLI).

    PYTHONPATH=src python -m repro.launch.train --arch tiny-lm --steps 200

Runs the fault-tolerant driver on the local device(s): synthetic-but-
learnable data, AdamW, periodic atomic checkpoints, straggler accounting,
optional failure injection (to demo checkpoint-restart end to end).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax

from repro.configs import get_config
from repro.data.pipeline import DataConfig
from repro.ft.driver import FailureInjector, TrainDriver
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.train.step import make_opt_init, make_train_step


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny-lm")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke config of the arch")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="artifacts/train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject failures at these steps (demo FT)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=args.reduced)
    cfg = dataclasses.replace(
        cfg, plan=cfg.plan.replace(microbatches=args.microbatches))
    model = Model(cfg)
    train_step = jax.jit(make_train_step(model), donate_argnums=(0, 1))

    if not args.resume:
        import shutil
        shutil.rmtree(args.ckpt_dir, ignore_errors=True)

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch)
    driver = TrainDriver(
        model=model, train_step=train_step,
        opt_init=make_opt_init(model), data_cfg=data_cfg,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        injector=FailureInjector(fail_at=set(args.fail_at)) if args.fail_at
        else None)

    t0 = time.time()
    result = driver.run(args.steps)
    wall = time.time() - t0

    losses = result["losses"]
    for rec in losses[:: args.log_every]:
        print(f"step {rec['step']:5d}  loss {rec['loss']:.4f}  "
              f"{rec['seconds']*1e3:.0f} ms")
    first = losses[0]["loss"] if losses else float("nan")
    last = losses[-1]["loss"] if losses else float("nan")
    print(f"\n{args.arch}: {len(losses)} steps in {wall:.1f}s  "
          f"loss {first:.3f} -> {last:.3f}  "
          f"stragglers={len(result['stragglers'])}")
    out = Path(args.ckpt_dir) / "train_log.json"
    out.write_text(json.dumps(result, indent=1))
    print(f"log: {out}")


if __name__ == "__main__":
    main()
