"""End-to-end training driver (runs for real on whatever devices exist).

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-27b --reduced \
        --steps 200 --batch 16 --seq 128 --workdir /tmp/run1

Demonstrates the full runtime: sharded deterministic data pipeline, jitted
train step, async layered-snapshot checkpointing, crash + resume
(--simulate-failure), and straggler work-stealing (--straggler).
The production-mesh path (256/512 chips) is exercised by launch/dryrun.py;
this driver is the runnable-on-CPU end of the same stack.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax

from repro.configs import get_config, reduced
from repro.data.pipeline import ShardedLoader
from repro.launch.cache import enable_compilation_cache
from repro.models import build_model
from repro.optim import OptimizerConfig
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--workdir", default="/tmp/repro_train")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--simulate-failure", type=int, default=None,
                    help="crash at this step (then rerun with --resume)")
    ap.add_argument("--straggler", action="store_true",
                    help="simulate a slow peer loader and steal its shard")
    args = ap.parse_args()

    enable_compilation_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg, remat=False)
    opt = OptimizerConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps)

    loader = ShardedLoader(
        seed=0, vocab=cfg.vocab_size, seq_len=args.seq,
        batch_per_shard=args.batch // 2, num_shards=2, owned=[0, 1],
    )
    peers = []
    if args.straggler:
        loader = ShardedLoader(seed=0, vocab=cfg.vocab_size, seq_len=args.seq,
                               batch_per_shard=args.batch // 2, num_shards=2,
                               owned=[0])
        peers = [ShardedLoader(seed=0, vocab=cfg.vocab_size, seq_len=args.seq,
                               batch_per_shard=args.batch // 2, num_shards=2,
                               owned=[1], delay_s=0.5)]

    tcfg = TrainerConfig(workdir=args.workdir,
                         checkpoint_every=args.checkpoint_every)
    trainer = Trainer(model, opt, loader, tcfg, peer_loaders=peers,
                      microbatches=args.microbatches)

    if args.resume and trainer.resume():
        print(f"[train] resumed from step {trainer.step}")
    else:
        trainer.init_state(seed=0)
        print("[train] fresh start")

    try:
        summary = trainer.train(args.steps - trainer.step,
                                fail_at=args.simulate_failure)
    except RuntimeError as e:
        trainer.checkpoint()
        trainer.writer.drain()
        print(f"[train] CRASH: {e} — state checkpointed; rerun with --resume")
        raise SystemExit(17)

    trainer.checkpoint()
    trainer.writer.drain()
    time.sleep(0.2)
    first = trainer.metrics_log[0]["loss"] if trainer.metrics_log else None
    last = trainer.metrics_log[-1]["loss"] if trainer.metrics_log else None
    print(json.dumps({
        "arch": cfg.name, "steps": trainer.step,
        "first_loss": first, "final_loss": last,
        "loss_decreased": bool(first and last and last < first),
        "steals": trainer.steals,
        "stored_mb": round(trainer.store.stored_bytes() / 2**20, 1),
        "wall_s": round(summary["wall"], 1),
    }, indent=1))
    with open(os.path.join(args.workdir, "metrics.jsonl"), "w") as f:
        for m in trainer.metrics_log:
            f.write(json.dumps(m) + "\n")
    trainer.close()


if __name__ == "__main__":
    main()
