"""Training launcher: synchronous pjit mode and JJPF farm mode.

    python -m repro.launch.train --arch qwen3-1.7b --steps 200 \
        --mode sync --reduced --ckpt-dir /tmp/ckpt
    python -m repro.launch.train --arch llama3.2-1b --mode farm \
        --services 4 --rounds 10 --reduced

``--reduced`` runs the CPU-sized config (the full configs are exercised via
``repro.launch.dryrun`` on the production mesh).  On a real fleet this same
driver runs under one controller per pod; farm mode then recruits pods via
the lookup service (see DESIGN.md §2).
"""

from __future__ import annotations

import argparse
import json

import jax

import repro.configs as cfgs
from repro.checkpoint import AsyncCheckpointer
from repro.core import LookupService, Service
from repro.data import make_dataset
from repro.launch.compile_cache import configure_compile_cache
from repro.models import build
from repro.runtime import TrainConfig, Trainer
from repro.runtime.local_sgd import LocalSGDConfig, LocalSGDTrainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mode", choices=["sync", "farm"], default="sync")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--services", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--schedule", default="cosine",
                    choices=["cosine", "wsd", "constant"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args()
    configure_compile_cache()

    cfg = cfgs.get(args.arch)
    if args.reduced:
        cfg = cfgs.reduced(cfg)
    api = build(cfg)
    tc = TrainConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                     total_steps=args.steps, schedule=args.schedule,
                     stable_steps=args.steps // 2, decay_steps=args.steps // 4)

    if args.mode == "sync":
        ds = make_dataset("markov", cfg.vocab_size, args.seq_len, args.batch)
        ck = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
        trainer = Trainer(api, tc, ds, checkpointer=ck, ckpt_every=50)
        logs = trainer.run(args.steps)
        print(f"final loss: {logs[-1]['loss']:.4f} "
              f"(step {logs[-1]['step']}, {logs[-1]['step_time_s']*1e3:.0f} ms/step)")
    else:
        lookup = LookupService()
        devices = jax.devices()
        for i in range(args.services):
            Service(lookup, devices=[devices[i % len(devices)]]).start()
        ls = LocalSGDConfig(inner_steps=4, n_shards=args.services * 2,
                            batch_per_shard=args.batch,
                            seq_len=args.seq_len)
        trainer = LocalSGDTrainer(api, tc, ls, lookup=lookup)
        losses = trainer.run(args.rounds)
        print(f"round losses: {[round(l, 4) for l in losses]}")
        print(f"farm stats: {trainer.farm_stats[-1]}")
        logs = [{"round": i, "loss": l} for i, l in enumerate(losses)]

    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(logs, f, indent=1)


if __name__ == "__main__":
    main()
