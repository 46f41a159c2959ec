"""Serving launcher: the paper's workload — a farm of generation requests.

    python -m repro.launch.serve --arch qwen3-1.7b --reduced \
        --requests 32 --services 3 --new-tokens 8
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

import repro.configs as cfgs
from repro.core import LookupService, Service
from repro.launch.compile_cache import configure_compile_cache
from repro.models import build
from repro.runtime.serve_loop import (ServeConfig, generated_tokens,
                                     make_generate_program, serve_requests)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--services", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--batch-per-task", type=int, default=4)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--kill-one", action="store_true",
                    help="fault-inject a service mid-run")
    ap.add_argument("--tune-cache", default=None, metavar="PATH",
                    help="kernel tuning cache (JSON from repro.tune) — "
                         "attention/scan dispatch picks tuned chunkings "
                         "up from it; untuned shapes keep the defaults")
    args = ap.parse_args()
    configure_compile_cache()

    if args.tune_cache:
        from repro.tune import configure

        cache = configure(args.tune_cache)
        print(f"tuning cache {args.tune_cache}: {len(cache)} entries")

    cfg = cfgs.get(args.arch)
    if args.reduced:
        cfg = cfgs.reduced(cfg)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))

    lookup = LookupService()
    devices = jax.devices()
    services = [Service(lookup, devices=[devices[i % len(devices)]])
                for i in range(args.services)]
    for s in services:
        s.start()
    if args.kill_one:
        services[0].fail_after(1)

    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (args.requests, args.prompt_len))
    sc = ServeConfig(max_new_tokens=args.new_tokens,
                     prompt_len=args.prompt_len,
                     batch_per_task=args.batch_per_task)
    t0 = time.perf_counter()
    results, stats = serve_requests(make_generate_program(api, sc, params),
                                    prompts, sc, lookup=lookup)
    gen = generated_tokens(results)
    dt = time.perf_counter() - t0
    toks = gen.shape[0] * gen.shape[1]
    print(f"generated {gen.shape} in {dt:.2f}s "
          f"({toks/dt:.0f} tok/s across the farm)")
    print(f"farm stats: {stats}")


if __name__ == "__main__":
    main()
