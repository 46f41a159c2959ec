#!/usr/bin/env python3
"""Readings behind a cell's output limit, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 5 [--control]

For each seed: weights and requests from that seed, one short window of
the cell's own traffic through the farm, then the same check as
``bench/run.py`` (``max_logit_gap`` over a sample of the served tokens,
against the float32 reference).  ``--control`` also reads the control:
the reference with its weights in float8, at the same prompts and
positions, judged by the same gap.  One JSON line per seed.  The lower
reading of a limit is the largest program gap over a dozen seeds or
more, the upper the smallest control gap (``PERF.md``).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run  # noqa: E402  (sets up paths and the cache)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    run.configure_cache()
    import jax
    from bench.lib import farm, spec

    cell = spec.cell(args.workload)
    devices = run.chips_or_none(cell.chips)
    if devices is None:
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        program, services, lookup = farm.build(cell, seed, devices)
        for svc in services:
            svc.start()
        served = farm.drive(cell, seed, args.seconds, program, services,
                            lookup)
        for svc in services:
            svc.kill()
        del program, services, lookup
        jax.clear_caches()
        gc.collect()
        res = run.check(served, cell, seed, quantize=args.control)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "tasks": len(served.served),
                          "max_gap": res["max_logit_gap"]["value"],
                          "control_gap": res.get("control_gap"),
                          "unfinished": res["unfinished"]["value"],
                          "idle_services": res["idle_services"]["value"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
