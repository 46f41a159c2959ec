#!/usr/bin/env python3
"""The knee of an open-loop cell: its latency at several fixed rates, in
one process on the chip.

    python3 bench/sweep.py --workload qwen3-chat-open --rates 4,5,6 \
        --seconds 45 --windows 2 --seed 1

The farm is built and warmed once; each rate then gets ``--windows``
windows of the cell's traffic at that rate, each on the cell's own fixed
arrival schedule (``bench.lib.farm.arrival_gaps``).  One JSON line per
window: the median and 90th percentile latency of the requests due in
it, the same for its first and last thirds (a backlog that grows shows
as a last third slower than the first), the requests that never came
back, the completions inside the window over the requests due in it,
and how late the generator ran.  A window holds when all its requests
came back and the farm completed, inside the window, at least
``KEEPS_UP`` of the requests due in it: below capacity completions keep
pace with arrivals, above it the backlog grows all through the window.
(The thirds are not the test: on a fixed schedule they follow where its
bursts fall.)  The knee is the highest rate whose windows all hold, and
the cell runs at four fifths of it (``bench/traffic``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run  # noqa: E402  (sets up paths and the cache)

KEEPS_UP = 0.97


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--windows", type=int, default=1)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    run.configure_cache()
    from bench.lib import farm, spec
    from bench.lib.stats import percentile

    cell = spec.cell(args.workload)
    if cell.traffic.loop != "open":
        raise SystemExit(f"{cell.name} is not an open-loop cell")
    devices = run.chips_or_none(cell.chips)
    if devices is None:
        return 2
    program, services, lookup = farm.build(cell, args.seed, devices)
    farm.warm(program, services, cell, args.seed)
    for svc in services:
        svc.start()
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        holds = True
        for window in range(args.windows):
            s = farm.drive(cell, args.seed, args.seconds, program, services,
                           lookup, rate_per_s=rate)
            lat = {i: s.done.get(i, s.t_close + farm.DRAIN_S) - s.due[i]
                   for i in s.in_window}
            order = sorted(lat, key=lambda i: s.due[i])
            third = max(1, len(order) // 3)
            first = [lat[i] for i in order[:third]]
            last = [lat[i] for i in order[-third:]]
            batches = [ev[3] for ev in s.events if ev[1] == "dispatch"]
            kept = float(sum(bool(s.t_open <= d <= s.t_close)
                             for d in s.done.values()) / max(1, len(order)))
            row = {"rate_per_s": rate, "window": window,
                   "requests": len(order), "failed": len(s.failed),
                   "p50_s": percentile(list(lat.values()), 50),
                   "p90_s": percentile(list(lat.values()), 90),
                   "first_third_p50_s": percentile(first, 50),
                   "last_third_p50_s": percentile(last, 50),
                   "completed_over_due": kept,
                   "batch_size_mean": sum(batches) / max(1, len(batches)),
                   "lateness_max_s": max(s.lateness_s),
                   "compiles_in_window": s.compiles_in_window}
            row["holds"] = not s.failed and kept >= KEEPS_UP
            holds = holds and row["holds"]
            print(json.dumps(row), flush=True)
        if holds:
            knee = rate
    print(json.dumps({"knee_rate_per_s": knee,
                      "cell_rate_per_s": None if knee is None
                      else 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
