"""Benchmark harness: one module per paper claim/table.

Prints ``name,us_per_call,derived`` CSV.  The roofline table (per-arch
TPU-target analysis) is produced separately by ``repro.launch.roofline``
from the dry-run artifacts and summarized here if present.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def compare_batched(args) -> None:
    """Batched-vs-unbatched dispatch comparison (verified against the
    sequential ``interpret()`` reference)."""
    from benchmarks import farm_scalability

    print("name,us_per_call,derived")
    for name, us, derived in farm_scalability.bench_batched(
            args.services, max_batch=args.max_batch,
            max_inflight=args.max_inflight, transport=args.transport):
        print(f"{name},{us:.1f},{derived}")


def run_engine_overhead(args) -> None:
    """The engine-unification gate: the single-tenant adapters vs the
    raw one-job FarmScheduler path; writes ``BENCH_engine.json`` and
    fails if BasicClient's overhead exceeds the floor."""
    from benchmarks import engine_overhead as mod

    mod.main(["--out", args.engine_out])


def run_scale(args) -> None:
    """The NoW-scale scheduler gate: per-dispatch overhead curve, trace
    determinism, incremental-vs-full arbiter equivalence, churn and
    join-burst coalescing; writes ``BENCH_scale.json``.  CI runs a
    reduced configuration (200 services / 100k tasks); the full 1,000 /
    1M figures are produced locally with ``benchmarks/scale.py``."""
    from benchmarks import scale as mod

    mod.main(["--services", str(args.scale_services),
              "--tasks", str(args.scale_tasks),
              "--out", args.scale_out])


def run_obs(args) -> None:
    """The telemetry-spine gate: recorder overhead vs tracing-disabled
    on the batched inproc path (≤3%), plus the heterogeneous-NoW Chrome
    trace artifact; writes ``BENCH_obs.json`` and the trace JSON.  CI
    runs a reduced configuration; the committed figures come from the
    module's defaults (``benchmarks/observability.py``)."""
    from benchmarks import observability as mod

    mod.main(["--tasks", str(args.obs_tasks),
              "--repeats", str(args.obs_repeats),
              "--out", args.obs_out,
              "--trace-out", args.obs_trace_out])


def run_wire(args) -> None:
    """The transport gate: µs/task and socket payload bytes for inproc vs
    shm vs proc vs tcp on array payloads; writes ``BENCH_wire.json`` and
    fails unless shm beats proc on both bytes and the speedup floor.  CI
    runs a reduced configuration; the committed figures come from the
    module's defaults (``benchmarks/wire.py``)."""
    from benchmarks import wire as mod

    mod.main(["--tasks", str(args.wire_tasks),
              "--repeats", str(args.wire_repeats),
              "--out", args.wire_out])


def run_autotune(args) -> None:
    """The autotuning gate: real farm sweep + serial re-time (tuned must
    beat the hand-picked default by the speedup floor), same-seed sim://
    determinism, and cache-hit dispatch overhead ≤3% of kernel time;
    writes ``BENCH_autotune.json``.  CI runs a reduced sweep (mamba
    only); the committed figures come from the module's defaults
    (``benchmarks/autotune.py``)."""
    from benchmarks import autotune as mod

    mod.main(["--kernels", args.autotune_kernels,
              "--reps", str(args.autotune_reps),
              "--out", args.autotune_out])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--compare-batched", action="store_true",
                    help="only run the batched-vs-per-task dispatch "
                         "comparison (farm_scalability --batched)")
    ap.add_argument("--engine-overhead", action="store_true",
                    help="only run the unified-engine adapter-overhead "
                         "gate (BasicClient/FarmExecutor vs raw "
                         "FarmScheduler; writes BENCH_engine.json)")
    ap.add_argument("--engine-out", default="BENCH_engine.json")
    ap.add_argument("--scale", action="store_true",
                    help="only run the NoW-scale scheduler stress gate "
                         "(overhead curve + determinism + churn; writes "
                         "BENCH_scale.json)")
    ap.add_argument("--scale-services", type=int, default=200)
    ap.add_argument("--scale-tasks", type=int, default=100_000)
    ap.add_argument("--scale-out", default="BENCH_scale.json")
    ap.add_argument("--obs", action="store_true",
                    help="only run the telemetry-spine gate (recorder "
                         "overhead vs tracing-disabled + the hetero-NoW "
                         "Perfetto trace; writes BENCH_obs.json and "
                         "BENCH_obs_trace.json)")
    ap.add_argument("--obs-tasks", type=int, default=10_000)
    ap.add_argument("--obs-repeats", type=int, default=2)
    ap.add_argument("--obs-out", default="BENCH_obs.json")
    ap.add_argument("--obs-trace-out", default="BENCH_obs_trace.json")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="with the full table: also export a Chrome "
                         "trace-event JSON of the heterogeneous-NoW "
                         "scenario to PATH (load in Perfetto)")
    ap.add_argument("--wire", action="store_true",
                    help="only run the transport wire gate (inproc/shm/"
                         "proc/tcp µs-per-task + socket payload bytes; "
                         "writes BENCH_wire.json)")
    ap.add_argument("--wire-tasks", type=int, default=100)
    ap.add_argument("--wire-repeats", type=int, default=2)
    ap.add_argument("--wire-out", default="BENCH_wire.json")
    ap.add_argument("--autotune", action="store_true",
                    help="only run the kernel-autotuning gate (farm "
                         "sweep speedup + sim:// determinism + dispatch "
                         "overhead; writes BENCH_autotune.json)")
    ap.add_argument("--autotune-kernels", default="xla_flash,mamba",
                    help="comma-separated kernels for the real sweep")
    ap.add_argument("--autotune-reps", type=int, default=3)
    ap.add_argument("--autotune-out", default="BENCH_autotune.json")
    ap.add_argument("--services", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-inflight", type=int, default=2)
    ap.add_argument("--transport", choices=("inproc", "proc"),
                    default="inproc",
                    help="farm backend for --compare-batched (proc = one "
                         "OS process per service)")
    args = ap.parse_args()
    if args.compare_batched:
        compare_batched(args)
        return
    if args.engine_overhead:
        run_engine_overhead(args)
        return
    if args.scale:
        run_scale(args)
        return
    if args.obs:
        run_obs(args)
        return
    if args.wire:
        run_wire(args)
        return
    if args.autotune:
        run_autotune(args)
        return

    from benchmarks import (autotune, elasticity,
                            engine_overhead, farm_scalability,
                            fault_tolerance, heterogeneous_now, kernels,
                            load_balance, multi_tenant, normal_form,
                            observability, scale, wire)

    print("name,us_per_call,derived")
    for mod in (farm_scalability, load_balance, fault_tolerance, normal_form,
                elasticity, heterogeneous_now, multi_tenant, engine_overhead,
                scale, wire, observability, autotune, kernels):
        for name, us, derived in mod.bench():
            print(f"{name},{us:.1f},{derived}")

    if args.trace:
        from benchmarks.observability import export_hetero_trace

        info = export_hetero_trace(args.trace)
        print(f"trace/{args.trace},{info['events']},"
              f"tracks={info['service_tracks']} "
              f"types={len(info['event_types'])}")

    # roofline summary (if the dry-run grid has been produced)
    dr = os.path.join(os.path.dirname(__file__), "results", "dryrun")
    files = glob.glob(os.path.join(dr, "*.json"))
    if files:
        from repro.launch.roofline import analyze_cell

        ok = skipped = err = 0
        fits = 0
        for f in files:
            rec = json.load(open(f))
            if rec.get("status") == "ok":
                ok += 1
                row = analyze_cell(rec)
                if row and row["fits_hbm"]:
                    fits += 1
            elif rec.get("status") == "skipped":
                skipped += 1
            else:
                err += 1
        print(f"dryrun/cells_ok,{ok},skipped={skipped} errors={err} "
              f"fits_hbm={fits}/{ok}")


if __name__ == "__main__":
    main()
