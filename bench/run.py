#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration, traffic mix, output check and per-layer metric readers
are files under ``bench/`` found by name.  One process, no children: it
makes the weights on the chip from the seed, serves the cell's traffic
through the farm for ``--seconds`` after warming every shape, checks a
sample of what was served against the float32 reference, and prints one
JSON line last on stdout.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace and
the farm's events.  Without a TPU, or with fewer chips than the cell
asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench.lib import farm, flops, reference, spec  # noqa: E402
from bench.lib.farm import log  # noqa: E402
from bench.lib.peaks import peaks  # noqa: E402
from bench.lib.stats import percentile  # noqa: E402

ROWS_PER_REFERENCE_CHUNK_BYTES = 1.5e9  # attention scores of one chunk


class RunView:
    """What a per-layer metric reader gets to read."""

    def __init__(self, cell, served, task_cost, device_peaks):
        self.cell, self.served = cell, served
        self.trace = served.trace
        self.task_cost, self.peaks = task_cost, device_peaks

    def generate_seconds(self) -> float:
        """Mean device time of one execution of a program named
        ``*generate*`` in the traced window: one task, in the gen mixes
        (``max_batch`` 1)."""
        times = self.trace.module_times("generate")
        if not times:
            raise ValueError("no execution of a program named *generate* "
                             "in the device trace's window")
        return sum(times) / len(times)

    def events_in_window(self) -> list:
        lo, hi = self.served.t_open, self.served.t_close
        return [ev for ev in self.served.events if lo <= ev[0] <= hi]


def read_metric(name: str, view: RunView):
    path = os.path.join(ROOT, "bench", "metrics", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(view)


def sample_rows(served, cell, seed: int) -> tuple[list, list]:
    """The (task, row) pairs checked: ``check_tasks_per_service`` tasks
    of every service, drawn from the seed, and ``check_rows_per_task``
    rows of each, evenly spaced from a random first one (so both halves
    of a task's batch are read)."""
    t = cell.traffic
    rng = np.random.default_rng([seed, 1])
    by_service: dict = {}
    for i in sorted(served.served):
        by_service.setdefault(served.service_of.get(i), []).append(i)
    picks = []
    for sid in sorted(by_service, key=str):
        tasks = by_service[sid]
        k = min(t.check_tasks_per_service, len(tasks))
        for i in rng.choice(tasks, size=k, replace=False):
            first = int(rng.integers(t.prompts_per_task))
            step = t.prompts_per_task // t.check_rows_per_task
            picks += [(int(i), (first + j * step) % t.prompts_per_task)
                      for j in range(t.check_rows_per_task)]
    return picks, sorted(by_service, key=str)


def check(served, cell, seed: int, *, quantize: bool = False) -> dict:
    """The numbers compared, each with its limit (``bench/checks``)."""
    picks, serving = sample_rows(served, cell, seed)
    idle = len(served.tasks_per_service) - len(
        [s for s in serving if s is not None])
    out = {}
    if picks:
        m, t = cell.model, cell.traffic
        prompts = np.stack([served.prompts[i][r] for i, r in picks])
        tokens = np.stack([served.served[i][r] for i, r in picks])
        T = t.prompt_len + t.new_tokens
        per_row = 4 * m.num_attention_heads * T * T
        chunk = max(1, int(ROWS_PER_REFERENCE_CHUNK_BYTES // per_row))
        t0 = time.monotonic()
        gap = reference.served_gap(seed, m, prompts, tokens,
                                   quantize=quantize, chunk=chunk)
        log(f"reference: {len(picks)} rows, {gap['tokens']} served tokens "
            f"in {time.monotonic() - t0:.3f} s")
        out["max_logit_gap"] = {"value": gap["max_gap"],
                                "limit": cell.check["max_logit_gap"]}
        if quantize:
            out["control_gap"] = gap["control_gap"]
    else:  # nothing was served: nothing to compare, not correct
        out["max_logit_gap"] = {"value": None,
                                "limit": cell.check["max_logit_gap"]}
    out["unfinished"] = {"value": len(served.failed), "limit": 0}
    out["idle_services"] = {"value": idle, "limit": 0}
    return out


def name_gap(served, trace, start_ns: int, length_ns: int) -> str:
    """An idle gap, named by the farm events around it on the host."""
    t0 = served.trace_span[0] + (start_ns - trace.window[0]) / 1e9
    t1 = t0 + length_ns / 1e9
    before = [ev[1] for ev in served.events if ev[0] <= t0]
    after = [ev[1] for ev in served.events if ev[0] >= t1]
    return (f"after {before[-1] if before else 'start'}, "
            f"before {after[0] if after else 'end'}")


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float) -> dict:
    """One run of ``cell``: the result line's fields, without the
    device's description."""
    trace_dir = None
    if trace:
        trace_dir = os.path.join(TRACE_DIR, cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    served, t_open = farm.serve(cell, seed, seconds, devices,
                                trace_dir=trace_dir)
    setup_s = t_open - t_start
    jax.clear_caches()
    gc.collect()
    t = cell.traffic
    log(f"window {served.window_s:.6f} s, {len(served.in_window)} tasks "
        f"counted, tasks per service {served.tasks_per_service}")
    log(f"compiles in window {served.compiles_in_window}, service "
        f"compile-cache misses in window {served.cache_misses_in_window}")
    log(f"memory_peak_bytes {served.memory_peak_bytes}")
    busy = [ev[0] - ev[4] for ev in served.events
            if ev[1] == "drain" and served.t_open <= ev[0] <= served.t_close]
    if busy:
        log(f"service time per batch: mean {np.mean(busy):.6f} s, p90 "
            f"{percentile(busy, 90):.6f} s, max {max(busy):.6f} s over "
            f"{len(busy)} batches")
    if served.lateness_s:
        log(f"open-loop lateness: max {max(served.lateness_s):.6f} s, "
            f"p99 {percentile(served.lateness_s, 99):.6f} s over "
            f"{len(served.lateness_s)} requests")
    res = {"attempted": len(set(served.in_window) | served.failed),
           "failed": len(served.failed), "metrics": {}}
    bench = spec.benchmark()
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    if not trace:
        values = {"setup_s": setup_s}
        if t.loop == "closed":
            values["gen_tokens_per_s"] = (len(served.in_window)
                                          * t.tokens_per_task
                                          / served.window_s)
        else:
            lat = []
            for i in served.in_window:
                end = served.done.get(i, served.t_close + farm.DRAIN_S)
                lat.append(end - served.due[i])
            values["task_latency_p50_s"] = percentile(lat, 50)
            values["task_latency_p90_s"] = percentile(lat, 90)
        for name in spec.end_to_end_names(cell.name):
            res["metrics"][name] = {"value": values[name],
                                    "unit": units[name]}
    else:
        kind = devices[0].device_kind
        p = peaks(kind)
        cost = flops.task(cell.model, t.prompts_per_task, t.prompt_len,
                          t.new_tokens, p["flops_bf16"], p["hbm_bytes_per_s"])
        view = RunView(cell, served, cost, p)
        for m in spec.per_layer_names(cell.name):
            v = read_metric(m["name"], view)
            if v is not None:
                res["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        tr = served.trace
        for d in tr.devices:
            log(f"{d.name} programs in the window (name, start s, "
                f"seconds): " + "; ".join(
                    f"{n.split('(')[0]} {(s0 - tr.window[0]) / 1e9:.4f} "
                    f"{dur / 1e9:.4f}" for n, s0, dur in d.modules[:40]))
        res["trace"] = {"busy_s": tr.busy_s, "window_s": tr.window_s}
        res["breakdown"] = {
            "device_ops": tr.top_ops(10),
            "idle_gaps": tr.top_gaps(10, lambda s, n: name_gap(served, tr,
                                                               s, n))}
    res["memory_peak_bytes"] = served.memory_peak_bytes
    res["check"] = check(served, cell, seed)
    res["correct"] = all(v["value"] is not None and v["value"] <= v["limit"]
                         for v in res["check"].values()
                         if isinstance(v, dict))
    return res


def configure_cache() -> None:
    """One fixed compile cache inside the checkout, for the program too."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def chips_or_none(n: int):
    """The first ``n`` TPU devices, or None (with the reason on stderr)."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX's devices are {devices[0].platform} "
            f"({len(devices)}); this benchmark runs only on TPU chips")
        return None
    if len(devices) < n:
        log(f"the cell needs {n} chips, JAX sees {len(devices)}")
        return None
    return devices[:n]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    configure_cache()
    cell = spec.cell(args.workload)
    devices = chips_or_none(cell.chips)
    if devices is None:
        return 2
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   T_START)
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices),
           "memory_peak_bytes": res.pop("memory_peak_bytes")}
    dev.update(res.pop("trace", {}))
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": res["metrics"],
            "device": dev}
    if "breakdown" in res:
        line["breakdown"] = res["breakdown"]
    line["check"] = res["check"]
    for name, v in res["check"].items():
        log(f"check {name} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
