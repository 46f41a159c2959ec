"""The system under test, set up and driven for one run of a cell.

Everything here goes through the program's normal path:
``make_generate_program`` (``repro.runtime.serve_loop``) served by
in-process ``Service``s (``services_per_chip`` on each chip) and fed
through ``FarmExecutor`` (``repro.core``), whose pull scheduler leases
tasks to the services.  The benchmark gives the program only its weights (made
here from the seed) and the generated requests; it reads back the
results, the farm's ``repro.obs`` events and the services' compile-cache
counters.

Traffic comes from one general generator that reads the mix's parameters:

- ``closed``: every service keeps ``DEPTH_PER_SERVICE`` tasks queued; a
  task completes, the next is submitted.  The window opens on the first
  completion (the last of any seen together with it) and closes on the
  first completion at least ``seconds`` later; it counts the tasks
  completed after its opening, so it holds whole tasks only.
- ``open``: Poisson arrivals at ``rate_per_s``, on one fixed schedule
  for every seed (quantiles of the exponential law, scaled to fill the
  window exactly, in a fixed shuffled order).  The window holds the
  requests due in it; each is timed from its due time to its result on
  the host, and waited for up to ``DRAIN_S`` past the close.  For
  ``LEAD_S`` before the window, requests arrive evenly at the same rate,
  so that the window opens on a farm under load.

With a trace, the profiler covers the last ``trace_seconds`` of the
window (all of it when 0) inside a host span named ``window``; it stops
after the window, so its write-out falls outside.
"""

from __future__ import annotations

import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from functools import partial

import jax
import numpy as np

from .spec import Cell
from . import trace as trace_mod
from . import weights as W

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


WAIT_S = 120.0  # longest wait for one completion
TOGETHER_S = 0.05  # completions this close are seen together
DEPTH_PER_SERVICE = 2  # closed loop: tasks outstanding per service
LEAD_S = 2.0  # open loop: load before the window
DRAIN_S = 60.0  # open loop: wait for due requests after the close


def log(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts compilations and compile-cache loads while ``on``."""

    def __init__(self):
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_):
        if self.on and name in COMPILE_EVENTS:
            self.count += 1


@dataclass
class Served:
    """What one run of the farm left on the host."""

    t_open: float
    t_close: float
    window_s: float
    prompts: dict  # task index -> (B, P) int32
    served: dict = field(default_factory=dict)  # index -> (B, N) int32
    due: dict = field(default_factory=dict)  # index -> due time (open)
    done: dict = field(default_factory=dict)  # index -> host time
    failed: set = field(default_factory=set)
    service_of: dict = field(default_factory=dict)  # index -> service id
    # closed loop: tasks completed in the window; open: requests due in it
    in_window: list = field(default_factory=list)
    events: list = field(default_factory=list)  # repro.obs events
    trace: object = None  # trace_mod.Trace
    trace_span: tuple = (0.0, 0.0)  # host times of the traced part
    compiles_in_window: int = 0
    cache_misses_in_window: int = 0
    lateness_s: list = field(default_factory=list)
    memory_peak_bytes: int = 0
    tasks_per_service: dict = field(default_factory=dict)


def peak_bytes(device) -> int:
    """The device's peak memory: buffers in use plus the memory reserved
    for the loaded programs' temporaries, where the generate program
    keeps its cache (``peak_bytes_in_use`` alone leaves those out).  0
    where the backend keeps no statistics (the CPU)."""
    stats = device.memory_stats() or {}
    if stats:
        log(f"memory_stats {device}: {stats}")
    reserved = stats.get("peak_bytes_reserved", stats.get("bytes_reserved",
                                                          0))
    return int(stats.get("peak_bytes_in_use", 0)) + int(reserved)


def program_config(cell: Cell):
    """The program's own ModelConfig for the cell, held against the
    configuration file: a size that differs is an error."""
    import repro.configs as cfgs

    cfg = cfgs.get(cell.config["arch"])
    m = cell.model
    pairs = {"d_model": m.hidden_size, "d_ff": m.intermediate_size,
             "n_layers": m.num_hidden_layers,
             "n_heads": m.num_attention_heads,
             "n_kv_heads": m.num_key_value_heads, "head_dim": m.head_dim,
             "vocab_size": m.vocab_size, "rope_theta": m.rope_theta,
             "qk_norm": m.qk_norm, "param_dtype": m.dtype,
             "compute_dtype": m.dtype, "tie_embeddings": True}
    bad = {k: (getattr(cfg, k), v) for k, v in pairs.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"{cell.config['arch']}: the program's config "
                         f"differs from {cell.config_name}.json: {bad}")
    return cfg


def prompts_for(seed: int, index: int, cell: Cell) -> np.ndarray:
    """Task ``index``'s prompts, from the seed alone."""
    t, m = cell.traffic, cell.model
    rng = np.random.default_rng([seed, index])
    return rng.integers(0, m.vocab_size, (t.prompts_per_task, t.prompt_len),
                        dtype=np.int32)


def arrival_gaps(rate: float, seconds: float) -> np.ndarray:
    """Gaps between the window's arrivals: quantiles of Exp(rate), scaled
    to sum to ``seconds``, in one fixed shuffled order.  Every seed gets
    the same schedule (the seed draws prompts and weights): near the knee
    the order of the gaps alone moved p90 latency by 2.6x between seeds."""
    n = max(1, round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= seconds / gaps.sum()
    return np.random.default_rng(0).permutation(gaps)


def build(cell: Cell, seed: int, devices):
    """(program, services, lookup): weights on the device from the seed,
    the generate program, and the cell's services, registered."""
    from repro.core import LookupService, Service
    from repro.models import build as build_model
    from repro.runtime.serve_loop import ServeConfig, make_generate_program

    cfg = program_config(cell)
    api = build_model(cfg)
    W.check_layout(jax.eval_shape(api.init, jax.random.PRNGKey(0)),
                   cell.model)
    params = W.make_on_device(seed, cell.model, devices[0])
    t = cell.traffic
    sc = ServeConfig(max_new_tokens=t.new_tokens, prompt_len=t.prompt_len,
                     batch_per_task=t.prompts_per_task)
    program = make_generate_program(api, sc, params)
    lookup = LookupService()
    services = [Service(lookup, devices=[d]) for _ in
                range(t.services_per_chip) for d in devices]
    return program, services, lookup


def _batched(t) -> bool:
    return t.farm.get("max_batch", 1) > 1 or t.farm.get("max_inflight",
                                                         1) > 1


def warm(program, services, cell: Cell, seed: int) -> None:
    """Run every shape the cell's traffic can use on every service, so
    that the window compiles nothing and every service's compile cache
    holds its entries.  Services on different chips warm at once."""
    from repro.core.batching import bucket_size, pad_stacked, stack_payloads

    t = cell.traffic
    payload = {"tokens": prompts_for(seed, 2**31 - 1, cell)}
    max_batch = t.farm.get("max_batch", 1)
    sizes = sorted({bucket_size(n, max_batch)
                    for n in range(1, max_batch + 1)})

    def one(svc):
        if not _batched(t):
            jax.block_until_ready(svc.execute(program, payload))
            return
        for m in sizes:
            svc.execute_batch(program, [payload] * m, pad_to=m)

    by_device: dict = {}
    for svc in services:
        by_device.setdefault(svc.devices[0].id, []).append(svc)
    with ThreadPoolExecutor(len(by_device)) as pool:
        for rank in range(t.services_per_chip):
            for f in [pool.submit(one, svcs[rank])
                      for svcs in by_device.values()]:
                f.result()
    if _batched(t):  # the host-side stacking of every lease size
        for n in range(1, max_batch + 1):
            pad_stacked(stack_payloads([payload] * n), n,
                        bucket_size(n, max_batch))


class _Driver:
    """Feeds the farm and records, for every task, when it was due and
    when it was done."""

    def __init__(self, ex, cell: Cell, seed: int, served: Served):
        self.ex, self.cell, self.seed, self.s = ex, cell, seed, served
        self.lock = threading.Lock()
        self.next_index = 0
        self.results: dict = {}
        self.stop_submitting = False
        self.all_done = threading.Condition(self.lock)

    def submit(self, due: float | None = None) -> None:
        with self.lock:
            if self.stop_submitting:
                return
            i = self.next_index
            self.next_index += 1
            prompts = prompts_for(self.seed, i, self.cell)
            self.s.prompts[i] = prompts
            with jax.profiler.TraceAnnotation("submit"):
                now = time.monotonic()
                fut = self.ex.submit({"tokens": prompts})
            if due is not None:
                self.s.due[i] = due
                self.s.lateness_s.append(now - due)
        fut.add_done_callback(partial(self._done, i))

    def _done(self, i, fut) -> None:
        now = time.monotonic()
        if fut.cancelled():
            return
        with self.lock:
            if fut.exception() is not None:
                self.s.failed.add(i)
            else:
                self.results[i] = fut.result()
            self.s.done[i] = now
            more = self.on_done(now)
            self.all_done.notify_all()
        if more:
            self.submit()

    def on_done(self, now) -> bool:
        """Called under ``self.lock``; True: submit the next task."""
        return False

    def wait_done(self, indices, deadline: float) -> None:
        with self.lock:
            while not all(i in self.s.done for i in indices):
                left = deadline - time.monotonic()
                if left <= 0:
                    return
                self.all_done.wait(min(left, 1.0))


class _Closed(_Driver):
    def __init__(self, *a, seconds: float):
        super().__init__(*a)
        self.seconds = seconds
        self.opened = threading.Event()
        self.closed = threading.Event()

    def on_done(self, now) -> bool:
        s = self.s
        if s.t_open is None or now - s.t_open <= TOGETHER_S:
            # completions that the host sees together open the window
            # together: the earlier one may have ended on the chip a
            # whole task before, and none of them is counted
            s.t_open = now
            self.opened.set()
        elif s.t_close is None and now >= s.t_open + self.seconds:
            s.t_close = now
            self.stop_submitting = True
            self.closed.set()
        return s.t_close is None


def _traced(log_dir, start_at: float, stop_at: float, served: Served):
    """Sleep to ``start_at``, trace until ``stop_at`` inside a host span
    named ``window``."""
    time.sleep(max(0.0, start_at - time.monotonic()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
        time.sleep(max(0.0, stop_at - time.monotonic()))
        t1 = time.monotonic()
    jax.profiler.stop_trace()
    served.trace_span = (t0, t1)


def serve(cell: Cell, seed: int, seconds: float, devices, *,
          trace_dir: str | None = None) -> tuple[Served, float]:
    """Build, warm and drive the farm for one run.  Returns what it left
    on the host and the host time at which the window opened.  Every
    reference to the program's device state is gone on return."""
    t0 = time.monotonic()
    program, services, lookup = build(cell, seed, devices)
    t1 = time.monotonic()
    warm(program, services, cell, seed)
    for svc in services:
        svc.start()
    log(f"set-up: weights and program {t1 - t0:.3f} s, warm-up "
        f"{time.monotonic() - t1:.3f} s")
    served = drive(cell, seed, seconds, program, services, lookup,
                   trace_dir=trace_dir)
    for svc in services:
        svc.kill()
    return served, served.t_open


_COUNTER: CompileCounter | None = None


def drive(cell: Cell, seed: int, seconds: float, program, services, lookup,
          *, trace_dir: str | None = None,
          rate_per_s: float | None = None) -> Served:
    """One window of the cell's traffic through a new ``FarmExecutor`` over
    ``services`` (warm and registered).  ``rate_per_s`` overrides the
    open loop's rate (the knee sweep)."""
    from repro.core import FarmExecutor
    from repro.obs import Observability

    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    counter = _COUNTER
    counter.count = 0
    t = cell.traffic
    rate = rate_per_s or t.rate_per_s
    devices = sorted({s.devices[0] for s in services}, key=lambda d: d.id)
    obs = Observability()
    ex = FarmExecutor(program, lookup=lookup, obs=obs, **t.farm)
    served = Served(t_open=None, t_close=None, window_s=0.0, prompts={})
    misses0 = sum(s.cache_misses for s in services)
    tasks0 = {s.service_id: s.tasks_executed for s in services}
    trace_s = t.trace_seconds or seconds
    if t.loop == "closed":
        drv = _Closed(ex, cell, seed, served, seconds=seconds)
        counter.on = True
        for _ in range(DEPTH_PER_SERVICE * len(services)):
            drv.submit()
        if not drv.opened.wait(WAIT_S):
            raise TimeoutError(f"no task completed in {WAIT_S} s")
        if trace_dir:
            end = served.t_open + seconds
            _traced(trace_dir, end - trace_s, end, served)
        if not drv.closed.wait(seconds + WAIT_S):
            raise TimeoutError(f"the window did not close {WAIT_S} s "
                               f"after its end")
    elif t.loop == "open":
        drv = _Driver(ex, cell, seed, served)
        start = time.monotonic() + 0.05
        lead = np.full(round(rate * LEAD_S), 1 / rate)
        gaps = arrival_gaps(rate, seconds)
        dues = start + np.concatenate([[0.0], np.cumsum(np.concatenate(
            [lead, gaps]))[:-1]])
        w0 = start + lead.sum()
        served.t_open, served.t_close = w0, w0 + seconds
        window = list(range(len(lead), len(dues)))

        def generate():
            for due in dues:
                time.sleep(max(0.0, due - time.monotonic()))
                drv.submit(due=float(due))

        gen = threading.Thread(target=generate, name="bench-open-loop")
        counter.on = True
        gen.start()
        if trace_dir:
            _traced(trace_dir, served.t_close - trace_s, served.t_close,
                    served)
        gen.join()
        with jax.profiler.TraceAnnotation("wait"):
            drv.wait_done(window, served.t_close + DRAIN_S)
        for i in window:
            if i not in served.done:
                served.failed.add(i)
        served.in_window = window
    else:
        raise ValueError(f"unknown loop {t.loop!r}")
    counter.on = False
    served.compiles_in_window = counter.count
    served.cache_misses_in_window = (sum(s.cache_misses for s in services)
                                     - misses0)
    served.memory_peak_bytes = max(peak_bytes(d) for d in devices)
    with drv.lock:
        drv.stop_submitting = True
    ex.shutdown()
    served.window_s = served.t_close - served.t_open
    if t.loop == "closed":
        served.in_window = sorted(  # what failed is in served.failed
            i for i, d in served.done.items()
            if served.t_open < d <= served.t_close
            and i not in served.failed)
    with jax.profiler.TraceAnnotation("fetch"):
        for i, r in drv.results.items():
            served.served[i] = np.asarray(r["generated"])
    served.events = obs.events()
    for ev in served.events:
        if ev[1] == "complete":
            for tid, _ in ev[3]:
                served.service_of[tid] = ev[2]
    served.tasks_per_service = {s.service_id: s.tasks_executed
                                - tasks0[s.service_id] for s in services}
    if trace_dir:
        served.trace = trace_mod.reduce(trace_dir)
    return served
