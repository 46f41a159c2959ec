"""A JJPF service: the distributed slave, re-homed to a JAX device group.

Paper Algorithm 2:
    1 network discovery of the LookupService;
    2 while not terminated do
    3    register into lookup;
    4    wait for requests;
    5    unregister from the lookup;   (serve exactly one client)
    6 end

A service owns a set of JAX devices (here: CPU/host devices standing in for
a pod slice) and executes *compiled* programs on task payloads.  Fault
injection (``kill``, ``fail_after``) and a speed factor (heterogeneous
clusters) are built in for the paper's fault-tolerance and load-balancing
experiments.

Since the transport refactor this class is the *execution engine* only:
clients never hold it directly, they hold a ``ServiceHandle`` resolved
from the registered endpoint address.  In-process, the handle delegates
straight to this object (``inproc://`` — zero-copy, the default); in a
NoW deployment the same object runs inside a spawned worker process
behind ``repro.core.transport.proc.ServiceWorker``, in which case it is
constructed with ``lookup=None`` (registration is the launcher's job).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable

import jax

from .batching import (pad_stacked, payload_signature, stack_payloads,
                       unstack_results)
from .discovery import LookupService, ServiceDescriptor, new_service_id
from .errors import ServiceFailure  # noqa: F401  (re-exported: old import path)
from .skeletons import Program
from .transport.inproc import register_local

_NO_SPAN = contextlib.nullcontext()


def _span(obs, kind: str, *fields):
    """``obs.span(kind, *fields)``, or a shared no-op without ``obs``:
    tracing off constructs nothing."""
    return _NO_SPAN if obs is None else obs.span(kind, *fields)


class Service:
    def __init__(self, lookup: LookupService | None, *, devices=None,
                 service_id: str | None = None, speed_factor: float = 1.0,
                 capabilities: dict | None = None,
                 task_delay_s: float = 0.0,
                 advertise: str | None = None):
        self.lookup = lookup
        # Registered endpoint address override: a worker serving sockets
        # advertises its network address ("tcp://host:port") instead of
        # the in-process token, so recruit/release re-registration through
        # a RemoteLookup lands the *reachable* endpoint.
        self._advertise = advertise
        self.devices = list(devices) if devices else [jax.devices()[0]]
        self.service_id = service_id or new_service_id()
        self.speed_factor = speed_factor
        self.task_delay_s = task_delay_s
        caps = {"n_devices": len(self.devices),
                "speed_factor": speed_factor}
        caps.update(capabilities or {})
        self.capabilities = caps

        # endpoint token is per-instance: stale descriptors must never
        # resolve to a newer service that reused the same service_id
        self._endpoint_token = register_local(self)

        self._lock = threading.Lock()
        self._alive = True
        self._recruited_by: str | None = None
        self._fail_after: int | None = None
        self._tasks_executed = 0
        # Compile cache keyed by (program uid+name, payload signature,
        # batch size).  NOT by id(program): CPython reuses addresses after
        # GC, which can silently serve a dead program's executable; and an
        # id key cannot distinguish payload shapes, so cache stats were
        # meaningless.  batch_size is None for the per-task path.
        self._compiled: dict[tuple, Callable] = {}
        self._prepared: dict[int, Callable] = {}  # warm per-program wrappers
        self.cache_hits = 0
        self.cache_misses = 0
        self.last_heartbeat = time.monotonic()
        # the recruiting client's repro.obs bundle, set by its in-process
        # handle while recruited; None: no spans
        self.obs = None

    # ---------------- lifecycle (Algorithm 2) ------------------------ #
    def start(self) -> None:
        """Register into the lookup and wait for requests."""
        if self.lookup is not None:
            self.lookup.register(self.descriptor())

    def descriptor(self) -> ServiceDescriptor:
        """Endpoint is an *address*, resolved through the transport
        registry at recruitment — never the live object.  ``keepalive``
        pins this service while it sits in a lookup (the endpoint table is
        weak; see ``transport/inproc.py``); an advertised network address
        needs no pinning (the worker process itself is the lifetime)."""
        if self._advertise is not None:
            return ServiceDescriptor(self.service_id, self._advertise,
                                     dict(self.capabilities))
        return ServiceDescriptor(self.service_id,
                                 f"inproc://{self._endpoint_token}",
                                 dict(self.capabilities),
                                 keepalive=self)

    def recruit(self, client_id: str) -> bool:
        """A client claims this service; it unregisters (single-client)."""
        with self._lock:
            if not self._alive or self._recruited_by is not None:
                return False
            self._recruited_by = client_id
        if self.lookup is not None:
            self.lookup.unregister(self.service_id)
        return True

    def release(self) -> None:
        """Client done: re-register for the next one (the while-loop)."""
        with self._lock:
            self._recruited_by = None
            if not self._alive:
                return
        if self.lookup is not None:
            self.lookup.register(self.descriptor())

    # ---------------- execution -------------------------------------- #
    def prepare(self, program: Program) -> None:
        """Warm the per-program jit wrapper (shape-agnostic; the shape-keyed
        cache entries are created lazily at first execution)."""
        with self._lock:
            if program.uid not in self._prepared:
                self._prepared[program.uid] = program.prepare(self.devices)

    def _get_compiled(self, program: Program, payload,
                      batch_size: int | None) -> Callable:
        """Shape-keyed compile-cache lookup.

        ``batch_size=None`` is the per-task path; an integer selects the
        vmap wrapper specialized to that batch size (different batch sizes
        are different XLA shapes, so each is its own executable).  Non-jit
        programs are shape-agnostic host callables — one cache entry per
        path, not one per (signature, size)."""
        if not program._jit:
            key = (program.uid, program.name, None,
                   None if batch_size is None else "host_loop")
        else:
            key = (program.uid, program.name, payload_signature(payload),
                   batch_size)
        with self._lock:
            fn = self._compiled.get(key)
            if fn is not None:
                self.cache_hits += 1
                return fn
            self.cache_misses += 1
        if batch_size is None:
            fn = self._prepared.get(program.uid) or program.prepare(self.devices)
        else:
            fn = program.prepare_batched(self.devices)
        with self._lock:
            if batch_size is None:
                self._prepared.setdefault(program.uid, fn)
            return self._compiled.setdefault(key, fn)

    def _check_dispatchable(self) -> None:
        """Locked check of liveness + fault injection at batch start (the
        paper's natural descheduling point is the task start)."""
        if not self._alive:
            raise ServiceFailure(f"{self.service_id} is dead")
        if (self._fail_after is not None
                and self._tasks_executed >= self._fail_after):
            self._alive = False
            raise ServiceFailure(f"{self.service_id} failed (injected)")

    def _finish_tasks(self, n: int) -> None:
        with self._lock:
            if not self._alive:  # killed mid-task
                raise ServiceFailure(f"{self.service_id} died mid-task")
            self._tasks_executed += n
            self.last_heartbeat = time.monotonic()

    def execute(self, program: Program, payload) -> Any:
        """Run one task.  Raises ServiceFailure if the node is dead or its
        fault-injection counter fires.  With ``self.obs`` set, the
        compiled call is a ``launch`` span."""
        with self._lock:
            self._check_dispatchable()
        fn = self._get_compiled(program, payload, None)
        if self.task_delay_s:
            time.sleep(self.task_delay_s)  # network/serialization stand-in
        with _span(self.obs, "launch", self.service_id, 1):
            result = fn(payload)
        result = jax.block_until_ready(result)
        if self.speed_factor != 1.0:
            # heterogeneity simulation: slower nodes take proportionally longer
            time.sleep(max(0.0, (self.speed_factor - 1.0)) * 0.002)
        self._finish_tasks(1)
        return result

    def execute_batch(self, program: Program, payloads: list, *,
                      block: bool = True, pad_to: int | None = None) -> list:
        """Run a batch of shape-compatible tasks as ONE compiled call.

        Payloads are stacked along a new leading axis and computed by the
        ``jax.jit(jax.vmap(fn))`` executable for this (signature, batch
        size).  With ``block=False`` the returned per-task results are
        un-materialized device values — the caller can keep the batch in
        flight (device compute overlapping host scheduling) and
        ``jax.block_until_ready`` them later.

        The dispatch round-trip stand-in (``task_delay_s``) is paid once
        per batch — that is the point of batching — while the
        heterogeneity stand-in (``speed_factor``) scales with the number
        of tasks, like real compute would.

        With ``self.obs`` set, the host work of a jitted batch is timed
        as spans: ``stack`` (stacking and padding), ``launch`` (the
        compiled call) and ``unstack``."""
        n = len(payloads)
        if n == 0:
            return []
        with self._lock:
            self._check_dispatchable()
        if self.task_delay_s:
            time.sleep(self.task_delay_s)  # one round-trip per *batch*
        if not program._jit:
            host_loop = self._get_compiled(program, payloads[0], n)
            results = host_loop(payloads)
        else:
            m = pad_to if pad_to is not None and pad_to > n else n
            fn = self._get_compiled(program, payloads[0], m)
            sid, obs = self.service_id, self.obs
            with _span(obs, "stack", sid, n):
                stacked = pad_stacked(stack_payloads(payloads), n, m)
            with _span(obs, "launch", sid, n):
                out = fn(stacked)
            if block:
                out = jax.block_until_ready(out)
            with _span(obs, "unstack", sid, n):
                results = unstack_results(out, n)  # padding rows dropped
        if self.speed_factor != 1.0:
            time.sleep(max(0.0, (self.speed_factor - 1.0)) * 0.002 * n)
        self._finish_tasks(n)
        return results

    # ---------------- fault injection -------------------------------- #
    def kill(self) -> None:
        with self._lock:
            self._alive = False
        if self.lookup is not None:
            self.lookup.unregister(self.service_id)

    def revive(self) -> None:
        with self._lock:
            self._alive = True
            self._fail_after = None
            self._recruited_by = None
        if self.lookup is not None:
            self.lookup.register(self.descriptor())

    def fail_after(self, n_tasks: int) -> None:
        with self._lock:
            self._fail_after = self._tasks_executed + n_tasks

    @property
    def alive(self) -> bool:
        with self._lock:
            return self._alive

    @property
    def tasks_executed(self) -> int:
        with self._lock:
            return self._tasks_executed

    def heartbeat_age(self) -> float:
        return time.monotonic() - self.last_heartbeat
