"""Skeleton AST: the composition language of JJPF.

The paper: *"Programmers must write their applications as an arbitrary
composition of task farm and pipeline computation patterns."*  A ``Program``
is the JAX analogue of the paper's ``ProcessIf`` (setData / run / getData):
a pure function from task payload to result, plus an optional ``prepare``
step that specializes (jit-compiles) it for a service's devices.
"""

from __future__ import annotations

import functools
import itertools
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax


class Program:
    """ProcessIf analogue.  ``fn`` must be a pure function (pytree -> pytree).

    ``prepare(devices)`` returns a compiled callable for a service; the
    default jit-compiles for the service's first device, with the payload
    committed there.  Set ``jit=False`` for host-side tasks (e.g. I/O
    simulation in tests).

    ``resident`` is state that ships once with the program rather than
    with every task (model weights, in the paper's terms the code a
    service loads at recruit time).  ``fn`` is then ``fn(resident,
    payload)``; each device that prepares the program holds one copy,
    passed to the executable as an argument.  (Arrays a jitted ``fn``
    closes over instead are baked into the executable as constants.)
    """

    _uid_counter = itertools.count()

    def __init__(self, fn: Callable, *, name: str | None = None, jit: bool = True,
                 static_argnames: Sequence[str] = (), resident: Any = None):
        self.fn = fn
        self.name = name or getattr(fn, "__name__", "program")
        # Stable identity for compile caches.  ``id(program)`` is unsafe as a
        # cache key: CPython reuses addresses after GC, so a dead program's
        # compiled artifact could be served for a new one.
        self.uid = next(Program._uid_counter)
        self._jit = jit
        self._static = tuple(static_argnames)
        self.resident = resident
        # jit wrappers and resident copies memoized per device: services on
        # the same device share ONE wrapper (and therefore XLA's tracing/
        # compile cache) and one copy of the resident state.
        self._wrappers: dict[tuple, Callable] = {}
        self._placed: dict[int, Any] = {}
        self._lock = threading.Lock()

    def _device_key(self, devices) -> tuple:
        return tuple(id(d) for d in devices) if devices else ()

    def _resident_on(self, device):
        with self._lock:
            if id(device) not in self._placed:
                self._placed[id(device)] = jax.device_put(self.resident,
                                                          device)
            return self._placed[id(device)]

    def _bind(self, fn: Callable, device) -> Callable:
        """``fn`` as a callable of the payload alone, run on ``device``."""
        if device is None:
            if self.resident is None:
                return fn
            return functools.partial(fn, self.resident)
        resident = None if self.resident is None else self._resident_on(device)

        def run(payload):
            payload = jax.device_put(payload, device)
            return fn(payload) if resident is None else fn(resident, payload)
        return run

    def _wrapper(self, kind: str, devices, make: Callable) -> Callable:
        key = (kind, self._device_key(devices))
        fn = self._wrappers.get(key)
        if fn is None:
            fn = self._bind(make(), devices[0] if devices else None)
            fn = self._wrappers.setdefault(key, fn)
        return fn

    def prepare(self, devices=None) -> Callable:
        if not self._jit:
            return self._bind(self.fn, None)
        return self._wrapper(
            "task", devices,
            lambda: jax.jit(self.fn, static_argnames=self._static))

    def prepare_batched(self, devices=None) -> Callable:
        """Compiled callable over a stacked batch: one XLA program computes
        N tasks (payloads stacked along a new leading axis).  Non-jit
        programs fall back to a host-side loop over the batch."""
        if not self._jit:
            fn = self._bind(self.fn, None)

            def host_loop(payloads):
                return [fn(p) for p in payloads]
            return host_loop
        in_axes = 0 if self.resident is None else (None, 0)
        return self._wrapper(
            "batch", devices,
            lambda: jax.jit(jax.vmap(self.fn, in_axes=in_axes)))

    def __call__(self, task):
        if self.resident is None:
            return self.fn(task)
        return self.fn(self.resident, task)

    def __repr__(self):
        return f"Program({self.name})"


def compose_programs(programs: Sequence[Program], name=None) -> Program:
    """Sequential composition g_n ∘ ... ∘ g_1 as ONE program.

    On TPU this is the payoff of the normal form: the composed stages become
    a single XLA program (cross-stage fusion, no host round-trips between
    stages).  The stages' resident state travels with the composed
    program as one tuple, stage i reading entry i, so it stays an
    argument of the executable and is not baked into it."""
    progs = list(programs)

    def fused(resident, task):
        for p, r in zip(progs, resident):
            task = p.fn(task) if p.resident is None else p.fn(r, task)
        return task

    return Program(fused, name=name or "∘".join(p.name for p in progs),
                   jit=all(p._jit for p in progs),
                   resident=tuple(p.resident for p in progs))


# ----------------------------- AST ----------------------------------- #
@dataclass(frozen=True)
class Skeleton:
    def pretty(self) -> str:
        raise NotImplementedError


@dataclass(frozen=True)
class Seq(Skeleton):
    program: Program

    def pretty(self) -> str:
        return f"seq({self.program.name})"


@dataclass(frozen=True)
class Pipe(Skeleton):
    stages: tuple

    def __init__(self, *stages):
        stages = tuple(s if isinstance(s, Skeleton) else Seq(_as_program(s))
                       for s in stages)
        object.__setattr__(self, "stages", stages)

    def pretty(self) -> str:
        return "pipe(" + ", ".join(s.pretty() for s in self.stages) + ")"


@dataclass(frozen=True)
class Farm(Skeleton):
    worker: Skeleton

    def __init__(self, worker):
        if not isinstance(worker, Skeleton):
            worker = Seq(_as_program(worker))
        object.__setattr__(self, "worker", worker)

    def pretty(self) -> str:
        return f"farm({self.worker.pretty()})"


def _as_program(x) -> Program:
    return x if isinstance(x, Program) else Program(x)


# ------------------- reference (sequential) semantics ----------------- #
def interpret(skel: Skeleton, tasks: list) -> list:
    """Denotational reference: what the skeleton means on a task stream.
    Used by tests to check the normal form preserves semantics."""
    if isinstance(skel, Seq):
        return [skel.program(t) for t in tasks]
    if isinstance(skel, Pipe):
        for s in skel.stages:
            tasks = interpret(s, tasks)
        return tasks
    if isinstance(skel, Farm):
        return interpret(skel.worker, tasks)
    raise TypeError(skel)
