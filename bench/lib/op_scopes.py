"""Device time of the generate program's named scopes inside its phases,
read from a traced run's profile.

The phases are ``spans.SCOPES`` (``prefill``, ``decode``); the scopes are
the ones a model's layers name: ``mla`` (latent attention), ``router``,
``experts`` (the routed experts' grouped matmul) and ``shared_experts``.
An op counts in (phase, scope) when its name stack (``tf_op``, from
``spans.op_name_stacks``) holds both as path segments, bare or inside a
transformation (``vmap(experts)``).  A program whose ops carry no such
scope gives None.
"""

from __future__ import annotations

import os
import re

from . import spans
from . import trace as trace_mod

SCOPES = ("mla", "router", "experts", "shared_experts")
_SEGMENT = re.compile(r"(?:\w+\()*(\w+)\)*")

_CACHE: dict = {}


def segments(tf_op: str | None) -> set:
    """The scope names on an op's name stack; the op type after ``:`` is
    ignored."""
    if not tf_op:
        return set()
    return {m.group(1) for seg in tf_op.split(":", 1)[0].split("/")
            for m in [_SEGMENT.fullmatch(seg)] if m}


def intervals(path: str) -> dict:
    """{(device plane, phase, scope): [(start_ns, end_ns)]} of the ops of
    the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    stacks = spans.op_name_stacks(path)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(spans.DEVICE_PREFIX):
            continue
        ops = stacks.get(plane.name, {})
        keys_of: dict = {}
        for line in plane.lines:
            if line.name != trace_mod.OPS_LINE:
                continue
            for ev in line.events:
                keys = keys_of.get(ev.name)
                if keys is None:
                    seg = segments(ops.get(ev.name))
                    keys = keys_of[ev.name] = [
                        (plane.name, phase, scope) for phase in spans.SCOPES
                        if phase in seg for scope in SCOPES if scope in seg]
                if keys:
                    s = int(ev.start_ns)
                    for k in keys:
                        out.setdefault(k, []).append(
                            (s, s + int(ev.duration_ns)))
    return out


def ms_per_execution(run, phase: str, scope: str) -> float | None:
    """Mean device milliseconds of ``scope`` within ``phase`` per whole
    generate execution (``spans.whole_generate_executions``): the union of
    its ops' intervals, so nested loops count once.  None without a trace,
    or where no op of the window ran in the scope."""
    if run.trace is None:
        return None
    path = getattr(run, "trace_dir", None) or os.path.join(spans.TRACE_DIR,
                                                           run.cell.name)
    if not os.path.isdir(path):
        return None
    path = trace_mod.find_xplane(path)
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = intervals(path)
    found = _CACHE[key]
    times = []
    for dev, s, e in spans.whole_generate_executions(run):
        times.append(trace_mod.union_ns([
            (max(a, s), min(b, e))
            for a, b in found.get((dev, phase, scope), []) if a < e and b > s]))
    if not times or not any(times):
        return None
    return sum(times) / len(times) / 1e6
