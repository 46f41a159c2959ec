"""The program's own spans and scopes, read from a traced run's profile
and the farm's ``repro.obs`` events, on the device trace's clock.

What the program leaves there (``repro.obs.Observability.span``,
``repro.runtime.serve_loop``):

- host spans ``stack``, ``launch`` and ``unstack`` around a service's
  host work, each with the argument ``t_start``: the recorder's time of
  its start.  The pairs (profile time, recorder time) fit the offset
  between the two clocks (the median of their differences), which puts
  every ``repro.obs`` event on the profile's time axis;
- name-stack scopes ``prefill`` and ``decode`` in the generate program.
  A TPU op's scope is read from the ``tf_op`` stat of its event
  metadata, which ``jax.profiler.ProfileData`` does not expose: that
  part of the ``XSpace`` protobuf is decoded here.

A ``launch`` is matched to the device execution it started by the
profiler's run id: the span's thread hands the call to the runtime
through a flow (``_p`` -> ``_c``), and the runtime's enqueue event
under it carries the ``run_id`` that the execution's event carries on
the device plane.  Where the device's executions carry no run id and
there is one device, launches and executions pair first in, first out.
Executions whose launch fell before the profile are left out.

Without these spans and scopes (a program that predates them) every
reader here returns None.
"""

from __future__ import annotations

import bisect
import os
import re
import statistics
from dataclasses import dataclass, field

from . import spec
from . import trace as trace_mod

TRACE_DIR = os.path.join(spec.ROOT, ".bench_trace")  # bench/run.py's
SPAN_KINDS = ("stack", "launch", "unstack")
SCOPES = ("prefill", "decode")
DEVICE_PREFIX = "/device:TPU:"


@dataclass
class HostSpan:
    name: str
    start_ns: int
    end_ns: int
    t_start: float  # the recorder's clock


@dataclass
class Execution:
    device: str
    name: str
    start_ns: int
    end_ns: int
    run_id: int | None


@dataclass
class Profile:
    spans: list = field(default_factory=list)  # HostSpan
    offset_s: float | None = None  # profile seconds - recorder seconds
    residuals_s: list = field(default_factory=list)
    executions: list = field(default_factory=list)  # Execution
    scope_ns: dict = field(default_factory=dict)  # (device, scope) -> [(s, e)]
    launches: int = 0
    launched: list = field(default_factory=list)  # (HostSpan, Execution)
    matched_by: str = "none"

    def to_profile_ns(self, t: float) -> int:
        """A recorder time on the profile's clock."""
        return int(round((t + self.offset_s) * 1e9))

    def scope_time_ns(self, device: str, scope: str, lo: int,
                      hi: int) -> int:
        """Device time in ``scope`` within [lo, hi]: the union of its
        ops' intervals, so nested loops count once."""
        return trace_mod.union_ns([(max(s, lo), min(e, hi)) for s, e in
                                   self.scope_ns.get((device, scope), [])
                                   if s < hi and e > lo])


# ---------------------------------------------------------------- #
# XSpace protobuf: the event metadata's tf_op stats
# ---------------------------------------------------------------- #
def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message; length-
    delimited values are memoryview slices."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, wire, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", errors="replace")


def op_name_stacks(path: str) -> dict:
    """{device plane name: {op event name: tf_op}} from the profile at
    ``path``: XSpace.planes (1) -> XPlane.name (2), event_metadata (4)
    and stat_metadata (5); XEventMetadata.name (2), stats (5); XStat
    metadata_id (1), str_value (5) or ref_value (7)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out = {}
    for num, _, plane in _fields(data):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pnum, _, value in _fields(plane):
            if pnum == 2:
                name = _text(value)
            elif pnum == 4:
                events.append(value)
            elif pnum == 5:
                entry = {k: v for k, _, v in _fields(value)}
                meta = {k: v for k, _, v in _fields(entry.get(2, b""))}
                stat_names[entry.get(1, 0)] = _text(meta.get(2, b""))
        if not name.startswith(DEVICE_PREFIX):
            continue
        tf_op_id = next((k for k, v in stat_names.items() if v == "tf_op"),
                        None)
        ops = {}
        for value in events:
            entry = {k: v for k, _, v in _fields(value)}
            ev_name, tf_op = "", None
            for enum, _, ev_value in _fields(entry.get(2, b"")):
                if enum == 2:
                    ev_name = _text(ev_value)
                elif enum == 5 and tf_op_id is not None:
                    stat = {k: v for k, _, v in _fields(ev_value)}
                    if stat.get(1) == tf_op_id:
                        if 5 in stat:
                            tf_op = _text(stat[5])
                        elif 7 in stat:
                            tf_op = stat_names.get(stat[7])
            if tf_op:
                ops[ev_name] = tf_op
        out[name] = ops
    return out


def scope_of(tf_op: str | None) -> str | None:
    """The program scope an op ran in: a path segment of its name stack
    that is ``prefill`` or ``decode``, bare or inside a transformation
    (``vmap(decode)``); the op type after ``:`` is ignored."""
    if not tf_op:
        return None
    for seg in tf_op.split(":", 1)[0].split("/"):
        m = re.fullmatch(r"(?:\w+\()*(\w+)\)*", seg)
        if m and m.group(1) in SCOPES:
            return m.group(1)
    return None


# ---------------------------------------------------------------- #
# the profile
# ---------------------------------------------------------------- #
def fit_offset(spans: list) -> tuple[float | None, list]:
    """(offset, residuals) of profile seconds minus recorder seconds over
    the spans' start pairs; the median, which a span delayed between its
    two clock reads (another thread took the GIL) does not pull."""
    diffs = [s.start_ns / 1e9 - s.t_start for s in spans]
    if not diffs:
        return None, []
    offset = statistics.median(diffs)
    return offset, [d - offset for d in diffs]


def _inside(line: list, lo: int, hi: int) -> list:
    """The events of a host line (sorted by start) that start in
    [lo, hi]."""
    starts = [ev[1] for ev in line]
    return line[bisect.bisect_left(starts, lo):bisect.bisect_right(starts,
                                                                   hi)]


def match_by_run_id(host_lines: list, launches: list,
                    executions: list) -> list:
    """(launch, execution) pairs: on the launch span's own line, the flows
    that start inside it lead to the runtime's events, under which an
    event carries the run id of the execution it enqueued."""
    by_run = {x.run_id: x for x in executions if x.run_id is not None}
    # a flow is its id within its type (``_pt`` / ``_ct``): ids repeat
    # across types, a host-to-device transfer's and an execution's
    consumers = {}  # (flow id, type) -> (line index, start, end)
    for li, line in enumerate(host_lines):
        for _, s, e, st in line:
            if "_c" in st:
                consumers[st["_c"], st.get("_ct")] = (li, s, e)
    out = []
    for li, span in launches:
        runs = set()
        for _, _, _, st in _inside(host_lines[li], span.start_ns,
                                   span.end_ns):
            hit = consumers.get((st.get("_p"), st.get("_pt")))
            if hit is not None:
                cli, cs, ce = hit
                runs |= {st2["run_id"] for _, _, _, st2
                         in _inside(host_lines[cli], cs, ce)
                         if "run_id" in st2}
        hits = [by_run[r] for r in sorted(runs) if r in by_run]
        if hits:
            out.append((span, hits[0]))
    return out


def match_fifo(launches: list, executions: list) -> list:
    """(launch, execution) pairs, first in first out on one device: the
    n-th launch by end starts the n-th execution by start; executions
    that start before the first launch do not belong to one."""
    if not launches:
        return []
    spans = sorted((s for _, s in launches), key=lambda s: s.end_ns)
    first = spans[0].start_ns
    execs = sorted((x for x in executions if x.start_ns >= first),
                   key=lambda x: x.start_ns)
    return list(zip(spans, execs))


def read_profile(path: str) -> Profile:
    """The spans, executions and scoped ops of the ``.xplane.pb`` at
    ``path``."""
    from jax.profiler import ProfileData

    stacks = op_name_stacks(path)
    prof = Profile()
    host_lines, launches = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = []
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    st = dict(ev.stats)
                    evs.append((ev.name, s, e, st))
                    if ev.name in SPAN_KINDS and "t_start" in st:
                        span = HostSpan(ev.name, s, e, float(st["t_start"]))
                        prof.spans.append(span)
                        if ev.name == "launch":
                            launches.append((len(host_lines), span))
                host_lines.append(sorted(evs, key=lambda ev: ev[1]))
        elif plane.name.startswith(DEVICE_PREFIX):
            ops = stacks.get(plane.name, {})
            scoped = {}
            for line in plane.lines:
                if line.name == trace_mod.MODULES_LINE:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        run_id = dict(ev.stats).get("run_id")
                        prof.executions.append(Execution(
                            plane.name, ev.name, s, s + int(ev.duration_ns),
                            None if run_id is None else int(run_id)))
                elif line.name == trace_mod.OPS_LINE:
                    for ev in line.events:
                        scope = scoped.get(ev.name, "")
                        if scope == "":
                            scope = scoped[ev.name] = scope_of(
                                ops.get(ev.name))
                        if scope is not None:
                            s = int(ev.start_ns)
                            prof.scope_ns.setdefault(
                                (plane.name, scope), []).append(
                                    (s, s + int(ev.duration_ns)))
    prof.offset_s, prof.residuals_s = fit_offset(prof.spans)
    prof.launches = len(launches)
    if any(x.run_id is not None for x in prof.executions):
        prof.launched = match_by_run_id(host_lines, launches,
                                        prof.executions)
        prof.matched_by = "run_id"
    elif len({x.device for x in prof.executions}) == 1:
        prof.launched = match_fifo(launches, prof.executions)
        prof.matched_by = "fifo"
    return prof


_CACHE: dict = {}


def profile_at(path: str) -> Profile:
    """The profile under the directory ``path``, read once for all the
    readers."""
    path = trace_mod.find_xplane(path)
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = prof = read_profile(path)
        _log_fit(prof)
    return _CACHE[key]


def _log_fit(prof: Profile) -> None:
    from .farm import log

    if prof.offset_s is None:
        log("spans: no program span in the profile")
        return
    res = sorted(abs(r) for r in prof.residuals_s)
    waits = [(x.start_ns - s.end_ns) / 1e9 for s, x in prof.launched]
    log(f"spans: {len(prof.spans)} host spans, clock offset "
        f"{prof.offset_s!r} s, residual largest {res[-1]:.9f} s, p90 "
        f"{res[int(0.9 * (len(res) - 1))]:.9f} s, over 100 us "
        f"{sum(r > 100e-6 for r in res)}; {len(prof.launched)} of "
        f"{prof.launches} launches matched by {prof.matched_by}"
        + (f", device wait min {min(waits):.6f} s" if waits else ""))


def profile(run) -> Profile | None:
    """The run's profile: under ``run.trace_dir`` where the run names
    one, else where ``bench/run.py`` writes a traced run's; None without
    a trace."""
    if run.trace is None:
        return None
    path = getattr(run, "trace_dir", None) or os.path.join(TRACE_DIR,
                                                           run.cell.name)
    return profile_at(path) if os.path.isdir(path) else None


# ---------------------------------------------------------------- #
# what the readers compute
# ---------------------------------------------------------------- #
def whole_generate_executions(run) -> list:
    """(device, start_ns, end_ns) of the executions that
    ``RunView.generate_seconds`` averages: a ``*generate*`` program that
    started and ended in the window and was followed by another."""
    tr = run.trace
    out = []
    for d in tr.devices:
        starts = [s for _, s, _ in d.modules]
        out += [(d.name, s, s + dur) for name, s, dur in d.modules
                if "generate" in name and s + dur <= tr.window[1]
                and any(t >= s + dur for t in starts)]
    return out


def scope_ms_per_execution(run, scope: str) -> float | None:
    """Mean device milliseconds in ``scope`` per whole generate
    execution; None where no op of the window ran in the scope."""
    prof = profile(run)
    if prof is None:
        return None
    execs = whole_generate_executions(run)
    times = [prof.scope_time_ns(dev, scope, s, e) for dev, s, e in execs]
    if not times or not any(times):
        return None
    return sum(times) / len(times) / 1e6


def service_batches(events: list) -> list:
    """One dict per ``launch`` event: its service's ``stack`` just before
    it and the ``drain`` whose dispatch holds it, as the recorder's
    (t_start, t_end) and times."""
    by_service: dict = {}
    for ev in events:
        if ev[1] in SPAN_KINDS:
            by_service.setdefault(ev[2], []).append(
                (ev[1], ev[-1], ev[0]))
    drains = [ev for ev in events if ev[1] == "drain"]
    out = []
    for sid, seq in by_service.items():
        seq.sort(key=lambda x: x[1])
        for i, (kind, t0, t1) in enumerate(seq):
            if kind != "launch":
                continue
            b = {"service": sid, "launch": (t0, t1)}
            if i > 0 and seq[i - 1][0] == "stack":
                b["stack"] = seq[i - 1][1:]
            held = [d for d in drains
                    if d[2] == sid and d[4] <= t0 and t1 <= d[0]]
            if held:
                d = min(held, key=lambda d: d[0])
                b["dispatch"], b["drain"] = d[4], d[0]
            out.append(b)
    return out


def first_launch_ends(events: list) -> dict:
    """{task id: (submit time, end of the launch that carried it)} in the
    recorder's clock: a task's first lease, then the first ``launch`` of
    the leasing service that started after it."""
    submitted, leased = {}, {}
    launches: dict = {}
    for ev in events:
        if ev[1] == "task-submit":
            n, base = ev[2], ev[3]
            for tid in range(base, base + n):
                submitted[tid] = ev[0]
        elif ev[1] == "lease":
            for tid, _attempt in ev[3]:
                leased.setdefault(tid, (ev[0], ev[2]))
        elif ev[1] == "launch":
            launches.setdefault(ev[2], []).append((ev[-1], ev[0]))
    out = {}
    for tid, (t_lease, sid) in leased.items():
        if tid not in submitted:
            continue
        ends = [t1 for t0, t1 in sorted(launches.get(sid, []))
                if t0 >= t_lease]
        if ends:
            out[tid] = (submitted[tid], ends[0])
    return out


def overlap_ns(a: list, b: list) -> int:
    """Length of the intersection of two sets of (start, end) intervals."""
    def merged(xs):
        out = []
        for s, e in sorted(xs):
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return out

    a, b = merged(a), merged(b)
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def service_time_split(run) -> dict | None:
    """Mean seconds, over the traced batches, of each part of a batch's
    service time (``dispatch`` to ``drain``): before ``stack``, ``stack``,
    ``launch``, the device wait, the execution, and from its end to the
    ``drain`` (``unstack`` and materialisation); with their sum and the
    mean service time."""
    prof = profile(run)
    if prof is None or not prof.launched:
        return None
    by_start = {b["launch"][0]: b for b in service_batches(run.served.events)}
    parts = []
    for span, x in prof.launched:
        b = by_start.get(span.t_start)
        if b is None or "drain" not in b:
            continue
        stack = b.get("stack", b["launch"][:1] * 2)
        parts.append({
            "before_stack": stack[0] - b["dispatch"],
            "stack": stack[1] - stack[0],
            "launch": b["launch"][1] - b["launch"][0],
            "device_wait": (x.start_ns - span.end_ns) / 1e9,
            "execution": (x.end_ns - x.start_ns) / 1e9,
            "to_drain": (prof.to_profile_ns(b["drain"]) - x.end_ns) / 1e9,
            "service_time": b["drain"] - b["dispatch"]})
    if not parts:
        return None
    out = {k: sum(p[k] for p in parts) / len(parts) for k in parts[0]}
    out["parts_sum"] = sum(v for k, v in out.items() if k != "service_time")
    out["batches"] = len(parts)
    return out
