"""From a profiler trace (``.xplane.pb``) to device busy time, idle gaps,
per-program executions and the operations that took the time.

The trace window is the harness's own host span named ``window``
(``jax.profiler.TraceAnnotation``); host and device events share one
time base in the trace.  For each ``/device:TPU:<n>`` plane:

- busy time is the union of the intervals of the ``XLA Ops`` line,
  clipped to the window;
- program executions are the events of the ``XLA Modules`` line, named
  like ``jit_generate(<fingerprint>)``; the one running when the
  profiler stops is cut short there and looks whole, so an execution
  counts only once another has started on its device after it ended;
- idle gaps are the stretches of the window that no operation covers,
  each named by the harness span (submit, wait, fetch ...) that the host
  was in at the gap's middle.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW = "window"


@dataclass
class Device:
    name: str
    busy_ns: int
    gaps: list  # (start_ns, length_ns), longest first
    modules: list  # (name, start_ns, duration_ns) starting in the window
    op_ns: dict  # short op name -> total ns inside the window


@dataclass
class Trace:
    window: tuple  # (start_ns, end_ns) of the harness's window span
    devices: list = field(default_factory=list)
    host_spans: list = field(default_factory=list)  # (name, start, end)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices traced."""
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_times(self, part: str) -> list[float]:
        """Seconds of each execution, on any device, of a program whose
        name contains ``part``, that started and ended in the window and
        was followed there by another execution on its device."""
        out = []
        for d in self.devices:
            starts = [s for _, s, _ in d.modules]
            out += [dur / 1e9 for name, s, dur in d.modules
                    if part in name and s + dur <= self.window[1]
                    and any(t >= s + dur for t in starts)]
        return out

    def top_ops(self, n: int = 10) -> list:
        total: dict = {}
        for d in self.devices:
            for k, v in d.op_ns.items():
                total[k] = total.get(k, 0) + v
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9 / len(self.devices)] for k, v in top]

    def top_gaps(self, n: int = 10, namer=None) -> list:
        """The ``n`` longest idle gaps on any device, as [name, seconds];
        ``namer(start_ns, length_ns)`` names one, by default the host
        span at its middle."""
        namer = namer or (lambda s, n: self.host_span_at(s + n // 2))
        gaps = sorted(((length, start, d.name) for d in self.devices
                       for start, length in d.gaps), reverse=True)[:n]
        return [[f"{namer(start, length)} ({dev})", length / 1e9]
                for length, start, dev in gaps]

    def host_span_at(self, t_ns: int) -> str:
        """The innermost harness span that covers ``t_ns``."""
        best = None
        for name, s, e in self.host_spans:
            if s <= t_ns <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "outside harness spans"


def short_op_name(name: str) -> str:
    """``%fusion.12 = f32[..] fusion(...)`` -> ``fusion.12``."""
    head = name.split(" = ", 1)[0]
    return head.lstrip("%")


def union_ns(intervals: list) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps_ns(intervals: list, lo: int, hi: int) -> list:
    """(start, length) of the stretches of [lo, hi] that no interval
    covers, longest first."""
    out, cursor = [], lo
    for s, e in sorted(intervals):
        if s > cursor:
            out.append((cursor, s - cursor))
        cursor = max(cursor, e)
    if hi > cursor:
        out.append((cursor, hi - cursor))
    return sorted(out, key=lambda g: -g[1])


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {files}")
    return files[0]


def reduce(path: str, host_span_names=("submit", "wait", "fetch")) -> Trace:
    """Read the trace at ``path`` (a file, or a directory holding one)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    planes = list(data.planes)
    window, spans = None, []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (int(ev.start_ns),
                              int(ev.start_ns + ev.duration_ns))
                elif ev.name in host_span_names:
                    spans.append((ev.name, int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns)))
    if window is None:
        raise ValueError(f"{path}: no host span named {WINDOW!r}")
    lo, hi = window
    trace = Trace(window=window, host_spans=spans)
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops, modules, op_ns = [], [], {}
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    s, e = max(s, lo), min(e, hi)
                    if e > s:
                        ops.append((s, e))
                        k = short_op_name(ev.name)
                        op_ns[k] = op_ns.get(k, 0) + (e - s)
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    s = int(ev.start_ns)
                    if lo <= s < hi:
                        modules.append((ev.name, s, int(ev.duration_ns)))
        trace.devices.append(Device(plane.name, union_ns(ops),
                                    gaps_ns(ops, lo, hi), modules, op_ns))
    if not trace.devices:
        raise ValueError(f"{path}: no /device:TPU:* plane in the trace")
    return trace
