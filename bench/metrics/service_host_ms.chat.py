"""Service host time: the host time of a batch's ``stack``, ``launch``
and ``unstack`` spans (``repro.obs`` events of the in-process services),
summed and averaged over the batches launched in the window, in
milliseconds."""

from bench.lib.spans import SPAN_KINDS


def read(run):
    events = [ev for ev in run.events_in_window() if ev[1] in SPAN_KINDS]
    batches = sum(1 for ev in events if ev[1] == "launch")
    if not batches:
        return None
    return 1000.0 * sum(ev[0] - ev[-1] for ev in events) / batches
