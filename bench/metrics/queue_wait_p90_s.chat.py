"""Queue wait: from a request's ``task-submit`` to its first ``lease``
(``repro.obs`` events of the task repository), 90th percentile over the
requests due in the window."""

from bench.lib.stats import percentile


def read(run):
    submitted, leased = {}, {}
    for ev in run.served.events:
        if ev[1] == "task-submit":
            n, base = ev[2], ev[3]
            for tid in range(base, base + n):
                submitted[tid] = ev[0]
        elif ev[1] == "lease":
            for tid, _attempt in ev[3]:
                leased.setdefault(tid, ev[0])
    waits = [leased[i] - submitted[i] for i in run.served.in_window
             if i in leased and i in submitted]
    return percentile(waits, 90) if waits else None
