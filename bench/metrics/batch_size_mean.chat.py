"""Batch size: the mean number of tasks per ``dispatch`` (``repro.obs``
events of the control threads) in the window."""


def read(run):
    sizes = [ev[3] for ev in run.events_in_window() if ev[1] == "dispatch"]
    return sum(sizes) / len(sizes) if sizes else None
