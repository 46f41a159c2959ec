"""Farm turnaround: the gap from one batch's ``drain`` to the same
service's next ``dispatch`` (``repro.obs`` events of the control
threads), averaged per service, then over services, in the window."""


def read(run):
    per_service: dict = {}
    for ev in run.events_in_window():
        if ev[1] in ("drain", "dispatch"):
            per_service.setdefault(ev[2], []).append((ev[0], ev[1]))
    means = []
    for evs in per_service.values():
        gaps, last_drain = [], None
        for t, kind in sorted(evs):
            if kind == "drain":
                last_drain = t
            elif last_drain is not None:
                gaps.append(t - last_drain)
                last_drain = None
        if gaps:
            means.append(sum(gaps) / len(gaps))
    if not means:
        return None
    return 1000.0 * sum(means) / len(means)
