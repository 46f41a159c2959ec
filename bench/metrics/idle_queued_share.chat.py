"""Idle with work due: the share of the traced window in which the
device ran no op while some request had been submitted (its
``task-submit``) and the launch that carried it had not yet ended, the
request times put on the profile's clock by the fitted offset; mean over
the chips, in percent.  Idle the host caused, apart from idle with
nothing due."""

from bench.lib import spans


def read(run):
    prof = spans.profile(run)
    if prof is None or prof.offset_s is None:
        return None
    pending = spans.first_launch_ends(run.served.events)
    if not pending:
        return None
    due = [(prof.to_profile_ns(t0), prof.to_profile_ns(t1))
           for t0, t1 in pending.values()]
    tr = run.trace
    shares = [spans.overlap_ns([(s, s + n) for s, n in d.gaps], due)
              / (tr.window[1] - tr.window[0]) for d in tr.devices]
    return 100.0 * sum(shares) / len(shares)
