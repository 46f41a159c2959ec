"""Device wait: from the end of a batch's ``launch`` span on the host to
the start of the device execution it started, 90th percentile over the
traced window's batches.  A batch waits there while the chip runs
another service's batch."""

from bench.lib import spans
from bench.lib.farm import log
from bench.lib.stats import percentile


def read(run):
    prof = spans.profile(run)
    if prof is None or not prof.launched:
        return None
    split = spans.service_time_split(run)
    if split:
        log("service time split (mean s): " + ", ".join(
            f"{k} {v!r}" for k, v in split.items()))
    return percentile([(x.start_ns - s.end_ns) / 1e9
                       for s, x in prof.launched], 90)
