"""Device idle share: 1 - busy / window, busy being the union of the
device's operations in the traced window, averaged over the chips."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
