"""The generate program's share of its roofline: the least time of one
task (``bench.lib.flops``: each phase at the larger of FLOPs over peak
and bytes over HBM bandwidth) over the mean device time of one execution
of the program in the traced window, in percent."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.task_cost["least_s"] / run.generate_seconds()
