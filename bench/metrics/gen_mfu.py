"""Model FLOP/s utilization of the generate program on the device: the
FLOPs that one task cannot avoid (``bench.lib.flops``) over the mean
device time of one execution of the program in the traced window, over
the chip's bf16 peak, in percent."""


def read(run):
    if run.trace is None:
        return None
    flops_per_s = run.task_cost["flops"] / run.generate_seconds()
    return 100.0 * flops_per_s / run.peaks["flops_bf16"]
