"""Prefill's device time: the union of the intervals of the generate
program's ops in its ``prefill`` scope, per whole execution of the
program in the traced window (the executions ``gen_mfu`` averages), in
milliseconds."""

from bench.lib import spans


def read(run):
    return spans.scope_ms_per_execution(run, "prefill")
