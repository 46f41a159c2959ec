"""One decode step's device time: the union of the intervals of the
generate program's ops in its ``decode`` scope, per whole execution of
the program in the traced window, over the task's new tokens, in
milliseconds."""

from bench.lib import spans


def read(run):
    ms = spans.scope_ms_per_execution(run, "decode")
    return None if ms is None else ms / run.cell.traffic.new_tokens
