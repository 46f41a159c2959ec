"""The routed experts' device time per decode step: the union of the
intervals of the generate program's ops in both its ``decode`` and its
``experts`` scope (the grouped matmul over the held experts, with the
gather before it and the weighted sum after it), per whole execution of
the program in the traced window, over the task's new tokens, in
milliseconds."""

from bench.lib import op_scopes


def read(run):
    ms = op_scopes.ms_per_execution(run, "decode", "experts")
    return None if ms is None else ms / run.cell.traffic.new_tokens
