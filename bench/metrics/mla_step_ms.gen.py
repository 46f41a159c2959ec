"""Latent attention's device time per decode step: the union of the
intervals of the generate program's ops in both its ``decode`` and its
``mla`` scope (projections, the absorbed scores and values over the latent
cache, the cache row's write), per whole execution of the program in the
traced window, over the task's new tokens, in milliseconds."""

from bench.lib import op_scopes


def read(run):
    ms = op_scopes.ms_per_execution(run, "decode", "mla")
    return None if ms is None else ms / run.cell.traffic.new_tokens
