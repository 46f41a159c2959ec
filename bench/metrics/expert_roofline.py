"""The routed experts' grouped matmul's share of its roofline in decode:
the least time of one step's expert phase (the family's
``task_extras``: every held expert's weights read once, at the larger of
FLOPs over peak and bytes over HBM bandwidth) over ``expert_step_ms.gen``,
in percent."""

from bench.lib import op_scopes


def read(run):
    least_s = run.task_cost.get("expert_decode_step_least_s")
    ms = op_scopes.ms_per_execution(run, "decode", "experts")
    if least_s is None or ms is None:
        return None
    return 100.0 * least_s / (ms / run.cell.traffic.new_tokens / 1e3)
