"""The searched plan's predicted step time over the measured median step.
Recorded, not gated: a better cost model picks a better plan, and that shows
in tokens/s; 1.0 is a perfect prediction."""

from benchmark.lib.stats import percentile

NAME, UNIT, BETTER, SOURCE = "search_pred_over_meas", "ratio", "lower", "program_span"
LAYER, MOVES = "search", "tokens_per_s_per_chip"


def compute(ctx):
    pred = (ctx["plan"] or {}).get("search_cost_ms")
    if not pred or not ctx["step_s"]:
        return None
    return pred / (percentile(ctx["step_s"], 50) * 1e3)
