"""The search's price of embed + head + loss over what device 0 spent there:
``other_compute`` of the plan's ``time_ms`` (``cost_model.other_time_terms``)
over the busy time under ``embed`` / ``head`` / ``loss``, forward and backward,
less its communication part.  1.0 is the aim; recorded, not gated."""

from benchmark.metrics import _search_terms

NAME, UNIT, BETTER, SOURCE = "search_other_pred_over_meas", "ratio", "lower", "program_counter"
LAYER, MOVES = "search", "tokens_per_s_per_chip"


def compute(ctx):
    t = _search_terms.of_ctx(ctx)
    if t is None or t["other_ratio"] is None:
        return None
    return t["other_ratio"]
