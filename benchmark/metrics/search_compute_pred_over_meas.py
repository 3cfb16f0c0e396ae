"""The search's price of the layers' compute over what device 0 spent on it:
``compute + overlap_slowdown`` of the plan's ``time_ms`` (``search/price.py``:
forward x the recomputation factor, plus the slowdown the model charges where
dp traffic runs under it) over the busy time under ``layer_<i>`` and
``grad_accum`` that is neither a collective nor under a comm scope.  1.0 is
the aim; recorded, not gated: a rate priced too low reads above 1 whatever the
plan.  The table of all terms is printed once a traced run."""

from benchmark.metrics import _search_terms

NAME, UNIT, BETTER, SOURCE = "search_compute_pred_over_meas", "ratio", "lower", "program_counter"
LAYER, MOVES = "search", "tokens_per_s_per_chip"


def compute(ctx):
    t = _search_terms.of_ctx(ctx)
    if t is None or t["compute_ratio"] is None:
        return None
    return t["compute_ratio"]
