"""Share of the median ``step`` span that no term of the search's price covers:
device 0's time under ``optimizer``, in operations without a scope of the
program (compiler-inserted copies and waits) and idle.  0 is the aim (``better``
is lower); recorded, not gated.  ``pp_bubble`` is printed against the idle time
and joins no ratio until a pipeline cell exists."""

from benchmark.metrics import _search_terms

NAME, UNIT, BETTER, SOURCE = "search_unpriced_share", "%", "lower", "program_counter"
LAYER, MOVES = "search", "tokens_per_s_per_chip"


def compute(ctx):
    t = _search_terms.of_ctx(ctx)
    if t is None or t["unpriced_share"] is None:
        return None
    return t["unpriced_share"]
