"""Wall time of the in-process plan search (cells whose plan is searched)."""

NAME, UNIT, BETTER, SOURCE = "search_s", "s", "lower", "host_clock"
LAYER, MOVES = "search", "setup_s"


def compute(ctx):
    return ctx["search_s"]
