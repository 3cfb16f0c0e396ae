"""Slots in use over slots held, a mean over the window's decode iterations (``active`` of the ``sample`` spans)."""

from benchmark.metrics import _serve

NAME, UNIT, BETTER, SOURCE = "slot_occupancy_share", "%", "higher", "program_counter"
LAYER, MOVES = "serving scheduler and slots", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _serve.occupancy_share(ctx)
