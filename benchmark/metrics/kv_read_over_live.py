"""Positions a decode step's attention of a windowed stack FETCHES by construction
over the positions live in its rows, weighted by the layers of each stack (a full
layer: every slot's capacity over the rows' lengths n; a window layer: every slot's
ring over min(n, window)): 1 when the step reads what it needs and no more.  Median
over the window's ``decode`` spans, from the engine's counters; 0 for a stack
without sliding-window layers (its iterations carry no such counters)."""

from benchmark.lib.stats import percentile
from benchmark.metrics import _swa

NAME, UNIT, BETTER, SOURCE = "kv_read_over_live", "ratio", "lower", "program_counter"
LAYER, MOVES = "serving scheduler and slots", "serve_tokens_per_s_per_chip"


def compute(ctx):
    steps = _swa.step_counters(ctx)
    if not steps:
        return None if steps is None else 0.0  # no iteration | another stack
    ratios = []
    for s in steps:
        live = (s["kv_full_live_positions"] * s["kv_full_layers"]
                + s["kv_window_live_positions"] * s["kv_window_layers"])
        read = (s["kv_full_read_positions"] * s["kv_full_layers"]
                + s["kv_window_read_positions"] * s["kv_window_layers"])
        if live:
            ratios.append(read / live)
    if not ratios:
        return 0.0
    ctx["say"](f"positions read over live, by layers, over {len(ratios)} decode iterations: median "
               f"{percentile(ratios, 50):.3f}, min {min(ratios):.3f}, max {max(ratios):.3f}")
    return percentile(ratios, 50)
