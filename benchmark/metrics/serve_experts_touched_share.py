"""Share of the experts this copy HOLDS that got at least one row in a decode step:
``moe_held_experts_touched`` over ``moe_held_experts``, which the engine notes on the
``decode`` spans of a model with dropless expert layers (PR 61: 32 tokens under 4-of-256
put 16 pairs on 32 held experts, half a row an expert; 39% under even routing, less where
Zipf tokens repeat; 86-95% at 2-3 rows an expert).  The held share's layout gives
every held expert a tile whether it has a row or not, so 100 minus this is the share of
the held experts' weights a step fetches and multiplies by nothing.  Median over the
window's decode iterations; 0 where the iterations carry no such counter (a dense model,
the parent's program)."""

from benchmark.lib.stats import percentile
from benchmark.metrics._touched import touched_and_held

NAME, UNIT, BETTER, SOURCE = "serve_experts_touched_share", "%", "higher", "program_counter"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    read = touched_and_held(ctx)
    if read is None:
        return None
    touched, held = read
    if not touched:
        return 0.0
    ctx["say"](f"held experts touched over {len(touched)} decode iterations: median "
               f"{percentile(touched, 50):.3f}, min {min(touched):.3f}, max {max(touched):.3f} "
               f"of {held:.0f} held")
    return 100.0 * percentile(touched, 50) / held
