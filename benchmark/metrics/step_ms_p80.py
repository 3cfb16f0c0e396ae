"""80th percentile of the ``step`` spans; only where the window holds at least
51 steps, so that ten samples lie beyond it (the one-chip cells hold ~70 in a
20 s window; the highest honest percentile of any window is printed by
``step_ms_p50``)."""

from benchmark.lib.stats import percentile

NAME, UNIT, BETTER, SOURCE = "step_ms_p80", "ms", "lower", "program_span"
LAYER, MOVES = "trainer loop", "tokens_per_s_per_chip"


def compute(ctx):
    return percentile(ctx["step_s"], 80) * 1e3 if len(ctx["step_s"]) >= 51 else None
