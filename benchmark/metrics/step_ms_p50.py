"""Median of the window's ``step`` spans (data + dispatch + sync)."""

from benchmark.lib.stats import honest_tail, percentile

NAME, UNIT, BETTER, SOURCE = "step_ms_p50", "ms", "lower", "program_span"
LAYER, MOVES = "trainer loop", "tokens_per_s_per_chip"


def compute(ctx):
    steps = ctx["step_s"]
    if not steps:
        return None
    tail = honest_tail(steps)
    if tail:
        ctx["say"](f"step spans: n={len(steps)}, highest percentile with ten samples beyond it: "
                   f"p{tail[0]:.1f} = {tail[1] * 1e3:.3f} ms")
    return percentile(steps, 50) * 1e3
