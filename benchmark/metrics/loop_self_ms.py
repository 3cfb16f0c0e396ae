"""Median of a ``step`` span minus what its child spans cover of it: the
trainer loop's own host time a step (batch accounting, fault hooks, stepstats,
the JSONL record), which no other span names."""

from benchmark.lib import scoped
from benchmark.lib.stats import percentile

NAME, UNIT, BETTER, SOURCE = "loop_self_ms", "ms", "lower", "program_span"
LAYER, MOVES = "trainer loop", "tokens_per_s_per_chip"


def compute(ctx):
    by_step = {}
    for s in ctx["spans"]:
        by_step.setdefault(s["step"], []).append(s)
    own = [scoped.self_s(s, by_step[s["step"]]) for s in ctx["spans"] if s["name"] == "step"]
    return percentile(own, 50) * 1e3 if own else None
