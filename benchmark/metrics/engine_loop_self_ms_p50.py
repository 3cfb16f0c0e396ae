"""Median over the window's ``iteration`` spans of the span less what ``admit``, ``sample`` and
``decode`` / ``decode_verify`` cover of it: the loop's own host time an iteration (queue expiry, retirements,
draft building, counters), which no other span names."""

from benchmark.metrics import _engine_spans
from benchmark.lib.stats import percentile

NAME, UNIT, BETTER, SOURCE = "engine_loop_self_ms_p50", "ms", "lower", "program_span"
LAYER, MOVES = "serving engine loop", "serve_tokens_per_s_per_chip"


def compute(ctx):
    own = _engine_spans.self_ms(ctx)
    return percentile(own, 50) if own else None
