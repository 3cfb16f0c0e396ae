"""Median ``decode`` span: one shared forward over all slots, closed when its logits are on the host."""

from benchmark.metrics import _serve

NAME, UNIT, BETTER, SOURCE = "decode_step_ms_p50", "ms", "lower", "program_span"
LAYER, MOVES = "serving engine loop", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _serve.span_ms_p50(ctx, "decode")
