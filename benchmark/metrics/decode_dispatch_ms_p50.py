"""Median ``decode_dispatch`` span: the host's part of the shared forward, from the operands' way
to the device to the jitted call's return."""

from benchmark.metrics import _engine_spans

NAME, UNIT, BETTER, SOURCE = "decode_dispatch_ms_p50", "ms", "lower", "program_span"
LAYER, MOVES = "serving engine loop", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _engine_spans.ms_p50(ctx, "decode_dispatch")
