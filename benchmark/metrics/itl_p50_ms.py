"""Median gap between consecutive tokens of a request, over the window's tokens (the benchmark's stamps)."""

from benchmark.metrics import _serve

NAME, UNIT, BETTER, SOURCE = "itl_p50_ms", "ms", "lower", "host_clock"
LAYER, MOVES = "serving engine loop", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _serve.stamps_ms(ctx, "itl_s", 50)
