"""Median time from one decode iteration's start to the next one's: sample + decode + admissions + the loop's own."""

from benchmark.metrics import _serve

NAME, UNIT, BETTER, SOURCE = "engine_iteration_ms_p50", "ms", "lower", "program_span"
LAYER, MOVES = "serving engine loop", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _serve.iteration_ms_p50(ctx)
