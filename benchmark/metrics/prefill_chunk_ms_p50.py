"""Median ``prefill`` span over the chunks it holds (a request's prompt over ``--prefill_chunk``)."""

from benchmark.metrics import _serve

NAME, UNIT, BETTER, SOURCE = "prefill_chunk_ms_p50", "ms", "lower", "program_span"
LAYER, MOVES = "serving engine loop", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _serve.prefill_ms_p50(ctx)
