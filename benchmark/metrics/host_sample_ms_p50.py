"""Median ``sample`` span: the host's sampling of one token a slot in use, from the logits copied out."""

from benchmark.metrics import _serve

NAME, UNIT, BETTER, SOURCE = "host_sample_ms_p50", "ms", "lower", "program_span"
LAYER, MOVES = "serving engine loop", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _serve.span_ms_p50(ctx, "sample")
