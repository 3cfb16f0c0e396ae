"""Median ``logits_readback`` span: the copy of the step's logits to the host, once the device has
them; the bytes a step are printed."""

from benchmark.metrics import _engine_spans

NAME, UNIT, BETTER, SOURCE = "logits_readback_ms_p50", "ms", "lower", "program_span"
LAYER, MOVES = "serving engine loop", "serve_tokens_per_s_per_chip"


def compute(ctx):
    found = _engine_spans.named(ctx, "logits_readback")
    if found:
        ctx["say"](f"logits_readback: {found[-1]['args'].get('bytes')} bytes a step")
    return _engine_spans.ms_p50(ctx, "logits_readback")
