"""Median ``decode_wait`` span: the loop thread blocked until the step's logits exist on the device (the
device's time less what overlapped the dispatch).  How much of the ``decode`` / ``decode_verify`` span its
three children cover is printed."""

from benchmark.metrics import _engine_spans
from benchmark.lib.stats import percentile

NAME, UNIT, BETTER, SOURCE = "decode_device_wait_ms_p50", "ms", "lower", "program_span"
LAYER, MOVES = "serving engine loop", "serve_tokens_per_s_per_chip"


def compute(ctx):
    whole = [_engine_spans.ms(s) for s in _engine_spans.named(ctx, "decode", "decode_verify")]
    parts = [_engine_spans.ms(s) for s in _engine_spans.named(
        ctx, "decode_dispatch", "decode_wait", "logits_readback")]
    if whole and parts:
        ctx["say"](f"decode_dispatch + decode_wait + logits_readback cover {sum(parts):.1f} of "
                   f"{sum(whole):.1f} ms of shared forwards ({100 * sum(parts) / sum(whole):.2f}%; "
                   f"median forward {percentile(whole, 50):.3f} ms)")
    return _engine_spans.ms_p50(ctx, "decode_wait")
