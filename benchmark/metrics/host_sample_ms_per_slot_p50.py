"""Median ``sample_slot`` span: the host's draw of one token for one slot (float64 softmax, the sorts
over the vocabulary, the draw, the logits tap), whatever the slots in use; sampled and greedy medians printed apart."""

from benchmark.metrics import _engine_spans

NAME, UNIT, BETTER, SOURCE = "host_sample_ms_per_slot_p50", "ms", "lower", "program_span"
LAYER, MOVES = "serving engine loop", "serve_tokens_per_s_per_chip"


def compute(ctx):
    for label, greedy in (("sampled", False), ("greedy", True)):
        _engine_spans.ms_p50(ctx, "sample_slot", label=label,
                             keep=lambda s, g=greedy: bool(s["args"].get("greedy")) is g)
    return _engine_spans.ms_p50(ctx, "sample_slot")
