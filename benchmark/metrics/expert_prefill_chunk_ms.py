"""The part of a prefill-chunk execution under ``mlp`` > ``experts``, all layers: what the
held share of the routed experts costs a prompt chunk (the bounded held body's gather, its
grouped products and its weighted sum; the router, the layout and the shared expert are
beside it, printed, not in it).  Median over the window's executions, device 0; 0 for a
model without dropless expert layers; None where the profile holds no chunk."""

from benchmark.metrics import _expert_chunk

NAME, UNIT, BETTER, SOURCE = "expert_prefill_chunk_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    ms = _expert_chunk.chunk_ms_p50(ctx)
    if ms:
        parts = {part: _expert_chunk.chunk_ms_p50(ctx, (part,)) for part in _expert_chunk.PARTS}
        ctx["say"](f"a prompt chunk's expert layers, {ms:.3f} ms under experts; by scope (a "
                   f"scope opened again under experts counts in both): " + ", ".join(
                       f"{part} {parts[part]:.3f}" for part in _expert_chunk.PARTS))
    return ms
