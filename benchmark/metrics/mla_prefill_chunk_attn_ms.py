"""The part of a prefill-chunk execution under ``attn_core`` of a latent-attention
stack: the chunk form (``W_kvb`` expansion a block of keys at a time, running
softmax), which grows with the positions live in the slot.  Median over the
window's executions, device 0; 0 for a stack without latent attention."""

from benchmark.metrics import _mla

NAME, UNIT, BETTER, SOURCE = "mla_prefill_chunk_attn_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _mla.scope_ms_p50(ctx, "prefill", ("attn_core",), ("expand",))
