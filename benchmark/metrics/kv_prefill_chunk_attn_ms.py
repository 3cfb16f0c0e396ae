"""The part of a prefill-chunk execution under ``attn_core`` of a windowed stack:
the chunk's attention a block of keys at a time with a running softmax, a full layer
up to the chunk's end and a window layer over its ring.  Median over the window's
executions, device 0, both stacks together (the split is printed); 0 for a stack
without sliding-window layers."""

from benchmark.metrics import _swa

NAME, UNIT, BETTER, SOURCE = "kv_prefill_chunk_attn_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    ms = _swa.stack_ms_p50(ctx, "prefill", ("attn_core",))
    if ms is None:
        return None
    if ms["window"] or ms["full"]:
        ctx["say"](f"a prompt chunk's attention: {ms['window']:.3f} ms under the window layers' "
                   f"attn_core, {ms['full']:.3f} ms under the full layers'")
    return ms["window"] + ms["full"]
