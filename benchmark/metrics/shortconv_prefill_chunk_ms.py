"""The part of a prefill-chunk execution under ``shortconv``, all conv layers: the
chunk's projections, the conv over [state | chunk] and the state written as of the
chunk's last real row.  Median over the window's executions, device 0; 0 for a stack
without gated short-convolution layers."""

from benchmark.metrics import _shortconv

NAME, UNIT, BETTER, SOURCE = "shortconv_prefill_chunk_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _shortconv.ms_p50(ctx, "prefill")
