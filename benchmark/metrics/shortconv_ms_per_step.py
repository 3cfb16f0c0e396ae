"""The part of a decode execution under ``shortconv``, all conv layers: the two
projections, the state's read and write and the taps.  Median over the window's
executions, device 0; 0 for a stack without gated short-convolution layers."""

from benchmark.metrics import _shortconv

NAME, UNIT, BETTER, SOURCE = "shortconv_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _shortconv.ms_p50(ctx, "decode")
