"""The part of a decode execution under ``state_read`` + ``state_write`` of the layers
that keep a per-row state: what the state stack's reach and layout cost a step (a copy
of the whole stack would show here).  Median over the window's executions, device 0; 0
for a stack without such layers."""

from benchmark.metrics import _shortconv

NAME, UNIT, BETTER, SOURCE = "state_cache_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _shortconv.ms_p50(ctx, "decode", _shortconv.STATE)
