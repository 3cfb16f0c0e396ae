"""The part of a decode execution under ``attn_core`` + ``cache_write`` of the FULL
layers of a windowed stack: what whole slots cost a step (the plain body reads every
slot's capacity).  Median over the window's executions, device 0; 0 for a stack
without sliding-window layers (its attention is read by the accepted readers)."""

from benchmark.metrics import _swa

NAME, UNIT, BETTER, SOURCE = "full_attn_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    ms = _swa.stack_ms_p50(ctx, "decode", ("attn_core", "cache_write"))
    return None if ms is None else ms["full"]
