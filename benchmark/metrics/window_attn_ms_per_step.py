"""The part of a decode execution under ``attn_core`` + ``cache_write`` of the
sliding-window layers of a windowed stack: what the ring's reach costs a step (the
plain body reads every slot's whole ring).  Median over the window's executions,
device 0; 0 for a stack without sliding-window layers."""

from benchmark.metrics import _swa

NAME, UNIT, BETTER, SOURCE = "window_attn_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    ms = _swa.stack_ms_p50(ctx, "decode", ("attn_core", "cache_write"))
    return None if ms is None else ms["window"]
