"""The part of a decode execution under ``attn_core`` + ``cache_write``: what the
slot cache's reach and layout cost (ROADMAP A3 ii).  Median over the window's
executions, device 0; None where the program's operations carry no scope."""

from benchmark.metrics import _decode_device

NAME, UNIT, BETTER, SOURCE = "decode_cache_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _decode_device.of(ctx, "cache_ms")
