"""The part of a decode execution under ``attn_core`` + ``cache_write`` of a
latent-attention stack: what the latent slot cache's reach costs a step (the
absorbed form reads the slots' whole capacity).  Median over the window's
executions, device 0; 0 for a stack without latent attention."""

from benchmark.metrics import _mla

NAME, UNIT, BETTER, SOURCE = "mla_attn_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _mla.scope_ms_p50(ctx, "decode", ("attn_core", "cache_write"), ("absorb",))
