"""The part of a decode execution under ``mlp`` > ``dispatch`` + ``experts`` +
``combine``: what the held share of the routed experts costs a step (the shared
expert and the router are beside it, not in it).  Median over the window's
executions, device 0; 0 for a model without dropless expert layers."""

from benchmark.metrics import _mla

NAME, UNIT, BETTER, SOURCE = "serve_expert_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"

ROUTED = ("dispatch", "experts", "combine")


def compute(ctx):
    return _mla.scope_ms_p50(ctx, "decode", ROUTED, ROUTED)
