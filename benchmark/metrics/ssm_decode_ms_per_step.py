"""The part of a decode execution under ``ssm``, all Mamba-2 layers: the two projections,
the conv over [tail | position], the single step over the state stack, the grouped gate
norm, the tail's read and write.  Median over the window's executions, device 0; 0 for a
stack without served state-space layers."""

from benchmark.metrics import _ssm_serve

NAME, UNIT, BETTER, SOURCE = "ssm_decode_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _ssm_serve.ms_p50(ctx)
