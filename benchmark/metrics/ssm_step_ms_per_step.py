"""The part of a decode execution under ``ssm`` > ``step`` + ``state_read`` +
``state_write``, all Mamba-2 layers: what moving the state costs a step (the single-step
body over the stack in place, the conv tail's read and write; a copy of the stack would
show here).  Median over the window's executions, device 0; 0 for a stack without served
state-space layers."""

from benchmark.metrics import _ssm_serve

NAME, UNIT, BETTER, SOURCE = "ssm_step_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    ms = _ssm_serve.ms_p50(ctx)
    if not ms:
        return ms
    return _ssm_serve.scope_ms_p50(ctx, "decode", _ssm_serve.STEP, _ssm_serve.STEP)
