"""The part of a prefill-chunk execution under ``ssm`` > ``scan``, all Mamba-2 layers: the
chunk form of the recurrence from the state the row enters with (chunks of ``chunk_size``
positions; padding after the last real row reaches neither output nor state).  Median over
the window's executions, device 0; 0 for a stack without served state-space layers."""

from benchmark.metrics import _ssm_serve

NAME, UNIT, BETTER, SOURCE = "ssm_chunk_scan_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _ssm_serve.chunk_ms_p50(ctx, ("scan",))
