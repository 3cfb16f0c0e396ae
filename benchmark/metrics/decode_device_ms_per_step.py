"""Device 0's busy time of ONE execution of the decode program (the shared forward
over all slots), median over the executions in the traced window: what
``decode_device_wait_ms_p50`` waits for, less what overlapped the dispatch.  Once
sampling leaves the host this is the iteration.  Prints every program of the
window with its scopes (``benchmark/metrics/_decode_device.py``)."""

from benchmark.metrics import _decode_device

NAME, UNIT, BETTER, SOURCE = "decode_device_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _decode_device.of(ctx, "busy_ms")
