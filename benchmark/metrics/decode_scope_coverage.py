"""Share of a decode execution's device time whose operation carries a scope of
the program (the rest: waits and copies the compiler inserted, no ``op_name``).
None, with the reason said, where none does."""

from benchmark.metrics import _decode_device

NAME, UNIT, BETTER, SOURCE = "decode_scope_coverage", "%", "higher", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _decode_device.of(ctx, "coverage")
