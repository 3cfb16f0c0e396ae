"""Device 0's time a step in the forward pass: operations whose ``op_name``
carries a scope of the program, is not under ``optimizer`` and was not
transposed by autodiff.  Forward + backward + optimizer + the unscoped rest
(printed by ``scope_coverage``) is device 0's busy time."""

from benchmark.lib import scoped

NAME, UNIT, BETTER, SOURCE = "forward_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "tokens_per_s_per_chip"


def compute(ctx):
    return scoped.phase_ms_per_step(ctx, "forward")
