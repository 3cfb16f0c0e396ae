"""Device 0's time a step under the ``optimizer`` scope (gradient clipping and
the AdamW update).  Forward + backward + optimizer + the unscoped rest
(printed by ``scope_coverage``) is device 0's busy time."""

from benchmark.lib import scoped

NAME, UNIT, BETTER, SOURCE = "optimizer_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "tokens_per_s_per_chip"


def compute(ctx):
    return scoped.phase_ms_per_step(ctx, "optimizer")
