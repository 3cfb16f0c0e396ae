"""1 - busy over the traced window on device 0: the union of its operations'
intervals, from its first operation's start to its last one's end."""

from benchmark.lib import xplane

NAME, UNIT, BETTER, SOURCE = "device_idle_share", "%", "lower", "device_trace"
LAYER, MOVES = "device", "tokens_per_s_per_chip"


def compute(ctx):
    ops = xplane.first_device(ctx["trace"])
    if not ops:
        return None
    a, b = xplane.window_of(ops)
    return 100.0 * (1.0 - xplane.busy_ns(ops) / (b - a))
