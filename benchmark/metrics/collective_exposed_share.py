"""Share of device 0's in-flight collective time during which no other
operation runs there: communication that the step waits for."""

from benchmark.lib import xplane

NAME, UNIT, BETTER, SOURCE = "collective_exposed_share", "%", "lower", "device_trace"
LAYER, MOVES = "runtime and plan", "tokens_per_s_per_chip"


def compute(ctx):
    ops = xplane.first_device(ctx["trace"])
    if not ops:
        return None
    flight, exposed = xplane.collective_ns(ops)
    return 100.0 * exposed / flight if flight else None
