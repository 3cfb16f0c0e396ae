"""Time a collective is in flight on device 0, per profiled step.  Left out
where the trace holds no collective (the one-chip cells)."""

from benchmark.lib import xplane

NAME, UNIT, BETTER, SOURCE = "collective_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "runtime and plan", "tokens_per_s_per_chip"


def compute(ctx):
    ops = xplane.first_device(ctx["trace"])
    if not ops:
        return None
    flight, _ = xplane.collective_ns(ops)
    return flight / 1e6 / ctx["n_profiled"] if flight else None
