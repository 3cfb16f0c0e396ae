"""How long after device 0 finished a step the host knew: the end of the
``sync`` annotation minus the end of the step's last device operation, median
over the profiled steps.  Both from the profiler's trace alone, on its one
clock (the tracer's spans are ``TraceAnnotation``s while a window is open)."""

from benchmark.lib import scoped, xplane
from benchmark.lib.stats import percentile

NAME, UNIT, BETTER, SOURCE = "host_sync_lag_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "trainer loop", "tokens_per_s_per_chip"


def compute(ctx):
    data = scoped.of_ctx(ctx)
    ops = xplane.first_device(ctx.get("trace"))
    if data is None or not ops:
        return None
    lags = scoped.sync_lags_ns(data["annotations"],
                               sorted(o.end for o in xplane.leaf_ops(ops)))
    if not lags:
        return None
    ctx["say"]("host sync lag by profiled step: " + ", ".join(f"{x / 1e6:.3f}" for x in lags)
               + " ms")
    return percentile(lags, 50) / 1e6
