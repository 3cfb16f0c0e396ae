"""Median ``fwd_bwd`` span of the window: the host handing the step program to
the runtime (argument handling, donation, enqueue), not the device's work."""

from benchmark.lib.stats import percentile

NAME, UNIT, BETTER, SOURCE = "host_dispatch_ms", "ms", "lower", "program_span"
LAYER, MOVES = "trainer loop", "tokens_per_s_per_chip"


def compute(ctx):
    d = [s["end"] - s["start"] for s in ctx["spans"] if s["name"] == "fwd_bwd"]
    return percentile(d, 50) * 1e3 if d else None
