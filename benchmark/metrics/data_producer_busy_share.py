"""Time the prefetcher's producer thread spent building and putting batches
(``data_produce`` spans, by the part of each that lies inside the window) over
the window's step time: the data layer's time busy, beside ``data_wait_share``,
its time waited for.  Near 100% the producer is the bottleneck in waiting."""

NAME, UNIT, BETTER, SOURCE = "data_producer_busy_share", "%", "lower", "program_span"
LAYER, MOVES = "data", "tokens_per_s_per_chip"


def compute(ctx):
    steps = [s for s in ctx["spans"] if s["name"] == "step"]
    produce = [s for s in ctx["setup_spans"] + ctx["spans"] if s["name"] == "data_produce"]
    if not steps or not produce or not ctx["step_s"]:
        return None
    a, b = min(s["start"] for s in steps), max(s["end"] for s in steps)
    busy = sum(max(0.0, min(s["end"], b) - max(s["start"], a)) for s in produce)
    return 100.0 * busy / sum(ctx["step_s"])
