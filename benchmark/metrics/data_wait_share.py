"""Share of the window's step time that the trainer loop spent waiting for its
next batch (the ``data`` spans inside the ``step`` spans: a dequeue from the
prefetcher plus the device put)."""

NAME, UNIT, BETTER, SOURCE = "data_wait_share", "%", "lower", "program_span"
LAYER, MOVES = "data", "tokens_per_s_per_chip"


def compute(ctx):
    if not ctx["step_s"]:
        return None
    wait = sum(s["end"] - s["start"] for s in ctx["spans"] if s["name"] == "data")
    return 100.0 * wait / sum(ctx["step_s"])
