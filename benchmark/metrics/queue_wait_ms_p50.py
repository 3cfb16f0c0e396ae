"""Median ``queue_wait`` span of the requests admitted in the window: submission to slot.  The queue's
depth at the window's first and last ``iteration`` is printed: under the knee it ends empty, above it it grows."""

from benchmark.metrics import _engine_spans

NAME, UNIT, BETTER, SOURCE = "queue_wait_ms_p50", "ms", "lower", "program_span"
LAYER, MOVES = "serving scheduler and slots", "serve_tokens_per_s_per_chip"


def compute(ctx):
    its = sorted(_engine_spans.named(ctx, "iteration"), key=lambda s: s["start"])
    if its:
        ctx["say"](f"queue depth: {its[0]['args'].get('queued')} at the window's first iteration, "
                   f"{its[-1]['args'].get('queued')} at its last (of {len(its)})")
    return _engine_spans.ms_p50(ctx, "queue_wait")
