"""Median ``prefill_dispatch`` span of the window: what ONE admission costs the loop thread's host,
from the slot's allocation through the buffers and the dispatch of the prompt's chunks to the dispatch of
its first draw (PR 72: the traced loop waits for the device nowhere in it, as the untraced loop does not,
so this is the untraced loop's cost too).  Printed beside it: the median ``chunk_dispatch`` (one a chunk,
inside it) and the admissions a second of the window."""

from benchmark.metrics import _serve

NAME, UNIT, BETTER, SOURCE = "admission_host_ms_p50", "ms", "lower", "program_span"
LAYER, MOVES = "serving engine loop", "serve_tokens_per_s_per_chip"


def compute(ctx):
    sends = _serve.spans_named(ctx, "prefill_dispatch")
    if not sends:
        return None  # a training cell, or a program from before PR 72
    a_chunk = _serve.span_ms_p50(ctx, "chunk_dispatch")  # (each prints n and its honest tail)
    value = _serve.span_ms_p50(ctx, "prefill_dispatch")
    seconds = ctx["serve"].get("seconds")
    chunks = sum(int(s["args"].get("chunks", 0)) for s in sends)
    ctx["say"](f"admissions: n={len(sends)} in the window, p50 = {value:.3f} ms on the loop thread; "
               f"{chunks} chunks, chunk_dispatch p50 = "
               + (f"{a_chunk:.3f} ms" if a_chunk is not None else "not recorded")
               + (f"; {len(sends) / seconds:.3f} admissions a second" if seconds else ""))
    return value
