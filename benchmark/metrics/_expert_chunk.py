"""What the two readers of a prompt chunk's routed experts share (PR 70).  The program's
cached forward of a stack with dropless expert layers opens, under ``layer_<i>`` > ``mlp``,
``router``, ``dispatch``, ``experts`` (the bounded held body opens ``dispatch`` / ``experts``
/ ``combine`` again under it), ``combine`` and ``shared_expert``; with the tracer on, the
``prefill`` span of a prompt carries, beside the LAST chunk's router counters, the means over
the prompt's chunks of ``prefill_chunk`` REAL rows: ``moe_chunks_counted``, ``moe_held_pairs``
(the (token, expert) pairs a chunk puts on the held experts, a layer) and
``moe_held_experts_touched_a_chunk``.

Both read the PREFILL program, answer None where the profile holds no chunk, and so carry
``workloads``; a program from before PR 70 carries no such counters and the roofline then
answers None too (the metric is left out of the line)."""

from benchmark.metrics._mla import scope_ms_p50

MARK = ("experts",)
PARTS = ("router", "dispatch", "experts", "combine", "shared_expert")


def chunk_ms_p50(ctx, wanted=MARK):
    """Median over the prefill program's executions (a prompt chunk each) of the device time
    under a scope of ``wanted``; 0 where the program carries no ``experts`` scope; None where
    the profile holds no chunk."""
    return scope_ms_p50(ctx, "prefill", wanted, MARK)


def pairs_and_touched(ctx):
    """(held pairs, held experts touched) of ONE chunk of ``prefill_chunk`` real rows, a
    layer: the means over the window's ``prefill`` spans, each weighted by the chunks it
    counted; None where no span carries the counters (no prompt of a whole chunk was
    prefilled in the window, or the program has none)."""
    pairs = touched = chunks = 0.0
    for s in ctx.get("spans") or []:
        a = s.get("args") or {}
        n = a.get("moe_chunks_counted")
        if s["name"] != "prefill" or not isinstance(n, (int, float)) or n <= 0:
            continue
        if not all(isinstance(a.get(k), (int, float))
                   for k in ("moe_held_pairs", "moe_held_experts_touched_a_chunk")):
            continue
        pairs += n * a["moe_held_pairs"]
        touched += n * a["moe_held_experts_touched_a_chunk"]
        chunks += n
    return (pairs / chunks, touched / chunks) if chunks else None
