"""What the serving readers share.  A serving cell's context carries ``serve``
(the benchmark's own stamps: first-token times and gaps between tokens of the
window, the slot count, the window) beside the window's spans from the
program's tracer ring: ``sample`` (one a decode iteration, ``active`` = slots in
use at its start), ``decode`` (the iteration's shared forward, closed on the
logits' arrival on the host) and ``prefill`` (one a request, ``tokens`` = its
prompt).  A training cell has no ``serve`` and none of these spans: every
reader here then returns None and its metric is left out (PERF.md section 3).
Every serving reader moves ``serve_tokens_per_s_per_chip`` and lists no cells,
so it is read in every cell that reports that metric, a later PR's too."""

import math

from benchmark.lib.stats import honest_tail, percentile


def spans_named(ctx, name):
    if "serve" not in ctx:
        return []
    return [s for s in ctx["spans"] if s["name"] == name]


def span_ms_p50(ctx, name, per=lambda s: 1):
    """Median duration, in ms, of the window's spans ``name``, each divided by
    ``per(span)``; the highest percentile the sample supports is printed."""
    ms = [1e3 * (s["end"] - s["start"]) / per(s) for s in spans_named(ctx, name)]
    if not ms:
        return None
    tail = honest_tail(ms)
    if tail:
        ctx["say"](f"{name} spans: n={len(ms)}, p{tail[0]:.1f} = {tail[1]:.3f} ms")
    return percentile(ms, 50)


def prefill_ms_p50(ctx):
    """Median ``prefill`` span over the calls of the prefill program it holds
    (its prompt's tokens over the engine's chunk)."""
    if "serve" not in ctx:
        return None
    chunk = ctx["serve"]["prefill_chunk"]
    return span_ms_p50(ctx, "prefill",
                       lambda s: max(1, math.ceil(int(s["args"].get("tokens", 1)) / chunk)))


def iteration_ms_p50(ctx):
    """Median time from one decode iteration's start to the next one's: the
    ``sample`` span's start opens an iteration."""
    starts = sorted(s["start"] for s in spans_named(ctx, "sample"))
    if len(starts) < 2:
        return None
    return percentile([1e3 * (b - a) for a, b in zip(starts, starts[1:])], 50)


def occupancy_share(ctx):
    """Slots in use over slots held, a mean over the window's decode iterations."""
    active = [int(s["args"]["active"]) for s in spans_named(ctx, "sample") if "active" in s["args"]]
    if not active:
        return None
    return 100.0 * sum(active) / len(active) / ctx["serve"]["num_slots"]


def stamps_ms(ctx, key, q):
    if "serve" not in ctx or not ctx["serve"][key]:
        return None
    xs = ctx["serve"][key]
    tail = honest_tail(xs)
    ctx["say"](f"{key}: n={len(xs)}"
               + (f", p{tail[0]:.1f} = {1e3 * tail[1]:.3f} ms" if tail else ", too few for a tail"))
    return 1e3 * percentile(xs, q)
