"""What the readers of the engine's own span tree share (PR 39).  Since that PR
the program's loop thread records, tracer on: ``iteration`` (``step`` = the
engine's step counter, ``active``, ``queued``) around ``admit`` (around each
request's ``prefill``), ``sample`` (around one ``sample_slot`` a slot that draws:
``slot``, ``rid``, ``greedy``) and ``decode`` or ``decode_verify`` (around
``decode_dispatch``, ``decode_wait``, ``logits_readback``); ``queue_wait`` (one a
request, at admission, on a track of its own: ``rid``, ``depth``); and the
``jax_compile`` spans carry the ``step`` of the iteration they fell into.  The
window's spans reach a reader through ``ctx["spans"]`` as the ring had them
(name, start, end, args).  A reader returns None in a context without
``"serve"`` (a training cell) and where the spans it reads are absent (a program
from before that PR): its metric is then left out.  Every reader here moves
"serve_tokens_per_s_per_chip" and lists no cells."""

from bisect import bisect_left, bisect_right

from benchmark.lib.stats import honest_tail, percentile

#: what an ``iteration`` span holds directly; the rest of it is the loop's own
CHILDREN = ("admit", "sample", "decode", "decode_verify")


def named(ctx, *names):
    if "serve" not in ctx:
        return []
    return [s for s in ctx["spans"] if s["name"] in names]


def ms(span):
    return 1e3 * (span["end"] - span["start"])


def ms_p50(ctx, name, keep=lambda s: True, label="all"):
    """Median duration, in ms, of the window's spans ``name`` that ``keep``
    admits; printed under ``label`` with the highest percentile the sample
    supports."""
    xs = [ms(s) for s in named(ctx, name) if keep(s)]
    if not xs:
        return None
    tail = honest_tail(xs)
    ctx["say"](f"{name} spans, {label}: n={len(xs)}, p50 = {percentile(xs, 50):.3f} ms"
               + (f", p{tail[0]:.1f} = {tail[1]:.3f} ms" if tail else ""))
    return percentile(xs, 50)


def self_ms(ctx):
    """For every ``iteration`` span of the window, its duration less what its
    children cover of it, in ms.  Children of one thread's span lie inside it,
    one after another: the one that starts inside an iteration is its own."""
    kids = sorted(named(ctx, *CHILDREN), key=lambda s: s["start"])
    starts = [s["start"] for s in kids]
    out = []
    for it in named(ctx, "iteration"):
        mine = kids[bisect_left(starts, it["start"]):bisect_right(starts, it["end"])]
        out.append(ms(it) - sum(1e3 * (min(k["end"], it["end"]) - k["start"]) for k in mine))
    return out
