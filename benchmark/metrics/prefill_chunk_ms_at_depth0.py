"""A prompt chunk's device time at depth 0, by least squares over the window's ``prefill`` spans (PR 72:
each is its prompt's chunks ON THE DEVICE, from the ids in force before them to the ids behind its draw,
and says ``chunks`` and ``depth_sum``, the sum of the positions its chunks began at):

    duration_ms = a x chunks + b x depth_sum / 1024

The value is a; b (ms a chunk for every 1,024 positions of depth: what a chunk's attention over the keys
before it costs), the residual's standard deviation and n are printed.  Two windows that admitted
different prompts give the same a where ``prefill_chunk_ms_p50`` (a span over its chunks, whatever their
depth) does not.  None under 8 spans, and where the prompts' shapes do not separate a from b (every span
the same ``depth_sum`` a chunk, other than 0: with every chunk at depth 0 the fit is a alone).  Spans that
say ``error``, ``pending`` or ``opened_late`` (the device was through before anyone looked: the span's
start is a bound, not a stamp) are left out.  One pass of trimming: the spans farther from the first fit
than 3 of its residual's sd (a prompt that held the profiler's start, a collection: 100 ms on a span of
65 in one chip run, which alone moved a by 4%) are left out of a second fit, 5% of the spans at most."""

from benchmark.metrics import _serve

NAME, UNIT, BETTER, SOURCE = "prefill_chunk_ms_at_depth0", "ms", "lower", "program_span"
LAYER, MOVES = "serving engine loop", "serve_tokens_per_s_per_chip"

MIN_SPANS = 8
#: the second fit leaves out the spans farther than this many sd from the first, this share at most
TRIM_SD, TRIM_SHARE = 3.0, 0.05
#: 1 - the squared cosine between the two columns, under which they are one direction
SEPARATION_MIN = 1e-6


def fit(rows):
    """Least squares of ``y = a * c + b * d`` over rows (c, d, y) -> (a, b, residual sd); b
    None where every d is 0; None where c and d are one direction."""
    scc = sum(c * c for c, _, _ in rows)
    scd = sum(c * d for c, d, _ in rows)
    sdd = sum(d * d for _, d, _ in rows)
    scy = sum(c * y for c, _, y in rows)
    sdy = sum(d * y for _, d, y in rows)
    if sdd == 0:
        a, b = scy / scc, None
    elif 1.0 - scd * scd / (scc * sdd) < SEPARATION_MIN:
        return None
    else:
        det = scc * sdd - scd * scd
        a, b = (scy * sdd - sdy * scd) / det, (sdy * scc - scy * scd) / det
    free = len(rows) - (1 if b is None else 2)
    rss = sum((y - a * c - (b or 0.0) * d) ** 2 for c, d, y in rows)
    return a, b, (rss / free) ** 0.5 if free > 0 else 0.0


def residual(row, a, b):
    return row[2] - a * row[0] - (b or 0.0) * row[1]


def compute(ctx):
    say = ctx["say"]
    rows, left_out = [], {}  # rows: (chunks, depth_sum / 1024, ms, rid)
    for s in _serve.spans_named(ctx, "prefill"):
        args = s["args"]
        chunks, depth = args.get("chunks"), args.get("depth_sum")
        if not chunks or depth is None:
            continue
        why = next((k for k in ("error", "pending", "opened_late") if args.get(k)), None)
        if why:
            left_out[why] = left_out.get(why, 0) + 1
            continue
        rows.append((float(chunks), float(depth) / 1024.0, 1e3 * (s["end"] - s["start"]),
                     args.get("rid")))
    if len(rows) < MIN_SPANS:
        if rows:
            say(f"prefill spans with chunks and depth_sum: n={len(rows)} < {MIN_SPANS}: no fit")
        return None
    got = fit([row[:3] for row in rows])
    if got is None:
        say(f"prefill spans: n={len(rows)}, every one the same depth a chunk "
            f"({rows[0][1] / rows[0][0]:.3f} x 1,024): a chunk's time cannot be told from its depth's")
        return None
    a, b, sd = got
    by_distance = sorted(rows, key=lambda row: -abs(residual(row, a, b)))
    far = [row for row in by_distance[:int(TRIM_SHARE * len(rows))]
           if abs(residual(row, a, b)) > TRIM_SD * sd]
    kept = [row for row in rows if row not in far]
    again = fit([row[:3] for row in kept]) if far and len(kept) >= MIN_SPANS else None
    if again is not None:
        say("prefill spans trimmed: " + "; ".join(
            f"rid {rid} {c:.0f} chunks {y:.3f} ms" for c, _, y, rid in far)
            + f" lie more than {TRIM_SD:g} sd ({sd:.3f} ms) from the first fit (a = {a:.3f})")
        left_out["trimmed"] = len(far)
        rows, (a, b, sd) = kept, again
    shapes = sorted({(int(c), round(d, 3)) for c, d, _, _ in rows})
    say(f"prefill spans fitted: n={len(rows)} ({left_out or 'none'} left out) of {len(shapes)} "
        "shapes (chunks, depth_sum / 1024) "
        f"{shapes[:6]}{' ...' if len(shapes) > 6 else ''}; a = {a:.3f} ms a chunk at depth 0, b = "
        + ("undetermined (every chunk at depth 0)" if b is None
           else f"{b:.3f} ms a chunk a 1,024 positions of depth")
        + f", residual sd {sd:.3f} ms; the three spans farthest from it: " + "; ".join(
            f"rid {row[3]} {row[0]:.0f} chunks {row[2]:.3f} ms ({residual(row, a, b):+.3f})"
            for row in sorted(rows, key=lambda row: -abs(residual(row, a, b)))[:3]))
    return a
