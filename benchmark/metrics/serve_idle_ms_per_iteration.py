"""Device 0's idle time inside the profiled window over its profiled iterations, and what the LOOP THREAD
was doing in it: every idle gap (not the ten longest) goes to the innermost loop-thread span open at its
middle, ``between_iterations`` where none is.  ``ctx["spans"]`` carries no thread, so the loop thread's
spans are told by name, from the fixed list below; ``queue_wait`` (the track ``serving queue``) and
``prefill`` (the track ``device``: it COVERS its chunks' device time by construction and says nothing of
the host) never name a gap.  The ten longest gaps are printed with that span's ``rid`` / ``start``.

None, with the reason said, without a trace.  Where the ring has lost the profiled seconds (the harness's
ring of 131,072 records overflows in the fastest cells: PERF.md section 7) the VALUE stands, because it is
the device's and needs no span, and no gap gets a wrong name: a gap in front of the oldest loop-thread
record the ring still holds is ``lost_by_the_ring``, all of them where no such record overlaps the profile,
which is then said in so many words.  (ISSUE 72 asked for None there; the driver refuses a traced run whose
line lacks a metric that lists no cells, and which cells overflow moves with the engine's speed.)"""

from benchmark.lib import xplane

NAME, UNIT, BETTER, SOURCE = "serve_idle_ms_per_iteration", "ms", "lower", "device_trace"
LAYER, MOVES = "device", "serve_tokens_per_s_per_chip"

#: the spans of `Engine._iterate`'s tree and what the runtime does on its thread behind its back
LOOP_SPANS = frozenset((
    "iteration", "admit", "prefill_dispatch", "chunk_dispatch", "decode", "decode_verify",
    "decode_dispatch", "decode_wait", "logits_readback", "sample", "sample_slot", "gc"))
LONGEST = 10


def loop_spans(ctx):
    """The loop thread's records as (start, end, name, args), ns on the trace's clock."""
    t0 = ctx["trace"]["start_unix_ns"] or 0
    return sorted((s["start"] * 1e9 - t0, s["end"] * 1e9 - t0, s["name"], s["args"])
                  for s in ctx["spans"]
                  if s["name"] in LOOP_SPANS or s["name"].startswith("jax_"))


def attribute(gaps, host):
    """[(gap, innermost covering span of ``host`` or None)]: both sorted by start; the spans
    open at a gap's middle are few (a thread's tree), so each gap looks at those alone."""
    out, active, k = [], [], 0
    for a, b in gaps:
        mid = (a + b) / 2
        while k < len(host) and host[k][0] <= mid:
            active.append(host[k])
            k += 1
        active = [s for s in active if s[1] > mid]
        out.append(((a, b), min(active, key=lambda s: s[1] - s[0]) if active else None))
    return out


def compute(ctx):
    if "serve" not in ctx:
        return None
    say = ctx["say"]
    ops = xplane.first_device(ctx.get("trace"))
    n = ctx.get("n_profiled")
    if not ops or not n:
        say("serve_idle_ms_per_iteration: no device trace of profiled iterations")
        return None
    a, b = xplane.window_of(ops)
    host = loop_spans(ctx)
    if not [s for s in host if s[0] < b and s[1] > a]:
        say(f"serve_idle_ms_per_iteration: none of the {len(host)} loop-thread records the ring "
            f"still holds overlaps the {(b - a) / 1e9:.3f} profiled seconds: the ring lost them, "
            "and no gap below has a name")
        host = []
    oldest = host[0][0] if host else float("inf")
    gaps = sorted(xplane.gaps((o.start, o.end) for o in xplane.leaf_ops(ops)))
    by_name, named = {}, []
    for (ga, gb), span in attribute(gaps, host):
        if (ga + gb) / 2 < oldest:
            name = "lost_by_the_ring"
        else:
            name = span[2] if span else "between_iterations"
        ns, count = by_name.get(name, (0.0, 0))
        by_name[name] = (ns + gb - ga, count + 1)
        named.append((gb - ga, name, span))
    idle = sum(ns for ns, _ in by_name.values())
    say(f"device 0 idle {idle / 1e6:.3f} ms of {(b - a) / 1e6:.3f} profiled ms "
        f"({100 * idle / (b - a):.2f}%) in {len(gaps)} gaps over {n} iterations, ms an iteration by "
        "the loop thread's innermost span: " + "; ".join(
            f"{name} {ns / 1e6 / n:.4f} ({count})"
            for name, (ns, count) in sorted(by_name.items(), key=lambda kv: -kv[1][0])))
    say(f"the {LONGEST} longest gaps, ms: " + "; ".join(
        f"{name} {ns / 1e6:.3f}" + "".join(
            f" {k}={span[3][k]}" for k in ("rid", "start") if span and k in span[3])
        for ns, name, span in sorted(named, key=lambda g: -g[0])[:LONGEST]))
    return idle / 1e6 / n
