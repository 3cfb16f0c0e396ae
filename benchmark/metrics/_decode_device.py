"""What the readers of the decode step's DEVICE half share (PR 42).  A traced
serving run's window holds several jitted programs; ``lib/scoped.executions``
gives device 0's executions of each.  The decode program is the one whose jit
name holds ``decode`` (``_decode_step``, its paged and verify twins: PERF.md
section 3's table of names); where a window holds several of them the one with
most device time is read and the others are printed beside it.

``step(ctx)`` -> ``{"busy_ms", "cache_ms", "weights_ms", "table_ms", "coverage"}``
of one execution, medians over the window's executions (device 0), or None in a
context without ``serve``, without a trace, or without a decode program in it.
The three parts are the time under ``attn_core`` + ``cache_write`` (what the
slot cache's reach and layout cost), under ``qkv_proj`` + ``out_proj`` + ``mlp`` +
``norm`` inside a layer (what the weights' passes cost) and under ``embed`` +
``head`` (the table and its conversions); they and ``coverage`` are None, with
the reason said, where no operation of the program carries a scope (jax's
persistent cache returns an executable compiled before the names existed:
PERF.md section 7).  Every program and scope of the window is printed once."""

from benchmark.lib import scoped
from benchmark.lib.stats import percentile

CACHE = ("attn_core", "cache_write")
WEIGHTS = ("qkv_proj", "out_proj", "mlp", "norm")
TABLE = ("embed", "head")


def part_of(op_name):
    """``table`` | ``cache`` | ``weights`` | ``other`` (a scope of the program,
    none of the three) | ``unscoped``."""
    scopes = scoped.scopes_of(op_name)
    if not scopes:
        return "unscoped"
    if scopes[0] in TABLE:
        return "table"
    if any(s in CACHE for s in scopes):
        return "cache"
    if any(s in WEIGHTS for s in scopes):
        return "weights"
    return "other"


def parts_ms(ex):
    out = {"table": 0.0, "cache": 0.0, "weights": 0.0, "other": 0.0, "unscoped": 0.0}
    for o in ex.ops:
        out[part_of(o.op_name)] += (o.end - o.start) / 1e6
    return out


def step(ctx):
    if "serve" not in ctx:
        return None
    if "_decode_device" not in ctx:
        ctx["_decode_device"] = _step(ctx)
    return ctx["_decode_device"]


def _step(ctx):
    execs, say = scoped.executions(ctx), ctx["say"]
    if not execs:
        say("decode step on the device: no trace, or a trace without module events")
        return None
    for line in scoped.program_table(execs):
        say(line)
    progs = {k: v for k, v in scoped.by_program(execs).items() if "decode" in k}
    if not progs:
        say("decode step on the device: no program of the window has 'decode' in its jit name")
        return None
    name, runs = max(progs.items(), key=lambda kv: sum(map(scoped.busy_ns_of, kv[1])))
    parts = [parts_ms(ex) for ex in runs]
    out = {"busy_ms": percentile([scoped.busy_ns_of(ex) / 1e6 for ex in runs], 50),
           "cache_ms": None, "weights_ms": None, "table_ms": None, "coverage": None}
    total = sum(sum(p.values()) for p in parts)
    scoped_ms = total - sum(p["unscoped"] for p in parts)
    if not scoped_ms:
        say(f"decode step on the device: no operation of {name} carries a scope of the program "
            f"(the compile cache returned an executable from before the names, or the names "
            f"changed: PERF.md section 7); its parts are left out")
        return out
    for key in ("cache", "weights", "table"):
        out[key + "_ms"] = percentile([p[key] for p in parts], 50)
    out["coverage"] = 100.0 * scoped_ms / total
    say(f"decode step on the device ({name}, {len(runs)} executions, medians, ms): busy "
        f"{out['busy_ms']:.3f} = cache {out['cache_ms']:.3f} + weights {out['weights_ms']:.3f} + "
        f"table {out['table_ms']:.3f} + other scopes "
        f"{percentile([p['other'] for p in parts], 50):.3f} + unscoped "
        f"{percentile([p['unscoped'] for p in parts], 50):.3f}; scope coverage "
        f"{out['coverage']:.2f}%")
    return out


def of(ctx, key):
    """What a reader returns: one number of :func:`step`, or None."""
    got = step(ctx)
    return None if got is None else got[key]
