"""Host time in backend compiles before the window: the ``jax_compile`` spans,
each a compile or, on a persistent-cache hit, the load of the executable.
Hits and misses are printed from the spans' arguments, and what the call's
first step (``compile_s``) is made of: trace and lower, compile or load, its
``data`` span, and its ``sync`` span, which is the program's first run."""

from benchmark.lib import scoped

NAME, UNIT, BETTER, SOURCE = "compile_or_load_s", "s", "lower", "program_span"
LAYER, MOVES = "entry and compile cache", "setup_s"


def compute(ctx):
    spans = [s for s in ctx["setup_spans"] if s["name"] == "jax_compile"]
    if not spans:
        return None
    first = scoped.first_step(ctx["setup_spans"])
    in_first = sum(s["end"] - s["start"] for s in spans
                   if s["step"] is not None and s["step"] == first)
    args = scoped.exported_span_args("jax_compile")
    hits = sum(1 for a in args if a.get("hit") is True)
    misses = sum(1 for a in args if a.get("hit") is False)
    loads = sum(a.get("retrieval_s") or 0.0 for a in args)
    ctx["say"](f"backend compiles before the window: {len(spans)} spans; of the whole call's "
               f"{len(args)}: {hits} cache hits ({loads:.3f} s loading), {misses} misses, "
               f"{len(args) - hits - misses} without the cache; inside the first step {in_first:.3f} s")
    of_first = {n: sum(s["end"] - s["start"] for s in ctx["setup_spans"]
                       if s["name"] == n and s["step"] is not None and s["step"] == first)
                for n in ("step", "data", "sync")}
    traced = scoped.covered_s([s for s in ctx["setup_spans"] if s["step"] is not None
                               and s["step"] == first], ("jax_trace", "jax_lower"))
    parts = traced + in_first + of_first["data"] + of_first["sync"]
    if of_first["step"]:
        ctx["say"](f"first step {of_first['step']:.3f} s = trace and lower {traced:.3f} + compile or "
                   f"load {in_first:.3f} + first run (its sync span) {of_first['sync']:.3f} + data "
                   f"{of_first['data']:.3f} + {of_first['step'] - parts:.3f} s the spans do not name "
                   f"({100 * parts / of_first['step']:.1f}% named)")
    return sum(s["end"] - s["start"] for s in spans)
