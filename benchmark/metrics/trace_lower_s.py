"""Host time the call spent tracing Python to jaxprs and lowering them to MLIR
before the window: the union of the ``jax_trace`` and ``jax_lower`` spans (a
jitted function's trace holds the traces of those it calls).  The part inside
the call's first step, which ``compile_s`` times whole, is printed."""

from benchmark.lib import scoped

NAME, UNIT, BETTER, SOURCE = "trace_lower_s", "s", "lower", "program_span"
LAYER, MOVES = "entry and compile cache", "setup_s"

NAMES = ("jax_trace", "jax_lower")


def compute(ctx):
    spans = [s for s in ctx["setup_spans"] if s["name"] in NAMES]
    if not spans:
        return None
    first = scoped.first_step(ctx["setup_spans"])
    in_first = [s for s in spans if s["step"] is not None and s["step"] == first]
    ctx["say"](f"trace and lower before the window: trace {scoped.covered_s(spans, NAMES[:1]):.3f} s, "
               f"lower {scoped.covered_s(spans, NAMES[1:]):.3f} s in {len(spans)} spans; inside "
               f"the first step {scoped.covered_s(in_first, NAMES):.3f} s")
    return scoped.covered_s(spans, NAMES)
