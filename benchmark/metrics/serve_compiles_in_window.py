"""Backend compiles (or cache loads) inside a serving window: its ``jax_compile`` spans, each printed
with the iteration it fell into and the function compiled.  Expected 0.  Left out where the program numbers
no iteration, or records no compile at all (no listener)."""

from benchmark.metrics import _engine_spans

NAME, UNIT, BETTER, SOURCE = "serve_compiles_in_window", "count", "lower", "program_counter"
LAYER, MOVES = "entry and compile cache", "serve_tokens_per_s_per_chip"


def compute(ctx):
    if not _engine_spans.named(ctx, "iteration") or not any(
            s["name"] == "jax_compile" for s in ctx["setup_spans"] + ctx["spans"]):
        return None
    found = _engine_spans.named(ctx, "jax_compile")
    for s in found:
        ctx["say"](f"compile inside the window: iteration {s['args'].get('step')}, "
                   f"{s['args'].get('fun_name')}, {s['end'] - s['start']:.3f} s")
    return len(found)
