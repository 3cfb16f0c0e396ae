"""Backend compiles (or cache loads) inside the measured window: ``jax_compile``
spans that carry a window step.  Expected 0; each one found is printed with
its step.  Left out where the program records no compile at all (no listener)."""

NAME, UNIT, BETTER, SOURCE = "compiles_in_window", "count", "lower", "program_counter"
LAYER, MOVES = "entry and compile cache", "tokens_per_s_per_chip"


def compute(ctx):
    if not any(s["name"] == "jax_compile" for s in ctx["setup_spans"] + ctx["spans"]):
        return None
    found = [s for s in ctx["spans"] if s["name"] == "jax_compile"]
    for s in found:
        ctx["say"](f"compile inside the window: step {s['step']}, {s['end'] - s['start']:.3f} s")
    return len(found)
