"""Wall time of the call's first step: trace, then compile or load from the
persistent cache, then one step."""

NAME, UNIT, BETTER, SOURCE = "compile_s", "s", "lower", "program_span"
LAYER, MOVES = "entry and compile cache", "setup_s"


def compute(ctx):
    steps = sorted((s for s in ctx["setup_spans"] if s["name"] == "step"),
                   key=lambda s: s["start"])
    return steps[0]["end"] - steps[0]["start"] if steps else None
