"""Set-up the program spends building what it runs: the ``build_runtime`` span
(mesh, shardings, jitted step), ``init_state`` (the parameters' and the
optimizer's arrays, realized) and ``data_open`` (corpus and prefetcher).  Their
trace / lower / compile time is inside them, and also in ``trace_lower_s`` and
``compile_or_load_s``."""

NAME, UNIT, BETTER, SOURCE = "runtime_build_s", "s", "lower", "program_span"
LAYER, MOVES = "runtime and plan", "setup_s"

PARTS = ("build_runtime", "init_state", "data_open")


def compute(ctx):
    parts = {n: sum(s["end"] - s["start"] for s in ctx["setup_spans"] if s["name"] == n)
             for n in PARTS}
    if not any(parts.values()):
        return None
    ctx["say"]("set-up spans: " + ", ".join(f"{n} {v:.3f} s" for n, v in parts.items()))
    return sum(parts.values())
