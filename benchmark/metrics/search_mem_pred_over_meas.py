"""The search's price of the plan's memory over what the fullest device held:
the sum of ``memory_mb`` (``states + activations + other + rings + transient``,
``search/price.plan_memory_mb``, MB of 1e6 bytes) over ``peak_bytes_in_use +
peak_bytes_reserved`` (what ``hbm_peak_gib`` reads).  1.0 is the aim; recorded,
not gated: under 1 the search admits plans that do not fit, over 1 it refuses
plans that do.  The terms are printed."""

from benchmark.metrics import _search_terms

NAME, UNIT, BETTER, SOURCE = "search_mem_pred_over_meas", "ratio", "lower", "program_counter"
LAYER, MOVES = "search", "tokens_per_s_per_chip"


def compute(ctx):
    t = _search_terms.of_ctx(ctx)
    if t is None or t["mem_ratio"] is None:
        return None
    mem = t["price"].get("memory_mb", {})
    ctx["say"]("  memory priced, MB: " + ", ".join(f"{k} {v:.1f}" for k, v in mem.items())
               + f"; sum {sum(mem.values()):.1f} against a peak of "
               f"{(ctx.get('memory_peak_bytes') or 0) / 1e6:.1f}")
    return t["mem_ratio"]
