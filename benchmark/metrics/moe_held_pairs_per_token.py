"""Median over the window's steps of ``moe_held_pairs_per_token``: the (token,
expert) pairs a token puts on the experts this copy HOLDS, mean over the layers,
which the program logs in every ``train_iter`` record of a model that holds a
share of its experts.  ``k * held / E`` when the router's load is even (0.625 at
10 x 32 / 512); it is what the expert GEMMs' row count, and so their time,
scales with.  A model that holds all its experts logs none: left out."""

from benchmark.lib.stats import percentile

NAME, UNIT, BETTER, SOURCE = "moe_held_pairs_per_token", "pairs", "higher", "program_counter"
LAYER, MOVES = "model step", "tokens_per_s_per_chip"


def compute(ctx):
    pairs = [r["moe_held_pairs_per_token"] for r in ctx["records"]
             if isinstance(r.get("moe_held_pairs_per_token"), (int, float))]
    if not pairs:
        return None
    ctx["say"](f"moe held pairs a token: first {pairs[0]:.4f}, median "
               f"{percentile(pairs, 50):.4f}, last {pairs[-1]:.4f}")
    return percentile(pairs, 50)
