"""Median over the window's decode iterations of ``moe_held_pairs_per_token``: the
(token, expert) pairs a token of the step puts on the experts this copy HOLDS,
mean over the expert layers, which the engine notes on every ``decode`` span of a
model with dropless expert layers.  ``k * held / E`` when the load is even (2.0 at
8 x 32 / 128); the routed GEMMs' rows scale with it.  0 for a model without such
layers: its tokens put no pair on an expert."""

from benchmark.lib.stats import percentile
from benchmark.metrics import _mla

NAME, UNIT, BETTER, SOURCE = "serve_moe_held_pairs_per_token", "pairs", "higher", "program_counter"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    pairs = _mla.decode_counter(ctx, "moe_held_pairs_per_token")
    if not pairs:
        return None if pairs is None else 0.0  # no iteration | no expert layers
    imbalance = _mla.decode_counter(ctx, "moe_load_imbalance")
    ctx["say"](f"moe held pairs a token over {len(pairs)} decode iterations: median "
               f"{percentile(pairs, 50):.4f}, min {min(pairs):.4f}, max {max(pairs):.4f}"
               + (f"; load imbalance (fullest held expert over the even share) median "
                  f"{percentile(imbalance, 50):.3f}" if imbalance else ""))
    return percentile(pairs, 50)
