"""Ring positions a decode step's window layers FETCH by construction over the positions
live in their windows (``latent_ring_read_positions`` / ``latent_ring_live_positions``:
every slot's ring over the sum of min(n, window)): what ``kv_read_over_live`` is to a K/V
ring, for a ring of latents.  1 when the step reads what it needs and no more.  Median over
the window's decode iterations; 0 for a stack without a latent ring."""

from benchmark.metrics import _dsa

NAME, UNIT, BETTER, SOURCE = "latent_ring_read_over_live", "ratio", "lower", "program_counter"
LAYER, MOVES = "serving scheduler and slots", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _dsa.counter_ratio_p50(ctx, "latent_ring_read_positions",
                                  "latent_ring_live_positions", "ring positions read over live")
