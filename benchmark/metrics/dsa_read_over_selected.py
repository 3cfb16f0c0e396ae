"""Latent positions a decode step's sparse attention FETCHES by construction over the
positions its selection keeps (``dsa_read_positions`` / ``dsa_selected_positions``, the
engine's counters on the ``decode`` spans): 1.0 is the least, where a step fetches the
selected latents of the rows in use and nothing else; a program that attends under a mask
over every row's slot up to the longest row's end reads (rows x that length) / selected.  Median over the
window's decode iterations; 0 for a stack without an indexer."""

from benchmark.metrics import _dsa

NAME, UNIT, BETTER, SOURCE = "dsa_read_over_selected", "ratio", "lower", "program_counter"
LAYER, MOVES = "serving scheduler and slots", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _dsa.counter_ratio_p50(ctx, "dsa_read_positions", "dsa_selected_positions",
                                  "latents read over selected")
