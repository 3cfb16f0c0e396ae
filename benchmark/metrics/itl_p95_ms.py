"""95th percentile of the gaps between consecutive tokens: it falls where iterations with and without a joining
request's prefill meet, so it swings from run to run; recorded, no bound (PERF.md section 7)."""

from benchmark.metrics import _serve

NAME, UNIT, BETTER, SOURCE = "itl_p95_ms", "ms", "lower", "host_clock"
LAYER, MOVES = "serving engine loop", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _serve.stamps_ms(ctx, "itl_s", 95)
