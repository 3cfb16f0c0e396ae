"""Device time of ONE decode step under ``indexer`` of the full layers (PR 65): the index
projections, the index key's write and the scores of every live index key against the
step's index queries.  Median over the decode program's executions under the trace; 0 for
a stack without an indexer (``benchmark/metrics/_dsa.py``)."""

from benchmark.metrics import _dsa

NAME, UNIT, BETTER, SOURCE = "dsa_indexer_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _dsa.full_scope_ms_p50(ctx, ("indexer",))
