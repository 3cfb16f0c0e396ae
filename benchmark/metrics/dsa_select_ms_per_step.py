"""Device time of ONE decode step under ``select`` of the full layers (PR 65): the exact
top-k of the indexer's scores and the mask of the selected keys that ``attn_core`` attends under.
Median over the decode program's executions under the trace; 0 for a stack without an
indexer (``benchmark/metrics/_dsa.py``)."""

from benchmark.metrics import _dsa

NAME, UNIT, BETTER, SOURCE = "dsa_select_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _dsa.full_scope_ms_p50(ctx, ("select",))
