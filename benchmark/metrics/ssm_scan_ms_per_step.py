"""The part of ``ssm_ms_per_step`` under ``conv`` + ``scan``: the causal
depthwise convolution and the chunked SSD scan (with softplus, the decays and
the ``D x`` skip), forward + backward: what a state-space layer runs that a
Transformer layer does not.  The rest of ``ssm`` is two projections and the
gated norm."""

from benchmark.metrics import _ssm

NAME, UNIT, BETTER, SOURCE = "ssm_scan_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "tokens_per_s_per_chip"


def compute(ctx):
    split = _ssm.of_ctx(ctx)
    if split is None:
        return None
    return _ssm.under(split, "conv", "scan") / 1e6 / ctx["n_profiled"]
