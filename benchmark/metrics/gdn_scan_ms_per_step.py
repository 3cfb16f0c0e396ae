"""The part of ``gdn_ms_per_step`` under ``conv`` + ``scan``: the causal
depthwise convolution and the chunked gated delta rule (with the L2 norms, the
decays, the triangular solve a chunk and the carried state), forward + backward:
what a Gated DeltaNet layer runs that a Transformer layer does not.  The rest of
``gdn`` is the projections and the gated norm."""

from benchmark.metrics import _gdn

NAME, UNIT, BETTER, SOURCE = "gdn_scan_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "tokens_per_s_per_chip"


def compute(ctx):
    split = _gdn.of_ctx(ctx)
    if split is None:
        return None
    return _gdn.under(split, "conv", "scan") / 1e6 / ctx["n_profiled"]
