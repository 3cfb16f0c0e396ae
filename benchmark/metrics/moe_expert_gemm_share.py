"""The part of ``moe_ms_per_step`` spent under ``experts``: the grouped GEMMs
and the SwiGLU between them, forward + backward.  The rest is routing, sorting
and moving rows, which a dense MLP does not pay."""

from benchmark.metrics import _moe

NAME, UNIT, BETTER, SOURCE = "moe_expert_gemm_share", "%", "higher", "device_trace"
LAYER, MOVES = "model step", "tokens_per_s_per_chip"


def compute(ctx):
    split = _moe.of_ctx(ctx)
    if split is None or not _moe.under(split):
        return None
    return 100.0 * _moe.under(split, "experts") / _moe.under(split)
