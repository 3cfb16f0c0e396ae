"""Device 0's time a step under ``mlp`` in a model whose MLP is a dropless
top-k MoE layer: router, dispatch (top-k, sort, gather), experts (the grouped
GEMMs and SwiGLU) and combine, forward + backward.  The split by scope and
phase is printed.  The experts' optimizer update is not in (an update's
``op_name`` names no parameter)."""

from benchmark.metrics import _moe

NAME, UNIT, BETTER, SOURCE = "moe_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "tokens_per_s_per_chip"


def compute(ctx):
    split = _moe.of_ctx(ctx)
    if split is None:
        return None
    n = ctx["n_profiled"]
    for scope in _moe.MOE_SCOPES + ("other",):
        fwd, bwd = (split.get((scope, ph), 0.0) / 1e6 / n for ph in ("forward", "backward"))
        ctx["say"](f"  moe scope {scope}: forward {fwd:.3f}, backward {bwd:.3f} ms a step")
    return _moe.under(split) / 1e6 / n
