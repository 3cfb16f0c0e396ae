"""Device 0's time a step under ``gdn``: the Gated DeltaNet mixers of the
linear-attention layers, forward + backward (under full-layer recomputation the
replayed forward is in the backward's part).  The split by the mixer's five
scopes and by phase is printed.  The mixers' optimizer update is not in (an
update's ``op_name`` names no parameter)."""

from benchmark.metrics import _gdn

NAME, UNIT, BETTER, SOURCE = "gdn_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "tokens_per_s_per_chip"


def compute(ctx):
    split = _gdn.of_ctx(ctx)
    if split is None:
        return None
    n = ctx["n_profiled"]
    for scope in _gdn.GDN_SCOPES + ("other",):
        fwd, bwd = (split.get((scope, ph), 0.0) / 1e6 / n for ph in ("forward", "backward"))
        ctx["say"](f"  gdn scope {scope}: forward {fwd:.3f}, backward {bwd:.3f} ms a step")
    return _gdn.under(split) / 1e6 / n
