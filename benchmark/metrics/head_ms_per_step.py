"""Device 0's time a step under ``embed``, ``head`` and ``loss``, forward and
backward: the model's two ends, which a depth cut inflates (two layers beside a
full vocabulary).  Their parameters' part of the optimizer is NOT in: an
update's ``op_name`` is ``optimizer/<primitive>`` and names no parameter; the
printed line says so."""

from benchmark.lib import scoped

NAME, UNIT, BETTER, SOURCE = "head_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "tokens_per_s_per_chip"


def compute(ctx):
    sops = scoped.device0(ctx)
    if sops is None:
        return None
    ms = scoped.head_ns(sops) / 1e6 / ctx["n_profiled"]
    ctx["say"](f"head: embed + head + loss, forward and backward, {ms:.3f} ms a step; their "
               "parameters' optimizer updates are not in (op_name names no parameter)")
    return ms
