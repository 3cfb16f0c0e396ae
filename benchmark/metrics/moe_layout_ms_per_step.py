"""Device 0's time a step under ``mlp/dispatch/layout``: where a dropless top-k
MoE layer's (token, expert) pairs get their rows in the expert-sorted buffer
(`models/moe.sorted_layout` / `held_layout`), forward + backward (under
full-layer recomputation the replayed layout lands in the backward's part).  The
split by phase is printed.  A program without the scope (a dense model, a parent
before PR 67) gives None."""

from benchmark.lib import scoped

NAME, UNIT, BETTER, SOURCE = "moe_layout_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "tokens_per_s_per_chip"

PATH = ("mlp", "dispatch", "layout")


def under_layout(op_name):
    """Whether ``PATH``'s scopes lie on an operation's path, in that order (a part's
    wrappers, ``transpose(jvp(..))``, taken off by `lib/scoped.py`'s own pattern: it
    knows ``mlp`` and not what is below it)."""
    parts = (scoped._WRAPPED.sub(r"\1", part) for part in op_name.rstrip(":").split("/"))
    return all(scope in parts for scope in PATH)  # (``in`` consumes: an ordered search)


def split_ns(sops):
    """``{phase: ns}`` of the operations under the layout's scope, forward and
    backward; None where there is none."""
    out = {}
    for o in sops:
        phase = scoped.phase_of(o.op_name)
        if phase in ("forward", "backward") and under_layout(o.op_name):
            out[phase] = out.get(phase, 0.0) + (o.end - o.start)
    return out or None


def compute(ctx):
    sops = scoped.device0(ctx)
    split = None if sops is None else split_ns(sops)
    if split is None:
        return None
    n = ctx["n_profiled"]
    fwd, bwd = (split.get(ph, 0.0) / 1e6 / n for ph in ("forward", "backward"))
    ctx["say"](f"  moe layout: forward {fwd:.3f}, backward {bwd:.3f} ms a step")
    return fwd + bwd
