"""What the four MoE metrics share: device 0's time under ``mlp`` by the
scopes a dropless top-k MoE layer opens inside it (``router``, ``dispatch``,
``experts``, ``combine``; PERF.md §3).  ``lib/scoped.py`` knows ``mlp`` and
not what is below it, so the path is read here.  A program without these
scopes (a dense model, a parent before them) gives None: the metrics then
leave themselves out."""

import re

from benchmark.lib import scoped

MOE_SCOPES = ("router", "dispatch", "experts", "combine")
_WRAPPED = re.compile(r"^(?:[A-Za-z_]\w*\()+([^()]*)\)+$")


def moe_scope(op_name):
    """The MoE scope an operation under ``mlp`` belongs to, or ``"other"``."""
    for part in op_name.rstrip(":").split("/"):
        m = _WRAPPED.match(part)
        part = m.group(1) if m else part
        if part in MOE_SCOPES:
            return part
    return "other"


def split_ns(sops):
    """``{(scope, phase): ns}`` of the operations under ``mlp``, forward and
    backward; None where none carries a MoE scope."""
    out = {}
    for o in sops:
        phase = scoped.phase_of(o.op_name)
        if phase not in ("forward", "backward") or "mlp" not in scoped.scopes_of(o.op_name):
            continue
        key = (moe_scope(o.op_name), phase)
        out[key] = out.get(key, 0.0) + (o.end - o.start)
    if not any(k[0] in MOE_SCOPES for k in out):
        return None
    return out


def of_ctx(ctx):
    """``split_ns`` of the traced run's device 0, once a run."""
    if "_moe_split" not in ctx:
        sops = scoped.device0(ctx)
        ctx["_moe_split"] = None if sops is None else split_ns(sops)
    return ctx["_moe_split"]


def under(split, scope=None):
    return sum(v for (s, _), v in split.items() if scope is None or s == scope)
