"""What the three state-space metrics share: device 0's time under ``ssm`` (the
Mamba-2 mixer of a hybrid stack's state-space layers) by the scopes the mixer
opens inside it (``in_proj``, ``conv``, ``scan``, ``gate_norm``, ``out_proj``;
PERF.md §3).  ``lib/scoped.py`` knows ``layer`` and not ``ssm``, so the path is
read here.  A program without these scopes (a model with no such layer, a
parent before them) gives None: the metrics then leave themselves out."""

import re

from benchmark.lib import scoped

SSM_SCOPES = ("in_proj", "conv", "scan", "gate_norm", "out_proj")
_WRAPPED = re.compile(r"^(?:[A-Za-z_]\w*\()+([^()]*)\)+$")


def ssm_scope(op_name):
    """The mixer's scope an operation belongs to: the first of the five below
    ``ssm`` on its path, ``"other"`` under ``ssm`` alone, None outside it."""
    inside = False
    for part in op_name.rstrip(":").split("/"):
        m = _WRAPPED.match(part)
        part = m.group(1) if m else part
        if part == "ssm":
            inside = True
        elif inside and part in SSM_SCOPES:
            return part
    return "other" if inside else None


def split_ns(sops):
    """``{(scope, phase): ns}`` of the operations under ``ssm``, forward and
    backward (a recomputed forward carries autodiff's ``transpose(`` mark and
    counts as backward); None where there is none."""
    out = {}
    for o in sops:
        scope = ssm_scope(o.op_name)
        phase = scoped.phase_of(o.op_name)
        if scope is None or phase not in ("forward", "backward"):
            continue
        out[(scope, phase)] = out.get((scope, phase), 0.0) + (o.end - o.start)
    return out or None


def of_ctx(ctx):
    """``split_ns`` of the traced run's device 0, once a run."""
    if "_ssm_split" not in ctx:
        sops = scoped.device0(ctx)
        ctx["_ssm_split"] = None if sops is None else split_ns(sops)
    return ctx["_ssm_split"]


def under(split, *scopes):
    return sum(v for (s, _), v in split.items() if not scopes or s in scopes)
