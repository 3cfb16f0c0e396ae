"""What the three Gated DeltaNet metrics share: device 0's time under ``gdn``
(the mixer of a hybrid stack's Gated DeltaNet layers) by the scopes the mixer
opens inside it (``in_proj``, ``conv``, ``scan``, ``gate_norm``, ``out_proj``;
PERF.md §3), read as ``_ssm.py`` reads ``ssm``.  A program without these scopes
(a model with no such layer, a parent before them) gives None: the metrics then
leave themselves out."""

import re

from benchmark.lib import scoped

GDN_SCOPES = ("in_proj", "conv", "scan", "gate_norm", "out_proj")
_WRAPPED = re.compile(r"^(?:[A-Za-z_]\w*\()+([^()]*)\)+$")


def gdn_scope(op_name):
    """The mixer's scope an operation belongs to: the first of the five below
    ``gdn`` on its path, ``"other"`` under ``gdn`` alone, None outside it."""
    inside = False
    for part in op_name.rstrip(":").split("/"):
        m = _WRAPPED.match(part)
        part = m.group(1) if m else part
        if part == "gdn":
            inside = True
        elif inside and part in GDN_SCOPES:
            return part
    return "other" if inside else None


def split_ns(sops):
    """``{(scope, phase): ns}`` of the operations under ``gdn``, forward and
    backward (a recomputed forward carries autodiff's ``transpose(`` mark and
    counts as backward); None where there is none."""
    out = {}
    for o in sops:
        scope = gdn_scope(o.op_name)
        phase = scoped.phase_of(o.op_name)
        if scope is None or phase not in ("forward", "backward"):
            continue
        out[(scope, phase)] = out.get((scope, phase), 0.0) + (o.end - o.start)
    return out or None


def of_ctx(ctx):
    """``split_ns`` of the traced run's device 0, once a run."""
    if "_gdn_split" not in ctx:
        sops = scoped.device0(ctx)
        ctx["_gdn_split"] = None if sops is None else split_ns(sops)
    return ctx["_gdn_split"]


def under(split, *scopes):
    return sum(v for (s, _), v in split.items() if not scopes or s in scopes)
