#!/usr/bin/env python3
"""Device time of a traced serving run by the cached forward's scopes, by hand.

    python experiments/serve_scope_table.py <run's --out directory or a .xplane.pb>

``benchmark/run.py --workload <serving cell> --trace 1 --out DIR`` keeps the
profiler window's ``.xplane.pb`` under ``DIR/profile``.  This reads device 0's
operations from it, gives each its ``op_name`` (the trace's own ``tf_op`` stat,
matched by the event's whole HLO text, so that two programs' ``fusion.12`` stay
apart), and prints, for every jitted program in the window, ms an execution by
scope path (``embed``; ``layer/attn/{qkv_proj,cache_write,attn_core,out_proj}``;
``layer/mlp``; ``layer/norm``; ``head``) and under each scope by category, with
its largest operations.  No benchmark reader does this yet for a serving run
(``benchmark/lib/scoped.of_ctx`` finds the trace through the program's own
profiler window; a serving run's window is the benchmark's: ROADMAP B10 l).
Needs no chip: the file is read with jax's ``ProfileData`` on any backend.
"""

from __future__ import annotations

import os
import re
import sys
from collections import Counter, defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import scoped, xplane  # noqa: E402

NAMES = ("embed", "attn", "qkv_proj", "cache_write", "attn_core", "out_proj", "mlp", "norm", "head")


def scope_path(op_name: str) -> tuple:
    """(program, scope path) of an ``op_name``: ``jit(_decode_step)/layer_3/attn/
    cache_write/dynamic_update_slice`` -> ``("_decode_step", "layer/attn/cache_write")``."""
    parts = op_name.rstrip(":").split("/")
    m = re.match(r"^jit\((\w+)\)$", parts[0]) if parts else None
    path = []
    for part in parts[1:]:
        part = "layer" if re.fullmatch(r"layer_\d+", part) else part
        if (part == "layer" or part in NAMES) and (not path or path[-1] != part):
            path.append(part)
    return (m.group(1) if m else "(no op_name)"), "/".join(path) or "unscoped"


def device_ops(path: str):
    """Device 0's leaf operations as (ns, category, shape, instruction, op_name)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = [(scoped._plane_name(p), p) for no, p in scoped._fields(space) if no == 1]
    name0, plane0 = min((x for x in planes if xplane.DEVICE_PLANE.match(x[0])),
                        key=lambda x: int(xplane.DEVICE_PLANE.match(x[0]).group(1)))
    by_text = {row["name"]: row.get("tf_op", "") for row in scoped._event_metadata(
        plane0, scoped._stat_names(plane0), ("tf_op",)).values()}
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != name0:
            continue
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            for ev in line.events:
                name, category, _, shape, _ = xplane.parse(ev.name)
                if category != "container":
                    out.append((float(ev.duration_ns), category, shape, name,
                                by_text.get(ev.name, "")))
    return out


def main(argv) -> int:
    path = argv[1]
    if os.path.isdir(path):
        path = xplane.find_trace(os.path.join(path, "profile")) or xplane.find_trace(path)
    ops = device_ops(path)
    programs = defaultdict(list)
    for op in ops:
        programs[scope_path(op[4])[0]].append(op)
    for program, mine in sorted(programs.items(), key=lambda kv: -sum(o[0] for o in kv[1])):
        # an instruction runs once an execution: the commonest one counts them
        runs = Counter(o[3] for o in mine).most_common(1)[0][1]
        total = sum(o[0] for o in mine)
        print(f"\n{program}: {runs} executions in the window, {total / runs / 1e6:.3f} ms each")
        by_scope = defaultdict(list)
        for o in mine:
            by_scope[scope_path(o[4])[1]].append(o)
        for scope, its in sorted(by_scope.items(), key=lambda kv: -sum(o[0] for o in kv[1])):
            cats = Counter()
            for o in its:
                cats[o[1]] += o[0]
            ms = sum(o[0] for o in its) / runs / 1e6
            print(f"  {scope:28s} {ms:8.3f} ms ({100 * ms * runs * 1e6 / total:5.1f}%)  "
                  + "; ".join(f"{c} {v / runs / 1e6:.3f}" for c, v in cats.most_common(5)))
            tops, calls = Counter(), Counter()
            for o in its:
                tops[(o[1], o[2])] += o[0]
                calls[(o[1], o[2])] += 1
            for (cat, shape), v in tops.most_common(4):
                print(f"      {v / runs / 1e6:7.3f} ms  {calls[cat, shape] / runs:6.1f} x {cat} {shape}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
