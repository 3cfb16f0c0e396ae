"""What the profiler's trace says beyond intervals: which scope of the program
each device operation belongs to, and where the host's spans lie on the
trace's own clock.

``xplane.load`` (and so ``ctx["trace"]``) keeps an operation's interval and
its HLO text.  The ``.xplane.pb`` holds more, in places ``jax.profiler``'s
``ProfileData`` does not show: every device operation's *event metadata*
carries the stats ``tf_op`` (the instruction's ``op_name``:
``jit(train_step)/transpose(jvp(layer_0))/attn/qkv_proj/dot_general:``, the
program's ``jax.named_scope`` path with the autodiff wrappers around it) and
``hlo_category``; and the host plane's lines carry the program's
``TraceAnnotation`` / ``StepTraceAnnotation`` events (the tracer's spans while
a profiler window is open).  This module reads both straight from the file,
with a wire-format reader for the few messages it needs (no protobuf schema
is installed with jax), and reduces them with plain functions over tuples that
the tests check by hand on a small recorded trace.

Where the file is: a serving run opens the profiler window itself and hands
the file's path over as ``ctx["trace_path"]``; a training run's window is the
program's, so ``window()`` asks the program where its last one went
(``galvatron_tpu.obs.flight.last_profile_window``, same process).  A program
without that function, without scopes or without annotations gives ``None``
or empty results here, never an error: every metric built on this module
then leaves itself out.
"""

from __future__ import annotations

import functools
import re
from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple

from benchmark.lib import xplane

# ---------------------------------------------------------------------------
# the names the program promises (PERF.md §3 has the table)
# ---------------------------------------------------------------------------

#: ``jax.named_scope`` names of the step program and of the cached forwards a
#: serving engine runs (``cache_write`` is theirs alone); ``layer_<i>`` reads as ``layer``
SCOPES = ("embed", "layer", "attn", "qkv_proj", "cache_write", "attn_core", "out_proj", "mlp",
          "norm", "head", "loss", "optimizer", "grad_accum", "grad_sync", "redistribute",
          "allgather_einsum", "einsum_reducescatter")
#: scopes whose work is communication by construction
COMM_SCOPES = ("grad_sync", "redistribute", "allgather_einsum", "einsum_reducescatter")
#: scopes of the model's two ends: what a depth cut inflates
HEAD_SCOPES = ("embed", "head", "loss")
#: the trainer's spans as the profiler's trace names them (``step`` is the
#: ``StepTraceAnnotation`` "train")
ANNOTATIONS = ("train", "data", "fwd_bwd", "sync", "data_produce")

_WRAPPED = re.compile(r"^(?:[A-Za-z_]\w*\()+([^()]*)\)+$")
_LAYER = re.compile(r"^layer_\d+$")


def scopes_of(op_name: str) -> Tuple[str, ...]:
    """The program's scopes on an ``op_name`` path, outermost first:
    ``jit(step)/transpose(jvp(layer_3))/attn/dot_general:`` -> ``("layer", "attn")``."""
    out = []
    for part in op_name.rstrip(":").split("/"):
        m = _WRAPPED.match(part)
        part = m.group(1) if m else part
        if _LAYER.match(part):
            part = "layer"
        # a rematerialized region repeats its path: transpose(jvp(layer_1))/
        # jvp(layer_1)/checkpoint/mlp reads ("layer", "mlp")
        if part in SCOPES and (not out or out[-1] != part):
            out.append(part)
    return tuple(out)


def is_backward(op_name: str) -> bool:
    """Autodiff marks what it transposed: the backward pass, for free."""
    return "transpose(" in op_name


def phase_of(op_name: str) -> str:
    """``optimizer`` | ``backward`` | ``forward`` | ``unscoped`` (no scope of
    the program on the path, or no ``op_name`` at all)."""
    scopes = scopes_of(op_name)
    if not scopes:
        return "unscoped"
    if "optimizer" in scopes:
        return "optimizer"
    return "backward" if is_backward(op_name) else "forward"


def model_scopes(op_name: str) -> Tuple[str, ...]:
    """:func:`scopes_of` without the micro-batch loop around the model: under
    ``grad_accum`` every operation of the model sits one level down."""
    scopes = scopes_of(op_name)
    return scopes[1:] if scopes[:1] == ("grad_accum",) and len(scopes) > 1 else scopes


def second_level(op_name: str) -> str:
    """``layer/attn``, ``layer/mlp``, ``head``, ``optimizer``: the first scope
    and, under a layer, the one below it (``grad_accum`` alone: the
    accumulation's own adds)."""
    scopes = model_scopes(op_name)
    if not scopes:
        return "unscoped"
    return "/".join(scopes[:2]) if scopes[0] == "layer" else scopes[0]


# ---------------------------------------------------------------------------
# the file: a reader for the few messages needed
# ---------------------------------------------------------------------------
# tsl/profiler/protobuf/xplane.proto, by field number:
#   XSpace.planes=1
#   XPlane.name=2 .lines=3 .event_metadata=4 (map) .stat_metadata=5 (map)
#   XLine.name=2 .timestamp_ns=3 .events=4
#   XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3 .stats=4
#   XEventMetadata.id=1 .name=2 .display_name=4 .stats=5
#   XStatMetadata.id=1 .name=2
#   XStat.metadata_id=1 .double=2 .uint64=3 .int64=4 .str=5 .bytes=6 .ref=7
#   a map entry is a message {key=1, value=2}


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one message: an int for a varint or a fixed
    field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif kind == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {kind} in an xplane message")
        yield tag >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, Any]:
    """(name, value) of one XStat; a ``ref`` resolves to the name it points at."""
    name, value = "", None
    for no, v in _fields(buf):
        if no == 1:
            name = stat_names.get(v, str(v))
        elif no in (3, 4):
            value = v
        elif no == 5:
            value = _text(v)
        elif no == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entries(plane, field: int) -> Iterator[Tuple[int, Any]]:
    for no, entry in _fields(plane):
        if no != field:
            continue
        key, value = 0, None
        for eno, ev in _fields(entry):
            if eno == 1:
                key = ev
            elif eno == 2:
                value = ev
        if value is not None:
            yield key, value


def _plane_name(plane) -> str:
    for no, v in _fields(plane):
        if no == 2:
            return _text(v)
    return ""


def _stat_names(plane) -> Dict[int, str]:
    out = {}
    for key, md in _map_entries(plane, 5):
        for no, v in _fields(md):
            if no == 2:
                out[key] = _text(v)
    return out


def _event_metadata(plane, stat_names, want: Sequence[str]) -> Dict[int, Dict[str, Any]]:
    """``{id: {"name", <wanted stats>}}`` of a plane's event metadata."""
    out = {}
    for key, md in _map_entries(plane, 4):
        row: Dict[str, Any] = {"name": ""}
        for no, v in _fields(md):
            if no == 2:
                row["name"] = _text(v)
            elif no == 5:
                sname, sval = _stat(v, stat_names)
                if sname in want:
                    row[sname] = sval
        out[key] = row
    return out


class Annotation(NamedTuple):
    start: float  # ns since the profile's start: the device operations' clock
    end: float
    name: str
    step: Optional[int]  # a ``StepTraceAnnotation``'s ``step_num``
    line: str  # the host thread's line


def _host_annotations(plane) -> List[Annotation]:
    stat_names = _stat_names(plane)
    names = {k: md["name"] for k, md in _event_metadata(plane, stat_names, ()).items()}
    wanted = {k for k, n in names.items() if n in ANNOTATIONS}
    out: List[Annotation] = []
    if not wanted:
        return out
    for no, line in _fields(plane):
        if no != 3:
            continue
        lname, t0, events = "", 0, []
        for lno, v in _fields(line):
            if lno == 2:
                lname = _text(v)
            elif lno == 3:
                t0 = v
            elif lno == 4:
                events.append(v)
        for ev in events:
            mid = off = dur = 0
            stats = []
            for eno, v in _fields(ev):
                if eno == 1:
                    mid = v
                elif eno == 2:
                    off = v
                elif eno == 3:
                    dur = v
                elif eno == 4:
                    stats.append(v)
            if mid not in wanted:
                continue
            step = None
            for st in stats:
                sname, sval = _stat(st, stat_names)
                if sname == "step_num":
                    step = int(sval)
            start = t0 + off / 1e3
            out.append(Annotation(start, start + dur / 1e3, names[mid], step, lname))
    out.sort(key=lambda a: a.start)
    return out


@functools.lru_cache(maxsize=2)
def read(path: str) -> Dict[str, Any]:
    """``{"op_names": {instruction: op_name}, "categories": {instruction:
    hlo_category}, "annotations": [Annotation, ...]}`` of one ``.xplane.pb``:
    the lowest-numbered TPU plane's event metadata, and the host planes'
    annotation events.  Read once per path (a four-chip trace is 85 MiB)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    device: Optional[Tuple[int, Any]] = None
    annotations: List[Annotation] = []
    for no, plane in _fields(space):
        if no != 1:
            continue
        name = _plane_name(plane)
        m = xplane.DEVICE_PLANE.match(name)
        if m and (device is None or int(m.group(1)) < device[0]):
            device = (int(m.group(1)), plane)
        elif name.startswith("/host:"):
            annotations += _host_annotations(plane)
    op_names: Dict[str, str] = {}
    categories: Dict[str, str] = {}
    by_text: Dict[str, str] = {}  # two programs' ``fusion.12`` differ in their HLO text
    if device is not None:
        plane = device[1]
        for md in _event_metadata(plane, _stat_names(plane), ("tf_op", "hlo_category")).values():
            instruction = xplane.parse(md["name"])[0]
            if md.get("tf_op"):
                op_names[instruction] = by_text[md["name"]] = md["tf_op"]
            if md.get("hlo_category"):
                categories[instruction] = md["hlo_category"]
    annotations.sort(key=lambda a: a.start)
    return {"op_names": op_names, "categories": categories, "annotations": annotations,
            "op_names_by_text": by_text}


def window() -> Optional[Dict[str, Any]]:
    """The program's record of its last profiler window, or None where the
    program keeps none (a parent before the function existed)."""
    try:
        from galvatron_tpu.obs import flight
    except ImportError:
        return None
    fn = getattr(flight, "last_profile_window", None)
    return fn() if fn else None


def exported_span_args(name: str) -> List[Dict[str, Any]]:
    """Arguments of the spans called ``name`` in the span file the traced run
    exported beside its profile directory (``ctx["spans"]`` keeps a span's
    ``step`` only).  Empty where there is no such file."""
    import json
    import os

    win = window()
    if not win:
        return []
    path = os.path.join(os.path.dirname(os.path.abspath(win["trace_dir"])), "spans.json")
    try:
        with open(path) as f:
            events = json.load(f)["traceEvents"]
    except (OSError, ValueError, KeyError):
        return []
    return [ev.get("args", {}) for ev in events if ev.get("name") == name and ev.get("ph") == "X"]


def trace_path(ctx) -> Optional[str]:
    """The traced run's ``.xplane.pb``: the path a serving run hands over (its
    profiler window is the benchmark's own), else the program's record of its
    last window (a training run); None where there is neither."""
    if not ctx.get("trace"):
        return None
    if ctx.get("trace_path"):
        return ctx["trace_path"]
    win = window()
    return win.get("xplane") if win else None


def of_ctx(ctx) -> Optional[Dict[str, Any]]:
    """``read`` of the traced run's file, or None: no trace, no record of the
    window, or a file the reader cannot parse."""
    path = trace_path(ctx)
    if not path:
        return None
    try:
        return read(path)
    except (OSError, ValueError, IndexError):
        return None


# ---------------------------------------------------------------------------
# reductions: plain functions over tuples
# ---------------------------------------------------------------------------


class ScopedOp(NamedTuple):
    start: float
    end: float
    name: str
    category: str
    op_name: str  # "" where the trace has none for the instruction
    hlo_category: str = ""


def scoped_ops(ops: Sequence[xplane.Op], op_names: Dict[str, str],
               categories: Optional[Dict[str, str]] = None) -> List[ScopedOp]:
    """A device's leaf operations, each with its instruction's ``op_name``."""
    categories = categories or {}
    return [ScopedOp(o.start, o.end, o.name, o.category, op_names.get(o.name, ""),
                     categories.get(o.name, ""))
            for o in xplane.leaf_ops(ops)]


def device0(ctx) -> Optional[List[ScopedOp]]:
    """Device 0's leaf operations with their ``op_name``s, or None where the
    trace has no device or the program gave its operations no scope at all."""
    if "_scoped_device0" not in ctx:  # once a run: every scope metric asks
        data = of_ctx(ctx)
        ops = xplane.first_device(ctx.get("trace"))
        sops = None
        if data is not None and ops:
            sops = scoped_ops(ops, data["op_names"], data["categories"])
            if not any(scopes_of(o.op_name) for o in sops):
                sops = None
        ctx["_scoped_device0"] = sops
    return ctx["_scoped_device0"]


def phase_ns(sops: Sequence[ScopedOp]) -> Dict[str, float]:
    """Summed durations by :func:`phase_of`; the four parts add up to the
    summed duration of all of ``sops``."""
    out = {"forward": 0.0, "backward": 0.0, "optimizer": 0.0, "unscoped": 0.0}
    for o in sops:
        out[phase_of(o.op_name)] += o.end - o.start
    return out


def head_ns(sops: Sequence[ScopedOp]) -> float:
    """Time under ``embed`` / ``head`` / ``loss``, forward and backward (the
    optimizer's part for their parameters is not in: an update's ``op_name``
    is ``optimizer/<primitive>`` and names no parameter)."""
    return sum(o.end - o.start for o in sops
               if phase_of(o.op_name) in ("forward", "backward")
               and model_scopes(o.op_name)[0] in HEAD_SCOPES)


def second_level_ns(sops: Sequence[ScopedOp]) -> Dict[Tuple[str, str], float]:
    """``{(second-level scope, phase): ns}``."""
    out: Dict[Tuple[str, str], float] = {}
    for o in sops:
        key = (second_level(o.op_name), phase_of(o.op_name))
        out[key] = out.get(key, 0.0) + (o.end - o.start)
    return out


def top_unscoped(sops: Sequence[ScopedOp], n: int = 10) -> List[Tuple[str, float, int]]:
    """(``category:instruction [op_name]``, ns, calls) of the largest operations
    that carry none of the program's scopes."""
    sums: Dict[str, List[float]] = {}
    for o in sops:
        if phase_of(o.op_name) != "unscoped":
            continue
        key = f"{o.category}:{o.name} [{o.op_name or 'no op_name'}]"
        row = sums.setdefault(key, [0.0, 0])
        row[0] += o.end - o.start
        row[1] += 1
    return [(k, v[0], int(v[1])) for k, v in
            sorted(sums.items(), key=lambda kv: -kv[1][0])[:n]]


def is_comm(o: ScopedOp) -> bool:
    """A collective by what it is (``xplane``'s category, or the trace's own
    ``hlo_category`` for a fusion that wraps one) or by where the program put
    it (a scope that is communication by construction)."""
    if o.category == "collective":
        return True
    if any(s in COMM_SCOPES for s in scopes_of(o.op_name)):
        return True
    return o.category == "fusion:kCustom" and any(
        c in o.hlo_category for c in xplane.COLLECTIVES)


def comm_ns(sops: Sequence[ScopedOp]) -> float:
    return sum(o.end - o.start for o in sops if is_comm(o))


# ---------------------------------------------------------------------------
# a window of several jitted programs (a serving run): executions by program
# ---------------------------------------------------------------------------
# A training window holds one program, a step after a step.  A serving window
# holds the decode step, prefill chunks between them, and whatever small
# programs the loop dispatches; the device plane's line ``XLA Modules`` has one
# event an execution (``jit__decode_step(<fingerprint>)``), and an operation of
# ``XLA Ops`` belongs to the execution whose event covers its start.


class Execution(NamedTuple):
    program: str  # ``_decode_step``: the module's name less ``jit_`` and the fingerprint
    start: float
    end: float
    ops: Tuple[ScopedOp, ...]  # its leaf operations


_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def program_name(module: str) -> str:
    """``jit__decode_step(9036214310235559293)`` -> ``_decode_step``."""
    return _MODULE.match(module).group(1)


def group_executions(modules: Sequence[Tuple[float, float, str]],
                     ops: Sequence[ScopedOp]) -> List[Execution]:
    """One :class:`Execution` a module event, in time order, each with the leaf
    operations that start inside it; an operation outside every module event
    (the window opened or closed inside its execution) is left out."""
    import bisect

    modules = sorted(modules)
    starts = [m[0] for m in modules]
    mine: List[List[ScopedOp]] = [[] for _ in modules]
    for o in ops:
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and o.start < modules[i][1] and o.category != "container":
            mine[i].append(o)
    return [Execution(program_name(name), a, b, tuple(its))
            for (a, b, name), its in zip(modules, mine)]


def device0_lines(path: str) -> Tuple[List[Tuple[float, float, str]], List[Tuple[float, float, str]]]:
    """(module events, operations) of the lowest-numbered TPU plane, each
    (start ns, end ns, the event's name: a module's, an operation's HLO text)."""
    from jax.profiler import ProfileData

    planes = [p for p in ProfileData.from_file(path).planes if xplane.DEVICE_PLANE.match(p.name)]
    modules: List[Tuple[float, float, str]] = []
    ops: List[Tuple[float, float, str]] = []
    if planes:
        plane = min(planes, key=lambda p: int(xplane.DEVICE_PLANE.match(p.name).group(1)))
        for line in plane.lines:
            into = {"XLA Modules": modules, xplane.OPS_LINE: ops}.get(line.name)
            if into is not None:
                into += [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns), ev.name)
                         for ev in line.events]
    return modules, ops


def executions(ctx) -> Optional[List[Execution]]:
    """Device 0's executions in the traced window, every operation with the
    ``op_name`` its own HLO text carries (two programs' ``fusion.12`` stay
    apart); None without a trace, or where the trace has no module events."""
    if "_executions" not in ctx:  # once a run: every reader of the decode step asks
        path, out = trace_path(ctx), None
        data = of_ctx(ctx) if path else None
        if data is not None:
            modules, ops = device0_lines(path)
            by_text = data["op_names_by_text"]
            sops = []
            for start, end, text in ops:
                name, category = xplane.parse(text)[:2]
                sops.append(ScopedOp(start, end, name, category, by_text.get(text, ""),
                                     data["categories"].get(name, "")))
            out = group_executions(modules, sops) or None
        ctx["_executions"] = out
    return ctx["_executions"]


def scope_path(op_name: str) -> str:
    """``layer/attn/cache_write``, ``head``, ``unscoped``: every scope of the
    program on the path, so a scope a later PR adds under a known one shows."""
    return "/".join(scopes_of(op_name)) or "unscoped"


def busy_ns_of(ex: Execution) -> float:
    return xplane.length((o.start, o.end) for o in ex.ops)


def scope_category_ns(ops: Sequence[ScopedOp]) -> Dict[str, Dict[str, float]]:
    """``{scope path: {category: summed duration}}`` of some operations."""
    out: Dict[str, Dict[str, float]] = {}
    for o in ops:
        cats = out.setdefault(scope_path(o.op_name), {})
        cats[o.category] = cats.get(o.category, 0.0) + (o.end - o.start)
    return out


def scope_ns(ex: Execution) -> Dict[str, float]:
    """``{scope path: summed duration}`` of one execution's operations."""
    return {key: sum(cats.values()) for key, cats in scope_category_ns(ex.ops).items()}


def by_program(execs: Sequence[Execution]) -> Dict[str, List[Execution]]:
    out: Dict[str, List[Execution]] = {}
    for ex in execs:
        out.setdefault(ex.program, []).append(ex)
    return out


def program_table(execs: Sequence[Execution]) -> List[str]:
    """The window's programs, most device time first: executions, median busy
    ms of one, and under it every scope path with its mean ms an execution and
    its largest categories.  Every program and every scope in the window is
    listed, so one a later PR adds (a device sampler's) is printed without an
    edit here."""
    from benchmark.lib.stats import percentile

    lines = []
    for name, runs in sorted(by_program(execs).items(),
                             key=lambda kv: -sum(map(busy_ns_of, kv[1]))):
        lines.append(f"program {name}: {len(runs)} executions in the window, busy "
                     f"{percentile([busy_ns_of(ex) / 1e6 for ex in runs], 50):.3f} ms each "
                     f"(median; module event "
                     f"{percentile([(ex.end - ex.start) / 1e6 for ex in runs], 50):.3f} ms)")
        table = scope_category_ns([o for ex in runs for o in ex.ops])
        for key, cats in sorted(table.items(), key=lambda kv: -sum(kv[1].values())):
            top = sorted(cats.items(), key=lambda kv: -kv[1])[:3]
            lines.append(f"  {key:28s} {sum(cats.values()) / 1e6 / len(runs):8.3f} ms  "
                         + "; ".join(f"{c} {v / 1e6 / len(runs):.3f}" for c, v in top))
    return lines


def phase_ms_per_step(ctx, phase: str) -> Optional[float]:
    """What the three phase metrics return."""
    sops = device0(ctx)
    return None if sops is None else phase_ns(sops)[phase] / 1e6 / ctx["n_profiled"]


def kernel_ms_per_step(ctx, prefix: str) -> Optional[float]:
    """What the kernel metrics return; says the kernels' names and calls a step."""
    ops = xplane.first_device(ctx.get("trace"))
    ns, calls = kernel_ns(ops or [], prefix)
    if not calls:
        return None
    names = sorted({xplane.base_name(o.name) for o in ops if o.name.startswith(prefix)})
    ctx["say"](f"kernels: {calls / ctx['n_profiled']:g} {prefix}* calls a step on device 0 "
               f"({', '.join(names)})")
    return ns / 1e6 / ctx["n_profiled"]


def kernel_ns(ops: Sequence[xplane.Op], prefix: str) -> Tuple[float, int]:
    """(summed duration, calls) of the operations whose instruction name
    starts with ``prefix``: a Pallas kernel's ``name=`` is its instruction's."""
    hit = [o for o in ops if o.name.startswith(prefix)]
    return sum(o.end - o.start for o in hit), len(hit)


def sync_lags_ns(annotations: Sequence[Annotation], op_ends: Sequence[float]) -> List[float]:
    """For each ``sync`` annotation, its end minus the end of the last device
    operation that ended since the previous ``sync`` ended: how long after the
    device finished a step the host knew.  ``op_ends`` sorted ascending."""
    import bisect

    lags, prev = [], float("-inf")
    for a in annotations:
        if a.name != "sync":
            continue
        i = bisect.bisect_right(op_ends, a.end)
        if i and op_ends[i - 1] > prev:
            lags.append(a.end - op_ends[i - 1])
        prev = a.end
    return lags


# ---------------------------------------------------------------------------
# reductions over the program's exported spans (``ctx["spans"]`` rows)
# ---------------------------------------------------------------------------


def first_step(setup_spans: Sequence[Dict[str, Any]]) -> Optional[int]:
    """The call's first step (what ``compile_s`` times), or None."""
    return min((s["step"] for s in setup_spans
                if s["name"] == "step" and s["step"] is not None), default=None)


def covered_s(spans: Sequence[Dict[str, Any]], names: Sequence[str]) -> float:
    """Seconds covered by the spans called one of ``names`` (their union: a
    trace of a jitted function holds the traces of the functions it calls)."""
    return xplane.length((s["start"], s["end"]) for s in spans if s["name"] in names)


def self_s(step: Dict[str, Any], spans: Sequence[Dict[str, Any]]) -> float:
    """A ``step`` span's duration minus what the other spans of its step cover
    of it."""
    inside = [(max(s["start"], step["start"]), min(s["end"], step["end"]))
              for s in spans if s is not step and s["step"] == step["step"]
              and s["name"] != "step"]
    return (step["end"] - step["start"]) - xplane.length(inside)
