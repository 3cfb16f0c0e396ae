"""From a profiler trace (``.xplane.pb``) to intervals, and from intervals to
numbers.  The arithmetic is plain functions over lists of tuples, so that the
tests check it by hand on a small recorded trace.

A device's operations are the events of the line ``XLA Ops`` on the plane
``/device:TPU:<n>`` (asynchronous copies in flight sit on a line of their own
and are not work of the core); times are nanoseconds since the profile's
start, and the plane ``Task Environment`` carries that start on the unix
clock, which is how host spans are laid over the device's idle gaps.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # [start, end) in ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: by opcode, or by name where the compiler wraps one in a fusion of its own
#: (``%async-collective-start.5 = ... fusion(...), kind=kCustom``)
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
               "all-to-all", "collective-broadcast", "async-collective")
#: operations that only contain other operations of the same line
CONTAINERS = ("while", "conditional", "call")


class Op(NamedTuple):
    start: float
    end: float
    name: str  # the instruction's name, e.g. ``fusion.16``
    category: str
    opcode: str = ""
    shape: str = ""  # its result, e.g. ``bf16[16,512,4096]``
    operand: str = ""  # an asynchronous ``-done``'s ``-start``


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def base_name(name: str) -> str:
    """``%all-gather-start.12`` -> ``all-gather-start``."""
    return re.sub(r"[.:]\d+$", "", name.lstrip("%"))


def parse(text: str) -> Tuple[str, str, str, str, str]:
    """(name, category, opcode, result shape, first operand) of a device event, whose
    name on a TPU is the instruction's whole HLO text,
    ``%name = shape opcode(operands), attributes``.

    Categories: ``collective`` (by opcode, or by the name of a fusion that
    wraps one); ``mosaic-kernel`` (a custom call whose target
    is ``tpu_custom_call``; other targets are ``custom-call:<target>``);
    ``container`` (an operation that only spans others of its line);
    ``fusion:<kind>``; else the opcode."""
    name, _, rest = text.partition(" = ")
    name = name.lstrip("%")
    m = _OPCODE.search(" " + rest) if rest else None
    opcode = m.group(1) if m else base_name(name)
    shape = re.split(r"[{ ]", rest.lstrip("("), maxsplit=1)[0] if rest else ""
    operand = re.search(r"%([\w.\-]+)", rest[m.end() - 1:]) if m else None
    if any(stem.startswith(c) for stem in (opcode, base_name(name)) for c in COLLECTIVES):
        category = "collective"
    elif opcode == "custom-call":
        target = re.search(r'custom_call_target="([^"]+)"', rest)
        target = target.group(1) if target else "unknown"
        category = "mosaic-kernel" if target == "tpu_custom_call" else f"custom-call:{target}"
    elif opcode in CONTAINERS:
        category = "container"
    elif opcode == "fusion":
        kind = re.search(r"kind=(\w+)", rest)
        category = f"fusion:{kind.group(1)}" if kind else "fusion"
    else:
        category = opcode
    return name, category, opcode, shape, operand.group(1) if operand else ""


def find_trace(trace_dir: str) -> Optional[str]:
    """Newest ``.xplane.pb`` under a ``jax.profiler`` trace directory."""
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> Dict[str, Any]:
    """``{"start_unix_ns", "stop_unix_ns", "devices": {ordinal: [Op, ...]}}``;
    ``devices`` is empty when the trace holds no TPU plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict[str, Any] = {"start_unix_ns": None, "stop_unix_ns": None, "devices": {}}
    for plane in data.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            out["start_unix_ns"] = stats.get("profile_start_time")
            out["stop_unix_ns"] = stats.get("profile_stop_time")
            continue
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops: List[Op] = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                ops.append(Op(float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                              *parse(ev.name)))
        ops.sort(key=lambda o: o.start)
        out["devices"][int(m.group(1))] = ops
    return out


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint sorted cover of ``intervals``."""
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The part of ``a`` that no interval of ``b`` covers."""
    out: List[Interval] = []
    cover = union(b)
    for s, e in union(a):
        cur = s
        for cs, ce in cover:
            if ce <= cur:
                continue
            if cs >= e:
                break
            if cs > cur:
                out.append((cur, cs))
            cur = max(cur, ce)
        if cur < e:
            out.append((cur, e))
    return out


def gaps(intervals: Iterable[Interval]) -> List[Interval]:
    """Idle stretches between the first start and the last end."""
    u = union(intervals)
    return [(u[i][1], u[i + 1][0]) for i in range(len(u) - 1)]


# ---------------------------------------------------------------------------
# reductions over one device's operations
# ---------------------------------------------------------------------------


def first_device(trace: Optional[Dict[str, Any]]) -> Optional[List[Op]]:
    """Operations of the lowest-numbered device of a :func:`load` result."""
    devs = (trace or {}).get("devices") or {}
    return devs[min(devs)] if devs else None


def leaf_ops(ops: Sequence[Op]) -> List[Op]:
    """Operations that do work themselves (containers span their bodies)."""
    return [o for o in ops if o.category != "container"]


def window_of(ops: Sequence[Op]) -> Interval:
    """First start to last end: what the device's busy share is taken over."""
    return (min(o.start for o in ops), max(o.end for o in ops))


def busy_ns(ops: Sequence[Op]) -> float:
    return length((o.start, o.end) for o in leaf_ops(ops))


def collective_flights(ops: Sequence[Op]) -> List[Interval]:
    """One interval per collective: an asynchronous pair runs from the start
    of ``<op>-start`` to the end of the ``<op>-done`` that names it as its
    operand; any other collective event is its own interval."""
    flights: List[Interval] = []
    starts: Dict[str, Interval] = {}
    for o in ops:
        if o.category != "collective":
            continue
        if base_name(o.name).endswith("-start"):
            starts[o.name] = (o.start, o.end)
        elif o.operand in starts:
            flights.append((starts.pop(o.operand)[0], o.end))
        else:
            flights.append((o.start, o.end))
    return flights + list(starts.values())


def collective_ns(ops: Sequence[Op]) -> Tuple[float, float]:
    """(time a collective is in flight, the part of it during which no other
    operation runs on this device)."""
    flights = collective_flights(ops)
    others = [(o.start, o.end) for o in leaf_ops(ops) if o.category != "collective"]
    return length(flights), length(subtract(flights, others))


def category_sums(ops: Sequence[Op]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for o in leaf_ops(ops):
        out[o.category] = out.get(o.category, 0.0) + (o.end - o.start)
    return out


def top_ops(ops: Sequence[Op], n: int = 10) -> List[Tuple[str, float]]:
    """Total seconds by ``category:operation`` (an operation recurs once a
    step under one name), largest first."""
    sums: Dict[str, float] = {}
    for o in leaf_ops(ops):
        key = f"{o.category}:{o.name} {o.shape}".rstrip()[:120]
        sums[key] = sums.get(key, 0.0) + (o.end - o.start)
    return [(k, v / 1e9) for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:n]]


def attribute_gaps(ops: Sequence[Op], spans: Sequence[Tuple[float, float, str]],
                   n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps, each named after the innermost host span
    (``(start, end, name)`` on the trace's clock) that covers its middle;
    ``between_steps`` when none does."""
    out = []
    for a, b in sorted(gaps((o.start, o.end) for o in leaf_ops(ops)), key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        cover = [s for s in spans if s[0] <= mid < s[1]]
        name = min(cover, key=lambda s: s[1] - s[0])[2] if cover else "between_steps"
        out.append((name, (b - a) / 1e9))
    return out
