"""Span tracer: nestable named host-side spans with Perfetto export.

The reference's observability is print-based (SURVEY §5); production
trainers (MegaScale §5, NSDI '24) treat a per-step span timeline as a
first-class subsystem. This module is the host half of that layer:

- ``tracer.span("fwd_bwd", step=i)`` — a nestable context manager recording
  wall-clock spans into a bounded in-memory ring (thread-aware: concurrent
  threads get their own nesting stacks and their own timeline tracks).
- ``Span.sync(value)`` — an explicit ``jax.block_until_ready`` measurement
  boundary, so a span can close on *device completion* rather than dispatch
  return. Tracing OFF is the hot-path default and adds **zero** host syncs:
  ``tracer.span`` returns a no-op singleton without reading the clock.
- ``chrome_trace(spans)`` / ``export_chrome_trace(path)`` — Chrome
  trace-event JSON (the format Perfetto and chrome://tracing load).
- one instrumentation point, two sinks: while a ``jax.profiler`` window is
  open (``tracer.profiling``, set by ``obs/flight.ProfilerWindow`` and the
  trainer's whole-run ``--trace_dir`` capture) every span also opens a
  ``jax.profiler.TraceAnnotation`` of its name, and the trainer's ``step``
  span a ``StepTraceAnnotation("train", step_num=...)`` — the host's rows
  land on the ``python`` line of the profiler's own ``.xplane.pb``, on the
  clock the device's operations are on.
- what the runtime does behind the loop's back, as spans (tracer ON only):
  ``jax_trace`` / ``jax_lower`` / ``jax_compile`` from the ``jax.monitoring``
  duration events (every trace, lowering, backend compile or cache load —
  ``jax_compile`` carries ``hit`` and ``retrieval_s``), and ``gc`` from
  ``gc.callbacks``. Each carries the ``step`` of the span open on its
  thread, so a compile or a collection inside a training step names it.
- synthetic schedule spans (``emit_tick_spans``) — pipeline schedules run
  inside ONE jitted clocked scan, so no host probe can observe per-tick
  activity; instead the schedule's exact clock model (the same index
  arithmetic the scan executes — ``gpipe_schedule_ticks`` /
  ``pipedream_schedule_ticks``) is rendered onto the measured step window,
  one track per stage. Gaps on a stage track are the schedule's bubbles.
  These spans are labeled ``synthetic: true``: they are the schedule's
  lockstep model scaled to the measured step, not a device-side measurement
  (the XLA op timeline for that lives in ``--trace_dir``/``--profile_steps``).

The module-level ``tracer`` singleton is what the trainer, checkpoint layer,
search engine, and serving engine all record into — enable it once
(``tracer.enable()``) and every subsystem's spans land on one timeline.
"""

from __future__ import annotations

import gc
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

# process-wide pid for the trace; Chrome trace groups tracks by (pid, tid)
_PID = os.getpid()


class _NullSpan:
    """Singleton no-op span: returned when tracing is disabled so the hot
    path costs one attribute read and no clock access, no allocation, and —
    critically — ``sync`` does NOT block (tracing off ⇒ zero host syncs)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def sync(self, value=None):
        return value

    def set(self, **attrs):
        return self


_NULL_SPAN = _NullSpan()

#: collections shorter than this leave no ``gc`` span: a trace-and-lower makes
#: hundreds of young-generation passes of ~0.1 ms that explain no slow step
#: and would push what does out of the ring
GC_SPAN_MIN_S = 1e-3


def _annotation(name: str, args: Dict[str, Any]):
    """The span's twin in the profiler's trace. The trainer's ``step`` span
    is the profiler's step boundary too (xprof groups device work by it)."""
    if name == "step" and "step" in args:
        return jax.profiler.StepTraceAnnotation("train", step_num=args["step"])
    return jax.profiler.TraceAnnotation(name)


class Span:
    """One live span; records itself into the tracer ring on ``__exit__``."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_tid", "_tname", "_synced", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._t0 = 0.0
        self._synced = False
        self._ann = None
        t = threading.current_thread()
        self._tid = t.ident or 0
        self._tname = t.name

    def __enter__(self):
        self._tracer._stack_for_thread().append(self)
        if self._tracer.profiling:
            self._ann = _annotation(self.name, self.args)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def sync(self, value=None):
        """Block until ``value`` (a jax array/tree) is device-complete, so
        the span measures realized compute, not dispatch. Returns ``value``."""
        if value is not None:
            jax.block_until_ready(value)
        self._synced = True
        return value

    def set(self, **attrs):
        self.args.update(attrs)
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = self._tracer._stack_for_thread()
        if stack:
            stack.pop()
        args = self.args
        if self._synced:
            args = {**args, "synced": True}
        if exc_type is not None:
            args = {**args, "error": exc_type.__name__}
        self._tracer._record(
            {
                "name": self.name,
                "ph": "X",
                "ts": self._tracer.pc_to_us(self._t0),
                "dur": (t1 - self._t0) * 1e6,
                "tid": self._tid,
                "tname": self._tname,
                "depth": len(stack),
                "args": args,
            }
        )
        return False


class Tracer:
    """Thread-aware span recorder over a bounded ring.

    ``enabled`` gates everything: disabled (the default), ``span``/``instant``
    return/do nothing without touching the clock. The ring is a
    ``deque(maxlen=capacity)`` — the flight recorder's "last N spans before
    the crash" is exactly its contents (obs/flight.py dumps it)."""

    def __init__(self, capacity: int = 4096):
        self.enabled = False
        #: a jax.profiler window is open: spans also open TraceAnnotations
        self.profiling = False
        self._ring: deque = deque(maxlen=capacity)
        self._local = threading.local()
        self._epoch_pc = time.perf_counter()
        self._epoch_wall = time.time()

    # -- lifecycle ----------------------------------------------------------

    def enable(self, capacity: Optional[int] = None) -> "Tracer":
        if capacity is not None and capacity != self._ring.maxlen:
            self._ring = deque(self._ring, maxlen=max(16, capacity))
        if self is tracer:
            # the process's hooks feed the process-wide tracer alone
            if not self.enabled:
                gc.callbacks.append(self._on_gc)
            _install_jax_listeners()
        self.enabled = True
        return self

    def disable(self) -> "Tracer":
        if self.enabled and self is tracer:
            gc.callbacks.remove(self._on_gc)
        self.enabled = False
        self.profiling = False
        return self

    def clear(self) -> None:
        self._ring.clear()

    # -- recording ----------------------------------------------------------

    def span(self, name: str, **attrs):
        """``with tracer.span("step", step=i) as sp: ...`` — no-op singleton
        when disabled (zero clock reads, zero syncs)."""
        if not self.enabled:
            return _NULL_SPAN
        return Span(self, name, attrs)

    def instant(self, name: str, **attrs) -> None:
        """Point event (anomaly skips, fallbacks, emergency saves): shows as
        an instant marker on the timeline and in flight dumps."""
        if not self.enabled:
            return
        t = threading.current_thread()
        self._record(
            {
                "name": name,
                "ph": "i",
                "ts": self.pc_to_us(time.perf_counter()),
                "tid": t.ident or 0,
                "tname": t.name,
                "args": attrs,
            }
        )

    def _record(self, rec: Dict[str, Any]) -> None:
        # deque.append with maxlen is atomic in CPython — no lock on the hot
        # path; snapshot() copies defensively for readers
        self._ring.append(rec)

    def _stack_for_thread(self) -> List[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def current_step(self) -> Optional[int]:
        """``step`` of the innermost span open on this thread that has one."""
        for sp in reversed(self._stack_for_thread()):
            if "step" in sp.args:
                return sp.args["step"]
        return None

    def record_span(self, name: str, dur_s: float, **attrs) -> None:
        """A span that ended now and lasted ``dur_s``, reported after the
        fact (a ``jax.monitoring`` duration event, a finished collection).
        Carries the ``step`` of the span open on this thread, if any."""
        if not self.enabled:
            return
        step = self.current_step()
        if step is not None:
            attrs["step"] = step
        t = threading.current_thread()
        self._record(
            {
                "name": name,
                "ph": "X",
                "ts": self.pc_to_us(time.perf_counter() - dur_s),
                "dur": dur_s * 1e6,
                "tid": t.ident or 0,
                "tname": t.name,
                "depth": len(self._stack_for_thread()),
                "args": attrs,
            }
        )

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        # the collector runs on whichever thread tripped it, start and stop
        # on the same one
        if phase == "start":
            self._local.gc_t0 = time.perf_counter()
            return
        t0 = getattr(self._local, "gc_t0", None)
        if t0 is None:
            return
        self._local.gc_t0 = None
        dur = time.perf_counter() - t0
        if dur >= GC_SPAN_MIN_S:
            self.record_span(
                "gc", dur,
                generation=info.get("generation"), collected=info.get("collected"),
            )

    def pc_to_us(self, pc: float) -> float:
        return (pc - self._epoch_pc) * 1e6

    # -- readout ------------------------------------------------------------

    def snapshot(self) -> List[Dict[str, Any]]:
        return list(self._ring)

    @property
    def epoch_wall(self) -> float:
        """Wall-clock time of the tracer's perf_counter epoch (ts=0)."""
        return self._epoch_wall

    def export_chrome_trace(self, path: str) -> str:
        doc = chrome_trace(self.snapshot())
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


#: the process-wide tracer every subsystem records into
tracer = Tracer()


# ---------------------------------------------------------------------------
# jax.monitoring -> spans
# ---------------------------------------------------------------------------

#: duration events of jax 0.9 -> span names. They fire on a trace, a lowering
#: and a backend compile (or its cache load) only: never in a warm step.
_JAX_DURATION_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lower",
    "/jax/core/compile/backend_compile_duration": "jax_compile",
}
_CACHE_HITS = "/jax/compilation_cache/cache_hits"
_CACHE_MISSES = "/jax/compilation_cache/cache_misses"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
#: a jitted function's trace holds one short trace for each jitted helper it
#: calls (hundreds under a train step); those under this floor leave no span
JAX_TRACE_SPAN_MIN_S = 1e-3
_jax_listeners_installed = False
# what the persistent cache said during the backend compile now running on
# this thread; its events fire inside the compile's own duration event
_compile_local = threading.local()


def _on_jax_event(event: str, **_kw) -> None:
    if not tracer.enabled:
        return
    if event == _CACHE_HITS:
        _compile_local.hit = True
    elif event == _CACHE_MISSES:
        _compile_local.hit = False


def _on_jax_duration(event: str, duration_secs: float, **kw) -> None:
    if not tracer.enabled:
        return
    if event == _CACHE_RETRIEVAL:
        _compile_local.retrieval_s = duration_secs
        return
    name = _JAX_DURATION_SPANS.get(event)
    if name is None or (name == "jax_trace" and duration_secs < JAX_TRACE_SPAN_MIN_S):
        return
    attrs: Dict[str, Any] = {}
    if kw.get("fun_name"):
        attrs["fun_name"] = str(kw["fun_name"])
    if name == "jax_compile":
        # hit None: the persistent cache was not consulted for this program
        attrs["hit"] = getattr(_compile_local, "hit", None)
        attrs["retrieval_s"] = getattr(_compile_local, "retrieval_s", None)
        _compile_local.hit = _compile_local.retrieval_s = None
    tracer.record_span(name, float(duration_secs), **attrs)


def _install_jax_listeners() -> None:
    """Once per process, at the singleton's first ``enable()``; the listeners
    stay registered and return at once while the tracer is off."""
    global _jax_listeners_installed
    if _jax_listeners_installed:
        return
    _jax_listeners_installed = True
    jax.monitoring.register_event_listener(_on_jax_event)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


# ---------------------------------------------------------------------------
# Chrome trace-event / Perfetto export
# ---------------------------------------------------------------------------


def chrome_trace(
    spans: Sequence[Dict[str, Any]],
    pid: Optional[int] = None,
    ts_offset_us: float = 0.0,
    process_name: Optional[str] = None,
) -> Dict[str, Any]:
    """Render recorded spans as a Chrome trace-event JSON object (Perfetto /
    chrome://tracing load this directly). Span records are the tracer's ring
    schema; thread/track names become thread_name metadata events.

    ``pid``/``ts_offset_us``/``process_name`` exist for the multi-process
    merge (obs/correlate.py): each source dump renders under its own pid
    (its own track group) with its timestamps shifted onto the shared
    reference clock. Defaults reproduce the single-process export exactly."""
    use_pid = _PID if pid is None else int(pid)
    events: List[Dict[str, Any]] = []
    named: Dict[Tuple[int, int], str] = {}
    for rec in spans:
        tid = int(rec.get("tid", 0))
        ev: Dict[str, Any] = {
            "name": rec["name"],
            "ph": rec.get("ph", "X"),
            "pid": use_pid,
            "tid": tid,
            "ts": round(float(rec["ts"]) + ts_offset_us, 3),
            "args": dict(rec.get("args", {})),
        }
        if ev["ph"] == "X":
            ev["dur"] = round(float(rec.get("dur", 0.0)), 3)
        elif ev["ph"] == "i":
            ev["s"] = "t"
        events.append(ev)
        tname = rec.get("tname")
        if tname and named.get((use_pid, tid)) != tname:
            named[(use_pid, tid)] = tname
    for (epid, tid), tname in named.items():
        events.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": epid,
                "tid": tid,
                "args": {"name": tname},
            }
        )
    if process_name:
        events.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": use_pid,
                "tid": 0,
                "args": {"name": process_name},
            }
        )
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# Synthetic pipeline-schedule spans
# ---------------------------------------------------------------------------

# synthetic stage tracks live at tids far from real thread idents
_STAGE_TID_BASE = 1_000_000
#: relative tick weights (the cost model's bwd = 2x fwd convention,
#: reference galvatron/core/cost_model.py:190-191)
_TICK_WEIGHTS = {"fwd": 1.0, "bwd": 2.0}


def emit_tick_spans(
    trc: Tracer,
    ticks: Sequence[Dict[str, int]],
    total_ticks: int,
    t0_us: float,
    dur_us: float,
    step: Optional[int] = None,
) -> int:
    """Render a schedule's tick grid onto the measured step window.

    ``ticks``: ``{"stage", "tick", "kind" ("fwd"|"bwd"), "mb"}`` records from
    ``gpipe_schedule_ticks``/``pipedream_schedule_ticks``. Each stage gets
    its own synthetic track (``pp stage S``); within a tick that carries both
    a forward and a backward (1F1B steady state), the tick is split by the
    fwd:bwd = 1:2 cost convention. Ticks with no work emit nothing — the
    gaps on a stage track ARE the schedule's bubbles. Returns span count."""
    if not trc.enabled or not ticks or total_ticks <= 0 or dur_us <= 0:
        return 0
    tick_us = dur_us / total_ticks
    by_cell: Dict[Tuple[int, int], List[Dict[str, int]]] = {}
    for t in ticks:
        by_cell.setdefault((t["stage"], t["tick"]), []).append(t)
    n = 0
    for (stage, tick), cell in sorted(by_cell.items()):
        cell_t0 = t0_us + tick * tick_us
        wsum = sum(_TICK_WEIGHTS.get(c["kind"], 1.0) for c in cell)
        off = 0.0
        # fwd renders before bwd within a shared tick (the 1F1B last stage
        # forwards a micro-batch, then backwards it, in one tick)
        for c in sorted(cell, key=lambda c: 0 if c["kind"] == "fwd" else 1):
            frac = _TICK_WEIGHTS.get(c["kind"], 1.0) / wsum
            args: Dict[str, Any] = {
                "mb": c["mb"], "tick": tick, "synthetic": True,
                "model": "lockstep clocked schedule",
            }
            if step is not None:
                args["step"] = step
            trc._record(
                {
                    "name": f"stage{stage} {c['kind']} mb{c['mb']}",
                    "ph": "X",
                    "ts": cell_t0 + off * tick_us,
                    "dur": frac * tick_us,
                    "tid": _STAGE_TID_BASE + stage,
                    "tname": f"pp stage {stage}",
                    "args": args,
                }
            )
            off += frac
            n += 1
    return n
