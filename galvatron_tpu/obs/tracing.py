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
  open (``tracer.profiling``, set by ``obs/flight.ProfilerWindow``, by
  ``obs/flight.capture_profile`` (the server's ``POST /profile``) and by the
  trainer's whole-run ``--trace_dir`` capture) every span also opens a
  ``jax.profiler.TraceAnnotation`` of its name, the trainer's ``step`` span
  a ``StepTraceAnnotation("train", step_num=...)`` and the serving engine's
  ``iteration`` span a ``StepTraceAnnotation("serve", step_num=...)`` — the
  host's rows land on the ``python`` line of the profiler's own
  ``.xplane.pb``, on the clock the device's operations are on.
- what the runtime does behind the loop's back, as spans (tracer ON only):
  ``jax_trace`` / ``jax_lower`` / ``jax_compile`` from the ``jax.monitoring``
  duration events (every trace, lowering, backend compile or cache load —
  ``jax_compile`` carries ``hit`` and ``retrieval_s``), and ``gc`` from
  ``gc.callbacks``. Each carries the ``step`` of the span open on its
  thread, so a compile or a collection inside a training step or a serving
  iteration names it.
- ``record_span(..., track="serving queue")`` — a span reported after the
  fact on a named track that is no thread's (a request's wait in the queue
  began before the loop thread's open spans did, so it cannot nest there).
- ``complete_span("prefill", after=ids0, done=ids1, track="device")`` — a
  COMPLETION span: it opens when ``after`` is complete on the device and
  closes when ``done`` is, stamped by the tracer's own worker thread; the
  caller pays an enqueue. Handed in without ``done`` it returns a handle whose
  ``close(done=...)`` says the other end later, so that the worker is already
  waiting on ``after`` while the caller still dispatches what lies between
  the two. ``snapshot()`` waits, bounded, for the worker.

THE RULE a traced hot loop keeps, so that the loop the tracer times is the loop
its users run: ``Span.sync`` on a loop's own thread only where the untraced
loop waits at that very place (the serving engine's ``decode_wait``: untraced,
``np.asarray(ids)`` blocks right there). Any other "when was the device through
with this" goes through a completion span, which waits on a thread that is
nobody's loop.

The module-level ``tracer`` singleton is what the trainer, checkpoint layer,
search engine, and serving engine all record into — enable it once
(``tracer.enable()``) and every subsystem's spans land on one timeline.
"""

from __future__ import annotations

import gc
import heapq
import json
import os
import threading
import time
import zlib
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

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

    def close(self, **attrs):
        """(what `Tracer.complete_span` hands out when tracing is off)"""
        return None


_NULL_SPAN = _NullSpan()

#: the ring of the rare spans (`Tracer`): a cold start of the largest cell leaves ~600
RARE_SPANS = 4096

#: completion spans the worker has not got to yet; one more is dropped and counted
#: (`Tracer.complete_span` never blocks its caller)
COMPLETIONS_MAX = 1024

#: how long ``snapshot()`` waits for the completion worker: a crash dump must not
#: hang on a device that died
SNAPSHOT_WAIT_S = 2.0

#: how long the worker waits for a completion span's ``close``: a caller that lost its
#: handle must not stop every span behind it
CLOSE_WAIT_S = 60.0

#: collections shorter than this leave no ``gc`` span: a trace-and-lower makes
#: hundreds of young-generation passes of ~0.1 ms that explain no slow step
#: and would push what does out of the ring
GC_SPAN_MIN_S = 1e-3


def _track_tid(track: str) -> int:
    """The ``tid`` of a named track: a function of the name alone, in a range
    far below any thread ident (those are addresses)."""
    return 1_000_000 + zlib.crc32(track.encode()) % 1_000_000


#: the spans that are the profiler's step boundary too (xprof groups device
#: work by it): the trainer's ``step``, the serving engine's ``iteration``
_STEP_ANNOTATIONS = {"step": "train", "iteration": "serve"}


def _annotation(name: str, args: Dict[str, Any]):
    """The span's twin in the profiler's trace."""
    if name in _STEP_ANNOTATIONS and "step" in args:
        return jax.profiler.StepTraceAnnotation(
            _STEP_ANNOTATIONS[name], step_num=args["step"])
    return jax.profiler.TraceAnnotation(name)


class _Completion:
    """A completion span from `Tracer.complete_span` until the worker has recorded it;
    the handle the caller closes where the span's other end is said later."""

    __slots__ = ("name", "track", "after", "attrs", "handed_in", "done", "scalars", "finish",
                 "closed_at", "_closed")

    def __init__(self, name: str, track: str, after: Any, attrs: Dict[str, Any]):
        self.name, self.track, self.after, self.attrs = name, track, after, attrs
        self.handed_in = time.perf_counter()
        self.done = self.scalars = self.finish = None
        self.closed_at = 0.0
        self._closed = threading.Event()

    def close(self, done: Any = None, scalars: Any = None,
              finish: Optional[Callable[[Any], Dict[str, Any]]] = None, **attrs) -> None:
        """The span's other end: it closes when ``done`` is complete on the device
        (absent: now, on the caller's clock); ``scalars`` / ``finish`` / further
        arguments as `Tracer.complete_span` takes them. Costs a clock read and an
        event; the first call counts."""
        if self._closed.is_set():
            return
        self.done, self.scalars, self.finish = done, scalars, finish
        self.attrs = {**self.attrs, **attrs}  # (rebound, not mutated: a snapshot may be reading it)
        self.closed_at = time.perf_counter()
        self._closed.set()


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
    the crash" is exactly its contents (obs/flight.py dumps it). The
    ``jax.monitoring`` spans (a trace, a lowering, a backend compile: never in
    a warm step) live in a small ring of their own, merged into
    ``snapshot()`` where they ended: the hot path's spans, 37 an iteration of a 32-slot engine
    (``iteration``, ``decode`` and its three children, ``sample`` and 32 ``sample_slot``) and,
    an admission, ``admit`` + ``queue_wait`` + ``prefill_dispatch`` + ``prefill`` + one
    ``chunk_dispatch`` a chunk (4 + chunks; 2 + chunks of them new with the completion spans),
    would otherwise push a run's compiles out within the minute, and "did
    anything compile, and when" is read at a run's END.

    Completion spans (``complete_span``) are stamped by ONE daemon worker a
    tracer, started at the first of them; ``snapshot()`` waits for it."""

    def __init__(self, capacity: int = 4096):
        self.enabled = False
        #: a jax.profiler window is open: spans also open TraceAnnotations
        self.profiling = False
        self._ring: deque = deque(maxlen=capacity)
        self._rare: deque = deque(maxlen=RARE_SPANS)
        self._local = threading.local()
        self._epoch_pc = time.perf_counter()
        self._epoch_wall = time.time()
        # completion spans: enqueued (seq -> item) until the worker has recorded them
        self._cv = threading.Condition()
        self._pending: Dict[int, _Completion] = {}
        self._enqueued = self._started = self._forgotten = 0
        self._worker: Optional[threading.Thread] = None
        #: completion spans refused because ``COMPLETIONS_MAX`` were waiting
        self.completions_dropped = 0

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
        self._rare.clear()
        with self._cv:
            self._forgotten = self._enqueued
            self.completions_dropped = 0

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

    def _record(self, rec: Dict[str, Any], rare: bool = False) -> None:
        # deque.append with maxlen is atomic in CPython — no lock on the hot
        # path; snapshot() copies defensively for readers
        (self._rare if rare else self._ring).append(rec)

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

    def record_span(self, name: str, dur_s: float, track: Optional[str] = None,
                    rare: bool = False, **attrs) -> None:
        """A span that ended now and lasted ``dur_s``, reported after the
        fact (a ``jax.monitoring`` duration event, a finished collection, a
        request's wait in the queue). Carries the ``step`` of the span open
        on this thread, if any. ``track`` puts it on a timeline track of that
        name that is no thread's: a wait that began before the spans open
        here would break their nesting on the thread's own track. ``rare``:
        into the ring the hot path cannot push it out of (`Tracer`)."""
        if not self.enabled:
            return
        step = self.current_step()
        if step is not None:
            attrs["step"] = step
        rec = self._track_record(name, track or "", time.perf_counter() - dur_s, dur_s, attrs)
        if track is None:
            t = threading.current_thread()
            rec.update(tid=t.ident or 0, tname=t.name, depth=len(self._stack_for_thread()))
        self._record(rec, rare)

    def complete_span(self, name: str, *, after: Any = None, done: Any = None,
                      track: str = "device", scalars: Any = None,
                      finish: Optional[Callable[[Any], Dict[str, Any]]] = None, **attrs):
        """A span ``name`` on the named track ``track`` that opens when ``after``
        (a jax array or tree; absent: now) is complete on the device and closes
        when ``done`` is. The caller pays an enqueue: the tracer's worker blocks
        on the two, in the order the spans were handed in, and stamps.

        With ``done`` the span is whole and nothing is returned. WITHOUT it the
        call returns a handle and the span stays open until ``handle.close(done=,
        scalars=, finish=, **more)``: hand it in BEFORE dispatching what lies
        between the two ends, so that the worker is waiting on ``after`` when it
        completes; a span handed in whole after that work was sent finds ``after``
        complete already wherever the dispatch outlasts it. ``close()`` without
        ``done`` closes the span at the call (the caller saw the work through
        itself); a handle never closed is given up after ``CLOSE_WAIT_S``.

        ``scalars`` (a tree of device scalars) become floats on the worker, behind
        ``done``, and join the span's arguments: as they are (a flat dict), or as
        ``finish(floats)`` returns them. The record says ``synced: True``; an
        array that was deleted or a device that died gives it ``error`` and
        raises on no thread; ``opened_late: True`` where ``after`` was complete
        before the worker looked (the span then opened no LATER than stamped).
        Never blocks: with ``COMPLETIONS_MAX`` waiting, the span is dropped and
        counted (``completions_dropped``). Hold no donated buffer in ``after`` /
        ``done`` / ``scalars``: the worker reads them after the caller went on.
        Tracer off: the no-op singleton, before anything is touched."""
        if not self.enabled:
            return _NULL_SPAN
        item = _Completion(name, track, after, attrs)
        if done is not None:
            item.close(done, scalars, finish)
        with self._cv:
            if len(self._pending) >= COMPLETIONS_MAX:
                self.completions_dropped += 1
                return _NULL_SPAN
            if self._worker is None:
                self._worker = threading.Thread(
                    target=self._complete_loop, name="tracer-completions", daemon=True)
                self._worker.start()
            self._enqueued += 1
            self._pending[self._enqueued] = item
            self._cv.notify_all()
        return item

    def _complete_loop(self) -> None:
        chain: Tuple[Any, float] = (None, 0.0)  # the last span's ``done`` and its stamp
        while True:
            with self._cv:
                while self._started == self._enqueued:
                    self._cv.wait()
                self._started += 1
                seq = self._started
                item = self._pending[seq]
            rec, chain = self._complete(item, chain)
            with self._cv:
                if seq > self._forgotten:  # (a ring cleared meanwhile stays clear)
                    self._record(rec)
                del self._pending[seq]
                self._cv.notify_all()

    def _complete(self, item: _Completion, chain: Tuple[Any, float]):
        """Wait for one completion span's two ends (the worker's thread) -> its
        record and (its ``done``, the stamp it closed on) for the next one."""
        after, args, t_open = item.after, {}, item.handed_in
        try:
            if after is not None and after is chain[0]:
                t_open = chain[1]  # it opens where the span before it closed: that very stamp
            elif after is not None:
                ready = [leaf.is_ready() for leaf in jax.tree_util.tree_leaves(after)
                         if hasattr(leaf, "is_ready")]
                if ready and all(ready):
                    args["opened_late"] = True
                jax.block_until_ready(after)
                t_open = time.perf_counter()
            if not item._closed.wait(CLOSE_WAIT_S):
                raise TimeoutError(f"completion span {item.name!r} was never closed")
            if item.done is None:
                t_close = item.closed_at
            else:
                jax.block_until_ready(item.done)
                t_close = time.perf_counter()
                chain = (item.done, t_close)
            if item.scalars is not None:
                # (one batch of copies, not a round trip a scalar: the next span's ``after``
                # lands while this thread is busy here, and is then stamped late)
                values = jax.tree_util.tree_map(float, jax.device_get(item.scalars))
                args.update(values if item.finish is None else item.finish(values))
            args["synced"] = True
        except Exception as e:  # noqa: BLE001 — a deleted array, a dead device: said, not raised
            t_close = time.perf_counter()
            args["error"] = type(e).__name__
        return self._track_record(item.name, item.track, t_open, max(0.0, t_close - t_open),
                                  {**item.attrs, **args}), chain

    def _track_record(self, name: str, track: str, t0: float, dur_s: float,
                      args: Dict[str, Any]) -> Dict[str, Any]:
        return {"name": name, "ph": "X", "ts": self.pc_to_us(t0), "dur": dur_s * 1e6,
                "tid": _track_tid(track), "tname": track, "depth": 0, "args": args}

    def _await_completions(self, wait_s: float) -> List[Dict[str, Any]]:
        """Wait, ``wait_s`` at most, for the completion spans enqueued so far;
        those still not through, as records that say ``pending``."""
        if self._worker is None or threading.current_thread() is self._worker:
            return []
        with self._cv:
            upto = self._enqueued
            self._cv.wait_for(lambda: next(iter(self._pending), upto + 1) > upto, timeout=wait_s)
            left = [item for seq, item in self._pending.items()
                    if self._forgotten < seq <= upto]
        return [self._track_record(it.name, it.track, it.handed_in, 0.0,
                                   {**it.attrs, "pending": True}) for it in left]

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

    def snapshot(self, wait_s: float = SNAPSHOT_WAIT_S) -> List[Dict[str, Any]]:
        """Both rings' records in the order they were made (a record is made when its
        span ends; a completion span's when the worker has stamped it). Waits first,
        ``wait_s`` at most, for the completion spans handed in before the call, so no
        reader need know of the worker; what is still not through comes last, as
        records of no duration that say ``pending``, and behind them one instant
        ``completions_dropped`` (``count``) where any was."""
        tail = self._await_completions(wait_s)
        if self.completions_dropped:
            tail.append({"name": "completions_dropped", "ph": "i",
                         "ts": self.pc_to_us(time.perf_counter()), "tid": 0,
                         "tname": "tracer-completions",
                         "args": {"count": self.completions_dropped}})
        rare, ring = list(self._rare), list(self._ring)
        if not rare:
            return ring + tail
        return list(heapq.merge(rare, ring, key=lambda r: r["ts"] + r.get("dur", 0.0))) + tail

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
    tracer.record_span(name, float(duration_secs), rare=True, **attrs)


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
