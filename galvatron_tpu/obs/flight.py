"""Crash flight recorder + programmatic jax.profiler capture windows.

Flight recorder (MegaScale's event recorder, NSDI '24 §5): the tracer's
bounded ring IS the recorder — the last N spans/instants before a crash.
``dump_flight`` serializes it to ``flight_<ts>.json`` and is called from the
trainer's PR 1 crash ``finally`` path, so every exceptional exit leaves the
seconds-before-the-crash timeline on disk next to the emergency checkpoint.
``galvatron_tpu.cli trace-export flight_*.json`` turns a dump back into a
Perfetto-loadable trace.

Profiler capture: ``--profile_steps A:B`` (trainer) and ``POST
/profile?steps=N`` (server) open a bounded ``jax.profiler`` window — the
full XLA op/kernel timeline for exactly the steps asked for, instead of the
whole-run ``--trace_dir`` firehose. Backends without xprof support degrade
to a logged warning: profiling is an observation, never a crash source.
While a window is open the tracer's spans also land in the profiler's trace
(``tracer.profiling``), and where the last window went is kept:
``last_profile_window()`` returns it, the trainer logs it as a
``profile_window`` record.  The same way the trainer keeps what the search's
cost model charges the plan it runs: ``last_plan_price()``, logged as a
``plan_price`` record.
"""

from __future__ import annotations

import glob
import json
import os
import re
import time
from typing import Any, Callable, Dict, Optional, Tuple

from galvatron_tpu.obs.tracing import tracer

FLIGHT_SCHEMA = "galvatron-flight-v1"

_last_window: Optional[Dict[str, Any]] = None


def last_profile_window() -> Optional[Dict[str, Any]]:
    """The last ``ProfilerWindow`` this process closed: ``{trace_dir, xplane,
    start_step, stop_step, first_step, last_step}`` (``xplane``: the
    ``.xplane.pb`` it wrote, None when the backend left none; ``first_step``
    .. ``last_step``: the iterations it really covered). None before any."""
    return dict(_last_window) if _last_window else None


_last_plan_price: Optional[Dict[str, Any]] = None


def note_plan_price(price: Optional[Dict[str, Any]]) -> None:
    """The trainer's: what it priced the plan it is about to run at (None: a
    run that priced nothing, so that an earlier run's price is not read as its)."""
    global _last_plan_price
    _last_plan_price = price


def last_plan_price() -> Optional[Dict[str, Any]]:
    """What the search's cost model charges the plan the last ``train()`` of this
    process ran, by term (``search/price.price_plan``: ``time_ms``,
    ``volume_mb``, ``memory_mb``, ``basis``, whose ``source`` says whether the
    plan file carried it or the trainer priced the plan itself), or
    ``{"error": why}`` where the model cannot be priced; None before any."""
    return dict(_last_plan_price) if _last_plan_price else None


def dump_flight(
    out_dir: str, trc, reason: str, extra: Optional[Dict[str, Any]] = None
) -> Optional[str]:
    """Write the tracer ring (+ context) to ``<out_dir>/flight_<ts>.json``.
    Returns the path, or None when there is nothing to record (tracing was
    never enabled and the ring is empty). Never raises — callers sit in
    crash ``finally`` blocks where a dump failure must not mask the crash."""
    try:
        spans = trc.snapshot()
        if not spans and not trc.enabled:
            return None
        os.makedirs(out_dir, exist_ok=True)
        ts = time.strftime("%Y%m%d_%H%M%S")
        path = os.path.join(out_dir, f"flight_{ts}_{os.getpid()}.json")
        doc: Dict[str, Any] = {
            "schema": FLIGHT_SCHEMA,
            "wall_time": time.time(),
            "epoch_wall": trc.epoch_wall,  # wall clock at span ts=0
            "pid": os.getpid(),  # merge-export keys each dump's track group
            "reason": reason,
            "spans": spans,
        }
        if extra:
            doc["extra"] = extra
        try:
            from galvatron_tpu.obs.stepstats import hbm_gauges

            doc["hbm_bytes"] = hbm_gauges()
        except Exception:
            pass
        try:
            # under GALVATRON_LOCK_CHECK=1 the dump answers "which thread
            # holds what" directly — the first question of any hang forensic
            from galvatron_tpu.analysis.locks import held_snapshot, lock_check_armed

            if lock_check_armed():
                doc["held_locks"] = held_snapshot()
        except Exception:
            pass
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return path
    except Exception as e:  # noqa: BLE001 — crash-path best effort
        print(f"flight-recorder dump failed: {e!r}")
        return None


def read_flight(path: str) -> Dict[str, Any]:
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") != FLIGHT_SCHEMA:
        raise ValueError(f"{path}: not a {FLIGHT_SCHEMA} dump")
    return doc


def parse_profile_steps(spec: str) -> Tuple[int, int]:
    """``"A:B"`` → (A, B): capture iterations A..B-1 (half-open, like range).
    Validated loudly — a silently-ignored malformed window would look like a
    backend limitation instead of a typo."""
    m = re.fullmatch(r"(\d+):(\d+)", spec.strip())
    if not m:
        raise ValueError(
            f"--profile_steps expects START:STOP (e.g. 3:6), got {spec!r}"
        )
    a, b = int(m.group(1)), int(m.group(2))
    if b <= a:
        raise ValueError(f"--profile_steps {spec!r}: STOP must be > START")
    return a, b


class ProfilerWindow:
    """Step-bounded jax.profiler capture: ``maybe_start(it)`` /
    ``maybe_stop(it)`` around the trainer iteration. A backend without xprof
    (start_trace raising) disables the window with a warning and the run
    continues untraced."""

    def __init__(self, trace_dir: str, start_step: int, stop_step: int):
        self.trace_dir = trace_dir
        self.start_step = start_step
        self.stop_step = stop_step
        self.active = False
        self.failed = False
        self.done = False
        self.first_step = self.last_step = None

    def open(self, it: int) -> None:
        """Start the capture now, at iteration ``it``; raises what the
        backend raises when it cannot (``maybe_start`` degrades instead)."""
        import jax

        jax.profiler.start_trace(self.trace_dir)
        self.active = True
        self.first_step = it
        tracer.profiling = True

    def maybe_start(self, it: int) -> None:
        # >= not ==: a resumed run whose batch offset already passed START
        # must still capture (from where it is) rather than silently skip
        if self.failed or self.active or self.done or it < self.start_step:
            return
        if it >= self.stop_step:
            self.done = True  # resumed entirely past the window: nothing to do
            return
        try:
            self.open(it)
        except Exception as e:  # noqa: BLE001 — degrade, don't crash training
            self.failed = True
            print(f"--profile_steps: backend lacks profiler support ({e!r}); "
                  "continuing without capture")

    def maybe_stop(self, it: int, verbose: bool = True) -> Optional[Dict[str, Any]]:
        """Closes the window once iteration ``it`` (the last one run) reaches
        STOP; returns what ``close`` returns."""
        if not self.active:
            return None
        self.last_step = it
        if it + 1 < self.stop_step:
            return None
        return self.close(verbose=verbose)

    def close(self, verbose: bool = True) -> Optional[Dict[str, Any]]:
        """Idempotent stop — also called from the trainer ``finally`` so a
        crash inside the window cannot wedge process-wide profiler state.
        Returns the window's record (see ``last_profile_window``) the one
        time it closes an open window, else None."""
        global _last_window
        if not self.active:
            return None
        self.active = False
        self.done = True
        tracer.profiling = False
        stop_error = None
        try:
            import jax

            jax.profiler.stop_trace()
            if verbose:
                print(f"profiler window [{self.start_step}:{self.stop_step}) "
                      f"→ {self.trace_dir}")
        except Exception as e:  # noqa: BLE001
            stop_error = repr(e)
            print(f"failed to close profiler window: {e!r}")
        found = sorted(glob.glob(
            os.path.join(self.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        _last_window = {
            "trace_dir": self.trace_dir, "xplane": found[-1] if found else None,
            "start_step": self.start_step, "stop_step": self.stop_step,
            "first_step": self.first_step, "last_step": self.last_step,
        }
        if stop_error:
            _last_window["stop_error"] = stop_error
        return dict(_last_window)


def capture_profile(
    trace_dir: str, n_steps: int, counter_fn: Callable[[], int],
    timeout_s: float = 30.0, poll_s: float = 0.02,
) -> Dict[str, Any]:
    """On-demand capture (server ``POST /profile``): a ``ProfilerWindow``
    opened now and closed once ``counter_fn`` (the engine's ``steps``) has
    advanced by ``n_steps`` or ``timeout_s`` has passed; an exception closes
    it too. While it is open the engine's spans are annotations on the
    profiler's clock (``tracer.profiling``), and ``last_profile_window()``
    then names the ``.xplane.pb`` and the engine steps it covers. Raises
    RuntimeError when the backend cannot start a trace at all."""
    start = counter_fn()
    pw = ProfilerWindow(trace_dir, start, start + n_steps)
    try:
        pw.open(start)
    except Exception as e:
        raise RuntimeError(f"profiler unavailable on this backend: {e!r}") from e
    deadline = time.time() + timeout_s
    try:
        while counter_fn() - start < n_steps and time.time() < deadline:
            time.sleep(poll_s)
    finally:
        captured = counter_fn() - start  # stopping takes seconds, the engine runs on
        if captured > 0:
            pw.last_step = start + captured - 1
        rec = pw.close(verbose=False)
    out = {
        "trace_dir": trace_dir,
        "xplane": rec["xplane"],
        "steps_captured": captured,
        "requested": n_steps,
        "timed_out": captured < n_steps,
    }
    if "stop_error" in rec:
        out["stop_error"] = rec["stop_error"]
    return out
