"""Serving resilience: request lifecycle state machine + engine crash supervision.

PR 1/7 made the *training* path preemption-proof; this module brings the
serving stack to the same bar. Production continuous-batching systems
(Orca's iteration-level scheduling, vLLM's preemptible slot management)
treat admission, cancellation, and engine recovery as first-class state
transitions — so every request here moves through ONE explicit lifecycle::

    QUEUED → PREFILLING → DECODING → {COMPLETED, FAILED, EXPIRED,
                                      CANCELLED, SHED}

Every transition is a tracer instant (``req_<state>``) and lands in a
counter, so ``/healthz``, ``/metrics`` and the flight recorder all tell the
same story. The terminal states are disjoint by *cause*:

- ``COMPLETED``  — eos or token budget reached; full result delivered.
- ``FAILED``     — prefill/decode exception or engine crash mid-flight
                   (continuous batching cannot replay mid-decode KV state).
- ``EXPIRED``    — out-waited its TTL: in queue (never admitted) or
                   mid-decode (the end-to-end deadline, checked at
                   decode-step granularity; ``deadline_policy`` decides
                   whether the partial text is returned or the request
                   fails).
- ``CANCELLED``  — the client vanished (disconnect poll) or asked to stop;
                   the slot frees at the next decode iteration.
- ``SHED``       — queued-but-unstarted when the server began draining;
                   failed fast so a load balancer retries elsewhere.

:class:`EngineSupervisor` is the in-process analogue of
``core/elastic.py``'s restart decision table: a decode/prefill-loop crash
fails the in-flight requests fast (503 ``engine_restarted``), keeps queued
requests that still have TTL budget, resets the KV cache, warm-rebuilds
the pinned programs from the PR 9 artifact store, and restarts the
loop under ``core/retry.py`` full-jitter backoff — bounded by
``max_restarts`` *consecutive no-progress* restarts (a completion between
crashes resets the budget, exactly like elastic's committed-step rule).
Every restart lands a flight-recorder dump.
"""

from __future__ import annotations

import time
from typing import Optional

from galvatron_tpu.core.restart_policy import RestartPolicy
from galvatron_tpu.obs.tracing import tracer

# --- request lifecycle states ------------------------------------------------

QUEUED = "QUEUED"
PREFILLING = "PREFILLING"
DECODING = "DECODING"
COMPLETED = "COMPLETED"
FAILED = "FAILED"
EXPIRED = "EXPIRED"
CANCELLED = "CANCELLED"
SHED = "SHED"

#: every lifecycle state, in flow order (DESIGN.md § Serving resilience
#: renders this exact list — a doc-sync test keeps them matched)
STATES = (QUEUED, PREFILLING, DECODING, COMPLETED, FAILED, EXPIRED,
          CANCELLED, SHED)

TERMINAL = frozenset((COMPLETED, FAILED, EXPIRED, CANCELLED, SHED))

#: legal transitions. QUEUED can reach every terminal state (expiry/shed/
#: cancel/failure all happen pre-admission too); a zero-token request
#: completes straight from QUEUED. PREFILLING cannot COMPLETE (the first
#: sampled token only exists once the request is DECODING).
TRANSITIONS = {
    QUEUED: frozenset((PREFILLING, COMPLETED, FAILED, EXPIRED, CANCELLED, SHED)),
    PREFILLING: frozenset((DECODING, FAILED, EXPIRED, CANCELLED)),
    DECODING: frozenset((COMPLETED, FAILED, EXPIRED, CANCELLED)),
}

#: terminal state → scheduler counter bumped on entry (the non-terminal
#: states are counted by admission itself: submitted/admitted)
_STATE_COUNTER = {
    COMPLETED: "completed",
    FAILED: "failed",
    EXPIRED: "expired",
    CANCELLED: "cancelled",
    SHED: "shed",
}


class IllegalTransition(RuntimeError):
    """A lifecycle edge outside :data:`TRANSITIONS` — a scheduling bug."""


def advance(req, state: str, counters=None, **info) -> None:
    """Move ``req`` to ``state``: validate the edge, record a tracer
    instant, and bump the matching terminal counter. ``info`` lands on the
    tracer instant (reason, detail, ...)."""
    cur = getattr(req, "state", QUEUED)
    if state not in TRANSITIONS.get(cur, frozenset()):
        raise IllegalTransition(
            f"request {req.rid}: illegal lifecycle transition {cur} → {state}"
        )
    req.state = state
    if state in TERMINAL:
        req.finished_at = time.time()
    trace_id = getattr(req, "trace_id", None)
    if trace_id is not None:
        # the fleet-minted correlation id (obs/correlate.py) rides every
        # lifecycle instant so the merged timeline links this process's
        # events to the router's dispatch spans
        tracer.instant(f"req_{state.lower()}", rid=req.rid,
                       trace_id=trace_id, **info)
    else:
        tracer.instant(f"req_{state.lower()}", rid=req.rid, **info)
    if counters is not None:
        name = _STATE_COUNTER.get(state)
        if name:
            counters.inc(name)
        if state == CANCELLED and info.get("reason") == "disconnect":
            counters.inc("cancelled_disconnect")
        if state == EXPIRED and cur == DECODING:
            counters.inc("expired_decode")


# --- exceptions the server maps to HTTP --------------------------------------


class RequestShed(RuntimeError):
    """Queued-but-unstarted when the drain began — 503, retry elsewhere."""


class RequestCancelled(RuntimeError):
    """Cancelled before completion (client disconnect); nobody is listening."""


class DeadlineExceeded(RuntimeError):
    """The end-to-end deadline passed mid-decode and ``deadline_policy`` is
    ``fail`` (``partial`` resolves the future with the truncated text
    instead)."""


class EngineDraining(RuntimeError):
    """The server is draining: admission is closed. Mapped to 503 with a
    ``Retry-After`` header so a well-behaved client backs off."""

    def __init__(self, msg: str, retry_after_s: Optional[float] = None):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class EngineClosed(RuntimeError):
    """The engine is shut down (or gave up restarting): ``submit`` refuses
    immediately instead of returning a future that can never resolve."""


class EngineRestarted(RuntimeError):
    """The engine crashed and restarted while this request was in flight.
    Mid-decode KV state cannot be replayed — the request fails fast with a
    503 so the client retries against the recovered engine.
    ``retry_after_s`` is the supervisor's own backoff delay (it knows when
    the engine will be looping again) — the server surfaces it as a
    ``Retry-After`` header, like draining 503s, and the fleet router uses
    it to time the re-dispatch."""

    def __init__(self, msg: str, retry_after_s: Optional[float] = None):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


# --- in-process crash supervision -------------------------------------------


class EngineSupervisor:
    """Restart decision table for the serving engine, in-process.

    Modeled on ``core/elastic.py``'s supervisor, minus the child process:
    the engine loop thread survives the crash, so "restart" means fail the
    unreplayable in-flight work, reset the KV cache, warm-rebuild the two
    pinned programs, and keep looping. Decisions mirror elastic's:

    ====================================  =====================================
    condition                             decision
    ====================================  =====================================
    crash, completions since last crash   restart (budget resets — progress)
    crash, no progress, budget left       restart after full-jitter backoff
    crash, no progress, budget exhausted  give up: engine closes, /readyz
                                          unready, every request 503s
    ====================================  =====================================

    Every crash lands a flight-recorder dump (when ``flight_dir`` is set)
    and a tracer instant; restarts count into ``engine_restarts``.
    """

    def __init__(self, max_restarts: int = 3, backoff_s: float = 0.05,
                 backoff_cap_s: float = 2.0, flight_dir: Optional[str] = None):
        self.max_restarts = max(0, int(max_restarts))
        # the shared supervisor decision table (core/restart_policy.py):
        # elastic, this supervisor, and the fleet router all budget restarts
        # with the same consecutive-no-progress arithmetic
        self.policy = RestartPolicy(
            max_restarts=self.max_restarts,
            backoff_s=float(backoff_s),
            backoff_cap_s=float(backoff_cap_s),
        )
        self.flight_dir = flight_dir
        self.restarts_total = 0
        self.gave_up = False
        self._last_completed = 0

    @property
    def consecutive(self) -> int:
        """Restarts since the last completed request (the policy's streak)."""
        return self.policy.consecutive

    def note_counter_reset(self) -> None:
        """The engine reset its counters (``reset_metrics``): drop the
        completed-count high-water mark with them, so progress detection
        keeps comparing like with like."""
        self._last_completed = 0

    def on_crash(self, engine, exc: BaseException) -> bool:
        """One crash of the engine loop. Returns True when the loop should
        continue (recovered), False on give-up (the engine is dead)."""
        completed = engine.scheduler.counters.get("completed")
        progressed = completed > self._last_completed
        self._last_completed = completed
        decision = self.policy.on_failure(progressed)
        tracer.instant(
            "engine_crash", error=f"{type(exc).__name__}: {exc}",
            consecutive=decision.consecutive, in_flight=len(engine._by_slot),
        )
        # in-flight 503s carry the supervisor's own backoff as Retry-After:
        # the engine is looping again after exactly that delay (give-up 503s
        # carry none — there is nothing to come back to)
        engine._crash_cleanup(
            exc,
            retry_after_s=None if decision.give_up else decision.backoff_s,
        )
        if self.flight_dir:
            from galvatron_tpu.obs.flight import dump_flight

            dump_flight(
                self.flight_dir, tracer,
                reason=f"engine {'give-up' if decision.give_up else 'crash'}: "
                       f"{type(exc).__name__}: {exc}",
                extra={"restarts_total": self.restarts_total,
                       "consecutive": decision.consecutive},
            )
        if decision.give_up:
            self.gave_up = True
            tracer.instant("engine_give_up", restarts=self.restarts_total,
                           consecutive=decision.consecutive)
            return False
        self.restarts_total += 1
        engine.counters.inc("engine_restarts")
        if decision.backoff_s:
            time.sleep(decision.backoff_s)
        engine._warm_rebuild()
        tracer.instant("engine_restart", restarts=self.restarts_total,
                       backoff_s=round(decision.backoff_s, 3))
        return True
