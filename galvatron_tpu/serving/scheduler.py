"""Iteration-level request scheduler (Orca, OSDI '22).

Requests enter a FIFO admission queue with a per-request deadline (TTL);
the engine loop admits the head of the queue whenever a KV slot frees up and
retires sequences the moment they hit eos or their token budget — admission
and retirement happen at *decode-step* granularity, between iterations of
one shared forward pass, never by preempting a running step.

Backpressure is explicit and accounted: a bounded queue rejects new work
immediately (``QueueFull`` → HTTP 503) instead of parking threads, and a
request that waits in queue past its deadline is expired with
``RequestExpired`` (→ 503) rather than eventually hogging a slot the live
traffic needs.
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Deque, List, Optional

from galvatron_tpu.analysis.locks import make_lock
from galvatron_tpu.serving import resilience as rz
from galvatron_tpu.utils.metrics import Counters


class QueueFull(RuntimeError):
    """Admission queue at capacity — reject fast, client should back off."""


class RequestExpired(RuntimeError):
    """Request out-lived its TTL: waiting in the admission queue, or (since
    the deadline became end-to-end) mid-prefill before any token existed."""


_rid = itertools.count()


@dataclass
class Request:
    """One generation request moving through the lifecycle state machine
    (``resilience.STATES``): queue → slot → terminal state."""

    tokens: List[int]                 # prompt token ids
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    deadline: Optional[float] = None  # absolute time() the request may run to
    rid: int = field(default_factory=lambda: next(_rid))
    future: Future = field(default_factory=Future)
    submitted_at: float = field(default_factory=time.time)
    # engine-managed state
    slot: Optional[int] = None
    generated: List[int] = field(default_factory=list)
    first_token_at: Optional[float] = None
    # always-on host times (``time.time()``; an access log and the benchmark
    # read them): the slot taken; one a generated token, read where the token
    # is appended, after its draw; the terminal state reached. Ordered
    # submitted_at <= admitted_at <= first_token_at <= token_times[0] <= ...
    # <= finished_at (``first_token_at`` is read BEFORE the first draw)
    admitted_at: Optional[float] = None
    token_times: List[float] = field(default_factory=list)
    finished_at: Optional[float] = None
    # the caller's float32 array, at least (max_new_tokens, vocab), handed to
    # ``Engine.submit_request(capture_logits=)``: row k is the logits row
    # token k was drawn from; ``logits_rows`` counts the rows written
    capture_logits: Optional[Any] = None
    logits_rows: int = 0
    state: str = rz.QUEUED
    cancel_requested: bool = False
    cancel_reason: Optional[str] = None
    # fleet-minted correlation id (obs/correlate.py): set only when the
    # router propagated X-Galvatron-Trace-Id (tracing armed); rides every
    # lifecycle instant + the prefill span so one id follows the request
    # across router → replica → failover replica
    trace_id: Optional[str] = None
    # terminal detail: "eos" | "length" | "deadline" (partial-policy
    # truncation — the server surfaces it as ``"truncated": "deadline"``)
    finish_reason: Optional[str] = None

    @property
    def prompt_len(self) -> int:
        return len(self.tokens)

    def cancel(self, reason: str = "cancelled") -> None:
        """Ask the engine to stop this request at the next decode iteration
        (or skip it at admission). Thread-safe: a bool write under the GIL;
        the engine loop is the only reader that acts on it."""
        self.cancel_requested = True
        if self.cancel_reason is None:
            self.cancel_reason = reason


class Scheduler:
    """FIFO admission queue with TTL expiry and bounded depth."""

    def __init__(self, max_queue: int = 64, default_ttl_s: Optional[float] = 30.0):
        self.max_queue = max(1, int(max_queue))
        self.default_ttl_s = default_ttl_s
        self._lock = make_lock("scheduler.q")
        self._q: Deque[Request] = deque()  # guarded-by: self._lock
        self.counters = self.new_counters()

    @staticmethod
    def new_counters() -> Counters:
        """One counter per request outcome (``reset_metrics`` rebuilds the
        same set, so the two sites cannot drift)."""
        return Counters(
            "submitted", "admitted", "completed", "failed",
            "rejected_queue_full", "expired", "expired_decode",
            "cancelled", "cancelled_disconnect", "shed",
        )

    def submit(self, req: Request, ttl_s: Optional[float] = None) -> Request:
        """Enqueue or raise ``QueueFull``. ``ttl_s`` overrides the default
        TTL; None with no default means the request never expires."""
        ttl = self.default_ttl_s if ttl_s is None else ttl_s
        if ttl is not None and req.deadline is None:
            req.deadline = req.submitted_at + float(ttl)
        with self._lock:
            if len(self._q) >= self.max_queue:
                self.counters.inc("rejected_queue_full")
                raise QueueFull(
                    f"admission queue full ({self.max_queue} pending)"
                )
            self._q.append(req)
        self.counters.inc("submitted")
        return req

    def expire(self, now: Optional[float] = None) -> List[Request]:
        """Drop every queued request past its deadline, failing its future.
        Called by the engine loop each iteration — a saturated server sheds
        dead-on-arrival work instead of eventually generating for it."""
        now = time.time() if now is None else now
        dropped: List[Request] = []
        with self._lock:
            keep: Deque[Request] = deque()
            for r in self._q:
                if r.deadline is not None and now > r.deadline:
                    dropped.append(r)
                else:
                    keep.append(r)
            self._q = keep
        for r in dropped:
            rz.advance(r, rz.EXPIRED, self.counters, where="queue")
            if not r.future.done():  # client may have cancelled already
                r.future.set_exception(RequestExpired(
                    f"request {r.rid} expired after "
                    f"{now - r.submitted_at:.2f}s in queue"
                ))
        return dropped

    def peek(self, now: Optional[float] = None) -> Optional[Request]:
        """Head of the queue WITHOUT admitting it (expired ones shed
        first). The paged engine's admission gate reads the head's block
        footprint before deciding to pop — a request too big for current
        pool headroom stays queued, burning its own TTL as backpressure.
        Only the engine loop pops, so peek→pop cannot race another
        consumer."""
        self.expire(now)
        with self._lock:
            return self._q[0] if self._q else None

    def pop(self, now: Optional[float] = None) -> Optional[Request]:
        """Next admissible request (expired ones already shed), or None."""
        self.expire(now)
        with self._lock:
            if not self._q:
                return None
            req = self._q.popleft()
        self.counters.inc("admitted")
        return req

    def _drop_all(self, state: str, reason: str, exc_for) -> List[Request]:
        """Pop every queued request and terminate it: advance to ``state``
        and fail its future with ``exc_for(request)`` — the one copy of the
        pop-and-fail exit both :meth:`drain` and :meth:`shed_all` share."""
        with self._lock:
            dropped = list(self._q)
            self._q.clear()
        for r in dropped:
            if r.state not in rz.TERMINAL:  # double-drain race: already dropped
                rz.advance(r, state, self.counters, reason=reason)
            if not r.future.done():
                r.future.set_exception(exc_for(r))
        return dropped

    def drain(self, exc: Exception) -> List[Request]:
        """Fail every queued request (engine shutdown/crash give-up)."""
        return self._drop_all(rz.FAILED, "engine_shutdown", lambda r: exc)

    def shed_all(self, retry_after_s: Optional[float] = None) -> List[Request]:
        """Graceful drain: fail every queued-but-unstarted request fast with
        the distinct ``SHED`` status (503 → a load balancer retries against
        a peer) instead of making dead-on-arrival work wait out the drain."""
        return self._drop_all(
            rz.SHED, "draining",
            lambda r: rz.RequestShed(
                f"request {r.rid} shed: server draining "
                "(queued, generation not started)"
            ),
        )

    @property
    def depth(self) -> int:
        with self._lock:
            return len(self._q)

    @property
    def saturated(self) -> bool:
        return self.depth >= self.max_queue

    def empty(self) -> bool:
        return self.depth == 0
