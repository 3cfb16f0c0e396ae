"""Minimal REST text-generation server.

Counterpart of the reference's Flask server (reference:
galvatron/site_package/megatron/text_generation_server.py — PUT /api with
{"prompts": [...], "tokens_to_generate": N, ...}). Stdlib-only
(http.server) so it carries no extra dependencies.

Two execution paths behind one API:

- **Continuous-batching engine** (``serving.Engine``, the default from the
  CLI): each prompt is submitted as a request and resolved via a future;
  overlapping requests share every decode iteration over one persistent
  slot-based KV cache instead of queueing on a lock. Backpressure is the
  engine's bounded admission queue (``QueueFull``/TTL expiry → 503).
- **Serialized legacy path** (``engine=None``): ``generate_np`` under the
  global service lock, pending work bounded by the ``max_pending`` gate
  (excess requests fail fast with 503). Kept as the compatible single-shot
  path.

API (POST or PUT /api, JSON body):
  {"prompts": ["..."], "tokens_to_generate": 32, "temperature": 0.0,
   "top_k": 0, "top_p": 0.0}
→ {"text": ["...completions..."], "tokens": [[...ids...]]}
GET /healthz → {"status": "ok" | "draining", "uptime_s": ..., "requests":
                {succeeded/failed/rejected/cancelled}, "gate" | "serving":
                saturation + engine stats, "model": {vocab/hidden/layers/
                heads/max_seq_len}}
GET /readyz  → 200 {"ready": true} while accepting traffic; 503 the moment
               a drain begins (or the engine gives up restarting) — a load
               balancer stops routing BEFORE the last in-flight token lands
POST /drain  → begin a graceful drain (same as SIGTERM): admission closes
               (new /api requests 503 + Retry-After), queued requests are
               shed, in-flight slots run to completion under
               --drain_timeout_s, then the server stops and exits 0
GET /metrics → the same stats in Prometheus text exposition (obs/prom.py):
               request counters, engine counters, TTFT quantiles, occupancy,
               HBM gauges — a scraper target next to the probe.
POST /profile?steps=N (or JSON {"steps": N, "timeout_s": S, "dir": ...})
             → on-demand jax.profiler capture over the next N engine decode
               iterations (obs/flight.capture_profile); 409 while another
               capture runs, 503 where the backend lacks xprof support.

Connections are handled on threads — /healthz answers while generations are
in flight — and each carries a socket timeout (``request_timeout_s``) so a
stalled client (connected but never sending, or trickling a body) releases
its thread instead of accumulating forever. Replies into sockets the client
already abandoned (BrokenPipeError/ConnectionResetError) are swallowed and
the connection closed, like the stalled-read TimeoutError path — a
disconnecting client must not leave tracebacks or a half-written 500.
"""

from __future__ import annotations

import json
import select
import signal
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Optional

import jax

from galvatron_tpu.core import faults
from galvatron_tpu.obs.tracing import tracer as _obs_tracer
from galvatron_tpu.utils.metrics import Counters


class _Gate:
    """Bounded pending-work gate for the legacy path, with visible
    saturation (capacity/in_use/rejected land in /healthz so a 503-storm
    shows up on the probe, not just client-side)."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._sem = threading.BoundedSemaphore(capacity)
        self._lock = threading.Lock()
        self.in_use = 0
        self.rejected = 0

    def acquire(self) -> bool:
        ok = self._sem.acquire(blocking=False)
        with self._lock:
            if ok:
                self.in_use += 1
            else:
                self.rejected += 1
        return ok

    def release(self) -> None:
        with self._lock:
            self.in_use -= 1
        self._sem.release()

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "capacity": self.capacity,
                "in_use": self.in_use,
                "saturated": self.in_use >= self.capacity,
                "rejected": self.rejected,
            }


class ServiceBusy(RuntimeError):
    """Mapped to HTTP 503 by the handler (queue full / TTL expired / drain /
    engine restart). ``detail`` lands in the JSON body so clients and the
    chaos harness can tell the causes apart; ``retry_after_s`` becomes a
    ``Retry-After`` header (draining: come back after the drain window)."""

    def __init__(self, msg: str, detail: Optional[str] = None,
                 retry_after_s: Optional[float] = None):
        super().__init__(msg)
        self.detail = detail
        self.retry_after_s = retry_after_s


class ClientDisconnected(RuntimeError):
    """The handler's disconnect poll saw the client vanish mid-generation:
    the requests were cancelled, nobody is listening — drop the connection
    without writing a reply."""


class GenerationService:
    def __init__(self, params, cfg, tokenizer, max_new_default: int = 64,
                 seed: int = 0, engine=None):
        self.params = params
        self.cfg = cfg
        self.tok = tokenizer
        self.max_new_default = max_new_default
        self.key = jax.random.key(seed)
        self.engine = engine  # serving.Engine, or None for the legacy path
        self.lock = threading.Lock()
        self.started_at = time.time()
        self.counters = Counters("succeeded", "failed", "rejected", "cancelled")
        self.gate: Optional[_Gate] = None  # set by run_server (legacy path)
        # one capture at a time: jax.profiler state is process-global
        self._profile_lock = threading.Lock()
        # SLO burn-rate engine (obs/slo.py), armed by cli serve wiring; when
        # None the service runs SLO-less with zero added work
        self.slo = None
        # graceful drain state (begin_drain): admission closes, /readyz goes
        # unready immediately, in-flight work completes under the deadline
        self.draining = False
        self.drain_timeout_s = 30.0
        self._drain_lock = threading.Lock()
        self._drained = threading.Event()
        # startup readiness gate (cli serve sets it, then clears it once the
        # engine's warm start AND a first real generation have completed):
        # a router/load-balancer watching /readyz must never dispatch into a
        # replica still paying cold compile. /api stays open while starting
        # — a direct client just shares the compile, exactly the lazy path.
        self.starting = False

    @property
    def ready(self) -> bool:
        """What ``/readyz`` keys on: accepting NEW work. Unready while the
        engine is still warming (``starting``), the moment a drain begins
        (in-flight work may still be finishing — that is the point: the
        load balancer stops routing before the last token lands), and when
        the engine is dead (crash-restart budget exhausted)."""
        if self.starting or self.draining:
            return False
        if self.engine is not None and not self.engine.alive:
            return False
        return True

    def begin_drain(self, reason: str = "drain") -> dict:
        """Graceful drain, blocking until drained (or the deadline): shed
        the queue, let in-flight slots finish, close the engine. Idempotent
        — a second caller (SIGTERM after POST /drain) waits for the first
        drain to finish. Returns the engine's post-drain audit."""
        with self._drain_lock:
            first = not self.draining
            self.draining = True
        if not first:
            self._drained.wait(timeout=self.drain_timeout_s + 10.0)
            return getattr(self, "drain_audit", {})
        _obs_tracer.instant("serving_drain_begin", reason=reason)
        if self.engine is not None:
            # close admission at the ENGINE first so racing submissions
            # refuse with EngineDraining even before handlers see the flag
            self.engine.begin_drain()
            audit = self.engine.drain(self.drain_timeout_s)
        else:
            # legacy path: the gate stops admitting (handler checks
            # `draining`); wait for in-flight generations to release it
            deadline = time.monotonic() + self.drain_timeout_s
            while time.monotonic() < deadline:
                if self.gate is None or self.gate.snapshot()["in_use"] == 0:
                    break
                time.sleep(0.02)
            g = self.gate.snapshot() if self.gate is not None else {}
            audit = {"leaked": bool(g.get("in_use")), **g}
        self.drain_audit = audit
        _obs_tracer.instant("serving_drain_done", reason=reason,
                            leaked=audit.get("leaked"))
        self._drained.set()
        return audit

    @property
    def requests_served(self) -> int:
        # back-compat alias (pre-engine probes read this): completed OK
        return self.counters.get("succeeded")

    def health(self) -> dict:
        c = self.cfg
        req = self.counters.snapshot()
        out = {
            "status": ("draining" if self.draining
                       else "starting" if self.starting else "ok"),
            "ready": self.ready,
            "uptime_s": round(time.time() - self.started_at, 3),
            "requests_served": req["succeeded"],
            "requests": req,
            "model": {
                "vocab_size": c.vocab_size,
                "hidden_size": c.hidden_size,
                "num_layers": c.num_layers,
                "num_heads": c.num_heads,
                "max_seq_len": c.max_seq_len,
            },
        }
        if self.gate is not None:
            out["gate"] = self.gate.snapshot()
        if self.engine is not None:
            out["serving"] = self.engine.stats()
        # SLO degradation is part of health, not just /metrics: a load
        # balancer's probe sees WHY the replica is degraded without scraping
        # (empty list = no rule in breach; absent only when no SLO is armed)
        if self.slo is not None:
            out["degraded_reasons"] = self.slo.degraded_reasons()
        return out

    def _validate(self, body: dict):
        if not isinstance(body, dict):
            raise ValueError("request body must be a JSON object")
        prompts = body.get("prompts")
        if not isinstance(prompts, list) or not prompts or not all(
            isinstance(p, str) for p in prompts
        ):
            raise ValueError("'prompts' must be a non-empty list of strings")
        n_new = int(body.get("tokens_to_generate", self.max_new_default))
        if n_new < 0 or n_new > self.cfg.max_seq_len:
            raise ValueError(f"tokens_to_generate out of range [0, {self.cfg.max_seq_len}]")
        return prompts, n_new

    def generate(self, body: dict,
                 disconnect_check: Optional[Callable[[], bool]] = None,
                 trace_id: Optional[str] = None) -> dict:
        prompts, n_new = self._validate(body)
        tok_prompts = [self.tok.encode(p) for p in prompts]
        if self.engine is not None:
            outs, truncated = self._generate_engine(
                body, tok_prompts, n_new, disconnect_check, trace_id=trace_id
            )
        else:
            outs = self._generate_serialized(body, tok_prompts, n_new)
            truncated = [None] * len(outs)
        texts = [self.tok.decode(o[len(tp):]) for o, tp in zip(outs, tok_prompts)]
        resp = {"text": texts, "tokens": outs}
        if any(truncated):
            # deadline_policy=partial: the row stopped at its deadline —
            # say so instead of passing truncation off as a completion
            resp["truncated"] = truncated
        return resp

    def _generate_engine(self, body: dict, tok_prompts, n_new: int,
                         disconnect_check: Optional[Callable[[], bool]] = None,
                         trace_id: Optional[str] = None):
        """Continuous-batching path: one engine request per prompt, futures
        resolved as slots retire. Prompts of one HTTP request overlap with
        each other AND with every other in-flight connection. While the
        futures are pending, ``disconnect_check`` polls the client socket —
        a vanished client cancels its requests at the next decode iteration
        (the slot frees) instead of burning chip time to completion."""
        from concurrent.futures import FIRST_EXCEPTION
        from concurrent.futures import TimeoutError as FuturesTimeout
        from concurrent.futures import wait as futures_wait

        from galvatron_tpu.serving import (
            DeadlineExceeded,
            EngineClosed,
            EngineDraining,
            EngineRestarted,
            QueueFull,
            RequestExpired,
            RequestShed,
        )

        ttl = body.get("ttl_s")
        reqs = []
        try:
            for tp in tok_prompts:
                reqs.append(self.engine.submit_request(
                    tp, n_new,
                    temperature=float(body.get("temperature", 0.0)),
                    top_k=int(body.get("top_k", 0)),
                    top_p=float(body.get("top_p", 0.0)),
                    ttl_s=float(ttl) if ttl is not None else None,
                    trace_id=trace_id,
                ))
            deadline = time.monotonic() + self.engine.result_timeout_s
            pending = {r.future for r in reqs}
            while pending:
                done, pending = futures_wait(
                    pending, timeout=0.05, return_when=FIRST_EXCEPTION
                )
                if done and any(f.exception() is not None for f in done):
                    break  # propagate via .result() below
                if not pending:
                    break
                if disconnect_check is not None and disconnect_check():
                    for r in reqs:
                        r.cancel("disconnect")
                    self.counters.inc("cancelled")
                    raise ClientDisconnected(
                        "client vanished mid-generation; requests cancelled"
                    )
                if time.monotonic() > deadline:
                    raise FuturesTimeout()
            outs = [r.future.result(timeout=self.engine.result_timeout_s)
                    for r in reqs]
            truncated = [r.finish_reason if r.finish_reason == "deadline"
                         else None for r in reqs]
            if self.slo is not None:
                # per-request SLO samples (obs/slo.py): success is an
                # availability "good"; a deadline-truncated row is a miss;
                # TTFT is the observed first-token latency
                for r in reqs:
                    self.slo.observe("availability", bad=False)
                    self.slo.observe("deadline_miss_ratio",
                                     bad=r.finish_reason == "deadline",
                                     rid=r.rid)
                    if r.first_token_at is not None:
                        self.slo.observe_latency(
                            "ttft_p99", r.first_token_at - r.submitted_at,
                            rid=r.rid)
            return outs, truncated
        except QueueFull as e:
            # paged admission may leave the head request queued until blocks
            # free up, so queue-full 503s carry a Retry-After hint sized to
            # the engine's backlog horizon (chaos `evict` asserts the header)
            raise ServiceBusy(
                str(e), detail="queue_full",
                retry_after_s=getattr(self.engine, "busy_retry_after_s", None),
            ) from e
        except (RequestExpired, DeadlineExceeded) as e:
            if self.slo is not None:
                self.slo.observe("deadline_miss_ratio", bad=True)
            raise ServiceBusy(str(e), detail="expired") from e
        except RequestShed as e:
            raise ServiceBusy(str(e), detail="shed") from e
        except EngineDraining as e:
            raise ServiceBusy(str(e), detail="draining",
                              retry_after_s=e.retry_after_s) from e
        except EngineRestarted as e:
            # Retry-After like draining 503s: the supervisor's own backoff
            # delay says when the recovered engine will be looping again
            if self.slo is not None:
                self.slo.observe("availability", bad=True,
                                 reason="engine_restarted")
            raise ServiceBusy(str(e), detail="engine_restarted",
                              retry_after_s=e.retry_after_s) from e
        except EngineClosed as e:
            if self.slo is not None:
                self.slo.observe("availability", bad=True,
                                 reason="engine_closed")
            raise ServiceBusy(str(e), detail="engine_closed") from e
        except FuturesTimeout as e:
            # distinct from the socket-read TimeoutError the handler treats
            # as a dead client: this request must get a real 500 and count
            # as failed (on 3.11+ FuturesTimeout aliases TimeoutError, which
            # the handler's stalled-client branch would silently swallow)
            if self.slo is not None:
                self.slo.observe("availability", bad=True, reason="timeout")
            raise RuntimeError(
                f"generation timed out after {self.engine.result_timeout_s}s"
            ) from e
        finally:
            # failed or abandoned siblings must not burn chip time: cancel
            # whatever has not completed (done futures ignore it; admitted
            # requests retire at the next decode iteration)
            for r in reqs:
                r.cancel("abandoned")
                r.future.cancel()

    def profile_capture(self, steps: int, trace_dir: Optional[str] = None,
                        timeout_s: float = 30.0) -> dict:
        """On-demand jax.profiler window over the next ``steps`` engine decode
        iterations (POST /profile). Raises ``ValueError`` for usage errors,
        ``ServiceBusy`` when a capture is already running, ``RuntimeError``
        when the backend has no xprof support (→ 503, not a crash)."""
        if self.engine is None:
            raise ValueError(
                "on-demand profiling needs the continuous-batching engine "
                "(--num_slots > 0): captures are bounded by decode iterations"
            )
        steps = int(steps)
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        # clamp client-supplied bounds: the capture holds the PROCESS-GLOBAL
        # jax.profiler plus a handler thread, and every concurrent /profile
        # 409s until it ends — an unbounded steps/timeout_s would let one
        # request pin both for as long as it likes
        steps = min(steps, 10_000)
        timeout_s = min(max(float(timeout_s), 1.0), 300.0)
        if not self._profile_lock.acquire(blocking=False):
            raise ServiceBusy("a profiler capture is already in progress")
        try:
            import tempfile

            from galvatron_tpu.obs.flight import capture_profile

            return capture_profile(
                trace_dir or tempfile.mkdtemp(prefix="galvatron_profile_"),
                steps,
                lambda: self.engine.counters.get("steps"),
                timeout_s=timeout_s,
            )
        finally:
            self._profile_lock.release()

    def _generate_serialized(self, body: dict, tok_prompts, n_new: int):
        """Legacy single-shot path: full prefill+decode per request under
        the global lock (generation holds the chip anyway)."""
        from galvatron_tpu.models import generation

        with self.lock:
            self.key, sub = jax.random.split(self.key)
            return generation.generate_np(
                self.params,
                self.cfg,
                tok_prompts,
                max_new_tokens=n_new,
                temperature=float(body.get("temperature", 0.0)),
                top_k=int(body.get("top_k", 0)),
                top_p=float(body.get("top_p", 0.0)),
                eos_id=self.tok.eos_id if self.tok.eos_id is not None else -1,
                pad_id=self.tok.pad_id if self.tok.pad_id is not None else 0,
                key=sub,
            )


def _make_handler(service: GenerationService, request_timeout_s: float):
    class Handler(BaseHTTPRequestHandler):
        # socketserver per-connection timeout: applied to the socket in
        # setup(), so a stalled read (request line or body) raises instead
        # of pinning its handler thread forever
        timeout = request_timeout_s

        def _reply(self, code: int, payload: dict, headers: Optional[dict] = None):
            self._reply_raw(code, json.dumps(payload).encode(),
                            "application/json", headers)

        def _reply_raw(self, code: int, data: bytes, ctype: str,
                       headers: Optional[dict] = None):
            # a client that disconnected mid-generation must not blow a
            # traceback out of the handler (nor can the 500-path itself be
            # allowed to throw) — drop the dead connection like the
            # stalled-read TimeoutError path does
            try:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(data)
            except (BrokenPipeError, ConnectionResetError, TimeoutError, OSError):
                self.close_connection = True

        def _client_disconnected(self) -> bool:
            """Is the client still on the other end? The request body was
            already read in full, so any readable-with-zero-bytes on the
            socket is the client's FIN (a clean close); a reset raises.
            ``client_stall`` (core/faults.py) simulates a vanished client
            for the chaos harness without a real socket reset."""
            if faults.maybe_client_stall():
                return True
            try:
                r, _, _ = select.select([self.connection], [], [], 0)
                if not r:
                    return False
                return self.connection.recv(1, socket.MSG_PEEK) == b""
            except OSError:
                return True

        def _handle(self):
            route, _, query = self.path.partition("?")
            route = route.rstrip("/")
            if route == "/drain":
                # admin endpoint, same lifecycle as SIGTERM: reply first
                # (the drain outlives this connection), then drain + stop
                # on a separate thread — serve_forever returns once the
                # in-flight work has landed
                threading.Thread(
                    target=drain_and_stop, args=(service, "POST /drain"),
                    daemon=True,
                ).start()
                return self._reply(200, {
                    "status": "draining",
                    "drain_timeout_s": service.drain_timeout_s,
                })
            if route == "/profile":
                return self._do_profile(query)
            if route != "/api":
                return self._reply(404, {"error": "use /api or /drain"})
            if service.draining:
                # admission gate is closed: fail fast with an honest 503 and
                # a Retry-After so a well-behaved client backs off while the
                # load balancer (watching /readyz) reroutes
                service.counters.inc("rejected")
                return self._reply(
                    503,
                    {"error": "server draining", "detail": "draining"},
                    headers={"Retry-After":
                             str(max(1, int(service.drain_timeout_s)))},
                )
            # bounded pending work (legacy path only): the threading server
            # gives every connection a thread, and a thread parked on the
            # generation lock is NOT covered by the socket timeout — without
            # the gate, a slow generation plus a request flood accumulates
            # unbounded threads and then burns chip time generating for
            # clients long gone. Saturated → fail fast with 503 (/healthz
            # stays open). With the engine, admission control lives in the
            # scheduler's bounded queue instead (QueueFull/TTL → 503).
            gate = service.gate
            if gate is not None and not gate.acquire():
                service.counters.inc("rejected")
                return self._reply(
                    503, {"error": "server busy: too many pending requests"}
                )
            try:
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length) or b"{}")
                # the fleet router's correlation id (obs/correlate.py):
                # present only when the router runs with tracing armed —
                # absent header ⇒ trace_id None ⇒ zero extra work
                from galvatron_tpu.obs.correlate import TRACE_HEADER

                resp = service.generate(
                    body, disconnect_check=self._client_disconnected,
                    trace_id=self.headers.get(TRACE_HEADER),
                )
                service.counters.inc("succeeded")
                return self._reply(200, resp)
            except TimeoutError:
                # stalled client mid-body: drop the connection without
                # attempting to write a reply into the dead socket
                self.close_connection = True
                return
            except ClientDisconnected:
                # the disconnect poll cancelled the requests (already
                # counted); nobody is listening for a reply
                self.close_connection = True
                return
            except ServiceBusy as e:
                service.counters.inc("rejected")
                payload = {"error": str(e)}
                if e.detail:
                    payload["detail"] = e.detail
                headers = None
                if e.retry_after_s is not None:
                    headers = {"Retry-After": str(max(1, int(e.retry_after_s)))}
                return self._reply(503, payload, headers)
            except ValueError as e:
                service.counters.inc("failed")
                return self._reply(400, {"error": str(e)})
            except Exception as e:  # noqa: BLE001 — surface to client
                service.counters.inc("failed")
                return self._reply(500, {"error": f"{type(e).__name__}: {e}"})
            finally:
                if gate is not None:
                    gate.release()

        def _do_profile(self, query: str):
            """POST /profile — bounded on-demand profiler capture."""
            from urllib.parse import parse_qs

            try:
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length) or b"{}")
                if not isinstance(body, dict):
                    raise ValueError("request body must be a JSON object")
                qs = parse_qs(query)
                steps = body.get("steps", qs.get("steps", [1])[0])
                timeout_s = body.get("timeout_s", qs.get("timeout_s", [30.0])[0])
                return self._reply(200, service.profile_capture(
                    steps, trace_dir=body.get("dir"), timeout_s=float(timeout_s)
                ))
            except TimeoutError:
                self.close_connection = True
                return
            except ServiceBusy as e:
                return self._reply(409, {"error": str(e)})
            except ValueError as e:
                return self._reply(400, {"error": str(e)})
            except RuntimeError as e:
                # no xprof on this backend: an honest 503, not a traceback
                return self._reply(503, {"error": str(e)})
            except Exception as e:  # noqa: BLE001 — surface to client
                return self._reply(500, {"error": f"{type(e).__name__}: {e}"})

        do_POST = _handle
        do_PUT = _handle

        def do_GET(self):
            route = self.path.partition("?")[0].rstrip("/")
            if route == "/healthz":
                # liveness: 200 even while draining — the process is healthy,
                # it is READINESS that flipped (status says "draining")
                return self._reply(200, service.health())
            if route == "/readyz":
                if service.ready:
                    return self._reply(200, {"ready": True})
                return self._reply(503, {
                    "ready": False,
                    "status": ("draining" if service.draining
                               else "starting" if service.starting
                               else "engine_dead"),
                })
            if route == "/metrics":
                from galvatron_tpu.obs.prom import CONTENT_TYPE, server_metrics_text

                try:
                    text = server_metrics_text(service)
                except Exception as e:  # noqa: BLE001 — scrape must not kill serving
                    return self._reply(500, {"error": f"{type(e).__name__}: {e}"})
                return self._reply_raw(200, text.encode(), CONTENT_TYPE)
            return self._reply(
                404,
                {"error": "use /api (POST/PUT), /healthz, /readyz, /metrics "
                          "(GET), or /profile, /drain (POST)"},
            )

        def log_message(self, *a):  # quiet
            pass

    return Handler


def drain_and_stop(service: GenerationService, reason: str) -> dict:
    """The zero-downtime shutdown sequence (SIGTERM and ``POST /drain``):
    ``begin_drain`` (admission closes, ``/readyz`` unready, queue shed,
    in-flight completes under ``drain_timeout_s``, engine closes with a
    zero-leak audit), then stop ``serve_forever`` so the process exits 0."""
    audit = service.begin_drain(reason=reason)
    httpd = getattr(service, "httpd", None)
    if httpd is not None:
        httpd.shutdown()
    return audit


def run_server(service: GenerationService, port: int = 5000, host: str = "127.0.0.1",
               ready_event: Optional[threading.Event] = None,
               request_timeout_s: float = 120.0, max_pending: int = 8,
               drain_timeout_s: float = 30.0) -> None:
    # threading server: /healthz must answer while a long generation is in
    # flight — a probe timing out against a busy single-threaded server
    # would get a healthy process restarted. On the legacy path max_pending
    # bounds queued /api work (excess → 503); with the engine, the
    # scheduler's bounded queue is the admission control.
    if service.engine is None:
        service.gate = _Gate(max_pending)
    service.drain_timeout_s = float(drain_timeout_s)
    httpd = ThreadingHTTPServer(
        (host, port), _make_handler(service, request_timeout_s)
    )
    service.httpd = httpd
    # SIGTERM = graceful drain (zero-downtime shutdown), not an abort: the
    # handler only installs from the main thread (tests run run_server on a
    # worker thread and drive POST /drain instead). The drain runs on its
    # own thread — a signal handler must not block for the drain window.
    try:
        signal.signal(
            signal.SIGTERM,
            lambda signum, frame: threading.Thread(
                target=drain_and_stop, args=(service, f"signal {signum}"),
                daemon=True,
            ).start(),
        )
    except ValueError:
        pass  # not the main thread
    if ready_event is not None:
        ready_event.set()
    print(f"generation server listening on http://{host}:{httpd.server_address[1]}/api")
    try:
        httpd.serve_forever()
    finally:
        httpd.server_close()
    if service.draining:
        audit = getattr(service, "drain_audit", {})
        print(f"server drained: leaked={audit.get('leaked')} "
              f"audit={json.dumps(audit)}")
