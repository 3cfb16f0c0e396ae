"""Serving resilience layer (serving/resilience.py + surgery across the
serving stack): request lifecycle state machine, graceful drain, engine
crash supervision, end-to-end deadlines, client-disconnect cancellation,
and the serving chaos harness — every exit path audited for zero leaked
slots."""

import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.core import faults
from galvatron_tpu.models import generation, modeling
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.models.tokenizer import ByteTokenizer, pad_vocab_size
from galvatron_tpu.obs.tracing import tracer
from galvatron_tpu.serving import (
    DeadlineExceeded,
    Engine,
    EngineClosed,
    EngineDraining,
    EngineRestarted,
    RequestShed,
    SlotKVCache,
)
from galvatron_tpu.serving import resilience as rz
from galvatron_tpu.serving.engine import _decode_step, _prefill_chunk

from tests._serving_common import CFG, params, prompts as _prompts  # noqa: F401  (`params`: a fixture)

TINY = ModelConfig(
    vocab_size=pad_vocab_size(259),
    hidden_size=32,
    num_layers=1,
    num_heads=2,
    ffn_dim=64,
    max_seq_len=64,
    dtype=jnp.float32,
)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# lifecycle state machine
# ---------------------------------------------------------------------------


def test_lifecycle_transitions_table():
    """Legal edges advance; illegal edges raise (a scheduling bug must be
    loud, not a silently-wrong counter)."""
    from galvatron_tpu.serving.scheduler import Request

    r = Request(tokens=[1], max_new_tokens=2)
    assert r.state == rz.QUEUED
    rz.advance(r, rz.PREFILLING)
    rz.advance(r, rz.DECODING)
    rz.advance(r, rz.COMPLETED)
    with pytest.raises(rz.IllegalTransition):
        rz.advance(r, rz.DECODING)  # terminal states have no exits
    r2 = Request(tokens=[1], max_new_tokens=2)
    with pytest.raises(rz.IllegalTransition):
        rz.advance(r2, rz.DECODING)  # cannot skip PREFILLING
    # SHED only exists pre-admission
    r3 = Request(tokens=[1], max_new_tokens=2)
    rz.advance(r3, rz.PREFILLING)
    with pytest.raises(rz.IllegalTransition):
        rz.advance(r3, rz.SHED)


def test_lifecycle_terminal_states_counted(params):
    """Every terminal state lands in its own counter: completed, expired
    (queue), shed, cancelled — disjoint by cause."""
    eng = Engine(params, CFG, num_slots=1, prefill_chunk=8, start_loop=False)
    done = eng.submit_request(_prompts(1, seed=1)[0], 2)
    doomed = eng.submit_request(_prompts(1, seed=2)[0], 2, ttl_s=0.01)
    time.sleep(0.03)
    for _ in range(10):
        eng.step_once()
        if done.future.done():
            break
    assert done.state == rz.COMPLETED and done.finish_reason == "length"
    assert doomed.state == rz.EXPIRED
    cancelled = eng.submit_request(_prompts(1, seed=3)[0], 2)
    cancelled.cancel("disconnect")
    eng.step_once()
    assert cancelled.state == rz.CANCELLED
    eng.begin_drain()
    st = eng.stats()
    assert st["completed"] == 1 and st["expired"] == 1
    assert st["cancelled"] == 1 and st["cancelled_disconnect"] == 1
    audit = eng.drain(timeout_s=1.0)
    assert not audit["leaked"]


# ---------------------------------------------------------------------------
# deadline propagation (end-to-end, decode-step granularity)
# ---------------------------------------------------------------------------


def test_deadline_truncates_mid_decode_partial(params):
    """An over-deadline DECODING request stops at the next iteration: the
    slot frees and (policy=partial) the client gets the partial text with
    finish_reason=deadline — one long hog cannot starve the queue."""
    eng = Engine(params, CFG, num_slots=1, prefill_chunk=8, start_loop=False,
                 deadline_policy="partial")
    hog = eng.submit_request(_prompts(1, seed=4)[0], 50, ttl_s=5.0)
    waiter = eng.submit_request(_prompts(1, seed=5)[0], 2, ttl_s=60.0)
    eng.step_once()   # hog admitted
    eng.step_once()   # first token sampled
    hog.deadline = time.time() - 0.001  # deadline passes mid-generation
    for _ in range(30):
        eng.step_once()
        if waiter.future.done():
            break
    out = hog.future.result(timeout=1)
    assert hog.state == rz.EXPIRED and hog.finish_reason == "deadline"
    assert len(out) < len(hog.tokens) + 50  # truncated, not completed
    assert out[:len(hog.tokens)] == hog.tokens
    # the slot went to the waiter, which completed in full
    assert waiter.future.result(timeout=1) is not None
    assert waiter.state == rz.COMPLETED
    st = eng.stats()
    assert st["expired_decode"] == 1 and st["completed"] == 1
    eng.close()


def test_deadline_policy_fail_raises(params):
    eng = Engine(params, CFG, num_slots=1, prefill_chunk=8, start_loop=False,
                 deadline_policy="fail")
    hog = eng.submit_request(_prompts(1, seed=6)[0], 50, ttl_s=5.0)
    eng.step_once()
    hog.deadline = time.time() - 0.001
    eng.step_once()
    with pytest.raises(DeadlineExceeded):
        hog.future.result(timeout=1)
    assert hog.state == rz.EXPIRED
    assert eng.slots.active_count == 0  # slot freed either way
    eng.close()


def test_deadline_checked_during_prefill(params):
    """The deadline is carried through prefill chunks: a long prompt whose
    client already stopped waiting aborts between chunks (both policies —
    no token was ever sampled) and the slot frees."""
    eng = Engine(params, CFG, num_slots=1, prefill_chunk=4, start_loop=False)
    # bypass queue-expiry so the deadline genuinely passes DURING prefill
    eng.scheduler.expire = lambda *a, **k: []
    req = eng.submit_request(list(range(1, 30)), 4, ttl_s=60.0)
    req.deadline = time.time() - 0.001
    eng.step_once()
    with pytest.raises(DeadlineExceeded):
        req.future.result(timeout=1)
    assert req.state == rz.EXPIRED
    assert eng.slots.active_count == 0 and eng.slots.free_slots == 1
    assert eng.stats()["expired"] == 1
    eng.close()


def test_invalid_deadline_policy_rejected(params):
    with pytest.raises(ValueError):
        Engine(params, CFG, num_slots=1, deadline_policy="sometimes")


# ---------------------------------------------------------------------------
# engine crash supervision
# ---------------------------------------------------------------------------


def test_engine_crash_recovers_and_stays_bit_identical(params):
    """Injected decode-loop crash: in-flight requests fail fast with
    EngineRestarted, the KV cache resets, and the recovered engine serves
    the single-shot path's exact tokens — under the recompile guard, so the
    crash→restart cycle provably compiles nothing new."""
    from galvatron_tpu.analysis import recompile_guard

    prompts = _prompts(5, seed=7)
    ref = generation.generate_np(params, CFG, prompts, max_new_tokens=6)
    eng = Engine(params, CFG, num_slots=2, prefill_chunk=4,
                 restart_backoff_s=0.01)
    eng.generate(prompts[:1], max_new_tokens=2)  # warm both programs
    with recompile_guard(_prefill_chunk, _decode_step, label="crash cycle"):
        faults.configure(engine_crash_at_iter=eng.counters.get("steps") + 2)
        futs = [eng.submit(p, 8) for p in prompts[:3]]
        failed = 0
        for f in futs:
            try:
                f.result(timeout=60)
            except EngineRestarted:
                failed += 1
        assert failed >= 1  # the crash caught requests mid-decode
        assert eng.generate(prompts, max_new_tokens=6) == ref
    st = eng.stats()
    assert st["engine_restarts"] == 1 and st["alive"]
    assert not eng.audit()["leaked"]
    eng.close()


def test_engine_restart_budget_and_progress_reset(params):
    """The restart budget counts CONSECUTIVE no-progress restarts: a
    completion between crashes resets it (elastic's committed-step rule);
    without progress the engine gives up, closes, and refuses new work."""
    p = _prompts(1, seed=8)[0]
    eng = Engine(params, CFG, num_slots=1, prefill_chunk=8,
                 max_engine_restarts=2, restart_backoff_s=0.01)
    # progress resets: crash → complete → crash → complete, budget 2 never hit
    for _ in range(2):
        faults.configure(engine_crash_at_iter=eng.counters.get("steps"))
        with pytest.raises(EngineRestarted):
            eng.submit(p, 4).result(timeout=60)
        assert eng.generate([p], max_new_tokens=2)  # progress
    assert eng.stats()["engine_restarts"] == 2 and eng.alive
    # three consecutive crashes with no completion exhaust the budget
    for i in range(3):
        faults.configure(engine_crash_at_iter=eng.counters.get("steps"))
        with pytest.raises((EngineRestarted, EngineClosed)):
            eng.submit(p, 4).result(timeout=60)
    deadline = time.time() + 10
    while time.time() < deadline and eng.alive:
        time.sleep(0.01)
    assert not eng.alive and eng.supervisor.gave_up
    with pytest.raises(EngineClosed):
        eng.submit(p, 2)
    assert not eng.audit()["leaked"]


def test_prefill_fault_fails_one_request_not_engine(params):
    """prefill_fail_at: the one request fails, its slot frees, the engine
    neither crashes nor restarts, and parallel traffic is untouched."""
    prompts = _prompts(3, seed=9)
    ref = generation.generate_np(params, CFG, prompts, max_new_tokens=4)
    eng = Engine(params, CFG, num_slots=2, prefill_chunk=4, start_loop=False)
    faults.configure(prefill_fail_at=0)
    doomed = eng.submit_request(prompts[0], 4)
    eng.step_once()
    with pytest.raises(faults.FaultInjected):
        doomed.future.result(timeout=1)
    assert doomed.state == rz.FAILED
    futs = [eng.submit(p, 4) for p in prompts]
    for _ in range(60):
        if all(f.done() for f in futs):
            break
        eng.step_once()
    assert [f.result(timeout=1) for f in futs] == ref
    st = eng.stats()
    assert st["failed"] == 1 and st["engine_restarts"] == 0
    assert not eng.audit()["leaked"]
    eng.close()


def test_crash_restart_hits_artifact_store(params, tmp_path):
    """Recovery is warm: the supervisor re-warms the three pinned programs
    (prefill, decode, the draw) from the AOT artifact store — the restart
    reports 3/3 cache hits (no
    clock is compared: a hit is the claim, and a busy host can make loading
    two tiny programs slower than compiling them)."""
    from galvatron_tpu.aot import warmup as aot_warmup
    from galvatron_tpu.aot.cache import ArtifactStore

    store = ArtifactStore(str(tmp_path / "store"))
    eng = Engine(params, CFG, num_slots=2, prefill_chunk=4,
                 restart_backoff_s=0.01)
    cold = aot_warmup.summarize(eng.warm_start(store, verbose=False))
    assert cold["compiled"] == 3 and cold["misses"] == 3
    faults.configure(engine_crash_at_iter=eng.counters.get("steps") + 1)
    with pytest.raises(EngineRestarted):
        eng.submit(_prompts(1, seed=10)[0], 8).result(timeout=60)
    deadline = time.time() + 30
    while time.time() < deadline and eng.last_restart_warm is None:
        time.sleep(0.02)
    warm = eng.last_restart_warm
    assert warm is not None, "restart did not re-warm from the store"
    assert warm["hits"] == 3 and warm["misses"] == 0, warm
    # and the recovered engine serves
    assert eng.generate(_prompts(2, seed=11), max_new_tokens=3)
    eng.close()


# ---------------------------------------------------------------------------
# graceful drain (engine level)
# ---------------------------------------------------------------------------


def test_drain_completes_in_flight_sheds_queued(params):
    faults.configure(slow_decode_ms=10)
    eng = Engine(params, CFG, num_slots=1, prefill_chunk=8,
                 drain_timeout_s=30.0)
    hog = eng.submit(_prompts(1, seed=12)[0], 10)
    deadline = time.time() + 10
    while time.time() < deadline and eng.slots.active_count == 0:
        time.sleep(0.005)
    queued = [eng.submit(p, 10) for p in _prompts(2, seed=13)]
    audit = eng.drain()
    assert hog.done() and hog.exception() is None  # in-flight completed
    for f in queued:
        assert isinstance(f.exception(), RequestShed)  # queued shed fast
    with pytest.raises(EngineClosed):
        eng.submit([1, 2], 2)
    assert not audit["leaked"] and audit["slots_ok"]
    assert eng.stats()["shed"] == 2


def test_drain_refuses_new_submissions_with_retry_hint(params):
    eng = Engine(params, CFG, num_slots=1, start_loop=False,
                 drain_timeout_s=7.0)
    eng.begin_drain()
    with pytest.raises(EngineDraining) as ei:
        eng.submit([1, 2, 3], 2)
    assert ei.value.retry_after_s == 7.0
    audit = eng.drain(timeout_s=0.1)
    assert not audit["leaked"]


def test_drain_deadline_bounds_stragglers(params):
    """A hog that cannot finish inside --drain_timeout_s is failed at the
    deadline — the process gets to exit on time, and no slot leaks."""
    faults.configure(slow_decode_ms=50)
    eng = Engine(params, CFG, num_slots=1, prefill_chunk=8)
    hog = eng.submit(_prompts(1, seed=14, hi=8)[0], 40)  # ~2s of slow steps
    deadline = time.time() + 10
    while time.time() < deadline and eng.slots.active_count == 0:
        time.sleep(0.005)
    t0 = time.monotonic()
    audit = eng.drain(timeout_s=0.3)
    assert time.monotonic() - t0 < 10.0
    assert hog.done() and isinstance(hog.exception(), EngineClosed)
    assert not audit["leaked"]


# ---------------------------------------------------------------------------
# HTTP: drain endpoint, readyz, disconnect cancellation, chaos e2e
# ---------------------------------------------------------------------------


def _start_engine_server(num_slots=2, request_ttl_s=30.0, drain_timeout_s=30.0,
                         **engine_kw):
    from galvatron_tpu.server import GenerationService, run_server

    tok = ByteTokenizer()
    params = modeling.init_model_params(jax.random.key(0), TINY)
    engine = Engine(
        params, TINY, num_slots=num_slots, prefill_chunk=8,
        request_ttl_s=request_ttl_s, eos_id=tok.eos_id, pad_id=tok.pad_id,
        drain_timeout_s=drain_timeout_s, restart_backoff_s=0.01, **engine_kw,
    )
    svc = GenerationService(params, TINY, tok, max_new_default=4, engine=engine)
    ready = threading.Event()
    t = threading.Thread(
        target=run_server, args=(svc, 0),
        kwargs={"ready_event": ready, "drain_timeout_s": drain_timeout_s},
        daemon=True,
    )
    t.start()
    assert ready.wait(10)
    return svc, engine, svc.httpd.server_address[1], t


def _post(port, body, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=30
    ) as r:
        return json.loads(r.read())


def test_http_chaos_engine_crash_under_load(tmp_path):
    """The acceptance chaos e2e: N concurrent HTTP clients, engine killed
    mid-decode via the GALVATRON_FAULTS spec → every in-flight request gets
    a well-formed 503 (detail=engine_restarted) within its deadline, the
    engine restarts, subsequent requests succeed, the crash left a
    flight-recorder dump, and the post-run slot audit shows zero leaks."""
    flight_dir = str(tmp_path / "flight")
    tracer.enable()
    try:
        svc, engine, port, _ = _start_engine_server(num_slots=2)
        engine.supervisor.flight_dir = flight_dir
        try:
            _post(port, {"prompts": ["warm"], "tokens_to_generate": 2})
            faults.init_from_env(
                f"engine_crash_at_iter={engine.counters.get('steps') + 4},"
                "slow_decode_ms=5"
            )
            outcomes = []

            def one(i):
                t0 = time.monotonic()
                try:
                    outcomes.append(("ok", _post(
                        port, {"prompts": [f"client {i}"],
                               "tokens_to_generate": 16, "ttl_s": 60.0},
                        timeout=90,
                    )))
                except urllib.error.HTTPError as e:
                    body = json.loads(e.read() or b"{}")
                    outcomes.append(("http", e.code, body,
                                     time.monotonic() - t0,
                                     e.headers.get("Retry-After")))

            with ThreadPoolExecutor(max_workers=6) as ex:
                list(ex.map(one, range(6)))
            faults.reset()
            fails = [o for o in outcomes if o[0] == "http"]
            assert fails, "crash caught no in-flight request"
            for o in fails:
                assert o[1] == 503 and o[2]["detail"] == "engine_restarted"
                assert o[3] < 60.0  # well inside the request deadline
                # engine_restarted carries Retry-After like draining 503s:
                # the supervisor's own backoff says when to come back
                assert o[4] is not None and int(o[4]) >= 1, o
            st = engine.stats()
            assert st["engine_restarts"] == 1
            # recovered: subsequent requests succeed
            assert _post(port, {"prompts": ["after"],
                                "tokens_to_generate": 4})["text"]
            assert not engine.audit()["leaked"]
            dumps = os.listdir(flight_dir)
            assert any(f.startswith("flight_") for f in dumps), dumps
        finally:
            svc.httpd.shutdown()
            engine.close()
    finally:
        tracer.disable()
        tracer.clear()


def test_http_drain_endpoint_sheds_and_exits(params):
    """POST /drain under load: /readyz goes unready immediately, new
    requests 503 with Retry-After, queued requests shed, in-flight
    completes, serve_forever returns (the process would exit 0)."""
    faults.configure(slow_decode_ms=15)
    svc, engine, port, server_thread = _start_engine_server(
        num_slots=1, drain_timeout_s=30.0
    )
    try:
        assert _get(port, "/readyz")["ready"] is True
        results = {}

        def client(name):
            try:
                results[name] = ("ok", _post(
                    port, {"prompts": [name], "tokens_to_generate": 20},
                    timeout=60,
                ))
            except urllib.error.HTTPError as e:
                results[name] = ("http", e.code,
                                 json.loads(e.read() or b"{}"))

        ths = [threading.Thread(target=client, args=(f"c{i}",))
               for i in range(3)]
        for t in ths:
            t.start()
        deadline = time.time() + 10
        while time.time() < deadline and engine.slots.active_count == 0:
            time.sleep(0.005)
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/drain", data=b"", method="POST"
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            assert json.loads(r.read())["status"] == "draining"
        # /readyz unready BEFORE the last token lands (in-flight still going)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(port, "/readyz")
        assert ei.value.code == 503
        assert _get(port, "/healthz")["status"] == "draining"
        # new admissions refused with Retry-After
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, {"prompts": ["late"], "tokens_to_generate": 2})
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After")
        for t in ths:
            t.join(timeout=60)
        server_thread.join(timeout=60)
        assert not server_thread.is_alive()  # serve_forever returned
        ok = [v for v in results.values() if v[0] == "ok"]
        shed = [v for v in results.values()
                if v[0] == "http" and v[2].get("detail") == "shed"]
        assert ok, results      # the in-flight request completed
        assert shed, results    # queued work was shed, not silently dropped
        assert not svc.drain_audit["leaked"]
    finally:
        faults.reset()
        engine.close()


def test_http_disconnect_cancels_and_frees_slot():
    """A vanished client cancels its request at the next decode iteration:
    the slot frees (cancelled_disconnect counts it) instead of burning to
    completion, and the server keeps serving."""
    svc, engine, port, _ = _start_engine_server(num_slots=2)
    try:
        faults.configure(slow_decode_ms=30)
        payload = json.dumps(
            {"prompts": ["bye"], "tokens_to_generate": 40}
        ).encode()
        s = socket.create_connection(("127.0.0.1", port))
        s.sendall(b"POST /api HTTP/1.1\r\nHost: x\r\nContent-Length: "
                  + str(len(payload)).encode() + b"\r\n\r\n" + payload)
        deadline = time.time() + 10
        while time.time() < deadline and engine.slots.active_count == 0:
            time.sleep(0.005)
        s.close()  # client gone mid-decode
        deadline = time.time() + 30
        while (time.time() < deadline
               and engine.stats()["cancelled_disconnect"] < 1):
            time.sleep(0.01)
        faults.reset()
        st = engine.stats()
        assert st["cancelled_disconnect"] >= 1, st
        assert st["active_slots"] == 0  # the slot is back
        assert svc.counters.get("cancelled") >= 1
        # server unaffected
        assert _post(port, {"prompts": ["still here"],
                            "tokens_to_generate": 2})["text"]
        assert not engine.audit()["leaked"]
    finally:
        faults.reset()
        svc.httpd.shutdown()
        engine.close()


def test_http_client_stall_fault_drives_cancellation():
    """client_stall=1 (chaos key): the disconnect poll treats the next
    connection as dead — deterministic cancellation without a real reset."""
    svc, engine, port, _ = _start_engine_server(num_slots=1)
    try:
        faults.configure(client_stall=1, slow_decode_ms=30)
        with pytest.raises(Exception):  # noqa: B017 — conn dropped, no reply
            _post(port, {"prompts": ["stall"], "tokens_to_generate": 40},
                  timeout=30)
        deadline = time.time() + 30
        while (time.time() < deadline
               and engine.stats()["cancelled_disconnect"] < 1):
            time.sleep(0.01)
        faults.reset()
        assert engine.stats()["cancelled_disconnect"] >= 1
        assert not engine.audit()["leaked"]
    finally:
        faults.reset()
        svc.httpd.shutdown()
        engine.close()


def test_http_deadline_partial_truncation_marked():
    """deadline_policy=partial over HTTP: the response carries
    "truncated": ["deadline"] instead of passing a cut-off off as done."""
    svc, engine, port, _ = _start_engine_server(num_slots=1)
    try:
        faults.configure(slow_decode_ms=40)
        out = _post(port, {"prompts": ["y" * 6], "tokens_to_generate": 50,
                           "ttl_s": 0.4}, timeout=60)
        faults.reset()
        assert out.get("truncated") == ["deadline"], out
        assert engine.stats()["expired_decode"] == 1
        assert not engine.audit()["leaked"]
    finally:
        faults.reset()
        svc.httpd.shutdown()
        engine.close()


def test_metrics_exposition_carries_resilience_families():
    from galvatron_tpu.obs.prom import server_metrics_text
    from test_obs import assert_valid_exposition

    svc, engine, port, _ = _start_engine_server(num_slots=1)
    try:
        _post(port, {"prompts": ["m"], "tokens_to_generate": 2})
        text = server_metrics_text(svc)
        assert_valid_exposition(text)
        for family in ("galvatron_serving_shed_total",
                       "galvatron_serving_cancelled_disconnect_total",
                       "galvatron_serving_expired_decode_total",
                       "galvatron_serving_engine_restarts_total",
                       "galvatron_serving_draining",
                       "galvatron_server_ready",
                       "galvatron_server_draining"):
            assert family in text, family
    finally:
        svc.httpd.shutdown()
        engine.close()


# ---------------------------------------------------------------------------
# SIGTERM e2e: zero-downtime shutdown at the process surface
# ---------------------------------------------------------------------------


def test_sigterm_drains_and_exits_zero(tmp_path):
    """`cli serve` under load + SIGTERM: in-flight completes, the drain
    audit reports zero leaks, and the process exits 0 within
    --drain_timeout_s (the zero-downtime rollout contract)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               GALVATRON_FAULTS="slow_decode_ms=25")
    proc = subprocess.Popen(
        [sys.executable, "-m", "galvatron_tpu.cli", "serve",
         "--port", "0", "--num_slots", "2", "--prefill_chunk", "8",
         "--num_layers", "1", "--hidden_size", "32", "--num_heads", "2",
         "--ffn_dim", "64", "--seq_length", "64",
         "--drain_timeout_s", "30", "--request_ttl_s", "120"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    try:
        port = None
        deadline = time.time() + 120
        for line in proc.stdout:
            m = re.search(r"listening on http://[^:]+:(\d+)/api", line)
            if m:
                port = int(m.group(1))
                break
            assert time.time() < deadline, "server never came up"
        assert port, "no listening line"
        # honor the readiness gate: the server now listens BEFORE its warm
        # start (so /readyz is pollable), and a well-behaved load balancer
        # does not route until it flips — firing during the warm window
        # would race the warm probe for the slots
        deadline = time.time() + 120
        while time.time() < deadline:
            try:
                if _get(port, "/readyz")["ready"]:
                    break
            except Exception:  # noqa: BLE001 — 503 while starting
                pass
            time.sleep(0.1)
        results = []

        def client(i):
            try:
                results.append(("ok", _post(
                    port, {"prompts": [f"sig {i}"], "tokens_to_generate": 12},
                    timeout=60)))
            except urllib.error.HTTPError as e:
                results.append(("http", e.code, json.loads(e.read() or b"{}")))
            except Exception as e:  # noqa: BLE001
                results.append(("err", repr(e)))

        ths = [threading.Thread(target=client, args=(i,)) for i in range(3)]
        for t in ths:
            t.start()
        # wait until at least one request is actually decoding
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                if _get(port, "/healthz")["serving"]["active_slots"] > 0:
                    break
            except Exception:  # noqa: BLE001
                pass
            time.sleep(0.05)
        t0 = time.monotonic()
        proc.send_signal(signal.SIGTERM)
        out_rest = proc.stdout.read()
        rc = proc.wait(timeout=60)
        elapsed = time.monotonic() - t0
        for t in ths:
            t.join(timeout=60)
        assert rc == 0, (rc, out_rest[-2000:])
        assert elapsed < 45.0, elapsed  # inside drain_timeout_s + slack
        assert "server drained: leaked=False" in out_rest, out_rest[-2000:]
        ok = [r for r in results if r[0] == "ok"]
        assert ok, results  # in-flight requests completed through the drain
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=30)


# ---------------------------------------------------------------------------
# satellites: gate audit, slot fuzz, submit-after-close, doc sync
# ---------------------------------------------------------------------------


def test_gate_returns_to_capacity_under_mixed_traffic():
    """Leak audit for the legacy-path gate: hammer mixed success / 400 /
    503 / stalled traffic and assert the gate returns to full capacity —
    a leaked permit would strangle the server one request at a time."""
    from galvatron_tpu.server import GenerationService, run_server

    tok = ByteTokenizer()
    params = modeling.init_model_params(jax.random.key(0), TINY)
    svc = GenerationService(params, TINY, tok, max_new_default=2, engine=None)
    ready = threading.Event()
    t = threading.Thread(
        target=run_server, args=(svc, 0),
        kwargs={"ready_event": ready, "max_pending": 3,
                "request_timeout_s": 2.0},
        daemon=True,
    )
    t.start()
    assert ready.wait(10)
    port = svc.httpd.server_address[1]

    def mixed(i):
        kind = i % 4
        try:
            if kind == 0:
                _post(port, {"prompts": [f"ok {i}"], "tokens_to_generate": 2})
            elif kind == 1:
                _post(port, {"prompts": []})  # 400
            elif kind == 2:
                _post(port, {"prompts": [f"big {i}"],
                             "tokens_to_generate": 10_000})  # 400 range
            else:
                # stalled body: socket timeout path must release the gate
                s = socket.create_connection(("127.0.0.1", port))
                s.sendall(b"POST /api HTTP/1.1\r\nHost: x\r\n"
                          b"Content-Length: 50\r\n\r\n{")
                time.sleep(0.1)
                s.close()
        except Exception:  # noqa: BLE001 — outcomes are the gate's problem
            pass

    try:
        with ThreadPoolExecutor(max_workers=8) as ex:
            list(ex.map(mixed, range(24)))
        deadline = time.time() + 15
        while time.time() < deadline and svc.gate.snapshot()["in_use"] > 0:
            time.sleep(0.05)
        snap = svc.gate.snapshot()
        assert snap["in_use"] == 0, snap
        assert not snap["saturated"]
        # the semaphore itself is back at capacity: capacity acquires all
        # succeed (a leak would make the last one fail)
        got = [svc.gate.acquire() for _ in range(snap["capacity"])]
        assert all(got), got
        for _ in got:
            svc.gate.release()
    finally:
        svc.httpd.shutdown()


def test_slot_allocator_randomized_fuzz():
    """Property-style fuzz over SlotKVCache: random alloc/free/reset against
    a reference model — the free list never double-frees, occupancy stays in
    [0,1], audit() holds, and fits() agrees with the slot capacity."""
    rng = np.random.RandomState(42)
    slots = SlotKVCache(TINY, 4, 32)
    active = set()
    for op in range(400):
        r = rng.rand()
        if r < 0.45:
            s = slots.alloc()
            if len(active) == 4:
                assert s is None  # exhausted → None, never an overwrite
            else:
                assert s is not None and s not in active
                active.add(s)
                slots.lengths[s] = rng.randint(0, 32)
        elif r < 0.85:
            if active:
                s = active.pop()
                slots.free(s)
                assert slots.lengths[s] == 0
                with pytest.raises(ValueError):
                    slots.free(s)  # double-free always raises
            elif rng.rand() < 0.5:
                with pytest.raises(ValueError):
                    slots.free(int(rng.randint(0, 4)))
        else:
            slots.reset()
            active.clear()
        assert 0.0 <= slots.occupancy <= 1.0
        assert slots.active_count == len(active)
        assert slots.free_slots == 4 - len(active)
        a = slots.audit()
        assert a["ok"], (op, a)
    # fits() is the slot-capacity predicate the engine trusts at submit
    for p in range(0, 40):
        for m in (0, 1, 5, 31, 32):
            assert slots.fits(p, m) == (p >= 1 and p + m <= 32)


def test_submit_after_close_raises_engine_closed(params):
    """Satellite: submit() racing close() must refuse with EngineClosed
    instead of returning a future that never resolves."""
    eng = Engine(params, CFG, num_slots=1)
    eng.close()
    with pytest.raises(EngineClosed):
        eng.submit([1, 2, 3], 4)
    with pytest.raises(EngineClosed):
        eng.submit_request([1, 2, 3], 4)


def test_design_doc_state_machine_in_sync():
    """DESIGN.md § Serving resilience must name every lifecycle state the
    code defines (GTA/GTL doc-sync style: the table cannot drift)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    text = open(os.path.join(root, "docs", "DESIGN.md")).read()
    m = re.search(r"## Serving resilience\n(.*?)(?:\n## |\Z)", text, re.S)
    assert m, "DESIGN.md has no '## Serving resilience' section"
    section = m.group(1)
    missing = [s for s in rz.STATES if s not in section]
    assert not missing, f"states missing from DESIGN.md: {missing}"
