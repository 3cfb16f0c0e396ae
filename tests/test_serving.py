"""Continuous-batching serving engine: slots, scheduler, engine parity,
shared decode iterations, TTL/backpressure, and the HTTP end-to-end path."""

import json
import os
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.models import generation, modeling
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.models.tokenizer import ByteTokenizer, pad_vocab_size
from galvatron_tpu.serving import (
    Engine,
    QueueFull,
    Request,
    RequestExpired,
    Scheduler,
    SlotKVCache,
)
from galvatron_tpu.serving.engine import _decode_step, _prefill_chunk

from tests._serving_common import CFG, params, prompts as _prompts  # noqa: F401  (`params`: a fixture)


# ---------------------------------------------------------------------------
# kv_slots
# ---------------------------------------------------------------------------


def test_slot_alloc_free_reset():
    slots = SlotKVCache(CFG, 3, 32)
    assert slots.cache.k.shape == (2, 3, 32, 2, 16)
    a, b = slots.alloc(), slots.alloc()
    assert {a, b} == {0, 1} and slots.free_slots == 1
    slots.lengths[a] = 7
    slots.free(a)
    assert slots.lengths[a] == 0 and slots.free_slots == 2
    with pytest.raises(ValueError):
        slots.free(a)  # double free
    c, d = slots.alloc(), slots.alloc()
    assert d is not None and slots.alloc() is None  # exhausted → None
    assert slots.occupancy == 1.0
    slots.reset()
    assert slots.free_slots == 3 and slots.active_count == 0
    # capacity accounting: the whole request lifetime must fit the slot
    assert slots.fits(10, 22) and not slots.fits(10, 23) and not slots.fits(0, 1)


def test_slot_max_seq_len_clamped_to_model():
    slots = SlotKVCache(CFG, 2, 10_000)
    assert slots.max_seq_len == CFG.max_seq_len


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------


def test_scheduler_fifo_and_backpressure():
    s = Scheduler(max_queue=2, default_ttl_s=None)
    r1 = s.submit(Request(tokens=[1], max_new_tokens=1))
    r2 = s.submit(Request(tokens=[2], max_new_tokens=1))
    with pytest.raises(QueueFull):
        s.submit(Request(tokens=[3], max_new_tokens=1))
    assert s.saturated and s.depth == 2
    assert s.pop() is r1 and s.pop() is r2 and s.pop() is None  # FIFO
    c = s.counters.snapshot()
    assert c["submitted"] == 2 and c["admitted"] == 2
    assert c["rejected_queue_full"] == 1


def test_scheduler_ttl_expiry_fails_future():
    s = Scheduler(max_queue=8, default_ttl_s=0.01)
    r = s.submit(Request(tokens=[1], max_new_tokens=1))
    keeper = s.submit(Request(tokens=[2], max_new_tokens=1), ttl_s=60.0)
    time.sleep(0.03)
    assert s.pop() is keeper  # expired head shed, live request admitted
    with pytest.raises(RequestExpired):
        r.future.result(timeout=1)
    assert s.counters.get("expired") == 1


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


def test_engine_matches_generate_np_greedy(params):
    """Requests sharing decode iterations produce exactly what the
    single-shot path produces — continuous batching is a scheduling change,
    not a model change. More requests than slots forces slot reuse."""
    prompts = _prompts(5)
    ref = generation.generate_np(params, CFG, prompts, max_new_tokens=6)
    with Engine(params, CFG, num_slots=2, prefill_chunk=4) as eng:
        out = eng.generate(prompts, max_new_tokens=6)
        st = eng.stats()
    assert out == ref
    assert st["completed"] == 5 and st["active_slots"] == 0
    assert st["num_slots"] == 2  # 5 requests through 2 slots → reuse


def test_engine_shares_decode_iterations(params):
    """Driven deterministically: 4 requests admitted together decode in
    lockstep, so the iteration count is ~max(tokens) not sum(tokens)."""
    prompts = _prompts(4, lo=4, hi=8, seed=1)
    n_new = 8
    eng = Engine(params, CFG, num_slots=4, prefill_chunk=8, start_loop=False)
    futs = [eng.submit(p, n_new) for p in prompts]
    steps = 0
    while not all(f.done() for f in futs):
        eng.step_once()
        steps += 1
        assert steps < 100
    total = sum(len(f.result(timeout=1)) - len(p) for f, p in zip(futs, prompts))
    assert total == 4 * n_new
    # serial decode would need one iteration per generated token
    assert steps < total
    assert eng.stats()["steps"] == steps
    eng.close()


def test_engine_slot_reuse_across_requests(params):
    """A retired request's slot is handed to the next queued request."""
    prompts = _prompts(3, seed=2)
    eng = Engine(params, CFG, num_slots=1, prefill_chunk=8, start_loop=False)
    futs = [eng.submit(p, 3) for p in prompts]
    eng.step_once()
    # FIFO: the first submitted request holds the slot first
    assert eng._by_slot[0].tokens == prompts[0]
    for _ in range(40):
        if all(f.done() for f in futs):
            break
        eng.step_once()
    assert all(f.done() for f in futs)
    assert eng.stats()["completed"] == 3
    # all three ran through the single slot, one after another
    assert eng.slots.free_slots == 1
    ref = generation.generate_np(params, CFG, prompts, max_new_tokens=3)
    assert [f.result(timeout=1) for f in futs] == ref
    eng.close()


def test_engine_ttl_expires_queued_request(params):
    """A request out-waiting its TTL in queue fails with RequestExpired —
    it never takes the slot from live traffic."""
    eng = Engine(params, CFG, num_slots=1, prefill_chunk=8, start_loop=False)
    hog = eng.submit(_prompts(1, seed=3)[0], 10)
    eng.step_once()  # hog admitted into the only slot
    doomed = eng.submit(_prompts(1, seed=4)[0], 4, ttl_s=0.01)
    time.sleep(0.03)
    eng.step_once()  # expiry happens at iteration granularity
    with pytest.raises(RequestExpired):
        doomed.result(timeout=1)
    assert eng.stats()["expired"] == 1
    # the hog is unaffected
    for _ in range(20):
        if hog.done():
            break
        eng.step_once()
    assert hog.done() and hog.exception() is None
    eng.close()


def test_engine_queue_full_rejects(params):
    eng = Engine(params, CFG, num_slots=1, max_queue=1, start_loop=False)
    eng.submit([1, 2], 4)
    with pytest.raises(QueueFull):
        eng.submit([3, 4], 4)
    assert eng.stats()["rejected_queue_full"] == 1
    eng.close()


def test_engine_eos_retires_row(params):
    """eos sampled → row retires mid-flight and the completion excludes it
    (generate_np row semantics)."""
    p = _prompts(1, seed=5)[0]
    ref = generation.generate_np(params, CFG, [p], max_new_tokens=1)[0]
    eos = ref[-1]  # greedy's first emitted token, reused as eos
    with Engine(params, CFG, num_slots=1, eos_id=eos) as eng:
        out = eng.generate([p], max_new_tokens=8)[0]
    assert out == p  # first sampled token == eos → empty completion


def test_engine_oversized_request_rejected(params):
    with Engine(params, CFG, num_slots=1, max_seq_len=16) as eng:
        with pytest.raises(ValueError):
            eng.submit(list(range(1, 10)), 8)  # 9 + 8 > 16
        out = eng.generate([[1, 2, 3]], max_new_tokens=2)
        assert len(out[0]) == 5  # engine still serves well-sized requests


def test_prefill_window_at_slot_end(params):
    """When the last prefill window would cross the slot end (max_seq_len
    not a multiple of prefill_chunk), it slides left instead of letting
    dynamic_update_slice clamp the start (which would silently shift the
    write over earlier positions). Parity pins the rewrite as idempotent."""
    prompts = [list(np.random.RandomState(9).randint(1, CFG.vocab_size, (35,))),
               [5, 6, 7]]
    ref = generation.generate_np(params, CFG, prompts, max_new_tokens=6)
    # slot len 51, chunk 32: the 35-token prompt's second window [32, 64)
    # crosses 51 and must slide to [19, 51)
    with Engine(params, CFG, num_slots=2, prefill_chunk=32, max_seq_len=51) as eng:
        out = eng.generate(prompts, max_new_tokens=6)
    assert out == ref


def test_engine_jit_cache_stays_bounded(params):
    """The whole point of fixed shapes: traffic of any mix compiles exactly
    one prefill program and one decode program (recompile_guard raises,
    naming the offender, if any traffic mix grows the cache)."""
    from galvatron_tpu.analysis import recompile_guard

    with Engine(params, CFG, num_slots=2, prefill_chunk=4) as eng:
        eng.generate(_prompts(3, seed=6), max_new_tokens=3)
        with recompile_guard(_prefill_chunk, _decode_step, label="traffic mix"):
            eng.generate(_prompts(4, lo=5, hi=13, seed=7), max_new_tokens=5,
                         temperature=0.7, top_k=3, top_p=0.9)


def test_slotwise_forward_matches_scalar_offset(params):
    """forward_with_cache at uniform per-row offsets == at a scalar offset
    (the slot-wise entry point degrades to the lockstep one)."""
    cache = generation.init_kv_cache(CFG, 2, 32)
    toks = jnp.asarray(np.random.RandomState(8).randint(1, CFG.vocab_size, (2, 5)), jnp.int32)
    from tests._stack_harness import step_forward

    l_ref, c_ref = step_forward(params, CFG, cache, toks, 0)
    l_slot, c_slot = step_forward(params, CFG, cache, toks, jnp.zeros((2,), jnp.int32))
    np.testing.assert_allclose(np.asarray(l_ref), np.asarray(l_slot), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(c_ref.k), np.asarray(c_slot.k), rtol=1e-5)


@pytest.mark.parametrize("program", ["prefill_chunk", "decode_step", "decode_verify"])
def test_engine_programs_match_the_replaced_forwards_bitwise(params, program):
    """The engine's three jitted programs over a DONATED cache of four slots,
    against the forwards they ran before the cache was written in place (the
    row sliced out and written back for a prefill chunk; a vmapped update and
    a re-stack for the decode step and its 1 + k verify window): logits and
    cache bit for bit, with ragged offsets and an inactive (0, 0) row, and
    every slot a prefill chunk does not name left as it was."""
    import _cached_forward_reference as ref
    from galvatron_tpu.serving.engine import _decode_verify

    smax = 32
    cache = ref.random_cache(CFG, 4, smax, seed=5)
    fresh = lambda: jax.tree.map(jnp.copy, cache)  # noqa: E731 — the programs donate theirs
    rng = np.random.RandomState(9)
    if program == "prefill_chunk":
        toks = jnp.asarray(rng.randint(1, CFG.vocab_size, (1, 4)), jnp.int32)
        slot, offset = np.int32(2), np.int32(8)
        # the program keeps ONE row of the chunk's logits, row ``last``, in row
        # ``slot`` of the engine's rows: ask for each in turn, on rows whose
        # other slots must come back as they went in
        before = np.asarray(rng.randn(4, CFG.vocab_size), np.float32)
        got = []
        for last in range(4):
            rows, out, counters = _prefill_chunk(params, CFG, fresh(), toks, slot, offset,
                                                 jnp.asarray(before), np.int32(last))
            assert counters == {}  # (a model without expert layers hands up none: PR 57)
            rows = np.asarray(rows)
            np.testing.assert_array_equal(np.delete(rows, slot, 0), np.delete(before, slot, 0))
            got.append(rows[slot])
        logits = np.stack(got)
        # (compared under jit, as the engine runs: eager steps round otherwise)
        ref_logits, ref_out = jax.jit(
            lambda c, t, sl, o: ref.prefill_chunk(params, t, CFG, c, sl, o)
        )(cache, toks, slot, offset)
        ref_logits = ref_logits[0]
        others = np.asarray([0, 1, 3])
        np.testing.assert_array_equal(np.asarray(out.k)[:, others], np.asarray(cache.k)[:, others])
        np.testing.assert_array_equal(np.asarray(out.v)[:, others], np.asarray(cache.v)[:, others])
    else:
        width = 1 if program == "decode_step" else 4
        toks = jnp.asarray(rng.randint(1, CFG.vocab_size, (4, width)), jnp.int32)
        toks = toks.at[1].set(0)  # the inactive row: token 0 at offset 0
        offsets = jnp.asarray([5, 0, 17, smax - width], jnp.int32)
        if program == "decode_step":
            logits, out, counters = _decode_step(params, CFG, fresh(), toks[:, 0], offsets)
        else:
            logits, out, counters = _decode_verify(params, CFG, fresh(), toks, offsets)
        assert counters == {}  # (a dense model has no expert layers to count: PR 51)
        ref_logits, ref_out = jax.jit(
            lambda c, t, o: ref.forward_with_cache_slots(params, t, CFG, c, o)
        )(cache, toks, offsets)
        if program == "decode_step":
            ref_logits = ref_logits[:, 0]
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    np.testing.assert_array_equal(np.asarray(out.k), np.asarray(ref_out.k))
    np.testing.assert_array_equal(np.asarray(out.v), np.asarray(ref_out.v))


# ---------------------------------------------------------------------------
# HTTP end-to-end
# ---------------------------------------------------------------------------

TINY = ModelConfig(
    vocab_size=pad_vocab_size(259),
    hidden_size=32,
    num_layers=1,
    num_heads=2,
    ffn_dim=64,
    max_seq_len=64,
    dtype=jnp.float32,
)


def _start_engine_server(num_slots=4, max_queue=16, request_ttl_s=30.0):
    from galvatron_tpu.server import GenerationService, run_server

    tok = ByteTokenizer()
    params = modeling.init_model_params(jax.random.key(0), TINY)
    engine = Engine(
        params, TINY, num_slots=num_slots, prefill_chunk=8,
        max_queue=max_queue, request_ttl_s=request_ttl_s,
        eos_id=tok.eos_id, pad_id=tok.pad_id,
    )
    svc = GenerationService(params, TINY, tok, max_new_default=4, engine=engine)
    ready = threading.Event()
    t = threading.Thread(target=run_server, args=(svc, 0),
                         kwargs={"ready_event": ready}, daemon=True)
    t.start()
    assert ready.wait(10)
    return svc, engine, svc.httpd.server_address[1], params, tok


def _post(port, body, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _healthz(port):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
        return json.loads(r.read())


def test_http_overlapping_requests_share_engine():
    """≥4 overlapping HTTP requests through one engine: all complete with
    the single-shot path's exact tokens, decode iterations are shared
    (step count < serial sum), and slots are reused across requests."""
    svc, engine, port, params, tok = _start_engine_server(num_slots=2)
    try:
        prompts = ["hello", "serving", "tpu", "batch", "engine!"]
        n_new = 8
        with ThreadPoolExecutor(max_workers=len(prompts)) as ex:
            results = list(ex.map(
                lambda p: _post(port, {"prompts": [p], "tokens_to_generate": n_new}),
                prompts,
            ))
        for p, body in zip(prompts, results):
            ref = generation.generate_np(
                params, TINY, [tok.encode(p)], max_new_tokens=n_new,
                eos_id=tok.eos_id, pad_id=tok.pad_id,
            )[0]
            assert body["tokens"][0] == ref
            assert body["text"][0] == tok.decode(ref[len(tok.encode(p)):])
        h = _healthz(port)
        assert h["requests"]["succeeded"] == len(prompts)
        s = h["serving"]
        total_generated = s["tokens_generated"]
        # serial decode needs >= one iteration per generated token; sharing
        # must beat that even though 5 requests squeezed through 2 slots
        assert s["steps"] < total_generated
        assert s["completed"] == len(prompts) and s["num_slots"] == 2
        assert s["active_slots"] == 0 and s["queue_depth"] == 0
        assert s["ttft_p50_s"] is not None and s["ttft_p95_s"] >= s["ttft_p50_s"]
        assert s["tokens_per_s"] > 0
        # GET /metrics next to /healthz: Prometheus text exposition carrying
        # the serving counters and TTFT quantiles (obs/prom.py)
        from test_obs import assert_valid_exposition

        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30
        ) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        assert_valid_exposition(text)
        assert f"galvatron_serving_completed_total {len(prompts)}" in text
        assert f"galvatron_server_requests_total{{outcome=\"succeeded\"}} " \
               f"{len(prompts)}" in text
        assert 'galvatron_serving_ttft_seconds{quantile="0.5"}' in text
        assert 'galvatron_serving_ttft_seconds{quantile="0.95"}' in text
        assert "galvatron_serving_tokens_generated_total" in text
        assert "galvatron_model_info{" in text
    finally:
        svc.httpd.shutdown()
        engine.close()


def test_http_profile_capture_endpoint():
    """POST /profile: bounded on-demand jax.profiler capture keyed to engine
    decode iterations; bad params 400; no engine → 400."""
    svc, engine, port, params, tok = _start_engine_server(num_slots=2)
    try:
        # drive some decode activity concurrently so the capture sees steps
        with ThreadPoolExecutor(max_workers=2) as ex:
            gen = ex.submit(
                _post, port, {"prompts": ["profile me"], "tokens_to_generate": 24}
            )
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/profile?steps=2&timeout_s=20",
                data=b"{}", method="POST",
            )
            with urllib.request.urlopen(req, timeout=60) as r:
                resp = json.loads(r.read())
            gen.result(timeout=60)
        assert resp["requested"] == 2 and os.path.isdir(resp["trace_dir"])
        assert resp["steps_captured"] >= 0
        # the capture was one profiler window: closed, and where it went is kept
        from galvatron_tpu.obs import flight
        from galvatron_tpu.obs.tracing import tracer

        win = flight.last_profile_window()
        assert not tracer.profiling and win["trace_dir"] == resp["trace_dir"]
        assert win["xplane"] == resp["xplane"] and win["first_step"] is not None
        # usage errors are 400s, not tracebacks
        bad = urllib.request.Request(
            f"http://127.0.0.1:{port}/profile?steps=0", data=b"{}", method="POST"
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad, timeout=30)
        assert ei.value.code == 400
    finally:
        svc.httpd.shutdown()
        engine.close()


def test_http_ttl_rejects_queued_request_with_503():
    """With the only slot hogged, a short-TTL request 503s from the queue
    instead of waiting for the slot."""
    svc, engine, port, params, tok = _start_engine_server(
        num_slots=1, request_ttl_s=30.0
    )
    try:
        hog_done = []
        def hog():
            hog_done.append(_post(port, {"prompts": ["x" * 8], "tokens_to_generate": 50}))
        t = threading.Thread(target=hog)
        t.start()
        deadline = time.time() + 10
        while time.time() < deadline and engine.slots.active_count == 0:
            time.sleep(0.005)
        assert engine.slots.active_count == 1
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, {"prompts": ["y"], "tokens_to_generate": 4, "ttl_s": 0.02})
        assert ei.value.code == 503
        t.join(timeout=120)
        assert hog_done  # the hog still completed fine
        h = _healthz(port)
        assert h["requests"]["rejected"] == 1
        assert h["serving"]["expired"] == 1
    finally:
        svc.httpd.shutdown()
        engine.close()


def test_http_queue_full_503_and_counter_split():
    """Queue saturation 503s; the probe separates succeeded/failed/rejected."""
    svc, engine, port, params, tok = _start_engine_server(
        num_slots=1, max_queue=1
    )
    try:
        # bad request → failed counter
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, {"prompts": []})
        assert ei.value.code == 400
        # hog the slot, fill the queue, then overflow it
        t = threading.Thread(target=lambda: _post(
            port, {"prompts": ["x" * 8], "tokens_to_generate": 50}))
        t.start()
        deadline = time.time() + 10
        while time.time() < deadline and engine.slots.active_count == 0:
            time.sleep(0.005)
        filler = threading.Thread(target=lambda: _post(
            port, {"prompts": ["f"], "tokens_to_generate": 1}))
        filler.start()
        deadline = time.time() + 10
        while time.time() < deadline and engine.scheduler.depth == 0:
            time.sleep(0.002)
        got_503 = False
        for _ in range(50):  # race the filler's admission
            try:
                _post(port, {"prompts": ["z"], "tokens_to_generate": 1})
            except urllib.error.HTTPError as e:
                assert e.code == 503
                got_503 = True
                break
        assert got_503
        t.join(timeout=120)
        filler.join(timeout=120)
        h = _healthz(port)
        assert h["requests"]["failed"] == 1      # the 400
        assert h["requests"]["rejected"] >= 1    # the queue-full 503
        assert h["requests"]["succeeded"] >= 2   # hog + filler
        assert h["serving"]["rejected_queue_full"] >= 1
    finally:
        svc.httpd.shutdown()
        engine.close()


def test_dead_socket_does_not_kill_handler():
    """A client that disconnects mid-generation: no traceback storm, the
    server keeps serving, and the request either completed before the
    disconnect poll noticed (fast generation wins the race) or was
    cancelled to free its slot — never a leaked slot or a wedged handler.
    (tests/test_serving_resilience.py pins the deterministic cancellation
    path with a slowed decode.)"""
    import socket

    svc, engine, port, params, tok = _start_engine_server(num_slots=2)
    try:
        payload = json.dumps({"prompts": ["bye"], "tokens_to_generate": 30}).encode()
        s = socket.create_connection(("127.0.0.1", port))
        s.sendall(b"POST /api HTTP/1.1\r\nHost: x\r\nContent-Length: "
                  + str(len(payload)).encode() + b"\r\n\r\n" + payload)
        s.close()  # gone before the engine finishes
        deadline = time.time() + 60
        while time.time() < deadline and (
            svc.counters.get("succeeded") + svc.counters.get("cancelled") < 1
        ):
            time.sleep(0.01)
        assert svc.counters.get("succeeded") + svc.counters.get("cancelled") == 1
        body = _post(port, {"prompts": ["still here"], "tokens_to_generate": 2})
        assert body["text"] and _healthz(port)["status"] == "ok"
        assert engine.slots.active_count == 0  # no slot leaked either way
    finally:
        svc.httpd.shutdown()
        engine.close()
