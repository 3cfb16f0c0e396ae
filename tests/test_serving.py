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
# the loop one step ahead of its bookkeeping (PR 64)
# ---------------------------------------------------------------------------


def _until_done(eng, reqs, limit=300):
    """``step_once`` until every request is finished -> the iterations it took."""
    for n in range(limit):
        if all(r.future.done() for r in reqs):
            return n
        eng.step_once()
    raise AssertionError("the engine did not finish its requests")


def _tapped(eng, prompt, max_new_tokens, **ask):
    buf = np.full((max_new_tokens, eng.cfg.vocab_size), np.nan, np.float32)
    return eng.submit_request(prompt, max_new_tokens, capture_logits=buf, **ask)


def test_every_token_is_draw_rows_of_the_row_it_was_tapped_from(params):
    """A mix of greedy and sampled requests through three slots, served a step
    ahead of the bookkeeping: token k of every request is what
    ``generation.draw_rows`` draws from the row the tap kept for it under
    (engine seed, rid, k), which is what a synchronous loop serves; and the
    greedy ones are ``generate_np``'s."""
    seed = 2**33 + 5
    asks = [dict(), dict(temperature=0.8, top_p=0.9), dict(temperature=1.0, top_k=5),
            dict(), dict(temperature=0.7, top_k=8, top_p=0.95)]
    prompts = _prompts(5, lo=3, hi=12, seed=21)
    lengths = [7, 9, 5, 1, 8]  # (one request's first token is its last)
    eng = Engine(params, CFG, num_slots=3, prefill_chunk=4, start_loop=False, seed=seed)
    try:
        reqs = [_tapped(eng, p, n, **a) for p, n, a in zip(prompts, lengths, asks)]
        _until_done(eng, reqs)
        stats = eng.stats()
    finally:
        eng.close()
    words = jnp.asarray([seed & 0xFFFFFFFF, seed >> 32], jnp.uint32)
    for req, ask, n, prompt in zip(reqs, asks, lengths, prompts):
        assert req.finish_reason == "length" and req.logits_rows == len(req.generated) == n
        want = generation.draw_rows(
            jnp.asarray(req.capture_logits[:n]), jnp.full((n,), ask.get("temperature", 0.0)),
            jnp.full((n,), ask.get("top_k", 0), jnp.int32), jnp.full((n,), ask.get("top_p", 0.0)),
            words, jnp.full((n,), req.rid & 0xFFFFFFFF, jnp.uint32), jnp.arange(n, dtype=jnp.uint32))
        assert req.generated == list(np.asarray(want))
        if not ask:
            assert prompt + req.generated == generation.generate_np(
                params, CFG, [prompt], max_new_tokens=n)[0]
    # no eos, no cancel, no deadline: no row-step was spent on a row that had ended
    assert stats["row_steps_wasted"] == 0 and stats["draws_device"] == sum(lengths)


def _stack_cfg(kind):
    """One small configuration a kind of slot cache: whole K/V slots, a ring beside
    them (three window layers of four), a per-row state (three short-conv layers)."""
    from galvatron_tpu.models.modeling import PRESETS

    if kind == "kv":
        return CFG
    small = dict(vocab_size=96, hidden_size=32, num_layers=4, num_heads=4, num_kv_heads=2,
                 max_seq_len=64, moe_experts=4, moe_top_k=2, moe_ffn_dim=24, dtype=jnp.float32)
    if kind == "ring":
        return PRESETS["smallthinker-21b-a3b"].replace(
            **small, attn_head_dim=8, ffn_dim=24, sliding_window_size=8)
    return PRESETS["lfm2-24b-a2b"].replace(**small, ffn_dim=48)


@pytest.mark.parametrize("kind", ["kv", "ring", "state"])
def test_a_row_that_draws_eos_wastes_one_step_and_leaves_its_slot_clean(kind):
    """eos is seen when the token is booked, one iteration after its row's next
    step was sent: the request ends as a synchronous loop ends it (``eos``, the
    eos not among its tokens), ONE row-step is counted wasted, and the request
    admitted into the slot next (its prompt sent behind the wasted step) has,
    token for token and row for row, what it has served alone in a fresh engine:
    the wasted write is invisible in a K/V slot, a ring and a state."""
    cfg = _stack_cfg(kind)
    weights = modeling.init_model_params(jax.random.key(3), cfg)
    first, second = [5, 9, 2, 7, 1, 3], [8, 4, 6, 11, 2, 9, 7, 1, 3, 5]  # (three chunks)
    build = lambda **kw: Engine(weights, cfg, num_slots=1, prefill_chunk=4,  # noqa: E731
                                start_loop=False, **kw)
    eng = build()
    try:
        probe, alone = _tapped(eng, first, 20), _tapped(eng, second, 16)
        _until_done(eng, [probe, alone])
        assert eng.stats()["row_steps_wasted"] == 0
    finally:
        eng.close()
    # the token that first shows latest, not last: its row has budget left when it
    # is drawn, so its next step is on the way when the host reads it
    firsts = {}
    for i, tok in enumerate(probe.generated[:-1]):
        firsts.setdefault(tok, i)
    eos, j = max(firsts.items(), key=lambda kv: kv[1])
    assert j >= 1
    eng = build(eos_id=eos)
    try:
        ended, after = _tapped(eng, first, 20), _tapped(eng, second, 16)
        _until_done(eng, [ended])
        assert eng.stats()["row_steps_wasted"] == 1
        _until_done(eng, [after])
        stats, audit = eng.stats(), eng.audit()
    finally:
        eng.close()
    assert ended.finish_reason == "eos" and ended.generated == probe.generated[:j]
    assert ended.logits_rows == j + 1 and int(ended.capture_logits[j].argmax()) == eos
    assert after.slot == ended.slot == 0 and not audit["leaked"]  # the same slot, after it
    # (the second request may end on the eos too; up to there it is the same request)
    n = len(after.generated)
    assert after.generated == alone.generated[:n] and n >= 1
    rows = after.logits_rows
    assert np.array_equal(after.capture_logits[:rows].view(np.uint32),
                          alone.capture_logits[:rows].view(np.uint32))
    assert stats["row_steps_wasted"] == 1 + (after.finish_reason == "eos" and n + 1 < 16)


def test_cancel_and_deadline_seen_one_iteration_late_free_the_slot(params):
    """A cancel and a deadline are found when the row's token is booked, after its
    next step was sent: the slot is free at the end of that iteration, the step
    is counted wasted, the neighbour is untouched and nothing leaks."""
    prompts = _prompts(3, seed=31)
    ref = generation.generate_np(params, CFG, [prompts[2]], max_new_tokens=12)[0]
    eng = Engine(params, CFG, num_slots=3, prefill_chunk=8, start_loop=False)
    try:
        dropped, late, kept = (eng.submit_request(prompts[0], 12),
                               eng.submit_request(prompts[1], 12, ttl_s=3600.0),
                               eng.submit_request(prompts[2], 12))
        for _ in range(3):
            eng.step_once()
        assert eng.slots.active_count == 3 and eng.stats()["row_steps_wasted"] == 0
        dropped.cancel("test")
        eng.step_once()
        assert eng.slots.active_count == 2 and eng.stats()["row_steps_wasted"] == 1
        assert dropped.state == "CANCELLED" and len(dropped.generated) == 3
        late.deadline = time.time() - 1.0
        eng.step_once()
        assert eng.slots.active_count == 1 and eng.stats()["row_steps_wasted"] == 2
        assert late.finish_reason == "deadline" and len(late.generated) == 4
        assert late.future.result(timeout=1) == prompts[1] + late.generated
        _until_done(eng, [kept])
        audit = eng.audit()
    finally:
        eng.close()
    assert kept.future.result(timeout=1) == ref and not audit["leaked"]
    assert audit["free_slots"] == 3 and audit["tracked_requests"] == 0


@pytest.mark.parametrize("backend", [{}, {"kv_num_blocks": -1, "kv_block_size": 8}],
                         ids=["slot", "paged"])
def test_a_tapped_row_outlives_the_prompt_chunks_sent_behind_it(params, backend):
    """The prefill program DONATES the rows and the next step replaces them, and
    both are sent before the host reads the rows a token was drawn from: a tapped
    request served while three-chunk prompts are admitted beside it has, bit for
    bit, the rows it has served alone, each the row its token was drawn from."""
    prompt, joiners = [5, 9, 2, 7, 1, 3], _prompts(3, lo=9, hi=12, seed=41)

    def serve(beside):
        eng = Engine(params, CFG, num_slots=2, prefill_chunk=4, start_loop=False, **backend)
        try:
            req = _tapped(eng, prompt, 14)
            others = []
            for i in range(60):
                if i in (2, 5, 9) and beside:
                    # admitted at the head of the next iteration: its chunks go out
                    # between the tapped row's draw and the read of that row
                    others.append(_tapped(eng, beside[len(others)], 3, temperature=0.8))
                if req.future.done() and all(o.future.done() for o in others):
                    break
                eng.step_once()
            assert eng.stats()["prefill_chunks"] >= 2 + 3 * len(others)
            return req, others
        finally:
            eng.close()

    alone, _ = serve([])
    crowded, others = serve(joiners)
    assert len(others) == 3 and all(o.logits_rows == 3 for o in others)
    assert crowded.generated == alone.generated and crowded.logits_rows == 14
    assert np.array_equal(crowded.capture_logits.view(np.uint32),
                          alone.capture_logits.view(np.uint32))
    assert list(crowded.capture_logits.argmax(-1)) == crowded.generated
    assert prompt + crowded.generated == generation.generate_np(
        params, CFG, [prompt], max_new_tokens=14)[0]


def test_steps_ahead_counts_the_steps_sent_before_the_last_ids_were_read(params):
    """One request of six tokens: five forwards (its last token is fed to none),
    the first behind the prompt, the other four sent while the step before was
    still unread; the sixth iteration sends nothing and books the last token. An
    engine that stood idle starts over: its next request's first step is not
    ahead of anything."""
    eng = Engine(params, CFG, num_slots=2, prefill_chunk=8, start_loop=False)
    try:
        req = eng.submit_request(_prompts(1, seed=51)[0], 6)
        ahead = []
        while not req.future.done():
            eng.step_once()
            ahead.append(eng.stats()["steps_ahead"])
        assert ahead == [0, 1, 2, 3, 4, 4] and eng.stats()["steps"] == 6
        assert len(req.generated) == 6 and eng.stats()["row_steps_wasted"] == 0
        again = eng.submit_request(_prompts(1, seed=52)[0], 3)
        assert _until_done(eng, [again]) == 3
        assert eng.stats()["steps_ahead"] == 4 + 1 and eng.stats()["steps"] == 9
    finally:
        eng.close()


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
