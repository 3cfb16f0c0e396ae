"""The serving engine's own observability (PR 39): the always-on host times and
the logits tap on ``Request``, the span tree of one iteration on the loop
thread, the queue's wait on a track of its own, and what tracing costs when it
is off (no ring record, no annotation, no clock read through the tracer).
CPU, a toy model; no number here is a device number."""

import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from galvatron_tpu.models import modeling  # noqa: E402
from galvatron_tpu.models.modeling import ModelConfig  # noqa: E402
from galvatron_tpu.obs import tracing  # noqa: E402
from galvatron_tpu.obs.tracing import tracer  # noqa: E402
from galvatron_tpu.serving import Engine  # noqa: E402

CFG = ModelConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4, ffn_dim=128,
                  max_seq_len=64)
#: (engine flags, the span of the iteration's shared forward)
BACKENDS = {
    "slot-plain": ({}, "decode"),
    "paged-plain": ({"kv_num_blocks": -1, "kv_block_size": 8}, "decode"),
    "slot-verify": ({"spec_decode_k": 2}, "decode_verify"),
    "paged-verify": ({"kv_num_blocks": -1, "kv_block_size": 8, "spec_decode_k": 2},
                     "decode_verify"),
}


@pytest.fixture(scope="module")
def params():
    return modeling.init_model_params(jax.random.key(0), CFG)


class _EchoDrafter:
    """Drafts the last token again: a draft for every row, so every iteration
    of a speculating engine runs the verify program."""

    def draft(self, tokens, k):
        return [int(tokens[-1])] * k


def _engine(params, backend="slot-plain", **kw):
    flags, _ = BACKENDS[backend]
    eng = Engine(params, CFG, num_slots=kw.pop("num_slots", 2), prefill_chunk=8,
                 start_loop=False, **flags, **kw)
    if eng.spec_k:
        eng.drafter = _EchoDrafter()
    return eng


def _drive(eng, reqs, limit=200):
    for _ in range(limit):
        if all(r.future.done() for r in reqs):
            return
        eng.step_once()
    raise AssertionError("the engine did not finish its requests")


@pytest.fixture(autouse=True)
def _empty_ring():
    """Every test starts on an empty ring: a traced run of another file in this
    process (the benchmark's runner disables the tracer and leaves its ring as it
    was) must not show up here as spans of this file's engines."""
    if not tracer.enabled:
        tracer.clear()


@pytest.fixture()
def traced():
    assert not tracer.enabled
    tracer.enable(capacity=1 << 14)
    try:
        yield tracer
    finally:
        tracer.disable()
        tracer.clear()


def _spans(trc, name=None):
    return [r for r in trc.snapshot() if r["ph"] == "X" and (name is None or r["name"] == name)]


def _inside(child, parent, slack_us=1.0):
    return (parent["ts"] - slack_us <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + slack_us)


# --- tracer off: what is always on, and what is not there -------------------


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_request_times_are_filled_and_ordered_with_the_tracer_off(params, backend, monkeypatch):
    assert not tracer.enabled
    for ann in ("TraceAnnotation", "StepTraceAnnotation"):
        monkeypatch.setattr(jax.profiler, ann, lambda *a, **k: pytest.fail("annotation while off"))
    reads = []

    class CountingTime:
        """The tracer's own view of ``time``: any read through it is counted."""

        def __getattr__(self, name):
            reads.append(name)
            return getattr(time, name)

    monkeypatch.setattr(tracing, "time", CountingTime())
    handed_in = tracer._enqueued
    eng = _engine(params, backend)
    try:
        reqs = [eng.submit_request([1, 2, 3, 4, 5], 6), eng.submit_request([7, 8, 9], 4,
                                                                          temperature=0.8),
                eng.submit_request([9, 8, 7, 6], 3)]
        _drive(eng, reqs)
    finally:
        eng.close()
    assert tracer.snapshot() == [] and reads == []
    assert tracer._enqueued == handed_in and tracer._pending == {}  # (no completion span)
    for r in reqs:
        assert len(r.token_times) == len(r.generated) == r.max_new_tokens
        stamps = [r.submitted_at, r.admitted_at, r.first_token_at, *r.token_times, r.finished_at]
        assert all(s is not None for s in stamps)
        assert stamps == sorted(stamps)
    # the third request waited for a slot: admitted after the first's first token
    assert reqs[2].admitted_at >= reqs[0].first_token_at


def test_a_request_that_never_takes_a_slot_is_finished_without_being_admitted(params):
    eng = _engine(params, num_slots=1)
    try:
        a = eng.submit_request([1, 2, 3], 4)
        b = eng.submit_request([4, 5, 6], 4)
        b.cancel("test")
        _drive(eng, [a, b])
    finally:
        eng.close()
    assert b.admitted_at is None and b.token_times == [] and b.finished_at >= b.submitted_at
    assert a.finished_at >= a.token_times[-1]


# --- tracer on: the tree of one iteration ----------------------------------------------


@pytest.fixture()
def traced_run(params, traced, request):
    """Three requests through two slots under the tracer, one cancelled while it
    decodes; the ring's spans and the requests."""
    backend = request.param
    eng = _engine(params, backend)
    try:
        reqs = [eng.submit_request([1, 2, 3, 4, 5], 8), eng.submit_request([7, 8, 9], 8,
                                                                          temperature=0.8),
                eng.submit_request([9, 8, 7, 6], 3)]
        for _ in range(3):
            eng.step_once()
        reqs[1].cancel("test")
        _drive(eng, reqs)
    finally:
        eng.close()
    return {"backend": backend, "spans": _spans(traced), "reqs": reqs,
            "forward": BACKENDS[backend][1], "all": traced.snapshot()}


@pytest.mark.parametrize("traced_run", sorted(BACKENDS), indirect=True)
def test_every_child_lies_inside_its_parent_on_the_loop_thread(traced_run):
    """(PR 72: what ``admit`` holds on the loop thread is the admission's HOST side,
    ``prefill_dispatch`` around one ``chunk_dispatch`` a chunk; ``prefill``, the
    prompt's chunks on the device, is no longer this thread's: next case.)"""
    spans, fwd = traced_run["spans"], traced_run["forward"]
    parents = {"admit": "iteration", "prefill_dispatch": "admit",
               "chunk_dispatch": "prefill_dispatch", "sample": "iteration",
               "sample_slot": "sample", fwd: "iteration", "decode_dispatch": fwd,
               "decode_wait": fwd, "logits_readback": fwd}
    loop_tid = {s["tid"] for s in spans if s["name"] == "iteration"}
    assert len(loop_tid) == 1
    for child, parent in parents.items():
        mine = [s for s in spans if s["name"] == child]
        assert mine, child
        for s in mine:
            assert s["tid"] in loop_tid
            hosts = [p for p in spans if p["name"] == parent and _inside(s, p)]
            assert len(hosts) == 1, (child, parent)
            assert s["depth"] == hosts[0]["depth"] + 1
    assert all(s["depth"] == 0 for s in spans if s["name"] == "iteration")
    # nothing else is the loop thread's (but what the runtime did behind its back: a first
    # run's compiles, a collection): the two tracks that are no thread's hold the rest
    assert {s["name"] for s in spans if s["tid"] in loop_tid
            and not s["name"].startswith("jax_") and s["name"] != "gc"} == (
        set(parents) | {"iteration"})
    assert {s["name"]: s["tname"] for s in spans if s["tid"] not in loop_tid} == {
        "queue_wait": "serving queue", "prefill": "device"}


@pytest.mark.parametrize("traced_run", sorted(BACKENDS), indirect=True)
def test_a_prompts_device_span_lies_on_the_device_track_with_its_chunks(traced_run):
    """One ``prefill`` a request on the track ``device``: the arguments its readers take
    (``rid``, ``tokens``, ``synced``) plus ``chunks`` and ``depth_sum``, which are what its
    ``prefill_dispatch`` and that span's ``chunk_dispatch`` children say: ``seq`` numbers
    the prefill program's executions, consecutive over the run."""
    spans, reqs = traced_run["spans"], traced_run["reqs"]
    prompts = {r.rid: len(r.tokens) for r in reqs}
    prefills = {s["args"]["rid"]: s for s in spans if s["name"] == "prefill"}
    sends = {s["args"]["rid"]: s for s in spans if s["name"] == "prefill_dispatch"}
    assert set(prefills) == set(sends) == set(prompts)
    chunks = sorted((s for s in spans if s["name"] == "chunk_dispatch"), key=lambda s: s["ts"])
    assert [c["args"]["seq"] for c in chunks] == list(range(len(chunks)))
    for rid, tokens in prompts.items():
        device, send = prefills[rid], sends[rid]
        assert (device["tid"], device["tname"], device["depth"]) == (
            tracing._track_tid("device"), "device", 0)
        assert device["args"]["synced"] is True and "error" not in device["args"]
        mine = [c["args"] for c in chunks if _inside(c, send)]
        assert send["args"] == {"rid": rid, "tokens": tokens, "chunks": len(mine),
                                "first_start": mine[0]["start"]}
        assert sum(c["rows"] for c in mine) == tokens - send["args"]["first_start"]
        assert {"rid": rid, "tokens": tokens, "chunks": len(mine),
                "depth_sum": sum(c["start"] for c in mine)}.items() <= device["args"].items()
        # the device is not through with a prompt before its first chunk was sent
        assert device["ts"] + device["dur"] >= send["ts"]
    if traced_run["forward"] == "decode":
        # (drawing on the device, nobody on the loop thread saw the chunks through: the
        # completion worker did)
        assert all("step" not in s["args"] for s in prefills.values())


@pytest.mark.parametrize("traced_run", sorted(BACKENDS), indirect=True)
def test_iterations_are_numbered_by_the_engines_steps(traced_run):
    its = [s for s in traced_run["spans"] if s["name"] == "iteration"]
    assert [s["args"]["step"] for s in its] == list(range(len(its)))
    assert all({"active", "queued"} <= set(s["args"]) for s in its)
    assert its[0]["args"]["queued"] == 3 and its[0]["args"]["active"] == 0


def test_a_compile_inside_an_iteration_carries_its_number(traced):
    """What the runtime does behind the loop's back names the iteration it fell
    into: a model no other test compiled, so both programs compile here, the
    prefill program and the first decode step inside iteration 0."""
    cfg = CFG.replace(vocab_size=136)
    eng = Engine(modeling.init_model_params(jax.random.key(1), cfg), cfg, num_slots=2,
                 prefill_chunk=8, start_loop=False)
    try:
        req = eng.submit_request([1, 2, 3], 4)
        _drive(eng, [req])
    finally:
        eng.close()
    compiles = {s["args"]["fun_name"]: s for s in _spans(traced, "jax_compile")
                if s["args"]["fun_name"] in ("jit(_prefill_chunk)", "jit(_decode_step)")}
    assert len(compiles) == 2
    assert all(s["args"]["step"] == 0 for s in compiles.values())
    lowered = [s for s in _spans(traced, "jax_lower") if "decode_step" in s["args"]["fun_name"]]
    assert lowered and lowered[0]["args"]["step"] == 0
    first = _spans(traced, "iteration")[0]
    assert all(_inside(s, first) for s in compiles.values())


@pytest.mark.parametrize("traced_run", sorted(BACKENDS), indirect=True)
def test_the_three_children_cover_the_shared_forward(traced_run):
    spans, fwd = traced_run["spans"], traced_run["forward"]
    forwards = [s for s in spans if s["name"] == fwd]
    assert forwards and not [s for s in spans if s["name"] in ("decode", "decode_verify")
                             and s["name"] != fwd]
    for f in forwards:
        kids = [s for s in spans if s["name"] in ("decode_dispatch", "decode_wait",
                                                  "logits_readback") and _inside(s, f)]
        assert [k["name"] for k in sorted(kids, key=lambda k: k["ts"])] == [
            "decode_dispatch", "decode_wait", "logits_readback"]
        # what the three leave uncovered is two span exits and two entries
        assert f["dur"] - sum(k["dur"] for k in kids) < max(200.0, 0.02 * f["dur"])
        assert f["args"]["active"] >= 1
    wait = [s for s in spans if s["name"] == "decode_wait"]
    assert all(s["args"].get("synced") for s in wait)
    # the speculative engine reads its window's logits back whole; the plain one
    # draws on the device and reads back two slots' ids (no request here taps)
    want = (2 * 3 * CFG.vocab_size * np.dtype(CFG.dtype).itemsize if fwd == "decode_verify"
            else 2 * np.dtype(np.int32).itemsize)
    assert {s["args"]["bytes"] for s in spans if s["name"] == "logits_readback"} == {want}


@pytest.mark.parametrize("traced_run", sorted(BACKENDS), indirect=True)
def test_one_requests_spans_and_instants_share_its_rid(traced_run):
    spans, reqs = traced_run["spans"], traced_run["reqs"]
    for r in reqs:
        assert len([s for s in spans if s["name"] == "queue_wait" and s["args"]["rid"] == r.rid]) == 1
        assert len([s for s in spans if s["name"] == "prefill" and s["args"]["rid"] == r.rid]) == 1
        instants = {e["name"] for e in traced_run["all"]
                    if e["ph"] == "i" and e["args"].get("rid") == r.rid}
        assert {"req_queued", "req_prefilling", "req_decoding"} <= instants
    for s in spans:
        if s["name"] == "sample_slot":
            assert {"slot", "rid", "greedy"} <= set(s["args"])
    by_rid = {r.rid: r for r in reqs}
    for s in (s for s in spans if s["name"] == "sample_slot"):
        assert s["args"]["greedy"] == (by_rid[s["args"]["rid"]].temperature <= 0)
    # a slot that draws opens one; the cancelled request's slot opened none when
    # it was found cancelled, so it drew as often as it has tokens
    cancelled = reqs[1]
    assert cancelled.state == "CANCELLED"
    drew = [s for s in spans if s["name"] == "sample_slot" and s["args"]["rid"] == cancelled.rid]
    if traced_run["forward"] == "decode":
        assert len(drew) == len(cancelled.generated) == len(cancelled.token_times)
    else:  # a verify step appends the accepted drafts without a draw of their own
        assert len(drew) <= len(cancelled.generated) == len(cancelled.token_times)


def test_the_queues_wait_lies_on_a_track_of_its_own(params, traced):
    """One slot, three requests: the third waits while the first two are
    served, on a track that is no thread's."""
    eng = _engine(params, num_slots=1)
    try:
        reqs = [eng.submit_request([1, 2, 3], 5) for _ in range(3)]
        _drive(eng, reqs)
    finally:
        eng.close()
    waits = {s["args"]["rid"]: s for s in _spans(traced, "queue_wait")}
    assert set(waits) == {r.rid for r in reqs}
    first, third = reqs[0], reqs[2]
    service_us = 1e6 * (first.finished_at - first.admitted_at)
    assert waits[third.rid]["dur"] >= service_us > 0
    assert waits[third.rid]["dur"] == pytest.approx(
        1e6 * (third.admitted_at - third.submitted_at), abs=1.0)
    loop_tid = {s["tid"] for s in _spans(traced, "iteration")}
    assert {s["tid"] for s in waits.values()} == {tracing._track_tid("serving queue")}
    assert not loop_tid & {tracing._track_tid("serving queue")}
    assert {s["tname"] for s in waits.values()} == {"serving queue"}
    assert [waits[r.rid]["args"]["depth"] for r in reqs] == [2, 1, 0]
    doc = tracing.chrome_trace(traced.snapshot())
    assert "serving queue" in {e["args"]["name"] for e in doc["traceEvents"] if e["ph"] == "M"}


def test_an_iteration_is_the_profilers_step_while_a_window_is_open(params, traced, monkeypatch):
    seen = []

    class Spy:
        def __init__(self, name, **kw):
            self.rec = (type(self).__name__, name, kw)

        def __enter__(self):
            seen.append(self.rec)

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", type("TraceAnnotation", (Spy,), {}))
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation",
                        type("StepTraceAnnotation", (Spy,), {}))
    eng = _engine(params)
    try:
        req = eng.submit_request([1, 2, 3], 3)
        eng.step_once()
        assert seen == []
        traced.profiling = True
        eng.step_once()
        traced.profiling = False
        _drive(eng, [req])
    finally:
        traced.profiling = False
        eng.close()
    assert seen[0] == ("StepTraceAnnotation", "serve", {"step_num": 1})
    # (the forward is sent first; the tokens booked beside it are the last draw's)
    assert [name for _, name, _ in seen[1:]] == [
        "decode", "decode_dispatch", "decode_wait", "logits_readback", "sample", "sample_slot"]


# --- the loop one step ahead of its bookkeeping (PR 64) ----------------------------------


def _three_requests(eng):
    return [eng.submit_request([1, 2, 3, 4, 5], 6), eng.submit_request([7, 8, 9], 4,
                                                                      temperature=0.8),
            eng.submit_request([9, 8, 7, 6], 3)]


@pytest.mark.parametrize("backend", ["slot-plain", "paged-plain"])
def test_an_iteration_sends_its_forward_and_then_books_the_last_draws(params, traced, backend):
    """The tree of an iteration on the device-draw path: ``admit`` (where a
    request waits), then ``decode`` (one a step SENT: dispatch, the wait for the
    PREVIOUS draws' ids, their way to the host), then ``sample`` (their
    bookkeeping, beside the step on the device).  An iteration whose rows all
    hold their last token sends nothing: ``sample`` alone."""
    eng = _engine(params, backend)
    try:
        reqs = _three_requests(eng)
        _drive(eng, reqs)
        stats = eng.stats()
    finally:
        eng.close()
    spans = _spans(traced)
    its = [s for s in spans if s["name"] == "iteration"]
    trees = [[s["name"] for s in sorted(spans, key=lambda s: s["ts"])
              if s["depth"] == 1 and _inside(s, it)] for it in its]
    assert all(t in (["admit", "decode", "sample"], ["decode", "sample"], ["sample"],
                     ["admit", "sample"]) for t in trees), trees
    assert trees[0] == ["admit", "decode", "sample"] and trees[-1] == ["sample"]
    # one ``decode`` a dispatched step, no more and no fewer: a request's last token is fed
    # to none, so the request of 6 tokens takes 5 steps; the third request joins its fifth
    # (the slot of the second, which ended at the fourth) and takes one more
    decodes = [s for s in spans if s["name"] == "decode"]
    assert len(decodes) == trees.count(["admit", "decode", "sample"]) + trees.count(
        ["decode", "sample"]) == 6 and stats["steps"] == len(its) == 7
    assert all(s["args"].get("synced") for s in spans if s["name"] == "decode_wait")
    # the counters ride the ``decode`` span (cumulative, as of its dispatch); the step behind
    # an idle engine's first admission is ahead of nothing
    assert [s["args"]["steps_ahead"] for s in decodes] == [0, 1, 2, 3, 4, 5]
    assert stats["steps_ahead"] == 5 and {s["args"]["row_steps_wasted"] for s in decodes} == {0}
    # every token was booked under a ``sample_slot``, the first of each with its prompt's iteration
    assert len([s for s in spans if s["name"] == "sample_slot"]) == 6 + 4 + 3
    # tracer on, a ``prefill`` closes on realized compute as before (``synced``); since PR 72
    # it is the completion worker that saw it, on the track ``device``, and ``admit`` holds
    # the admission's host side: one ``prefill_dispatch`` a request
    prefills = [s for s in spans if s["name"] == "prefill"]
    assert len(prefills) == 3 and all(s["args"].get("synced") for s in prefills)
    assert {s["tname"] for s in prefills} == {"device"}
    admits = [s for s in spans if s["name"] == "admit"]
    assert sum(s["args"]["admitted"] for s in admits) == 3 == len(
        [s for s in spans if s["name"] == "prefill_dispatch"
         and any(_inside(s, a) for a in admits)])


@pytest.mark.parametrize("backend", ["slot-plain", "paged-plain"])
def test_a_traced_and_an_untraced_engine_send_the_same_steps_in_the_same_order(params, backend):
    """The tracer times the loop the users run: with it on, the same forwards are
    sent ahead of the same bookkeeping (its ``sync`` blocks on what the host waits
    for anyway, never on the step just sent)."""
    def run():
        eng = _engine(params, backend, seed=11)
        try:
            reqs = _three_requests(eng)
            for r, rid in zip(reqs, (901, 902, 903)):
                r.rid = rid  # (the sampled request's stream is its rid's)
            order = []
            while not all(r.future.done() for r in reqs):
                eng.step_once()
                st = eng.stats()
                order.append((st["steps_ahead"], st["prefill_chunks"], st["tokens_generated"]))
            return [r.generated for r in reqs], order, eng.stats()["row_steps_wasted"]
        finally:
            eng.close()

    assert not tracer.enabled
    plain = run()
    tracer.enable(capacity=1 << 14)
    try:
        under_the_tracer = run()
        assert len(_spans(tracer, "decode")) == 6
    finally:
        tracer.disable()
        tracer.clear()
    assert plain == under_the_tracer and plain[1][-1][0] == 5 and plain[2] == 0


def _calls_and_waits(params, backend, monkeypatch):
    """An admission of two chunks and two decode iterations, every jitted call of the
    engine and every place a thread could wait for the device recorded by thread: a call
    as ``("jit", name)``, a wait as ``("wait", the engine's function it was made in)``
    (``jax.block_until_ready``; the engine's ``np.asarray`` of a device array; and
    ``ArrayImpl._value``, which ``float`` and ``tolist`` of one go through).  Waits that
    follow one another at one place are one."""
    from jax._src import array as jax_array

    from galvatron_tpu.serving import engine as engine_mod

    events = {}

    def note(kind, what):
        mine = events.setdefault(threading.current_thread().name, [])
        if kind == "jit" or not mine or mine[-1] != (kind, what):
            mine.append((kind, what))

    def place():
        frame = sys._getframe(1)
        while frame is not None:
            if frame.f_code.co_filename == engine_mod.__file__:
                # (a closure waits at its method's place: ``_step_ahead``'s ``read``)
                return frame.f_code.co_qualname.split(".<locals>")[0].split(".")[-1]
            frame = frame.f_back
        return None  # (a wait outside the engine: the worker's, the test's own)

    for name in ("_prefill_chunk", "_paged_prefill_chunk", "_decode_step", "_paged_decode_step",
                 "_sample_rows"):
        def jitted(*a, _f=getattr(engine_mod, name), _name=name, **k):
            note("jit", _name)
            return _f(*a, **k)
        monkeypatch.setattr(engine_mod, name, jitted)
    class Numpy:
        """The engine's ``np``: numpy, with ``asarray`` of a device array noted."""

        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, x, *a, **k):
            if isinstance(x, jax.Array):
                note("wait", place())
            return np.asarray(x, *a, **k)

    monkeypatch.setattr(engine_mod, "np", Numpy())
    block = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda x: (note("wait", place()), block(x))[1])
    value = jax_array.ArrayImpl._value
    monkeypatch.setattr(jax_array.ArrayImpl, "_value",
                        property(lambda self: (note("wait", place()), value.fget(self))[1]))
    eng = _engine(params, backend)
    try:
        req = eng.submit_request(list(range(1, 13)), 4)  # 12 tokens: two chunks of 8
        for _ in range(3):
            eng.step_once()
        generated = list(req.generated)
    finally:
        eng.close()
    return events, generated


@pytest.mark.parametrize("backend", ["slot-plain", "paged-plain"])
def test_the_traced_loop_thread_calls_and_waits_where_the_untraced_one_does(
        params, backend, monkeypatch):
    """THE RULE of ``obs/tracing.py``: tracer on, the loop thread makes the same jitted
    calls in the same order and can wait for the device at the same places between them as
    tracer off (its one ``Span.sync``, ``decode_wait``, sits where ``np.asarray(ids)``
    blocks anyway); what waits for a prompt's chunks is the completion worker."""
    me = threading.current_thread().name
    assert not tracer.enabled
    with monkeypatch.context() as patch:
        plain, plain_tokens = _calls_and_waits(params, backend, patch)
    tracer.enable(capacity=1 << 14)
    try:
        with monkeypatch.context() as patch:
            under, tokens = _calls_and_waits(params, backend, patch)
            spans = _spans(tracer)
    finally:
        tracer.disable()
        tracer.clear()
    prefill = "_paged_prefill_chunk" if "paged" in backend else "_prefill_chunk"
    decode = "_paged_decode_step" if "paged" in backend else "_decode_step"
    assert plain[me] == [
        ("jit", prefill), ("jit", prefill), ("jit", "_sample_rows"),  # the admission: no wait
        ("jit", decode), ("jit", "_sample_rows"), ("wait", "_step_ahead"),
        ("jit", decode), ("jit", "_sample_rows"), ("wait", "_step_ahead"),
        ("jit", decode), ("jit", "_sample_rows"), ("wait", "_step_ahead")]
    assert under[me] == plain[me] and tokens == plain_tokens and len(tokens) == 3
    assert set(plain) == {me}
    # the prompt's two ends were waited for, by the worker, outside the engine
    assert set(under) == {me, "tracer-completions"}
    assert under["tracer-completions"] == [("wait", None)]
    device, = [s for s in spans if s["name"] == "prefill"]
    assert device["args"]["chunks"] == 2 and device["args"]["depth_sum"] == 8


def test_an_idle_engine_opens_no_iteration(params, traced):
    eng = _engine(params)
    try:
        for _ in range(3):
            eng.step_once()
    finally:
        eng.close()
    # (a collection of a millisecond that falls in here is the runtime's span, not the engine's)
    assert [s for s in _spans(traced) if s["name"] != "gc"] == []


# --- the tap ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["slot-plain", "paged-plain"])
def test_tapped_rows_equal_the_benchmarks_own_bit_for_bit(params, backend):
    """Both taps on the SAME requests in one run: the program's
    ``capture_logits`` and the benchmark's ``serve.stamp`` (which swaps the
    request's list and copies ``Engine._last_logits[req.slot]``)."""
    from benchmark.lib import serve

    eng = _engine(params, backend)
    try:
        store = serve.RowStore(64, CFG.vocab_size)
        bufs = [np.full((n, CFG.vocab_size), np.nan, np.float32) for n in (7, 5, 4)]
        where = [b.ctypes.data for b in bufs]
        reqs = [eng.submit_request([1, 2, 3, 4, 5], 7, capture_logits=bufs[0]),
                eng.submit_request([7, 8, 9], 5, temperature=0.8, top_p=0.95,
                                   capture_logits=bufs[1]),
                eng.submit_request([9, 8, 7, 6], 4, temperature=1e-4, capture_logits=bufs[2])]
        stamped = [serve.stamp(eng, r, greedy=r.temperature < 1e-3, store=store) for r in reqs]
        _drive(eng, reqs)
    finally:
        eng.close()
    for r, buf, st, addr in zip(reqs, bufs, stamped, where):
        assert r.capture_logits is buf and buf.ctypes.data == addr  # the caller's, not a copy
        assert r.logits_rows == len(r.generated) == r.max_new_tokens
        assert all(k is not None for k in st.lines)
        theirs = np.stack([store.buf[k] for k in st.lines])
        assert np.array_equal(buf[:r.logits_rows].view(np.uint32), theirs.view(np.uint32))
        assert st.stamps == sorted(st.stamps)
        # the program's token times are read after the benchmark's stamp of the same token
        assert all(a <= b for a, b in zip(st.stamps, r.token_times))
        assert all(a <= b for a, b in zip(r.token_times, st.stamps[1:]))
        if r.temperature < 1e-3:
            assert list(buf[:r.logits_rows].argmax(-1)) == r.generated and st.not_best == 0


def test_a_tap_on_an_answer_cut_short_by_eos_counts_the_row_it_ended_on(params):
    eng = _engine(params)
    try:
        probe = eng.submit_request([1, 2, 3, 4, 5], 6)
        _drive(eng, [probe])
        # greedy: the same prompt draws the same tokens, and ends on the first
        # draw of ``eos``; take the token that first shows latest
        firsts = {}
        for i, tok in enumerate(probe.generated):
            firsts.setdefault(tok, i)
        eos, j = max(firsts.items(), key=lambda kv: kv[1])
        eng.eos_id = eos
        buf = np.zeros((6, CFG.vocab_size), np.float32)
        req = eng.submit_request([1, 2, 3, 4, 5], 6, capture_logits=buf)
        _drive(eng, [req])
    finally:
        eng.close()
    assert req.finish_reason == "eos" and req.generated == probe.generated[:j]
    assert req.logits_rows == j + 1 == len(req.token_times) + 1
    assert int(buf[j].argmax()) == eos and buf[:j + 1].any(-1).all() and not buf[j + 1:].any()


@pytest.mark.parametrize("bad,why", [
    (np.zeros((3, 128), np.float32), "too few rows"),
    (np.zeros((4, 127), np.float32), "another vocabulary"),
    (np.zeros((4, 128), np.float64), "not float32"),
    (np.zeros((4 * 128,), np.float32), "not two-dimensional"),
    ([[0.0] * 128] * 4, "not an array"),
    ("readonly", "not writable"),
])
def test_a_tap_the_loop_could_not_write_is_refused_at_submit(params, bad, why):
    if isinstance(bad, str):
        bad = np.zeros((4, 128), np.float32)
        bad.flags.writeable = False
    eng = _engine(params)
    try:
        with pytest.raises(ValueError, match="capture_logits must be a writable float32"):
            eng.submit_request([1, 2, 3], 4, capture_logits=bad)
        assert eng.scheduler.empty(), why
    finally:
        eng.close()


def test_a_tap_on_a_speculating_engine_is_refused_by_name(params):
    eng = _engine(params, "slot-verify")
    try:
        with pytest.raises(ValueError, match="capture_logits is not supported with spec_decode_k"):
            eng.submit_request([1, 2, 3], 4, capture_logits=np.zeros((4, 128), np.float32))
    finally:
        eng.close()


def test_the_engine_still_appends_to_the_list_it_was_handed(params):
    """The invariant the benchmark stands on until it switches to the tap: one
    ``append`` a token on ``req.generated``, with ``req.slot`` set and the row
    the token was drawn from in ``_last_logits[req.slot]``."""
    eng = _engine(params)
    seen = []

    class Spy(list):
        def append(self, tok):
            seen.append((req.slot, int(eng._last_logits[req.slot].argmax()), tok))
            super().append(tok)

    try:
        req = eng.submit_request([1, 2, 3, 4], 5)
        req.generated = Spy()
        _drive(eng, [req])
    finally:
        eng.close()
    assert len(seen) == 5 and all(slot is not None and best == tok for slot, best, tok in seen)
