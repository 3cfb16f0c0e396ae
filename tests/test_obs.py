"""Observability layer (galvatron_tpu/obs/): span tracing + Perfetto export,
MFU step accounting, Prometheus exposition, flight recorder, profiler windows.

The acceptance contract (ISSUE 6): an end-to-end traced training run exports
a Chrome trace whose spans nest correctly; train_iter JSONL carries
tokens_per_s/mfu validated against a hand-computed FLOPs estimate; tracing
OFF adds zero per-iteration host syncs; killing a traced run dumps a flight
recorder with the last N spans.
"""

import json
import math
import os
import re
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from galvatron_tpu.obs import flight, prom, stepstats, tracing
from galvatron_tpu.obs.tracing import Tracer, chrome_trace


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_spans_nest_and_export_chrome_trace(tmp_path):
    t = Tracer(capacity=64)
    t.enable()
    with t.span("step", step=0):
        with t.span("fwd_bwd", step=0) as sp:
            sp.sync(None)
        with t.span("sync", step=0):
            pass
    t.instant("anomaly_skip", step=0)
    path = str(tmp_path / "trace.json")
    t.export_chrome_trace(path)
    doc = json.load(open(path))
    evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert set(evs) == {"step", "fwd_bwd", "sync"}
    # containment on the same track = nesting in Perfetto
    step, fb = evs["step"], evs["fwd_bwd"]
    assert step["tid"] == fb["tid"]
    assert step["ts"] <= fb["ts"]
    assert fb["ts"] + fb["dur"] <= step["ts"] + step["dur"] + 1e-6
    assert fb["args"]["synced"] is True
    # depth recorded: fwd_bwd sat one level under step
    recs = {r["name"]: r for r in t.snapshot() if r.get("ph") == "X"}
    assert recs["step"]["depth"] == 0 and recs["fwd_bwd"]["depth"] == 1
    inst = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert inst and inst[0]["name"] == "anomaly_skip"
    # thread_name metadata present for the recording thread
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in doc["traceEvents"])


def test_disabled_tracer_is_nullop(monkeypatch):
    """Disabled tracing: the SAME singleton comes back for every span (no
    allocation), sync() never touches jax, nothing is recorded."""
    t = Tracer()
    assert t.span("a") is t.span("b")
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda *_: pytest.fail("sync while disabled"))
    with t.span("a") as sp:
        sp.sync(object())
    t.instant("x")
    assert t.snapshot() == []


def test_ring_is_bounded():
    t = Tracer(capacity=16)
    t.enable()
    for i in range(100):
        with t.span("s", i=i):
            pass
    spans = t.snapshot()
    assert len(spans) == 16
    assert spans[-1]["args"]["i"] == 99  # newest survive


def test_the_hot_paths_spans_cannot_push_a_compile_out_of_the_ring():
    """The ``jax.monitoring`` spans live in a ring of their own (`record_span(rare=True)`):
    a 32-slot engine records 37 spans an iteration, 130,000 a minute, and a run's compiles
    are read at its END (`compile_or_load_s`, `serve_compiles_in_window`: PERF.md section 6,
    PR 62, where an engine half as fast again lost them from a ring of 131,072)."""
    t = Tracer(capacity=16)
    t.enable()
    t.record_span("jax_compile", 0.5, rare=True, fun_name="decode_step")
    for i in range(100):
        with t.span("sample_slot", i=i):
            pass
    spans = t.snapshot()
    assert [s["name"] for s in spans].count("jax_compile") == 1 and len(spans) == 17
    # in the order they ended: the compile (reported first) in front, the newest span last
    assert spans[0]["args"] == {"fun_name": "decode_step"} and spans[-1]["args"]["i"] == 99
    t.clear()
    assert t.snapshot() == []


def test_thread_aware_tracks():
    t = Tracer()
    t.enable()

    def worker():
        with t.span("worker_span"):
            pass

    th = threading.Thread(target=worker, name="worker-thread")
    with t.span("main_span"):
        th.start()
        th.join()
    by_name = {r["name"]: r for r in t.snapshot()}
    assert by_name["worker_span"]["tid"] != by_name["main_span"]["tid"]
    assert by_name["worker_span"]["tname"] == "worker-thread"
    # concurrent threads have independent nesting stacks
    assert by_name["worker_span"]["depth"] == 0


# ---------------------------------------------------------------------------
# completion spans (`Tracer.complete_span`), on fake device values
# ---------------------------------------------------------------------------


class _Value:
    """What the completion worker asks of a device value: ``is_ready`` and
    ``block_until_ready``; ``land()`` is the device getting through with it."""

    def __init__(self, ready=False, deleted=False):
        self._event, self.deleted, self.blocked_on = threading.Event(), deleted, []
        if ready:
            self._event.set()

    def land(self):
        self._event.set()
        return time.perf_counter()

    def is_ready(self):
        return self._event.is_set()

    def block_until_ready(self):
        if self.deleted:
            raise RuntimeError("Array has been deleted.")
        self.blocked_on.append(threading.current_thread().name)
        assert self._event.wait(10.0)
        return self


class _Scalar:
    """A device scalar: says which thread turned it into a float."""

    read_on = []

    def __init__(self, x):
        self.x = x

    def __float__(self):
        _Scalar.read_on.append(threading.current_thread().name)
        return float(self.x)


@pytest.fixture()
def completions():
    """A tracer of its own, on; whatever the test left waiting is let through at
    the end so that the daemon worker idles."""
    t, values = Tracer(capacity=64), []
    t.enable()

    def value(**kw):
        values.append(_Value(**kw))
        return values[-1]

    yield t, value
    for v in values:
        v.land()
    t.snapshot(wait_s=5.0)


def _us(t, pc):
    return t.pc_to_us(pc)


def test_completion_spans_open_on_after_and_close_on_done_in_the_order_handed_in(completions):
    t, value = completions
    a0, a1, b1 = value(), value(), value()
    _Scalar.read_on.clear()
    caller = threading.current_thread().name
    t0 = time.perf_counter()
    t.complete_span("prefill", after=a0, done=a1, rid=1,
                    scalars={"last": {"x": _Scalar(3)}, "full": [{"x": _Scalar(5)}]},
                    finish=lambda v: {"x": v["last"]["x"], "mean": v["full"][0]["x"] / 2})
    t.complete_span("prefill", after=a1, done=b1, rid=2, scalars={"y": _Scalar(7)})
    assert time.perf_counter() - t0 < 0.5 and t._worker.daemon
    time.sleep(0.02)
    opened = a0.land()
    time.sleep(0.03)
    closed = a1.land()
    time.sleep(0.02)
    closed_b = b1.land()
    first, second = t.snapshot(wait_s=5.0)
    assert [r["args"]["rid"] for r in (first, second)] == [1, 2]
    for r in (first, second):
        assert (r["name"], r["ph"], r["tname"], r["depth"]) == ("prefill", "X", "device", 0)
        assert r["tid"] == tracing._track_tid("device") and r["args"]["synced"] is True
    # the stamps are the worker's, taken as each value landed (a few ms of wake-up)
    assert _us(t, opened) <= first["ts"] <= _us(t, opened) + 20e3
    assert _us(t, closed) <= first["ts"] + first["dur"] <= _us(t, closed) + 20e3
    assert first["dur"] >= 30e3 - 1
    # a span that opens on what the one before it closed on opens on that very stamp
    assert second["ts"] == pytest.approx(first["ts"] + first["dur"], abs=1e-3)
    assert _us(t, closed_b) <= second["ts"] + second["dur"] <= _us(t, closed_b) + 20e3
    assert "opened_late" not in first["args"] and "opened_late" not in second["args"]
    # the arguments were finished on the worker: floats, through ``finish`` where given
    assert first["args"] == {"rid": 1, "x": 3.0, "mean": 2.5, "synced": True}
    assert second["args"] == {"rid": 2, "y": 7.0, "synced": True}
    assert _Scalar.read_on == ["tracer-completions"] * 3
    assert caller not in a0.blocked_on + a1.blocked_on + b1.blocked_on
    assert a0.blocked_on == ["tracer-completions"]


def test_a_completion_span_without_after_opens_when_it_is_handed_in(completions):
    t, value = completions
    done = value()
    before = time.perf_counter()
    t.complete_span("write", done=done, track="checkpoint")
    after = time.perf_counter()
    time.sleep(0.02)
    done.land()
    rec, = t.snapshot(wait_s=5.0)
    assert _us(t, before) <= rec["ts"] <= _us(t, after) and rec["dur"] >= 20e3 - 1
    assert rec["tname"] == "checkpoint" and rec["args"] == {"synced": True}


def test_a_handle_lets_the_worker_wait_on_after_while_the_caller_still_dispatches(completions):
    """The serving engine's use: the span is handed in when the admission begins and closed
    when its last dispatch is out; ``after`` lands in between and is stamped as it lands."""
    t, value = completions
    after, done = value(), value()
    handle = t.complete_span("prefill", after=after, rid=3, tokens=2000)
    for _ in range(200):  # the worker is on it before the caller says anything more
        if after.blocked_on:
            break
        time.sleep(0.005)
    assert after.blocked_on == ["tracer-completions"]
    opened = after.land()
    time.sleep(0.03)  # (the caller is still dispatching: the worker waits for the close)
    assert [r["args"] for r in t.snapshot(wait_s=0.01)] == [
        {"rid": 3, "tokens": 2000, "pending": True}]
    handle.close(done=done, scalars={"x": _Scalar(2)}, chunks=2, depth_sum=1024)
    handle.close(done=value(), chunks=99)  # (the first close counts)
    time.sleep(0.02)
    closed = done.land()
    rec, = t.snapshot(wait_s=5.0)
    assert rec["args"] == {"rid": 3, "tokens": 2000, "chunks": 2, "depth_sum": 1024, "x": 2.0,
                           "synced": True}
    assert _us(t, opened) <= rec["ts"] <= _us(t, opened) + 20e3
    assert _us(t, closed) <= rec["ts"] + rec["dur"] <= _us(t, closed) + 20e3


def test_a_handle_closed_without_done_closes_at_the_call_and_one_never_closed_is_given_up(
        completions, monkeypatch):
    t, value = completions
    # the speculative engine's use: no ``after`` (the hand-in opens it), no ``done`` (the
    # caller saw the work through itself); the scalars still become floats on the worker
    _Scalar.read_on.clear()
    before = time.perf_counter()
    handle = t.complete_span("prefill", rid=5)
    time.sleep(0.02)
    handle.close(scalars={"y": _Scalar(4)}, chunks=1)
    at = time.perf_counter()
    rec, = t.snapshot(wait_s=5.0)
    assert rec["args"] == {"rid": 5, "chunks": 1, "y": 4.0, "synced": True}
    assert _us(t, before) <= rec["ts"] and rec["ts"] + rec["dur"] <= _us(t, at)
    assert rec["dur"] >= 20e3 - 1 and _Scalar.read_on == ["tracer-completions"]
    # an admission that raised closes its span with the error's name
    t.clear()
    t.complete_span("prefill", rid=6).close(error="DeadlineExceeded")
    rec, = t.snapshot(wait_s=5.0)
    assert rec["args"] == {"rid": 6, "error": "DeadlineExceeded", "synced": True}
    # a handle that was lost is given up, and the spans behind it go on
    t.clear()
    monkeypatch.setattr(tracing, "CLOSE_WAIT_S", 0.05)
    t.complete_span("prefill", rid=7)
    t.complete_span("prefill", done=value(ready=True), rid=8)
    lost, behind = t.snapshot(wait_s=5.0)
    assert lost["args"] == {"rid": 7, "error": "TimeoutError"}
    assert behind["args"] == {"rid": 8, "synced": True}


def test_an_after_that_was_through_before_the_worker_looked_says_so(completions):
    t, value = completions
    t.complete_span("prefill", after=value(ready=True), done=value(ready=True))
    rec, = t.snapshot(wait_s=5.0)
    assert rec["args"] == {"opened_late": True, "synced": True}


def test_a_tracer_that_is_off_touches_nothing_for_a_completion_span(monkeypatch):
    t = Tracer()
    monkeypatch.setattr(tracing, "time", None)  # any clock read through the tracer raises
    monkeypatch.setattr(tracing.threading, "Thread",
                        lambda *a, **k: pytest.fail("a thread while off"))
    v = _Value()
    # whole or as a handle, the same no-op singleton every span is while tracing is off
    assert t.complete_span("prefill", after=v, done=v, scalars={"x": _Scalar(1)}) is t.span("a")
    handle = t.complete_span("prefill", after=v, rid=1)
    assert handle is t.span("a") and handle.close(done=v, scalars={"x": _Scalar(1)}) is None
    assert t._worker is None and t._pending == {} and t._enqueued == 0
    assert v.blocked_on == [] and t.snapshot() == []


def test_no_completion_worker_exists_until_a_span_is_handed_to_an_enabled_tracer(completions):
    t, value = completions
    assert t.enabled and t._worker is None
    with t.span("iteration", step=0):
        t.record_span("queue_wait", 0.1, track="serving queue")
    assert t._worker is None and len(t.snapshot()) == 2
    t.complete_span("prefill", done=value(ready=True))
    assert t._worker is not None and t._worker.name == "tracer-completions"
    assert [r["name"] for r in t.snapshot(wait_s=5.0)] == ["queue_wait", "iteration", "prefill"]


def test_a_full_completion_queue_drops_and_counts_and_never_blocks(completions, monkeypatch):
    t, value = completions
    monkeypatch.setattr(tracing, "COMPLETIONS_MAX", 2)
    stuck = [value() for _ in range(4)]
    t0 = time.perf_counter()
    for i, v in enumerate(stuck):
        t.complete_span("prefill", done=v, rid=i)
    assert time.perf_counter() - t0 < 0.5  # the device never answered; the caller went on
    assert t.completions_dropped == 2
    snap = t.snapshot(wait_s=0.05)
    assert [(r["name"], r["args"]) for r in snap] == [
        ("prefill", {"rid": 0, "pending": True}), ("prefill", {"rid": 1, "pending": True}),
        ("completions_dropped", {"count": 2})]
    for v in stuck:
        v.land()
    snap = t.snapshot(wait_s=5.0)
    assert [r["args"].get("rid") for r in snap] == [0, 1, None] and snap[-1]["ph"] == "i"
    t.clear()
    assert t.completions_dropped == 0 and t.snapshot() == []


def test_a_deleted_array_gives_the_record_an_error_and_raises_on_no_thread(completions,
                                                                            monkeypatch):
    t, value = completions
    raised = []
    monkeypatch.setattr(threading, "excepthook", lambda args: raised.append(args))
    t.complete_span("prefill", after=value(ready=True), done=value(deleted=True), rid=1,
                    scalars={"x": _Scalar(1)})
    t.complete_span("prefill", done=value(ready=True), rid=2)
    bad, good = t.snapshot(wait_s=5.0)
    assert bad["args"]["error"] == "RuntimeError" and "synced" not in bad["args"]
    assert bad["args"]["rid"] == 1 and "x" not in bad["args"] and bad["dur"] >= 0
    assert good["args"] == {"rid": 2, "synced": True}
    assert raised == [] and t._worker.is_alive()


def test_snapshot_waits_bounded_and_marks_what_is_still_pending(completions):
    t, value = completions
    dead = value()
    t.complete_span("prefill", done=dead, rid=9)
    t0 = time.perf_counter()
    snap = t.snapshot(wait_s=0.1)
    assert 0.1 <= time.perf_counter() - t0 < 2.0
    assert [(r["args"], r["dur"], r["tname"]) for r in snap] == [
        ({"rid": 9, "pending": True}, 0.0, "device")]
    # (the wait a reader that says nothing gets: a crash dump returns within it)
    assert tracing.SNAPSHOT_WAIT_S <= 5.0
    dead.land()
    rec, = t.snapshot(wait_s=5.0)
    assert rec["args"] == {"rid": 9, "synced": True} and rec["dur"] >= 100e3 - 1


def test_a_cleared_ring_stays_clear_of_spans_that_were_in_flight(completions):
    t, value = completions
    late = value()
    t.complete_span("prefill", done=late, rid=1)
    t.clear()
    late.land()
    t.complete_span("prefill", done=value(ready=True), rid=2)
    assert [r["args"]["rid"] for r in t.snapshot(wait_s=5.0)] == [2]


def test_a_flight_dump_holds_a_prompts_span_without_knowing_of_the_worker(completions, tmp_path):
    t, value = completions
    done = value()
    t.complete_span("prefill", done=done, rid=4, tokens=300)
    threading.Timer(0.05, done.land).start()
    doc = flight.read_flight(flight.dump_flight(str(tmp_path), t, reason="test"))
    assert [(r["name"], r["args"]["rid"], r["args"].get("synced")) for r in doc["spans"]] == [
        ("prefill", 4, True)]
    trace = chrome_trace(doc["spans"])
    named = {e["args"]["name"] for e in trace["traceEvents"] if e["name"] == "thread_name"}
    assert named == {"device"}


# ---------------------------------------------------------------------------
# step accounting (FLOPs / MFU)
# ---------------------------------------------------------------------------


def _tiny_cfg():
    from galvatron_tpu.models.modeling import ModelConfig

    return ModelConfig(vocab_size=256, hidden_size=64, num_layers=2,
                       num_heads=4, ffn_dim=128, max_seq_len=32)


def test_step_flops_hand_computed(monkeypatch):
    """The analytic estimate against an independent hand computation for a
    pinned tiny shape (h=64, 4 heads, ffn=128, swiglu, V=256, L=2, s=32)."""
    monkeypatch.delenv("GALVATRON_PEAK_TFLOPS", raising=False)
    cfg = _tiny_cfg()
    bsz, seq = 8, 32
    # per token per layer: qkv = 2*64*(64 + 2*64) = 24576 ; out = 2*64*64 = 8192
    # attn core = 2 * 2 * 64 per key, a causal token sees (32 + 1) / 2 keys on
    # average: 4224 ; mlp (swiglu, 3 GEMMs) = 2*3*64*128
    attn_proj = 24576 + 8192
    attn_core = 4224
    mlp = 49152
    per_layer = attn_proj + attn_core + mlp
    head = 2 * 64 * 256  # per loss token
    fwd = bsz * seq * (2 * per_layer + head)
    st = stepstats.StepStats(cfg, bsz, seq, peak_tflops_override=0.001)
    assert st.model_flops_per_step == 3.0 * fwd
    # remat-aware hardware FLOPs: default mlp_recompute='policy' replays the
    # MLP branch once per layer in backward
    assert st.hardware_flops_per_step == 3.0 * fwd + bsz * seq * 2 * mlp
    out = st.per_iter(10.0)  # 10 ms
    assert out["tokens_per_s"] == pytest.approx(bsz * seq / 0.010)
    ndev = jax.device_count()
    assert out["mfu"] == pytest.approx(
        (3.0 * fwd / 0.010) / (0.001e12 * ndev), rel=1e-4)
    assert out["hfu"] > out["mfu"]
    # batch rescaling (rampup): half the batch, same time → half the MFU
    half = st.per_iter(10.0, bsz // 2)
    assert half["mfu"] == pytest.approx(out["mfu"] / 2, rel=1e-4)


def test_full_ckpt_layers_raise_hfu_only():
    from galvatron_tpu.core.strategy import HybridParallelConfig

    cfg = _tiny_cfg()
    hp = HybridParallelConfig.uniform(2, ckpt=1)
    st_plain = stepstats.StepStats(cfg.replace(mlp_recompute="off"), 4, 32)
    st_ckpt = stepstats.StepStats(cfg.replace(mlp_recompute="off"), 4, 32, hp=hp)
    assert st_ckpt.model_flops_per_step == st_plain.model_flops_per_step
    # full remat replays the whole layer forward
    assert st_ckpt.hardware_flops_per_step == pytest.approx(
        st_plain.hardware_flops_per_step
        + 4 * 32 * 2 * stepstats.layer_fwd_flops_per_token(cfg, 32))


def test_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv("GALVATRON_PEAK_TFLOPS", "123.5")
    assert stepstats.peak_flops_per_device() == 123.5e12
    # explicit override wins over env
    assert stepstats.peak_flops_per_device(2.0) == 2.0e12
    # a malformed override is an error, not a silent fall-through to the table
    monkeypatch.setenv("GALVATRON_PEAK_TFLOPS", "197 TFLOPs")
    with pytest.raises(ValueError, match="GALVATRON_PEAK_TFLOPS"):
        stepstats.peak_flops_per_device()
    monkeypatch.delenv("GALVATRON_PEAK_TFLOPS")
    # CPU device kind is unknown → None, never a made-up denominator
    assert stepstats.peak_flops_per_device() is None


def test_apply_xla_overlap_flag_sets(monkeypatch):
    """--xla_overlap: unknown modes are hard errors; 'off' is a no-op; the
    TPU-only flag sets never reach XLA_FLAGS on non-TPU backends (the CPU
    client crashes the process on unknown --xla_tpu_* flags)."""
    from galvatron_tpu.parallel.mesh import (
        XLA_OVERLAP_FLAG_SETS, apply_xla_overlap,
    )

    with pytest.raises(ValueError):
        apply_xla_overlap("fastest")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    assert apply_xla_overlap("off") == []
    # this suite runs on CPU: auto/aggressive must not touch XLA_FLAGS
    assert apply_xla_overlap("aggressive") == []
    assert os.environ["XLA_FLAGS"] == "--xla_force_host_platform_device_count=8"
    # the curated sets are ordered supersets: aggressive ⊃ auto ⊃ off
    assert set(XLA_OVERLAP_FLAG_SETS["auto"]) < set(
        XLA_OVERLAP_FLAG_SETS["aggressive"])
    assert XLA_OVERLAP_FLAG_SETS["off"] == ()
    # on a TPU-pinned backend the flags append once (idempotent)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    got = apply_xla_overlap("auto")
    assert list(XLA_OVERLAP_FLAG_SETS["auto"]) == got
    assert all(f in os.environ["XLA_FLAGS"] for f in got)
    before = os.environ["XLA_FLAGS"]
    apply_xla_overlap("auto")
    assert os.environ["XLA_FLAGS"] == before


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------

_LABEL_VAL = r"\"(?:[^\"\\]|\\.)*\""  # escaped \" \\ \n allowed inside
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*=" + _LABEL_VAL
    + r"(,[a-zA-Z_][a-zA-Z0-9_]*=" + _LABEL_VAL + r")*\})? "
    r"(-?[0-9.e+-]+|NaN|\+Inf|-Inf)$"
)


def assert_valid_exposition(text: str):
    """Every non-comment line must be a well-formed sample; TYPE declared at
    most once per family."""
    assert text.endswith("\n")
    types_seen = set()
    for line in text.strip().split("\n"):
        if line.startswith("# TYPE "):
            fam = line.split()[2]
            assert fam not in types_seen, f"duplicate TYPE for {fam}"
            types_seen.add(fam)
            continue
        if line.startswith("#"):
            continue
        assert _SAMPLE_RE.match(line), f"malformed sample line: {line!r}"


def test_prom_text_renders_and_validates():
    out = prom.PromText()
    out.add("requests_total", 5, labels={"outcome": "ok"}, mtype="counter",
            help_="requests")
    out.add("requests_total", 2, labels={"outcome": "failed"})
    out.add("occupancy", 0.5)
    out.add("none_skipped", None)   # None values are skipped, not rendered
    out.add("flag", True)
    out.add("nan_val", float("nan"))
    out.add("escaped", 1, labels={"p": 'a"b\\c\nd'})
    text = out.render()
    assert_valid_exposition(text)
    assert 'galvatron_requests_total{outcome="ok"} 5' in text
    assert "none_skipped" not in text
    assert "galvatron_flag 1" in text
    with pytest.raises(ValueError):
        out.add("bad name!", 1)
    with pytest.raises(ValueError):
        out.add("x", 1, labels={"bad-label": 1})


def test_train_stats_render_and_obs_server():
    ts = prom.TrainStats()
    ts.iterations = 3
    ts.last_loss = 2.5
    ts.mfu = 0.41
    srv = prom.ObsServer(ts.render, port=0)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=30
        ) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        assert_valid_exposition(text)
        assert "galvatron_train_iterations_total 3" in text
        assert "galvatron_train_mfu 0.41" in text
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/healthz", timeout=30
        ) as r:
            assert json.load(r)["status"] == "ok"
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# flight recorder + profiler windows
# ---------------------------------------------------------------------------


def test_flight_dump_roundtrip_and_trace_export(tmp_path):
    t = Tracer(capacity=32)
    t.enable()
    for i in range(5):
        with t.span("step", step=i):
            pass
    p = flight.dump_flight(str(tmp_path), t, reason="TestCrash: boom",
                           extra={"iter": 5})
    doc = flight.read_flight(p)
    assert doc["reason"].startswith("TestCrash")
    assert len(doc["spans"]) == 5 and doc["extra"]["iter"] == 5
    # cli trace-export converts the dump to a loadable Chrome trace
    from galvatron_tpu.cli import main as cli_main

    out = str(tmp_path / "out.trace.json")
    assert cli_main(["trace-export", p, "--output", out]) == 0
    trace = json.load(open(out))
    assert sum(e["name"] == "step" for e in trace["traceEvents"]) == 5
    # non-dump inputs are rejected loudly
    bad = str(tmp_path / "bad.json")
    json.dump({"x": 1}, open(bad, "w"))
    assert cli_main(["trace-export", bad]) == 2


def test_parse_profile_steps():
    assert flight.parse_profile_steps("3:6") == (3, 6)
    for bad in ("6:3", "3", "a:b", "3:3"):
        with pytest.raises(ValueError):
            flight.parse_profile_steps(bad)


def test_profiler_window_degrades_without_xprof(monkeypatch, capsys):
    """A backend whose start_trace raises disables the window with a warning;
    training continues (graceful degradation, never a crash source)."""
    def boom(*a, **k):
        raise RuntimeError("no xprof here")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    pw = flight.ProfilerWindow("/tmp/nowhere", 1, 3)
    pw.maybe_start(1)
    assert pw.failed and not pw.active
    pw.maybe_stop(2)  # no-op, no crash
    pw.close()
    assert "lacks profiler support" in capsys.readouterr().out


def test_profiler_window_resumed_run_still_captures(monkeypatch, tmp_path):
    """A resumed run whose batch offset already passed START must capture
    from where it is (>= start), not silently skip the window; one past STOP
    marks done without starting; a closed window never restarts."""
    started, stopped = [], []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: started.append(d))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: stopped.append(1))
    pw = flight.ProfilerWindow(str(tmp_path), 50, 54)
    pw.maybe_start(52)  # resumed at iter 52, inside [50, 54)
    assert pw.active and len(started) == 1
    pw.maybe_stop(52)   # 53 < 54: still open
    assert pw.active
    pw.maybe_stop(53, verbose=False)  # 54 >= 54: closes
    assert not pw.active and pw.done and len(stopped) == 1
    pw.maybe_start(55)  # done: never restarts
    assert not pw.active and len(started) == 1
    # resumed entirely past the window: done immediately, no capture
    pw2 = flight.ProfilerWindow(str(tmp_path), 10, 12)
    pw2.maybe_start(30)
    assert pw2.done and not pw2.active and len(started) == 1


# ---------------------------------------------------------------------------
# trainer end-to-end
# ---------------------------------------------------------------------------

TINY_TRAIN = [
    "--model_size", "llama-0.3b", "--num_layers", "2", "--hidden_size", "64",
    "--num_heads", "4", "--vocab_size", "256", "--seq_length", "32",
    "--global_train_batch_size", "8", "--mixed_precision", "fp32",
]


def _train(args, **kw):
    from galvatron_tpu.core.arguments import initialize_galvatron
    from galvatron_tpu.core.trainer import train

    return train(initialize_galvatron("train", TINY_TRAIN + args), **kw)


def test_traced_training_exports_nested_spans_and_mfu(tmp_path, monkeypatch):
    """The acceptance e2e: ≥4 traced iterations; exported Chrome trace has
    step ⊃ fwd_bwd nesting per iteration; train_iter JSONL carries
    tokens_per_s and mfu consistent with the hand-computable FLOPs model."""
    monkeypatch.setenv("GALVATRON_PEAK_TFLOPS", "0.001")
    trace = str(tmp_path / "spans.trace.json")
    mpath = str(tmp_path / "m.jsonl")
    _train(["--train_iters", "4", "--trace_spans", trace,
            "--metrics_path", mpath, "--save", str(tmp_path / "ckpt"),
            "--save_interval", "2"], verbose=False)

    doc = json.load(open(trace))
    by_name = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "X":
            by_name.setdefault(e["name"], []).append(e)
    for name in ("step", "data", "fwd_bwd", "sync"):
        assert len(by_name[name]) == 4, f"missing per-iter {name} spans"
    # checkpoint saves land on the same timeline (steps 2 and 4)
    assert len(by_name["ckpt_save"]) == 2
    # the interval save ran inside its step span (crash-path exit saves do not)
    in_step = [c for c in by_name["ckpt_save"]
               if any(s["ts"] <= c["ts"] and
                      c["ts"] + c["dur"] <= s["ts"] + s["dur"] + 1e-6
                      for s in by_name["step"])]
    assert in_step, "no interval ckpt_save nested under a step span"
    # nesting: each fwd_bwd/data/sync sits inside its step span (same track)
    for child_name in ("data", "fwd_bwd", "sync"):
        for child in by_name[child_name]:
            step = next(s for s in by_name["step"]
                        if s["args"]["step"] == child["args"]["step"])
            assert step["tid"] == child["tid"]
            assert step["ts"] <= child["ts"] + 1e-6
            assert child["ts"] + child["dur"] <= step["ts"] + step["dur"] + 1e-6
    assert all(e["args"]["synced"] for e in by_name["sync"])

    # JSONL: tokens_per_s + mfu/hfu validated against the FLOPs estimate
    from galvatron_tpu.utils.metrics import read_metrics

    recs = [r for r in read_metrics(mpath) if r["event"] == "train_iter"]
    assert len(recs) == 4
    cfg_ffn_default = None  # (shape pinned via flags above)
    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args

    cfg = model_config_from_args(initialize_galvatron("train", TINY_TRAIN))
    st = stepstats.StepStats(cfg, 8, 32)
    for r in recs[1:]:  # iter 0 is profiler warmup (no iter_ms yet)
        assert r["iter_ms"] > 0
        expect = st.per_iter(r["iter_ms"])
        assert r["tokens_per_s"] == pytest.approx(expect["tokens_per_s"], rel=1e-6)
        assert r["mfu"] == pytest.approx(expect["mfu"], rel=1e-3)
        assert r["hfu"] >= r["mfu"]
    # the tracer is returned to its disabled default after the run
    assert not tracing.tracer.enabled and tracing.tracer.snapshot() == []


def test_traced_pp_training_records_measured_spans_only(tmp_path):
    """Under a pipeline schedule the timeline carries what the host measured
    (``step`` and its children) and nothing painted on: no per-stage track, no
    span marked synthetic (a masked tick costs what a steady one does, so the
    schedule's lockstep model said nothing about where the time went)."""
    trace = str(tmp_path / "pp.trace.json")
    _train(["--train_iters", "3", "--pp_deg", "2", "--chunks", "2",
            "--pipeline_type", "pipedream_flush", "--trace_spans", trace],
           verbose=False)
    doc = json.load(open(trace))
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {"step", "fwd_bwd", "sync"} <= {e["name"] for e in spans}
    assert not [e for e in spans if e["name"].startswith("stage")
                or e["args"].get("synthetic")]
    assert not [e for e in doc["traceEvents"]
                if e["ph"] == "M" and str(e["args"].get("name", "")).startswith("pp stage")]


def test_tracing_off_adds_zero_host_syncs(tmp_path, monkeypatch):
    """The dispatch-count pin: without --trace_spans (and with no other
    per-iter observable armed) the trainer makes ZERO jax.block_until_ready
    calls and records ZERO spans — observability must cost nothing when off."""
    calls = {"n": 0}
    orig = jax.block_until_ready

    def counting(x):
        calls["n"] += 1
        return orig(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    _train(["--train_iters", "3"], verbose=False)
    assert calls["n"] == 0, "tracing-off run performed host syncs"
    assert tracing.tracer.snapshot() == []
    # and ON: the sync span blocks once per iteration
    _train(["--train_iters", "3",
            "--trace_spans", str(tmp_path / "t.json")], verbose=False)
    assert calls["n"] >= 3


def test_crashed_traced_run_dumps_flight_recorder(tmp_path, monkeypatch):
    """Fault-injected divergence (the PR 1 harness) under tracing: the
    AnomalyAbort crash path dumps flight_<ts>.json carrying the last spans
    including the anomaly_skip instants."""
    from galvatron_tpu.core import faults
    from galvatron_tpu.core.resilience import AnomalyAbort

    monkeypatch.setenv("GALVATRON_FAULTS", "nan_at_step=1,nan_count=5")
    fdir = str(tmp_path / "flight")
    trace = str(tmp_path / "spans.json")
    try:
        with pytest.raises(AnomalyAbort):
            _train(["--train_iters", "6", "--anomaly_max_skips", "1",
                    "--trace_spans", trace, "--flight_dir", fdir],
                   verbose=False)
    finally:
        faults.reset()
    dumps = [f for f in os.listdir(fdir) if f.startswith("flight_")]
    assert len(dumps) == 1
    doc = flight.read_flight(os.path.join(fdir, dumps[0]))
    assert "AnomalyAbort" in doc["reason"]
    names = [s["name"] for s in doc["spans"]]
    assert "step" in names and "anomaly_skip" in names
    # the dump converts to a Perfetto-loadable trace via the CLI
    from galvatron_tpu.cli import main as cli_main

    assert cli_main(["trace-export", os.path.join(fdir, dumps[0])]) == 0
    # the span export also landed (crash path exports too)
    assert os.path.exists(trace)


def test_setup_crash_still_dumps_flight_recorder(tmp_path):
    """A crash BEFORE the training loop (here: a --load dir whose steps
    carry no manifests) must still honor --flight_dir/--trace_spans — the
    setup forensics are dumped before the wrapper drops the ring."""
    load = tmp_path / "legacy_ckpt"
    (load / "step_3").mkdir(parents=True)  # pre-manifest legacy step
    fdir = str(tmp_path / "flight")
    with pytest.raises(FileNotFoundError):
        _train(["--train_iters", "2", "--load", str(load),
                "--flight_dir", fdir,
                "--trace_spans", str(tmp_path / "s.json")], verbose=False)
    dumps = [f for f in os.listdir(fdir) if f.startswith("flight_")]
    assert len(dumps) == 1
    assert "FileNotFoundError" in flight.read_flight(
        os.path.join(fdir, dumps[0]))["reason"]
    assert os.path.exists(tmp_path / "s.json")  # span export landed too
    assert not tracing.tracer.enabled  # and nothing leaked


def test_flight_dir_alone_arms_the_recorder(tmp_path, monkeypatch):
    """--flight_dir WITHOUT --trace_spans must still dump on a crash: the
    flag arms span tracing itself (a recorder with no ring would be a silent
    no-op exactly when forensics were requested)."""
    from galvatron_tpu.core import faults
    from galvatron_tpu.core.resilience import AnomalyAbort

    monkeypatch.setenv("GALVATRON_FAULTS", "nan_at_step=1,nan_count=5")
    fdir = str(tmp_path / "flight")
    try:
        with pytest.raises(AnomalyAbort):
            _train(["--train_iters", "6", "--anomaly_max_skips", "1",
                    "--flight_dir", fdir], verbose=False)
    finally:
        faults.reset()
    dumps = [f for f in os.listdir(fdir) if f.startswith("flight_")]
    assert len(dumps) == 1
    doc = flight.read_flight(os.path.join(fdir, dumps[0]))
    assert any(s["name"] == "step" for s in doc["spans"])
    # and the run returned the tracer to its disabled default
    assert not tracing.tracer.enabled and tracing.tracer.snapshot() == []


def test_profile_steps_window(tmp_path):
    """--profile_steps A:B captures a bounded jax.profiler window on backends
    that support it (CPU does) without touching the run's results."""
    tdir = str(tmp_path / "prof")
    out = _train(["--train_iters", "4", "--profile_steps", "1:3",
                  "--trace_dir", tdir], verbose=False)
    assert out["iter_ms"] is None or out["iter_ms"] >= 0  # run completed
    captured = [os.path.join(r, f) for r, _, fs in os.walk(tdir) for f in fs]
    assert captured, "profiler window captured nothing"


def test_obs_port_sidecar_scrapes_during_training(tmp_path, monkeypatch):
    """--obs_port: GET /metrics on the sidecar reports training gauges
    (scraped post-run here; the server lives for the train() call)."""
    import socket

    from galvatron_tpu.core import trainer as trainer_mod

    monkeypatch.setenv("GALVATRON_PEAK_TFLOPS", "0.001")
    # grab a free port (bind/release; narrow race acceptable in CI)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    scraped = {}
    orig_begin = trainer_mod.RuntimeProfiler.begin_iter
    count = {"n": 0}

    def scrape_mid_run(self):
        count["n"] += 1
        if count["n"] == 3:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30
            ) as r:
                scraped["text"] = r.read().decode()
        return orig_begin(self)

    monkeypatch.setattr(trainer_mod.RuntimeProfiler, "begin_iter", scrape_mid_run)
    # --obs_port ALONE: the sidecar must still populate loss/iter_ms/mfu
    # gauges (the sync it needs is implied by opening the port)
    _train(["--train_iters", "4", "--obs_port", str(port)], verbose=False)
    assert_valid_exposition(scraped["text"])
    assert "galvatron_train_iterations_total 2" in scraped["text"]
    assert "galvatron_train_mfu" in scraped["text"]
    assert "galvatron_train_last_loss" in scraped["text"]
    assert "galvatron_train_tokens_per_s" in scraped["text"]
    # the sidecar is torn down with the run
    with pytest.raises(Exception):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=2)
