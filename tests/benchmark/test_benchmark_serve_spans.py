"""The seven readers of the engine's own span tree (PR 39), each on a hand-made
serving context with its value worked out by hand; that they leave a training
context and a context holding only the older spans alone; that the manifest and
the modules agree; that an idle gap is named after the innermost of the new
spans.  No number here is a device number."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.lib import harness, xplane  # noqa: E402

#: what marks a reader of the new tree: the helper they share
MARKER = "_engine_spans"
NEW = {"host_sample_ms_per_slot_p50", "decode_dispatch_ms_p50", "decode_device_wait_ms_p50",
       "logits_readback_ms_p50", "engine_loop_self_ms_p50", "queue_wait_ms_p50",
       "serve_compiles_in_window"}


def _span(name, start_ms, dur_ms, **args):
    return {"name": name, "start": 100.0 + start_ms / 1e3, "end": 100.0 + (start_ms + dur_ms) / 1e3,
            "step": None, "args": args}


def _iteration(step, t0, *, admit=0.0, slots=(), dispatch, wait, readback, tail, queued=0,
               forward="decode"):
    """One iteration's spans from ``t0`` ms: 0.1 of the loop's own, ``admit``,
    0.05, the ``sample`` span (0.02 + each slot's draw + 0.01 between), 0.1, the
    forward (0.01 + its three parts + 0.01), ``tail`` of the loop's own."""
    out, t = [], t0 + 0.1
    if admit:
        out += [_span("admit", t, admit, admitted=1),
                _span("prefill", t + 0.01, admit - 0.02, rid=step, tokens=300)]
        t += admit
    t += 0.05
    s0, t = t, t + 0.02
    for i, (dur, greedy) in enumerate(slots):
        out.append(_span("sample_slot", t, dur, slot=i, rid=i, greedy=greedy))
        t += dur + 0.01
    out.append(_span("sample", s0, t - s0, active=len(slots)))
    t += 0.1
    f0, t = t, t + 0.01
    for name, dur, extra in (("decode_dispatch", dispatch, {}), ("decode_wait", wait, {}),
                             ("logits_readback", readback, {"bytes": 1608704})):
        out.append(_span(name, t, dur, **extra))
        t += dur
    t += 0.01
    out.append(_span(forward, f0, t - f0, active=len(slots)))
    t += tail
    out.append(_span("iteration", t0, t - t0, step=step, active=len(slots), queued=queued))
    return out, t


@pytest.fixture()
def serving_ctx():
    said = []
    spans, t = [], 0.0
    # three iterations: the loop's own 0.1 + 0.05 + 0.1 + tail = 0.45, 0.55, 0.75 ms
    for step, kw in enumerate((
            dict(slots=[(2.0, False), (3.0, False), (1.0, True)], dispatch=1.0, wait=14.0,
                 readback=0.5, tail=0.2, queued=4),
            dict(admit=13.0, slots=[(2.5, False), (1.2, True)], dispatch=1.5, wait=15.0,
                 readback=0.7, tail=0.3, queued=3),
            dict(slots=[(2.2, False)], dispatch=1.2, wait=16.0, readback=0.6, tail=0.5,
                 queued=7, forward="decode_verify"))):
        more, t = _iteration(40 + step, t, **kw)
        spans += more
    queue_tid_spans = [_span("queue_wait", 0.0, 5.0, rid=1, depth=3),
                       _span("queue_wait", 1.0, 250.0, rid=2, depth=2),
                       _span("queue_wait", 2.0, 900.0, rid=3, depth=5)]
    return {"spans": spans + queue_tid_spans, "said": said,
            "setup_spans": [_span("jax_compile", -5000.0, 900.0, fun_name="jit(_decode_step)")],
            "trace": None, "memory_peak_bytes": 1 << 30, "say": said.append,
            "traffic": {"kind": "serve"},
            "serve": {"num_slots": 8, "prefill_chunk": 256, "ttft_s": [0.1], "itl_s": [0.03]}}


def _mods():
    return {m.NAME: m for m in harness.discover_metrics(REPO) if m.NAME in NEW}


@pytest.mark.parametrize("name,want", [
    ("host_sample_ms_per_slot_p50", 2.1),   # draws 1.0 1.2 2.0 2.2 2.5 3.0
    ("decode_dispatch_ms_p50", 1.2),        # 1.0 1.2 1.5
    ("decode_device_wait_ms_p50", 15.0),    # 14 15 16
    ("logits_readback_ms_p50", 0.6),        # 0.5 0.6 0.7
    ("engine_loop_self_ms_p50", 0.55),      # 0.45 0.55 0.75
    ("queue_wait_ms_p50", 250.0),           # 5 250 900
    ("serve_compiles_in_window", 0),
])
def test_reader_on_a_hand_made_serving_context(serving_ctx, name, want):
    assert _mods()[name].compute(serving_ctx) == pytest.approx(want, abs=1e-6)


def test_what_the_readers_print(serving_ctx):
    mods = _mods()
    for name in sorted(NEW):
        mods[name].compute(serving_ctx)
    said = "\n".join(serving_ctx["said"])
    # sampled and greedy apart: sampled 2.0 2.2 2.5 3.0, greedy 1.0 1.2
    assert "sample_slot spans, sampled: n=4, p50 = 2.350 ms" in said
    assert "sample_slot spans, greedy: n=2, p50 = 1.100 ms" in said
    # the three parts against the forwards they lie in: 15.5 + 17.2 + 17.8 of 0.02 more each
    assert "cover 50.5 of 50.6 ms of shared forwards (99.88%" in said
    assert "logits_readback: 1608704 bytes a step" in said
    assert "queue depth: 4 at the window's first iteration, 7 at its last (of 3)" in said


def test_a_compile_inside_the_window_is_counted_and_named(serving_ctx):
    serving_ctx["spans"].append(_span("jax_compile", 20.0, 400.0, step=41,
                                      fun_name="jit(_decode_step)", hit=False))
    assert _mods()["serve_compiles_in_window"].compute(serving_ctx) == 1
    assert any("iteration 41, jit(_decode_step), 0.400 s" in line for line in serving_ctx["said"])
    # a program that records no compile at all (no listener) reports nothing, not 0
    quiet = dict(serving_ctx, setup_spans=[], spans=[s for s in serving_ctx["spans"]
                                                     if s["name"] != "jax_compile"])
    assert _mods()["serve_compiles_in_window"].compute(quiet) is None


def test_the_new_readers_leave_a_training_context_alone(serving_ctx):
    """A training cell's context has no ``serve``: every reader of the new tree
    returns None, whatever spans it holds (never a 0).  The older serving
    readers are counted by the substring their helper's name leaves in them;
    these by theirs, and they hold none of the other."""
    ctx = {k: v for k, v in serving_ctx.items() if k != "serve"}
    ctx["traffic"] = {"seq_len": 8}
    mods = [m for m in harness.discover_metrics(REPO) if MARKER in open(m.__file__).read()]
    assert {m.NAME for m in mods} == NEW and len(mods) == 7
    assert all(m.compute(ctx) is None for m in mods)
    assert not any("_serve" in open(m.__file__).read() for m in mods)
    helper = os.path.join(REPO, "benchmark", "metrics", MARKER + ".py")
    assert "_serve" not in open(helper).read()


def test_a_program_from_before_the_spans_leaves_every_reader_out(serving_ctx):
    """The parent's view: ``sample``, ``decode``, ``prefill`` and the set-up's
    compiles, none of the new names."""
    old = dict(serving_ctx, spans=[s for s in serving_ctx["spans"]
                                   if s["name"] in ("sample", "decode", "prefill")])
    assert old["spans"] and all(m.compute(old) is None for m in _mods().values())


def test_every_new_entry_has_a_module_and_every_module_an_entry():
    per = {m["name"]: m for m in harness.load_manifest(REPO)["per_layer"]}
    mods = _mods()
    assert set(mods) == NEW <= set(per)
    # appended in one block; later PRs append theirs after it
    names = [m["name"] for m in harness.load_manifest(REPO)["per_layer"]]
    first = names.index("host_sample_ms_per_slot_p50")
    assert names[first:first + 7] == [
        "host_sample_ms_per_slot_p50", "decode_dispatch_ms_p50", "decode_device_wait_ms_p50",
        "logits_readback_ms_p50", "engine_loop_self_ms_p50", "queue_wait_ms_p50",
        "serve_compiles_in_window"]
    for name, mod in mods.items():
        entry = per[name]
        assert "workloads" not in entry and entry["moves"] == "serve_tokens_per_s_per_chip"
        assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"])
    assert per["serve_compiles_in_window"]["source"] == "program_counter"
    assert per["queue_wait_ms_p50"]["layer"] == "serving scheduler and slots"


@pytest.mark.parametrize("gap_ms,want", [
    ((1.0, 2.0), "sample_slot"),      # in the middle of the first slot's draw
    ((2.6, 2.62), "sample"),          # between two draws
    ((20.0, 21.0), "decode_wait"),    # the host waits for the device it idles: a trace's artefact
    ((40.0, 41.0), "iteration"),      # the loop's own
    ((60.0, 61.0), "between_steps"),
])
def test_an_idle_gap_is_named_after_the_innermost_span(gap_ms, want):
    host = [(0.0, 50.0, "iteration"), (0.5, 8.0, "sample"), (0.52, 2.52, "sample_slot"),
            (2.7, 5.0, "sample_slot"), (9.0, 30.0, "decode"), (9.01, 10.0, "decode_dispatch"),
            (10.0, 29.0, "decode_wait"), (29.0, 29.9, "logits_readback")]
    host = [(a * 1e6, b * 1e6, n) for a, b, n in host]
    a, b = (x * 1e6 for x in gap_ms)
    ops = [xplane.Op(a - 1e5, a, "fusion.1", "fusion"), xplane.Op(b, b + 1e5, "fusion.2", "fusion")]
    (name, secs), = xplane.attribute_gaps(ops, host, n=1)
    assert name == want and secs == pytest.approx((b - a) / 1e9)
