"""What PR 72 adds to the benchmark, on the CPU: the three readers of the admission's new spans
(`admission_host_ms_p50`, `serve_idle_ms_per_iteration`, `prefill_chunk_ms_at_depth0`), each on a
hand-made serving context with its value worked out by hand; that they leave a training context, a
program from before the spans and a run without a trace alone; the manifest's three appends; and
the three accepted cases of `test_benchmark_granite_small.py` that pin the manifest's length and
tail, whole, one clause amended.  No number here is a device number."""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.lib import harness, xplane  # noqa: E402

#: the readers this PR appends, in the manifest's order
NEW_METRICS = ["admission_host_ms_p50", "serve_idle_ms_per_iteration",
               "prefill_chunk_ms_at_depth0"]
DECLARED = {
    "admission_host_ms_p50": ("ms", "lower", "program_span", "serving engine loop"),
    "serve_idle_ms_per_iteration": ("ms", "lower", "device_trace", "device"),
    "prefill_chunk_ms_at_depth0": ("ms", "lower", "program_span", "serving engine loop"),
}
T0 = 100.0  # the profile's start on the unix clock, s


def _metric(name):
    path = os.path.join(REPO, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("_t72_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _accepted(name):
    """An accepted test file as a module: its constants and its cases."""
    spec = importlib.util.spec_from_file_location("_held72_" + name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _span(name, start_ms, end_ms, **args):
    return {"name": name, "start": T0 + start_ms / 1e3, "end": T0 + end_ms / 1e3, "step": None,
            "args": args}


def _ctx(spans, trace=None, n_profiled=0, seconds=2.0):
    said = []
    return {"spans": spans, "setup_spans": [], "trace": trace, "n_profiled": n_profiled,
            "memory_peak_bytes": 1 << 30, "say": said.append, "said": said,
            "traffic": {"kind": "serve"},
            "serve": {"num_slots": 32, "prefill_chunk": 1024, "ttft_s": [0.1], "itl_s": [0.03],
                      "seconds": seconds}}


def _training(ctx):
    out = {k: v for k, v in ctx.items() if k != "serve"}
    out["traffic"] = {"seq_len": 8}
    return out


# -- admission_host_ms_p50 ------------------------------------------------------------------


def _admissions():
    spans, t = [], 0.0
    for rid, (dur, chunks) in enumerate(((2.0, [1.5]), (3.0, [1.4, 1.3]),
                                         (10.0, [1.6, 1.7, 1.5, 4.6]))):
        spans.append(_span("prefill_dispatch", t, t + dur, rid=rid, tokens=1000 * len(chunks),
                           chunks=len(chunks), first_start=0))
        at = t + 0.2
        for i, c in enumerate(chunks):
            spans.append(_span("chunk_dispatch", at, at + c, start=1024 * i, rows=1000, seq=i))
            at += c
        spans.append(_span("prefill", t + 1.0, t + 40.0, rid=rid, tokens=1000 * len(chunks),
                           chunks=len(chunks), depth_sum=0, synced=True))
        t += 50.0
    return spans


def test_an_admissions_host_time_is_the_median_dispatch_span():
    ctx = _ctx(_admissions(), seconds=2.0)
    assert _metric("admission_host_ms_p50").compute(ctx) == pytest.approx(3.0)
    last = ctx["said"][-1]
    # n, the median, the chunks and their median (1.4 1.3 1.5 1.5 1.6 1.7 4.6), the rate
    assert "n=3 in the window, p50 = 3.000 ms" in last and "7 chunks" in last
    assert "chunk_dispatch p50 = 1.500 ms" in last and "1.500 admissions a second" in last


def test_the_admission_reader_is_silent_without_its_spans():
    mod = _metric("admission_host_ms_p50")
    ctx = _ctx(_admissions())
    assert mod.compute(_training(ctx)) is None
    # a program from before PR 72: ``prefill`` on the loop thread, no ``prefill_dispatch``
    old = _ctx([s for s in ctx["spans"] if s["name"] == "prefill"])
    assert mod.compute(old) is None and old["said"] == []


# -- prefill_chunk_ms_at_depth0 --------------------------------------------------------------


def _prompt(rid, chunks, a, b, first_start=0, extra_ms=0.0, **more):
    """A prompt of ``chunks`` chunks of 1,024 from ``first_start``: duration a a chunk + b a
    chunk a 1,024 positions of depth, exactly."""
    depth = sum(first_start + 1024 * i for i in range(chunks))
    dur = a * chunks + b * depth / 1024 + extra_ms
    return _span("prefill", 100.0 * rid, 100.0 * rid + dur, rid=rid, tokens=1024 * chunks,
                 chunks=chunks, depth_sum=depth, synced=True, **more)


def test_the_fit_recovers_a_planted_chunk_time_and_depth_cost():
    mod = _metric("prefill_chunk_ms_at_depth0")
    shapes = [1, 2, 3, 4, 1, 2, 3, 4, 6]
    spans = [_prompt(i, n, a=20.0, b=3.0) for i, n in enumerate(shapes)]
    spans.append(_prompt(9, 2, a=20.0, b=3.0, first_start=512))
    # left out, each of which would bend the fit: the device was through before anyone looked,
    # the device died, the worker had not got to it
    spans += [_prompt(10, 2, 20.0, 3.0, extra_ms=500.0, opened_late=True),
              _prompt(11, 3, 20.0, 3.0, extra_ms=500.0, error="RuntimeError"),
              _prompt(12, 1, 20.0, 3.0, extra_ms=500.0, pending=True)]
    ctx = _ctx(spans)
    assert mod.compute(ctx) == pytest.approx(20.0, abs=1e-9)
    assert "n=10 ({'opened_late': 1, 'error': 1, 'pending': 1} left out) of 6 shapes" in ctx["said"][-1]
    assert "a = 20.000 ms" in ctx["said"][-1]
    assert "b = 3.000 ms a chunk a 1,024 positions of depth" in ctx["said"][-1]
    assert "residual sd 0.000 ms" in ctx["said"][-1]
    # by hand, two shapes: (1 chunk, depth 0) takes 20; (2 chunks, depth 1) takes 43
    assert mod.fit([(1.0, 0.0, 20.0), (2.0, 1.0, 43.0)])[:2] == pytest.approx((20.0, 3.0))
    # noise of +-1 ms on alternate spans moves a by less than it
    noisy = [_prompt(i, n, 20.0, 3.0, extra_ms=(-1.0) ** i) for i, n in enumerate(shapes * 2)]
    assert mod.compute(_ctx(noisy)) == pytest.approx(20.0, abs=0.5)


def test_the_fit_leaves_out_a_span_that_held_something_else():
    """One pass of trimming: a prompt that held the profiler's start (+100 ms) among forty
    that scatter by +-0.5 ms moves the plain fit's a by more than a millisecond and the
    trimmed one's by nothing; the span left out is said, with its request."""
    mod = _metric("prefill_chunk_ms_at_depth0")
    spans = [_prompt(i, 1 + i % 5, 20.0, 3.0, extra_ms=0.5 * (-1.0) ** (i // 5))
             for i in range(40)]
    clean = mod.compute(_ctx(spans))
    spans[7] = _prompt(7, 3, 20.0, 3.0, extra_ms=100.0)
    plain = mod.fit([(s["args"]["chunks"], s["args"]["depth_sum"] / 1024,
                      1e3 * (s["end"] - s["start"])) for s in spans])[0]
    ctx = _ctx(spans)
    assert abs(plain - clean) > 0.5 and mod.compute(ctx) == pytest.approx(clean, abs=0.1)
    assert "prefill spans trimmed: rid 7 3 chunks 169.000 ms" in ctx["said"][-2]
    assert "n=39 ({'trimmed': 1} left out)" in ctx["said"][-1]
    # 5% of the spans at most: of three far ones among forty, the two farthest go
    for i, extra in ((8, 90.0), (9, 80.0)):
        spans[i] = _prompt(i, 1 + i % 5, 20.0, 3.0, extra_ms=extra)
    ctx = _ctx(spans)
    mod.compute(ctx)
    assert "n=38 ({'trimmed': 2} left out)" in ctx["said"][-1]


def test_the_fit_refuses_a_window_of_one_prompt_shape_and_a_short_one():
    mod = _metric("prefill_chunk_ms_at_depth0")
    same = _ctx([_prompt(i, 3, 20.0, 3.0) for i in range(12)])
    assert mod.compute(same) is None and "cannot be told from its depth" in same["said"][-1]
    short = _ctx([_prompt(i, 1 + i % 4, 20.0, 3.0) for i in range(7)])
    assert mod.compute(short) is None and "n=7 < 8" in short["said"][-1]
    # every chunk at depth 0 (prompts of one chunk): the fit is a alone, and says so
    flat = _ctx([_prompt(i, 1, 20.0 + i % 2, 3.0) for i in range(8)])
    assert mod.compute(flat) == pytest.approx(20.5)
    assert "b = undetermined (every chunk at depth 0)" in flat["said"][-1]


def test_the_fit_is_silent_without_its_arguments():
    mod = _metric("prefill_chunk_ms_at_depth0")
    ctx = _ctx([_prompt(i, 1 + i % 4, 20.0, 3.0) for i in range(12)])
    assert mod.compute(_training(ctx)) is None
    # a program from before PR 72: ``prefill`` spans that say neither ``chunks`` nor ``depth_sum``
    old = _ctx([dict(s, args={"rid": s["args"]["rid"], "tokens": s["args"]["tokens"]})
                for s in ctx["spans"]])
    assert mod.compute(old) is None and old["said"] == []


# -- serve_idle_ms_per_iteration -------------------------------------------------------------


def _op(start_ms, end_ms, name="fusion.1", category="fusion:kLoop"):
    return xplane.Op(start_ms * 1e6, end_ms * 1e6, name, category)


def _trace():
    """Device 0 busy 0-10, 12-20, 25-30, 30.5-40 ms: idle 2 + 5 + 0.5 = 7.5 ms in three gaps
    (a ``while`` over the first two is a container, not work)."""
    return {"start_unix_ns": T0 * 1e9, "stop_unix_ns": (T0 + 1) * 1e9,
            "devices": {0: [_op(0, 10), _op(12, 20), _op(25, 30), _op(30.5, 40),
                            _op(0, 20, "while.3", "container")]}}


def _loop():
    return [
        _span("iteration", 0.0, 22.0, step=7, active=3, queued=4),
        _span("admit", 9.0, 13.0, admitted=1),
        _span("prefill_dispatch", 9.5, 12.5, rid=7, tokens=2000, chunks=2, first_start=0),
        _span("chunk_dispatch", 10.5, 11.8, start=1024, rows=976, seq=41),
        _span("iteration", 24.0, 40.0, step=8, active=3, queued=3),
        _span("decode", 24.2, 31.0, active=3),
        _span("decode_wait", 24.5, 30.8, synced=True),
    ]


#: spans that cover the first gap's middle (11 ms) more tightly than any of the loop thread's
#: and are none of its: the queue's track, the device's track
NOT_THE_LOOPS = [_span("queue_wait", 10.9, 11.1, rid=7, depth=3),
                 _span("prefill", 10.8, 11.2, rid=7, tokens=2000, chunks=2, depth_sum=1024)]


def test_idle_goes_to_the_loop_threads_innermost_span_at_each_gaps_middle():
    mod = _metric("serve_idle_ms_per_iteration")
    ctx = _ctx(_loop() + NOT_THE_LOOPS, trace=_trace(), n_profiled=2)
    assert mod.compute(ctx) == pytest.approx(7.5 / 2)
    table, longest = ctx["said"][-2:]
    assert "idle 7.500 ms of 40.000 profiled ms (18.75%) in 3 gaps over 2 iterations" in table
    # all of it, by name, ms an iteration: 5 / 2, 2 / 2, 0.5 / 2
    assert table.endswith("between_iterations 2.5000 (1); chunk_dispatch 1.0000 (1); "
                          "decode_wait 0.2500 (1)")
    assert "queue_wait" not in table and "prefill " not in table
    assert longest.endswith("between_iterations 5.000; chunk_dispatch 2.000 start=1024; "
                            "decode_wait 0.500")
    # the chunk's span gone, the gap is its admission's: with the request's id
    fewer = _ctx([s for s in _loop() if s["name"] != "chunk_dispatch"] + NOT_THE_LOOPS,
                 trace=_trace(), n_profiled=2)
    assert mod.compute(fewer) == pytest.approx(3.75)
    assert "prefill_dispatch 2.000 rid=7" in fewer["said"][-1]


def test_idle_names_nothing_and_says_why_where_only_other_tracks_cover_the_profile():
    mod = _metric("serve_idle_ms_per_iteration")
    # the ring overflown: what is left to cover the profiled seconds is the queue's and the
    # device's tracks, and loop-thread records of LATER seconds.  No gap gets a name (never
    # ``queue_wait``, never ``between_iterations``); the value is the device's and stands
    later = [dict(s, start=s["start"] + 30.0, end=s["end"] + 30.0) for s in _loop()]
    ctx = _ctx(later + NOT_THE_LOOPS + [_span("queue_wait", 0.0, 40.0, rid=1, depth=9)],
               trace=_trace(), n_profiled=2)
    assert mod.compute(ctx) == pytest.approx(3.75)
    assert "overlaps the 0.040 profiled seconds: the ring lost them" in ctx["said"][-3]
    assert ctx["said"][-2].endswith("innermost span: lost_by_the_ring 3.7500 (3)")
    assert ctx["said"][-1].endswith(
        "lost_by_the_ring 5.000; lost_by_the_ring 2.000; lost_by_the_ring 0.500")
    # part of them lost: what lies in front of the oldest record the ring holds is said so
    half = _ctx(_loop()[4:], trace=_trace(), n_profiled=2)
    assert mod.compute(half) == pytest.approx(3.75)
    assert half["said"][-2].endswith("lost_by_the_ring 3.5000 (2); decode_wait 0.2500 (1)")


def test_idle_is_none_without_a_trace_and_in_a_training_context():
    mod = _metric("serve_idle_ms_per_iteration")
    untraced = _ctx(_loop())
    assert mod.compute(untraced) is None and "no device trace" in untraced["said"][-1]
    assert mod.compute(_ctx(_loop(), trace=_trace(), n_profiled=0)) is None
    assert mod.compute(_training(_ctx(_loop(), trace=_trace(), n_profiled=2))) is None
    # a program from before PR 72 has the loop's older spans: the reader answers there too,
    # and an admission's gap is ``admit``'s (``prefill``, then the loop thread's, never names one)
    old = [s for s in _loop() if s["name"] not in ("prefill_dispatch", "chunk_dispatch")]
    old.append(_span("prefill", 9.2, 12.8, rid=7, tokens=2000))
    ctx = _ctx(old, trace=_trace(), n_profiled=2)
    assert mod.compute(ctx) == pytest.approx(3.75) and "admit 1.0000 (1)" in ctx["said"][-2]


def test_a_gaps_span_is_looked_for_among_the_open_ones_alone():
    mod = _metric("serve_idle_ms_per_iteration")
    host = sorted([(0.0, 100.0, "iteration", {}), (10.0, 20.0, "admit", {}),
                   (12.0, 14.0, "chunk_dispatch", {}), (30.0, 50.0, "decode", {})])
    got = mod.attribute([(12.5, 13.5), (14.5, 15.5), (24.0, 26.0), (99.0, 103.0)], host)
    assert [span and span[2] for _, span in got] == ["chunk_dispatch", "admit", "iteration", None]


# -- the manifest ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_is_declared_as_an_unlisted_serving_reader(name):
    manifest = harness.load_manifest(REPO)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    mod = _metric(name)
    assert entry["moves"] == "serve_tokens_per_s_per_chip"
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"]) == DECLARED[name]
    assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(
        entry[k] for k in ("name", "unit", "better", "source", "layer", "moves"))
    layers = {m["layer"] for m in manifest["per_layer"] if m["name"] not in NEW_METRICS}
    assert entry["layer"] in layers  # a layer the benchmark already names
    assert set(entry) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    serving = next(m for m in manifest["end_to_end"]
                   if m["name"] == "serve_tokens_per_s_per_chip")["workloads"]
    # (a list, where the chip's runs ask for one, names serving cells alone)
    assert set(entry.get("workloads", serving)) <= set(serving)


def test_the_three_join_the_manifest_by_appends():
    manifest = harness.load_manifest(REPO)
    readers = [m["name"] for m in manifest["per_layer"]]
    assert readers[-3:] == NEW_METRICS and len(readers) == 101 and len(set(readers)) == 101
    assert len(manifest["workloads"]) == 15 and len(manifest["configs"]) == 12  # no cell added
    assert manifest["run_seconds"] == 51
    assert len(json.dumps(manifest, indent=2)) < 64 * 1024
    for name in NEW_METRICS:
        assert os.path.exists(os.path.join(REPO, "benchmark", "metrics", name + ".py"))
    # every reader's module is discovered once, under its entry's name
    found = [m.NAME for m in harness.discover_metrics(REPO)]
    assert sorted(found) == sorted(readers)


# -- the accepted cases this PR's appends broke, whole, one clause amended ---------------------

GS = _accepted("test_benchmark_granite_small")
NM = GS.NM


def test_granite_small_the_cell_joins_the_manifest_by_appends():
    """`test_benchmark_granite_small.py::test_the_cell_joins_the_manifest_by_appends`, amended:
    its two readers are followed by this PR's three (98 readers become 101)."""
    manifest = harness.load_manifest(REPO)
    names = [w["name"] for w in manifest["workloads"]]
    assert names[-2:] == [GS.NEMOTRON, GS.CELL] and len(names) == 15
    cell = manifest["workloads"][-1]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert (cell["config"], cell["traffic"], cell["chips"]) == (GS.CONFIG, GS.TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2  # 2 of 15: no more
    entry = manifest["configs"][-1]
    assert set(entry) == {"name", "source", "file", "reduced", "why"} and len(manifest["configs"]) == 12
    assert (entry["name"], entry["source"]) == (GS.CONFIG, GS.SOURCE) and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/granite-4.0-h-small.json"
    assert sorted(entry["reduced"]) == sorted(GS.CUT)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["serve_tokens_per_s_per_chip"]["workloads"][-2:] == [GS.NEMOTRON, GS.CELL]
    assert GS.CELL not in e2e["tokens_per_s_per_chip"]["workloads"]
    assert e2e["serve_tokens_per_s_per_chip"]["bound"] == 0.035 and manifest["run_seconds"] == 51
    readers = [m["name"] for m in manifest["per_layer"]]
    # THE amended clause (the original: ``readers[-2:] == NEW_METRICS and len(readers) == 98``)
    assert readers[-5:] == GS.NEW_METRICS + NEW_METRICS and len(readers) == 101
    # the lists that name the cell: its two readers and the four accepted readers of a
    # prompt chunk, to each of whose lists the cell is APPENDED (what was there as it was);
    # THE other amended clause: and what this PR's readers list, if the chip asked for a list
    listed = {m["name"]: m["workloads"] for m in manifest["per_layer"]
              if GS.CELL in m.get("workloads", []) and m["name"] not in NEW_METRICS}
    assert sorted(listed) == sorted(GS.NEW_METRICS + GS.JOINED)
    assert all(listed[name][-2:] == [GS.NEMOTRON, GS.CELL] for name in GS.JOINED)
    assert len(listed["kv_prefill_chunk_attn_ms"]) == 7
    assert all(listed[name] == [GS.NEMOTRON, GS.CELL] for name in GS.JOINED[1:])
    assert len(json.dumps(manifest, indent=2)) < 64 * 1024
    # a full check fits the driver's budget at one more cell
    cells = len(names)
    assert (2 + 14 * cells) * (manifest["run_seconds"] + 60) + 2 * 90 * cells + 1200 <= 43200


def test_nemotron_the_cell_joins_the_manifest_by_appends():
    """`test_benchmark_granite_small.py::test_nemotron_the_cell_joins_the_manifest_by_appends`
    (itself `test_benchmark_nemotron.py`'s case, amended by PR 70), amended: PR 68's seven
    readers are followed by PR 70's two and this PR's three."""
    manifest = harness.load_manifest(REPO)
    names = [w["name"] for w in manifest["workloads"]]
    at = names.index(NM.CELL)
    assert at == 13 and names[:at][-1] == NM.SERVING[-1]
    cell = manifest["workloads"][at]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and cell["config"] == NM.CONFIG
    assert cell["traffic"] == "serve_chat_ssm_open_above_knee"
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("dots3-note-prev") < configs.index(NM.CONFIG)
    entry = manifest["configs"][configs.index(NM.CONFIG)]
    assert entry["source"] == NM.SOURCE and len(entry["why"]) <= 200 and sorted(
        entry["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert entry["file"] == "benchmark/configs/nemotron-3-nano-30b-a3b.json"
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    serving = e2e["serve_tokens_per_s_per_chip"]["workloads"]
    assert serving[:serving.index(NM.CELL)] == NM.SERVING
    assert NM.CELL not in e2e["tokens_per_s_per_chip"]["workloads"]
    assert e2e["serve_tokens_per_s_per_chip"]["bound"] == 0.035 and manifest["run_seconds"] == 51
    readers = [m["name"] for m in manifest["per_layer"]]
    first = readers.index(NM.NEW_METRICS[0])
    # THE amended clause (PR 70's: ``readers[first:] == NM.NEW_METRICS + NM.CHUNK_METRICS +
    # NEW_METRICS``, its own two)
    assert readers[first:] == NM.NEW_METRICS + NM.CHUNK_METRICS + GS.NEW_METRICS + NEW_METRICS
    assert readers.index("moe_layout_ms_per_step") < first
    # THE other amended clause: this PR's readers, if the chip asked one for a list, aside
    listed = {m["name"]: m["workloads"] for m in manifest["per_layer"]
              if NM.CELL in m.get("workloads", []) and m["name"] not in NEW_METRICS}
    assert sorted(listed) == sorted(NM.CHUNK_METRICS + ["kv_prefill_chunk_attn_ms"])
    assert listed["kv_prefill_chunk_attn_ms"] == NM.SERVING[:5] + [NM.CELL, GS.CELL]
    assert len(json.dumps(manifest, indent=2)) < 64 * 1024


def test_dots3_the_cell_joins_the_manifest_by_appends():
    """`test_benchmark_granite_small.py::test_dots3_the_cell_joins_the_manifest_by_appends`
    (itself `test_benchmark_dots3.py`'s case, amended by PR 67, 68 and 70), amended: the dots3
    cell's readers are followed by PR 67's, PR 68's, PR 70's and this PR's."""
    d3 = _accepted("test_benchmark_dots3")
    manifest = harness.load_manifest(REPO)
    names = [w["name"] for w in manifest["workloads"]]
    assert names[-3:] == [d3.CELL, NM.CELL, GS.CELL] and len(names) == 15
    assert [n for n in names if n in d3.SERVING_BEFORE] == d3.SERVING_BEFORE
    cell = manifest["workloads"][-3]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("dots3-note-prev", d3.TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and len(manifest["configs"][-3]["why"]) <= 200
    assert [c["name"] for c in manifest["configs"]][-3:] == [
        "dots3-note-prev", NM.CONFIG, GS.CONFIG]
    assert len(manifest["configs"]) == 12
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2  # 2 of 15: no more
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["serve_tokens_per_s_per_chip"]["workloads"] == d3.SERVING_BEFORE + [
        d3.CELL, NM.CELL, GS.CELL]
    assert d3.CELL not in e2e["tokens_per_s_per_chip"]["workloads"]
    assert "workloads" not in e2e["setup_s"] and manifest["run_seconds"] == 51
    per = [m["name"] for m in manifest["per_layer"]]
    # THE amended clause (PR 70's: ``per[-15:] == ...`` and 98)
    assert per[-18:] == (d3.NEW_METRICS + ["moe_layout_ms_per_step"] + NM.NEW_METRICS
                         + NM.CHUNK_METRICS + GS.NEW_METRICS + NEW_METRICS)
    assert len(per) == 101
    # THE other amended clause: this PR's readers, if the chip asked one for a list, aside
    assert not [m["name"] for m in manifest["per_layer"]
                if d3.CELL in m.get("workloads", []) and m["name"] not in NEW_METRICS]
    cells = len(names)
    assert (2 + 14 * cells) * (manifest["run_seconds"] + 60) + 2 * 90 * cells + 1200 <= 43200


def test_every_marked_case_has_its_whole_copy_here():
    """tests/conftest.py's list and this file, one for one: a case marked there without its
    copy here would be a test switched off."""
    sys.path.insert(0, os.path.dirname(HERE))
    import conftest

    at = "test_benchmark_granite_small.py::"
    copies = {
        at + "test_the_cell_joins_the_manifest_by_appends":
            test_granite_small_the_cell_joins_the_manifest_by_appends,
        at + "test_nemotron_the_cell_joins_the_manifest_by_appends":
            test_nemotron_the_cell_joins_the_manifest_by_appends,
        at + "test_dots3_the_cell_joins_the_manifest_by_appends":
            test_dots3_the_cell_joins_the_manifest_by_appends,
    }
    marked = {node.split("tests/benchmark/")[1] for node in conftest._PINNED_BEFORE_PR_72}
    assert marked == set(copies) and len(marked) == 3
    assert all(callable(f) for f in copies.values())
