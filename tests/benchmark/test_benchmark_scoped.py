"""``benchmark/lib/scoped.py`` and the metrics PR 25 built on it: the reader on
a profile written from text, the reductions on a trace small enough to add up
by hand and on one step recorded on the chip, and every new metric on a run
made of hand-written spans and that small trace.  No number here is a device
number; the recorded step's are quoted from the chip run that made it."""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.lib import scoped, xplane  # noqa: E402

# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------

OP_NAMES = [
    # op_name, scopes, phase, second level
    ("jit(train_step)/jvp(embed)/gather:", ("embed",), "forward", "embed"),
    ("jit(train_step)/jvp(layer_0)/attn/qkv_proj/bsh,hcnd->bcnsd/dot_general:",
     ("layer", "attn", "qkv_proj"), "forward", "layer/attn"),
    ("jit(train_step)/transpose(jvp(layer_11))/attn/attn_core/flash_bwd_blocked/pallas_call:",
     ("layer", "attn", "attn_core"), "backward", "layer/attn"),
    # a rematerialized region repeats its path
    ("jit(train_step)/transpose(jvp(layer_1))/jvp(layer_1)/checkpoint/mlp/bsf,fh->bsh/dot_general:",
     ("layer", "mlp"), "backward", "layer/mlp"),
    ("jit(train_step)/transpose(jvp(head))/dot_general:", ("head",), "backward", "head"),
    ("jit(train_step)/jvp(head)/norm/mul:", ("head", "norm"), "forward", "head"),
    ("jit(train_step)/jvp(loss)/reduce_max:", ("loss",), "forward", "loss"),
    ("jit(train_step)/optimizer/sub:", ("optimizer",), "optimizer", "optimizer"),
    ("jit(train_step)/grad_accum/while/body/transpose(jvp(layer_3))/mlp/dot_general:",
     ("grad_accum", "layer", "mlp"), "backward", "layer/mlp"),
    ("jit(train_step)/grad_accum/while/body/jvp(layer_3)/redistribute/sharding_constraint:",
     ("grad_accum", "layer", "redistribute"), "forward", "layer/redistribute"),
    ("jit(train_step)/grad_accum/while/body/closed_call/transpose(jvp(head))/dot_general:",
     ("grad_accum", "head"), "backward", "head"),
    ("jit(train_step)/grad_accum/while/body/closed_call/add:", ("grad_accum",), "forward",
     "grad_accum"),
    ("jit(train_step)/slice:", (), "unscoped", "unscoped"),
    ("", (), "unscoped", "unscoped"),
    # a scope's name inside another word is not the scope
    ("jit(train_step)/my_head_thing/lossy:", (), "unscoped", "unscoped"),
]


@pytest.mark.parametrize("op_name,scopes,phase,second", OP_NAMES)
def test_scopes_phase_and_second_level(op_name, scopes, phase, second):
    assert scoped.scopes_of(op_name) == scopes
    assert scoped.phase_of(op_name) == phase
    assert scoped.second_level(op_name) == second


# ---------------------------------------------------------------------------
# a trace small enough to add up by hand, written as a profile
# ---------------------------------------------------------------------------

KLOOP = "%{n} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, calls=%fc"
KOUT = "%{n} = bf16[8,8]{{1,0}} fusion(bf16[8,8]{{1,0}} %p), kind=kOutput, calls=%fc"
KCUSTOM = "%{n} = bf16[4,512]{{1,0}} fusion(bf16[4,512]{{1,0}} %p), kind=kCustom, calls=%fc"
MOSAIC = ('%{n} = bf16[2,8]{{1,0}} custom-call(bf16[2,8]{{1,0}} %p), '
          'custom_call_target="tpu_custom_call"')
AG_START = "%{n} = (bf16[8]{{0}}, bf16[32]{{0}}) all-gather-start(bf16[8]{{0}} %p), dimensions={{0}}"
AG_DONE = "%{n} = bf16[32]{{0}} all-gather-done((bf16[8]{{0}}, bf16[32]{{0}}) %all-gather-start.1)"
COPY = "%{n} = f32[8]{{0}} copy(f32[8]{{0}} %p)"
#: instruction, HLO text, start ns, duration ns, tf_op, hlo_category
SMALL = [
    ("fusion.1", KLOOP, 0, 100, "jit(train_step)/jvp(embed)/gather:", "loop fusion"),
    ("flash_fwd_qkv.1", MOSAIC, 100, 200,
     "jit(train_step)/jvp(layer_0)/attn/attn_core/flash_fwd_qkv/pallas_call:", "custom-call"),
    ("fusion.2", KOUT, 300, 400, "jit(train_step)/jvp(head)/dot_general:", "convolution fusion"),
    ("fusion.3", KOUT, 700, 800, "jit(train_step)/transpose(jvp(head))/dot_general:",
     "convolution fusion"),
    ("flash_bwd_blocked.1", MOSAIC, 1500, 500,
     "jit(train_step)/transpose(jvp(layer_0))/attn/attn_core/flash_bwd_blocked/pallas_call:",
     "custom-call"),
    ("fusion.4", KCUSTOM, 2000, 300,
     "jit(train_step)/transpose(jvp(layer_0))/mlp/bsf,fh->bsh/dot_general:", "reduce-scatter"),
    ("all-gather-start.1", AG_START, 2300, 10,
     "jit(train_step)/jvp(layer_0)/redistribute/sharding_constraint:", "all-gather-start"),
    ("all-gather-done.1", AG_DONE, 2400, 50,
     "jit(train_step)/jvp(layer_0)/redistribute/sharding_constraint:", "all-gather-done"),
    ("fusion.5", KLOOP, 2450, 500, "jit(train_step)/optimizer/sub:", "loop fusion"),
    ("copy.1", COPY, 2950, 50, "", "copy"),
]
# by hand: busy 100+200+400+800+500+300+10+50+500+50 = 2910 (idle 2310..2400)
#   forward   = fusion.1 100 + flash_fwd 200 + fusion.2 400 + all-gather 10+50 = 760
#   backward  = fusion.3 800 + flash_bwd 500 + fusion.4 300                    = 1600
#   optimizer = fusion.5 500 ; unscoped = copy.1 50 ; 760+1600+500+50 = 2910
#   head      = embed 100 + head 400 + 800 = 1300 ; coverage 2860 / 2910
#   comm      = fusion.4 300 (kCustom whose hlo_category names a collective) + 10 + 50 = 360
#   sync ends at 3200, the last operation at 3000: lag 200
#: name, start ns, duration ns, step_num
HOST = [("train", 0, 3300, 7), ("data", 5, 10, None), ("fwd_bwd", 20, 20, None),
        ("sync", 50, 3150, None), ("$profiler.py:101 start_trace", 0, 1, None)]


def _profile_text(device_rows, host_rows, ordinal=0):
    stat_md = ('stat_metadata { key: 1 value { id: 1 name: "tf_op" } } '
               'stat_metadata { key: 2 value { id: 2 name: "hlo_category" } } ')
    events, metadata = [], []
    for i, (name, text, start, dur, tf_op, cat) in enumerate(device_rows, start=1):
        events.append(f"events {{ metadata_id: {i} offset_ps: {start * 1000} "
                      f"duration_ps: {dur * 1000} }}")
        stats = f'stats {{ metadata_id: 2 str_value: "{cat}" }}'
        if tf_op:
            stats += f' stats {{ metadata_id: 1 str_value: "{tf_op}" }}'
        full = text.format(n=name).replace('"', '\\"')
        metadata.append(f'event_metadata {{ key: {i} value {{ id: {i} name: "{full}" '
                        f'display_name: "{name}" {stats} }} }}')
    device = (f'planes {{ name: "/device:TPU:{ordinal}" lines {{ name: "XLA Ops" timestamp_ns: 0 '
              + " ".join(events) + " } " + " ".join(metadata) + " " + stat_md + "}")
    hevents, hmeta = [], []
    for i, (name, start, dur, step) in enumerate(host_rows, start=1):
        stat = f" stats {{ metadata_id: 1 int64_value: {step} }}" if step is not None else ""
        hevents.append(f"events {{ metadata_id: {i} offset_ps: {start * 1000} "
                       f"duration_ps: {dur * 1000}{stat} }}")
        hmeta.append(f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" }} }}')
    host = ('planes { name: "/host:CPU" lines { name: "python" timestamp_ns: 0 '
            + " ".join(hevents) + " } " + " ".join(hmeta)
            + ' stat_metadata { key: 1 value { id: 1 name: "step_num" } } }')
    return device + "\n" + host


@pytest.fixture(scope="module")
def small_profile(tmp_path_factory):
    """The small trace as a ``.xplane.pb`` beside an exported span file, laid
    out as a traced run leaves them: ``<out>/profile/plugins/profile/<t>/``."""
    from jax.profiler import ProfileData

    out = tmp_path_factory.mktemp("run")
    run = out / "profile" / "plugins" / "profile" / "2026_09_27"
    run.mkdir(parents=True)
    # a second, higher-numbered device whose names must not be read
    other = _profile_text([("fusion.1", KLOOP, 0, 7, "jit(train_step)/optimizer/mul:", "x")],
                          [], ordinal=1).split("\nplanes { name: \"/host")[0]
    raw = ProfileData.text_proto_to_serialized_xspace(other + "\n" + _profile_text(SMALL, HOST))
    path = run / "host.xplane.pb"
    path.write_bytes(raw)
    with open(out / "spans.json", "w") as f:
        json.dump({"traceEvents": [
            {"name": "jax_compile", "ph": "X", "ts": 0, "dur": 1,
             "args": {"hit": True, "retrieval_s": 0.75}},
            {"name": "jax_compile", "ph": "X", "ts": 0, "dur": 1, "args": {"hit": False}},
            {"name": "jax_compile", "ph": "X", "ts": 0, "dur": 1, "args": {"hit": None}},
            {"name": "step", "ph": "X", "ts": 0, "dur": 1, "args": {"step": 0}}]}, f)
    return {"trace_dir": str(out / "profile"), "xplane": str(path), "start_step": 7,
            "stop_step": 8, "first_step": 7, "last_step": 7}


def test_reader_finds_op_names_categories_and_annotations(small_profile):
    data = scoped.read(small_profile["xplane"])
    assert data["op_names"] == {name: tf_op for name, _, _, _, tf_op, _ in SMALL if tf_op}
    assert data["categories"]["fusion.4"] == "reduce-scatter"
    assert [(a.name, a.start, a.end, a.step) for a in data["annotations"]] == [
        ("train", 0.0, 3300.0, 7), ("data", 5.0, 15.0, None), ("fwd_bwd", 20.0, 40.0, None),
        ("sync", 50.0, 3200.0, None)]
    # the profile's own reader agrees on the events the reducer pairs them with
    ops = xplane.first_device(xplane.load(small_profile["xplane"]))
    assert [(o.name, o.start, o.end) for o in ops] == [
        (name, float(start), float(start + dur)) for name, _, start, dur, _, _ in SMALL]
    assert scoped.read(small_profile["xplane"]) is data  # read once


def _small_sops(small_profile):
    data = scoped.read(small_profile["xplane"])
    ops = xplane.first_device(xplane.load(small_profile["xplane"]))
    return ops, scoped.scoped_ops(ops, data["op_names"], data["categories"]), data


def test_reductions_add_up_by_hand(small_profile):
    ops, sops, data = _small_sops(small_profile)
    assert xplane.busy_ns(ops) == 2910
    phases = scoped.phase_ns(sops)
    assert phases == {"forward": 760, "backward": 1600, "optimizer": 500, "unscoped": 50}
    assert sum(phases.values()) == xplane.busy_ns(ops)
    assert scoped.head_ns(sops) == 1300
    assert scoped.comm_ns(sops) == 360
    assert [o.name for o in sops if scoped.is_comm(o)] == [
        "fusion.4", "all-gather-start.1", "all-gather-done.1"]
    assert scoped.kernel_ns(ops, "flash_fwd") == (200, 1)
    assert scoped.kernel_ns(ops, "flash_bwd") == (500, 1)
    assert scoped.second_level_ns(sops) == {
        ("embed", "forward"): 100, ("layer/attn", "forward"): 200, ("head", "forward"): 400,
        ("head", "backward"): 800, ("layer/attn", "backward"): 500,
        ("layer/mlp", "backward"): 300, ("layer/redistribute", "forward"): 60,
        ("optimizer", "optimizer"): 500, ("unscoped", "unscoped"): 50}
    assert scoped.top_unscoped(sops) == [("copy:copy.1 [no op_name]", 50, 1)]
    assert scoped.sync_lags_ns(data["annotations"], sorted(o.end for o in sops)) == [200]


def test_head_is_found_under_the_micro_batch_loop():
    op = "jit(train_step)/grad_accum/while/body/closed_call/{}/dot_general:"
    sops = [scoped.ScopedOp(0, 7, "fusion.1", "fusion:kOutput", op.format("jvp(head)")),
            scoped.ScopedOp(7, 10, "fusion.2", "fusion:kOutput",
                            op.format("transpose(jvp(layer_2))/mlp")),
            scoped.ScopedOp(10, 12, "fusion.3", "fusion:kLoop",
                            "jit(train_step)/grad_accum/while/body/closed_call/add:")]
    assert scoped.head_ns(sops) == 7
    assert scoped.phase_ns(sops) == {"forward": 9, "backward": 3, "optimizer": 0, "unscoped": 0}


def test_sync_lag_pairs_each_sync_with_its_own_step():
    ann = [scoped.Annotation(0, 100, "sync", None, "python"),
           scoped.Annotation(110, 130, "fwd_bwd", None, "python"),
           scoped.Annotation(130, 300, "sync", None, "python"),
           # a sync during which nothing ended on the device is left out
           scoped.Annotation(300, 310, "sync", None, "python")]
    assert scoped.sync_lags_ns(ann, [40.0, 90.0, 150.0, 280.0]) == [10.0, 20.0]


def test_recorded_step():
    """Device 0 of one step of ``baichuan-7b_s4096`` as ``scoped.py`` read it on
    the chip (recorded_scoped_step.json): the reductions on real names."""
    with open(os.path.join(HERE, "recorded_scoped_step.json")) as f:
        rec = json.load(f)
    sops = [scoped.ScopedOp(*o) for o in rec["ops"]]
    ops = [xplane.Op(o.start, o.end, o.name, o.category) for o in sops]
    expect = rec["expect"]
    assert len(sops) == expect["n"] == 374
    phases = scoped.phase_ns(sops)
    assert phases == pytest.approx(expect["phase_ns"])
    # 82.8 + 157.6 + 39.3 + 1.6 = 281.3 ms: one core, nothing overlaps
    assert sum(phases.values()) == pytest.approx(xplane.busy_ns(ops)) == pytest.approx(281313581.0)
    # two kernels under one number until now: 8 forward calls, 2 backward
    fwd, bwd = scoped.kernel_ns(ops, "flash_fwd"), scoped.kernel_ns(ops, "flash_bwd")
    assert (fwd[1], bwd[1]) == (8, 2)
    assert fwd[0] + bwd[0] == pytest.approx(xplane.category_sums(ops)["mosaic-kernel"])
    assert {xplane.base_name(o.name) for o in ops if o.category == "mosaic-kernel"} == {
        "flash_fwd_qkv", "flash_bwd_blocked"}
    assert all("attn_core" in o.op_name for o in sops if o.category == "mosaic-kernel")
    # the head's three GEMMs PR 24 found by their shapes, now by name
    table = scoped.second_level_ns(sops)
    assert table[("head", "backward")] / 1e6 == pytest.approx(57.507, abs=1e-3)
    assert table[("head", "forward")] / 1e6 == pytest.approx(25.152, abs=1e-3)
    assert scoped.head_ns(sops) == pytest.approx(expect["head_ns"])
    assert not any(scoped.is_comm(o) for o in sops)  # one chip
    unscoped = scoped.top_unscoped(sops, 400)
    assert sum(ns for _, ns, _ in unscoped) == pytest.approx(phases["unscoped"])
    assert all("no op_name" in key or not scoped.scopes_of(key) for key, _, _ in unscoped)
    ann = [scoped.Annotation(*a) for a in rec["annotations"]]
    assert scoped.sync_lags_ns(ann, sorted(o.end for o in sops)) == expect["sync_lag_ns"]


# ---------------------------------------------------------------------------
# every new metric, on hand-written spans and the small trace
# ---------------------------------------------------------------------------


def _span(name, start, end, step=None):
    return {"name": name, "start": start, "end": end, "step": step}


SETUP_SPANS = [
    _span("build_runtime", 0.0, 1.0), _span("init_state", 1.0, 3.0), _span("data_open", 3.0, 3.5),
    _span("jax_trace", 1.0, 1.4), _span("jax_trace", 1.1, 1.2), _span("jax_lower", 1.4, 1.5),
    _span("jax_compile", 1.5, 2.5),
    _span("step", 4.0, 9.0, 0), _span("jax_trace", 4.0, 5.0, 0), _span("jax_lower", 5.0, 5.5, 0),
    _span("jax_compile", 5.5, 8.5, 0),
    _span("data_produce", 9.9, 10.1), _span("data_produce", 10.4, 10.45),
    _span("data_produce", 10.85, 11.0),
]
WINDOW_SPANS = [
    _span("step", 10.0, 10.3, 3), _span("data", 10.0, 10.01, 3),
    _span("fwd_bwd", 10.01, 10.012, 3), _span("sync", 10.012, 10.295, 3),
    _span("step", 10.3, 10.6, 4), _span("data", 10.3, 10.32, 4),
    _span("fwd_bwd", 10.32, 10.324, 4), _span("sync", 10.324, 10.59, 4),
    _span("gc", 10.59, 10.596, 4),
    _span("step", 10.6, 10.9, 5), _span("data", 10.6, 10.605, 5),
    _span("fwd_bwd", 10.605, 10.608, 5), _span("sync", 10.608, 10.894, 5),
]
#: by hand (see the comments at SMALL and below)
EXPECTED = {
    "runtime_build_s": 3.5,            # 1 + 2 + 0.5
    "trace_lower_s": 2.0,              # union: 1.0..1.5 and 4.0..5.5
    "compile_or_load_s": 4.0,          # 1 + 3
    "compiles_in_window": 0.0,
    "host_dispatch_ms": 3.0,           # median of 2, 4, 3
    "loop_self_ms": 5.0,               # 300 - 295, 300 - 296, 300 - 294: median of 5, 4, 6
    "data_producer_busy_share": 100 * 0.2 / 0.9,  # 0.1 + 0.05 + 0.05 inside 10.0..10.9
    "host_sync_lag_ms": 200e-6,
    "forward_ms_per_step": 760e-6,
    "backward_ms_per_step": 1600e-6,
    "optimizer_ms_per_step": 500e-6,
    "head_ms_per_step": 1300e-6,
    "scope_coverage": 100 * 2860 / 2910,
    "flash_fwd_ms_per_step": 200e-6,
    "flash_bwd_ms_per_step": 500e-6,
    "comm_scope_ms_per_step": 360e-6,
}


def _metric(name):
    spec = importlib.util.spec_from_file_location(
        f"_metric_{name}", os.path.join(REPO, "benchmark", "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ctx(trace, said):
    return {"spans": list(WINDOW_SPANS), "setup_spans": list(SETUP_SPANS),
            "step_s": [0.3, 0.3, 0.3], "trace": trace, "n_profiled": 1, "say": said.append,
            "records": [], "chips": 1}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_on_the_hand_made_run(name, small_profile, monkeypatch):
    monkeypatch.setattr(scoped, "window", lambda: dict(small_profile))
    said = []
    mod = _metric(name)
    assert mod.NAME == name
    value = mod.compute(_ctx(xplane.load(small_profile["xplane"]), said))
    assert value == pytest.approx(EXPECTED[name], rel=1e-9)
    declared = {m["name"]: m for m in json.load(open(os.path.join(REPO, "BENCHMARK.json")))
                ["per_layer"]}[name]
    assert (declared["unit"], declared["better"], declared["source"], declared["layer"],
            declared["moves"]) == (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES)
    if name == "compile_or_load_s":
        assert any("1 cache hits (0.750 s loading), 1 misses, 1 without the cache" in s
                   and "inside the first step 3.000 s" in s for s in said)
    if name == "compile_or_load_s":
        # 5.0 = 1.5 traced and lowered + 3.0 compiled; no data or sync span in step 0 here
        assert any(s.startswith("first step 5.000 s = trace and lower 1.500 + compile or load 3.000")
                   and "(90.0% named)" in s for s in said)
    if name == "trace_lower_s":
        assert any("inside the first step 1.500 s" in s for s in said)
    if name == "scope_coverage":
        text = "\n".join(said)
        assert "forward 0.001, backward 0.002, optimizer 0.001, unscoped 0.000" in text
        assert "scope head: forward 0.000, backward 0.001" in text
        assert "copy:copy.1 [no op_name]" in text
    if name == "head_ms_per_step":
        assert any("optimizer updates are not in" in s for s in said)
    if name == "flash_fwd_ms_per_step":
        assert any("1 flash_fwd* calls a step on device 0 (flash_fwd_qkv)" in s for s in said)
    if name == "comm_scope_ms_per_step":
        assert any("fusion:kCustom[reduce-scatter] under layer/mlp" in s for s in said)


def test_a_compile_inside_the_window_is_counted_and_named(small_profile, monkeypatch):
    said = []
    ctx = _ctx(None, said)
    ctx["spans"].append(_span("jax_compile", 10.33, 10.5, 4))
    assert _metric("compiles_in_window").compute(ctx) == 1
    assert said == ["compile inside the window: step 4, 0.170 s"]


DEVICE_METRICS = ["host_sync_lag_ms", "forward_ms_per_step", "backward_ms_per_step",
                  "optimizer_ms_per_step", "head_ms_per_step", "scope_coverage",
                  "comm_scope_ms_per_step"]


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_leaves_itself_out_on_a_program_without_the_names(name, small_profile, monkeypatch):
    """The parent of PR 25: its spans are ``step`` / ``data`` / ``fwd_bwd`` /
    ``sync`` alone, it keeps no record of its window, and its kernels are
    ``jvp__`` and ``shard_map``.  Nothing raises; what needs more says None."""
    monkeypatch.setattr(scoped, "window", lambda: None)
    old = ("step", "data", "fwd_bwd", "sync")
    trace = xplane.load(small_profile["xplane"])
    renamed = {"flash_fwd_qkv.1": "jvp__.2", "flash_bwd_blocked.1": "transpose_jvp___.3"}
    trace["devices"] = {d: [o._replace(name=renamed.get(o.name, o.name)) for o in ops]
                        for d, ops in trace["devices"].items()}
    ctx = {"spans": [s for s in WINDOW_SPANS if s["name"] in old],
           "setup_spans": [s for s in SETUP_SPANS if s["name"] in old],
           "step_s": [0.3, 0.3, 0.3], "trace": trace, "n_profiled": 1,
           "say": lambda s: None, "records": [], "chips": 1}
    value = _metric(name).compute(ctx)
    if name in ("host_dispatch_ms", "loop_self_ms"):
        assert value is not None  # they read spans the parent has
    else:
        assert value is None
    # and on the CPU, where the trace holds no device at all
    ctx["trace"] = {"devices": {}, "start_unix_ns": None, "stop_unix_ns": None}
    if name in DEVICE_METRICS + ["flash_fwd_ms_per_step", "flash_bwd_ms_per_step"]:
        monkeypatch.setattr(scoped, "window", lambda: dict(small_profile))
        ctx.pop("_scoped_device0", None)
        assert _metric(name).compute(ctx) is None


def test_window_asks_the_program(monkeypatch):
    from galvatron_tpu.obs import flight

    monkeypatch.setattr(flight, "_last_window", {"trace_dir": "/x", "xplane": None})
    assert scoped.window() == {"trace_dir": "/x", "xplane": None}
    assert scoped.of_ctx({"trace": {"devices": {0: []}}}) is None  # no file, no error
    monkeypatch.delattr(flight, "last_profile_window")
    assert scoped.window() is None
