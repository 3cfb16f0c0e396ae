"""The decode step's device half and the serving cell's shares of the chip's
peaks (PR 42): ``lib/scoped.py``'s executions by program on a profile written
from text (two jitted programs in one window, an instruction name they share)
and on a decode and a prefill execution recorded on the chip; the eight readers
on a hand-made serving context; ``lib/flops.py``'s least bytes and FLOPs of a
served forward on counts worked out by hand for ``opt-1.3b``.  No number here is
a device number; the recorded step's are quoted from the chip run that made it."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.lib import flops, harness, reference, scoped, serve, xplane  # noqa: E402
from benchmark.metrics import _decode_device, _serve_work  # noqa: E402

# ---------------------------------------------------------------------------
# a window of two programs, small enough to add up by hand
# ---------------------------------------------------------------------------

D, P = "jit(_decode_step)/", "jit(_prefill_chunk)/"
#: instruction, HLO text, tf_op; the two programs both have a ``fusion.1`` and a ``fusion.2``
TEXTS = {
    "d.fusion.1": ("fusion.1", "%fusion.1 = bf16[64,8]{1,0} fusion(f32[64,8]{1,0} %p), kind=kLoop",
                   D + "embed/convert_element_type:"),
    "d.fusion.2": ("fusion.2", "%fusion.2 = bf16[4,16,2]{2,1,0} fusion(bf16[4,16,2]{2,1,0} %p), "
                   "kind=kLoop", D + "layer_0/attn/attn_core/dot_general:"),
    "d.dus.1": ("dynamic-update-slice.1", "%dynamic-update-slice.1 = bf16[4,16,2]{2,1,0} "
                "dynamic-update-slice(bf16[4,16,2]{2,1,0} %c, bf16[4,1,2]{2,1,0} %k)",
                D + "layer_0/attn/cache_write/dynamic_update_slice:"),
    "d.fusion.3": ("fusion.3", "%fusion.3 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %p), kind=kOutput",
                   D + "layer_0/mlp/dot_general:"),
    "d.fusion.4": ("fusion.4", "%fusion.4 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %q), kind=kLoop",
                   D + "layer_0/attn/qkv_proj/norm/mul:"),
    "d.copy-done.1": ("copy-done.1", "%copy-done.1 = f32[8]{0} copy-done((f32[8]{0}) %s)", ""),
    "d.fusion.5": ("fusion.5", "%fusion.5 = bf16[4,64]{1,0} fusion(bf16[4,8]{1,0} %p), kind=kOutput",
                   D + "head/dot_general:"),
    "p.fusion.1": ("fusion.1", "%fusion.1 = bf16[16,8]{1,0} fusion(bf16[16,8]{1,0} %p), "
                   "kind=kOutput", P + "layer_0/mlp/dot_general:"),
    "p.fusion.2": ("fusion.2", "%fusion.2 = bf16[16,8]{1,0} fusion(bf16[16,8]{1,0} %q), "
                   "kind=kOutput", P + "layer_0/attn/attn_core/dot_general:"),
    "x.fusion.9": ("fusion.9", "%fusion.9 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop",
                   "jit(squeeze)/squeeze:"),
}
#: (module name, start, duration); the decode program runs three times
MODULES = [("jit__decode_step(11)", 0, 1000), ("jit__prefill_chunk(22)", 1200, 500),
           ("jit__decode_step(11)", 2000, 1100), ("jit__decode_step(11)", 4000, 1000)]
#: (key into TEXTS, start, duration)
DECODE = [("d.fusion.1", 0, 100), ("d.fusion.2", 100, 300), ("d.dus.1", 400, 200),
          ("d.fusion.3", 600, 200), ("d.fusion.4", 800, 50), ("d.copy-done.1", 850, 50),
          ("d.fusion.5", 900, 100)]
# the second execution's slab read takes 400: everything after it starts 100 later
DECODE_B = [(k, s + (100 if s > 100 else 0), d + (100 if k == "d.fusion.2" else 0))
            for k, s, d in DECODE]
OPS = (DECODE + [("p.fusion.1", 1200, 300), ("p.fusion.2", 1500, 200)]
       + [(k, 2000 + s, d) for k, s, d in DECODE_B] + [("x.fusion.9", 3500, 100)]
       + [(k, 4000 + s, d) for k, s, d in DECODE])
# by hand, one decode execution: table = embed 100 + head 100 = 200; cache = attn_core 300 +
# cache_write 200 = 500 (600 the second time); weights = mlp 200 + qkv_proj/norm 50 = 250;
# unscoped 50; busy 1000 (1100); coverage, over all three: (3100 - 150) / 3100


def _profile(with_names=True):
    keys = sorted(TEXTS)
    ids = {k: i for i, k in enumerate(keys, start=1)}
    mod_ids = {name: 100 + i for i, name in enumerate(sorted({m[0] for m in MODULES}))}
    md = []
    for k in keys:
        name, text, tf_op = TEXTS[k]
        stats = (f' stats {{ metadata_id: 1 str_value: "{tf_op}" }}' if tf_op and with_names else "")
        md.append(f'event_metadata {{ key: {ids[k]} value {{ id: {ids[k]} name: "{text}" '
                  f'display_name: "{name}"{stats} }} }}')
    md += [f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" }} }}'
           for name, i in mod_ids.items()]

    def events(rows):
        return " ".join(f"events {{ metadata_id: {i} offset_ps: {s * 1000} duration_ps: {d * 1000} }}"
                        for i, s, d in rows)

    text = ('planes { name: "/device:TPU:0" '
            'lines { name: "XLA Modules" timestamp_ns: 0 '
            + events((mod_ids[n], s, d) for n, s, d in MODULES) + " } "
            'lines { name: "XLA Ops" timestamp_ns: 0 '
            + events((ids[k], s, d) for k, s, d in OPS) + " } " + " ".join(md)
            + ' stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }')
    from jax.profiler import ProfileData

    return ProfileData.text_proto_to_serialized_xspace(text)


@pytest.fixture(scope="module")
def small_trace(tmp_path_factory):
    out = tmp_path_factory.mktemp("serve_device")
    paths = {}
    for names in (True, False):
        path = out / f"names_{int(names)}.xplane.pb"
        path.write_bytes(_profile(names))
        paths[names] = str(path)
    return paths


def _ctx(path, **kw):
    said = []
    ctx = {"trace": xplane.load(path), "trace_path": path, "say": said.append, "said": said,
           "serve": {"num_slots": 4, "prefill_chunk": 16}, "spans": []}
    ctx.update(kw)
    return ctx


def test_program_names_and_serving_scope_paths():
    assert scoped.program_name("jit__decode_step(9036214310235559293)") == "_decode_step"
    assert scoped.program_name("jit_dynamic_slice(1955245645409990204)") == "dynamic_slice"
    assert scoped.program_name("train_step") == "train_step"
    assert "cache_write" in scoped.SCOPES
    op = "jit(_decode_step)/layer_3/attn/cache_write/dynamic_update_slice:"
    assert scoped.scopes_of(op) == ("layer", "attn", "cache_write")
    assert scoped.scope_path(op) == "layer/attn/cache_write"
    assert scoped.scope_path("jit(_decode_step)/head/norm/mul:") == "head/norm"
    assert scoped.scope_path("") == scoped.scope_path("jit(squeeze)/squeeze:") == "unscoped"
    # a training step's second level is what it was
    assert scoped.second_level("jit(train_step)/jvp(layer_0)/attn/qkv_proj/dot_general:") == \
        "layer/attn"


@pytest.mark.parametrize("op_name,part", [
    ("jit(_decode_step)/embed/convert_element_type:", "table"),
    ("jit(_decode_step)/head/norm/mul:", "table"),
    ("jit(_decode_step)/layer_1/attn/attn_core/dot_general:", "cache"),
    ("jit(_decode_step)/layer_1/attn/cache_write/copy:", "cache"),
    ("jit(_decode_step)/layer_1/attn/qkv_proj/norm/mul:", "weights"),
    ("jit(_decode_step)/layer_1/attn/out_proj/dot_general:", "weights"),
    ("jit(_decode_step)/layer_1/mlp/dot_general:", "weights"),
    ("jit(_decode_step)/layer_1/norm/mul:", "weights"),
    ("jit(_decode_step)/layer_1/add:", "other"),
    ("jit(_decode_step)/sample/sort:", "unscoped"),  # a scope this file does not know yet
    ("", "unscoped"),
])
def test_an_operation_falls_into_one_part(op_name, part):
    assert _decode_device.part_of(op_name) == part


def test_two_programs_of_one_window_are_kept_apart(small_trace):
    ctx = _ctx(small_trace[True])
    execs = scoped.executions(ctx)
    assert [(e.program, e.start, e.end, len(e.ops)) for e in execs] == [
        ("_decode_step", 0.0, 1000.0, 7), ("_prefill_chunk", 1200.0, 1700.0, 2),
        ("_decode_step", 2000.0, 3100.0, 7), ("_decode_step", 4000.0, 5000.0, 7)]
    # the operation between the executions (3500) belongs to none of them
    assert not any(o.name == "fusion.9" for e in execs for o in e.ops)
    # both programs have a ``fusion.1``: each keeps the op_name of its own HLO text,
    # where the reader by instruction name alone knows one of the two
    assert execs[0].ops[0].op_name == D + "embed/convert_element_type:"
    assert execs[1].ops[0].op_name == P + "layer_0/mlp/dot_general:"
    assert scoped.read(small_trace[True])["op_names"]["fusion.1"] in (
        D + "embed/convert_element_type:", P + "layer_0/mlp/dot_general:")
    assert [scoped.busy_ns_of(e) for e in execs] == [1000, 500, 1100, 1000]
    assert scoped.scope_ns(execs[0]) == {
        "embed": 100, "layer/attn/attn_core": 300, "layer/attn/cache_write": 200,
        "layer/mlp": 200, "layer/attn/qkv_proj/norm": 50, "unscoped": 50, "head": 100}
    assert scoped.scope_ns(execs[1]) == {"layer/mlp": 300, "layer/attn/attn_core": 200}
    assert scoped.executions(ctx) is execs  # read once a run
    table = "\n".join(scoped.program_table(execs))
    assert "program _decode_step: 3 executions in the window, busy 0.001 ms each" in table
    assert "program _prefill_chunk: 1 executions" in table
    assert table.index("_decode_step") < table.index("_prefill_chunk")
    assert "layer/attn/cache_write" in table and "unscoped" in table


def test_group_executions_by_hand():
    ops = [scoped.ScopedOp(5, 9, "a", "fusion:kLoop", "jit(f)/embed/x:"),
           scoped.ScopedOp(9, 12, "w", "container", ""),  # spans its body: no work of its own
           scoped.ScopedOp(20, 30, "a", "fusion:kLoop", "jit(g)/head/x:"),
           scoped.ScopedOp(31, 33, "b", "copy", "")]  # after the last module's end
    got = scoped.group_executions([(20.0, 31.0, "jit_g(2)"), (0.0, 15.0, "jit_f(1)")], ops)
    assert [(e.program, [o.name for o in e.ops]) for e in got] == [("f", ["a"]), ("g", ["a"])]
    assert scoped.group_executions([], ops) == []


def test_the_decode_readers_on_the_small_window(small_trace):
    ctx = _ctx(small_trace[True])
    mods = {m.NAME: m for m in harness.discover_metrics(REPO)}
    got = {name: mods[name].compute(ctx) for name in (
        "decode_device_ms_per_step", "decode_cache_ms_per_step", "decode_weights_ms_per_step",
        "decode_table_ms_per_step", "decode_scope_coverage")}
    assert got == {
        "decode_device_ms_per_step": pytest.approx(1000 / 1e6),
        "decode_cache_ms_per_step": pytest.approx(500 / 1e6),
        "decode_weights_ms_per_step": pytest.approx(250 / 1e6),
        "decode_table_ms_per_step": pytest.approx(200 / 1e6),
        "decode_scope_coverage": pytest.approx(100 * 2950 / 3100)}
    said = "\n".join(ctx["said"])
    # every program of the window is printed once, whichever reader came first
    assert said.count("program _decode_step") == said.count("program _prefill_chunk") == 1
    assert "decode step on the device (_decode_step, 3 executions" in said


def test_a_program_without_scope_names_says_why(small_trace):
    """The compile cache returned an executable from before the names: the
    device's time is read, its parts are left out, and the run says why."""
    ctx = _ctx(small_trace[False])
    assert _decode_device.of(ctx, "busy_ms") == pytest.approx(1000 / 1e6)
    assert [_decode_device.of(ctx, k) for k in ("cache_ms", "weights_ms", "table_ms", "coverage")] \
        == [None] * 4
    assert "no operation of _decode_step carries a scope" in "\n".join(ctx["said"])


def test_the_decode_readers_leave_other_contexts_alone(small_trace):
    ctx = _ctx(small_trace[True])
    del ctx["serve"]  # a training cell
    assert _decode_device.step(ctx) is None and _serve_work.window(ctx) is None
    no_trace = {"serve": {}, "trace": None, "say": lambda s: None, "spans": []}
    assert _decode_device.step(no_trace) is None
    # a window without a decode program (its jit name changed): said, and left out
    renamed = [scoped.Execution("_step", 0.0, 1.0, ())]
    ctx = {"serve": {}, "_executions": renamed, "say": (said := []).append}
    assert _decode_device.step(ctx) is None and "'decode' in its jit name" in said[-1]


def test_recorded_decode_and_prefill_executions():
    """One ``_decode_step`` (16 of 16 slots) and one ``_prefill_chunk`` execution of
    ``opt-1.3b_serve_above_knee`` as ``scoped.executions`` read them on the chip's
    trace (recorded_serve_step.json): the reductions on real names."""
    with open(os.path.join(HERE, "recorded_serve_step.json")) as f:
        rec = json.load(f)
    names = rec["op_names"]
    execs = [scoped.Execution(e["program"], e["start"], e["end"], tuple(
        scoped.ScopedOp(s, t, n, c, names[i]) for s, t, n, c, i in e["ops"]))
        for e in rec["executions"]]
    assert [e.program for e in execs] == ["_decode_step", "_prefill_chunk", "dynamic_slice",
                                          "squeeze"]
    dec, pre = execs[0], execs[1]
    want = rec["expect"]
    assert len(dec.ops) == want["decode"]["n"] == 2704
    assert scoped.busy_ns_of(dec) == pytest.approx(want["decode"]["busy_ns"]) == \
        pytest.approx(23851936.0)
    # the step's operations fill its module event: 23.852 of 23.873 ms
    assert 0.999 < scoped.busy_ns_of(dec) / (dec.end - dec.start) <= 1.0
    parts = _decode_device.parts_ms(dec)
    assert parts == pytest.approx(want["decode"]["parts_ms"])
    assert sum(parts.values()) == pytest.approx(scoped.busy_ns_of(dec) / 1e6)  # one core
    # 15.66 + 5.01 + 2.19 + 0.99 unscoped = 23.85 ms; every scoped operation is in a part
    assert (parts["cache"], parts["weights"], parts["table"], parts["other"]) == (
        pytest.approx(15.658, abs=1e-3), pytest.approx(5.014, abs=1e-3),
        pytest.approx(2.187, abs=1e-3), 0.0)
    by_scope = {k: v / 1e6 for k, v in scoped.scope_ns(dec).items()}
    assert by_scope == pytest.approx(want["decode"]["scope_ms"])
    # the cache's reach and layout: 24 layers x (K and V slab reads + one write each)
    assert by_scope["layer/attn/attn_core"] == pytest.approx(8.74, abs=0.01)
    assert by_scope["layer/attn/cache_write"] == pytest.approx(6.92, abs=0.01)
    assert by_scope["layer/attn/attn_core"] + by_scope["layer/attn/cache_write"] == \
        pytest.approx(parts["cache"])
    # the tied table is converted to bf16 twice a step, under ``embed``
    converts = [o for o in dec.ops if o.category == "convert" and "50272" not in o.name
                and scoped.scope_path(o.op_name) == "embed" and o.end - o.start > 5e5]
    assert len(converts) == 2
    # what carries no op_name is mostly the compiler's own waits for prefetched weights
    bare = [o for o in dec.ops if not o.op_name]
    waits = sum(o.end - o.start for o in bare if o.category == "async-done")
    assert waits / sum(o.end - o.start for o in bare) > 0.85
    assert {k: v / 1e6 for k, v in scoped.scope_ns(pre).items()} == \
        pytest.approx(want["prefill"]["scope_ms"])
    assert scoped.busy_ns_of(pre) == pytest.approx(want["prefill"]["busy_ns"])
    # the two programs share instruction names, and a few operations even their whole HLO
    # text (the tied table's conversion): the trace keeps ONE op_name for such a text, so a
    # program is told by its module event, never by the ``jit(...)`` prefix of an op_name;
    # the scope below the prefix is the same in both
    shared = {o.name for o in dec.ops} & {o.name for o in pre.ops}
    assert len(shared) > 100
    own = sum(o.end - o.start for o in dec.ops if o.op_name.startswith("jit(_decode_step)/"))
    other = [o for o in dec.ops if o.op_name.startswith("jit(_prefill_chunk)/")]
    assert own / scoped.busy_ns_of(dec) > 0.9 and other
    assert {scoped.scope_path(o.op_name) for o in other} == {"embed", "layer/attn/qkv_proj"}
    table = "\n".join(scoped.program_table(execs))
    assert "program dynamic_slice: 1 executions" in table and "program squeeze" in table


# ---------------------------------------------------------------------------
# the window's work, and the model's least bytes and FLOPs for it
# ---------------------------------------------------------------------------


class _Req:
    def __init__(self, admitted_at, token_times):
        self.admitted_at, self.token_times = admitted_at, list(token_times)


def _rec(prompt_len, max_new, admitted_at, token_times):
    return {"prompt": [1] * prompt_len, "max_new_tokens": max_new,
            "req": _Req(admitted_at, token_times)}


def test_window_work_is_rebuilt_from_the_requests_own_records():
    records = [
        # admitted before the window; tokens 0-1 before it, 2-4 inside, 5 after: tokens 2, 3, 4
        # are fed at positions 102, 103, 104, attending to 103, 104, 105 live positions
        _rec(100, 8, 5.0, [8.0, 9.0, 10.0, 11.0, 19.0, 21.0]),
        # admitted inside it: 300 prompt tokens in chunks of 256 (ends 256, 300); its last
        # token (k = 2 of 3) is drawn inside the window and fed to no step
        _rec(300, 3, 12.0, [12.5, 13.0, 13.5]),
        # refused, and queued all run: neither was worked on
        {"prompt": [1] * 50, "max_new_tokens": 4, "req": None},
        _rec(50, 4, None, []),
    ]
    assert serve.window_work(records, 10.0, 20.0, 256) == {
        "decode_tokens": 3 + 2, "decode_positions": (103 + 104 + 105) + (301 + 302),
        "prefills": 1, "prefill_chunks": 2, "prefill_tokens": 300,
        "prefill_positions": 256 + 300, "prefill_pairs": 300 * 301 // 2}
    assert serve.window_work([], 0.0, 1.0, 256)["decode_tokens"] == 0


def _opt():
    _, config, _ = harness.load_cell(REPO, "opt-1.3b_serve_above_knee")
    return reference.load(REPO, "opt"), config


def test_opt_1_3b_parameters_and_cache_by_hand():
    arch, cfg = _opt()
    h, f, v, layers = 2048, 8192, 50272, 24
    layer = 4 * h * h + 4 * h + 2 * h * f + f + h + 4 * h  # projections, MLP, biases, two norms
    params = arch.served_params(cfg)
    assert params == {"a_forward": layers * layer + 2 * h + v * h, "a_token": h}
    # with its whole position table the model is the program's 1,315,753,984 parameters:
    # 2.63 GB in bf16
    assert params["a_forward"] + 2048 * h == 1_315_753_984
    assert 2 * (params["a_forward"] + 2048 * h) == pytest.approx(2.63e9, rel=1e-3)
    # K and V of one position: 2 x 24 layers x 32 heads x 64 x 2 bytes
    assert flops.kv_bytes_per_position(arch.serve_dims(cfg)) == 196_608


def test_least_bytes_and_flops_of_served_work_by_hand():
    arch, cfg = _opt()
    fwd, h, v = 1_311_559_680, 2048, 50272
    # one decode step over 16 slots holding 500 live positions each
    step = flops.serve_least_bytes(arch, cfg, forwards=1, tokens=16, positions_read=8000,
                                   rows_out=16)
    assert step == 2 * (fwd + 16 * h + 16 * v) + 196_608 * (8000 + 16)
    assert step == pytest.approx(4.201e9, rel=1e-3)  # 2.62 GB of parameters + 1.58 GB of K and V
    # float32 weights or a cache read to its capacity are the engine's, not the model's
    assert step < 4 * fwd + 16 * 2048 * 196_608
    body = 24 * (4 * h * h + 2 * h * 8192)
    assert flops.serve_fwd_flops(arch, cfg, tokens=16, attn_pairs=8000, rows_out=16) == \
        2.0 * body * 16 + 4.0 * h * 24 * 8000 + 2.0 * h * v * 16
    # a prompt of 300 in two chunks: causal pairs, one sampled position
    assert flops.serve_fwd_flops(arch, cfg, tokens=300, attn_pairs=45150, rows_out=1) == \
        2.0 * body * 300 + 4.0 * h * 24 * 45150 + 2.0 * h * v


PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_three_shares_on_counts_worked_out_by_hand(small_trace):
    """Ten decode steps of 16 tokens at 500 live positions each and two prompts
    (300 and 200 tokens, chunks of 256) in a 2 s window of one chip."""
    arch, cfg = _opt()
    work = {"decode_tokens": 160, "decode_positions": 80_000, "prefills": 2, "prefill_chunks": 3,
            "prefill_tokens": 500, "prefill_positions": 256 + 300 + 200,
            "prefill_pairs": 45150 + 20100}
    spans = [{"name": "decode", "start": i, "end": i + 0.5, "args": {}} for i in range(9)]
    spans += [{"name": "decode_verify", "start": 9, "end": 9.5, "args": {}},
              {"name": "sample", "start": 0, "end": 0.1, "args": {}}]
    ctx = _ctx(small_trace[True], arch=arch, config=cfg, peaks=PEAKS, chips=1, spans=spans)
    ctx["serve"].update(seconds=2.0, work=work)
    fwd, h, v = 1_311_559_680, 2048, 50272
    want_bytes = (2 * (13 * fwd + 660 * h + 162 * v) + 196_608 * (80_000 + 756 + 660))
    body = 24 * (4 * h * h + 2 * h * 8192)
    want_flops = 2.0 * body * 660 + 4.0 * h * 24 * (80_000 + 65_250) + 2.0 * h * v * 162
    got = _serve_work.window(ctx)
    assert (got["bytes"], got["flops"], got["decode_forwards"]) == (want_bytes, want_flops, 10)
    step_bytes = 2 * (fwd + 16 * h + 16 * v) + 196_608 * (8000 + 16)
    assert got["step_bytes"] == pytest.approx(step_bytes)
    mods = {m.NAME: m for m in harness.discover_metrics(REPO)}
    assert mods["serve_hbm_roofline"].compute(ctx) == pytest.approx(
        100 * want_bytes / (2.0 * 819e9))  # 50.2 GB in 2 s: 3.1%
    assert mods["serve_mfu"].compute(ctx) == pytest.approx(100 * want_flops / (2.0 * 197e12))
    # the small trace's decode execution is busy 0.001 ms: the share is a ratio of
    # times, least over measured
    assert mods["decode_step_hbm_roofline"].compute(ctx) == pytest.approx(
        100 * (1e3 * step_bytes / 819e9) / 0.001)
    assert 0 < mods["serve_hbm_roofline"].compute(ctx) < 100
    said = "\n".join(ctx["said"])
    assert said.count("served work of the window") == 1 and "196608 of K and V" in said
    # without the chip's peaks (a CPU run), without a ``decode`` span, or for an
    # architecture whose reference has no served counts: left out, never a 0
    for lack in ({"peaks": None}, {"spans": []}, {"arch": object()}):
        bare = dict(ctx, **lack)
        bare.pop("_serve_work", None)
        assert all(mods[n].compute(bare) is None for n in (
            "serve_hbm_roofline", "serve_mfu", "decode_step_hbm_roofline"))
