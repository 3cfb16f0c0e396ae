"""What PR 61 adds to the benchmark, on the CPU: the trinity-large-preview configuration
against its catalog row and against the flags the program is built from, the decode-heavy
serving mix, the reference module's counts against hand counts, the three new readers on a
hand-made traced window and on a recorded step of another stack, the manifest's appends,
and the whole serving cell at a tiny size through the harness on the new files (with the
gate dropped reading not correct).  No number here is a device number."""

import importlib.util
import json
import os
import shutil
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.lib import flops, harness, reference, scoped, traffic as traffic_lib  # noqa: E402

CELL = "trinity-large-preview_serve_agent_above_knee"
TRAFFIC = "serve_agent_swa_open_above_knee"
#: the serving cells the benchmark had before this PR, in the rate's order
SERVING_BEFORE = ["opt-1.3b_serve_above_knee", "sarvam-105b_serve_long_above_knee",
                  "smallthinker-21b-a3b_serve_long_above_knee",
                  "lfm2-24b-a2b_serve_long_above_knee"]
SOURCE = "https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/config.json"
LAYER_TYPES = ["sliding_attention", "sliding_attention", "sliding_attention",
               "full_attention"] * 15
#: the ``config`` of the catalog row Trinity-Large-Preview (model-configs guide)
CATALOG = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 3072,
    "intermediate_size": 12288, "layer_types": LAYER_TYPES, "load_balance_coeff": 5e-05,
    "max_position_embeddings": 262144, "model_type": "afmoe", "moe_intermediate_size": 3072,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 48, "num_dense_layers": 6,
    "num_expert_groups": 1, "num_experts": 256, "num_experts_per_tok": 4,
    "num_hidden_layers": 60, "num_key_value_heads": 8, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "route_norm": True, "route_scale": 2.448, "score_func": "sigmoid", "sliding_window": 4096,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192,
}
NEW_METRICS = ["serve_experts_touched_share", "serve_expert_hbm_roofline"]
ARCH = reference.load(REPO, "afmoe")
H, EXPERT = 3072, 3 * 3072 * 3072


def _metric(name):
    path = os.path.join(REPO, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("_t_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config():
    return harness.load_cell(REPO, CELL)[1]


# -- the configuration ----------------------------------------------------------------


def test_configuration_is_the_catalog_row_with_depth_experts_and_vocabulary_cut():
    cell, config, _ = harness.load_cell(REPO, CELL)
    assert cell["chips"] == 1 and config["source"] == SOURCE
    assert LAYER_TYPES.count("sliding_attention") == 45
    changed = {k for k, v in CATALOG.items() if config.get(k) != v}
    assert changed == set(config["reduced"]) == set(config["published"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"}
    assert {k: CATALOG[k] for k in changed} == config["published"]
    assert (config["num_hidden_layers"], config["num_dense_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 1, 32, 25024)
    # the guide's floors: a period and four layers behind the dense one, 8 experts, 1/8
    assert config["layer_types"][1:5] == ["sliding_attention", "sliding_attention",
                                          "full_attention", "sliding_attention"]
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"] and config["num_experts"] >= 8
    assert config["expert_share"] == {"rank": 0, "of": 8}
    assert config["num_experts"] * config["expert_share"]["of"] == CATALOG["num_experts"]
    assert {"embedding_multiplier", "attention_gate", "qk_norm", "position_signal",
            "sliding_window", "sandwich_norm", "router", "initializer", "slot_length"} <= set(
        config["assumed"])
    for key in ("deployment", "distorts"):
        assert isinstance(config[key], str) and len(config[key]) > 100


def test_the_program_runs_the_widths_the_file_states():
    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args

    _, config, spec = harness.load_cell(REPO, CELL)
    ns = initialize_galvatron("serve", [*config["program_flags"], *spec["serve_flags"]])
    cfg = model_config_from_args(ns)
    harness.check_widths(cfg, config)
    flags = dict(zip(config["program_flags"][::2], config["program_flags"][1::2]))
    assert flags["--num_layers"] == str(config["num_hidden_layers"])
    assert flags["--moe_dense_layers"] == str(config["num_dense_layers"])
    assert flags["--vocab_size"] == str(config["vocab_size"])
    assert flags["--moe_share"] == "%d/%d" % (config["expert_share"]["rank"],
                                              config["expert_share"]["of"])
    assert (cfg.moe_held, cfg.moe_experts) == (config["num_experts"],
                                               config["published"]["num_experts"])
    assert (cfg.kv_heads, cfg.head_dim, cfg.expert_ffn, cfg.moe_top_k, cfg.sliding_window_size) == (
        config["num_key_value_heads"], config["head_dim"], config["moe_intermediate_size"],
        config["num_experts_per_tok"], config["sliding_window"])
    assert (cfg.moe_route_scale, cfg.norm_eps, cfg.rope_theta) == (
        config["route_scale"], config["rms_norm_eps"], config["rope_theta"])
    assert [t == "sliding_attention" for t in config["layer_types"]] == [
        bool(w) for w in cfg.sliding_window_layout] == [bool(r) for r in cfg.rope_layout]
    assert (ns.num_slots, ns.prefill_chunk) == (32, 1024) and cfg.max_seq_len == 16384


def test_traffic_is_the_mix_the_issue_names():
    cell, _, spec = harness.load_cell(REPO, CELL)
    assert cell["traffic"] == TRAFFIC and spec["kind"] == "serve"
    shapes = traffic_lib.grid(spec)
    prompts = [sh["prompt_len"] for sh in shapes]
    outputs = sorted(sh["output_len"] for sh in shapes)
    # prompt sigma 0.4 and settle_s 20: both steps ISSUE 61 allows, taken as its rule fired
    assert spec["lengths"]["prompt"] == {"median": 3072, "sigma": 0.4, "lo": 768, "hi": 8192}
    assert spec["lengths"]["output"] == {"median": 1024, "sigma": 0.5, "lo": 256, "hi": 3072}
    assert (prompts[0], prompts[-1], outputs[0], outputs[-1]) == (1458, 6472, 403, 2599)
    assert sum(prompts) / 16 == 3305.9375 and traffic_lib.mean_output_len(spec) == 1147.9375
    assert sum(-(-p // 1024) for p in prompts) / 16 == 3.8125  # chunks a prompt
    assert sum(p > 4096 for p in prompts) == 4  # longer than the window at admission
    # half the requests pass 4,096 positions before they end: rings lap during DECODE
    assert sum(sh["prompt_len"] + sh["output_len"] > 4096 for sh in shapes) == 8
    assert max(sh["prompt_len"] + sh["output_len"] for sh in shapes) <= 16000 < 16384
    assert spec["sampling"] == {"temperature": 0.8, "top_p": 0.95, "greedy_every": 4,
                                "greedy_temperature": 0.0001}
    assert spec["corpus"] == {"tokens": 262144, "zipf_a": 1.0, "follow_p": 0.5}
    assert spec["arrivals"]["process"] == "exponential_gap_quantiles"
    assert spec["arrivals"]["burst_at_start"] == 64 and spec["arrivals"]["rate_rps"] > 0
    assert spec["serve_flags"] == ["--num_slots", "32", "--prefill_chunk", "1024",
                                   "--max_queue", "4096", "--request_ttl_s", "0"]
    assert spec["window"]["opens"] == "all_slots_used" and spec["window"]["settle_s"] == 20
    assert spec["window"]["first_token_grace_s"] == 0
    correct = spec["correct"]
    assert (correct["requests"], correct["capture_every"], correct["rows_kept"]) == (4, 5, 16384)
    assert 0 < correct["logits_kl_max"] < 1e-2
    assert len(spec["why"]) <= 2000 and "K32" in spec["why"]
    # the file's rate is twice what the program completes with every slot in use
    k32 = float(spec["why"].split("K32 = ")[1].split(" ")[0].rstrip(":,;"))
    assert spec["arrivals"]["rate_rps"] == pytest.approx(2.0 * k32, rel=0.01)


# -- the reference's counts ----------------------------------------------------------------


def test_served_counts_against_a_hand_count():
    config = _config()
    attn = H * 6144 * 3 + H * 1024 * 2  # W_q, W_g, W_o; W_k, W_v
    served = ARCH.served_params(config)
    layer = attn + 2 * 128 + 4 * H
    expert_layer = H * 256 + 256 + EXPERT * (4 + 1)  # the top-4 of 32 held, the shared one
    assert served["a_forward"] == 5 * layer + 3 * H * 12288 + 4 * expert_layer + H + H * 25024
    assert served["a_token"] == H
    dims = ARCH.serve_dims(config)
    assert (dims["hidden"], dims["heads"], dims["kv_heads"], dims["layers"], dims["vocab"]) == (
        H, 48, 8, 5, 25024)
    assert dims["head_dim"] == pytest.approx(128 * (1 + 4 * 4096 / 16384) / 5)
    body = 5 * (attn) + 3 * H * 12288 + 4 * (H * 256 + EXPERT * (4 / 8 + 1))
    assert dims["ffn"] * H + 4 * H * H == pytest.approx(body / 5)
    assert ARCH.decode_attn_bytes(config, 100, 40, 32, 1, 4) == 4096 * (100 + 4 * 40 + 5 * 32)
    assert ARCH.expert_step_bytes(config, 12.0) == 2 * 12.0 * 4 * EXPERT
    # a forward's flops a token: weights twice, a pair 2 x 2 x 48 x 128
    pairs = (8193 / 2 + 4 * ARCH.window_pairs(8192, 4096) / 8192)
    assert ARCH.fwd_flops_per_token(config, 8192) == pytest.approx(
        2.0 * (body + H * 25024) + 2 * 2.0 * 48 * 128 * pairs)


@pytest.mark.parametrize("n", [1, 2, 1024, 4096, 5000, 16384])
def test_the_stated_bytes_are_a_lower_bound_at_every_length(n):
    config = _config()
    stated = flops.kv_bytes_per_position(ARCH.serve_dims(config)) * n
    assert stated <= ARCH.least_bytes_per_position(config, n) * n + 1e-6
    assert stated == pytest.approx(4096 * 2 * n)


def test_the_three_shares_of_the_chips_peaks_read_under_100():
    """Over a window of 1,000 decode steps (32 slots, 4,500 live positions each) and 60
    chunks of 16 prompts, on the chip's peaks, from this cell's ``serve_dims``: low by
    design (the module's note), never over 100."""
    config = _config()
    work = {"decode_tokens": 32000, "decode_positions": 144_000_000, "prefills": 16,
            "prefill_tokens": 61440, "prefill_chunks": 60, "prefill_positions": 60 * 2048,
            "prefill_pairs": 16 * 3840 * 3841 // 2}
    ctx = {"serve": {"work": work, "seconds": 20.0}, "arch": ARCH, "config": config, "chips": 1,
           "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}, "say": print,
           "spans": [{"name": "decode", "args": {}}] * 1000}
    for name in ("serve_hbm_roofline", "serve_mfu"):
        assert 0 < _metric(name).compute(ctx) < 100, name


# -- the three readers -------------------------------------------------------------------

D, P = "jit(_decode_step)/", "jit(_prefill_chunk)/"


def _op(start, end, op_name):
    return scoped.ScopedOp(float(start), float(end), "fusion.1", "fusion:kLoop", op_name, "")


def _window(decode_ops, prefill_ops, touched=(10.0, 12.0, 14.0)):
    """Two decode executions and one prefill chunk on device 0, and the window's
    ``decode`` spans with the engine's counters (``touched``: one value a span; None:
    the counters of a step of whole rows an expert)."""
    execs = [scoped.Execution("_decode_step", 0.0, 1e6, tuple(decode_ops)),
             scoped.Execution("_prefill_chunk", 2e6, 3e6, tuple(prefill_ops)),
             scoped.Execution("_decode_step", 4e6, 5e6, tuple(decode_ops))]
    said = []
    spans = []
    for t in touched or (None,) * 3:
        args = {"active": 32, "moe_held_pairs_per_token": 0.5, "moe_held_experts": 32}
        if t is not None:
            args["moe_held_experts_touched"] = t
        spans.append({"name": "decode", "start": 0.0, "end": 0.02, "step": None, "args": args})
    return {"serve": {"num_slots": 32}, "spans": spans, "_executions": execs, "say": said.append,
            "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}, "said": said,
            "arch": ARCH, "config": _config()}


GATED_DECODE = [
    _op(0, 100e3, D + "layer_0/attn/window/attn_core/kv_decode:"),
    _op(100e3, 104e3, D + "layer_0/attn/window/gate/mul:"),
    _op(104e3, 150e3, D + "layer_0/attn/window/out_proj/dot_general:"),
    _op(150e3, 153e3, D + "layer_0/attn/post_attn_norm/norm/mul:"),
    _op(153e3, 300e3, D + "layer_0/mlp/dot_general:"),
    _op(300e3, 302e3, D + "layer_0/post_mlp_norm/norm/mul:"),
    _op(302e3, 310e3, D + "layer_3/attn/full/gate/logistic:"),
    _op(310e3, 4310e3, D + "layer_3/mlp/experts/moe_gmm:"),
    _op(4310e3, 4410e3, D + "layer_3/mlp/shared_expert/dot_general:"),
    _op(4410e3, 4411e3, D + "layer_3/post_mlp_norm/norm/mul:"),
]
GATED_PREFILL = [_op(0, 900e3, P + "layer_3/attn/full/gate/mul:")]


def test_metrics_on_a_hand_made_window():
    ctx = _window(GATED_DECODE, GATED_PREFILL)
    assert _metric("serve_experts_touched_share").compute(ctx) == pytest.approx(100 * 12 / 32)
    # 12 of 32 held experts a layer x 4 expert layers x 28.3 M x 2 B at 819 GB/s over 4.0 ms
    least = 2 * 12.0 * 4 * EXPERT
    assert _metric("serve_expert_hbm_roofline").compute(ctx) == pytest.approx(
        100 * (least / 819e9 * 1e3) / 4.0)
    assert _metric("serve_expert_hbm_roofline").compute(ctx) <= 100
    assert any("12.00 of 32 held experts" in line for line in ctx["said"])
    # every held expert touched: the bytes are the held experts', the share at most 100
    full = _window(GATED_DECODE, GATED_PREFILL, touched=(32.0,) * 3)
    assert ARCH.expert_step_bytes(full["config"], 32.0) == 4 * 32 * 2 * EXPERT == 7_247_757_312
    assert _metric("serve_experts_touched_share").compute(full) == 100.0
    # the older readers answer in this window as they do in the older cells
    assert _metric("serve_expert_ms_per_step").compute(ctx) == pytest.approx(4.0)
    assert _metric("window_attn_ms_per_step").compute(ctx) == pytest.approx(0.1)  # attn_core
    assert _metric("serve_moe_held_pairs_per_token").compute(ctx) == 0.5


def test_metrics_read_zero_on_another_stack_and_nothing_without_a_window():
    # a step of 2 rows an expert on another stack: experts, no gate, no such counter
    other = [_op(0, 100e3, D + "layer_1/attn/window/attn_core/dot_general:"),
             _op(100e3, 900e3, D + "layer_1/mlp/experts/moe_gmm:")]
    ctx = _window(other, [_op(0, 100e3, P + "layer_1/attn/full/attn_core/dot_general:")],
                  touched=None)
    for name in NEW_METRICS:
        assert _metric(name).compute(ctx) == 0.0, name
    # a program of this stack's scopes without the arch's byte count (the parent under
    # this PR's files cannot run the cell; a reference without it answers 0)
    ctx = _window(GATED_DECODE, GATED_PREFILL)
    ctx["arch"] = object()
    assert _metric("serve_expert_hbm_roofline").compute(ctx) == 0.0
    for name in NEW_METRICS:
        assert _metric(name).compute({"spans": [], "say": print}) is None
        assert _metric(name).compute({"serve": {}, "spans": [], "trace": None, "say": print,
                                      "_executions": None}) is None


def test_metrics_read_zero_on_the_recorded_serving_step():
    """``recorded_serve_step.json`` is a decode step of opt-1.3b's cell as the chip's
    profiler recorded it: the new readers answer 0 on it."""
    with open(os.path.join(HERE, "recorded_serve_step.json")) as f:
        rec = json.load(f)
    names = rec["op_names"]
    execs = [scoped.Execution(ex["program"], ex["start"], ex["end"], tuple(
        scoped.ScopedOp(a, b, inst, cat, names[i], "") for a, b, inst, cat, i in ex["ops"]))
        for ex in rec["executions"]]
    assert not any("/gate/" in n or "post_attn_norm" in n for n in names)
    ctx = {"serve": {"num_slots": 16}, "spans": [{"name": "decode", "args": {"active": 16}}],
           "say": print, "_executions": execs, "peaks": {"hbm_bytes_per_s": 819e9},
           "arch": ARCH, "config": _config()}
    for name in NEW_METRICS:
        assert _metric(name).compute(ctx) == 0.0, name


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_is_declared_as_a_serving_reader(name):
    manifest = harness.load_manifest(REPO)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    mod = _metric(name)
    assert "workloads" not in entry and entry["moves"] == "serve_tokens_per_s_per_chip"
    assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(
        entry[k] for k in ("name", "unit", "better", "source", "layer", "moves"))
    layers = {m["layer"] for m in manifest["per_layer"] if m["name"] not in NEW_METRICS}
    assert entry["layer"] in layers  # a layer the benchmark already names


def test_the_cell_joins_the_manifest_by_appends():
    manifest = harness.load_manifest(REPO)
    # membership and relative order, no tail positions and no totals: the next PR that
    # appends breaks nothing here
    names = [w["name"] for w in manifest["workloads"]]
    at = names.index(CELL)
    assert names.index("lfm2-24b-a2b_serve_long_above_knee") < at
    cell = manifest["workloads"][at]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and cell["traffic"] == TRAFFIC
    assert 4 * sum(w["chips"] == 4 for w in manifest["workloads"]) <= len(names)
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("lfm2-24b-a2b") < configs.index("trinity-large-preview")
    entry = manifest["configs"][configs.index("trinity-large-preview")]
    assert entry["source"] == SOURCE and len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert sorted(entry["reduced"]) == ["num_dense_layers", "num_experts", "num_hidden_layers",
                                        "vocab_size"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    serving = e2e["serve_tokens_per_s_per_chip"]["workloads"]
    assert serving[:serving.index(CELL)] == SERVING_BEFORE
    assert CELL not in e2e["tokens_per_s_per_chip"]["workloads"]
    readers = [m["name"] for m in manifest["per_layer"]]
    first = readers.index(NEW_METRICS[0])
    assert readers[first:first + len(NEW_METRICS)] == NEW_METRICS
    assert readers.index("shortconv_hbm_roofline") < first  # PR 58's last
    # no other list names the cell: a serving reader names none
    assert not [m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", [])]


# -- what a traced run of this cell can lack ------------------------------------------------
#
# This cell is decode-heavy: one engine iteration in ten runs a prompt chunk, so the 50
# iterations a traced run profiles (``lib/serve.PROFILE_ITERS``) hold no execution of the
# prefill program about one run in three (two of this PR's five traced chip runs held none).
# The three readers of a prompt chunk's DEVICE time then answer None and their metrics are
# left out of the line, though the cell runs their layer all through its window.  They name
# no cell, as every serving reader (test_benchmark_manifest.py::test_metrics), and this PR
# may not edit them or the runner: PERF.md section 7 asks a ``benchmark`` PR for a profile
# that waits for a ``prefill`` span.

CHUNK_READERS = ["mla_prefill_chunk_attn_ms", "kv_prefill_chunk_attn_ms",
                 "shortconv_prefill_chunk_ms"]


@pytest.mark.parametrize("name", CHUNK_READERS)
def test_a_profile_without_a_prompt_chunk_leaves_the_chunk_readers_silent(name):
    manifest = harness.load_manifest(REPO)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert "workloads" not in entry and entry["moves"] == "serve_tokens_per_s_per_chip"
    mod = _metric(name)
    ctx = _window(GATED_DECODE, GATED_PREFILL)
    ctx["_executions"] = [ex for ex in ctx["_executions"] if "prefill" not in ex.program]
    assert mod.compute(ctx) is None
    # with one, in this stack, what the stack's own scopes hold (0 for another's)
    ctx = _window(GATED_DECODE, [_op(0, 700e3, P + "layer_3/attn/full/attn_core/dot_general:")])
    assert mod.compute(ctx) == pytest.approx(0.7 if name.startswith("kv_") else 0.0)


def test_the_older_serving_cells_stand_in_the_manifest_where_they_were():
    """What lfm2's and smallthinker's two cases on the manifest's order hold beside the
    serving readers' lists: the older cells and readers in their relative order, the latent
    cell's three shares of the chip's peaks under 100."""
    manifest = harness.load_manifest(REPO)
    names = [w["name"] for w in manifest["workloads"]]
    order = ["opt-1.3b_serve_above_knee", "qwen3-next-80b-a3b_s4096", SERVING_BEFORE[1],
             SERVING_BEFORE[2], "opt-1.3b_4chip_zero3", SERVING_BEFORE[3], CELL]
    assert [n for n in names if n in order] == order
    readers = [m["name"] for m in manifest["per_layer"]]
    assert (readers.index("mla_attn_ms_per_step") < readers.index("kv_read_over_live")
            < readers.index("shortconv_ms_per_step") < readers.index(NEW_METRICS[0]))
    for cell in SERVING_BEFORE[1:]:
        entry = next(w for w in manifest["workloads"] if w["name"] == cell)
        assert entry["chips"] == 1 and len(entry["why"]) <= 200
        # no list but the rate's names an older cell
        assert not [m["name"] for m in manifest["per_layer"] if cell in m.get("workloads", [])]
    arch = reference.load(REPO, "sarvam_mla")
    config = harness.load_cell(REPO, SERVING_BEFORE[1])[1]
    work = {"decode_tokens": 3200, "decode_positions": 16_000_000, "prefills": 8,
            "prefill_tokens": 40960, "prefill_chunks": 40, "prefill_positions": 40 * 3072,
            "prefill_pairs": 8 * 5120 * 5121 // 2}
    ctx = {"serve": {"work": work, "seconds": 5.6}, "arch": arch, "config": config, "chips": 1,
           "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}, "say": print,
           "spans": [{"name": "decode", "args": {}}] * 100}
    assert 20 < _metric("serve_hbm_roofline").compute(ctx) < 30
    assert 5 < _metric("serve_mfu").compute(ctx) < 12


# -- the whole cell, tiny, on the new files ----------------------------------------------

TINY = {
    "model_type": "afmoe", "hidden_size": 256, "intermediate_size": 96, "head_dim": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_hidden_layers": 5,
    "num_dense_layers": 1, "vocab_size": 2048, "tie_word_embeddings": False,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "max_position_embeddings": 128,
    "sliding_window": 16, "layer_types": LAYER_TYPES, "mup_enabled": True,
    "moe_intermediate_size": 32, "num_experts": 4, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "route_norm": True, "route_scale": 2.448,
    "expert_share": {"rank": 1, "of": 2},
    "program_flags": ["--model_size", "trinity-large-preview", "--num_layers", "5",
                      "--moe_dense_layers", "1", "--hidden_size", "256", "--num_heads", "4",
                      "--num_kv_heads", "2", "--ffn_dim", "96", "--vocab_size", "2048",
                      "--moe_experts", "8", "--moe_share", "1/2", "--seq_length", "128",
                      "--param_dtype", "bf16"],
}
#: the tiny cell's limit: here (CPU, 55-100 compared rows a run) the mean divergence of the
#: engine's softmax from the float32 reference's reads 8e-5 to 4.6e-4 over four seeds (bf16
#: weights and cache against float32; a norm AFTER each block makes every block's part of
#: the residual stream unit-sized, so one flipped expert moves a row as it does not in a
#: pre-norm stack); the gate left out reads 7.7e-3 to 8.4e-3, the post norms 8.8e-2
TINY_KL_MAX = 2e-3


def _tiny_root(tmp_path, monkeypatch):
    from galvatron_tpu.models.modeling import PRESETS

    # (the head, window and expert sizes have no flag: the test narrows the preset)
    monkeypatch.setitem(PRESETS, "trinity-large-preview", PRESETS["trinity-large-preview"].replace(
        attn_head_dim=64, sliding_window_size=16, moe_top_k=2, moe_ffn_dim=32,
        moe_shared_ffn_dim=32, embedding_multiplier=16.0))
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = harness.load_cell(REPO, CELL)[2]
    spec["lengths"] = {"grid": 8, "pair_stride": 3, "max_total": 120,
                       "prompt": {"median": 24, "sigma": 0.7, "lo": 4, "hi": 80},
                       "output": {"median": 16, "sigma": 0.5, "lo": 4, "hi": 40}}
    spec["corpus"]["tokens"] = 4096
    spec["arrivals"].update(rate_rps=150.0, burst_at_start=8)
    spec["serve_flags"] = ["--num_slots", "3", "--prefill_chunk", "16", "--max_queue", "4096",
                           "--request_ttl_s", "0"]
    spec["window"]["settle_s"] = 0.2
    spec["correct"].update(requests=12, capture_every=3, rows_kept=4096,
                           logits_kl_max=TINY_KL_MAX)
    manifest = harness.load_manifest(REPO)
    with open(os.path.join(root, "benchmark/configs/tiny-trinity.json"), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(root, "benchmark/traffic/tiny_agent.json"), "w") as f:
        json.dump(spec, f)
    manifest["configs"].append({"name": "tiny-trinity", "source": "test", "reduced": [],
                                "file": "benchmark/configs/tiny-trinity.json", "why": "test"})
    manifest["workloads"].append({"name": "tiny-trinity_agent", "config": "tiny-trinity",
                                  "traffic": "tiny_agent", "chips": 1, "why": "test"})
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in entry.get("workloads", []):
            entry["workloads"].append("tiny-trinity_agent")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def _run(root, tmp_path, seed, trace=False):
    return harness.run(root, "tiny-trinity_agent", seed=seed, seconds=1.0, trace=trace,
                       out_dir=str(tmp_path / f"run_{seed}_{int(trace)}"), t_start=time.time())


def test_whole_serve_cell_tiny(tmp_path, monkeypatch):
    """The new cell's path through the serve runner at a tiny size: bf16 weights from the
    seed in the program's tree, the engine on the cache of two stacks (four rings of 16 +
    16 places, one layer of slots of 128; prompts of up to 80 and answers of up to 40 lap
    the rings), 3 slots used many times over (6 pairs a step on 8 experts: the touched
    counter rides), the held share of 8 sigmoid-routed experts beside the shared one, the
    open loop, and ``correct`` against the new reference."""
    root = _tiny_root(tmp_path, monkeypatch)
    end = _run(root, tmp_path, 2**31 + 61)
    cmp = end["compared"]
    assert end["correct"] is True, cmp
    assert end["failed"] == 0 and end["attempted"] > 0
    assert set(end["metrics"]) == {"serve_tokens_per_s_per_chip", "setup_s"}
    assert cmp["rows"] > 0 and 0 < cmp["logits_kl"] <= TINY_KL_MAX
    assert cmp["greedy_served"] > 0 and cmp["greedy_not_best"] == 0
    assert cmp["sampled_tokens"] > 0 and cmp["sampled_outside_nucleus"] == 0
    json.dumps(end)

    traced = _run(root, tmp_path, 2**31 + 62, trace=True)
    assert traced["correct"] is True, traced["compared"]
    got = traced["metrics"]
    assert {"decode_step_ms_p50", "prefill_chunk_ms_p50", "engine_iteration_ms_p50",
            "slot_occupancy_share", "itl_p50_ms", "queue_wait_ms_p50"} <= set(got)
    # the program's counters reach their readers; what needs a device trace does not exist here
    assert got["kv_read_over_live"]["value"] >= 1.0
    assert 0.2 < got["serve_moe_held_pairs_per_token"]["value"] <= 2.0
    assert 0 < got["serve_experts_touched_share"]["value"] < 100
    assert "serve_expert_hbm_roofline" not in got


def test_a_dropped_gate_is_not_correct(tmp_path, monkeypatch):
    """The timed path broken underneath: the cached forwards multiply by no gate, the rest
    of the run as it is."""
    import jax

    from galvatron_tpu.models import modeling

    root = _tiny_root(tmp_path, monkeypatch)
    monkeypatch.setattr(modeling, "gate_output", lambda o, gate: o)
    jax.clear_caches()  # (the engine's jitted programs keep the body they were traced with)
    try:
        end = _run(root, tmp_path, 2**31 + 61)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert end["correct"] is False and end["compared"]["checks"]["logits"] is False, end["compared"]
