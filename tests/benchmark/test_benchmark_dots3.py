"""What PR 65 adds to the benchmark, on the CPU: the dots3-note-prev configuration against
its catalog row and against the flags the program is built from, the decode-heavy reasoning
mix, the reference module's counts against hand counts and as lower bounds at every length,
the five new readers on a hand-made traced window, on another stack's and on a recorded
step, the manifest's appends and the three prompt-chunk readers' lists, and the whole
serving cell at a tiny size through the harness on the new files (with the selection
replaced by dense attention reading not correct).  No number here is a device number."""

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

CELL = "dots3-note-prev_serve_reason_above_knee"
TRAFFIC = "serve_reason_dsa_open_above_knee"
#: the serving cells the benchmark had before this PR, in the rate's order
SERVING_BEFORE = ["opt-1.3b_serve_above_knee", "sarvam-105b_serve_long_above_knee",
                  "smallthinker-21b-a3b_serve_long_above_knee",
                  "lfm2-24b-a2b_serve_long_above_knee",
                  "trinity-large-preview_serve_agent_above_knee"]
SOURCE = "https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json"
LAYER_TYPES = ["full_attention"] * 2 + ["sliding_attention", "sliding_attention",
                                        "sliding_attention", "full_attention"] * 11
#: the ``config`` of the catalog row dots3-note-prev (model-configs guide)
CATALOG = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 5120, "index_head_dim": 128, "index_n_heads": 64, "index_topk": 2048,
    "intermediate_size": 13824, "kv_lora_rank": 512, "layer_types": LAYER_TYPES,
    "max_position_embeddings": 524288, "model_type": "dots3_note",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 46, "num_key_value_heads": 128,
    "q_lora_rank": 1024, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 80000000, "routed_scaling_factor": 1,
    "scoring_func": "sigmoid", "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
    "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64, "swa_num_key_value_heads": 64,
    "swa_q_lora_rank": 1024, "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
    "swa_rope_theta": 50000, "swa_v_head_dim": 128, "tie_word_embeddings": False,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 152064,
}
NEW_METRICS = ["dsa_indexer_ms_per_step", "dsa_select_ms_per_step", "dsa_read_over_selected",
               "dsa_attn_hbm_roofline", "latent_ring_read_over_live"]
CHUNK_READERS = ["mla_prefill_chunk_attn_ms", "kv_prefill_chunk_attn_ms",
                 "shortconv_prefill_chunk_ms"]
ARCH = reference.load(REPO, "dots3_note")
H, EXPERT = 5120, 3 * 5120 * 1536
FULL_MIXER = (5120 * 1024 + 1024 * 24576 + 5120 * 576 + 512 * 32768 + 16384 * 5120 + 5120 * 128
              + 1024 * 8192 + 5120 * 128 + 5120 * 64)
WINDOW_MIXER = (5120 * 1024 + 1024 * 16384 + 5120 * 1088 + 1024 * 20480 + 8192 * 5120
                + 5120 * 64)


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
    assert LAYER_TYPES.count("sliding_attention") == 33 and len(LAYER_TYPES) == 46
    changed = {k for k, v in CATALOG.items() if config.get(k) != v}
    assert changed == set(config["reduced"]) == set(config["published"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert {k: CATALOG[k] for k in changed} == config["published"]
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (
        5, 32, 19008)
    entry = next(c for c in harness.load_manifest(REPO)["configs"] if c["name"] == cell["config"])
    assert entry["source"] == SOURCE and sorted(entry["reduced"]) == sorted(changed)
    # the guide's floors: the dense layer and a whole period behind it, 8 experts, 1/8
    assert config["layer_types"][:5] == ["full_attention", "full_attention", "sliding_attention",
                                         "sliding_attention", "sliding_attention"]
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"] and config["n_routed_experts"] >= 8
    assert config["expert_share"] == {"rank": 0, "of": 8}
    assert config["n_routed_experts"] * config["expert_share"]["of"] == CATALOG["n_routed_experts"]
    assert {"apply_mla_qkv_lora_rescale", "rotary_pairing", "indexer", "indexer_layers",
            "attention_gate_type", "sliding_window_size", "router", "left_out"} <= set(
        config["assumed"])
    for key in ("deployment", "distorts", "counts", "program_flags"):
        assert config[key]
    # no width in the reduced keys
    assert not [k for k in config["reduced"] if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]


def test_the_program_runs_the_widths_the_file_states():
    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args
    from galvatron_tpu.models import mla

    _, config, spec = harness.load_cell(REPO, CELL)
    cfg = model_config_from_args(initialize_galvatron(
        "serve", [*config["program_flags"], *spec["serve_flags"]]))
    harness.check_widths(cfg, config)
    assert mla.dims(cfg.layer_view(1)) == tuple(config[k] for k in (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "kv_lora_rank"))
    assert mla.dims(cfg.layer_view(2)) == tuple(config["swa_" + k] for k in (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "kv_lora_rank"))
    assert (cfg.mla_q_rank, cfg.swa_q_rank) == (config["q_lora_rank"], config["swa_q_lora_rank"])
    assert (cfg.mla_index_heads, cfg.mla_index_dim, cfg.mla_index_topk) == (
        config["index_n_heads"], config["index_head_dim"], config["index_topk"])
    assert (cfg.rope_theta, cfg.swa_rope_theta, cfg.sliding_window_size, cfg.norm_eps) == (
        config["rope_theta"], config["swa_rope_theta"], config["sliding_window_size"],
        config["rms_norm_eps"])
    assert (cfg.expert_ffn, cfg.moe_top_k, cfg.moe_experts, cfg.moe_held, cfg.moe_dense_layers) == (
        config["moe_intermediate_size"], config["num_experts_per_tok"],
        config["published"]["n_routed_experts"], config["n_routed_experts"],
        config["first_k_dense_replace"])
    assert list(cfg.window_layers) == [t == "sliding_attention" for t in config["layer_types"][:5]]
    assert ARCH.slot_positions(config) == cfg.max_seq_len == 20480


def test_traffic_is_the_mix_the_issue_names():
    _, _, spec = harness.load_cell(REPO, CELL)
    agent = harness.load_cell(REPO, SERVING_BEFORE[4])[2]
    assert spec["kind"] == "serve" and spec["lengths"]["prompt"] == agent["lengths"]["prompt"]
    # output sigma 0.3: the ONE step ISSUE 65 allows where the sets spread too widely, taken
    # after the driver refused the cell at 0.4 as too noisy (PERF.md 6, refusal round)
    assert spec["lengths"]["output"] == {"median": 4096, "sigma": 0.3, "lo": 1536, "hi": 10240}
    assert (spec["lengths"]["grid"], spec["lengths"]["pair_stride"], spec["lengths"]["max_total"]) == (
        16, 7, 20000)
    assert spec["sampling"] == agent["sampling"] and spec["corpus"] == agent["corpus"]
    assert spec["serve_flags"] == ["--num_slots", "32", "--prefill_chunk", "1024", "--max_queue",
                                   "4096", "--request_ttl_s", "0"]
    assert spec["window"] == {"opens": "all_slots_used", "settle_s": 20, "first_token_grace_s": 0}
    assert spec["arrivals"]["process"] == "exponential_gap_quantiles"
    assert spec["arrivals"]["burst_at_start"] == 64
    shapes = traffic_lib.grid(spec)
    totals = [s["prompt_len"] + s["output_len"] for s in shapes]
    assert max(totals) <= 20000 < 20480 and min(s["output_len"] for s in shapes) >= 1536
    # decode-heavy: a mean answer of 4.3k behind a mean prompt of 3.3k; 14 of 16 requests
    # pass the selection's 2,048 positions at ADMISSION, every one during decode, and 5 of
    # 16 end past 8,192 (the selection keeping a quarter or less)
    mean_out = traffic_lib.mean_output_len(spec)
    assert 4200 < mean_out < 4350 < 1.4 * sum(s["prompt_len"] for s in shapes) / 16
    assert sum(s["prompt_len"] > 2048 for s in shapes) == 14 and min(totals) > 2048
    assert sum(t > 8192 for t in totals) == 5
    # the rate is 2.0 K32, the six readings in the ``why`` (a ``knee`` block is the opt
    # cell's form: tests/benchmark/test_benchmark_replay.py holds one to 3.0 K and a
    # ``judges_up_to``, and a traffic file takes no other block)
    import statistics
    readings = [2400.6, 2371.8, 2356.7, 2359.2, 2309.6, 2330.7]
    assert ", ".join(map(str, readings)) in spec["why"]
    assert statistics.median(readings) == pytest.approx(2358.0, abs=0.06)
    assert spec["arrivals"]["rate_rps"] == pytest.approx(2.0 * 2358.0 / mean_out, rel=5e-3)
    assert "2.0 K32" in spec["why"] and "knee" not in spec and len(spec["why"]) <= 2000
    limits = spec["correct"]
    assert limits["requests"] >= 4 and limits["rows_kept"] >= 4 * 4096
    import math
    assert math.gcd(limits["capture_every"], spec["sampling"]["greedy_every"]) == 1


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 3000000139])
def test_the_tap_runs_through_the_whole_window_under_every_seed(seed):
    """While ANY slot taps, an iteration copies the whole rows buffer to the host
    (`Engine._step_ahead`), so a tap that ended inside the window would change an iteration's
    cost at a moment the seed picks.  It cannot: the first cycle of the grid (every shape
    once, whatever the seed) arrives in the opening burst, is admitted in the first 32 and is
    captured whole, the longest answer with it, and that answer is still being written when
    the window closes, up to an engine 1.15x this one (1.4x at output sigma 0.4)."""
    _, config, spec = harness.load_cell(REPO, CELL)
    shapes = traffic_lib.grid(spec)
    longest = max(s["output_len"] for s in shapes)
    requests = traffic_lib.schedule(seed, spec, config["vocab_size"],
                                    traffic_lib.horizon_s(spec, 51))
    first = requests[:16]
    assert all(r["due_s"] == 0.0 and r["capture"] for r in first)
    assert sorted(r["max_new_tokens"] for r in first) == sorted(s["output_len"] for s in shapes)
    assert sum(r["max_new_tokens"] for r in first) <= spec["correct"]["rows_kept"]
    # the window closes settle_s + 51 s behind the last of the 32 first admissions (6-10 s of
    # prompt chunks: the chip's logs open it 27-30 s into the traffic); a slot writes 1/32 of
    # the engine's 2,358 tokens/s
    close_s = 10.0 + spec["window"]["settle_s"] + 51.0
    assert longest == 7162 and longest > 1.15 * (2358.0 / 32) * close_s


# -- the reference module's counts ------------------------------------------------------


def test_served_counts_against_a_hand_count():
    config = _config()
    assert ARCH.mixer_weights(config, False) == FULL_MIXER == 144_048_128
    assert ARCH.mixer_weights(config, True) == WINDOW_MIXER == 90_832_896
    assert ARCH.mixer_vectors(config, False) == 1024 + 512 + 256
    assert ARCH.mixer_vectors(config, True) == 2048
    served = ARCH.served_params(config)
    expert_layer = H * 256 + 256 + EXPERT * (8 + 1)
    assert served["a_forward"] == (
        2 * (FULL_MIXER + 1792 + 2 * H) + 3 * (WINDOW_MIXER + 2048 + 2 * H)
        + 3 * H * 13824 + 4 * expert_layer + H + H * 19008)
    assert served["a_token"] == H and ARCH.expert_layers(config) == 4
    assert ARCH.expert_step_bytes(config, 20.0) == 2 * 20.0 * 4 * EXPERT
    # a step's sparse attention: every live index key, the selected latents, the writes
    assert ARCH.dsa_step_bytes(config, 300_000, 65_536, 32, 2) == 2 * (
        300_000 * 256 + 65_536 * 1152 + 32 * 1408)
    assert ARCH.position_bytes(config, False) == (1152, 256)
    assert ARCH.position_bytes(config, True) == (2176, 0)
    dims = ARCH.serve_dims(config)
    assert (dims["hidden"], dims["heads"], dims["layers"], dims["vocab"]) == (H, 128, 5, 19008)
    # the formula's bytes a live position are the exact least at a slot's capacity
    assert flops.kv_bytes_per_position(dims) == pytest.approx(
        2 * (256 + 1152 * 2048 / 20480) + 3 * 2176 * 513 / 20480)
    assert ARCH.fwd_flops_per_token(config, 4096) > 2.0 * (
        2 * FULL_MIXER + 3 * WINDOW_MIXER + H * 19008)


@pytest.mark.parametrize("n", [1, 2, 513, 514, 2048, 2049, 9000, 20480])
def test_the_stated_bytes_and_flops_are_a_lower_bound_at_every_length(n):
    """What `serve_dims` hands ``lib/flops.py`` a live position never passes the exact least
    of a row of n positions: the three shares of the chip's peaks are floors."""
    config = _config()
    dims = ARCH.serve_dims(config)
    assert flops.kv_bytes_per_position(dims) <= ARCH.least_bytes_per_position(config, n) + 1e-9
    per_pair = 4.0 * dims["heads"] * dims["head_dim"] * dims["layers"]
    assert per_pair <= ARCH.least_flops_per_pair(config, n) + 1e-6
    # and the exact least is what the stacks hold: index keys always, latents by the rule
    want = 2 * (256 + 1152 * min(n, 2048) / n) + 3 * 2176 * min(n, 513) / n
    assert ARCH.least_bytes_per_position(config, n) == pytest.approx(want)


def test_the_three_shares_of_the_chips_peaks_read_under_100():
    """A window as the cell's would be at a step of 12 ms, in the runner's counts: the three
    accepted shares read this model's floors, each under 100."""
    config = _config()
    steps = 4250
    work = {"decode_tokens": 32 * steps, "decode_positions": 32 * steps * 7000, "prefills": 28,
            "prefill_tokens": 28 * 3300, "prefill_chunks": 28 * 4, "prefill_positions": 28 * 9000,
            "prefill_pairs": 28 * 3300 * 3301 // 2}
    ctx = {"serve": {"work": work, "seconds": 51.0}, "arch": ARCH, "config": config, "chips": 1,
           "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}, "say": print,
           "spans": [{"name": "decode", "args": {}}] * steps}
    assert 20 < _metric("serve_hbm_roofline").compute(ctx) < 100
    assert 0 < _metric("serve_mfu").compute(ctx) < 100


# -- the readers on hand-made traces ------------------------------------------------------

D, P = "jit(_decode_step)/", "jit(_prefill_chunk)/"


def _op(start, end, op_name):
    return scoped.ScopedOp(float(start), float(end), "fusion.1", "fusion:kLoop", op_name, "")


#: what the engine's `step_counters` notes on a ``decode`` span of this stack (32 rows of
#: 9,000 positions: 2,048 selected a row; the plain bodies read every row's slot up to the
#: longest row's end in blocks of 1,024: 32 x 9,216)
DSA_ARGS = {"active": 32, "dsa_live_positions": 288_000, "dsa_selected_positions": 65_536,
            "dsa_read_positions": 294_912, "dsa_index_read_positions": 294_912,
            "latent_ring_live_positions": 16_416, "latent_ring_read_positions": 65_536,
            "dsa_full_layers": 2, "latent_ring_layers": 3, "dsa_latent_bytes_per_position": 1152,
            "dsa_index_bytes_per_position": 256, "latent_ring_bytes_per_position": 2176,
            "moe_held_experts": 32, "moe_held_experts_touched": 10.0,
            "moe_held_pairs_per_token": 1.0}


def _window(decode_ops, prefill_ops, args=DSA_ARGS, arch=ARCH):
    """Two decode executions and one prefill chunk on device 0, and three ``decode`` spans."""
    execs = [scoped.Execution("_decode_step", 0.0, 1e6, tuple(decode_ops)),
             scoped.Execution("_prefill_chunk", 2e6, 3e6, tuple(prefill_ops)),
             scoped.Execution("_decode_step", 4e6, 5e6, tuple(decode_ops))]
    said = []
    spans = [{"name": "decode", "start": 0.0, "end": 0.02, "step": None, "args": dict(args)}
             for _ in range(3)]
    return {"serve": {"num_slots": 32}, "spans": spans, "_executions": execs, "say": said.append,
            "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}, "said": said,
            "arch": arch, "config": _config()}


DSA_DECODE = [
    _op(0, 100e3, D + "layer_1/attn/full/qkv_proj/dot_general:"),
    _op(100e3, 150e3, D + "layer_1/attn/full/indexer/dot_general:"),
    _op(150e3, 400e3, D + "layer_1/attn/full/indexer/while/body/dot_general:"),
    _op(400e3, 900e3, D + "layer_1/attn/full/select/top_k:"),
    _op(900e3, 1000e3, D + "layer_1/attn/full/attn_core/while/body/dot_general:"),
    _op(1000e3, 1100e3, D + "layer_1/attn/full/attn_core/absorb/dot_general:"),
    _op(1100e3, 1110e3, D + "layer_1/attn/full/gate/mul:"),
    _op(1110e3, 1200e3, D + "layer_1/attn/full/out_proj/dot_general:"),
    _op(1200e3, 1500e3, D + "layer_2/attn/window/attn_core/dot_general:"),
    _op(1500e3, 4500e3, D + "layer_2/mlp/experts/moe_gmm:"),
]
DSA_PREFILL = [_op(0, 900e3, P + "layer_1/attn/full/select/top_k:")]


def test_metrics_on_a_hand_made_window():
    ctx = _window(DSA_DECODE, DSA_PREFILL)
    assert _metric("dsa_indexer_ms_per_step").compute(ctx) == pytest.approx(0.3)
    assert _metric("dsa_select_ms_per_step").compute(ctx) == pytest.approx(0.5)
    assert _metric("dsa_read_over_selected").compute(ctx) == pytest.approx(4.5)
    assert _metric("latent_ring_read_over_live").compute(ctx) == pytest.approx(65_536 / 16_416)
    # 288,000 live index keys x 256 B + 65,536 latents x 1,152 B + 32 x 1,408 B, two layers,
    # at 819 GB/s over the 1.0 ms under indexer + select + attn_core
    least = 2 * (288_000 * 256 + 65_536 * 1152 + 32 * 1408)
    got = _metric("dsa_attn_hbm_roofline").compute(ctx)
    assert got == pytest.approx(100 * (least / 819e9 * 1e3) / 1.0) and 0 < got <= 100
    assert any("288000 live index keys + 65536 selected latents" in line for line in ctx["said"])
    # the accepted readers answer in this window as the issue says: the two stacks' time
    # under the windowed stacks' names, a dense latent's and a K/V ring's rooflines 0
    assert _metric("full_attn_ms_per_step").compute(ctx) == pytest.approx(0.2)  # (attn_core)
    assert _metric("window_attn_ms_per_step").compute(ctx) == pytest.approx(0.3)
    assert _metric("mla_decode_attn_roofline").compute(ctx) == 0.0
    assert _metric("kv_decode_attn_roofline").compute(ctx) == 0.0
    assert _metric("kv_read_over_live").compute(ctx) == 0.0
    assert _metric("serve_expert_ms_per_step").compute(ctx) == pytest.approx(3.0)
    assert 0 < _metric("serve_expert_hbm_roofline").compute(ctx) <= 100
    # a program that fetched the selected latents alone would read 1.0, one that read every
    # slot's capacity 10.0
    for read, want in ((65_536, 1.0), (655_360, 10.0)):
        args = dict(DSA_ARGS, dsa_read_positions=read)
        assert _metric("dsa_read_over_selected").compute(
            _window(DSA_DECODE, DSA_PREFILL, args)) == want


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_profile_without_a_prompt_chunk_leaves_no_new_reader_out(name):
    """The new cell is decode-heavy: a profile of 50 decode iterations often holds no
    prompt chunk.  Every new reader reads the decode program or the ``decode`` spans only."""
    ctx = _window(DSA_DECODE, DSA_PREFILL)
    want = _metric(name).compute(ctx)
    ctx = _window(DSA_DECODE, DSA_PREFILL)
    ctx["_executions"] = [ex for ex in ctx["_executions"] if "prefill" not in ex.program]
    assert _metric(name).compute(ctx) == want and want > 0


def test_metrics_read_zero_on_another_stack_and_nothing_without_a_window():
    # a windowed K/V stack's step: ``full`` and ``window`` scopes, no indexer, no such counter
    other = [_op(0, 100e3, D + "layer_1/attn/full/attn_core/kv_decode:"),
             _op(100e3, 900e3, D + "layer_1/mlp/experts/moe_gmm:")]
    args = {"active": 32, "kv_full_live_positions": 1000, "moe_held_experts": 32}
    ctx = _window(other, [_op(0, 100e3, P + "layer_1/attn/full/attn_core/dot_general:")], args)
    for name in NEW_METRICS:
        assert _metric(name).compute(ctx) == 0.0, name
    # a program of this stack's scopes without the arch's byte count (a reference that
    # lacks it: the parent's files under this PR's readers) answers 0
    ctx = _window(DSA_DECODE, DSA_PREFILL, arch=object())
    assert _metric("dsa_attn_hbm_roofline").compute(ctx) == 0.0
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
    assert not any("/indexer/" in n or "/select/" in n for n in names)
    ctx = {"serve": {"num_slots": 16}, "spans": [{"name": "decode", "args": {"active": 16}}],
           "say": print, "_executions": execs, "peaks": {"hbm_bytes_per_s": 819e9},
           "arch": ARCH, "config": _config()}
    for name in NEW_METRICS:
        assert _metric(name).compute(ctx) == 0.0, name


# -- the manifest ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_is_declared_as_a_serving_reader(name):
    manifest = harness.load_manifest(REPO)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    mod = _metric(name)
    assert "workloads" not in entry and entry["moves"] == "serve_tokens_per_s_per_chip"
    assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(
        entry[k] for k in ("name", "unit", "better", "source", "layer", "moves"))
    layers = {m["layer"] for m in manifest["per_layer"] if m["name"] not in NEW_METRICS}
    assert entry["layer"] in layers  # a layer PERF.md section 3 already names
    assert (entry["unit"] == "%") == name.endswith("_roofline")


@pytest.mark.parametrize("name", CHUNK_READERS)
def test_a_prompt_chunk_reader_lists_the_five_accepted_serving_cells(name):
    """The driver's rule (ISSUE 65): a reader that finds nothing in a decode-heavy cell's
    profile carries the list of the ACCEPTED cells that report the end-to-end metric it
    moves, without the new cell; these three and no other serving reader carry one."""
    manifest = harness.load_manifest(REPO)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert entry["workloads"] == SERVING_BEFORE and CELL not in entry["workloads"]
    assert entry["moves"] == "serve_tokens_per_s_per_chip"
    listed = sorted(m["name"] for m in manifest["per_layer"]
                    if m["moves"] == "serve_tokens_per_s_per_chip" and "workloads" in m)
    assert listed == sorted(CHUNK_READERS)
    # so a traced run of the new cell is not asked for it, whatever its profile holds
    rate = next(m for m in manifest["end_to_end"] if m["name"] == entry["moves"])
    assert not all(CELL in e.get("workloads", [CELL]) for e in (entry, rate))
    for cell in SERVING_BEFORE:
        assert all(cell in e.get("workloads", [cell]) for e in (entry, rate))


@pytest.mark.parametrize("name", CHUNK_READERS)
def test_a_profile_without_a_prompt_chunk_leaves_the_chunk_readers_silent(name):
    """The trinity file's three cases of this name over THIS stack's window (their whole
    bodies over their own window: test_benchmark_chunk_lists.py): without a prompt chunk in
    the profile a chunk reader answers None; with one, what the stack's own scopes hold."""
    mod = _metric(name)
    ctx = _window(DSA_DECODE, DSA_PREFILL)
    ctx["_executions"] = [ex for ex in ctx["_executions"] if "prefill" not in ex.program]
    assert mod.compute(ctx) is None
    ctx = _window(DSA_DECODE, [_op(0, 700e3, P + "layer_1/attn/full/attn_core/dot_general:")])
    assert mod.compute(ctx) == pytest.approx(0.7 if name.startswith("kv_") else 0.0)


def test_the_older_cells_and_readers_stand_where_they_were():
    """The trinity file's case on the manifest's order with THIS cell behind the others
    (the eleven cases tests/conftest.py marks stand whole in test_benchmark_chunk_lists.py):
    the older cells and readers in their relative order, one chip and a one-line why each,
    the latent cell's shares of the chip's peaks under 100."""
    manifest = harness.load_manifest(REPO)
    names = [w["name"] for w in manifest["workloads"]]
    order = ["opt-1.3b_serve_above_knee", "qwen3-next-80b-a3b_s4096", SERVING_BEFORE[1],
             SERVING_BEFORE[2], "opt-1.3b_4chip_zero3", SERVING_BEFORE[3], SERVING_BEFORE[4], CELL]
    assert [n for n in names if n in order] == order
    readers = [m["name"] for m in manifest["per_layer"]]
    assert (readers.index("mla_attn_ms_per_step") < readers.index("kv_read_over_live")
            < readers.index("shortconv_ms_per_step") < readers.index("shortconv_hbm_roofline")
            < readers.index("serve_experts_touched_share") < readers.index(NEW_METRICS[0]))
    for cell in SERVING_BEFORE[1:]:
        entry = next(w for w in manifest["workloads"] if w["name"] == cell)
        assert entry["chips"] == 1 and len(entry["why"]) <= 200
        # no list but the rate's and the three chunk readers' names an older cell
        assert sorted(m["name"] for m in manifest["per_layer"]
                      if cell in m.get("workloads", [])) == sorted(CHUNK_READERS)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert all(c not in e2e["tokens_per_s_per_chip"]["workloads"] for c in SERVING_BEFORE)
    for name in CHUNK_READERS + ["mla_attn_ms_per_step", "kv_read_over_live",
                                 "shortconv_ms_per_step"]:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        mod = _metric(name)
        assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(
            entry[k] for k in ("name", "unit", "better", "source", "layer", "moves"))
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


def test_the_cell_joins_the_manifest_by_appends():
    manifest = harness.load_manifest(REPO)
    names = [w["name"] for w in manifest["workloads"]]
    assert names[-1] == CELL and len(names) == 13
    assert [n for n in names if n in SERVING_BEFORE] == SERVING_BEFORE
    cell = manifest["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("dots3-note-prev", TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and len(manifest["configs"][-1]["why"]) <= 200
    assert manifest["configs"][-1]["name"] == "dots3-note-prev" and len(manifest["configs"]) == 10
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2  # 2 of 13: no more
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["serve_tokens_per_s_per_chip"]["workloads"] == SERVING_BEFORE + [CELL]
    assert CELL not in e2e["tokens_per_s_per_chip"]["workloads"]
    assert "workloads" not in e2e["setup_s"] and manifest["run_seconds"] == 51
    per = [m["name"] for m in manifest["per_layer"]]
    assert per[-5:] == NEW_METRICS and len(per) == 88
    # no list of a per-layer metric names the new cell: its readers are the unlisted ones
    assert not [m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", [])]
    # a full check fits the driver's budget at one more cell
    cells = len(names)
    assert (2 + 14 * cells) * (manifest["run_seconds"] + 60) + 2 * 90 * cells + 1200 <= 43200


# -- the whole cell, tiny, on the new files ----------------------------------------------

TINY = {
    "model_type": "dots3_note", "hidden_size": 256, "intermediate_size": 96,
    "num_attention_heads": 4, "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "vocab_size": 2048, "tie_word_embeddings": False, "rms_norm_eps": 1e-05,
    "layer_types": LAYER_TYPES, "apply_mla_qkv_lora_rescale": True,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32, "kv_lora_rank": 64,
    "q_lora_rank": 96, "rope_theta": 80000000,
    "swa_num_attention_heads": 2, "swa_qk_nope_head_dim": 48, "swa_qk_rope_head_dim": 16,
    "swa_v_head_dim": 32, "swa_kv_lora_rank": 96, "swa_q_lora_rank": 64, "swa_rope_theta": 50000,
    "sliding_window_size": 17, "index_n_heads": 4, "index_head_dim": 32, "index_topk": 8,
    "moe_intermediate_size": 32, "n_routed_experts": 4, "num_experts_per_tok": 2,
    "n_shared_experts": 1, "routed_scaling_factor": 1, "expert_share": {"rank": 1, "of": 2},
    "program_flags": ["--model_size", "dots3-note-prev", "--num_layers", "5",
                      "--moe_dense_layers", "1", "--hidden_size", "256", "--num_heads", "4",
                      "--ffn_dim", "96", "--vocab_size", "2048", "--moe_experts", "8",
                      "--moe_share", "1/2", "--seq_length", "128", "--param_dtype", "bf16"],
}
#: the tiny cell's limit: here (CPU, bf16 weights and cache against float32, a few hundred
#: compared rows a run) the sound program's divergence reads a few 1e-4; with dense
#: attention in place of the 8 selected keys (fewer than the shortest request's 12 positions, so
#: every compared request has rows it moves) it reads several times the limit
TINY_KL_MAX = 2e-3


def _tiny_root(tmp_path, monkeypatch):
    from galvatron_tpu.models.modeling import PRESETS

    # (the latent, index and expert sizes have no flag: the test narrows the preset)
    monkeypatch.setitem(PRESETS, "dots3-note-prev", PRESETS["dots3-note-prev"].replace(
        attn_head_dim=48, mla_nope_dim=32, mla_rope_dim=16, mla_v_dim=32, mla_kv_rank=64,
        mla_q_rank=96, mla_index_heads=4, mla_index_dim=32, mla_index_topk=8,
        sliding_window_size=17, swa_num_heads=2, swa_nope_dim=48, swa_rope_dim=16, swa_v_dim=32,
        swa_kv_rank=96, swa_q_rank=64, moe_top_k=2, moe_ffn_dim=32, moe_shared_ffn_dim=32))
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = harness.load_cell(REPO, CELL)[2]
    spec["lengths"] = {"grid": 8, "pair_stride": 3, "max_total": 120,
                       "prompt": {"median": 20, "sigma": 0.7, "lo": 4, "hi": 64},
                       "output": {"median": 32, "sigma": 0.4, "lo": 8, "hi": 72}}
    spec["corpus"]["tokens"] = 4096
    spec["arrivals"].update(rate_rps=150.0, burst_at_start=8)
    spec["serve_flags"] = ["--num_slots", "3", "--prefill_chunk", "16", "--max_queue", "4096",
                           "--request_ttl_s", "0"]
    spec["window"]["settle_s"] = 0.2
    spec["correct"].update(requests=12, capture_every=3, rows_kept=4096,
                           logits_kl_max=TINY_KL_MAX)
    manifest = harness.load_manifest(REPO)
    with open(os.path.join(root, "benchmark/configs/tiny-dots3.json"), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(root, "benchmark/traffic/tiny_reason.json"), "w") as f:
        json.dump(spec, f)
    manifest["configs"].append({"name": "tiny-dots3", "source": "test", "reduced": [],
                                "file": "benchmark/configs/tiny-dots3.json", "why": "test"})
    manifest["workloads"].append({"name": "tiny-dots3_reason", "config": "tiny-dots3",
                                  "traffic": "tiny_reason", "chips": 1, "why": "test"})
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in entry.get("workloads", []):
            entry["workloads"].append("tiny-dots3_reason")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def _run(root, tmp_path, seed, trace=False):
    return harness.run(root, "tiny-dots3_reason", seed=seed, seconds=1.5, trace=trace,
                       out_dir=str(tmp_path / f"run_{seed}_{int(trace)}"), t_start=time.time())


def test_whole_serve_cell_tiny(tmp_path, monkeypatch):
    """The new cell's path through the serve runner at a tiny size: bf16 weights from the
    seed in the program's tree, the engine on the cache of three stacks (two layers of slots
    of 128 with their index keys, three rings of 32 places; prompts of up to 64 and answers
    of up to 72 pass the 8 selected keys and lap the rings), 3 slots used many times over,
    the held share of 8 sigmoid-routed experts beside the shared one, the open loop, and
    ``correct`` against the new reference."""
    root = _tiny_root(tmp_path, monkeypatch)
    end = _run(root, tmp_path, 2**31 + 65)
    cmp = end["compared"]
    assert end["correct"] is True, cmp
    assert end["failed"] == 0 and end["attempted"] > 0
    assert set(end["metrics"]) == {"serve_tokens_per_s_per_chip", "setup_s"}
    assert cmp["rows"] > 0 and 0 < cmp["logits_kl"] <= TINY_KL_MAX
    assert cmp["greedy_served"] > 0 and cmp["greedy_not_best"] == 0
    assert cmp["sampled_tokens"] > 0 and cmp["sampled_outside_nucleus"] == 0
    json.dumps(end)

    traced = _run(root, tmp_path, 2**31 + 66, trace=True)
    assert traced["correct"] is True, traced["compared"]
    got = traced["metrics"]
    assert {"decode_step_ms_p50", "prefill_chunk_ms_p50", "engine_iteration_ms_p50",
            "slot_occupancy_share", "itl_p50_ms", "queue_wait_ms_p50"} <= set(got)
    # the program's counters reach their readers; what needs a device trace does not exist here
    assert got["dsa_read_over_selected"]["value"] >= 1.0
    assert got["latent_ring_read_over_live"]["value"] >= 1.0
    assert got["kv_read_over_live"]["value"] == 0.0
    assert 0.2 < got["serve_moe_held_pairs_per_token"]["value"] <= 2.0
    assert "dsa_attn_hbm_roofline" not in got and "mla_prefill_chunk_attn_ms" not in got


def test_dense_attention_in_place_of_the_selection_is_not_correct(tmp_path, monkeypatch):
    """The timed path broken underneath: the cached forwards attend EVERY key at or before
    the query, the rest of the run as it is."""
    import jax
    import jax.numpy as jnp

    from galvatron_tpu.models import mla

    root = _tiny_root(tmp_path, monkeypatch)
    monkeypatch.setattr(mla, "select_mask", lambda scores, topk: scores > -jnp.inf)
    jax.clear_caches()  # (the engine's jitted programs keep the body they were traced with)
    try:
        end = _run(root, tmp_path, 2**31 + 65)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert end["correct"] is False and end["compared"]["checks"]["logits"] is False, end["compared"]
