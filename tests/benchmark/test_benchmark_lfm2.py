"""What PR 58 adds to the benchmark, on the CPU: the lfm2-24b-a2b configuration against
its catalog row, the serving mix, the reference module's counts against hand counts and
against the tree the program builds at the cut, the four new readers on a hand-made
traced window and on a recorded step of another stack, the manifest's appends, and the
whole serving cell at a tiny size through the harness on the new files (with a state
that is not carried reading not correct).  No number here is a device number."""

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

CELL = "lfm2-24b-a2b_serve_long_above_knee"
SARVAM_CELL = "sarvam-105b_serve_long_above_knee"
SWA_CELL = "smallthinker-21b-a3b_serve_long_above_knee"
OPT_SERVE = "opt-1.3b_serve_above_knee"
#: the cells the benchmark had before this PR, in its order
ACCEPTED_CELLS = ["baichuan-7b_s4096", "baichuan-7b_s512", "opt-1.3b_4chip_searched",
                  "olmoe-1b-7b_s4096", "granite-4.0-h-micro_s8192", OPT_SERVE,
                  "qwen3-next-80b-a3b_s4096", SARVAM_CELL, SWA_CELL, "opt-1.3b_4chip_zero3"]
SOURCE = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
LAYER_TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9 + [
    "full_attention", "conv"]
#: the ``config`` of the catalog row LFM2-24B-A2B (model-configs guide)
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
    "layer_types": LAYER_TYPES, "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
NEW_METRICS = ["shortconv_ms_per_step", "shortconv_prefill_chunk_ms", "state_cache_ms_per_step",
               "shortconv_hbm_roofline"]
KV_METRICS = ["full_attn_ms_per_step", "kv_prefill_chunk_attn_ms", "kv_decode_attn_roofline",
              "kv_read_over_live"]
ARCH = reference.load(REPO, "lfm2_moe")


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
    assert len(LAYER_TYPES) == 40 and LAYER_TYPES.count("conv") == 30
    changed = {k for k, v in CATALOG.items() if config.get(k) != v}
    assert changed == set(config["reduced"]) == set(config["published"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert {k: CATALOG[k] for k in changed} == config["published"]
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (
        22, 16, 16384)
    assert config["expert_share"] == {"rank": 0, "of": 4}
    assert config["vocab_size"] * 4 == CATALOG["vocab_size"]
    assert config["tie_word_embeddings"] is True  # not a published key: under ``assumed``
    assert {"tie_word_embeddings", "embedding_norm", "norm_topk_eps", "expert_bias",
            "rotary_pairing", "conv_state", "conv_order", "initializer", "slot_length"} <= set(
                config["assumed"])
    assert "4 chips share each layer" in config["deployment"]
    assert "one stage" in config["distorts"] and "2 rows an expert" in config["distorts"]
    # the two leading dense layers and five whole periods A C C C
    taken = config["layer_types"][:22]
    assert taken[:2] == ["conv", "conv"] and taken[2:] == ["full_attention", "conv", "conv", "conv"] * 5


def test_the_program_runs_the_widths_the_file_states():
    import jax.numpy as jnp

    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args

    config = _config()
    cfg = model_config_from_args(initialize_galvatron("serve", list(config["program_flags"])))
    harness.check_widths(cfg, config)
    assert (cfg.kv_heads, cfg.head_dim, cfg.expert_ffn, cfg.moe_top_k) == (
        config["num_key_value_heads"], 64, config["moe_intermediate_size"],
        config["num_experts_per_tok"])
    assert cfg.moe_held == config["num_experts"] and cfg.moe_experts == 64
    assert cfg.moe_dense_layers == config["num_dense_layers"]
    assert cfg.shortconv_taps == config["conv_L_cache"] and cfg.max_seq_len == 16384
    assert ["conv" if k == "shortconv" else "full_attention" for k in cfg.layer_kinds] == config[
        "layer_types"]
    assert cfg.rope_theta == config["rope_parameters"]["rope_theta"]
    assert cfg.norm_eps == config["norm_eps"] and cfg.moe_route_scale == 1
    assert cfg.param_dtype == jnp.bfloat16 and cfg.moe_router == "sigmoid_topk"


def test_traffic_is_the_mix_the_issue_names():
    _, config, spec = harness.load_cell(REPO, CELL)
    # the grid of the other long serving cells: the three differ by architecture, not by mix
    for name in (SARVAM_CELL, SWA_CELL):
        other = harness.load_cell(REPO, name)[2]
        for key in ("lengths", "sampling", "corpus", "serve_flags", "window"):
            assert spec[key] == other[key], (name, key)
    assert spec["arrivals"]["burst_at_start"] == 64 and "knee" not in spec
    assert spec["correct"]["requests"] == 4 and spec["correct"]["capture_every"] == 5
    assert spec["correct"]["rows_kept"] == 4096
    assert traffic_lib.mean_output_len(spec) == pytest.approx(300.625)
    shapes = traffic_lib.grid(spec)
    assert max(s["prompt_len"] + s["output_len"] for s in shapes) <= 16000
    # every prompt is more than a chunk: the conv state crosses a chunk's end in each
    flags = dict(zip(spec["serve_flags"][::2], spec["serve_flags"][1::2]))
    chunk = int(flags["--prefill_chunk"])
    assert min(s["prompt_len"] for s in shapes) > chunk and 16384 % chunk == 0
    assert "2.0" in spec["why"] and "K32" in spec["why"]


# -- the counts ---------------------------------------------------------------------------

H, F_DENSE, F_EXPERT = 2048, 11776, 1536
ATTN = H * (32 + 16) * 64 + 32 * 64 * H
CONV = H * 3 * H + H * H


def test_flop_count_against_a_hand_count():
    config = _config()
    routed = H * 64 + 3 * H * F_EXPERT * 4 / 4
    s = 8192
    body = 5 * ATTN + 17 * CONV + 2 * 3 * H * F_DENSE + 20 * routed
    want = (2.0 * (body + H * 16384) + 2.0 * 17 * H * 5
            + 2 * 2.0 * 32 * 64 * 5 * (s + 1) / 2)
    assert ARCH.fwd_flops_per_token(config, s) == pytest.approx(want)


def test_served_counts_against_a_hand_count():
    config = _config()
    dims = ARCH.serve_dims(config)
    assert ARCH.position_share(config) == pytest.approx(5 / 22)
    assert dims["head_dim"] == pytest.approx(64 * 5 / 22) and dims["kv_heads"] == 8
    # K and V of a live position: the five attention layers' 2,048 B each, exactly
    assert flops.kv_bytes_per_position(dims) == pytest.approx(5 * 2048)
    assert ARCH.least_bytes_per_position(config) == 5 * 2048
    body = flops.matmul_params(hidden=dims["hidden"], heads=dims["heads"], ffn=dims["ffn"],
                               mlp_matrices=dims["mlp_matrices"], layers=dims["layers"], vocab=0)
    routed = H * 64 + 3 * H * F_EXPERT * 1.0
    assert body == pytest.approx(5 * ATTN + 17 * CONV + 2 * 3 * H * F_DENSE + 20 * routed)
    # a (query, live position) pair: 2 x 2 x 32 x 64 in each of the five attention layers
    assert 2 * 2.0 * dims["heads"] * dims["head_dim"] * dims["layers"] == pytest.approx(
        2 * 2 * 32 * 64 * 5)
    served = ARCH.served_params(config)
    expert_layer = H * 64 + 64 + 16 * 3 * H * F_EXPERT
    assert served["a_forward"] == (
        5 * (ATTN + 128) + 17 * (CONV + 3 * H) + 22 * 2 * H + 2 * 3 * H * F_DENSE
        + 20 * expert_layer + H + H * 16384)
    assert served["a_token"] == H
    # the ISSUE's arithmetic: 3.54 B parameters, 7.08 GB in bf16
    assert 2 * served["a_forward"] == pytest.approx(7.08e9, rel=0.005)


def test_the_counts_are_the_tree_the_program_builds_at_the_cut():
    import jax

    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args
    from galvatron_tpu.models import generation, modeling

    config = _config()
    cfg = model_config_from_args(initialize_galvatron("serve", list(config["program_flags"])))
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    total = sum(a.size for a in jax.tree.leaves(shapes))
    # (tied: the head IS the embedding, counted once, as the parameters of a forward)
    assert ARCH.served_params(config)["a_forward"] == total
    weights = ARCH.published_weights(shapes, config)
    assert sum("conv" in lw for lw in weights["layers"]) == 17
    assert sum("gate" in lw["feed_forward"] for lw in weights["layers"]) == 20
    # the slot cache the engine builds: what the counts call a live position and a state
    layout = generation.cache_layout(cfg, 16384, 1024)
    assert layout["full_layers"] * layout["bytes_per_position_per_layer"] == (
        ARCH.least_bytes_per_position(config))
    assert layout["state_layers"] == 17 and layout["state_bytes_per_row"] == 2 * 2 * H
    assert ARCH.shortconv_step_bytes(config, 32, 17) == 2 * 17 * (CONV + 3 * H + 2 * 32 * 2 * H)
    assert ARCH.decode_attn_bytes(config, 160000, 0, 32, 5, 0) == 2048 * 5 * (160000 + 32)


@pytest.mark.parametrize("n", [1, 2, 1024, 4096, 5000, 16384])
def test_the_stated_bytes_are_a_lower_bound_at_every_length(n):
    """``serve_dims``'s bytes of a decode step over a row of n live positions against what
    the step must read: K and V of the n positions in the five attention layers AND the
    17 conv layers' state, which the linear count leaves out: never more than the least."""
    config = _config()
    stated = flops.kv_bytes_per_position(ARCH.serve_dims(config)) * n
    least = ARCH.least_bytes_per_position(config) * n + 17 * 2 * 2 * 2 * H  # read and written
    assert stated <= least and stated == pytest.approx(5 * 2048 * n)


# -- the four readers ---------------------------------------------------------------------

D, P = "jit(_decode_step)/", "jit(_prefill_chunk)/"


def _op(start, end, op_name):
    return scoped.ScopedOp(float(start), float(end), "fusion.1", "fusion:kLoop", op_name, "")


def _window(decode_ops, prefill_ops, counters=True):
    """Two decode executions and one prefill chunk on device 0, and the window's
    ``decode`` spans with the engine's counters."""
    execs = [scoped.Execution("_decode_step", 0.0, 1e6, tuple(decode_ops)),
             scoped.Execution("_prefill_chunk", 2e6, 3e6, tuple(prefill_ops)),
             scoped.Execution("_decode_step", 4e6, 5e6, tuple(decode_ops))]
    args = {"active": 32}
    if counters:
        args.update({"kv_cache_bytes_per_position": 2048, "kv_live_positions": 160000,
                     "kv_full_live_positions": 160000, "kv_window_live_positions": 0,
                     "kv_full_read_positions": 32 * 16384, "kv_window_read_positions": 0,
                     "kv_full_layers": 5, "kv_window_layers": 0, "state_layers": 17,
                     "state_bytes_per_row": 8192, "moe_held_pairs_per_token": 1.0,
                     "moe_load_imbalance": 3.0})
    said = []
    spans = [{"name": "decode", "start": 0.0, "end": 0.02, "step": None, "args": dict(args)}
             for _ in range(3)]
    return {"serve": {"num_slots": 32}, "spans": spans, "_executions": execs, "say": said.append,
            "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}, "said": said,
            "arch": ARCH, "config": _config()}


CONV_DECODE = [
    _op(0, 40e3, D + "layer_0/attn/shortconv/in_proj/dot_general:"),
    _op(40e3, 42e3, D + "layer_0/attn/shortconv/state_read/select_n:"),
    _op(42e3, 47e3, D + "layer_0/attn/shortconv/conv/mul:"),
    _op(47e3, 50e3, D + "layer_0/attn/shortconv/state_write/dynamic_update_slice:"),
    _op(50e3, 70e3, D + "layer_0/attn/shortconv/out_proj/dot_general:"),
    _op(70e3, 170e3, D + "layer_0/mlp/dot_general:"),
    _op(170e3, 180e3, D + "layer_2/attn/full/cache_write/dynamic_update_slice:"),
    _op(180e3, 980e3, D + "layer_2/attn/full/attn_core/dot_general:"),
    _op(980e3, 1180e3, D + "layer_2/mlp/experts/moe_gmm:"),
]
CONV_PREFILL = [
    _op(0, 300e3, P + "layer_0/attn/shortconv/in_proj/dot_general:"),
    _op(300e3, 350e3, P + "layer_0/attn/shortconv/conv/mul:"),
    _op(350e3, 500e3, P + "layer_0/attn/shortconv/out_proj/dot_general:"),
    _op(500e3, 1700e3, P + "layer_2/attn/full/attn_core/while/body/dot_general:"),
]


def test_metrics_on_a_hand_made_window():
    ctx = _window(CONV_DECODE, CONV_PREFILL)
    assert _metric("shortconv_ms_per_step").compute(ctx) == pytest.approx(0.070)
    assert _metric("state_cache_ms_per_step").compute(ctx) == pytest.approx(0.005)
    assert _metric("shortconv_prefill_chunk_ms").compute(ctx) == pytest.approx(0.5)
    # 17 layers x (16.78 M weights + 32 rows x 2 x 2 x 2048) x 2 B at 819 GB/s over 0.07 ms
    least = 2 * 17 * (CONV + 3 * H + 2 * 32 * 2 * H)
    assert _metric("shortconv_hbm_roofline").compute(ctx) == pytest.approx(
        100 * (least / 819e9 * 1e3) / 0.070)
    assert any("17 layers" in line and "conv mixers" in line for line in ctx["said"])
    # the attention layers lie as a windowed stack's full layers: PR 54's readers read them
    assert _metric("full_attn_ms_per_step").compute(ctx) == pytest.approx(0.81)
    assert _metric("window_attn_ms_per_step").compute(ctx) == 0.0
    assert _metric("kv_prefill_chunk_attn_ms").compute(ctx) == pytest.approx(1.2)
    assert _metric("kv_read_over_live").compute(ctx) == pytest.approx(32 * 16384 / 160000)
    least = 2048 * 5 * (160000 + 32)
    assert _metric("kv_decode_attn_roofline").compute(ctx) == pytest.approx(
        100 * (least / 819e9 * 1e3) / 0.8)
    assert _metric("serve_expert_ms_per_step").compute(ctx) == pytest.approx(0.2)


def test_metrics_read_zero_on_another_stack_and_nothing_without_a_window():
    # a windowed stack's programs, and a latent one's: no ``shortconv``, no state counters
    other_decode = [_op(0, 100e3, D + "layer_1/attn/window/attn_core/dot_general:"),
                    _op(100e3, 200e3, D + "layer_1/attn/attn_core/absorb/dot_general:")]
    other_prefill = [_op(0, 100e3, P + "layer_1/attn/full/attn_core/dot_general:")]
    ctx = _window(other_decode, other_prefill, counters=False)
    for name in NEW_METRICS:
        assert _metric(name).compute(ctx) == 0.0, name
    for name in NEW_METRICS:
        assert _metric(name).compute({"spans": [], "say": print}) is None
        assert _metric(name).compute({"serve": {}, "spans": [], "trace": None, "say": print,
                                      "_executions": None}) is None


def test_metrics_read_zero_on_the_recorded_serving_step():
    """``recorded_serve_step.json`` is a decode step of opt-1.3b's cell as the chip's
    profiler recorded it: the new device readers answer 0 on it."""
    with open(os.path.join(HERE, "recorded_serve_step.json")) as f:
        rec = json.load(f)
    names = rec["op_names"]
    execs = [scoped.Execution(ex["program"], ex["start"], ex["end"], tuple(
        scoped.ScopedOp(a, b, inst, cat, names[i], "") for a, b, inst, cat, i in ex["ops"]))
        for ex in rec["executions"]]
    assert not any("/shortconv/" in n for n in names)
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
    # membership, relative order and the older entries as they were, with no tail
    # positions and no totals: the next PR that appends breaks nothing here
    names = [w["name"] for w in manifest["workloads"]]
    at = names.index(CELL)
    assert names[:at] == ACCEPTED_CELLS
    cell = manifest["workloads"][at]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "serve_long_conv_open_above_knee"
    assert 4 * sum(w["chips"] == 4 for w in manifest["workloads"]) <= len(names)
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("smallthinker-21b-a3b") < configs.index("lfm2-24b-a2b")
    entry = manifest["configs"][configs.index("lfm2-24b-a2b")]
    assert entry["source"] == SOURCE and sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    serving = e2e["serve_tokens_per_s_per_chip"]["workloads"]
    assert serving[:serving.index(CELL)] == [OPT_SERVE, SARVAM_CELL, SWA_CELL]
    assert CELL not in e2e["tokens_per_s_per_chip"]["workloads"]
    readers = [m["name"] for m in manifest["per_layer"]]
    first = readers.index(NEW_METRICS[0])
    assert readers[first:first + len(NEW_METRICS)] == NEW_METRICS
    assert readers.index("kv_read_over_live") < first  # PR 54's last
    # no other list names the cell: a serving reader names none
    assert not [m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", [])]


def test_the_search_readers_stand_together_where_they_were():
    """What tests/benchmark/test_benchmark_search_terms.py::
    test_the_five_sit_at_the_tail_of_the_manifest holds beside its pin to the list's tail
    (tests/conftest.py expects that one case to fail, strictly, since this PR appended):
    PR 56's five readers together in their order, right in front of this PR's four, the two
    four-chip cells on each, the two older search readers as they were."""
    per = harness.load_manifest(REPO)["per_layer"]
    names = [m["name"] for m in per]
    five = ["search_compute_pred_over_meas", "search_comm_pred_over_meas",
            "search_other_pred_over_meas", "search_mem_pred_over_meas", "search_unpriced_share"]
    at = names.index(five[0])
    assert names[at:at + 5] == five and names[at + 5:at + 9] == NEW_METRICS
    four = {"opt-1.3b_4chip_searched", "opt-1.3b_4chip_zero3"}
    assert all(four <= set(m["workloads"]) for m in per[at:at + 5])
    old = {m["name"]: m for m in per}
    assert old["search_pred_over_meas"]["workloads"] == ["opt-1.3b_4chip_searched"]
    assert old["search_s"]["moves"] == "setup_s"


def test_the_three_shares_of_the_chips_peaks_read_under_100():
    """Over a window of 100 decode steps (32 slots, 5,000 live positions each) and 40
    chunks of 8 prompts, on the chip's peaks, from this cell's ``serve_dims``."""
    config = _config()
    work = {"decode_tokens": 3200, "decode_positions": 16_000_000, "prefills": 8,
            "prefill_tokens": 40960, "prefill_chunks": 40, "prefill_positions": 40 * 3072,
            "prefill_pairs": 8 * 5120 * 5121 // 2}
    said = []
    ctx = {"serve": {"work": work, "seconds": 5.6}, "arch": ARCH, "config": config, "chips": 1,
           "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}, "say": said.append,
           "spans": [{"name": "decode", "args": {}}] * 100}
    a_forward = ARCH.served_params(config)["a_forward"]
    assert _metric("serve_hbm_roofline").compute(ctx) == pytest.approx(
        100 * (2 * (140 * a_forward + 44160 * 2048 + 3208 * 16384)
               + 10240 * (16_000_000 + 40 * 3072 + 44160)) / (5.6 * 819e9))
    assert 0 < _metric("serve_hbm_roofline").compute(ctx) < 100
    assert 0 < _metric("serve_mfu").compute(ctx) < 100


# -- the whole cell, tiny, on the new files ----------------------------------------------

TINY = {
    "model_type": "lfm2_moe", "hidden_size": 256, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_hidden_layers": 6,
    "vocab_size": 2048, "tie_word_embeddings": True, "norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "max_position_embeddings": 128, "conv_L_cache": 3, "layer_types": LAYER_TYPES,
    "moe_intermediate_size": 32, "num_dense_layers": 2, "num_experts": 4,
    "num_experts_per_tok": 2, "routed_scaling_factor": 1,
    "expert_share": {"rank": 1, "of": 2},
    "program_flags": ["--model_size", "lfm2-24b-a2b", "--num_layers", "6",
                      "--hidden_size", "256", "--num_heads", "4", "--num_kv_heads", "2",
                      "--ffn_dim", "96", "--vocab_size", "2048", "--moe_experts", "8",
                      "--moe_share", "1/2", "--seq_length", "128", "--param_dtype", "bf16"],
}
#: the tiny cell's limit: here (CPU, ~140 compared rows a run) the mean divergence of the
#: engine's softmax from the float32 reference's reads 1e-6 to 1e-5 (bf16 weights and
#: state against float32); a state that no forward carries reads over 1e-4. (Hidden 256,
#: heads of 64 as published: under weights of deviation 0.02 a conv mixer's output is
#: 0.02 x sqrt(hidden) to the third power of its input, nothing at all at hidden 64.)
TINY_KL_MAX = 3e-5


def _tiny_root(tmp_path, monkeypatch):
    from galvatron_tpu.models.modeling import PRESETS

    # (the expert sizes have no flag: the test narrows the preset)
    monkeypatch.setitem(PRESETS, "lfm2-24b-a2b", PRESETS["lfm2-24b-a2b"].replace(
        moe_top_k=2, moe_ffn_dim=32))
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = harness.load_cell(REPO, CELL)[2]
    spec["lengths"] = {"grid": 8, "pair_stride": 3, "max_total": 120,
                       "prompt": {"median": 24, "sigma": 0.7, "lo": 4, "hi": 80},
                       "output": {"median": 12, "sigma": 0.5, "lo": 4, "hi": 40}}
    spec["corpus"]["tokens"] = 4096
    spec["arrivals"].update(rate_rps=150.0, burst_at_start=8)
    spec["serve_flags"] = ["--num_slots", "4", "--prefill_chunk", "16", "--max_queue", "4096",
                           "--request_ttl_s", "0"]
    spec["window"]["settle_s"] = 0.2
    spec["correct"].update(requests=12, capture_every=3, logits_kl_max=TINY_KL_MAX)
    manifest = harness.load_manifest(REPO)
    with open(os.path.join(root, "benchmark/configs/tiny-lfm2.json"), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(root, "benchmark/traffic/tiny_long_conv.json"), "w") as f:
        json.dump(spec, f)
    manifest["configs"].append({"name": "tiny-lfm2", "source": "test", "reduced": [],
                                "file": "benchmark/configs/tiny-lfm2.json", "why": "test"})
    manifest["workloads"].append({"name": "tiny-lfm2_long", "config": "tiny-lfm2",
                                  "traffic": "tiny_long_conv", "chips": 1, "why": "test"})
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in entry.get("workloads", []):
            entry["workloads"].append("tiny-lfm2_long")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def _run(root, tmp_path, seed, trace=False):
    return harness.run(root, "tiny-lfm2_long", seed=seed, seconds=1.0, trace=trace,
                       out_dir=str(tmp_path / f"run_{seed}_{int(trace)}"), t_start=time.time())


def test_whole_serve_cell_tiny(tmp_path, monkeypatch):
    """The new cell's path through the serve runner at a tiny size: bf16 weights from the
    seed in the program's tree, the engine on the cache of two stacks (two attention
    layers' slots of 128, four conv layers' state; chunks of 16: prompts of up to 80 carry
    the state over five chunk ends), 4 slots used many times over, the held share of 8
    sigmoid-routed experts, the open loop, and ``correct`` against the new reference."""
    root = _tiny_root(tmp_path, monkeypatch)
    end = _run(root, tmp_path, 2**31 + 58)
    cmp = end["compared"]
    assert end["correct"] is True, cmp
    assert end["failed"] == 0 and end["attempted"] > 0
    assert set(end["metrics"]) == {"serve_tokens_per_s_per_chip", "setup_s"}
    assert cmp["rows"] > 0 and 0 < cmp["logits_kl"] <= TINY_KL_MAX
    assert cmp["greedy_served"] > 0 and cmp["greedy_not_best"] == 0
    assert cmp["sampled_tokens"] > 0 and cmp["sampled_outside_nucleus"] == 0
    json.dumps(end)

    traced = _run(root, tmp_path, 2**31 + 59, trace=True)
    assert traced["correct"] is True, traced["compared"]
    got = set(traced["metrics"])
    assert {"decode_step_ms_p50", "prefill_chunk_ms_p50", "engine_iteration_ms_p50",
            "slot_occupancy_share", "itl_p50_ms", "queue_wait_ms_p50"} <= got
    # the program's counters reach their readers; what needs a device trace does not exist here
    assert traced["metrics"]["kv_read_over_live"]["value"] >= 1.0
    assert 0.2 < traced["metrics"]["serve_moe_held_pairs_per_token"]["value"] <= 2.0
    assert not got & set(NEW_METRICS) and not got & set(KV_METRICS[:3])


def test_a_state_that_is_not_carried_is_not_correct(tmp_path, monkeypatch):
    """The timed path broken underneath: every forward reads a zero state (a decode step
    then convolves its one position with nothing before it), the rest of the run as it is.
    (A state left from the slot's previous request moves the first two positions of a
    prompt alone and does not show in rows compared from the prompt's end on: tests/
    test_lfm2.py holds that fault, in float32, at every position.)"""
    import jax

    from galvatron_tpu.models import shortconv

    root = _tiny_root(tmp_path, monkeypatch)
    monkeypatch.setattr(shortconv, "fresh", lambda prev, offsets: 0 * prev)
    jax.clear_caches()  # (the engine's jitted programs keep the body they were traced with)
    try:
        end = _run(root, tmp_path, 2**31 + 58)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert end["correct"] is False and end["compared"]["checks"]["logits"] is False, end["compared"]
