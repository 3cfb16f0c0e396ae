"""What PR 54 adds to the benchmark, on the CPU: the smallthinker-21b-a3b
configuration against its catalog row, the serving mix, the reference module's
counts against hand counts (its bytes a position a lower bound at every length), the
five new readers on a hand-made traced window and on a recorded step of another
stack, the fixed ZeRO-3 cell's files, and the whole serving cell at a tiny size
through the harness on the new files.  No number here is a device number."""

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

CELL = "smallthinker-21b-a3b_serve_long_above_knee"
ZERO3 = "opt-1.3b_4chip_zero3"
SARVAM_CELL = "sarvam-105b_serve_long_above_knee"
OPT_SERVE = "opt-1.3b_serve_above_knee"
#: the cells the benchmark had before this PR, in its order
ACCEPTED_CELLS = ["baichuan-7b_s4096", "baichuan-7b_s512", "opt-1.3b_4chip_searched",
                  "olmoe-1b-7b_s4096", "granite-4.0-h-micro_s8192", OPT_SERVE,
                  "qwen3-next-80b-a3b_s4096", SARVAM_CELL]
SOURCE = "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json"
LAYOUT = [0, 1, 1, 1] * 13
#: the ``config`` of the catalog row SmallThinker-21BA3B-Instruct (model-configs guide)
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_layout": LAYOUT, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936,
}
NEW_METRICS = ["window_attn_ms_per_step", "full_attn_ms_per_step", "kv_prefill_chunk_attn_ms",
               "kv_decode_attn_roofline", "kv_read_over_live"]
SHARES = ["serve_mfu", "serve_hbm_roofline", "decode_step_hbm_roofline"]
ARCH = reference.load(REPO, "smallthinker")


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
    changed = {k for k, v in CATALOG.items() if config.get(k) != v}
    assert changed == set(config["reduced"]) == set(config["published"]) == {
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"}
    assert {k: CATALOG[k] for k in changed} == config["published"]
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (16, 16, 37984)
    assert config["expert_share"] == {"rank": 0, "of": 4}
    assert config["vocab_size"] * 4 == CATALOG["vocab_size"]
    assert config["model_type"] == "smallthinker" and config["intermediate_size"] == 768
    assert {"router_input", "sliding_window", "rotary_pairing", "secondary_experts",
            "intermediate_size", "initializer", "slot_length"} <= set(config["assumed"])
    assert "4 chips share each layer" in config["deployment"] and config["distorts"]
    # four whole periods F W W W
    assert config["sliding_window_layout"][:16] == [0, 1, 1, 1] * 4


def test_the_program_runs_the_widths_the_file_states():
    import jax.numpy as jnp

    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args

    config = _config()
    cfg = model_config_from_args(initialize_galvatron("serve", list(config["program_flags"])))
    harness.check_widths(cfg, config)
    assert (cfg.kv_heads, cfg.head_dim, cfg.expert_ffn, cfg.moe_top_k) == (
        config["num_key_value_heads"], config["head_dim"], config["moe_ffn_hidden_size"],
        config["moe_num_active_primary_experts"])
    assert cfg.moe_held == config["moe_num_primary_experts"] and cfg.moe_experts == 64
    assert cfg.max_seq_len == config["max_position_embeddings"] == 16384
    assert cfg.sliding_window_size == config["sliding_window_size"]
    assert list(cfg.sliding_window_layout) == config["sliding_window_layout"]
    assert list(cfg.rope_layout) == config["rope_layout"]
    assert cfg.rope_theta == config["rope_theta"] and cfg.norm_eps == config["rms_norm_eps"]
    assert cfg.param_dtype == jnp.bfloat16 and cfg.glu_act == "relu"


def test_traffic_is_the_mix_the_issue_names():
    _, config, spec = harness.load_cell(REPO, CELL)
    other = harness.load_cell(REPO, SARVAM_CELL)[2]
    # the grid of the other long serving cell: the two differ by architecture, not by mix
    for key in ("lengths", "sampling", "corpus", "serve_flags", "window"):
        assert spec[key] == other[key], key
    assert spec["arrivals"]["burst_at_start"] == 64 and "knee" not in spec
    assert spec["correct"]["requests"] == 4 and spec["correct"]["capture_every"] == 5
    assert spec["correct"]["rows_kept"] == 4096
    shapes = traffic_lib.grid(spec)
    assert traffic_lib.mean_output_len(spec) == pytest.approx(300.625)
    inside = sum(s["prompt_len"] <= 4096 for s in shapes)
    assert 4 <= inside <= 8  # a third to a half of the prompts end inside the window
    assert max(s["prompt_len"] + s["output_len"] for s in shapes) <= 16000
    # slots of a whole number of chunks (the ring's contract), a ring of 5 chunks
    flags = dict(zip(spec["serve_flags"][::2], spec["serve_flags"][1::2]))
    assert 16384 % int(flags["--prefill_chunk"]) == 0
    assert "2.0" in spec["why"] and "K32" in spec["why"]


# -- the counts ---------------------------------------------------------------------------


def test_flop_count_against_a_hand_count():
    config = _config()
    h, f = 2560, 768
    proj = h * (28 + 8) * 128 + 28 * 128 * h
    routed = h * 64 + 3 * h * f * 6 / 4
    s = 8192
    pairs = (4 * s * (s + 1) / 2 + 12 * (4096 * 4097 / 2 + (s - 4096) * 4096)) / s
    want = 2.0 * (16 * (proj + routed) + h * 37984) + 2 * 2.0 * 28 * 128 * pairs
    assert ARCH.fwd_flops_per_token(config, s) == pytest.approx(want)
    assert ARCH.window_pairs(10, 4) == 1 + 2 + 3 + 4 * 7
    # inside the window a window layer is a full layer
    assert ARCH.window_pairs(4096, 4096) == 4096 * 4097 // 2


def test_served_counts_against_a_hand_count():
    config = _config()
    dims = ARCH.serve_dims(config)
    assert ARCH.position_share(config) == pytest.approx(7 / 16)
    assert dims["head_dim"] == pytest.approx(56) and dims["kv_heads"] == 4
    assert flops.kv_bytes_per_position(dims) == pytest.approx(14336)
    h, f = 2560, 768
    proj = h * (28 + 8) * 128 + 28 * 128 * h
    body = flops.matmul_params(hidden=dims["hidden"], heads=dims["heads"], ffn=dims["ffn"],
                               mlp_matrices=dims["mlp_matrices"], layers=dims["layers"], vocab=0)
    assert body == pytest.approx(16 * (proj + h * 64 + 3 * h * f * 1.5))
    served = ARCH.served_params(config)
    assert served["a_forward"] == 16 * (proj + 2 * h + h * 64 + 16 * 3 * h * f) + h + h * 37984
    assert served["a_token"] == h
    # the ISSUE's arithmetic: 4.09 GB of weights in bf16 (the embedding's rows with them)
    assert 2 * (served["a_forward"] + h * 37984) == pytest.approx(4.09e9, rel=0.005)


def test_the_stated_bytes_a_position_never_exceed_the_exact_least():
    """``serve_dims``'s bytes a live position (a dense decoder's count, linear in the
    live positions) against what a decode step must read of a row of n positions,
    every n up to the slots' 16,384: a lower bound everywhere, equal at the end."""
    config = _config()
    stated = flops.kv_bytes_per_position(ARCH.serve_dims(config))
    least = [ARCH.least_bytes_per_position(config, n) for n in range(1, 16385)]
    assert all(stated <= x + 1e-9 for x in least)
    assert least[-1] == pytest.approx(stated) and least[0] == 16 * 2048
    assert least[4095] == 16 * 2048 and least[8191] == 2048 * (4 + 12 / 2)
    # the same factor bounds the pairs a window layer multiplies
    for n in (1, 4096, 5000, 16384):
        assert ARCH.window_pairs(n, 4096) >= 0.25 * n * (n + 1) / 2
    assert ARCH.decode_attn_bytes(config, 1000, 600, 32, 4, 12) == 2048 * (
        1000 * 4 + 600 * 12 + 32 * 16)


# -- the five readers ---------------------------------------------------------------------

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
                     "kv_full_live_positions": 160000, "kv_window_live_positions": 100000,
                     "kv_full_read_positions": 32 * 16384, "kv_window_read_positions": 32 * 5120,
                     "kv_full_layers": 4, "kv_window_layers": 12,
                     "moe_held_pairs_per_token": 1.5, "moe_load_imbalance": 3.0})
    said = []
    spans = [{"name": "decode", "start": 0.0, "end": 0.02, "step": None, "args": dict(args)}
             for _ in range(3)]
    return {"serve": {"num_slots": 32}, "spans": spans, "_executions": execs, "say": said.append,
            "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}, "said": said,
            "arch": ARCH, "config": _config()}


SWA_DECODE = [
    _op(0, 100e3, D + "layer_0/attn/full/qkv_proj/dot_general:"),
    _op(100e3, 110e3, D + "layer_0/attn/full/cache_write/dynamic_update_slice:"),
    _op(110e3, 910e3, D + "layer_0/attn/full/attn_core/dot_general:"),
    _op(910e3, 920e3, D + "layer_0/attn/full/out_proj/dot_general:"),
    _op(920e3, 930e3, D + "layer_1/attn/window/cache_write/dynamic_update_slice:"),
    _op(930e3, 1230e3, D + "layer_1/attn/window/attn_core/dot_general:"),
    _op(1230e3, 1240e3, D + "layer_1/mlp/router/dot_general:"),
    _op(1240e3, 1440e3, D + "layer_1/mlp/experts/moe_gmm:"),
]
SWA_PREFILL = [
    _op(0, 500e3, P + "layer_0/attn/full/qkv_proj/dot_general:"),
    _op(500e3, 1700e3, P + "layer_0/attn/full/attn_core/while/body/dot_general:"),
    _op(1700e3, 2100e3, P + "layer_1/attn/window/attn_core/while/body/dot_general:"),
]


def test_metrics_on_a_hand_made_window():
    ctx = _window(SWA_DECODE, SWA_PREFILL)
    assert _metric("full_attn_ms_per_step").compute(ctx) == pytest.approx(0.81)  # core + write
    assert _metric("window_attn_ms_per_step").compute(ctx) == pytest.approx(0.31)
    assert _metric("kv_prefill_chunk_attn_ms").compute(ctx) == pytest.approx(1.6)
    assert any("1.200 ms under the full" in line for line in ctx["said"])
    # read over live, by layers: (524288 x 4 + 163840 x 12) / (160000 x 4 + 100000 x 12)
    assert _metric("kv_read_over_live").compute(ctx) == pytest.approx(
        (32 * 16384 * 4 + 32 * 5120 * 12) / (160000 * 4 + 100000 * 12))
    # (160000 x 4 + 100000 x 12 + 32 x 16) x 2048 B at 819 GB/s over 1.1 ms under attn_core
    least = 2048 * (160000 * 4 + 100000 * 12 + 32 * 16)
    assert _metric("kv_decode_attn_roofline").compute(ctx) == pytest.approx(
        100 * (least / 819e9 * 1e3) / 1.1)
    assert any("live positions" in line and "window" in line for line in ctx["said"])


def test_metrics_read_zero_on_another_stack_and_nothing_without_a_window():
    # a plain K/V stack's programs, and a latent one's: no ``window``, no ``full``, no
    # counters by stack: 0, which is what such a step spends in a windowed stack's attention
    plain_decode = [_op(0, 100e3, D + "layer_1/attn/attn_core/dot_general:"),
                    _op(100e3, 200e3, D + "layer_1/attn/attn_core/absorb/dot_general:")]
    plain_prefill = [_op(0, 100e3, P + "layer_1/attn/attn_core/dot_general:")]
    ctx = _window(plain_decode, plain_prefill, counters=False)
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
    assert {ex.program for ex in execs} >= {"_decode_step", "_prefill_chunk"}
    assert not any("/window/" in n or "/full/" in n for n in names)
    ctx = {"serve": {}, "spans": [{"name": "decode", "args": {"active": 16}}], "say": print,
           "_executions": execs, "peaks": {"hbm_bytes_per_s": 819e9}, "arch": ARCH,
           "config": _config()}
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


def test_the_cells_join_the_manifest_by_appends():
    manifest = harness.load_manifest(REPO)
    # membership, relative order and the older entries as they were, with no tail
    # positions and no totals: the next PR that appends breaks nothing here
    names = [w["name"] for w in manifest["workloads"]]
    at = names.index(CELL)
    assert names[:at] == ACCEPTED_CELLS and names[at:at + 2] == [CELL, ZERO3]
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert (cells[CELL]["chips"], cells[ZERO3]["chips"]) == (1, 4)
    assert 4 * sum(w["chips"] == 4 for w in manifest["workloads"]) <= len(names)
    assert all(len(cells[name]["why"]) <= 200 for name in (CELL, ZERO3))
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("sarvam-105b") < configs.index("smallthinker-21b-a3b")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    serving = e2e["serve_tokens_per_s_per_chip"]["workloads"]
    assert serving[:serving.index(CELL)] == [OPT_SERVE, SARVAM_CELL]
    training = e2e["tokens_per_s_per_chip"]["workloads"]
    assert ZERO3 in training and CELL not in training
    assert training[:training.index(ZERO3)] == [
        name for name in ACCEPTED_CELLS if name not in serving]
    per = {m["name"]: m for m in manifest["per_layer"]}
    readers = [m["name"] for m in manifest["per_layer"]]
    first = readers.index(NEW_METRICS[0])
    assert readers[first:first + len(NEW_METRICS)] == NEW_METRICS
    assert readers.index("serve_moe_held_pairs_per_token") < first      # PR 51's last
    # the collectives are what the four-chip cell is for; it runs no search, and the two
    # whole-step flash readers' lists are held to their first cells by
    # test_benchmark_granite.py, a file this PR may not edit (ISSUE 54 asked for all five)
    for name in ("collective_ms_per_step", "collective_exposed_share", "comm_scope_ms_per_step"):
        assert per[name]["workloads"].index(ZERO3) > per[name]["workloads"].index(
            "opt-1.3b_4chip_searched"), name
    for name in ("search_s", "search_pred_over_meas", "flash_attention_ms_per_step",
                 "flash_attention_roofline"):
        assert ZERO3 not in per[name]["workloads"], name
    # the three shares over a window of 100 decode steps (32 slots, 5,000 live positions
    # each) and 40 chunks of 8 prompts, on the chip's peaks: all under 100
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
        100 * (2 * (140 * a_forward + 44160 * 2560 + 3208 * 37984)
               + 14336 * (16_000_000 + 40 * 3072 + 44160)) / (5.6 * 819e9))
    assert 0 < _metric("serve_mfu").compute(ctx) < 100
    assert any("14336" in line and "of K and V a live position" in line for line in said)


# -- what PR 51's six tail-pinned cases hold beside their pins ---------------------------
# tests/benchmark/test_benchmark_sarvam.py pins its entries to the END of BENCHMARK.json, so
# its six manifest cases cannot pass once a cell is appended, and this PR may not edit
# them (tests/conftest.py expects them to fail, strictly).  Everything else they assert
# runs here, with relative positions in place of the pins, so nothing of them goes dark.

SARVAM_METRICS = ["mla_attn_ms_per_step", "mla_decode_attn_roofline",
                  "mla_prefill_chunk_attn_ms", "serve_expert_ms_per_step",
                  "serve_moe_held_pairs_per_token"]


@pytest.mark.parametrize("name", SARVAM_METRICS)
def test_the_latent_readers_are_still_declared_as_serving_readers(name):
    manifest = harness.load_manifest(REPO)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    mod = _metric(name)
    assert "workloads" not in entry and entry["moves"] == "serve_tokens_per_s_per_chip"
    assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(
        entry[k] for k in ("name", "unit", "better", "source", "layer", "moves"))
    readers = [m["name"] for m in manifest["per_layer"]]
    first = readers.index(SARVAM_METRICS[0])
    assert readers[first:first + len(SARVAM_METRICS)] == SARVAM_METRICS


def test_the_latent_cell_still_reads_the_rate_and_every_serving_reader():
    manifest = harness.load_manifest(REPO)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["serve_tokens_per_s_per_chip"]["workloads"][:2] == [OPT_SERVE, SARVAM_CELL]
    assert SARVAM_CELL not in e2e["tokens_per_s_per_chip"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    cell = next(w for w in manifest["workloads"] if w["name"] == SARVAM_CELL)
    assert len(cell["why"]) <= 200 and (cell["chips"], cell["config"]) == (1, "sarvam-105b")
    serving = [m for m in manifest["per_layer"] if m["moves"] == "serve_tokens_per_s_per_chip"]
    assert not [m["name"] for m in serving if "workloads" in m]
    assert set(SHARES) | set(SARVAM_METRICS) | set(NEW_METRICS) <= {m["name"] for m in serving}
    # the three shares over the hand-made window of that file: all under 100
    arch, config = reference.load(REPO, "sarvam_mla"), harness.load_cell(REPO, SARVAM_CELL)[1]
    work = {"decode_tokens": 3200, "decode_positions": 16_000_000, "prefills": 8,
            "prefill_tokens": 40960, "prefill_chunks": 40, "prefill_positions": 40 * 3072,
            "prefill_pairs": 8 * 5120 * 5121 // 2}
    said = []
    ctx = {"serve": {"work": work, "seconds": 5.6}, "arch": arch, "config": config, "chips": 1,
           "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}, "say": said.append,
           "spans": [{"name": "decode", "args": {}}] * 100}
    a_forward = arch.served_params(config)["a_forward"]
    assert _metric("serve_hbm_roofline").compute(ctx) == pytest.approx(
        100 * (2 * (140 * a_forward + 44160 * 4096 + 3208 * 65536)
               + 5760 * (16_000_000 + 40 * 3072 + 44160)) / (5.6 * 819e9))
    assert 20 < _metric("serve_hbm_roofline").compute(ctx) < 30
    assert 5 < _metric("serve_mfu").compute(ctx) < 12
    assert any("5760" in line and "of K and V a live position" in line for line in said)


def test_the_fixed_zero3_cell_is_the_searched_cells_traffic_under_a_fixed_plan():
    cell, config, spec = harness.load_cell(REPO, ZERO3)
    searched = harness.load_cell(REPO, "opt-1.3b_4chip_searched")[2]
    assert cell["chips"] == 4 and cell["config"] == "opt-1.3b"
    for key in ("seq_len", "global_batch", "corpus", "loss_drop_by_step_20"):
        assert spec[key] == searched[key], key
    assert spec["plan"] == "single"
    flags = dict(zip(spec["train_flags"][::2], spec["train_flags"][1::2]))
    assert flags["--pp_deg"] == "1" and flags["--global_tp_deg"] == "1"
    assert flags["--sdp"] == "1" and flags["--embed_sdp"] == "1" and flags["--chunks"] in "124"
    # what the trainer makes of it: dp 4, ZeRO-3 on every layer and the embedding
    from galvatron_tpu.core.arguments import initialize_galvatron

    argv = harness.train_argv(config, spec, seed=1, data_prefix="x", iters=1, metrics_path="m",
                              plan_flags=harness.resolve_plan(REPO, "", cell, config, spec)["flags"])
    ns = initialize_galvatron("train", argv)
    assert (ns.pp_deg, ns.global_tp_deg, ns.sdp, ns.embed_sdp) == (1, 1, 1, 1)


# -- the whole cell, tiny, on the new files ----------------------------------------------

TINY = {
    "model_type": "smallthinker", "hidden_size": 64, "intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 4,
    "vocab_size": 2048, "tie_word_embeddings": False, "rms_norm_eps": 1e-06,
    "rope_theta": 1500000, "max_position_embeddings": 128, "sliding_window_size": 16,
    "sliding_window_layout": LAYOUT, "rope_layout": LAYOUT, "moe_ffn_hidden_size": 32,
    "moe_num_primary_experts": 4, "moe_num_active_primary_experts": 2,
    "expert_share": {"rank": 1, "of": 2},
    "program_flags": ["--model_size", "smallthinker-21b-a3b", "--num_layers", "4",
                      "--hidden_size", "64", "--num_heads", "4", "--num_kv_heads", "2",
                      "--ffn_dim", "32", "--vocab_size", "2048", "--moe_experts", "8",
                      "--moe_share", "1/2", "--seq_length", "128", "--param_dtype", "bf16"],
}
#: the tiny cell's limit: here (CPU, ~140 compared rows a run) the mean divergence of
#: the engine's softmax from the float32 reference's reads 1e-6 to 1e-5; a decode step
#: one position late reads over 1e-4
TINY_KL_MAX = 3e-5


def _tiny_root(tmp_path, monkeypatch):
    from galvatron_tpu.models.modeling import PRESETS

    # (the head, window and expert sizes have no flag: the test narrows the preset)
    monkeypatch.setitem(PRESETS, "smallthinker-21b-a3b", PRESETS["smallthinker-21b-a3b"].replace(
        attn_head_dim=16, sliding_window_size=16, moe_top_k=2, moe_ffn_dim=32))
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
    with open(os.path.join(root, "benchmark/configs/tiny-smallthinker.json"), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(root, "benchmark/traffic/tiny_long_swa.json"), "w") as f:
        json.dump(spec, f)
    manifest["configs"].append({"name": "tiny-smallthinker", "source": "test", "reduced": [],
                                "file": "benchmark/configs/tiny-smallthinker.json", "why": "test"})
    manifest["workloads"].append({"name": "tiny-smallthinker_long", "config": "tiny-smallthinker",
                                  "traffic": "tiny_long_swa", "chips": 1, "why": "test"})
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in entry.get("workloads", []):
            entry["workloads"].append("tiny-smallthinker_long")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def _run(root, tmp_path, seed, trace=False):
    return harness.run(root, "tiny-smallthinker_long", seed=seed, seconds=1.0, trace=trace,
                       out_dir=str(tmp_path / f"run_{seed}_{int(trace)}"), t_start=time.time())


def test_whole_serve_cell_tiny(tmp_path, monkeypatch):
    """The new cell's path through the serve runner at a tiny size: bf16 weights from
    the seed in the program's tree, the engine on the two-stack cache (window 16, chunk
    16, a ring of 32 in slots of 128: prompts of up to 80 lap it), the held share of 8
    experts routed from the attention's input, the open loop, and ``correct`` against
    the new reference."""
    root = _tiny_root(tmp_path, monkeypatch)
    end = _run(root, tmp_path, 2**31 + 54)
    cmp = end["compared"]
    assert end["correct"] is True, cmp
    assert end["failed"] == 0 and end["attempted"] > 0
    assert set(end["metrics"]) == {"serve_tokens_per_s_per_chip", "setup_s"}
    assert cmp["rows"] > 0 and 0 < cmp["logits_kl"] <= TINY_KL_MAX
    assert cmp["greedy_served"] > 0 and cmp["greedy_not_best"] == 0
    assert cmp["sampled_tokens"] > 0 and cmp["sampled_outside_nucleus"] == 0
    json.dumps(end)

    traced = _run(root, tmp_path, 2**31 + 55, trace=True)
    assert traced["correct"] is True, traced["compared"]
    got = set(traced["metrics"])
    assert {"decode_step_ms_p50", "prefill_chunk_ms_p50", "engine_iteration_ms_p50",
            "slot_occupancy_share", "itl_p50_ms", "queue_wait_ms_p50"} <= got
    # the program's counters reach their readers; what needs a device trace does not exist here
    assert traced["metrics"]["kv_read_over_live"]["value"] >= 1.0
    assert 0.2 < traced["metrics"]["serve_moe_held_pairs_per_token"]["value"] <= 2.0
    assert not got & {"window_attn_ms_per_step", "full_attn_ms_per_step",
                      "kv_prefill_chunk_attn_ms", "kv_decode_attn_roofline"}
    assert not got & set(SHARES)


def test_a_ring_one_position_late_is_not_correct(tmp_path, monkeypatch):
    """The timed path broken underneath: every decode step writes and reads its slot
    one position late, the rest of the run as it is."""
    from galvatron_tpu.serving import engine as engine_mod

    root = _tiny_root(tmp_path, monkeypatch)
    real = engine_mod._decode_step
    monkeypatch.setattr(engine_mod, "_decode_step",
                        lambda params, cfg, cache, tokens, offsets:
                        real(params, cfg, cache, tokens, offsets + 1))
    end = _run(root, tmp_path, 2**31 + 54)
    assert end["correct"] is False and end["compared"]["checks"]["logits"] is False, end["compared"]
