"""What PR 33 adds to the benchmark, on the CPU: the granite-4.0-h-micro
configuration against its catalog row, the traffic file, the reference module's
counts against a hand count, the three state-space metrics on a hand-made
scoped step, and the whole cell at a tiny size through the harness. No number
here is a device number."""

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

from benchmark.lib import harness, reference, scoped  # noqa: E402

CELL = "granite-4.0-h-micro_s8192"
PATTERN = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
#: the ``config`` of the catalog row granite-4.0-h-micro (model-configs guide)
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 8192,
    "layer_types": PATTERN, "logits_scaling": 8, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
}
SOURCE = "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
OLD_CELLS = ["baichuan-7b_s4096", "baichuan-7b_s512", "opt-1.3b_4chip_searched",
             "olmoe-1b-7b_s4096"]


def _metric(name):
    path = os.path.join(REPO, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("_t_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_configuration_is_the_catalog_row_with_depth_and_vocabulary_cut():
    cell, config, traffic = harness.load_cell(REPO, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-micro", "s8192_b1_ckpt", 1)
    assert config["source"] == SOURCE
    differs = sorted(k for k, v in CATALOG.items() if config[k] != v)
    assert differs == ["layer_types", "num_hidden_layers", "vocab_size"] == sorted(
        config["reduced"]) == sorted(config["published"])
    assert (config["num_hidden_layers"], config["vocab_size"]) == (10, 25088)
    assert config["layer_types"] == PATTERN[:10] and config["published"]["layer_types"] == PATTERN
    assert (config["published"]["num_hidden_layers"], config["published"]["vocab_size"]) == (
        40, 100352)
    # the floors of a model_config PR: a whole period, >= 1/8 of the vocabulary
    assert config["layer_types"].count("attention") == 1 and config["vocab_size"] * 8 >= 100352
    assert "initializer_range" in config["assumed"]
    assert config["program_flags"] == ["--model_size", "granite-4.0-h-micro", "--num_layers",
                                       "10", "--vocab_size", "25088"]
    # the preset runs the widths the file states (what check_widths holds a run to)
    from galvatron_tpu.models.modeling import PRESETS

    preset = PRESETS["granite-4.0-h-micro"].replace(num_layers=10, vocab_size=25088)
    harness.check_widths(preset, config)
    assert [("mamba" if k == "ssm" else k) for k in preset.kinds] == config["layer_types"]
    assert (preset.kv_heads, preset.head_dim, preset.ssm_heads, preset.ssm_head_dim,
            preset.ssm_state, preset.ssm_groups, preset.ssm_conv, preset.ssm_chunk) == (
                config["num_key_value_heads"], 64, config["mamba_n_heads"],
                config["mamba_d_head"], config["mamba_d_state"], config["mamba_n_groups"],
                config["mamba_d_conv"], config["mamba_chunk_size"])
    assert (preset.attention_multiplier, preset.embedding_multiplier,
            preset.residual_multiplier, preset.logits_scaling, preset.norm_eps,
            preset.max_seq_len, preset.pos_embed) == (
                config["attention_multiplier"], config["embedding_multiplier"],
                config["residual_multiplier"], config["logits_scaling"],
                config["rms_norm_eps"], config["max_position_embeddings"], "nope")
    assert preset.ssm_heads * preset.ssm_head_dim == config["mamba_expand"] * config["hidden_size"]
    # tied table of sd 0.02 against a unit-mean-square norm output, over 8: 0.0128
    assert config["initial_logit_variance"] == pytest.approx(2048 * 0.02 ** 2 / 8 ** 2)
    assert harness.expected_first_loss(config) == pytest.approx(10.136, abs=1e-3)


def test_traffic_is_the_cell_the_issue_names():
    _, config, traffic = harness.load_cell(REPO, CELL)
    assert (traffic["seq_len"], traffic["global_batch"], traffic["plan"],
            traffic["train_flags"]) == (8192, 1, "single", ["--global_checkpoint", "1"])
    assert traffic["corpus"] == harness.load_cell(REPO, "baichuan-7b_s4096")[2]["corpus"]
    assert traffic["seq_len"] // config["mamba_chunk_size"] == 32
    assert 0 < traffic["loss_drop_by_step_20"]


def test_flop_and_byte_counts_against_a_hand_count():
    arch = reference.load(REPO, "granitemoehybrid")
    _, config, _ = harness.load_cell(REPO, CELL)
    h, f, v, s = 2048, 8192, 25088, 8192
    mlp = 2 * 3 * h * f
    ssd = 2 * 128.5 * (128 + 4096) + 2 * 2 * 4096 * 128  # C B^T, scores x; state, read-out
    ssm = 2 * h * 8512 + 2 * 4 * 4352 + ssd + 2 * 4096 * h
    attn = 2 * h * (2 * 2048 + 2 * 512) + 2 * 2 * 2048 * (s * (s + 1) // 2) / s
    head = 2 * h * v
    assert [round(x / 1e6, 2) for x in (mlp, ssd, ssm, attn, head)] == [
        100.66, 3.18, 54.86, 54.53, 102.76]
    assert arch.fwd_flops_per_token(config, s) == pytest.approx(
        9 * (ssm + mlp) + (attn + mlp) + head, rel=1e-12)
    assert round(arch.fwd_flops_per_token(config, s) / 1e9, 3) == 1.658  # the issue's 1.67
    # the whole model: 36 + 4 layers and the whole table
    full = dict(config, num_hidden_layers=40, layer_types=PATTERN, vocab_size=100352)
    assert arch.fwd_flops_per_token(full, s) == pytest.approx(
        36 * (ssm + mlp) + 4 * (attn + mlp) + 2 * h * 100352, rel=1e-12)
    # the scans of a step: 12 GEMMs a layer over 9 layers, 0.70 TFLOP = 3.57 ms at the
    # v5e's bf16 peak, under the 3.86 ms its least bytes take: bound by memory
    assert arch.ssd_scan_flops(config, 8192) == pytest.approx(3 * 9 * 8192 * ssd)
    ins = 4096 + 256 + 64
    assert arch.ssd_scan_bytes(config, 8192) == 9 * 8192 * 2 * ((ins + 4096) + (2 * ins + 4096))
    t_flops, t_bytes = (arch.ssd_scan_flops(config, 8192) / 197e12,
                        arch.ssd_scan_bytes(config, 8192) / 819e9)
    assert (round(t_flops * 1e3, 2), round(t_bytes * 1e3, 2)) == (3.57, 3.86)


# -- the three metrics ----------------------------------------------------------

def _op(start, end, op_name, name="fusion.1", category="fusion:kLoop"):
    return scoped.ScopedOp(float(start), float(end), name, category, op_name)


J = "jit(train_step)/"
F = J + "layer_0/jit(_decoder_layer_once)/jvp(checkpoint)/ssm/"
B = J + "transpose(jvp(layer_0))/jit(_decoder_layer_once)/transpose(jvp(checkpoint))/ssm/"
#: one step by hand: 100 under ssm (in_proj 10 + 20, conv 2 + 4, scan 8 + 36, gate_norm
#: 1 + 3, out_proj 5 + 10, 1 under ssm alone), and work that is not the mixer's
HAND = [
    _op(0, 10, F + "in_proj/dot_general:"),
    _op(10, 12, F + "conv/mul:"),
    _op(12, 20, F + "scan/dot_general:"),
    _op(20, 21, F + "gate_norm/mul:"),
    _op(21, 26, F + "out_proj/dot_general:"),
    _op(26, 46, B + "in_proj/dot_general:"),
    _op(46, 50, B + "conv/mul:"),
    _op(50, 70, B + "scan/dot_general:"),
    _op(70, 86, B.replace("transpose(jvp(checkpoint))", "rematted_computation") + "scan/exp:"),
    _op(86, 89, B + "gate_norm/mul:"),
    _op(89, 99, B + "out_proj/dot_general:"),
    _op(99, 100, B + "reshape:"),
    _op(100, 130, J + "layer_5/jit(_decoder_layer_once)/jvp(checkpoint)/attn/attn_core/x:"),
    _op(130, 150, J + "layer_0/jit(_decoder_layer_once)/jvp(checkpoint)/mlp/dot_general:"),
    _op(150, 160, J + "optimizer/scan_like_name/add:"),  # the optimizer is not the mixer
]
CONFIG = {"num_hidden_layers": 10, "layer_types": PATTERN[:10], "mamba_n_heads": 64,
          "mamba_d_head": 64, "mamba_d_state": 128, "mamba_n_groups": 1,
          "mamba_chunk_size": 256}


def _ctx(sops, said):
    return {"_scoped_device0": sops, "n_profiled": 1, "say": said.append, "chips": 1,
            "config": CONFIG, "arch": reference.load(REPO, "granitemoehybrid"),
            "traffic": {"global_batch": 1, "seq_len": 8192},
            "peaks": {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}}


def test_metrics_on_a_hand_made_step():
    said = []
    assert _metric("ssm_ms_per_step").compute(_ctx(HAND, said)) == pytest.approx(100 / 1e6)
    text = "\n".join(said)
    assert "ssm scope scan: forward" in text and "ssm scope other:" in text
    assert _metric("ssm_scan_ms_per_step").compute(_ctx(HAND, [])) == pytest.approx(50 / 1e6)
    # 44 ns under scan against 3.86 ms of bytes: the arithmetic, not a device number
    roof = _metric("ssm_scan_roofline").compute(_ctx(HAND, said))
    ins = 4096 + 256 + 64
    assert roof == pytest.approx(
        100 * (9 * 8192 * 2 * (3 * ins + 2 * 4096) / 819e9) / 44e-9)
    assert any("bound by memory" in s for s in said)
    # the split is by the mixer's scope and by phase; a rematerialized forward is backward's
    from benchmark.metrics import _ssm

    split = _ssm.split_ns(HAND)
    assert split[("scan", "forward")] == 8 and split[("scan", "backward")] == 36
    assert split[("other", "backward")] == 1 and _ssm.under(split) == 100
    assert _ssm.ssm_scope(J + "layer_5/attn/out_proj/dot_general:") is None  # attention's out_proj


def test_metrics_leave_themselves_out_without_the_scopes():
    """A Transformer's step (or a parent's): layers without ``ssm``."""
    dense = [_op(0, 10, J + "jvp(layer_0)/attn/out_proj/dot_general:"),
             _op(10, 20, J + "jvp(head)/mul:")]
    for name in ("ssm_ms_per_step", "ssm_scan_ms_per_step", "ssm_scan_roofline"):
        assert _metric(name).compute(_ctx(dense, [])) is None
        assert _metric(name).compute(_ctx(None, [])) is None
    # and with another architecture's reference module, which counts no scan
    ctx = _ctx(HAND, [])
    ctx["arch"] = reference.load(REPO, "baichuan")
    assert _metric("ssm_scan_roofline").compute(ctx) is None


@pytest.mark.parametrize("name", ["ssm_ms_per_step", "ssm_scan_ms_per_step", "ssm_scan_roofline"])
def test_metric_is_declared_for_the_one_cell(name):
    manifest = harness.load_manifest(REPO)
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    mod = _metric(name)
    assert entry["workloads"] == [CELL]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == (
        mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES)
    assert entry["layer"] in {m["layer"] for m in manifest["per_layer"][:30]}  # a layer it has


@pytest.mark.parametrize("name", ["flash_attention_ms_per_step", "flash_attention_roofline"])
def test_whole_step_flash_metrics_stay_with_the_cells_they_count_rightly(name):
    """They reckon attention in every layer; in a cell with attention in one layer of ten
    that reads ten times the true share. The prefix metrics cover the new cell."""
    manifest = harness.load_manifest(REPO)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert entries[name]["workloads"] == OLD_CELLS
    for prefix in ("flash_fwd_ms_per_step", "flash_bwd_ms_per_step"):
        assert "workloads" not in entries[prefix]
    assert [w["name"] for w in manifest["workloads"]][:5] == OLD_CELLS + [CELL]


# -- the whole cell at a tiny size ------------------------------------------------

TINY = {
    "model_type": "granitemoehybrid", "hidden_size": 64, "intermediate_size": 96,
    "shared_intermediate_size": 96, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 6, "layer_types": PATTERN[:6], "rms_norm_eps": 1e-05,
    "attention_multiplier": 0.015625, "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "logits_scaling": 8, "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 32,
    "tie_word_embeddings": True, "vocab_size": 256,
    "initial_logit_variance": 64 * 0.02 ** 2 / 64,
    "program_flags": ["--model_size", "granite-4.0-h-micro", "--num_layers", "6", "--hidden_size",
                      "64", "--num_heads", "4", "--num_kv_heads", "2", "--ffn_dim", "96",
                      "--vocab_size", "256"],
}
TINY_TRAFFIC = {
    "seq_len": 128, "global_batch": 8, "plan": "single",
    "train_flags": ["--global_checkpoint", "1", "--lr", "1e-2"],
    "corpus": {"tokens": 65536, "doc_len": 256, "zipf_a": 1.0, "follow_p": 0.5},
    "loss_drop_by_step_20": 0.2, "why": "tiny CPU rehearsal",
}


def test_whole_cell_tiny(tmp_path, monkeypatch):
    """The new cell's path through the harness at a tiny size: corpus, one
    ``train()`` call on the preset under full-layer recomputation (five state-space
    layers and the attention layer, four chunks a sequence), the float32 reference
    check, the traced form. The state-space sizes have no flag (the issue adds none),
    so the test narrows the preset itself."""
    from galvatron_tpu.models.modeling import PRESETS

    monkeypatch.setitem(PRESETS, "granite-4.0-h-micro", PRESETS["granite-4.0-h-micro"].replace(
        ssm_heads=8, ssm_head_dim=16, ssm_state=16, ssm_chunk=32))
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = harness.load_manifest(REPO)
    with open(os.path.join(root, "benchmark/configs/tiny-granite.json"), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(root, "benchmark/traffic/tiny.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    manifest["configs"].append({"name": "tiny-granite", "source": "test", "reduced": [],
                                "file": "benchmark/configs/tiny-granite.json", "why": "test"})
    manifest["workloads"].append({"name": "tiny-granite_tiny", "config": "tiny-granite",
                                  "traffic": "tiny", "chips": 1, "why": "test"})
    # the lists a one-chip training cell is in (throughput names its cells), and its own
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if entry["name"].startswith("ssm_") or "baichuan-7b_s512" in entry.get("workloads", []):
            entry["workloads"].append("tiny-granite_tiny")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    end = harness.run_cell(root, "tiny-granite_tiny", seed=2147483659, seconds=0.5, trace=True,
                           out_dir=str(tmp_path / "run"), t_start=time.time(), min_steps=24)
    assert end["correct"] is True and end["failed"] == 0 and end["attempted"] >= 24
    got = set(end["metrics"])
    assert {"compile_s", "step_ms_p50", "runtime_build_s"} <= got
    # nothing that needs a device trace exists on the CPU
    assert not got & {"ssm_ms_per_step", "ssm_scan_ms_per_step", "ssm_scan_roofline",
                      "flash_attention_roofline"}
    # the run's fingerprint and its build_runtime span say what the stack holds
    with open(str(tmp_path / "run" / "spans.json")) as f:
        spans = json.load(f)["traceEvents"]
    build = [e for e in spans if e.get("name") == "build_runtime"]
    assert build and build[0]["args"]["layer_kinds"] == {"ssm": 5, "attention": 1}
