"""What PR 47 adds to the benchmark, on the CPU: the qwen3-next-80b-a3b
configuration against its catalog row, the traffic file, the reference module's
counts against a hand count, the four new metrics on a hand-made scoped step and
hand-made records, and the whole cell at a tiny size through the harness. No
number here is a device number."""

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

CELL = "qwen3-next-80b-a3b_s4096"
#: the ``config`` of the catalog row Qwen3-Next-80B-A3B-Instruct (model-configs guide)
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}
SOURCE = "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
NEW_METRICS = ["gdn_ms_per_step", "gdn_scan_ms_per_step", "gdn_scan_roofline",
               "moe_held_pairs_per_token"]


def _metric(name):
    path = os.path.join(REPO, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("_t_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_configuration_is_the_catalog_row_with_depth_experts_and_vocabulary_cut():
    cell, config, traffic = harness.load_cell(REPO, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-a3b", "s4096_b4_ckpt", 1)
    assert config["source"] == SOURCE
    differs = sorted(k for k, v in CATALOG.items() if config[k] != v)
    assert differs == ["num_experts", "num_hidden_layers", "vocab_size"] == sorted(
        config["reduced"]) == sorted(config["published"])
    entry = next(c for c in harness.load_manifest(REPO)["configs"]
                 if c["name"] == "qwen3-next-80b-a3b")
    assert sorted(entry["reduced"]) == differs and entry["source"] == SOURCE
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (
        4, 32, 18992)
    assert config["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                   "vocab_size": 151936}
    # the floors of a model_config PR: a whole period and 4 layers, >= 8 experts, >= 1/8 vocab
    assert config["num_hidden_layers"] % config["full_attention_interval"] == 0
    assert config["num_experts"] >= 8 and config["vocab_size"] * 8 >= 151936
    # the deployment beside the held count: rank 0 of 16 holds experts 0-31
    assert config["expert_share"] == {"rank": 0, "of": 16}
    assert config["num_experts"] * config["expert_share"]["of"] == 512
    for key in ("deployment", "distorts", "initial_logit_variance_why"):
        assert config[key]
    assert "multi_token_prediction" in config["assumed"] and "0.625" in config["distorts"]
    assert config["program_flags"] == ["--model_size", "qwen3-next-80b-a3b", "--num_layers", "4",
                                       "--vocab_size", "18992", "--moe_share", "0/16"]
    # the preset runs the widths the file states (what check_widths holds a run to)
    from galvatron_tpu.models.modeling import PRESETS

    preset = PRESETS["qwen3-next-80b-a3b"].replace(num_layers=4, vocab_size=18992,
                                                   moe_share=(0, 16))
    harness.check_widths(preset, config)
    arch = reference.load(REPO, "qwen3_next")
    assert [("gdn" if k == "linear_attention" else "attention") for k in arch.kinds(config)] == list(
        preset.kinds)
    assert (preset.kv_heads, preset.head_dim, preset.rotary_fraction, preset.rope_theta,
            preset.norm_eps, preset.max_seq_len, preset.expert_ffn, preset.moe_shared_ffn_dim,
            preset.moe_top_k, preset.moe_norm_topk, preset.moe_experts, preset.moe_held) == (
        config["num_key_value_heads"], config["head_dim"], config["partial_rotary_factor"],
        config["rope_theta"], config["rms_norm_eps"], config["max_position_embeddings"],
        config["moe_intermediate_size"], config["shared_expert_intermediate_size"],
        config["num_experts_per_tok"], config["norm_topk_prob"],
        config["published"]["num_experts"], config["num_experts"])
    assert (preset.gdn_key_heads, preset.gdn_value_heads, preset.gdn_key_dim,
            preset.gdn_value_dim, preset.gdn_conv) == tuple(
        config[k] for k in ("linear_num_key_heads", "linear_num_value_heads",
                            "linear_key_head_dim", "linear_value_head_dim",
                            "linear_conv_kernel_dim"))
    assert preset.moe_aux_coef == config["assumed"]["router_aux_loss_coef"] == 0.001
    assert arch.held_range(config, 512) == (preset.moe_first_held, preset.moe_held) == (0, 32)
    assert config["initial_logit_variance"] == pytest.approx(1 / 3)
    assert harness.expected_first_loss(config) == pytest.approx(10.018, abs=1e-3)


def test_traffic_is_the_cell_the_issue_names():
    _, config, traffic = harness.load_cell(REPO, CELL)
    assert (traffic["seq_len"], traffic["global_batch"], traffic["plan"],
            traffic["train_flags"]) == (4096, 4, "single", ["--global_checkpoint", "1"])
    assert traffic["corpus"] == harness.load_cell(REPO, "baichuan-7b_s4096")[2]["corpus"]
    assert traffic["loss_drop_by_step_20"] == 0.5
    # olmoe's token count, so that the two MoE cells' steps compare
    olmoe = harness.load_cell(REPO, "olmoe-1b-7b_s4096")[2]
    assert traffic["seq_len"] * traffic["global_batch"] == olmoe["seq_len"] * olmoe["global_batch"]


def test_flop_and_byte_counts_against_a_hand_count():
    arch = reference.load(REPO, "qwen3_next")
    _, config, _ = harness.load_cell(REPO, CELL)
    h, v, s = 2048, 18992, 4096
    delta = (16 * 2 * 2 * 32.5 * 128  # K K^T and Q K^T, causal half of a chunk of 64, a key head
             + 32 * (2 * 31.5 * 256 + 3 * 2 * 128 * 128 + 2 * 32.5 * 128))  # a value head
    gdn = 2 * h * (8192 + 4096 + 64) + 2 * 4 * 8192 + delta + 2 * 4096 * h
    attn = 2 * h * (2 * 4096 + 2 * 512) + 2 * 4096 * h + 2 * 2 * 4096 * (s * (s + 1) // 2) / s
    moe = 2 * h * 512 + 0.625 * 3 * 2 * h * 512 + 3 * 2 * h * 512 + 2 * h
    head = 2 * h * v
    assert [round(x / 1e6, 2) for x in (delta, gdn, attn, moe, head)] == [
        4.19, 71.63, 88.09, 12.32, 77.79]
    assert arch.held_pairs_per_token(config) == 0.625
    assert arch.fwd_flops_per_token(config, s) == pytest.approx(
        3 * (gdn + moe) + (attn + moe) + head, rel=1e-12)
    # the issue's ~1.3 GFLOP a trained token, ~21 TFLOP a step of 16384 tokens
    assert round(3 * arch.fwd_flops_per_token(config, s) / 1e9, 2) == 1.29
    # the held experts' GEMMs of a step: 10,240 pairs a layer
    assert arch.expert_gemm_flops(config, 16384) == 4 * 9 * 2 * 10240 * h * 512
    rows = 10240 * (2 * h + 3 * 512 + h)
    assert arch.expert_gemm_bytes(config, 16384) == 4 * 2 * (3 * rows + 9 * 32 * h * 512)
    # bound by the bytes of 32 experts' weights, not by 320 rows an expert
    t_flops, t_bytes = (arch.expert_gemm_flops(config, 16384) / 197e12,
                        arch.expert_gemm_bytes(config, 16384) / 819e9)
    assert t_bytes > t_flops and (round(t_flops * 1e3, 2), round(t_bytes * 1e3, 2)) == (3.92, 5.25)
    # the delta rules of a step: 3 layers
    assert arch.gdn_scan_flops(config, 16384) == pytest.approx(3 * 3 * 16384 * delta)
    ins = 2 * 2048 + 4096 + 64
    assert arch.gdn_scan_bytes(config, 16384) == 3 * 16384 * 2 * ((ins + 4096) + (2 * ins + 4096))
    t_flops, t_bytes = (arch.gdn_scan_flops(config, 16384) / 197e12,
                        arch.gdn_scan_bytes(config, 16384) / 819e9)
    assert (round(t_flops * 1e3, 2), round(t_bytes * 1e3, 2)) == (3.14, 3.96)


# -- the four metrics -------------------------------------------------------------

def _op(start, end, op_name, name="fusion.1", category="fusion:kLoop"):
    return scoped.ScopedOp(float(start), float(end), name, category, op_name)


J = "jit(train_step)/"
F = J + "layer_0/jit(_decoder_layer_once)/jvp(checkpoint)/gdn/"
B = J + "transpose(jvp(layer_0))/jit(_decoder_layer_once)/transpose(jvp(checkpoint))/gdn/"
#: one step by hand: 100 under gdn (in_proj 10 + 20, conv 2 + 4, scan 8 + 36, gate_norm
#: 1 + 3, out_proj 5 + 10, 1 under gdn alone), and work that is not the mixer's
HAND = [
    _op(0, 10, F + "in_proj/dot_general:"),
    _op(10, 12, F + "conv/mul:"),
    _op(12, 20, F + "scan/triangular_solve:"),
    _op(20, 21, F + "gate_norm/mul:"),
    _op(21, 26, F + "out_proj/dot_general:"),
    _op(26, 46, B + "in_proj/dot_general:"),
    _op(46, 50, B + "conv/mul:"),
    _op(50, 70, B + "scan/dot_general:"),
    _op(70, 86, B.replace("transpose(jvp(checkpoint))", "rematted_computation") + "scan/exp:"),
    _op(86, 89, B + "gate_norm/mul:"),
    _op(89, 99, B + "out_proj/dot_general:"),
    _op(99, 100, B + "reshape:"),
    _op(100, 130, J + "layer_3/jit(_decoder_layer_once)/jvp(checkpoint)/attn/gate/mul:"),
    _op(130, 150, J + "layer_0/jit(_decoder_layer_once)/jvp(checkpoint)/mlp/shared_expert/dot:"),
    _op(150, 160, J + "optimizer/scan_like_name/add:"),  # the optimizer is not the mixer
]


def _ctx(sops, said, records=()):
    _, config, traffic = harness.load_cell(REPO, CELL)
    return {"_scoped_device0": sops, "n_profiled": 1, "say": said.append, "chips": 1,
            "config": config, "arch": reference.load(REPO, "qwen3_next"), "traffic": traffic,
            "records": list(records),
            "peaks": {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}}


def test_metrics_on_a_hand_made_step():
    said = []
    assert _metric("gdn_ms_per_step").compute(_ctx(HAND, said)) == pytest.approx(100 / 1e6)
    text = "\n".join(said)
    assert "gdn scope scan: forward" in text and "gdn scope other:" in text
    assert _metric("gdn_scan_ms_per_step").compute(_ctx(HAND, [])) == pytest.approx(50 / 1e6)
    # 44 ns under scan against 3.95 ms of bytes: the arithmetic, not a device number
    roof = _metric("gdn_scan_roofline").compute(_ctx(HAND, said))
    ins = 2 * 2048 + 4096 + 64
    assert roof == pytest.approx(100 * (3 * 16384 * 2 * (3 * ins + 2 * 4096) / 819e9) / 44e-9)
    assert any("bound by memory" in s for s in said)
    from benchmark.metrics import _gdn

    split = _gdn.split_ns(HAND)
    assert split[("scan", "forward")] == 8 and split[("scan", "backward")] == 36
    assert split[("other", "backward")] == 1 and _gdn.under(split) == 100
    assert _gdn.gdn_scope(J + "layer_3/attn/out_proj/dot_general:") is None  # attention's
    # the MoE metrics read the same step: the shared expert is under mlp, beside the four scopes
    from benchmark.metrics import _moe

    assert _moe.moe_scope(HAND[-2].op_name) == "other"


def test_held_pairs_counter_reads_the_records():
    recs = [{"moe_held_pairs_per_token": x, "moe_load_max_over_mean": 2.0, "moe_aux_loss": 10.0}
            for x in (0.5, 0.625, 0.75)]
    said = []
    assert _metric("moe_held_pairs_per_token").compute(_ctx(None, said, recs)) == 0.625
    assert "first 0.5000" in said[0] and "last 0.7500" in said[0]
    # a model that holds all its experts logs none (olmoe's records): left out
    assert _metric("moe_held_pairs_per_token").compute(
        _ctx(None, [], [{"moe_load_max_over_mean": 2.0}])) is None


def test_metrics_leave_themselves_out_without_the_scopes():
    """A Transformer's step, a state-space stack's, or a parent's: nothing under ``gdn``."""
    other = [_op(0, 10, J + "jvp(layer_0)/attn/out_proj/dot_general:"),
             _op(10, 20, J + "jvp(layer_1)/ssm/scan/dot_general:"),
             _op(20, 30, J + "jvp(head)/mul:")]
    for name in NEW_METRICS[:3]:
        assert _metric(name).compute(_ctx(other, [])) is None
        assert _metric(name).compute(_ctx(None, [])) is None
    ctx = _ctx(HAND, [])
    ctx["arch"] = reference.load(REPO, "granitemoehybrid")  # counts another scan
    assert _metric("gdn_scan_roofline").compute(ctx) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_is_declared_for_the_one_cell(name):
    manifest = harness.load_manifest(REPO)
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    mod = _metric(name)
    assert entry["workloads"][0] == CELL  # a later cell may be appended behind it
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == (
        mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES)
    assert entry["layer"] in {m["layer"] for m in manifest["per_layer"][:30]}  # a layer it has


def test_the_cell_joins_no_list_but_its_own_metrics_and_the_rate():
    """ISSUE 47 also names the four ``moe_*`` lists of PR 28. They stay as they
    are: ``tests/benchmark/test_benchmark_olmoe.py`` holds each of them to its one
    cell, and a PR that adds a configuration edits no file the benchmark has
    (PERF.md §7: a ``benchmark`` PR appends the cell there; the reference module
    already counts the held share's GEMMs for their readers)."""
    manifest = harness.load_manifest(REPO)
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL) == 6 and [c["name"] for c in manifest["configs"]].index(
        "qwen3-next-80b-a3b") == 4
    listed = sorted(e["name"] for e in manifest["end_to_end"] + manifest["per_layer"]
                    if CELL in e.get("workloads", []))
    assert listed == sorted(NEW_METRICS + ["tokens_per_s_per_chip"])
    rate = next(e for e in manifest["end_to_end"] if e["name"] == "tokens_per_s_per_chip")
    assert rate["workloads"].index(CELL) == 5  # appended, nothing before it moved


# -- the whole cell at a tiny size ------------------------------------------------

TINY = {
    "model_type": "qwen3_next", "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000, "rms_norm_eps": 1e-06,
    "full_attention_interval": 4, "num_hidden_layers": 4, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 4, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "published": {"num_experts": 16},
    "expert_share": {"rank": 1, "of": 4}, "tie_word_embeddings": False, "vocab_size": 256,
    "initial_logit_variance": 1 / 3,
    "program_flags": ["--model_size", "qwen3-next-80b-a3b", "--num_layers", "4", "--hidden_size",
                      "64", "--num_heads", "4", "--num_kv_heads", "2", "--ffn_dim", "96",
                      "--vocab_size", "256", "--moe_experts", "16", "--moe_share", "1/4"],
}
TINY_TRAFFIC = {
    "seq_len": 128, "global_batch": 8, "plan": "single",
    "train_flags": ["--global_checkpoint", "1", "--lr", "1e-2"],
    "corpus": {"tokens": 65536, "doc_len": 256, "zipf_a": 1.0, "follow_p": 0.5},
    "loss_drop_by_step_20": 0.2, "why": "tiny CPU rehearsal",
}


def test_whole_cell_tiny(tmp_path, monkeypatch):
    """The new cell's path through the harness at a tiny size: corpus, one
    ``train()`` call on the preset under full-layer recomputation (three Gated
    DeltaNet layers and the gated attention layer, two chunks a sequence, rank 1
    of 4 holding 4 of 16 experts), the float32 reference check (recurrent delta
    rule, the same held range), the traced form. The head, DeltaNet and expert
    sizes have no flag (the issue adds none), so the test narrows the preset."""
    from galvatron_tpu.models.modeling import PRESETS

    monkeypatch.setitem(PRESETS, "qwen3-next-80b-a3b", PRESETS["qwen3-next-80b-a3b"].replace(
        attn_head_dim=16, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16, gdn_value_dim=16,
        moe_top_k=4, moe_ffn_dim=32, moe_shared_ffn_dim=32))
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = harness.load_manifest(REPO)
    with open(os.path.join(root, "benchmark/configs/tiny-qwen3-next.json"), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(root, "benchmark/traffic/tiny.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    manifest["configs"].append({"name": "tiny-qwen3-next", "source": "test", "reduced": [],
                                "file": "benchmark/configs/tiny-qwen3-next.json", "why": "test"})
    manifest["workloads"].append({"name": "tiny-qwen3-next_tiny", "config": "tiny-qwen3-next",
                                  "traffic": "tiny", "chips": 1, "why": "test"})
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        # the cell's own lists, and PR 28's reader of the router's load, which a
        # `benchmark` PR is to give the cell (its records carry the key already)
        if CELL in entry.get("workloads", []) or entry["name"] == "moe_load_imbalance":
            entry["workloads"].append("tiny-qwen3-next_tiny")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    end = harness.run_cell(root, "tiny-qwen3-next_tiny", seed=2147483659, seconds=0.5, trace=True,
                           out_dir=str(tmp_path / "run"), t_start=time.time(), min_steps=24)
    assert end["correct"] is True and end["failed"] == 0 and end["attempted"] >= 24
    got = set(end["metrics"])
    assert {"compile_s", "step_ms_p50", "runtime_build_s", "moe_load_imbalance",
            "moe_held_pairs_per_token"} <= got
    # 4 choices a token over 16 experts, 4 of them held: about 1 pair a token
    assert 0.3 < end["metrics"]["moe_held_pairs_per_token"]["value"] < 3.0
    # nothing that needs a device trace exists on the CPU
    assert not got & {"gdn_ms_per_step", "gdn_scan_ms_per_step", "gdn_scan_roofline",
                      "moe_expert_gemm_roofline"}
    # the run's build_runtime span says what the stack holds and which bodies it takes
    with open(str(tmp_path / "run" / "spans.json")) as f:
        spans = json.load(f)["traceEvents"]
    build = [e for e in spans if e.get("name") == "build_runtime"]
    assert build and build[0]["args"]["layer_kinds"] == {"gdn": 3, "attention": 1}
    assert build[0]["args"]["gdn_scan_path"] == {"fused": 0, "plain": 3}
    assert build[0]["args"]["gdn_conv_path"] == {"fused": 0, "plain": 3}
    assert build[0]["args"]["ssm_scan_path"] == {"fused": 0, "plain": 0}
