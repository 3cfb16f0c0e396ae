"""What PR 28 adds to the benchmark, on the CPU: the OLMoE configuration against
its catalog row, the traffic file, the reference module's counts against a
hand count, the four MoE metrics on a hand-made scoped step and on one
recorded on the chip, and the whole cell at a tiny size through the harness.
No number here is a device number; the recorded step's are quoted from the
chip run that made it."""

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

CELL = "olmoe-1b-7b_s4096"
#: the ``config`` of the catalog row OLMoE-1B-7B-0125-Instruct (model-configs guide)
CATALOG = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "tie_word_embeddings": False, "vocab_size": 50304,
}
SOURCE = "https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json"


def _metric(name):
    path = os.path.join(REPO, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("_t_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_configuration_is_the_catalog_row_with_the_depth_cut():
    cell, config, traffic = harness.load_cell(REPO, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("olmoe-1b-7b", "s4096_b4", 1)
    assert config["source"] == SOURCE
    differs = sorted(k for k, v in CATALOG.items() if config[k] != v)
    assert differs == ["num_hidden_layers"] == sorted(config["reduced"]) == sorted(config["published"])
    assert (config["num_hidden_layers"], config["published"]["num_hidden_layers"]) == (1, 16)
    assert {"intermediate_size", "router_aux_loss_coef", "router_z_loss"} <= set(config["assumed"])
    assert config["assumed"]["router_aux_loss_coef"] == 0.01
    assert config["program_flags"] == ["--model_size", "olmoe-1b-7b", "--num_layers", "1"]
    # the preset runs the widths the file states (what check_widths holds a run to)
    from galvatron_tpu.models.modeling import PRESETS

    preset = PRESETS["olmoe-1b-7b"].replace(num_layers=1)
    harness.check_widths(preset, config)
    assert (preset.moe_experts, preset.moe_top_k, preset.moe_aux_coef, preset.norm_eps,
            preset.rope_theta, preset.max_seq_len, preset.qk_norm, preset.moe_router) == (
                config["num_experts"], config["num_experts_per_tok"], 0.01,
                config["rms_norm_eps"], config["rope_theta"],
                config["max_position_embeddings"], True, "softmax_topk")
    assert harness.expected_first_loss(config) == pytest.approx(10.992, abs=1e-3)


def test_traffic_is_the_cell_the_issue_names():
    _, config, traffic = harness.load_cell(REPO, CELL)
    assert (traffic["seq_len"], traffic["global_batch"], traffic["plan"], traffic["train_flags"],
            traffic["loss_drop_by_step_20"]) == (4096, 4, "single", [], 0.5)
    other = harness.load_cell(REPO, "baichuan-7b_s4096")[2]
    assert traffic["corpus"] == other["corpus"]
    pairs = traffic["seq_len"] * traffic["global_batch"] * config["num_experts_per_tok"]
    assert pairs == 131072 and pairs // config["num_experts"] == 2048


def test_flop_and_byte_counts_against_a_hand_count():
    arch = reference.load(REPO, "olmoe")
    _, config, _ = harness.load_cell(REPO, CELL)
    h, f, v, s = 2048, 1024, 50304, 4096
    parts = {"projections": 2 * 4 * h * h, "pairs": 2 * 2 * h * (s * (s + 1) // 2) / s,
             "router": 2 * h * 64, "experts": 8 * 3 * 2 * h * f, "head": 2 * h * v}
    assert [round(parts[k] / 1e6, 1) for k in ("projections", "pairs", "router", "experts", "head")
            ] == [33.6, 16.8, 0.3, 100.7, 206.0]
    assert arch.fwd_flops_per_token(config, s) == pytest.approx(sum(parts.values()), rel=1e-12)
    assert round(arch.fwd_flops_per_token(config, s) / 1e6, 1) == 357.3
    # 16 layers: everything but the head, 16 times
    full = dict(config, num_hidden_layers=16)
    assert arch.fwd_flops_per_token(full, s) == pytest.approx(
        16 * (sum(parts.values()) - parts["head"]) + parts["head"], rel=1e-12)
    # the 9 expert GEMMs of a step: 4.95 TFLOP, 25.1 ms at the v5e's bf16 peak
    assert arch.expert_gemm_flops(config, 16384) == 9 * 2 * 131072 * h * f
    assert arch.expert_gemm_flops(config, 16384) / 197e12 == pytest.approx(25.11e-3, rel=1e-3)
    rows = 131072 * (2 * h + 3 * f + h)  # x twice, g, u, h, y
    assert arch.expert_gemm_bytes(config, 16384) == 2 * (3 * rows + 9 * 64 * h * f)
    assert arch.expert_gemm_bytes(config, 16384) / 819e9 < arch.expert_gemm_flops(config, 16384) / 197e12


# -- the four metrics --------------------------------------------------------

def _op(start, end, op_name, name="fusion.1", category="fusion:kLoop"):
    return scoped.ScopedOp(float(start), float(end), name, category, op_name)


J = "jit(train_step)/"
#: one step by hand: 100 under mlp (router 4 + 6, dispatch 10 + 5, experts 20 + 40,
#: combine 5 + 8, 2 of the norm's recomputation under mlp but under none of the four),
#: and work that is not the MLP's
HAND = [
    _op(0, 4, J + "jvp(layer_0)/mlp/router/dot_general:"),
    _op(4, 14, J + "jvp(layer_0)/mlp/dispatch/sort:"),
    _op(14, 34, J + "jvp(layer_0)/mlp/experts/moe_gmm/pallas_call:", "moe_gmm.1", "mosaic-kernel"),
    _op(34, 39, J + "jvp(layer_0)/mlp/combine/custom_vjp_call/gather:"),
    _op(39, 79, J + "transpose(jvp(layer_0))/mlp/experts/moe_tgmm/pallas_call:", "moe_tgmm.1",
        "mosaic-kernel"),
    _op(79, 87, J + "transpose(jvp(layer_0))/mlp/combine/gather:"),
    _op(87, 92, J + "transpose(jvp(layer_0))/mlp/dispatch/gather:"),
    _op(92, 98, J + "transpose(jvp(layer_0))/mlp/router/dot_general:"),
    _op(98, 100, J + "transpose(jvp(layer_0))/mlp/mul:"),
    _op(100, 130, J + "jvp(layer_0)/attn/qk_norm/mul:"),
    _op(130, 160, J + "jvp(head)/dot_general:"),
    _op(160, 170, J + "optimizer/experts_like_name/add:"),  # the optimizer is not the MLP
]
CONFIG = {"num_hidden_layers": 1, "num_experts_per_tok": 8, "num_experts": 64,
          "hidden_size": 2048, "intermediate_size": 1024}


def _ctx(sops, said, records=()):
    return {"_scoped_device0": sops, "n_profiled": 1, "say": said.append, "chips": 1,
            "records": list(records), "config": CONFIG, "arch": reference.load(REPO, "olmoe"),
            "traffic": {"global_batch": 4, "seq_len": 4096},
            "peaks": {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}}


def test_metrics_on_a_hand_made_step():
    said = []
    assert _metric("moe_ms_per_step").compute(_ctx(HAND, said)) == pytest.approx(100 / 1e6)
    text = "\n".join(said)
    assert "moe scope experts: forward 0.000, backward 0.000" in text  # ns shown as ms
    assert _metric("moe_expert_gemm_share").compute(_ctx(HAND, [])) == pytest.approx(60.0)
    # 60 ns under experts against 25.11 ms of operations: the arithmetic, not a device number
    roof = _metric("moe_expert_gemm_roofline").compute(_ctx(HAND, said))
    assert roof == pytest.approx(100 * (9 * 2 * 131072 * 2048 * 1024 / 197e12) / 60e-9)
    assert any("bound by compute" in s for s in said)


def test_the_flash_readers_count_flash_kernels_alone():
    """A step with grouped-GEMM kernels beside the attention's: the whole-step
    flash readers take the ``flash_`` names and no other Mosaic call (until PR 41
    they read 40.5 ms in this cell where ``flash_fwd`` + ``flash_bwd`` are 8.8)."""
    from benchmark.lib import flops, xplane

    ops = [xplane.Op(0.0, 3e6, "flash_fwd_qkv.1", "mosaic-kernel"),
           xplane.Op(3e6, 9e6, "flash_bwd_blocked.1", "mosaic-kernel"),
           xplane.Op(9e6, 29e6, "moe_gmm.1", "mosaic-kernel"),
           xplane.Op(29e6, 49e6, "moe_tgmm.2", "mosaic-kernel"),
           xplane.Op(49e6, 50e6, "fusion.7", "fusion:kLoop")]
    cfg = dict(CONFIG, num_attention_heads=16)
    said = []
    ctx = dict(_ctx(None, said), trace={"devices": {0: ops}}, config=cfg)
    assert _metric("flash_attention_ms_per_step").compute(ctx) == pytest.approx(9.0)
    assert any("2 flash_* calls a step on device 0 (flash_bwd_blocked, flash_fwd_qkv)" in s
               for s in said)
    shape = dict(batch=4, heads=16, seq_len=4096, head_dim=128, layers=1)
    least = max(flops.flash_attention_flops(**shape) / 197e12,
                flops.flash_attention_bytes(**shape) / 819e9)
    assert _metric("flash_attention_roofline").compute(ctx) == pytest.approx(100 * least / 9e-3)
    # a step with no flash kernel at all: both leave themselves out, never a 0
    bare = dict(ctx, trace={"devices": {0: ops[2:]}})
    assert _metric("flash_attention_ms_per_step").compute(bare) is None
    assert _metric("flash_attention_roofline").compute(bare) is None


def test_metrics_leave_themselves_out_without_the_scopes():
    """A dense model's step (or a parent's): ``mlp`` without the four scopes."""
    dense = [_op(0, 10, J + "jvp(layer_0)/mlp/dot_general:"), _op(10, 20, J + "jvp(head)/mul:")]
    for name in ("moe_ms_per_step", "moe_expert_gemm_share", "moe_expert_gemm_roofline"):
        assert _metric(name).compute(_ctx(dense, [])) is None
        assert _metric(name).compute(_ctx(None, [])) is None
    assert _metric("moe_load_imbalance").compute(_ctx(dense, [], [{"loss": 1.0}])) is None


def test_load_imbalance_is_the_median_of_the_windows_records():
    recs = [{"moe_load_max_over_mean": v, "moe_aux_loss": 8.1} for v in (2.0, 1.5, 1.7, 9.0, 1.6)]
    said = []
    assert _metric("moe_load_imbalance").compute(_ctx(None, said, recs)) == pytest.approx(1.7)
    assert "first 2.000" in said[0] and "aux loss first 8.1000" in said[0]


@pytest.mark.parametrize("name", ["moe_ms_per_step", "moe_expert_gemm_share",
                                  "moe_expert_gemm_roofline", "moe_load_imbalance"])
def test_metric_is_declared_for_the_one_cell(name):
    manifest = harness.load_manifest(REPO)
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    mod = _metric(name)
    assert entry["workloads"] == [CELL]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == (
        mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES)
    assert entry["layer"] in {m["layer"] for m in manifest["per_layer"][:30]}  # a layer it has


def test_recorded_step():
    """Device 0 of one profiled step of ``olmoe-1b-7b_s4096`` as ``scoped.py``
    read it on the chip (recorded_moe_step.json): the reductions on real names."""
    with open(os.path.join(HERE, "recorded_moe_step.json")) as f:
        rec = json.load(f)
    sops = [scoped.ScopedOp(*o) for o in rec["ops"]]
    expect = rec["expect"]
    assert len(sops) == expect["n"]
    said = []
    ms = _metric("moe_ms_per_step").compute(_ctx(sops, said))
    assert ms == pytest.approx(expect["moe_ms"], rel=1e-9)
    share = _metric("moe_expert_gemm_share").compute(_ctx(sops, []))
    assert share == pytest.approx(expect["experts_share"], rel=1e-9)
    roof = _metric("moe_expert_gemm_roofline").compute(_ctx(sops, []))
    assert roof == pytest.approx(expect["roofline"], rel=1e-9) and roof < 100
    # every grouped GEMM is a named kernel under mlp/experts, 3 forward and 6 backward
    gemms = [o for o in sops if o.name.startswith(("moe_gmm", "moe_tgmm"))]
    assert len(gemms) == 9 and all("/mlp/" in o.op_name and "experts" in o.op_name for o in gemms)
    assert sum(scoped.is_backward(o.op_name) for o in gemms) == 6
    # the four scopes and the norm's recomputation account for all of mlp's time
    from benchmark.metrics import _moe

    split = _moe.split_ns(sops)
    assert {s for s, _ in split} >= set(_moe.MOE_SCOPES)
    mlp_ns = sum(o.end - o.start for o in sops if "mlp" in scoped.scopes_of(o.op_name)
                 and scoped.phase_of(o.op_name) in ("forward", "backward"))
    assert _moe.under(split) == pytest.approx(mlp_ns)
    assert any("qk_norm" in o.op_name for o in sops)


# -- the whole cell at a tiny size --------------------------------------------

TINY = {
    "model_type": "olmoe", "hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 2,
    "num_hidden_layers": 1, "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "num_experts": 16, "num_experts_per_tok": 8, "tie_word_embeddings": False,
    "vocab_size": 256, "initial_logit_variance": 1 / 3,
    "program_flags": ["--model_size", "olmoe-1b-7b", "--num_layers", "1", "--hidden_size", "64",
                      "--num_heads", "2", "--ffn_dim", "32", "--vocab_size", "256",
                      "--moe_experts", "16"],
}
TINY_TRAFFIC = {
    "seq_len": 64, "global_batch": 8, "plan": "single", "train_flags": ["--lr", "1e-2"],
    "corpus": {"tokens": 65536, "doc_len": 256, "zipf_a": 1.0, "follow_p": 0.5},
    "loss_drop_by_step_20": 0.3, "why": "tiny CPU rehearsal",
}


def test_whole_cell_tiny(tmp_path):
    """The new cell's path through the harness at a tiny size: corpus, one
    ``train()`` call on the preset, the float32 reference check, the traced
    form with ``moe_load_imbalance`` read from the records."""
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = harness.load_manifest(REPO)
    with open(os.path.join(root, "benchmark/configs/tiny-olmoe.json"), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(root, "benchmark/traffic/tiny.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    manifest["configs"].append({"name": "tiny-olmoe", "source": "test", "reduced": [],
                                "file": "benchmark/configs/tiny-olmoe.json", "why": "test"})
    manifest["workloads"].append({"name": "tiny-olmoe_tiny", "config": "tiny-olmoe",
                                  "traffic": "tiny", "chips": 1, "why": "test"})
    # the lists a one-chip training cell is in (throughput names its cells), and its own
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if entry["name"].startswith("moe_") or "baichuan-7b_s512" in entry.get("workloads", []):
            entry["workloads"].append("tiny-olmoe_tiny")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    end = harness.run_cell(root, "tiny-olmoe_tiny", seed=2147483659, seconds=0.5, trace=True,
                           out_dir=str(tmp_path / "run"), t_start=time.time(), min_steps=24)
    assert end["correct"] is True and end["failed"] == 0 and end["attempted"] >= 24
    got = set(end["metrics"])
    assert {"moe_load_imbalance", "compile_s", "step_ms_p50"} <= got
    assert 1.0 <= end["metrics"]["moe_load_imbalance"]["value"] <= 2.0  # top-8 of 16
    # nothing that needs a device trace exists on the CPU
    assert not got & {"moe_ms_per_step", "moe_expert_gemm_share", "moe_expert_gemm_roofline"}
