"""What PR 68 adds to the benchmark, on the CPU: the nemotron-3-nano-30b-a3b configuration
against its catalog row key by key, the serving mix, the reference module's counts against
hand counts and against the tree the program builds at the cut, the seven new readers (four
of a decode step, three of a prompt chunk) on a hand-made traced window and on a recorded
step of another stack, the manifest's appends.
No number here is a device number."""

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

CELL = "nemotron-3-nano-30b-a3b_serve_chat_above_knee"
CONFIG = "nemotron-3-nano-30b-a3b"
SOURCE = "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json"
#: the serving cells the benchmark had before this PR, in its order
SERVING = ["opt-1.3b_serve_above_knee", "sarvam-105b_serve_long_above_knee",
           "smallthinker-21b-a3b_serve_long_above_knee", "lfm2-24b-a2b_serve_long_above_knee",
           "trinity-large-preview_serve_agent_above_knee", "dots3-note-prev_serve_reason_above_knee"]
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
#: the ``config`` of the catalog row NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (model-configs guide)
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 2688, "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 64, "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 52, "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072,
}
NEW_METRICS = ["ssm_decode_ms_per_step", "ssm_step_ms_per_step", "ssm_state_hbm_roofline",
               "ssm_step_roofline"]
#: the readers of a prompt chunk: they answer None where a profile holds no chunk, so they
#: carry a list (this cell alone: six traced runs of six held a chunk, PERF.md section 6)
CHUNK_METRICS = ["ssm_prefill_chunk_ms", "ssm_chunk_scan_ms", "ssm_chunk_scan_roofline"]
ARCH = reference.load(REPO, "nemotron_h")
H, F, FS = 2688, 1856, 3712
MAMBA = H * 10304 + 4096 * H
MAMBA_REST = 6144 * 5 + 3 * 64 + 4096
ATTN = H * (4096 + 256 + 256) + 4096 * H
ROUTER = H * 128 + 128


def _metric(name):
    path = os.path.join(REPO, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("_t_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config():
    return harness.load_cell(REPO, CELL)[1]


# -- the configuration ----------------------------------------------------------------


def test_the_catalog_row_is_the_guides():
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(rows):
        pytest.skip("the model-configs guide is not on this machine")
    with open(rows) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert row["config"] == CATALOG and row["source_url"] == SOURCE


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_configuration_holds_the_catalog_row_key_by_key(key):
    config = _config()
    cut = {"num_hidden_layers": 15, "n_routed_experts": 32, "vocab_size": 32768}
    assert config[key] == cut.get(key, CATALOG[key])
    assert (key in config["reduced"]) == (key in cut)


def test_configuration_states_the_cut_the_deployment_and_what_is_assumed():
    cell, config, _ = harness.load_cell(REPO, CELL)
    assert cell["chips"] == 1 and config["source"] == SOURCE
    assert set(config["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert config["published"] == {"num_hidden_layers": 52, "n_routed_experts": 128,
                                   "vocab_size": 131072}
    assert config["hybrid_override_pattern"] == PATTERN and len(PATTERN) == 52  # whole
    # depth in published blocks: 26 of 52, the program's 15 layers (``check_widths`` holds
    # ``num_hidden_layers`` to the program's layer count)
    assert config["published_blocks"] == 26 and ARCH.pattern(config) == PATTERN[:26]
    assert ARCH.block_counts(config) == {"M": 12, "*": 3, "E": 11}
    assert config["expert_share"] == {"rank": 0, "of": 4}
    assert config["n_routed_experts"] * 4 == CATALOG["n_routed_experts"]
    assert config["vocab_size"] * 4 == CATALOG["vocab_size"]
    assert {"no_rotary", "grouped_gate_norm", "no_dt_clamp", "state_types", "grouped_routing",
            "initializer", "slot_length"} <= set(config["assumed"])
    assert "8 chips" in config["deployment"] and "two pipeline stages" in config["deployment"]
    assert "3 rows an expert" in config["distorts"] and "one stage" in config["distorts"]
    flags = config["program_flags"]
    assert flags[flags.index("--moe_share") + 1] == "0/4"
    assert flags[flags.index("--param_dtype") + 1] == "bf16"
    assert flags[flags.index("--seq_length") + 1] == "8192"


def test_the_program_runs_the_widths_the_file_states():
    import jax.numpy as jnp

    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args
    from galvatron_tpu.models.modeling import blocks_to_layers

    config = _config()
    cfg = model_config_from_args(initialize_galvatron("serve", list(config["program_flags"])))
    harness.check_widths(cfg, config)
    assert (cfg.kv_heads, cfg.head_dim, cfg.expert_ffn, cfg.moe_top_k) == (
        config["num_key_value_heads"], config["head_dim"], config["moe_intermediate_size"],
        config["num_experts_per_tok"])
    assert cfg.moe_held == config["n_routed_experts"] and cfg.moe_experts == 128
    assert cfg.moe_shared_ffn_dim == config["moe_shared_expert_intermediate_size"]
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv,
            cfg.ssm_chunk) == tuple(config[k] for k in (
                "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
                "chunk_size"))
    assert cfg.ssm_heads * cfg.ssm_head_dim == config["expand"] * 2048  # d_inner 4096
    assert cfg.norm_eps == config["layer_norm_epsilon"] and cfg.moe_route_scale == 2.5
    assert cfg.act_fn == config["mlp_hidden_act"] and cfg.pos_embed == "nope"
    assert cfg.param_dtype == jnp.bfloat16 and cfg.max_seq_len == 8192
    # the program's 15 layers ARE the configuration's 26 blocks
    kinds, mlps = blocks_to_layers(config["hybrid_override_pattern"], config["published_blocks"])
    assert (cfg.kinds, cfg.mlp_layers) == (kinds, tuple(bool(m) for m in mlps))
    assert len(kinds) == config["num_hidden_layers"]


def test_traffic_is_the_mix_the_issue_names():
    _, config, spec = harness.load_cell(REPO, CELL)
    assert spec["kind"] == "serve" and "knee" not in spec
    lengths = spec["lengths"]
    assert (lengths["grid"], lengths["pair_stride"], lengths["max_total"]) == (16, 7, 8000)
    assert lengths["prompt"] == {"median": 1024, "sigma": 0.8, "lo": 128, "hi": 4096}
    assert lengths["output"]["median"] == 512 and lengths["output"]["sigma"] in (0.6, 0.4)
    assert (lengths["output"]["lo"], lengths["output"]["hi"]) == (128, 2048)
    assert spec["sampling"] == {"temperature": 0.8, "top_p": 0.95, "greedy_every": 4,
                                "greedy_temperature": 0.0001}
    assert spec["corpus"] == {"tokens": 262144, "zipf_a": 1.0, "follow_p": 0.5}
    assert spec["arrivals"]["process"] == "exponential_gap_quantiles"
    assert spec["arrivals"]["burst_at_start"] == 128
    flags = dict(zip(spec["serve_flags"][::2], spec["serve_flags"][1::2]))
    assert flags == {"--num_slots": "64", "--prefill_chunk": "1024", "--max_queue": "4096",
                     "--request_ttl_s": "0"}
    assert spec["window"] == {"opens": "all_slots_used", "settle_s": 20, "first_token_grace_s": 0}
    assert (spec["correct"]["requests"], spec["correct"]["capture_every"],
            spec["correct"]["rows_kept"]) == (4, 5, 4096)
    shapes = traffic_lib.grid(spec)
    assert max(s["prompt_len"] + s["output_len"] for s in shapes) <= 8000
    # half of the prompts cross a chunk's end: the state is handed on
    assert sum(s["prompt_len"] > 1024 for s in shapes) == 8
    mean = traffic_lib.mean_output_len(spec)
    assert 500 < mean < 700  # decode-heavy: hundreds of tokens an answer
    # the rate is 2.0 x K64 over the mix's mean answer, K64 and its readings in ``why``
    assert "2.0" in spec["why"] and "K64" in spec["why"] and f"{mean:.1f}" in spec["why"]
    assert 8192 % int(flags["--prefill_chunk"]) == 0


# -- the counts ---------------------------------------------------------------------------


def test_flop_count_against_a_hand_count():
    config = _config()
    routed = ROUTER + 2 * H * FS + 2 * H * F * 6 / 4
    body = 12 * MAMBA + 3 * ATTN + 11 * routed
    scan = 12 * 6.0 * 64 * 64 * 128
    for s in (1, 1024, 8192):
        want = 2.0 * (body + H * 32768) + scan + 2 * 2.0 * 32 * 128 * 3 * (s + 1) / 2
        assert ARCH.fwd_flops_per_token(config, s) == pytest.approx(want)


def test_served_counts_against_a_hand_count():
    config = _config()
    served = ARCH.served_params(config)
    body = (12 * (MAMBA + MAMBA_REST + H) + 3 * (ATTN + H) + 11 * (ROUTER + 2 * H * FS + H))
    assert served == {"a_forward": body + H + H * 32768, "a_token": H}
    dims = ARCH.serve_dims(config)
    assert dims["layers"] == 26 and dims["head_dim"] == pytest.approx(128 * 3 / 26)
    assert flops.kv_bytes_per_position(dims) == pytest.approx(3 * 1024)
    assert ARCH.least_bytes_per_position(config, 5000) == 3 * 1024
    assert ARCH.expert_layers(config) == 11
    assert ARCH.expert_step_bytes(config, 30.4) == pytest.approx(2 * 30.4 * 11 * 2 * H * F)
    assert ARCH.decode_attn_bytes(config, 160000, 0, 64, 3, 0) == 1024 * 3 * (160000 + 64)
    # a decode step's Mamba-2 mixers: 12 x (38.75 M weights in bf16 + 64 rows x 2 x 2,134,016 B)
    state = 3 * 6144 * 2 + 64 * 64 * 128 * 4
    assert ARCH.ssm_state_bytes(config) == {"conv": 36864, "scan": 2097152} and state == 2134016
    assert ARCH.ssm_step_bytes(config, 64, 12) == 12 * (2 * (MAMBA + MAMBA_REST) + 2 * 64 * state)
    assert ARCH.ssm_state_step_bytes(config, 64, 12) == 12 * 2 * 64 * state == 3_277_848_576
    # what the kernel `ssm_step` itself moves: the float32 scan state, not the conv tail
    assert ARCH.ssm_scan_step_bytes(config, 64, 12) == 12 * 2 * 64 * 2097152 == 3_221_225_472


def test_the_counts_are_the_tree_the_program_builds_at_the_cut():
    import jax

    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args
    from galvatron_tpu.models import generation, modeling

    config = _config()
    cfg = model_config_from_args(initialize_galvatron("serve", list(config["program_flags"])))
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    total = sum(a.size for a in jax.tree.leaves(shapes))
    served = ARCH.served_params(config)
    # what any forward must read: everything but the routed experts and the embedding
    routed = 11 * 32 * 2 * H * F
    assert served["a_forward"] == total - routed - 32768 * H
    assert round(total / 1e9, 3) == 4.447
    layout = generation.cache_layout(cfg, 8192, 1024)
    # the scan state's 2,097,152 B a row and layer are FLOAT32's (the configuration's
    # ``assumed.state_types``): a program that holds it lower fails HERE, in a file under the
    # benchmark's ``paths``, since the cell's ``correct`` cannot tell (PERF.md section 6)
    assert layout["state_part_bytes"] == ARCH.ssm_state_bytes(config)
    assert layout["state_part_bytes"]["scan"] == 64 * 64 * 128 * 4
    assert layout["bytes_per_position_per_layer"] * layout["full_layers"] == (
        ARCH.least_bytes_per_position(config, 1))
    assert 64 * layout["bytes_per_slot"] == 3 * 64 * 8192 * 1024 + 12 * 64 * 2134016


def test_the_three_shares_of_the_chips_peaks_read_under_100():
    """Over a window of 100 decode steps (64 slots, 2,500 live positions each) and 20 chunks
    of 10 prompts, on the chip's peaks, from this cell's ``serve_dims``: floors (the state's
    bytes have no term there), never over 100."""
    config = _config()
    work = {"decode_tokens": 6400, "decode_positions": 16_000_000, "prefills": 10,
            "prefill_tokens": 20480, "prefill_chunks": 20, "prefill_positions": 20 * 1536,
            "prefill_pairs": 10 * 2048 * 2049 // 2}
    ctx = {"serve": {"work": work, "seconds": 2.3}, "arch": ARCH, "config": config, "chips": 1,
           "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}, "say": print,
           "spans": [{"name": "decode", "args": {}}] * 100}
    assert 0 < _metric("serve_hbm_roofline").compute(ctx) < 100
    assert 0 < _metric("serve_mfu").compute(ctx) < 100


# -- the readers --------------------------------------------------------------------------

D, P = "jit(_decode_step)/", "jit(_prefill_chunk)/"


def _op(start, end, op_name, name="fusion.1"):
    return scoped.ScopedOp(float(start), float(end), name, "fusion:kLoop", op_name, "")


def _window(decode_ops, prefill_ops, counters=True):
    """Two decode executions and one prefill chunk on device 0, and the window's ``decode``
    spans with the engine's counters."""
    execs = [scoped.Execution("_decode_step", 0.0, 1e7, tuple(decode_ops)),
             scoped.Execution("_prefill_chunk", 2e7, 3e7, tuple(prefill_ops)),
             scoped.Execution("_decode_step", 4e7, 5e7, tuple(decode_ops))]
    args = {"active": 64}
    if counters:
        args.update({"kv_cache_bytes_per_position": 1024, "kv_live_positions": 160000,
                     "kv_full_live_positions": 160000, "kv_window_live_positions": 0,
                     "kv_full_read_positions": 200000, "kv_window_read_positions": 0,
                     "kv_full_layers": 3, "kv_window_layers": 0, "state_layers": 12,
                     "state_bytes_per_row": 2134016, "state_conv_bytes_per_row": 36864,
                     "state_scan_bytes_per_row": 2097152, "state_step_bytes": 3277848576})
    said = []
    spans = [{"name": "decode", "start": 0.0, "end": 0.02, "step": None, "args": dict(args)}
             for _ in range(3)]
    return {"serve": {"num_slots": 64, "prefill_chunk": 1024}, "spans": spans, "_executions": execs, "say": said.append,
            "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}, "said": said,
            "arch": ARCH, "config": _config()}


SSM_DECODE = [
    _op(0, 600e3, D + "layer_0/attn/ssm/in_proj/dot_general:"),
    _op(600e3, 620e3, D + "layer_0/attn/ssm/state_read/select_n:"),
    _op(620e3, 700e3, D + "layer_0/attn/ssm/conv/mul:"),
    _op(700e3, 5700e3, D + "layer_0/attn/ssm/step/ssm_step/pallas_call:", "ssm_step.12"),
    _op(5700e3, 5800e3, D + "layer_0/attn/ssm/step/add:"),
    _op(5800e3, 6000e3, D + "layer_0/attn/ssm/gate_norm/mul:"),
    _op(6000e3, 6400e3, D + "layer_0/attn/ssm/out_proj/dot_general:"),
    _op(6400e3, 6430e3, D + "layer_0/attn/ssm/state_write/dynamic_update_slice:"),
    _op(6430e3, 7000e3, D + "layer_0/mlp/experts/moe_gmm_dlhs:"),
    _op(7000e3, 7100e3, D + "layer_0/mlp/experts/relu2/mul:"),
    _op(7100e3, 8000e3, D + "layer_3/attn/full/attn_core/kv_decode:"),
]
SSM_PREFILL = [
    _op(0, 300e3, P + "layer_0/attn/ssm/in_proj/dot_general:"),
    _op(300e3, 320e3, P + "layer_0/attn/ssm/state_read/ssm_state_read/pallas_call:", "ssm_state_read.1"),
    _op(320e3, 900e3, P + "layer_0/attn/ssm/scan/dot_general:"),
    _op(900e3, 1000e3, P + "layer_0/attn/ssm/state_write/ssm_state_write/pallas_call:", "ssm_state_write.1"),
    _op(1000e3, 1400e3, P + "layer_3/attn/full/attn_core/kv_chunk/pallas_call:", "kv_chunk.3"),
]


def test_metrics_on_a_hand_made_window():
    ctx = _window(SSM_DECODE, SSM_PREFILL)
    assert _metric("ssm_decode_ms_per_step").compute(ctx) == pytest.approx(6.43)
    assert _metric("ssm_step_ms_per_step").compute(ctx) == pytest.approx(0.02 + 5.0 + 0.1 + 0.03)
    config = _config()
    least = ARCH.ssm_step_bytes(config, 64, 12)
    assert _metric("ssm_state_hbm_roofline").compute(ctx) == pytest.approx(
        100 * (least / 819e9 * 1e3) / 6.43)
    assert any("12 layers" in line and "Mamba-2 mixers" in line for line in ctx["said"])
    # the kernel by its name: the scan state's 3.22 GB at 819 GB/s = 3.9 ms over the 5.0
    # measured (the conv tail's 57 MB are state_read / state_write's, not the kernel's)
    assert _metric("ssm_step_roofline").compute(ctx) == pytest.approx(
        100 * (3221225472 / 819e9 * 1e3) / 5.0)
    assert 0 < _metric("ssm_step_roofline").compute(ctx) < 100
    # the serving readers the benchmark had read this stack's attention and experts
    assert _metric("full_attn_ms_per_step").compute(ctx) == pytest.approx(0.9)
    assert _metric("serve_expert_ms_per_step").compute(ctx) == pytest.approx(0.67)
    assert _metric("shortconv_ms_per_step").compute(ctx) == 0.0
    assert _metric("state_cache_ms_per_step").compute(ctx) == 0.0


def test_chunk_metrics_on_a_hand_made_window():
    """The three readers of a prompt chunk over the prefill program's scopes; None where the
    profile holds no chunk (why they carry a list), 0 on another stack's chunk."""
    ctx = _window(SSM_DECODE, SSM_PREFILL)
    assert _metric("ssm_prefill_chunk_ms").compute(ctx) == pytest.approx(0.3 + 0.02 + 0.58 + 0.1)
    assert any("scan 0.580" in line and "state_read 0.020" in line for line in ctx["said"])
    assert _metric("ssm_chunk_scan_ms").compute(ctx) == pytest.approx(0.58)
    flops, moved = ARCH.ssm_chunk_scan_work(_config(), 1024, 12)
    # 1,024 positions x 12 layers: 6 x 64 x 64 x 128 operations a position; x, y, B, C in
    # bf16, dt float32, the row's 2 MiB state in and out
    assert flops == 12 * 1024 * 6 * 64 * 64 * 128
    assert moved == 12 * (1024 * ((2 * 4096 + 2 * 1024) * 2 + 4 * 64) + 2 * 2097152)
    assert moved / 819e9 > flops / 197e12  # bound by bytes at these sizes
    assert _metric("ssm_chunk_scan_roofline").compute(ctx) == pytest.approx(
        100 * (moved / 819e9 * 1e3) / 0.58)
    # the chunk's attention, three GQA layers under ``full``: the accepted reader's
    assert _metric("kv_prefill_chunk_attn_ms").compute(ctx) == pytest.approx(0.4)
    silent = _window(SSM_DECODE, SSM_PREFILL)
    silent["_executions"] = [ex for ex in silent["_executions"] if "prefill" not in ex.program]
    other = _window(SSM_DECODE, [_op(0, 100e3, P + "layer_1/attn/full/attn_core/dot_general:")])
    for name in CHUNK_METRICS:
        assert _metric(name).compute(silent) is None, name
        assert _metric(name).compute(other) == 0.0, name


def test_metrics_read_zero_on_another_stack_and_nothing_without_a_window():
    other_decode = [_op(0, 100e3, D + "layer_1/attn/window/attn_core/dot_general:"),
                    _op(100e3, 200e3, D + "layer_1/attn/shortconv/state_read/select_n:")]
    other_prefill = [_op(0, 100e3, P + "layer_1/attn/full/attn_core/dot_general:")]
    ctx = _window(other_decode, other_prefill, counters=False)
    for name in NEW_METRICS:
        assert _metric(name).compute(ctx) == 0.0, name
    for name in NEW_METRICS + CHUNK_METRICS:
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
    assert not any("/ssm/" in n for n in names)
    ctx = {"serve": {"num_slots": 16}, "spans": [{"name": "decode", "args": {"active": 16}}],
           "say": print, "_executions": execs, "peaks": {"hbm_bytes_per_s": 819e9},
           "arch": ARCH, "config": _config()}
    for name in NEW_METRICS:
        assert _metric(name).compute(ctx) == 0.0, name


@pytest.mark.parametrize("name", NEW_METRICS + CHUNK_METRICS)
def test_metric_is_declared_as_a_serving_reader(name):
    manifest = harness.load_manifest(REPO)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    mod = _metric(name)
    assert entry.get("workloads") == ([CELL] if name in CHUNK_METRICS else None)
    assert entry["moves"] == "serve_tokens_per_s_per_chip"
    assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(
        entry[k] for k in ("name", "unit", "better", "source", "layer", "moves"))
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["name"] not in NEW_METRICS + CHUNK_METRICS}
    assert entry["layer"] in layers  # a layer the benchmark already names
    assert (entry["unit"] == "%") == name.endswith("_roofline")


def test_the_cell_joins_the_manifest_by_appends():
    manifest = harness.load_manifest(REPO)
    names = [w["name"] for w in manifest["workloads"]]
    at = names.index(CELL)
    assert at == 13 and names[:at][-1] == SERVING[-1]
    cell = manifest["workloads"][at]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and cell["config"] == CONFIG
    assert cell["traffic"] == "serve_chat_ssm_open_above_knee"
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("dots3-note-prev") < configs.index(CONFIG)
    entry = manifest["configs"][configs.index(CONFIG)]
    assert entry["source"] == SOURCE and len(entry["why"]) <= 200 and sorted(entry["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert entry["file"] == "benchmark/configs/nemotron-3-nano-30b-a3b.json"
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    serving = e2e["serve_tokens_per_s_per_chip"]["workloads"]
    assert serving[:serving.index(CELL)] == SERVING
    assert CELL not in e2e["tokens_per_s_per_chip"]["workloads"]
    assert e2e["serve_tokens_per_s_per_chip"]["bound"] == 0.035 and manifest["run_seconds"] == 51
    readers = [m["name"] for m in manifest["per_layer"]]
    first = readers.index(NEW_METRICS[0])
    assert readers[first:] == NEW_METRICS + CHUNK_METRICS  # appended, in this order
    assert readers.index("moe_layout_ms_per_step") < first  # PR 67's last
    # the lists that name the cell: the prompt chunk's readers, which answer None where a
    # profile holds no chunk: this PR's three and the accepted one of a K/V stack's chunk
    # attention, to whose list the cell is APPENDED (the five before it as they were)
    listed = {m["name"]: m["workloads"] for m in manifest["per_layer"] if CELL in m.get("workloads", [])}
    assert sorted(listed) == sorted(CHUNK_METRICS + ["kv_prefill_chunk_attn_ms"])
    assert listed["kv_prefill_chunk_attn_ms"] == SERVING[:5] + [CELL]
    assert len(json.dumps(manifest, indent=2)) < 64 * 1024


# -- the accepted cases this PR's appends broke, whole, one clause amended ---------------------


def _accepted(name):
    """An accepted test file as a module: its constants."""
    spec = importlib.util.spec_from_file_location("_held68_" + name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_dots3_the_cell_joins_the_manifest_by_appends():
    """`test_benchmark_moe_layout.py::test_dots3_the_cell_joins_the_manifest_by_appends`
    (itself `test_benchmark_dots3.py`'s case, amended by PR 67), amended: the dots3 cell,
    its configuration and its readers are followed by this PR's cell, configuration and
    seven readers."""
    d3 = _accepted("test_benchmark_dots3")
    manifest = harness.load_manifest(REPO)
    names = [w["name"] for w in manifest["workloads"]]
    # THE amended clauses (the originals: ``names[-1]``, 13, ``configs[-1]``, 10)
    assert names[-2:] == [d3.CELL, CELL] and len(names) == 14
    assert [n for n in names if n in d3.SERVING_BEFORE] == d3.SERVING_BEFORE
    cell = manifest["workloads"][-2]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("dots3-note-prev", d3.TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and len(manifest["configs"][-2]["why"]) <= 200
    assert [c["name"] for c in manifest["configs"]][-2:] == ["dots3-note-prev", CONFIG]
    assert len(manifest["configs"]) == 11
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2  # 2 of 14: no more
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["serve_tokens_per_s_per_chip"]["workloads"] == d3.SERVING_BEFORE + [d3.CELL, CELL]
    assert d3.CELL not in e2e["tokens_per_s_per_chip"]["workloads"]
    assert "workloads" not in e2e["setup_s"] and manifest["run_seconds"] == 51
    per = [m["name"] for m in manifest["per_layer"]]
    # THE amended clause (PR 67's: ``per[-6:] == NEW_METRICS + [NAME] and len(per) == 89``)
    assert per[-13:] == d3.NEW_METRICS + ["moe_layout_ms_per_step"] + NEW_METRICS + CHUNK_METRICS
    assert len(per) == 96
    # no list of a per-layer metric names the new cell: its readers are the unlisted ones
    assert not [m["name"] for m in manifest["per_layer"] if d3.CELL in m.get("workloads", [])]
    # a full check fits the driver's budget at one more cell
    cells = len(names)
    assert (2 + 14 * cells) * (manifest["run_seconds"] + 60) + 2 * 90 * cells + 1200 <= 43200


def test_granite_whole_cell_tiny(tmp_path, monkeypatch):
    """`test_benchmark_granite.py::test_whole_cell_tiny`, amended: the ``ssm_*`` readers the
    tiny training cell joins are those that carry a ``workloads`` list (the train step's
    three); this PR's four readers of a decode step carry none, and a training cell reports
    none of the seven."""
    from galvatron_tpu.models.modeling import PRESETS

    gr = _accepted("test_benchmark_granite")
    monkeypatch.setitem(PRESETS, "granite-4.0-h-micro", PRESETS["granite-4.0-h-micro"].replace(
        ssm_heads=8, ssm_head_dim=16, ssm_state=16, ssm_chunk=32))
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = harness.load_manifest(REPO)
    with open(os.path.join(root, "benchmark/configs/tiny-granite.json"), "w") as f:
        json.dump(gr.TINY, f)
    with open(os.path.join(root, "benchmark/traffic/tiny.json"), "w") as f:
        json.dump(gr.TINY_TRAFFIC, f)
    manifest["configs"].append({"name": "tiny-granite", "source": "test", "reduced": [],
                                "file": "benchmark/configs/tiny-granite.json", "why": "test"})
    manifest["workloads"].append({"name": "tiny-granite_tiny", "config": "tiny-granite",
                                  "traffic": "tiny", "chips": 1, "why": "test"})
    # the lists a one-chip training cell is in (throughput names its cells), and its own
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        # THE amended clause (the original: ``startswith("ssm_")`` alone)
        mine = entry["name"].startswith("ssm_") and "workloads" in entry
        if mine or "baichuan-7b_s512" in entry.get("workloads", []):
            entry["workloads"].append("tiny-granite_tiny")
    assert sorted(e["name"] for e in manifest["per_layer"] if e["name"].startswith("ssm_")
                  and "workloads" not in e) == sorted(NEW_METRICS)
    # (this PR's three chunk readers carry a list and so join; they answer None without a
    # serving window)
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
    assert not got & set(NEW_METRICS + CHUNK_METRICS)  # a training cell has no serving window
    # the run's fingerprint and its build_runtime span say what the stack holds
    with open(str(tmp_path / "run" / "spans.json")) as f:
        spans = json.load(f)["traceEvents"]
    build = [e for e in spans if e.get("name") == "build_runtime"]
    assert build and build[0]["args"]["layer_kinds"] == {"ssm": 5, "attention": 1}


#: the accepted cases that hold "three prompt-chunk readers, each listing the five cells
#: accepted before PR 65, and no other serving reader with a list"
CHUNK_LIST_CASES = [
    ("test_metrics", ()),
    ("test_smallthinker_metric_is_declared_as_a_serving_reader", ()),
    ("test_the_latent_cell_still_reads_the_rate_and_every_serving_reader", ()),
    ("test_a_profile_without_a_prompt_chunk_leaves_the_chunk_readers_silent",
     ("kv_prefill_chunk_attn_ms",)),
]
ACCEPTED_CHUNK_READERS = ["mla_prefill_chunk_attn_ms", "kv_prefill_chunk_attn_ms",
                          "shortconv_prefill_chunk_ms"]


@pytest.mark.parametrize("case,args", CHUNK_LIST_CASES)
def test_chunk_lists_case_runs_whole_under_the_amended_lists(case, args):
    """A case of `test_benchmark_chunk_lists.py`, its own body run WHOLE, with the file's two
    statements of the lists amended: the serving readers with a list are its three and this
    PR's three, and ``kv_prefill_chunk_attn_ms`` lists its five cells and this PR's."""
    cl = _accepted("test_benchmark_chunk_lists")
    assert cl.CHUNK_READERS == ACCEPTED_CHUNK_READERS
    want = {name: cl.LISTED for name in cl.CHUNK_READERS}
    want["kv_prefill_chunk_attn_ms"] = cl.LISTED + [CELL]
    want.update({name: [CELL] for name in CHUNK_METRICS})
    # THE amended statements (the originals: the three names; ``== LISTED`` for each)
    cl.CHUNK_READERS = cl.TRINITY.CHUNK_READERS = cl.CHUNK_READERS + CHUNK_METRICS
    cl._has_no_list_unless_a_chunk_reader = lambda entry: entry.get("workloads") == want.get(
        entry["name"])
    getattr(cl, case)(harness.load_manifest(REPO), *args)


@pytest.mark.parametrize("name", ACCEPTED_CHUNK_READERS)
def test_dots3_a_prompt_chunk_reader_lists_the_accepted_serving_cells(name):
    """`test_benchmark_dots3.py::test_a_prompt_chunk_reader_lists_the_five_accepted_serving_
    cells`, its own body run whole with two constants amended: the serving readers with a
    list are the three and this PR's three; the K/V chunk reader's list ends on this cell."""
    d3 = _accepted("test_benchmark_dots3")
    assert d3.CHUNK_READERS == ACCEPTED_CHUNK_READERS
    d3.CHUNK_READERS = d3.CHUNK_READERS + CHUNK_METRICS
    if name == "kv_prefill_chunk_attn_ms":
        d3.SERVING_BEFORE = d3.SERVING_BEFORE + [CELL]
    d3.test_a_prompt_chunk_reader_lists_the_five_accepted_serving_cells(name)


def test_every_marked_case_has_its_whole_copy_here():
    """tests/conftest.py's list and this file, one for one: a case marked there without its
    copy here would be a test switched off."""
    sys.path.insert(0, os.path.dirname(HERE))
    import conftest

    copies = {
        "test_benchmark_moe_layout.py::test_dots3_the_cell_joins_the_manifest_by_appends":
            test_dots3_the_cell_joins_the_manifest_by_appends,
        "test_benchmark_granite.py::test_whole_cell_tiny": test_granite_whole_cell_tiny,
    }
    for case, args in CHUNK_LIST_CASES:
        copies["test_benchmark_chunk_lists.py::" + case + ("[%s]" % args[0] if args else "")] = (
            test_chunk_lists_case_runs_whole_under_the_amended_lists)
    for name in ACCEPTED_CHUNK_READERS:
        copies["test_benchmark_dots3.py::test_a_prompt_chunk_reader_lists_the_five_accepted_"
               f"serving_cells[{name}]"] = (
            test_dots3_a_prompt_chunk_reader_lists_the_accepted_serving_cells)
    marked = {node.split("tests/benchmark/")[1] for node in conftest._PINNED_BEFORE_PR_68}
    assert marked == set(copies) and all(callable(f) for f in copies.values())
