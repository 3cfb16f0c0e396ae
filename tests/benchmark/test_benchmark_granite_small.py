"""What PR 70 adds to the benchmark, on the CPU: the granite-4.0-h-small configuration against
its catalog row key by key, the long-prompt serving mix as ISSUE 70 names it, the reference
module's counts against hand counts and against the tree the program builds at the cut, the
two new readers of a prompt chunk's routed experts on a hand-made traced window, the
manifest's appends, and the accepted cases those appends broke, whole, one clause amended.
No number here is a device number."""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.lib import flops, harness, reference, scoped, traffic as traffic_lib  # noqa: E402

CELL = "granite-4.0-h-small_serve_docs_above_knee"
CONFIG = "granite-4.0-h-small"
TRAFFIC = "serve_docs_ssm_open_above_knee"
SOURCE = "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json"
NEMOTRON = "nemotron-3-nano-30b-a3b_serve_chat_above_knee"
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
#: the ``config`` of the catalog row granite-4.0-h-small (model-configs guide)
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 768,
    "layer_types": PERIOD * 4, "logits_scaling": 16, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 72,
    "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 1536,
    "tie_word_embeddings": True, "vocab_size": 100352,
}
CUT = {"num_hidden_layers": 10, "layer_types": PERIOD, "num_local_experts": 36,
       "vocab_size": 50176}
#: the readers this PR adds: a prompt chunk's, None where a profile holds no chunk, so listed
NEW_METRICS = ["expert_prefill_chunk_ms", "expert_prefill_chunk_roofline"]
#: the accepted readers of a prompt chunk to whose lists the cell is appended
JOINED = ["kv_prefill_chunk_attn_ms", "ssm_prefill_chunk_ms", "ssm_chunk_scan_ms",
          "ssm_chunk_scan_roofline"]
ARCH = reference.load(REPO, "granitemoehybrid_moe")
H, F, FS, V = 4096, 768, 1536, 50176
MAMBA = H * 16768 + 8192 * H
MAMBA_REST = 8448 * 5 + 3 * 128 + 8192
ATTN = H * (4096 + 1024 + 1024) + 4096 * H
ROUTER, EXPERT, SHARED = H * 72, 3 * H * F, 3 * H * FS
STATE = {"conv": 3 * 8448 * 2, "scan": 128 * 64 * 128 * 4}


def _metric(name):
    path = os.path.join(REPO, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("_t70_" + name, path)
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
        row = next(r for r in map(json.loads, f) if r["name"] == CONFIG)
    assert row["config"] == CATALOG and row["source_url"] == SOURCE


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_configuration_holds_the_catalog_row_key_by_key(key):
    config = _config()
    entry = next(c for c in harness.load_manifest(REPO)["configs"] if c["name"] == CONFIG)
    if key == "model_type":
        # a string, and the one key that is neither the row's nor a cut: the harness finds a
        # reference by it and ``granitemoehybrid.py`` is the dense micro's (``assumed`` says so)
        assert config[key] == "granitemoehybrid_moe" and key not in entry["reduced"]
        assert "PUBLISHED value is granitemoehybrid" in config["assumed"]["model_type"]
        return
    assert config[key] == CUT.get(key, CATALOG[key])
    assert (key in config["reduced"]) == (key in CUT) == (key in entry["reduced"])


def test_configuration_states_the_cut_the_deployment_and_what_is_assumed():
    cell, config, _ = harness.load_cell(REPO, CELL)
    assert cell["chips"] == 1 and config["source"] == SOURCE
    assert set(config["reduced"]) == set(CUT)
    assert config["published"] == {k: CATALOG[k] for k in CUT}
    assert config["layer_types"] == CATALOG["layer_types"][:10]  # ONE period, 9 : 1
    assert config["expert_share"] == {"rank": 0, "of": 2}
    assert config["num_local_experts"] * 2 == CATALOG["num_local_experts"]
    assert config["vocab_size"] * 2 == CATALOG["vocab_size"]
    assert {"model_type", "expert_width", "input_linear_split", "router", "conv_order",
            "gate_norm", "no_dt_clamp", "state_types", "no_position_signal", "initializer",
            "slot_length"} <= set(config["assumed"])
    assert "8 chips" in config["deployment"] and "2 chips that share each layer" in config[
        "deployment"]
    assert "4.4 rows an expert" in config["distorts"] and "one stage" in config["distorts"]
    flags = config["program_flags"]
    assert flags == ["--model_size", CONFIG, "--num_layers", "10", "--vocab_size", "50176",
                     "--moe_share", "0/2", "--seq_length", "16384", "--param_dtype", "bf16"]
    # no width is among the cuts
    assert not [k for k in config["reduced"] if k.endswith(("_size", "_dim", "_rank", "_head"))
                and k != "vocab_size"]


def test_the_program_runs_the_widths_the_file_states():
    import jax.numpy as jnp

    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args

    config = _config()
    cfg = model_config_from_args(initialize_galvatron("serve", list(config["program_flags"])))
    harness.check_widths(cfg, config)
    assert (cfg.kv_heads, cfg.head_dim, cfg.expert_ffn, cfg.moe_top_k) == (
        config["num_key_value_heads"], 128, config["intermediate_size"],
        config["num_experts_per_tok"])
    assert cfg.moe_held == config["num_local_experts"] and cfg.moe_experts == 72
    assert cfg.moe_shared_ffn_dim == config["shared_intermediate_size"]
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv,
            cfg.ssm_chunk) == tuple(config[k] for k in (
                "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
                "mamba_chunk_size"))
    assert cfg.ssm_heads * cfg.ssm_head_dim == config["mamba_expand"] * config["hidden_size"]
    assert (cfg.embedding_multiplier, cfg.attention_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (12.0, 0.0078125, 0.22, 16.0)
    assert cfg.norm_eps == config["rms_norm_eps"] and cfg.pos_embed == "nope"
    assert cfg.param_dtype == jnp.bfloat16 and cfg.max_seq_len == 16384
    assert list(cfg.kinds) == ["ssm" if k == "mamba" else "attention"
                               for k in config["layer_types"]]
    assert all(cfg.mlp_layers) and len(cfg.kinds) == config["num_hidden_layers"]


def test_traffic_is_the_mix_the_issue_names():
    _, config, spec = harness.load_cell(REPO, CELL)
    assert spec["kind"] == "serve" and "knee" not in spec
    # lengths, sampling, corpus, burst and flags as the three long cells draw them
    with open(os.path.join(REPO, "benchmark/traffic/serve_long_open_above_knee.json")) as f:
        long_mix = json.load(f)
    for part in ("lengths", "sampling", "corpus", "serve_flags"):
        assert spec[part] == long_mix[part], part
    lengths = spec["lengths"]
    assert (lengths["grid"], lengths["pair_stride"], lengths["max_total"]) == (16, 7, 16000)
    assert lengths["prompt"] == {"median": 4096, "sigma": 0.6, "lo": 1024, "hi": 12288}
    assert lengths["output"] == {"median": 256, "sigma": 0.6, "lo": 64, "hi": 768}
    assert spec["sampling"] == {"temperature": 0.8, "top_p": 0.95, "greedy_every": 4,
                                "greedy_temperature": 0.0001}
    assert spec["corpus"] == {"tokens": 262144, "zipf_a": 1.0, "follow_p": 0.5}
    assert spec["arrivals"]["process"] == "exponential_gap_quantiles"
    assert spec["arrivals"]["burst_at_start"] == 64
    flags = dict(zip(spec["serve_flags"][::2], spec["serve_flags"][1::2]))
    assert flags == {"--num_slots": "32", "--prefill_chunk": "1024", "--max_queue": "4096",
                     "--request_ttl_s": "0"}
    # ISSUE 70's window, under its allowed step B (``settle_s`` 20; 3 spread 3.06%)
    assert spec["window"] == {"opens": "all_slots_used", "settle_s": 20, "first_token_grace_s": 0}
    assert (spec["correct"]["requests"], spec["correct"]["capture_every"],
            spec["correct"]["rows_kept"]) == (4, 5, 4096)
    shapes = traffic_lib.grid(spec)
    assert max(s["prompt_len"] + s["output_len"] for s in shapes) <= 16000
    # every prompt crosses a chunk's end (2-12 chunks of 1024): the state is handed on
    chunks = [-(-s["prompt_len"] // 1024) for s in shapes]
    assert (min(chunks), max(chunks)) == (2, 12) and sum(chunks) == 83
    mean = traffic_lib.mean_output_len(spec)
    assert mean == 300.625
    # the rate is 2.0 x K32 over the mix's mean answer, K32 and its readings in ``why``
    why = spec["why"]
    assert "2.0 K32" in why and f"{mean:.3f}" in why and "K32 = " in why
    k32 = float(why.split("K32 = ")[1].split(":")[0].split()[0])
    assert spec["arrivals"]["rate_rps"] == pytest.approx(2.0 * k32, rel=0.01)
    assert 16384 % int(flags["--prefill_chunk"]) == 0
    assert max(s["prompt_len"] for s in shapes) < config["vocab_size"]  # ids inside the slice


# -- the counts ---------------------------------------------------------------------------


def test_flop_count_against_a_hand_count():
    config = _config()
    routed = ROUTER + SHARED + EXPERT * 10 / 2
    body = 9 * MAMBA + ATTN + 10 * routed
    scan = 9 * 6.0 * 128 * 64 * 128
    for s in (1, 1024, 16384):
        want = 2.0 * (body + H * V) + scan + 2 * 2.0 * 32 * 128 * (s + 1) / 2
        assert ARCH.fwd_flops_per_token(config, s) == pytest.approx(want)


def test_served_counts_against_a_hand_count():
    config = _config()
    served = ARCH.served_params(config)
    body = 9 * (MAMBA + MAMBA_REST) + ATTN + 10 * (ROUTER + SHARED + 2 * H)
    assert served == {"a_forward": body + H + H * V, "a_token": 0}
    dims = ARCH.serve_dims(config)
    assert dims["layers"] == 10 and dims["head_dim"] == pytest.approx(128 / 10)
    assert flops.kv_bytes_per_position(dims) == pytest.approx(4096)
    assert ARCH.least_bytes_per_position(config, 5000) == 4096
    assert ARCH.expert_layers(config) == 10
    assert ARCH.expert_step_bytes(config, 35.7) == pytest.approx(2 * 35.7 * 10 * EXPERT)
    assert ARCH.decode_attn_bytes(config, 160000, 0, 32, 1, 0) == 4096 * (160000 + 32)
    # a decode step's Mamba-2 mixers: 9 x (102.3 M weights in bf16 + 32 rows x 2 x 4,244,992 B)
    state = sum(STATE.values())
    assert ARCH.ssm_state_bytes(config) == STATE and state == 4244992
    assert ARCH.ssm_step_bytes(config, 32, 9) == 9 * (2 * (MAMBA + MAMBA_REST) + 2 * 32 * state)
    assert ARCH.ssm_state_step_bytes(config, 32, 9) == 9 * 2 * 32 * state
    # what the kernel `ssm_step` itself moves: the float32 scan state, not the conv tail
    assert ARCH.ssm_scan_step_bytes(config, 32, 9) == 9 * 2 * 32 * 4194304 == 2_415_919_104
    flops_, moved = ARCH.ssm_chunk_scan_work(config, 1024, 9)
    assert flops_ == 9 * 1024 * 6 * 128 * 64 * 128
    assert moved == 9 * (1024 * ((2 * 8192 + 2 * 128) * 2 + 4 * 128) + 2 * 4194304)


def test_a_chunks_expert_work_against_a_hand_count():
    """1,024 rows x 10 choices, half of them held: ~5,120 pairs a layer on 36 experts."""
    config = _config()
    flops_, moved = ARCH.expert_chunk_work(config, 5063.0, 36.0)
    assert flops_ == pytest.approx(10 * 2 * 5063 * EXPERT) and round(flops_ / 1e12, 3) == 0.956
    assert moved == pytest.approx(10 * 2 * (36 * EXPERT + 2 * 5063 * H))
    assert round(moved / 1e9, 2) == 7.62
    # bound by bytes at 142 rows an expert: 9.3 ms at the HBM rate against 4.9 at the peak
    assert moved / 819e9 > flops_ / 197e12
    # no expert touched, no pair: no work
    assert ARCH.expert_chunk_work(config, 0.0, 0.0) == (0.0, 0.0)


def test_the_counts_are_the_tree_the_program_builds_at_the_cut():
    import jax

    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args
    from galvatron_tpu.models import generation, modeling

    config = _config()
    cfg = model_config_from_args(initialize_galvatron("serve", list(config["program_flags"])))
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    total = sum(a.size for a in jax.tree.leaves(shapes))
    served = ARCH.served_params(config)
    # what any forward must read: everything but the routed experts (the table is tied)
    assert served["a_forward"] == total - 10 * 36 * EXPERT
    assert round(total / 1e9, 3) == 4.757
    layout = generation.cache_layout(cfg, 16384, 1024)
    # the scan state's 4,194,304 B a row and layer are FLOAT32's (the configuration's
    # ``assumed.state_types``): a program that holds it lower fails HERE, in a file under the
    # benchmark's ``paths``, since the cell's ``correct`` cannot tell (PERF.md section 6)
    assert layout["state_part_bytes"] == ARCH.ssm_state_bytes(config) == STATE
    assert layout["bytes_per_position_per_layer"] * layout["full_layers"] == (
        ARCH.least_bytes_per_position(config, 1))
    assert 32 * layout["bytes_per_slot"] == 32 * 16384 * 4096 + 9 * 32 * 4244992


def test_the_three_shares_of_the_chips_peaks_read_under_100():
    """Over a window of 100 decode steps (32 slots, 5,000 live positions each) and 50 chunks
    of 10 prompts, on the chip's peaks, from this cell's ``serve_dims``: floors (the state's
    and the touched experts' bytes have no term there), never over 100."""
    config = _config()
    work = {"decode_tokens": 3200, "decode_positions": 16_000_000, "prefills": 10,
            "prefill_tokens": 51200, "prefill_chunks": 50, "prefill_positions": 50 * 3072,
            "prefill_pairs": 10 * 5120 * 5121 // 2}
    ctx = {"serve": {"work": work, "seconds": 4.8}, "arch": ARCH, "config": config, "chips": 1,
           "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}, "say": print,
           "spans": [{"name": "decode", "args": {}}] * 100}
    assert 0 < _metric("serve_hbm_roofline").compute(ctx) < 100
    assert 0 < _metric("serve_mfu").compute(ctx) < 100


# -- the readers --------------------------------------------------------------------------

D, P = "jit(_decode_step)/", "jit(_prefill_chunk)/"


def _op(start, end, op_name, name="fusion.1"):
    return scoped.ScopedOp(float(start), float(end), name, "fusion:kLoop", op_name, "")


DECODE = [
    _op(0, 400e3, D + "layer_0/attn/ssm/step/ssm_step/pallas_call:", "ssm_step.12"),
    _op(400e3, 1400e3, D + "layer_0/mlp/experts/experts/moe_gmm:", "moe_gmm.3"),
]
PREFILL = [
    _op(0, 700e3, P + "layer_0/attn/ssm/in_proj/dot_general:"),
    _op(700e3, 710e3, P + "layer_0/mlp/router/dot_general:"),
    _op(710e3, 760e3, P + "layer_0/mlp/dispatch/layout/cumsum:"),
    _op(760e3, 1060e3, P + "layer_0/mlp/experts/dispatch/moe_held_rows:", "moe_held_rows.1"),
    _op(1060e3, 2260e3, P + "layer_0/mlp/experts/experts/moe_gmm:", "moe_gmm.2"),
    _op(2260e3, 2760e3, P + "layer_0/mlp/experts/combine/moe_held_pairs:", "moe_held_pairs.1"),
    _op(2760e3, 2800e3, P + "layer_0/mlp/combine/add:"),
    _op(2800e3, 3000e3, P + "layer_0/mlp/shared_expert/dot_general:"),
]


def _window(prefill_ops=PREFILL, spans=None):
    """Two decode executions and two prefill chunks on device 0, and the window's spans."""
    execs = [scoped.Execution("_decode_step", 0.0, 1e7, tuple(DECODE)),
             scoped.Execution("_prefill_chunk", 2e7, 3e7, tuple(prefill_ops)),
             scoped.Execution("_prefill_chunk", 3e7, 4e7, tuple(prefill_ops)),
             scoped.Execution("_decode_step", 4e7, 5e7, tuple(DECODE))]
    if spans is None:
        spans = [
            {"name": "decode", "start": 0.0, "end": 0.02, "step": None, "args": {"active": 32}},
            # a prompt of 3 whole chunks and a ragged one: the means over the 3
            {"name": "prefill", "start": 0.02, "end": 0.2, "step": None,
             "args": {"moe_chunks_counted": 3, "moe_held_pairs": 5000.0,
                      "moe_held_experts_touched_a_chunk": 36.0, "moe_held_experts_touched": 9.0}},
            # a prompt of one whole chunk
            {"name": "prefill", "start": 0.2, "end": 0.3, "step": None,
             "args": {"moe_chunks_counted": 1, "moe_held_pairs": 5200.0,
                      "moe_held_experts_touched_a_chunk": 35.0}},
            # a prompt shorter than a chunk: its one ragged chunk is not counted
            {"name": "prefill", "start": 0.3, "end": 0.35, "step": None,
             "args": {"moe_chunks_counted": 0, "moe_held_pairs": 9999.0,
                      "moe_held_experts_touched_a_chunk": 10.0}},
        ]
    said = []
    return {"serve": {"num_slots": 32, "prefill_chunk": 1024}, "spans": spans,
            "_executions": execs, "say": said.append, "said": said,
            "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12},
            "arch": ARCH, "config": _config()}


def test_the_two_readers_on_a_hand_made_window():
    ctx = _window()
    # everything under ``experts``: the held body's gather, products and weighted sum
    assert _metric("expert_prefill_chunk_ms").compute(ctx) == pytest.approx(0.3 + 1.2 + 0.5)
    line = next(s for s in ctx["said"] if "by scope" in s)
    assert "router 0.010" in line and "shared_expert 0.200" in line
    # a scope opened again under ``experts`` counts in both: dispatch 0.05 + 0.3
    assert "dispatch 0.350" in line and "experts 2.000" in line and "combine 0.540" in line
    pairs, touched = (3 * 5000 + 5200) / 4, (3 * 36 + 35) / 4
    flops_, moved = ARCH.expert_chunk_work(_config(), pairs, touched)
    got = _metric("expert_prefill_chunk_roofline").compute(ctx)
    assert got == pytest.approx(100 * max(1e3 * flops_ / 197e12, 1e3 * moved / 819e9) / 2.0)
    assert any(f"{pairs:.1f} held pairs" in s and f"{touched:.2f} touched" in s
               for s in ctx["said"])


def test_the_share_cannot_pass_100_at_the_times_the_chip_read():
    """5,063 pairs on 36 experts in the 20.04 ms the chip read under ``experts`` (PERF.md
    section 5) is 46%; the least time the count allows is 9.3 ms."""
    ops = [_op(0, 20.04e6, P + "layer_0/mlp/experts/experts/moe_gmm:", "moe_gmm.2")]
    spans = [{"name": "prefill", "start": 0.0, "end": 0.1, "step": None,
              "args": {"moe_chunks_counted": 4, "moe_held_pairs": 5063.0,
                       "moe_held_experts_touched_a_chunk": 36.0}}]
    got = _metric("expert_prefill_chunk_roofline").compute(_window(ops, spans))
    assert got == pytest.approx(46.4, abs=0.1)


def test_the_readers_are_silent_without_a_chunk_and_zero_on_another_stack():
    silent = _window()
    silent["_executions"] = [ex for ex in silent["_executions"] if "prefill" not in ex.program]
    other = _window([_op(0, 100e3, P + "layer_1/attn/full/attn_core/dot_general:"),
                     _op(100e3, 200e3, P + "layer_1/mlp/fc1/dot_general:")])
    for name in NEW_METRICS:
        assert _metric(name).compute(silent) is None, name
        assert _metric(name).compute(other) == 0.0, name
        assert _metric(name).compute({"spans": [], "say": print}) is None
        assert _metric(name).compute({"serve": {}, "spans": [], "trace": None, "say": print,
                                      "_executions": None}) is None


def test_a_program_without_the_counters_leaves_the_share_out():
    """The parent's ``prefill`` spans carry the LAST chunk's counters alone: the time is read,
    the share is not (None: left out of the line), and nothing raises."""
    old = [{"name": "prefill", "start": 0.0, "end": 0.1, "step": None,
            "args": {"moe_held_pairs_per_token": 4.9, "moe_held_experts_touched": 36.0}}]
    ctx = _window(spans=old)
    assert _metric("expert_prefill_chunk_ms").compute(ctx) == pytest.approx(2.0)
    assert _metric("expert_prefill_chunk_roofline").compute(ctx) is None
    assert _metric("expert_prefill_chunk_roofline").compute(_window(spans=[])) is None
    # and an architecture module without ``expert_chunk_work`` reads 0
    ctx = _window()
    ctx["arch"] = reference.load(REPO, "nemotron_h")
    assert _metric("expert_prefill_chunk_roofline").compute(ctx) == 0.0


def test_the_accepted_chunk_readers_answer_on_this_stacks_window():
    """The four accepted prompt-chunk readers the cell joins, over this stack's scopes."""
    ops = PREFILL + [
        _op(3000e3, 3500e3, P + "layer_0/attn/ssm/scan/dot_general:"),
        _op(3500e3, 3560e3, P + "layer_5/attn/full/attn_core/kv_chunk/pallas_call:", "kv_chunk.1")]
    ctx = _window(ops)
    ctx["spans"] = ctx["spans"] + [{"name": "decode", "start": 0.0, "end": 0.02, "step": None,
                                    "args": {"active": 32, "state_layers": 9}}]
    assert _metric("ssm_prefill_chunk_ms").compute(ctx) == pytest.approx(0.7 + 0.5)
    assert _metric("ssm_chunk_scan_ms").compute(ctx) == pytest.approx(0.5)
    assert _metric("kv_prefill_chunk_attn_ms").compute(ctx) == pytest.approx(0.06)
    flops_, moved = ARCH.ssm_chunk_scan_work(_config(), 1024, 9)
    assert _metric("ssm_chunk_scan_roofline").compute(ctx) == pytest.approx(
        100 * max(1e3 * flops_ / 197e12, 1e3 * moved / 819e9) / 0.5)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_is_declared_as_a_listed_serving_reader(name):
    manifest = harness.load_manifest(REPO)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    mod = _metric(name)
    assert entry["workloads"] == [CELL] and entry["moves"] == "serve_tokens_per_s_per_chip"
    assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(
        entry[k] for k in ("name", "unit", "better", "source", "layer", "moves"))
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    layers = {m["layer"] for m in manifest["per_layer"] if m["name"] not in NEW_METRICS}
    assert entry["layer"] in layers  # a layer the benchmark already names
    assert (entry["unit"] == "%") == name.endswith("_roofline")


def test_the_cell_joins_the_manifest_by_appends():
    manifest = harness.load_manifest(REPO)
    names = [w["name"] for w in manifest["workloads"]]
    assert names[-2:] == [NEMOTRON, CELL] and len(names) == 15
    cell = manifest["workloads"][-1]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2  # 2 of 15: no more
    entry = manifest["configs"][-1]
    assert set(entry) == {"name", "source", "file", "reduced", "why"} and len(manifest["configs"]) == 12
    assert (entry["name"], entry["source"]) == (CONFIG, SOURCE) and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/granite-4.0-h-small.json"
    assert sorted(entry["reduced"]) == sorted(CUT)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["serve_tokens_per_s_per_chip"]["workloads"][-2:] == [NEMOTRON, CELL]
    assert CELL not in e2e["tokens_per_s_per_chip"]["workloads"]
    assert e2e["serve_tokens_per_s_per_chip"]["bound"] == 0.035 and manifest["run_seconds"] == 51
    readers = [m["name"] for m in manifest["per_layer"]]
    assert readers[-2:] == NEW_METRICS and len(readers) == 98  # appended, in this order
    # the lists that name the cell: its two readers and the four accepted readers of a
    # prompt chunk, to each of whose lists the cell is APPENDED (what was there as it was)
    listed = {m["name"]: m["workloads"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", [])}
    assert sorted(listed) == sorted(NEW_METRICS + JOINED)
    assert all(listed[name][-2:] == [NEMOTRON, CELL] for name in JOINED)
    assert len(listed["kv_prefill_chunk_attn_ms"]) == 7
    assert all(listed[name] == [NEMOTRON, CELL] for name in JOINED[1:])
    assert len(json.dumps(manifest, indent=2)) < 64 * 1024
    # a full check fits the driver's budget at one more cell
    cells = len(names)
    assert (2 + 14 * cells) * (manifest["run_seconds"] + 60) + 2 * 90 * cells + 1200 <= 43200


def test_every_file_the_cell_names_is_there_and_new_files_alone_carry_it():
    with open(os.path.join(REPO, "benchmark", "traffic", TRAFFIC + ".json")) as f:
        spec = json.load(f)
    assert set(spec) == {"kind", "lengths", "sampling", "corpus", "arrivals", "serve_flags",
                         "window", "correct", "why"}
    assert set(spec["correct"]) == {"requests", "capture_every", "rows_kept", "logits_kl_max"}
    for name in NEW_METRICS + ["_expert_chunk"]:
        assert os.path.exists(os.path.join(REPO, "benchmark", "metrics", name + ".py"))
    # the reference imports nothing of the program and nothing of the dense micro's module
    with open(os.path.join(REPO, "benchmark", "references", "granitemoehybrid_moe.py")) as f:
        text = f.read()
    lines = [ln.strip() for ln in text.splitlines() if ln.strip().startswith(("import ", "from "))]
    assert lines and not [ln for ln in lines if "galvatron" in ln or "granitemoehybrid" in ln]


# -- the accepted cases this PR's appends broke, whole, one clause amended ---------------------


def _accepted(name):
    """An accepted test file as a module: its constants and its cases."""
    spec = importlib.util.spec_from_file_location("_held70_" + name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


NM = _accepted("test_benchmark_nemotron")


@pytest.mark.parametrize("name", NM.CHUNK_METRICS)
def test_nemotron_metric_is_declared_as_a_serving_reader(name):
    """`test_benchmark_nemotron.py::test_metric_is_declared_as_a_serving_reader` for PR 68's
    three readers of a prompt chunk, amended: each lists its cell AND this one."""
    manifest = harness.load_manifest(REPO)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    mod = _metric(name)
    # THE amended clause (the original: ``== [CELL]``, the nemotron cell alone)
    assert entry.get("workloads") == [NM.CELL, CELL]
    assert entry["moves"] == "serve_tokens_per_s_per_chip"
    assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(
        entry[k] for k in ("name", "unit", "better", "source", "layer", "moves"))
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["name"] not in NM.NEW_METRICS + NM.CHUNK_METRICS}
    assert entry["layer"] in layers
    assert (entry["unit"] == "%") == name.endswith("_roofline")


def test_nemotron_the_cell_joins_the_manifest_by_appends():
    """`test_benchmark_nemotron.py::test_the_cell_joins_the_manifest_by_appends`, amended:
    its seven readers are followed by this PR's two, and the K/V chunk reader's list by this
    PR's cell."""
    manifest = harness.load_manifest(REPO)
    names = [w["name"] for w in manifest["workloads"]]
    at = names.index(NM.CELL)
    assert at == 13 and names[:at][-1] == NM.SERVING[-1]
    cell = manifest["workloads"][at]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and cell["config"] == NM.CONFIG
    assert cell["traffic"] == "serve_chat_ssm_open_above_knee"
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("dots3-note-prev") < configs.index(NM.CONFIG)
    entry = manifest["configs"][configs.index(NM.CONFIG)]
    assert entry["source"] == NM.SOURCE and len(entry["why"]) <= 200 and sorted(
        entry["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert entry["file"] == "benchmark/configs/nemotron-3-nano-30b-a3b.json"
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    serving = e2e["serve_tokens_per_s_per_chip"]["workloads"]
    assert serving[:serving.index(NM.CELL)] == NM.SERVING
    assert NM.CELL not in e2e["tokens_per_s_per_chip"]["workloads"]
    assert e2e["serve_tokens_per_s_per_chip"]["bound"] == 0.035 and manifest["run_seconds"] == 51
    readers = [m["name"] for m in manifest["per_layer"]]
    first = readers.index(NM.NEW_METRICS[0])
    # THE amended clause (the original: ``readers[first:] == NEW_METRICS + CHUNK_METRICS``)
    assert readers[first:] == NM.NEW_METRICS + NM.CHUNK_METRICS + NEW_METRICS
    assert readers.index("moe_layout_ms_per_step") < first
    listed = {m["name"]: m["workloads"] for m in manifest["per_layer"]
              if NM.CELL in m.get("workloads", [])}
    assert sorted(listed) == sorted(NM.CHUNK_METRICS + ["kv_prefill_chunk_attn_ms"])
    # THE amended clause (the original: ``== SERVING[:5] + [CELL]``)
    assert listed["kv_prefill_chunk_attn_ms"] == NM.SERVING[:5] + [NM.CELL, CELL]
    assert len(json.dumps(manifest, indent=2)) < 64 * 1024


def test_dots3_the_cell_joins_the_manifest_by_appends():
    """`test_benchmark_nemotron.py::test_dots3_the_cell_joins_the_manifest_by_appends` (itself
    `test_benchmark_dots3.py`'s case, amended by PR 67 and PR 68), amended: the dots3 cell, its
    configuration and its readers are followed by PR 68's and this PR's."""
    d3 = _accepted("test_benchmark_dots3")
    manifest = harness.load_manifest(REPO)
    names = [w["name"] for w in manifest["workloads"]]
    # THE amended clauses (PR 68's: ``names[-2:]``, 14, ``configs[-2:]``, 11)
    assert names[-3:] == [d3.CELL, NM.CELL, CELL] and len(names) == 15
    assert [n for n in names if n in d3.SERVING_BEFORE] == d3.SERVING_BEFORE
    cell = manifest["workloads"][-3]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("dots3-note-prev", d3.TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and len(manifest["configs"][-3]["why"]) <= 200
    assert [c["name"] for c in manifest["configs"]][-3:] == ["dots3-note-prev", NM.CONFIG, CONFIG]
    assert len(manifest["configs"]) == 12
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2  # 2 of 15: no more
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["serve_tokens_per_s_per_chip"]["workloads"] == d3.SERVING_BEFORE + [
        d3.CELL, NM.CELL, CELL]
    assert d3.CELL not in e2e["tokens_per_s_per_chip"]["workloads"]
    assert "workloads" not in e2e["setup_s"] and manifest["run_seconds"] == 51
    per = [m["name"] for m in manifest["per_layer"]]
    # THE amended clause (PR 68's: ``per[-13:] == ...`` and 96)
    assert per[-15:] == (d3.NEW_METRICS + ["moe_layout_ms_per_step"] + NM.NEW_METRICS
                         + NM.CHUNK_METRICS + NEW_METRICS)
    assert len(per) == 98
    assert not [m["name"] for m in manifest["per_layer"] if d3.CELL in m.get("workloads", [])]
    cells = len(names)
    assert (2 + 14 * cells) * (manifest["run_seconds"] + 60) + 2 * 90 * cells + 1200 <= 43200


#: the serving readers that carry a list once this PR's are in: the accepted three, PR 68's
#: three, this PR's two
ACCEPTED_CHUNK_READERS = NM.ACCEPTED_CHUNK_READERS


@pytest.mark.parametrize("case,args", NM.CHUNK_LIST_CASES)
def test_chunk_lists_case_runs_whole_under_the_amended_lists(case, args):
    """A case of `test_benchmark_chunk_lists.py`, its own body run WHOLE (as PR 68's copy ran
    it), with the file's two statements of the lists amended: the serving readers with a list
    are its three, PR 68's three and this PR's two; ``kv_prefill_chunk_attn_ms`` lists its
    five cells, PR 68's and this PR's; PR 68's three list both cells."""
    cl = _accepted("test_benchmark_chunk_lists")
    assert cl.CHUNK_READERS == ACCEPTED_CHUNK_READERS
    want = {name: cl.LISTED for name in cl.CHUNK_READERS}
    want["kv_prefill_chunk_attn_ms"] = cl.LISTED + [NM.CELL, CELL]
    want.update({name: [NM.CELL, CELL] for name in NM.CHUNK_METRICS})
    want.update({name: [CELL] for name in NEW_METRICS})
    cl.CHUNK_READERS = cl.TRINITY.CHUNK_READERS = cl.CHUNK_READERS + NM.CHUNK_METRICS + NEW_METRICS
    cl._has_no_list_unless_a_chunk_reader = lambda entry: entry.get("workloads") == want.get(
        entry["name"])
    getattr(cl, case)(harness.load_manifest(REPO), *args)


@pytest.mark.parametrize("name", ACCEPTED_CHUNK_READERS)
def test_dots3_a_prompt_chunk_reader_lists_the_accepted_serving_cells(name):
    """`test_benchmark_dots3.py::test_a_prompt_chunk_reader_lists_the_five_accepted_serving_
    cells`, its own body run whole with two constants amended: the serving readers with a
    list are the three, PR 68's three and this PR's two; the K/V chunk reader's list ends on
    PR 68's cell and this one."""
    d3 = _accepted("test_benchmark_dots3")
    assert d3.CHUNK_READERS == ACCEPTED_CHUNK_READERS
    d3.CHUNK_READERS = d3.CHUNK_READERS + NM.CHUNK_METRICS + NEW_METRICS
    if name == "kv_prefill_chunk_attn_ms":
        d3.SERVING_BEFORE = d3.SERVING_BEFORE + [NM.CELL, CELL]
    d3.test_a_prompt_chunk_reader_lists_the_five_accepted_serving_cells(name)


def test_every_marked_case_has_its_whole_copy_here():
    """tests/conftest.py's list and this file, one for one: a case marked there without its
    copy here would be a test switched off."""
    sys.path.insert(0, os.path.dirname(HERE))
    import conftest

    at = "test_benchmark_nemotron.py::"
    copies = {
        at + "test_the_cell_joins_the_manifest_by_appends":
            test_nemotron_the_cell_joins_the_manifest_by_appends,
        at + "test_dots3_the_cell_joins_the_manifest_by_appends":
            test_dots3_the_cell_joins_the_manifest_by_appends,
    }
    for name in NM.CHUNK_METRICS:
        copies[at + f"test_metric_is_declared_as_a_serving_reader[{name}]"] = (
            test_nemotron_metric_is_declared_as_a_serving_reader)
    for i, (case, args) in enumerate(NM.CHUNK_LIST_CASES):
        copies[at + f"test_chunk_lists_case_runs_whole_under_the_amended_lists[{case}-args{i}]"] = (
            test_chunk_lists_case_runs_whole_under_the_amended_lists)
    for name in ACCEPTED_CHUNK_READERS:
        copies[at + "test_dots3_a_prompt_chunk_reader_lists_the_accepted_serving_cells"
               f"[{name}]"] = test_dots3_a_prompt_chunk_reader_lists_the_accepted_serving_cells
    marked = {node.split("tests/benchmark/")[1] for node in conftest._PINNED_BEFORE_PR_70}
    assert marked == set(copies) and len(marked) == 12
    assert all(callable(f) for f in copies.values())
