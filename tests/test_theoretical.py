"""Analytic memory/param estimates + check_cost_model harness
(megatron theoretical_memory_usage.py equivalent; reference check_cost_model:
search_engine.py:369-421)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.core.strategy import LayerStrategy
from galvatron_tpu.models import modeling
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.search import theoretical as th


def _count_params(tree):
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))


def test_param_counts_match_actual_init():
    """Analytic counts must equal the real initialized trees exactly."""
    for name in ("llama-0.3b", "gpt-0.3b", "baichuan-13b"):
        cfg = modeling.PRESETS[name].replace(num_layers=2)
        params = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
        got_layer = _count_params(params["layers"][0])
        assert got_layer == th.layer_param_count(cfg), name
        total = _count_params(params)
        assert total == th.total_param_count(cfg), name


def test_param_counts_llama7b_magnitude():
    cfg = modeling.PRESETS["llama-7b"]
    n = th.total_param_count(cfg)
    assert 6.4e9 < n < 7.1e9, n  # ~6.7B


def test_zero_sharding_reduces_states():
    cfg = modeling.PRESETS["llama-0.3b"]
    ddp = th.layer_states_mb(cfg, LayerStrategy(dp_type="ddp"), world=8)
    z2 = th.layer_states_mb(cfg, LayerStrategy(dp_type="zero2"), world=8)
    z3 = th.layer_states_mb(cfg, LayerStrategy(dp_type="zero3"), world=8)
    assert ddp > z2 > z3
    tp2 = th.layer_states_mb(cfg, LayerStrategy(tp=2), world=8)
    assert abs(tp2 - (ddp - 0.5 * th.layer_param_count(cfg) * 4 / 1e6 / 2) / 2) < ddp * 0.3


def test_activation_estimate_flash_vs_xla():
    cfg = modeling.PRESETS["llama-7b"].replace(attn_impl="flash")
    s = LayerStrategy()
    flash = th.layer_activation_mb_per_sample(cfg, s)
    xla = th.layer_activation_mb_per_sample(cfg.replace(attn_impl="xla"), s)
    assert xla > flash  # (S,S) probs dominate
    # TP and SP shard activations
    tp4 = th.layer_activation_mb_per_sample(cfg, LayerStrategy(tp=4))
    tp4sp = th.layer_activation_mb_per_sample(cfg, LayerStrategy(tp=4, sp=True))
    assert flash > tp4 > tp4sp


def test_check_cost_model_table():
    from galvatron_tpu.search.cost_model import ProfiledHardware, ProfiledLayerType, ProfiledModelCosts
    from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace

    costs = ProfiledModelCosts(
        layer_types={
            0: ProfiledLayerType(
                fwd_ms_per_sample=1.0,
                parameter_mb=50.0,
                activation_mb_per_sample={1: 40.0, 2: 22.0, 4: 12.0},
                boundary_activation_mb_per_sample=4.0,
            )
        },
        other_param_mb=100.0,
        other_act_mb_per_sample=8.0,
    )
    eng = SearchEngine(
        costs, ProfiledHardware(), num_layers=4,
        space=SearchSpace(world_size=8), memory_budget_mb=16000,
    )
    table = eng.check_cost_model(global_bsz=8)
    assert "states MB" in table and "vocab strategy" in table
    assert "vtp2-zero3" in table  # vocab-TP tradeoff rows (searched dimension)
    # every generated strategy appears as a row
    assert table.count("\n") >= 4
    # explicit strategies path
    t2 = eng.check_cost_model(8, strategies=[LayerStrategy(tp=2, dp_type="zero3")])
    assert "1-2-4f" in t2


def test_analytic_costs_drive_search():
    """Search end-to-end on purely analytic costs (no profiling)."""
    from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace

    cfg = modeling.PRESETS["llama-0.3b"].replace(num_layers=4, attn_impl="flash")
    costs = th.analytic_model_costs(cfg, seq_len=512)
    assert costs.layer_types[0].fwd_ms_per_sample > 0
    from galvatron_tpu.search.cost_model import ProfiledHardware

    eng = SearchEngine(
        costs, ProfiledHardware(), num_layers=4,
        space=SearchSpace(world_size=8, max_tp=4), memory_budget_mb=8000,
    )
    res = eng.search([8], max_chunks=4)
    assert res is not None
    assert res.throughput_samples_per_s > 0
    res.config.validate(8)


def test_report_lines():
    cfg = modeling.PRESETS["llama-0.3b"]
    r = th.report(cfg, LayerStrategy(tp=2, dp_type="zero3"), world=8)
    s = r.lines()
    assert "params: total" in s and "per-chip layer states" in s
    assert r.model_states_total_mb > 0


# -- dropless top-k MoE (OLMoE-class): time counts k experts, memory all E ----


def olmoe_layer(**kw):
    return modeling.PRESETS["olmoe-1b-7b"].replace(num_layers=1, **kw)


def test_olmoe_param_counts_match_actual_init():
    cfg = olmoe_layer(vocab_size=128, hidden_size=64, num_heads=4, ffn_dim=32, moe_experts=8,
                      moe_top_k=2, max_seq_len=32)
    params = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    assert _count_params(params["layers"][0]) == th.layer_param_count(cfg)
    assert _count_params(params) == th.total_param_count(cfg)
    full = olmoe_layer()
    # one published layer: 419.6 M held (402.7 M in its experts), 67.1 M met by a token
    assert th.layer_param_count(full) == 419_569_664
    assert th.moe_expert_params(full) == 402_653_184
    assert th.layer_active_param_count(full) == 419_569_664 - 56 * 3 * 2048 * 1024


def test_topk_layer_time_is_k_experts_a_token_and_memory_all_experts():
    cfg = olmoe_layer()
    costs = th.analytic_model_costs(cfg, seq_len=4096)
    lt = costs.layer_types[0]
    one = th.analytic_model_costs(cfg.replace(moe_top_k=1), seq_len=4096).layer_types[0]
    dense = th.analytic_model_costs(
        cfg.replace(moe_experts=0, moe_router="switch"), seq_len=4096).layer_types[0]
    # a dense layer of the same widths = attention + ONE expert; top-1 adds the router
    expert = 2.0 * 3 * 2048 * 1024 * 4096 / (100e12 * 0.4) * 1e3
    router = 2.0 * 2048 * 64 * 4096 / (100e12 * 0.4) * 1e3
    assert one.fwd_ms_per_sample - dense.fwd_ms_per_sample == pytest.approx(router, rel=1e-6)
    assert lt.fwd_ms_per_sample - one.fwd_ms_per_sample == pytest.approx(7 * expert, rel=1e-6)
    # memory: every one of the 64 experts is held, whatever k
    assert lt.parameter_mb == one.parameter_mb == pytest.approx(419_569_664 * 4 / 1e6)
    assert lt.moe_expert_param_fraction == pytest.approx(402_653_184 / 419_569_664)
    # nothing of it scales with ep (the search leaves ep out); tp leaves the routed share whole
    assert lt.moe_expert_time_fraction == 0.0
    assert 0.5 < lt.moe_untp_time_fraction < 0.7
    assert dense.moe_untp_time_fraction == 0.0


def test_topk_layer_time_under_tp_divides_only_with_sequence_parallelism():
    from galvatron_tpu.search.cost_model import ProfiledHardware, layer_time_cost

    lt = th.analytic_model_costs(olmoe_layer(), seq_len=4096).layer_types[0]
    hw = ProfiledHardware()

    def compute(s):
        return layer_time_cost(lt, s, hw, world=4, pp=1, global_bsz=4, mixed_precision="bf16")

    # compare the models' compute terms through strategies whose communication is priced
    # the same way: tp 4 with and without sp move the same boundary volume
    plain, sp = compute(LayerStrategy(tp=4)), compute(LayerStrategy(tp=4, sp=True))
    assert plain > sp
    routed = lt.fwd_ms_per_sample * lt.moe_untp_time_fraction * 4 * 3.0  # 4 samples, fwd + bwd
    assert plain - sp == pytest.approx(routed * (1 - 1 / 4), rel=1e-6)
