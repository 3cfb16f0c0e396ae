"""Encoder-decoder (T5-class) support: cross-attention through the hybrid
runtime + the multi-layer-type search (reference legacy t5 model_type and the
multi-layer-type DP, galvatron/core/dynamic_programming.py:304-455)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.core.optim import AdamConfig
from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models import modeling
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.parallel.hybrid import build_runtime

T5 = ModelConfig(
    vocab_size=128, hidden_size=64, num_layers=2, num_heads=4, ffn_dim=128,
    max_seq_len=16, enc_layers=2, enc_seq=16, dtype=jnp.float32,
    pos_embed="learned", norm_type="rms", act_fn="gelu", tie_word_embeddings=True,
)


def batch(seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(0, 128, (8, T5.sample_len + 1)), jnp.int32)


def test_cross_attention_uses_encoder():
    """Changing the encoder input must change decoder logits."""
    params = modeling.init_model_params(jax.random.key(0), T5)
    b = batch()
    enc, dec = b[:, : T5.enc_seq], b[:, T5.enc_seq : -1]
    f = jax.jit(lambda e, d: modeling.forward_encdec(params, e, d, T5))
    out1 = np.asarray(f(enc, dec))
    out2 = np.asarray(f((enc + 1) % 128, dec))
    assert not np.allclose(out1, out2)
    # params actually carry cross-attention weights
    assert "cross" in params["layers"][0] and "enc_layers" in params


def test_encdec_trains_and_memorizes():
    hp = HybridParallelConfig.uniform(4, tp=1, mixed_precision="fp32")
    rt = build_runtime(T5, hp, adam=AdamConfig(lr=3e-3), global_batch_size=8)
    state = rt.init_state(jax.random.key(0))
    b = batch()
    losses = []
    for _ in range(5):
        state, loss = rt.train_step(state, b)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_encdec_parity_tp2_and_heterogeneous():
    """Hybrid strategies reproduce the single-device enc-dec loss, including
    different strategies for encoder vs decoder layers."""
    hp1 = HybridParallelConfig.uniform(4, tp=1, mixed_precision="fp32")
    hp2 = HybridParallelConfig(
        pp=1,
        layer_strategies=[
            LayerStrategy(tp=2, sp=True),        # enc 0
            LayerStrategy(tp=1, dp_type="zero3"),  # enc 1
            LayerStrategy(tp=2, ckpt=True),      # dec 0
            LayerStrategy(tp=4, dp_type="zero2"),  # dec 1
        ],
        vocab_tp=2,
        mixed_precision="fp32",
    )
    r1 = build_runtime(T5, hp1, adam=AdamConfig(lr=1e-3), global_batch_size=8)
    r2 = build_runtime(T5, hp2, adam=AdamConfig(lr=1e-3), global_batch_size=8)
    s1, s2 = r1.init_state(jax.random.key(0)), r2.init_state(jax.random.key(0))
    b = batch()
    np.testing.assert_allclose(
        float(r1.eval_loss(s1, b)), float(r2.eval_loss(s2, b)), rtol=2e-5
    )
    # decoder layer 1 (strategy index 3) is tp=4 on wqkv (blocked layout:
    # (h, 3, n*hd), tp shards the head dim of each slot)
    spec = s2["params"]["layers"][1]["attn"]["wqkv"].sharding.spec
    assert spec[2] is not None and len(spec[2]) == 2  # two binary axes = tp4


def test_encdec_rejects_cp_and_bad_pipeline_shapes():
    hp2 = HybridParallelConfig.uniform(4, cp=2, mixed_precision="fp32")
    with pytest.raises(ValueError, match="enc-dec"):
        build_runtime(T5, hp2, adam=AdamConfig(), global_batch_size=8)
    # ANY chunk count is legal (ring alignment is per-chunk) — the former
    # chunks % pp requirement was vestigial; chunks=1 at pp=2 builds
    hp3 = HybridParallelConfig.uniform(4, pp=2, chunks=1, mixed_precision="fp32")
    build_runtime(T5, hp3, adam=AdamConfig(), global_batch_size=8)
    # sub-stacks smaller than pp are legal (zero-layer masked stages) — only
    # an EMPTY stack is rejected
    from galvatron_tpu.parallel.pipeline_encdec import validate_encdec_pipeline

    cfg4 = T5.replace(enc_layers=2, num_layers=2)
    hp4 = HybridParallelConfig.uniform(4, pp=4, chunks=4, mixed_precision="fp32")
    lay = validate_encdec_pipeline(cfg4, hp4)
    assert sorted(lay.div_e) == [0, 0, 1, 1]
    cfg5 = T5.replace(enc_layers=0, num_layers=4)
    with pytest.raises(ValueError, match="at least one"):
        validate_encdec_pipeline(cfg5, HybridParallelConfig.uniform(
            4, pp=4, chunks=4, mixed_precision="fp32"))


@pytest.mark.parametrize("tp,dp_type,ckpt", [(1, "ddp", False), (2, "zero3", True)])
def test_encdec_pp2_parity(tp, dp_type, ckpt):
    """T5-class pp=2 (two coupled sub-pipelines) matches the flat pp=1 loss
    on identical weights — the reference pipelines enc-dec by arbitrary stage
    ranges (core/pipeline/pipeline.py:75-77); this is the capability
    equivalent."""
    hp = HybridParallelConfig.uniform(
        4, pp=2, tp=tp, dp_type=dp_type, ckpt=ckpt, chunks=2,
        vocab_tp=tp, mixed_precision="fp32",
    )
    rt = build_runtime(T5, hp, adam=AdamConfig(lr=1e-3), global_batch_size=8)
    flat = modeling.init_model_params(jax.random.key(0), T5)
    state = rt.init_state_from(flat)
    b = batch()
    ref = float(jax.jit(lambda p, bb: modeling.lm_loss(p, bb, T5))(flat, b))
    np.testing.assert_allclose(float(rt.eval_loss(state, b)), ref, rtol=3e-5, atol=3e-5)
    state, loss = rt.train_step(state, b)
    state, loss2 = rt.train_step(state, b)
    assert np.isfinite(float(loss2)) and float(loss2) < float(loss)


def test_encdec_pp2_ragged_counts_parity():
    """E=3 enc / D=5 dec layers at pp=2 — neither divisible by pp: the padded
    per-sub-stack divisions (reference: arbitrary stage ranges,
    core/pipeline/pipeline.py:75-77) must reproduce the flat pp=1 loss on
    identical weights, train, and round-trip the portable checkpoint layout."""
    cfg = T5.replace(enc_layers=3, num_layers=5)
    hp = HybridParallelConfig.uniform(8, pp=2, chunks=2, mixed_precision="fp32")
    rt = build_runtime(cfg, hp, adam=AdamConfig(lr=1e-3), global_batch_size=8)
    flat = modeling.init_model_params(jax.random.key(0), cfg)
    state = rt.init_state_from(flat)
    rng = np.random.RandomState(5)
    b = jnp.asarray(rng.randint(0, 128, (8, cfg.sample_len + 1)), jnp.int32)
    ref = float(jax.jit(lambda p, bb: modeling.lm_loss(p, bb, cfg))(flat, b))
    np.testing.assert_allclose(float(rt.eval_loss(state, b)), ref, rtol=3e-5, atol=3e-5)
    state, loss = rt.train_step(state, b)
    state, loss2 = rt.train_step(state, b)
    assert np.isfinite(float(loss2)) and float(loss2) < float(loss)
    # flatten drops padding and returns exactly E + D layers
    flat2 = rt.flatten_params(state["params"])
    assert len(flat2["enc_layers"]) == 3 and len(flat2["layers"]) == 5
    # an explicit 2*pp division (enc [2,1] ‖ dec [2,3]) is also accepted
    hp2 = HybridParallelConfig.uniform(8, pp=2, chunks=2, mixed_precision="fp32")
    hp2.pp_division = [2, 1, 2, 3]
    rt2 = build_runtime(cfg, hp2, adam=AdamConfig(lr=1e-3), global_batch_size=8)
    s2 = rt2.init_state_from(flat)
    np.testing.assert_allclose(float(rt2.eval_loss(s2, b)), ref, rtol=3e-5, atol=3e-5)
    # a user-provided single-stack division is rejected, not silently ignored
    hp3 = HybridParallelConfig.uniform(8, pp=2, chunks=2, mixed_precision="fp32")
    hp3.pp_division = [5, 3]
    with pytest.raises(ValueError, match="2\\*pp"):
        build_runtime(cfg, hp3, adam=AdamConfig(lr=1e-3), global_batch_size=8)


@pytest.mark.parametrize(
    "E,D,chunks",
    [
        (4, 4, 4),
        # ragged trajectory is also pinned by the dryrun + ragged parity test
        pytest.param(3, 5, 2, marks=pytest.mark.slow),
    ],
)
def test_encdec_1f1b_training_matches_flat_trajectory(E, D, chunks):
    """1F1B-ordered enc-dec (hand-written backward over the coupled
    sub-pipelines, bounded stashes): two train steps must track a manual flat
    AdamW loop exactly — the strongest gradient check; includes a ragged
    (E=3, D=5) division."""
    from tests._stack_harness import tracks_the_flat_trajectory

    cfg = T5.replace(enc_layers=E, num_layers=D)
    hp = HybridParallelConfig.uniform(
        E + D, pp=2, chunks=chunks, mixed_precision="fp32",
        pipeline_type="pipedream_flush",
    )
    rt = build_runtime(cfg, hp, adam=AdamConfig(lr=1e-3), global_batch_size=8)
    flat = modeling.init_model_params(jax.random.key(1), cfg)
    state = rt.init_state_from(flat)
    batches = [jnp.asarray(np.random.RandomState(i).randint(0, 128, (8, cfg.sample_len + 1)),
                           jnp.int32) for i in range(2)]
    tracks_the_flat_trajectory(rt, state, flat, cfg, batches, AdamConfig(lr=1e-3))


@pytest.mark.slow  # fp16 pipeline variants are slow-marked across the suite
def test_encdec_pp2_fp16_tracks_fp32():
    """fp16 (dynamic loss scaling) through the enc-dec pipeline: losses track
    the fp32 trajectory loosely, stay finite, and the scaler advances —
    previously rejected outright."""
    mk = lambda mp: HybridParallelConfig.uniform(
        4, pp=2, tp=1, chunks=2, mixed_precision=mp
    )
    rt16 = build_runtime(T5, mk("fp16"), adam=AdamConfig(lr=1e-3), global_batch_size=8)
    rt32 = build_runtime(T5, mk("fp32"), adam=AdamConfig(lr=1e-3), global_batch_size=8)
    s16 = rt16.init_state(jax.random.key(0))
    s32 = rt32.init_state(jax.random.key(0))
    assert "scaler" in s16 and float(s16["scaler"]["scale"]) == 2.0**16
    l16, l32 = [], []
    for i in range(3):
        b = batch(i)
        s16, a = rt16.train_step(s16, b)
        s32, c = rt32.train_step(s32, b)
        l16.append(float(a))
        l32.append(float(c))
    assert np.isfinite(l16).all()
    np.testing.assert_allclose(l16, l32, rtol=0.05, atol=0.05)
    assert int(s16["scaler"]["good_steps"]) == 3


def test_multi_layer_type_search():
    """Enc and dec layer types with different costs flow through the search
    (the reference's multi-layer-type DP) and the result trains."""
    from galvatron_tpu.search.cost_model import (
        ProfiledHardware,
        ProfiledLayerType,
        ProfiledModelCosts,
    )
    from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace

    enc_lt = ProfiledLayerType(
        fwd_ms_per_sample=1.0, parameter_mb=40.0,
        activation_mb_per_sample={1: 20.0, 2: 10.0, 4: 5.0},
        boundary_activation_mb_per_sample=2.0,
    )
    dec_lt = ProfiledLayerType(
        fwd_ms_per_sample=2.5, parameter_mb=70.0,  # cross-attn makes dec heavier
        activation_mb_per_sample={1: 40.0, 2: 20.0, 4: 10.0},
        boundary_activation_mb_per_sample=2.0,
    )
    costs = ProfiledModelCosts(
        layer_types={0: enc_lt, 1: enc_lt, 2: dec_lt, 3: dec_lt},
        other_param_mb=30.0, other_act_mb_per_sample=4.0,
        other_fwd_ms_per_sample=0.2,
    )
    hw = ProfiledHardware(
        allreduce_bw={"2_1": 150.0, "2_0": 30.0, "4_1": 140.0, "8_1": 120.0},
        p2p_bw={2: 50.0}, overlap_coe=1.1,
    )
    eng = SearchEngine(
        costs, hw, num_layers=4,
        space=SearchSpace(world_size=8, pp_choices=[1]),
        memory_budget_mb=700.0,
    )
    res = eng.search([8])
    assert res is not None
    hp = res.config
    assert len(hp.layer_strategies) == 4
    # heavier decoder layers must shave more memory than encoder layers can
    # afford to keep (or at minimum the plan is feasible and trains):
    rt = build_runtime(
        T5, HybridParallelConfig(
            pp=1, layer_strategies=hp.layer_strategies, chunks=hp.chunks,
            vocab_tp=hp.vocab_tp, mixed_precision="fp32",
        ),
        adam=AdamConfig(lr=1e-3), global_batch_size=8,
    )
    state = rt.init_state(jax.random.key(0))
    state, loss = rt.train_step(state, batch())
    assert np.isfinite(float(loss))


def test_t5_family_entry(capsys):
    from galvatron_tpu.models import t5

    rc = t5.main(
        ["train", "--model_size", "t5-base",
         "--hidden_size", "64", "--num_layers", "2", "--num_heads", "4",
         "--ffn_dim", "128", "--vocab_size", "128", "--seq_length", "16",
         "--enc_layers", "2", "--enc_seq", "16",
         "--global_train_batch_size", "8", "--train_iters", "1",
         "--mixed_precision", "fp32", "--check_loss", "1"]
    )
    assert rc == 0
    assert "iter 0: loss" in capsys.readouterr().out


def test_multi_layer_type_search_pp2():
    """The multi-layer-type search emits a pp>1 config for enc-dec models
    (reference: per-stage DP, dynamic_programming.py:304-455) and the config
    builds + trains through the enc-dec pipeline."""
    from galvatron_tpu.search.cost_model import (
        ProfiledHardware,
        ProfiledLayerType,
        ProfiledModelCosts,
    )
    from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace

    enc_lt = ProfiledLayerType(
        fwd_ms_per_sample=1.0, parameter_mb=40.0,
        activation_mb_per_sample={1: 20.0, 2: 10.0, 4: 5.0},
        boundary_activation_mb_per_sample=2.0,
    )
    dec_lt = ProfiledLayerType(
        fwd_ms_per_sample=2.5, parameter_mb=70.0,
        activation_mb_per_sample={1: 40.0, 2: 20.0, 4: 10.0},
        boundary_activation_mb_per_sample=2.0,
    )
    costs = ProfiledModelCosts(
        layer_types={0: enc_lt, 1: enc_lt, 2: dec_lt, 3: dec_lt},
        other_param_mb=30.0, other_act_mb_per_sample=4.0,
        other_fwd_ms_per_sample=0.2,
    )
    hw = ProfiledHardware(
        allreduce_bw={"2_1": 150.0, "2_0": 30.0, "4_1": 140.0, "8_1": 120.0},
        p2p_bw={2: 50.0}, overlap_coe=1.1,
    )
    eng = SearchEngine(
        costs, hw, num_layers=4,
        space=SearchSpace(world_size=8, pp_choices=[2], max_tp=2),
        memory_budget_mb=700.0,
    )
    res = eng.search([8])
    assert res is not None and res.config.pp == 2
    assert len(res.config.layer_strategies) == 4
    assert res.config.chunks % 2 == 0 and res.config.pipeline_type == "gpipe"
    # enc strategies (first 2) may differ from dec strategies (last 2), but
    # each pair must agree across stages (one virtual stage each here)
    ls = res.config.layer_strategies
    assert ls[0] == ls[1] and ls[2] == ls[3]
    rt = build_runtime(
        T5, res.config, adam=AdamConfig(lr=1e-3), global_batch_size=8,
    )
    state = rt.init_state(jax.random.key(0))
    state, loss = rt.train_step(state, batch())
    assert np.isfinite(float(loss))


def test_multi_layer_type_search_pp2_ragged():
    """The search emits a pp=2 config for an enc-dec model whose enc (3) and
    dec (5) counts are NOT divisible by pp (reference: per-stage DP over
    arbitrary stage ranges); the emitted 2*pp division loads and trains
    through the padded enc-dec pipeline."""
    from galvatron_tpu.search.cost_model import (
        ProfiledHardware,
        ProfiledLayerType,
        ProfiledModelCosts,
    )
    from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace

    enc_lt = ProfiledLayerType(
        fwd_ms_per_sample=1.0, parameter_mb=40.0,
        activation_mb_per_sample={1: 20.0, 2: 10.0, 4: 5.0},
        boundary_activation_mb_per_sample=2.0,
    )
    dec_lt = ProfiledLayerType(
        fwd_ms_per_sample=2.5, parameter_mb=70.0,
        activation_mb_per_sample={1: 40.0, 2: 20.0, 4: 10.0},
        boundary_activation_mb_per_sample=2.0,
    )
    costs = ProfiledModelCosts(
        layer_types={i: (enc_lt if i < 3 else dec_lt) for i in range(8)},
        other_param_mb=30.0, other_act_mb_per_sample=4.0,
        other_fwd_ms_per_sample=0.2,
    )
    hw = ProfiledHardware(
        allreduce_bw={"2_1": 150.0, "2_0": 30.0, "4_1": 140.0, "8_1": 120.0},
        p2p_bw={2: 50.0}, overlap_coe=1.1,
    )
    eng = SearchEngine(
        costs, hw, num_layers=8,
        space=SearchSpace(world_size=8, pp_choices=[2], max_tp=2),
        memory_budget_mb=1400.0,
    )
    res = eng.search([8])
    assert res is not None and res.config.pp == 2
    assert len(res.config.layer_strategies) == 8
    assert res.config.pp_division is not None and len(res.config.pp_division) == 4
    div = res.config.pp_division
    assert sum(div[:2]) == 3 and sum(div[2:]) == 5
    cfg = T5.replace(enc_layers=3, num_layers=5)
    rt = build_runtime(cfg, res.config, adam=AdamConfig(lr=1e-3), global_batch_size=8)
    state = rt.init_state(jax.random.key(0))
    rng = np.random.RandomState(9)
    b = jnp.asarray(rng.randint(0, 128, (8, cfg.sample_len + 1)), jnp.int32)
    state, loss = rt.train_step(state, b)
    assert np.isfinite(float(loss))


def test_encdec_measured_profile_two_types():
    """profile_model on an enc-dec config yields distinct enc/dec layer types
    (three-point layernum difference) that feed the multi-type search."""
    from galvatron_tpu.profiling.model import profile_model
    from galvatron_tpu.search.cost_model import ProfiledHardware
    from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace

    costs = profile_model(T5, bsz=8, measure_time=False)
    assert len(set(id(v) for v in costs.layer_types.values())) == 2
    enc, dec = costs.layer_types[0], costs.layer_types[T5.enc_layers]
    assert dec.parameter_mb > enc.parameter_mb  # cross-attention params
    eng = SearchEngine(
        costs, ProfiledHardware(), num_layers=T5.total_layers,
        space=SearchSpace(world_size=8, pp_choices=[2], max_tp=2),
        memory_budget_mb=2000.0,
    )
    r = eng.evaluate(2, 8, 2, "gpipe")
    assert r is not None and r.config.pp == 2


def test_encdec_search_emits_1f1b_and_trains():
    """The multi-type search prices the coupled enc-dec 1F1B
    (pipeline_type=pipedream_flush): at equal (pp, bsz, chunks) it must
    predict LESS activation memory than the gpipe schedule (input-stash ring
    vs act x chunks) at a higher-or-equal predicted time (more ticks +
    section recompute), and under a budget only the 1F1B fits, search()
    must emit it — and the emitted config must train. Reference: the
    multi-type DP prices any model under either schedule,
    galvatron/core/dynamic_programming.py:304-455."""
    from galvatron_tpu.profiling.model import profile_model
    from galvatron_tpu.search.cost_model import ProfiledHardware
    from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace

    costs = profile_model(T5, bsz=8, measure_time=False)

    def make_eng(budget, allow_ckpt=True):
        return SearchEngine(
            costs, ProfiledHardware(), num_layers=T5.total_layers,
            space=SearchSpace(world_size=4, pp_choices=[2], max_tp=2,
                              allow_ckpt=allow_ckpt),
            memory_budget_mb=budget, mixed_precision="fp32",
            mem_unit_mb=0.0625,  # tiny model: sub-MB per-layer activations
        )

    eng = make_eng(2000.0)
    r_g = eng.evaluate(2, 64, 64, "gpipe")
    r_f = eng.evaluate(2, 64, 64, "pipedream_flush")
    assert r_g is not None and r_f is not None
    assert r_f.config.pipeline_type == "pipedream_flush"
    assert r_f.memory_mb < r_g.memory_mb  # bounded stash vs act x chunks
    assert r_f.cost_ms >= r_g.cost_ms  # more ticks + section recompute

    # with remat disallowed (the regime where 1F1B is THE memory lever —
    # gpipe must hold act x chunks while the 1F1B stash ring is bounded), a
    # budget just above the 1F1B footprint leaves no feasible gpipe and the
    # search emits the 1F1B schedule. (With ckpt allowed, gpipe+full-remat
    # is often lighter than the coupled 1F1B, whose fp32 dx cotangent
    # buffers are charged via coupled_1f1b_overhead_mb — the search prices
    # all three and picks the real winner.)
    r_f2 = make_eng(2000.0, allow_ckpt=False).evaluate(2, 64, 64, "pipedream_flush")
    assert "coupled_1f1b_overhead_mb" in r_f2.details
    tight = make_eng(r_f2.memory_mb * 1.05, allow_ckpt=False)
    assert tight.evaluate(2, 64, 64, "gpipe") is None
    r = tight.search([64], max_chunks=64)
    assert r is not None and r.config.pipeline_type == "pipedream_flush"

    # the emitted config trains through the coupled 1F1B runtime
    rt = build_runtime(T5, r.config, adam=AdamConfig(lr=3e-3), global_batch_size=64)
    state = rt.init_state(jax.random.key(0))
    rng = np.random.RandomState(3)
    b = jnp.asarray(rng.randint(0, 128, (64, T5.sample_len + 1)), jnp.int32)
    losses = []
    for _ in range(4):
        state, loss = rt.train_step(state, rt.shard_batch(b))
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_encdec_small_encoder_stack_below_pp():
    """A sub-stack SMALLER than pp (E=2 at pp=4) rides zero-layer masked
    stages (balanced_division yields [0,1,1,0]): eval parity against the
    flat model on identical weights, training works under BOTH coupled
    schedules, and the search emits a pp=4 config for it. Reference:
    arbitrary per-stage layer ranges, core/pipeline/pipeline.py:75-77."""
    cfg = T5.replace(enc_layers=2, num_layers=4)
    flat = modeling.init_model_params(jax.random.key(0), cfg)
    rng = np.random.RandomState(7)
    b = jnp.asarray(rng.randint(0, 128, (8, cfg.sample_len + 1)), jnp.int32)
    ref = float(jax.jit(lambda p, bb: modeling.lm_loss(p, bb, cfg))(flat, b))
    for ptype in ("gpipe", "pipedream_flush"):
        hp = HybridParallelConfig.uniform(
            6, pp=4, chunks=4, mixed_precision="fp32", pipeline_type=ptype
        )
        rt = build_runtime(cfg, hp, adam=AdamConfig(lr=1e-3), global_batch_size=8)
        state = rt.init_state_from(flat)
        np.testing.assert_allclose(
            float(rt.eval_loss(state, b)), ref, rtol=3e-5, atol=3e-5,
            err_msg=ptype,
        )
        state, loss = rt.train_step(state, b)
        state, loss2 = rt.train_step(state, b)
        assert np.isfinite(float(loss2)) and float(loss2) < float(loss), ptype

    # the search no longer bails on count < pp
    from galvatron_tpu.profiling.model import profile_model
    from galvatron_tpu.search.cost_model import ProfiledHardware
    from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace

    costs = profile_model(cfg, bsz=8, measure_time=False)
    eng = SearchEngine(
        costs, ProfiledHardware(), num_layers=cfg.total_layers,
        space=SearchSpace(world_size=4, pp_choices=[4], max_tp=1),
        memory_budget_mb=2000.0, mixed_precision="fp32",
    )
    r = eng.evaluate(4, 8, 4, "gpipe")
    assert r is not None and r.config.pp == 4
    assert r.config.pp_division[:4] == [0, 1, 1, 0]  # enc split with zeros
    # the emitted config must survive validate() and BUILD (zero-entry 2*pp
    # divisions are legal only for the enc-dec layout)
    rt4 = build_runtime(cfg, r.config, adam=AdamConfig(lr=1e-3), global_batch_size=8)
    s4 = rt4.init_state(jax.random.key(1))
    s4, l4 = rt4.train_step(s4, rt4.shard_batch(b))
    assert np.isfinite(float(l4))


def test_encdec_any_chunks_parity():
    """The coupled engines run ANY chunk count — ring alignment is per-chunk
    (chunk m's section-k output wraps into device 0 exactly at its
    section-(k+1) slot for every m), so the former chunks % pp requirement
    was vestigial. Train-trajectory parity at chunks=3 and chunks=1 on pp=2,
    both schedules, against the flat single-device AdamW loop."""
    from tests._stack_harness import flat_losses

    flat = modeling.init_model_params(jax.random.key(0), T5)
    rng = np.random.RandomState(7)
    batches = [
        jnp.asarray(rng.randint(0, 128, (24, T5.sample_len + 1)), jnp.int32)
        for _ in range(2)
    ]
    adam = AdamConfig(lr=1e-3)
    ref = flat_losses(T5, flat, batches, adam)
    for chunks, ptype in [(3, "gpipe"), (3, "pipedream_flush"), (1, "pipedream_flush")]:
        hp = HybridParallelConfig.uniform(
            T5.total_layers, pp=2, chunks=chunks, mixed_precision="fp32",
            pipeline_type=ptype,
        )
        rt = build_runtime(T5, hp, adam=adam, global_batch_size=24)
        st = rt.init_state_from(flat)
        losses = []
        for b in batches:
            st, loss = rt.train_step(st, rt.shard_batch(b))
            losses.append(float(loss))
        np.testing.assert_allclose(
            losses, ref, rtol=2e-4, atol=2e-4,
            err_msg=f"chunks={chunks} {ptype}",
        )
