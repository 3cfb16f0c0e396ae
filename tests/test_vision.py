"""Vision families (ViT / Swin) through the hybrid-parallel runtime.

The reference carries vit/swin only as legacy model_type branches
(galvatron/core/parallel.py:64-89, cost_model.py:76,87-106); here they are
live families on the framework-wide int32 pixel-batch contract. Tests mirror
the `--check_loss` methodology (SURVEY §4): hybrid strategies must reproduce
the single-device fp32 loss trajectory.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.core.optim import AdamConfig
from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models import modeling
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.parallel.hybrid import build_runtime
from tests._stack_harness import flat_losses, tracks_the_flat_trajectory

VIT_CFG = ModelConfig(
    vocab_size=1, hidden_size=64, num_layers=4, num_heads=4, max_seq_len=0,
    pos_embed="learned", norm_type="layernorm", act_fn="gelu", causal=False,
    objective="cls", image_size=16, patch_size=4, num_classes=16,
    dtype=jnp.float32,
)
from _vision_common import SWIN_TINY as SWIN_CFG, make_vision_batches as make_batches

ADAM = AdamConfig(lr=1e-3, grad_clip=1.0)


def reference_losses(cfg, batches):
    return flat_losses(cfg, modeling.init_model_params(jax.random.key(0), cfg), batches, ADAM)


def run_hybrid(cfg, hp, batches):
    rt = build_runtime(cfg, hp, adam=ADAM, global_batch_size=8)
    state = rt.init_state(jax.random.key(0))
    losses = []
    for b in batches:
        state, loss = rt.train_step(state, b)
        losses.append(float(loss))
    return losses


@pytest.fixture(scope="module")
def vit_ref():
    batches = make_batches(VIT_CFG)
    return batches, reference_losses(VIT_CFG, batches)


VIT_STRATEGIES = {
    "tp2_sp": HybridParallelConfig.uniform(
        4, tp=2, sp=True, mixed_precision="fp32", vocab_tp=2
    ),
    "zero3_ckpt": HybridParallelConfig.uniform(
        4, tp=1, dp_type="zero3", ckpt=True, mixed_precision="fp32",
        embed_dp_type="zero3",
    ),
    "accum2": HybridParallelConfig.uniform(4, tp=1, mixed_precision="fp32", chunks=2),
}


@pytest.mark.parametrize("name", sorted(VIT_STRATEGIES))
def test_vit_loss_parity(vit_ref, name):
    batches, ref = vit_ref
    got = run_hybrid(VIT_CFG, VIT_STRATEGIES[name], batches)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def _unstack_pipe_params(pipe_params, cfg, pp):
    """stage-stacked → flat pp=1 param tree (test_pipeline methodology)."""
    lps = cfg.num_layers // pp
    layers = []
    for s in range(pp):
        for j in range(lps):
            layers.append(jax.tree.map(lambda a: np.asarray(a)[s], pipe_params["stages"][j]))
    flat = {k: jax.tree.map(np.asarray, v) for k, v in pipe_params.items() if k != "stages"}
    flat["layers"] = layers
    return flat


@pytest.mark.parametrize("schedule", ["gpipe", "pipedream_flush"])
def test_vit_pipeline_parity(vit_ref, schedule):
    """ViT layers are homogeneous → every pipeline schedule applies. Compare
    each step's loss against a single-device AdamW loop started from the
    identical (unstacked) params."""
    batches, _ = vit_ref
    pp = 2
    hp = HybridParallelConfig.uniform(
        4, pp=pp, tp=2, chunks=2, mixed_precision="fp32", vocab_tp=2,
        pipeline_type=schedule,
    )
    rt = build_runtime(VIT_CFG, hp, adam=ADAM, global_batch_size=8)
    state = rt.init_state(jax.random.key(0))
    flat = jax.tree.map(jnp.asarray, _unstack_pipe_params(state["params"], VIT_CFG, pp))
    tracks_the_flat_trajectory(rt, state, flat, VIT_CFG, batches, ADAM)


def test_vit_interleaved_trains(vit_ref):
    batches, _ = vit_ref
    hp = HybridParallelConfig.uniform(
        4, pp=2, vpp=2, chunks=2, mixed_precision="fp32", pipeline_type="gpipe"
    )
    got = run_hybrid(VIT_CFG, hp, batches * 2)
    assert np.isfinite(got).all() and got[-1] < got[0]


@pytest.fixture(scope="module")
def swin_ref():
    batches = make_batches(SWIN_CFG, seed=7)
    return batches, reference_losses(SWIN_CFG, batches)


SWIN_STRATEGIES = {
    "tp2": HybridParallelConfig.uniform(4, tp=2, mixed_precision="fp32"),
    # per-stage heterogeneity: narrow stage 0 data-parallel, wide stage 1
    # tensor-parallel + sequence-sharded + rematerialized
    "hetero": HybridParallelConfig(
        pp=1,
        layer_strategies=[
            LayerStrategy(tp=1, dp_type="zero3"),
            LayerStrategy(tp=1, dp_type="zero3"),
            LayerStrategy(tp=2, sp=True, ckpt="full"),
            LayerStrategy(tp=2, sp=True, ckpt="full"),
        ],
        mixed_precision="fp32",
    ),
}


@pytest.mark.parametrize("name", sorted(SWIN_STRATEGIES))
def test_swin_loss_parity(swin_ref, name):
    batches, ref = swin_ref
    got = run_hybrid(SWIN_CFG, SWIN_STRATEGIES[name], batches)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize(
    "pp,tp",
    [(2, 1), pytest.param(2, 2, marks=pytest.mark.slow)],
)
def test_swin_pp2_parity(swin_ref, pp, tp):
    """Swin pp>1: K coupled sections over the pp ring (pair-stacked stages).
    The pipeline must reproduce the flat pp=1 loss on identical weights and
    track the reference trajectory; flatten drops padding exactly."""
    batches, ref_traj = swin_ref
    hp = HybridParallelConfig.uniform(
        4, pp=pp, tp=tp, chunks=2, vocab_tp=tp, mixed_precision="fp32"
    )
    rt = build_runtime(SWIN_CFG, hp, adam=ADAM, global_batch_size=8)
    flat = modeling.init_model_params(jax.random.key(0), SWIN_CFG)
    state = rt.init_state_from(flat)
    losses = []
    for b in batches:
        state, loss = rt.train_step(state, b)
        losses.append(float(loss))
    np.testing.assert_allclose(losses, ref_traj, rtol=2e-4, atol=2e-4)
    flat2 = rt.flatten_params(state["params"])
    assert len(flat2["layers"]) == 4 and all(l is not None for l in flat2["layers"])


def test_swin_1f1b_parity(swin_ref):
    """The coupled-sections 1F1B (pipedream_flush): hand-written backward
    with per-section stash rings bounded by the schedule depth — must
    reproduce the flat single-device trajectory exactly like the
    gpipe-ordered engine (merge-on-sender placement is numerically identical
    to the gpipe body's merge-on-consumer; ppermute is exact)."""
    batches, ref_traj = swin_ref
    hp = HybridParallelConfig.uniform(
        4, pp=2, chunks=2, mixed_precision="fp32",
        pipeline_type="pipedream_flush",
    )
    rt = build_runtime(SWIN_CFG, hp, adam=ADAM, global_batch_size=8)
    flat = modeling.init_model_params(jax.random.key(0), SWIN_CFG)
    state = rt.init_state_from(flat)
    losses = []
    for b in batches:
        state, loss = rt.train_step(state, b)
        losses.append(float(loss))
    np.testing.assert_allclose(losses, ref_traj, rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # edge coverage; the pp=2 parity stays default
def test_swin_1f1b_sections_zero_pair_tp_fp16(swin_ref):
    """1F1B edge coverage: K=3 sections (chunks=4), pp=4 zero-pair stages,
    tp=2 composition, and fp16 dynamic scaling — each against the flat
    trajectory on identical weights."""
    batches, ref_traj = swin_ref
    # K=3 sections, chunks > pp
    cfg3 = SWIN_CFG.replace(num_layers=6, swin_depths=(2, 2, 2))
    b3 = make_batches(cfg3, seed=3, n=2)
    ref3 = reference_losses(cfg3, b3)
    hp3 = HybridParallelConfig.uniform(
        6, pp=2, chunks=4, mixed_precision="fp32", pipeline_type="pipedream_flush"
    )
    rt3 = build_runtime(cfg3, hp3, adam=ADAM, global_batch_size=8)
    s3 = rt3.init_state_from(modeling.init_model_params(jax.random.key(0), cfg3))
    l3 = []
    for b in b3:
        s3, loss = rt3.train_step(s3, b)
        l3.append(float(loss))
    np.testing.assert_allclose(l3, ref3, rtol=2e-4, atol=2e-4)
    # pp=4 on the 2-pair pyramid: zero-pair (masked) stages in every section
    hp4 = HybridParallelConfig.uniform(
        4, pp=4, chunks=4, mixed_precision="fp32", pipeline_type="pipedream_flush"
    )
    rt4 = build_runtime(SWIN_CFG, hp4, adam=ADAM, global_batch_size=8)
    s4 = rt4.init_state_from(modeling.init_model_params(jax.random.key(0), SWIN_CFG))
    s4, l4 = rt4.train_step(s4, batches[0])
    np.testing.assert_allclose(float(l4), ref_traj[0], rtol=2e-4, atol=2e-4)
    # tp=2 composition
    hpt = HybridParallelConfig.uniform(
        4, pp=2, tp=2, chunks=2, vocab_tp=2, mixed_precision="fp32",
        pipeline_type="pipedream_flush",
    )
    rtt = build_runtime(SWIN_CFG, hpt, adam=ADAM, global_batch_size=8)
    st = rtt.init_state_from(modeling.init_model_params(jax.random.key(0), SWIN_CFG))
    st, lt = rtt.train_step(st, batches[0])
    np.testing.assert_allclose(float(lt), ref_traj[0], rtol=2e-4, atol=2e-4)
    # fp16 dynamic scaling
    hpf = HybridParallelConfig.uniform(
        4, pp=2, chunks=2, mixed_precision="fp16", pipeline_type="pipedream_flush"
    )
    rtf = build_runtime(SWIN_CFG, hpf, adam=ADAM, global_batch_size=8)
    sf = rtf.init_state_from(modeling.init_model_params(jax.random.key(0), SWIN_CFG))
    sf, lf = rtf.train_step(sf, batches[0])
    assert np.isfinite(float(lf)) and abs(float(lf) - ref_traj[0]) < 0.05
    assert float(sf["scaler"]["scale"]) == 65536.0


def test_swin_search_prices_1f1b_and_emits_it_under_tight_budget():
    """The K-section search prices BOTH coupled schedules (the enc-dec
    behavior extended to Swin): at equal (pp, bsz, chunks) pipedream_flush
    must predict LESS activation memory (per-section stash rings
    min(chunks, 2(K-k)pp - 1) vs act x chunks) at higher-or-equal predicted
    time (2K*pp - 2 extra ticks + section recompute); with remat disallowed
    and a budget only the 1F1B fits, search() emits it — and the emitted
    config trains through the hand-written coupled backward."""
    from galvatron_tpu.search.cost_model import (
        ProfiledHardware,
        ProfiledLayerType,
        ProfiledModelCosts,
    )
    from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace

    lt0 = ProfiledLayerType(
        fwd_ms_per_sample=1.0, parameter_mb=10.0,
        activation_mb_per_sample={1: 8.0, 2: 4.0},
        boundary_activation_mb_per_sample=1.0,
    )
    lt1 = ProfiledLayerType(
        fwd_ms_per_sample=1.5, parameter_mb=30.0,
        activation_mb_per_sample={1: 6.0, 2: 3.0},
        boundary_activation_mb_per_sample=0.5,
    )
    costs = ProfiledModelCosts(
        layer_types={0: lt0, 1: lt0, 2: lt1, 3: lt1},
        other_param_mb=5.0, other_act_mb_per_sample=1.0,
        other_fwd_ms_per_sample=0.1,
    )

    def make_eng(budget, allow_ckpt=True):
        return SearchEngine(
            costs, ProfiledHardware(), num_layers=SWIN_CFG.num_layers,
            space=SearchSpace(world_size=4, pp_choices=[2], max_tp=2,
                              allow_ckpt=allow_ckpt),
            memory_budget_mb=budget, mixed_precision="fp32",
            mem_unit_mb=0.0625, section_pipeline=True,
        )

    eng = make_eng(2000.0)
    r_g = eng.evaluate(2, 64, 64, "gpipe")
    r_f = eng.evaluate(2, 64, 64, "pipedream_flush")
    assert r_g is not None and r_f is not None
    assert r_f.config.pipeline_type == "pipedream_flush"
    assert r_f.memory_mb < r_g.memory_mb  # bounded stash vs act x chunks
    assert r_f.cost_ms >= r_g.cost_ms  # more ticks + section recompute

    r_f2 = make_eng(2000.0, allow_ckpt=False).evaluate(2, 64, 64, "pipedream_flush")
    assert r_f2 is not None
    tight = make_eng(r_f2.memory_mb * 1.05, allow_ckpt=False)
    assert tight.evaluate(2, 64, 64, "gpipe") is None
    r = tight.search([64], max_chunks=64)
    assert r is not None and r.config.pipeline_type == "pipedream_flush"

    rt = build_runtime(SWIN_CFG, r.config, adam=ADAM, global_batch_size=64)
    state = rt.init_state(jax.random.key(0))
    b = make_batches(SWIN_CFG, seed=11, n=1, batch=64)[0]
    losses = []
    for _ in range(3):
        state, loss = rt.train_step(state, rt.shard_batch(b))
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


@pytest.mark.slow
def test_swin_1f1b_activation_footprint_measured():
    """The per-section stash bound min(chunks, 2(K-k)pp - 1), MEASURED on the
    compiled program: XLA's memory analysis of the actual train_step shows
    the 1F1B temp footprint plateaus as chunks grow while the gpipe-ordered
    autodiff backward grows with chunks (measured on the sim: 1.6M->2.3M
    [ratio 1.42, batch buffers only] vs 29.7M->80.6M [2.72])."""
    from galvatron_tpu.core.checkpoint import abstract_state_of

    cfg = SWIN_CFG.replace(image_size=32)  # longer maps so activations dominate

    def temp_bytes(ptype, chunks):
        hp = HybridParallelConfig.uniform(
            4, pp=2, chunks=chunks, mixed_precision="fp32", pipeline_type=ptype
        )
        rt = build_runtime(cfg, hp, adam=ADAM, global_batch_size=2 * chunks)
        batch = jax.ShapeDtypeStruct(
            (2 * chunks, cfg.sample_len + 1), jnp.int32, sharding=rt.batch_sharding
        )
        ma = rt.train_step.lower(abstract_state_of(rt), batch).compile().memory_analysis()
        if ma is None:
            pytest.skip("memory_analysis unavailable on this backend")
        return ma.temp_size_in_bytes

    r_1f1b = temp_bytes("pipedream_flush", 16) / temp_bytes("pipedream_flush", 4)
    r_gpipe = temp_bytes("gpipe", 16) / temp_bytes("gpipe", 4)
    assert r_1f1b < 2.0 < r_gpipe, (r_1f1b, r_gpipe)


@pytest.mark.slow  # edge coverage; the pp=2 parity + constraints stay default
def test_swin_pp4_zero_pair_stages_and_three_sections(swin_ref):
    """pp wider than a section's pair count leaves zero-pair (masked) stages;
    a 3-section pyramid exercises K>2 coupled sections. Both must match the
    flat loss on identical weights."""
    batches, _ = swin_ref
    # pp=4 on the 2-pair pyramid: two sections of 1 pair each -> 3 idle
    # stages per section
    hp4 = HybridParallelConfig.uniform(4, pp=4, chunks=4, mixed_precision="fp32")
    rt4 = build_runtime(SWIN_CFG, hp4, adam=ADAM, global_batch_size=8)
    flat = modeling.init_model_params(jax.random.key(0), SWIN_CFG)
    s4 = rt4.init_state_from(flat)
    ref = float(jax.jit(lambda p, b: modeling.lm_loss(p, b, SWIN_CFG))(flat, batches[0]))
    np.testing.assert_allclose(
        float(rt4.eval_loss(s4, batches[0])), ref, rtol=3e-5, atol=3e-5
    )
    # K=3 sections
    cfg3 = SWIN_CFG.replace(num_layers=6, swin_depths=(2, 2, 2))
    b3 = make_batches(cfg3, seed=3, n=1)[0]
    hp3 = HybridParallelConfig.uniform(6, pp=2, chunks=2, mixed_precision="fp32")
    rt3 = build_runtime(cfg3, hp3, adam=ADAM, global_batch_size=8)
    flat3 = modeling.init_model_params(jax.random.key(1), cfg3)
    s3 = rt3.init_state_from(flat3)
    ref3 = float(jax.jit(lambda p, b: modeling.lm_loss(p, b, cfg3))(flat3, b3))
    np.testing.assert_allclose(
        float(rt3.eval_loss(s3, b3)), ref3, rtol=3e-5, atol=3e-5
    )
    s3, l3 = rt3.train_step(s3, b3)
    assert np.isfinite(float(l3))


def test_swin_pipeline_constraints():
    # odd depths cannot pair-stack
    cfg_odd = SWIN_CFG.replace(num_layers=4, swin_depths=(1, 3))
    hp = HybridParallelConfig.uniform(4, pp=2, chunks=2, mixed_precision="fp32")
    with pytest.raises(ValueError, match="even"):
        build_runtime(cfg_odd, hp, adam=ADAM, global_batch_size=8)
    # pair halves must share a strategy
    hp_bad = HybridParallelConfig(
        pp=2, chunks=2, mixed_precision="fp32",
        layer_strategies=[
            LayerStrategy(tp=1), LayerStrategy(tp=2),
            LayerStrategy(tp=1), LayerStrategy(tp=2),
        ],
    )
    with pytest.raises(ValueError, match="pair"):
        build_runtime(SWIN_CFG, hp_bad, adam=ADAM, global_batch_size=8)


def test_swin_shift_mask_blocks_wrapped_pairs():
    """After the cyclic roll, a window containing wrapped image regions must
    not let those regions attend to each other; unwrapped windows attend
    fully."""
    m = modeling._swin_attn_mask(8, 8, 4, 2)
    assert m.shape == (4, 16, 16)
    assert m[0].all()  # top-left window: no wrap
    assert not m[1].all() and not m[2].all() and not m[3].all()
    assert (m == m.transpose(0, 2, 1)).all()  # may-attend is symmetric
    assert all(m[i].diagonal().all() for i in range(4))  # self-attention kept
    # bottom-right window mixes 4 regions → exactly 4 distinct row patterns
    assert len({r.tobytes() for r in m[3]}) == 4


def test_swin_geometry_pyramid():
    h0, w0, c0, n0 = modeling.swin_geometry(SWIN_CFG, 0)
    h1, w1, c1, n1 = modeling.swin_geometry(SWIN_CFG, 1)
    assert (h0, w0, c0, n0) == (8, 8, 16, 2)
    assert (h1, w1, c1, n1) == (4, 4, 32, 4)
    # stage-1 layers see the merged (quartered, doubled-width) map
    p = modeling.init_model_params(jax.random.key(0), SWIN_CFG)
    assert p["layers"][2]["attn"]["wqkv"].shape == (32, 3, 32)  # blocked q|k|v at C=32
    assert p["merges"][0]["w"].shape == (64, 32)


def test_vision_dataloader_contract():
    from galvatron_tpu.core.dataloader import build_dataloader

    it = build_dataloader(VIT_CFG, 8, seed=3)
    b = next(it)
    assert b.shape == (8, VIT_CFG.sample_len + 1) and b.dtype == np.int32
    assert b[:, :-1].min() >= 0 and b[:, :-1].max() <= 255
    assert (b[:, -1] < VIT_CFG.num_classes).all() and (b[:, -1] >= 0).all()
    # deterministic stream (resume contract)
    b2 = next(build_dataloader(VIT_CFG, 8, seed=3))
    np.testing.assert_array_equal(b, b2)


def test_analytic_costs_vision():
    """Analytic (unprofiled) cost model covers the vision families: ViT one
    uniform layer type; Swin one type per layer with the stage pyramid's
    shrinking seq / widening hidden reflected in the costs."""
    from galvatron_tpu.search.theoretical import analytic_model_costs, total_param_count

    vit = analytic_model_costs(modeling.PRESETS["vit-base"], mixed_precision="bf16")
    assert set(vit.layer_types) == {0}
    assert vit.layer_types[0].fwd_ms_per_sample > 0
    assert 1 in vit.layer_types[0].activation_mb_per_sample

    swin_cfg = modeling.PRESETS["swin-base"]
    swin = analytic_model_costs(swin_cfg, mixed_precision="bf16")
    assert set(swin.layer_types) == set(range(swin_cfg.num_layers))
    # deeper stages: fewer tokens but wider layers → more params per layer
    assert (
        swin.layer_types[23].parameter_mb > swin.layer_types[0].parameter_mb
    )
    assert (
        swin.layer_types[0].boundary_activation_mb_per_sample
        > swin.layer_types[23].boundary_activation_mb_per_sample
    )
    # param totals match the real init (exactness contract of theoretical.py)
    p = jax.eval_shape(lambda k: modeling.init_model_params(k, swin_cfg), jax.random.key(0))
    n_real = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(p))
    assert total_param_count(swin_cfg) == n_real


def test_search_engine_swin_multi_layer_type():
    """The DP search runs per-layer over Swin's heterogeneous layer types and
    returns a feasible pp=1 strategy."""
    from galvatron_tpu.search.cost_model import ProfiledHardware
    from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace
    from galvatron_tpu.search.theoretical import analytic_model_costs

    cfg = SWIN_CFG
    costs = analytic_model_costs(cfg, mixed_precision="bf16")
    # default pp sweep: the engine must gate heterogeneous layer types to
    # pp=1 itself (the runtime rejects Swin at pp>1 — a pp>1 "win" here would
    # break the search→train workflow)
    eng = SearchEngine(
        costs, ProfiledHardware(), num_layers=cfg.num_layers,
        space=SearchSpace(world_size=8, max_tp=2),
        memory_budget_mb=4096.0,
    )
    res = eng.search([8], max_chunks=1)
    assert res is not None and res.config.pp == 1
    assert len(res.config.layer_strategies) == cfg.num_layers


def test_vit_preset_shapes():
    cfg = modeling.PRESETS["vit-base"]
    assert cfg.n_patches == 196 and cfg.sample_len == 224 * 224 * 3
    p = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    assert p["embed"]["proj"].shape == (16 * 16 * 3, 768)
    assert p["head"]["w"].shape == (768, 1000)
    swin = modeling.PRESETS["swin-base"]
    assert swin.num_layers == sum(swin.swin_depths)
    ps = jax.eval_shape(lambda k: modeling.init_model_params(k, swin), jax.random.key(0))
    assert ps["head"]["w"].shape == (128 * 8, 1000)  # C·2^3 after 3 merges


def test_swin_search_emits_pp2_and_runtime_trains():
    """The multi-type search emits a pp=2 config for a Swin pyramid
    (section_pipeline=True routes even 2-group profiles to the K-section
    pair-stacked engine) and the config builds + trains."""
    from galvatron_tpu.search.cost_model import (
        ProfiledHardware,
        ProfiledLayerType,
        ProfiledModelCosts,
    )
    from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace

    lt0 = ProfiledLayerType(
        fwd_ms_per_sample=1.0, parameter_mb=10.0,
        activation_mb_per_sample={1: 8.0, 2: 4.0}, boundary_activation_mb_per_sample=1.0,
    )
    lt1 = ProfiledLayerType(
        fwd_ms_per_sample=1.5, parameter_mb=30.0,
        activation_mb_per_sample={1: 6.0, 2: 3.0}, boundary_activation_mb_per_sample=0.5,
    )
    costs = ProfiledModelCosts(
        layer_types={0: lt0, 1: lt0, 2: lt1, 3: lt1},
        other_param_mb=5.0, other_act_mb_per_sample=1.0,
        other_fwd_ms_per_sample=0.1,
    )
    hw = ProfiledHardware(
        allreduce_bw={"2_1": 150.0, "2_0": 30.0, "4_1": 140.0, "8_1": 120.0},
        p2p_bw={2: 50.0}, overlap_coe=1.1,
    )
    eng = SearchEngine(
        costs, hw, num_layers=4,
        space=SearchSpace(world_size=8, pp_choices=[2], max_tp=2),
        memory_budget_mb=600.0, section_pipeline=True,
    )
    res = eng.search([8])
    assert res is not None and res.config.pp == 2
    ls = res.config.layer_strategies
    assert len(ls) == 4
    # pair layout: layers 0/1 (stage-0 pair) and 2/3 share strategies
    assert ls[0] == ls[1] and ls[2] == ls[3]
    rt = build_runtime(SWIN_CFG, res.config, adam=ADAM, global_batch_size=8)
    state = rt.init_state(jax.random.key(0))
    b = make_batches(SWIN_CFG, seed=5, n=1)[0]
    state, loss = rt.train_step(state, b)
    assert np.isfinite(float(loss))


@pytest.mark.slow  # the enc-dec any-chunks test is the default-suite guard
def test_swin_any_chunks_parity(swin_ref):
    """chunks % pp lifted for the K-section engine too (same per-chunk ring
    alignment argument as enc-dec): trajectory parity at chunks=3, pp=2."""
    batches = make_batches(SWIN_CFG, n=2, batch=24)
    ref = reference_losses(SWIN_CFG, batches)
    for ptype in ("gpipe", "pipedream_flush"):
        hp = HybridParallelConfig.uniform(
            4, pp=2, chunks=3, mixed_precision="fp32", pipeline_type=ptype
        )
        rt = build_runtime(SWIN_CFG, hp, adam=ADAM, global_batch_size=24)
        st = rt.init_state_from(modeling.init_model_params(jax.random.key(0), SWIN_CFG))
        losses = []
        for b in batches:
            st, loss = rt.train_step(st, b)
            losses.append(float(loss))
        np.testing.assert_allclose(losses, ref, rtol=2e-4, atol=2e-4, err_msg=ptype)
