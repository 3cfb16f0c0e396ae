"""Switch-MoE + expert parallelism (reference: SwitchMLP,
galvatron/core/tensor_parallel/transformer.py:161-295; EP groups
site_package/megatron/core/parallel_state.py:450-478)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models import moe
from galvatron_tpu.models.modeling import ModelConfig


def small_moe_cfg(**kw):
    return ModelConfig(
        vocab_size=64,
        hidden_size=32,
        num_layers=2,
        num_heads=4,
        ffn_dim=64,
        max_seq_len=16,
        dtype=jnp.float32,
        moe_experts=4,
        **kw,
    )


def test_sinkhorn_balances():
    # heavily skewed logits: sinkhorn should spread assignment across experts
    key = jax.random.key(0)
    logits = jax.random.normal(key, (64, 4)) * 0.1
    logits = logits.at[:, 0].add(5.0)  # everyone prefers expert 0
    scores = moe.sinkhorn(logits, n_iters=20)
    assign = jnp.argmax(scores, axis=-1)
    counts = np.bincount(np.asarray(assign), minlength=4)
    # raw argmax would put all 64 on expert 0; sinkhorn must not
    assert counts[0] < 64
    assert (counts > 0).sum() >= 2


def test_route_top1_capacity():
    T, E, C = 16, 2, 8
    logits = jnp.zeros((T, E))
    dispatch, combine = moe.route_top1(logits, C)
    assert dispatch.shape == (T, E, C)
    # each token dispatched at most once, each expert slot used at most once
    assert float(dispatch.sum(axis=(1, 2)).max()) <= 1.0
    assert float(dispatch.sum(axis=0).max()) <= 1.0
    # combine is gate-scaled dispatch: zero exactly where dispatch is zero
    assert np.all((np.asarray(combine) > 0) <= (np.asarray(dispatch) > 0))


def test_moe_block_shapes_and_grads():
    cfg = small_moe_cfg()
    key = jax.random.key(1)
    p = moe.init_moe_params(key, cfg)
    x = jax.random.normal(jax.random.key(2), (2, 8, cfg.hidden_size), jnp.float32)

    def loss(p, x):
        return jnp.sum(moe.moe_block(x, p, cfg) ** 2)

    val, grads = jax.value_and_grad(loss)(p, x)
    assert np.isfinite(float(val))
    # router must receive gradient (through the gate), experts through dispatch
    assert float(jnp.abs(grads["router"]["w"]).sum()) > 0
    assert float(jnp.abs(grads["w1"]).sum()) > 0


def test_moe_full_capacity_routes_all_tokens():
    cfg = small_moe_cfg(moe_capacity_factor=8.0)  # no drops possible
    T, E = 32, cfg.moe_experts
    logits = jax.random.normal(jax.random.key(3), (T, E))
    C = moe.moe_capacity(T, E, cfg.moe_capacity_factor)
    dispatch, _ = moe.route_top1(logits, C)
    assert float(dispatch.sum()) == T  # every token kept


def test_moe_model_forward():
    cfg = small_moe_cfg()
    from galvatron_tpu.models import modeling

    params = modeling.init_model_params(jax.random.key(0), cfg)
    assert "router" in params["layers"][0]["mlp"]
    tokens = jnp.zeros((2, 8), jnp.int32)
    from tests._stack_harness import forward

    logits = forward(params, tokens, cfg)
    assert logits.shape == (2, 8, cfg.vocab_size)
    annots = modeling.model_annotations(cfg)
    assert annots["layers"][0]["mlp"]["w1"] == ("ep", "fsdp", "tp")


def test_ep_searchable_dimension():
    """EP is a searched dimension for MoE models (the reference carries
    SwitchMLP but never searches EP — SURVEY §2.3): the strategy space emits
    ep variants, the cost model rewards expert sharding, and the searched
    config trains through the hybrid runtime."""
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.parallel.hybrid import build_runtime
    from galvatron_tpu.search.cost_model import (
        ProfiledHardware,
        layer_memory_cost,
        layer_time_cost,
    )
    from galvatron_tpu.search.search_engine import (
        SearchEngine,
        SearchSpace,
        generate_layer_strategies,
    )
    from galvatron_tpu.search.theoretical import analytic_model_costs

    cfg = small_moe_cfg()
    space = SearchSpace(
        world_size=8, max_tp=2, allow_ep=True, moe_experts=cfg.moe_experts,
        pp_choices=[1],
    )
    cands = generate_layer_strategies(space, pp=1)
    eps = {s.ep for s in cands}
    assert {1, 2, 4}.issubset(eps)
    # ep must divide the expert count — ep=8 over 4 experts would silently
    # replicate in the runtime, so the search must never propose it
    assert 8 not in eps
    assert all(not (s.cp > 1 and s.ep > 1) for s in cands)
    # dense model (moe_experts=0): no ep candidates even with allow_ep
    dense = generate_layer_strategies(
        SearchSpace(world_size=8, max_tp=2, allow_ep=True, pp_choices=[1]), pp=1
    )
    assert {s.ep for s in dense} == {1}

    costs = analytic_model_costs(cfg, mixed_precision="bf16")
    lt = costs.layer_types[0]
    assert 0.5 < lt.moe_expert_param_fraction < 1.0
    assert lt.moe_a2a_mb_per_sample > 0
    # expert sharding must cut model-state memory and compute time
    m1 = layer_memory_cost(lt, LayerStrategy(tp=1), 8, 1, 8)
    m4 = layer_memory_cost(lt, LayerStrategy(tp=1, ep=4), 8, 1, 8)
    assert m4.states_mb < m1.states_mb
    hw = ProfiledHardware(allreduce_bw={"4_1": 1000.0, "8_1": 1000.0}, overlap_coe=1.0)
    t1 = layer_time_cost(lt, LayerStrategy(tp=1), hw, 8, 1, 8)
    t4 = layer_time_cost(lt, LayerStrategy(tp=1, ep=4), hw, 8, 1, 8)
    assert t4 < t1  # fast interconnect: expert-compute split dominates a2a

    eng = SearchEngine(
        costs, hw, num_layers=cfg.num_layers, space=space, memory_budget_mb=4096.0
    )
    res = eng.search([8], max_chunks=1)
    assert res is not None
    rt = build_runtime(
        cfg, res.config, adam=AdamConfig(lr=1e-3), global_batch_size=8, seq_len=16
    )
    state = rt.init_state(jax.random.key(0))
    batch = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (8, 17)), jnp.int32
    )
    state, loss = rt.train_step(state, batch)
    assert np.isfinite(float(loss))


def test_moe_profile_roundtrip_keeps_ep_fields(tmp_path):
    """The profiled-JSON path (the CLI default) must carry the MoE fields —
    otherwise --enable_ep silently costs every ep identically."""
    from galvatron_tpu.search.theoretical import analytic_model_costs
    from galvatron_tpu.utils.config_utils import load_profiled_model, save_profiled_model

    costs = analytic_model_costs(small_moe_cfg(), mixed_precision="bf16")
    tp, mp = str(tmp_path / "time.json"), str(tmp_path / "mem.json")
    save_profiled_model(costs, time_path=tp, mem_path=mp)
    loaded = load_profiled_model(tp, mp)
    lt0, lt1 = costs.layer_types[0], loaded.layer_types[0]
    assert lt1.moe_expert_param_fraction == pytest.approx(lt0.moe_expert_param_fraction)
    assert lt1.moe_a2a_mb_per_sample == pytest.approx(lt0.moe_a2a_mb_per_sample)


def test_moe_expert_parallel_train_step():
    """One hybrid train step with experts sharded over EP axes on the 8-dev
    CPU mesh: tp=2 × ep=2 (× dp=2 left over)."""
    from galvatron_tpu.parallel.hybrid import build_runtime
    from galvatron_tpu.parallel.mesh import build_mesh
    from galvatron_tpu.core.optim import AdamConfig

    cfg = small_moe_cfg()
    hp = HybridParallelConfig(
        pp=1,
        layer_strategies=[
            LayerStrategy(tp=2, dp_type="zero3", ep=2),
            LayerStrategy(tp=2, dp_type="zero3", ep=2),
        ],
        vocab_tp=2,
        mixed_precision="fp32",
    )
    mesh, axes = build_mesh(pp=1)
    rt = build_runtime(
        cfg, hp, mesh=mesh, axes=axes, adam=AdamConfig(lr=1e-3),
        global_batch_size=8, seq_len=16,
    )
    state = rt.init_state(jax.random.key(0))
    # expert dim must actually be sharded over the ep axes
    w1_spec = rt.state_shardings["params"]["layers"][0]["mlp"]["w1"].spec
    ep_entry = w1_spec[0] if isinstance(w1_spec[0], tuple) else (w1_spec[0],)
    assert ep_entry and all(a in axes.data_axes for a in ep_entry)
    batch = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (8, 17)), jnp.int32
    )
    state, loss = rt.train_step(state, batch)
    assert np.isfinite(float(loss))
    state, loss2 = rt.train_step(state, batch)
    assert float(loss2) < float(loss)  # training reduces loss on a repeated batch


def test_moe_profiled_costs_search():
    """Profiled (not analytic) MoE costs feed the search sanely: the expert
    param fraction is a true fraction and searched memory stays positive —
    regression for the dense-count bug that drove dense_mb negative."""
    from galvatron_tpu.profiling.model import layer_param_count, profile_model
    from galvatron_tpu.search.cost_model import ProfiledHardware, layer_memory_cost
    from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace

    cfg = small_moe_cfg()
    # the unified count includes the expert stack (router + E swiglu MLPs)
    dense = layer_param_count(cfg.replace(moe_experts=0))
    assert layer_param_count(cfg) > dense
    costs = profile_model(cfg, bsz=8, measure_time=False)
    lt = costs.layer_types[0]
    assert 0.0 < lt.moe_expert_param_fraction < 1.0
    mc = layer_memory_cost(
        lt, LayerStrategy(tp=1, dp_type="ddp", ep=2), world=8, pp=1,
        global_bsz=8, chunks=1, mixed_precision="bf16",
    )
    assert mc.states_mb > 0 and mc.total_mb > 0
    eng = SearchEngine(
        costs, ProfiledHardware(), num_layers=2,
        space=SearchSpace(world_size=8, allow_ep=True, moe_experts=4, max_tp=2),
        memory_budget_mb=20000.0,
    )
    r = eng.search([8])
    assert r is not None and r.memory_mb > 0


def test_moe_sp_with_ep_trains():
    """sp=True + ep>1 is a legal searched combination: the token-dim pin must
    include the SP sequence axes (regression: pin_tok once used the batch
    axes only, forcing a seq all-gather over the tp group before routing)."""
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.parallel.hybrid import build_runtime

    cfg = small_moe_cfg()
    hp = HybridParallelConfig(
        pp=1,
        layer_strategies=[LayerStrategy(tp=2, sp=True, dp_type="zero3", ep=2)] * 2,
        vocab_tp=2,
        mixed_precision="fp32",
    )
    rt = build_runtime(cfg, hp, adam=AdamConfig(lr=3e-3), global_batch_size=8, seq_len=16)
    state = rt.init_state(jax.random.key(0))
    rng = np.random.RandomState(0)
    batch = jnp.asarray(rng.randint(0, 64, (8, 17)), jnp.int32)
    losses = []
    for _ in range(3):
        state, loss = rt.train_step(state, batch)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_moe_pipeline_parallel_parity():
    """MoE composes with pipeline parallelism: tp=2 x ep=2 x pp=2 (all 8 sim
    devices) reproduces the flat single-device loss EXACTLY at chunks=1, and
    trains at chunks=2. (chunks>1 eval is deliberately not pinned to the
    full-batch loss: sinkhorn routing normalizes per micro-batch — see the
    models/moe.py docstring.)"""
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.parallel.hybrid import build_runtime

    from galvatron_tpu.models import modeling

    cfg = small_moe_cfg().replace(num_layers=4)
    flat = modeling.init_model_params(jax.random.key(0), cfg)
    b = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (8, 17)), jnp.int32
    )
    ref = float(jax.jit(lambda p, bb: modeling.lm_loss(p, bb, cfg))(flat, b))
    hp1 = HybridParallelConfig(
        pp=2, chunks=1,
        layer_strategies=[LayerStrategy(tp=2, ep=2)] * 4,
        vocab_tp=2, mixed_precision="fp32",
    )
    rt = build_runtime(cfg, hp1, adam=AdamConfig(lr=1e-3), global_batch_size=8, seq_len=16)
    st = rt.init_state_from(flat)
    np.testing.assert_allclose(
        float(rt.eval_loss(st, rt.shard_batch(b))), ref, rtol=3e-5, atol=3e-5
    )
    hp2 = HybridParallelConfig(
        pp=2, chunks=2,
        layer_strategies=[LayerStrategy(tp=2, ep=2)] * 4,
        vocab_tp=2, mixed_precision="fp32",
    )
    rt2 = build_runtime(cfg, hp2, adam=AdamConfig(lr=3e-3), global_batch_size=8, seq_len=16)
    st2 = rt2.init_state_from(flat)
    losses = []
    for _ in range(3):
        st2, loss = rt2.train_step(st2, rt2.shard_batch(b))
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_measured_expert_time_fraction_prices_ep():
    """EP compute scaling uses the MEASURED expert-time fraction when the
    profile carries one (on-chip 2026-07-31: 0.46 vs the 0.94 param
    fraction — routing/sinkhorn/dispatch do NOT shard by ep, so the param
    proxy overstated the ep win ~2x; BASELINE.md round 5). Fallback stays
    the param fraction."""
    from galvatron_tpu.core.strategy import LayerStrategy
    from galvatron_tpu.search.cost_model import (
        ProfiledHardware,
        ProfiledLayerType,
        layer_time_cost,
    )

    hw = ProfiledHardware(allreduce_bw={"2_1": 1e9, "4_1": 1e9, "8_1": 1e9})
    mk = lambda tf: ProfiledLayerType(
        fwd_ms_per_sample=4.26, parameter_mb=100.0,
        activation_mb_per_sample={1: 10.0},
        boundary_activation_mb_per_sample=0.0,
        moe_expert_param_fraction=0.943,
        moe_expert_time_fraction=tf,
    )
    t = lambda lt, ep: layer_time_cost(
        lt, LayerStrategy(tp=1, ep=ep), hw, 8, 1, 8
    )
    # measured fraction: ep=8 shards only 46% of the time
    sp_meas = t(mk(0.46), 1) / t(mk(0.46), 8)
    sp_proxy = t(mk(None), 1) / t(mk(None), 8)
    assert sp_meas < sp_proxy  # the proxy overstated the ep win
    expect = 1.0 / (1 - 0.46 + 0.46 / 8)
    assert sp_meas == pytest.approx(expect, rel=1e-6)


@pytest.mark.slow
def test_ep_memory_scaling_on_topology():
    """EP memory model vs the TPU compiler: sharding experts over ep=2 must
    drop per-device state by ~the expert fraction the model predicts
    (expert params / (tp*ep), ZeRO over the remaining dp extent)."""
    import jax.numpy as jnp

    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
    from galvatron_tpu.models.modeling import ModelConfig
    from galvatron_tpu.search.memory_fidelity import measured_train_mb
    from galvatron_tpu.search.theoretical import analytic_model_costs
    from galvatron_tpu.search.cost_model import layer_memory_cost

    cfg = ModelConfig(
        vocab_size=512, hidden_size=256, num_layers=2, num_heads=4,
        max_seq_len=512, dtype=jnp.bfloat16, attn_impl="flash", moe_experts=8,
    )
    costs = analytic_model_costs(cfg)
    lt = costs.layer_types[0]
    meas, pred = {}, {}
    for ep in (1, 2):
        hp = HybridParallelConfig(
            layer_strategies=[LayerStrategy(tp=1, dp_type="ddp", ep=ep)] * 2,
            vocab_tp=1, mixed_precision="bf16",
        )
        m = measured_train_mb(cfg, hp, 16)
        if m is None:
            pytest.skip("TPU topology AOT unavailable")
        meas[ep] = m["state_mb"]
        pred[ep] = 2 * layer_memory_cost(
            lt, LayerStrategy(tp=1, ep=ep), 8, 1, 16, chunks=1
        ).states_mb
    # predicted and compiled state savings from ep=2 agree within 25%
    assert meas[2] < meas[1]
    saved_meas = meas[1] - meas[2]
    saved_pred = pred[1] - pred[2]
    assert saved_pred == pytest.approx(saved_meas, rel=0.25), (pred, meas)


# -- the row tile of the expert-sorted layout follows the rows an expert holds (PR 57) ----
# `ops/grouped_matmul.row_tile` from static shapes, and `moe.held_experts` (kernels of
# ops/moe_held.py and the grouped GEMMs, interpreted here) at the tiles it can name.

@pytest.mark.parametrize("tokens,top_k,scored,dtype,tile", [
    (16384, 10, 512, jnp.bfloat16, 256),  # qwen3-next-80b-a3b_s4096: 320 rows an expert
    (16384, 8, 64, jnp.bfloat16, 256),  # olmoe-1b-7b_s4096: 2,048
    (32, 8, 128, jnp.bfloat16, 16),  # sarvam-105b, a decode step: 2 -> bf16's floor
    (1024, 8, 128, jnp.bfloat16, 64),  # its prompt chunk: 64
    (32, 6, 64, jnp.bfloat16, 16),  # smallthinker-21b-a3b, a decode step: 3
    (1024, 6, 64, jnp.bfloat16, 64),  # its prompt chunk: 96 -> the power of two it still fills
    (32, 8, 128, jnp.float32, 8),  # float32 packs 8 rows a sublane tile
    (1, 1, 64, jnp.float16, 16),  # any 16-bit type packs 16
    (1024, 8, 128, jnp.float32, 64),
    (4096, 8, 64, jnp.float32, 256),  # never past TILE_M
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_row_tile_follows_the_rows_an_expert_holds(tokens, top_k, scored, dtype, tile):
    from galvatron_tpu.ops.grouped_matmul import TILE_M, row_tile

    assert TILE_M == 256
    assert row_tile(tokens, top_k, scored, dtype) == tile
    cfg = small_moe_cfg(moe_router="softmax_topk", moe_top_k=top_k).replace(
        moe_experts=scored, dtype=dtype)
    assert moe.layer_row_tile(cfg, tokens) == tile


TILE_T, TILE_K, TILE_E, TILE_HELD, TILE_FIRST, TILE_F = 160, 4, 16, 4, 4, 128
#: float32: the same sums in another order; bf16: an ulp of the largest element between
#: two tiles (the same rounding points), a few between the kernels' float32 gate x up and
#: the plain body's bf16 one
TILE_TOL = {jnp.float32: (2e-6, 2e-6), jnp.bfloat16: (2 ** -7, 3e-2)}


def _tile_choices(load):
    """(T, k) choices over 16 scored experts of which 4 .. 7 are held: the first held
    expert draws 150 pairs (more than a tile's rows at every tile under 256), the second
    none, the other two a few."""
    ks = jax.random.split(jax.random.key(7), 3)
    outside = jax.random.randint(ks[0], (TILE_T, TILE_K), TILE_FIRST + TILE_HELD, TILE_E)
    if load == "none_held":
        return outside
    if load == "decode":
        # a decode step's few rows: two tokens name the third held expert, one the first,
        # the other two held experts get nothing (most of the share is empty)
        idx = outside.at[3, 1].set(TILE_FIRST + 2).at[90, 0].set(TILE_FIRST + 2)
        return idx.at[17, 2].set(TILE_FIRST)
    t = jnp.arange(TILE_T)
    idx = outside.at[:, 0].set(jnp.where(t < 150, TILE_FIRST, outside[:, 0]))
    idx = idx.at[:, 1].set(jnp.where(jax.random.uniform(ks[1], (TILE_T,)) < 0.2,
                                     TILE_FIRST + 2, outside[:, 1]))
    return idx.at[:, 2].set(jnp.where(jax.random.uniform(ks[2], (TILE_T,)) < 0.1,
                                      TILE_FIRST + 3, outside[:, 2]))


def _tile_operands(dtype):
    ks = jax.random.split(jax.random.key(11), 6)
    hidden = 256 if dtype == jnp.bfloat16 else 128  # one slab chunk of the dtype
    x = jax.random.normal(ks[0], (TILE_T, hidden), dtype)
    weights = jax.nn.softmax(jax.random.normal(ks[1], (TILE_T, TILE_K)), axis=-1)
    w1, w3 = (jax.random.normal(k, (TILE_HELD, hidden, TILE_F), dtype) * hidden ** -0.5
              for k in ks[2:4])
    w2 = jax.random.normal(ks[4], (TILE_HELD, TILE_F, hidden), dtype) * TILE_F ** -0.5
    cot = jax.random.normal(ks[5], (TILE_T, hidden), jnp.float32)
    return (x, weights, w1, w3, w2), cot


def _tile_bounded(x, weights, w1, w3, w2, idx, *, tile, act, joined, forward_only=False):
    """``forward_only``: what `moe._topk_local` runs for a cached forward: the layout
    without empty tiles under the forward body itself (no VJP)."""
    lay = moe.held_layout(idx, TILE_HELD, tile, TILE_FIRST, empty_tiles=not forward_only)
    w13 = jnp.concatenate([w1, w3], axis=-1) if joined else (w1, w3)
    run = moe.held_forward if forward_only else moe.held_experts
    return run(x, weights, w13, w2, lay.pair_row, lay.row_pair, lay.row_valid,
               lay.tile_group, lay.num_tiles, tile, act)


def _tile_plain(x, weights, w1, w3, w2, idx, *, tile, act):
    lay = moe.held_layout(idx, TILE_HELD, tile, TILE_FIRST)
    rows = moe._dispatch(x, lay.row_pair // TILE_K, lay.row_valid, lay.pair_row)
    gate = {"silu": jax.nn.silu, "relu": jax.nn.relu}[act](moe.grouped_gemm(rows, w1, lay, tile))
    out = moe.grouped_gemm(gate * moe.grouped_gemm(rows, w3, lay, tile), w2, lay, tile)
    return moe._combine(out, weights, lay.pair_row, lay.row_pair, lay.row_valid)


@functools.lru_cache(maxsize=None)
def _tile_want(dtype, act, joined):
    """The tile-256 forward and the plain body's, once a combination."""
    operands, _ = _tile_operands(dtype)
    idx = _tile_choices("skewed")
    return (_tile_bounded(*operands, idx, tile=256, act=act, joined=joined),
            _tile_plain(*operands, idx, tile=256, act=act))


def _within(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-30)


@pytest.mark.parametrize("joined", [True, False], ids=["w13", "w1_w3"])
@pytest.mark.parametrize("act", ["silu", "relu"], ids=["swiglu", "reglu"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("tile", [16, 32, 64, 128])
def test_held_experts_at_a_small_tile_is_the_tile_256_forward(tile, dtype, act, joined):
    idx = _tile_choices("skewed")
    lay = moe.held_layout(idx, TILE_HELD, tile, TILE_FIRST)
    sizes = list(np.asarray(lay.sizes))
    assert sizes[0] == 150 > tile and sizes[1] == 0 and min(sizes[2:]) > 0
    # the first expert's rows span consecutive tiles of its own, the empty one owns a tile
    groups = list(np.asarray(lay.tile_group)[:int(lay.num_tiles[0])])
    assert groups.count(0) == -(-150 // tile) and groups.count(1) == 1 and groups == sorted(groups)
    assert lay.row_valid.shape[0] == moe.buffer_rows(TILE_T * TILE_K, TILE_HELD + 1, tile)
    operands, _ = _tile_operands(dtype)
    got = _tile_bounded(*operands, idx, tile=tile, act=act, joined=joined)
    at_256, plain = _tile_want(dtype, act, joined)
    same, other = TILE_TOL[dtype]
    _within(got, at_256, same)
    _within(got, plain, other)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("tile", [16, 32, 64, 128])
def test_held_experts_at_a_small_tile_with_no_pair_held_is_exact_zeros(tile, dtype):
    idx = _tile_choices("none_held")
    lay = moe.held_layout(idx, TILE_HELD, tile, TILE_FIRST)
    assert int(lay.row_valid.sum()) == 0 and int(lay.num_tiles[0]) == TILE_HELD
    operands, _ = _tile_operands(dtype)
    got = _tile_bounded(*operands, idx, tile=tile, act="silu", joined=False)
    assert float(jnp.abs(got.astype(jnp.float32)).max()) == 0.0


@pytest.mark.parametrize("dtype,tile,act,joined", [
    (jnp.float32, 8, "relu", False), (jnp.bfloat16, 16, "silu", True),
    (jnp.bfloat16, 16, "relu", False)], ids=["float32_8_reglu_pair", "bf16_16_swiglu_w13",
                                             "bf16_16_reglu_pair"])
def test_held_experts_backward_at_the_dtypes_floor_tile(dtype, tile, act, joined):
    """Training at tiny shapes takes the floor tile: the output and every gradient (x,
    the combine weights, w1, w3, w2) against the plain body's at the same tile."""
    idx = _tile_choices("skewed")
    operands, cot = _tile_operands(dtype)

    def run(body):
        y, vjp = jax.vjp(lambda *t: body(*t, idx), *operands)
        return (y,) + vjp(cot.astype(y.dtype))

    got = run(functools.partial(_tile_bounded, tile=tile, act=act, joined=joined))
    want = run(functools.partial(_tile_plain, tile=tile, act=act))
    for name, g, w in zip(("y", "dx", "dweights", "dw1", "dw3", "dw2"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        _within(g, w, TILE_TOL[dtype][1])


# -- a forward-only held share gives tiles to the experts that got a row (PR 62) ----------
# `moe.held_layout(..., empty_tiles=False)`: what `generation._mlp_at` asks through
# `moe_topk_block(forward_only=True)`; the default layout is the cases above, untouched.

@pytest.mark.parametrize("load", ["skewed", "decode", "none_held"])
@pytest.mark.parametrize("tile", [16, 32, 64, 128])
def test_forward_only_layout_names_the_experts_that_got_a_pair(tile, load):
    idx = _tile_choices(load)
    lay = moe.held_layout(idx, TILE_HELD, tile, TILE_FIRST, empty_tiles=False)
    default = moe.held_layout(idx, TILE_HELD, tile, TILE_FIRST)
    sizes = np.asarray(lay.sizes)
    assert (sizes == np.asarray(default.sizes)).all()
    assert {"skewed": (sizes > 0).sum() == 3, "decode": list(sizes) == [1, 0, 2, 0],
            "none_held": not sizes.any()}[load]
    count = int(lay.num_tiles[0])
    assert count == sum(-(-int(n) // tile) for n in sizes)
    assert int(default.num_tiles[0]) == count + int((sizes == 0).sum())
    groups = list(np.asarray(lay.tile_group)[:count])
    assert groups == sorted(groups)
    assert [groups.count(g) for g in range(TILE_HELD)] == [-(-int(n) // tile) for n in sizes]
    # the buffer's rows are the default's (static, worst case); every held pair has a row
    # of its own expert's tiles, before ``num_tiles * tile``, and no other pair has
    assert lay.row_valid.shape == default.row_valid.shape
    assert lay.row_valid.shape[0] == moe.buffer_rows(TILE_T * TILE_K, TILE_HELD + 1, tile)
    assert int(lay.row_valid.sum()) == int(sizes.sum())
    local = np.asarray(idx).reshape(-1) - TILE_FIRST
    held = (local >= 0) & (local < TILE_HELD)
    pair_row = np.asarray(lay.pair_row)
    assert ((pair_row < count * tile) == held).all()
    assert (np.asarray(lay.tile_group)[pair_row[held] // tile] == local[held]).all()
    assert (np.asarray(lay.row_pair)[pair_row[held]] == np.flatnonzero(held)).all()
    assert len(set(pair_row[held])) == int(held.sum())


def test_sorted_layout_without_empty_tiles_keeps_the_groups_order():
    """`moe.sorted_layout` itself (every expert held): experts 1 and 3 of 5 without a pair,
    leading, inner and trailing groups of none."""
    idx = jnp.asarray([[2, 4], [2, 0], [4, 2], [2, 2]], jnp.int32)
    lay = moe.sorted_layout(idx, 5, 2, empty_tiles=False)
    assert list(np.asarray(lay.sizes)) == [1, 0, 5, 0, 2] and int(lay.num_tiles[0]) == 5
    assert list(np.asarray(lay.tile_group)[:5]) == [0, 2, 2, 2, 4]
    assert list(np.asarray(lay.row_valid)[:10]) == [True, False] + [True] * 5 + [False, True, True]
    assert not np.asarray(lay.row_valid)[10:].any()
    assert list(np.asarray(moe.sorted_layout(idx, 5, 2).tile_group)[:7]) == [0, 1, 2, 2, 2, 3, 4]
    lone = moe.sorted_layout(jnp.asarray([[0, 0]], jnp.int32), 5, 2, empty_tiles=False)
    assert int(lone.num_tiles[0]) == 1 and list(np.asarray(lone.pair_row)) == [0, 1]


@pytest.mark.parametrize("joined", [True, False], ids=["w13", "w1_w3"])
@pytest.mark.parametrize("act", ["silu", "relu"], ids=["swiglu", "reglu"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("tile", [16, 32])
@pytest.mark.parametrize("load", ["skewed", "decode"])
def test_forward_only_held_share_is_the_default_layouts_forward_exactly(load, tile, dtype, act,
                                                                        joined):
    """The same pairs by the same weights in the same order, only the rows' places in the
    buffer move: not a bit of the output does."""
    idx = _tile_choices(load)
    operands, _ = _tile_operands(dtype)
    want = _tile_bounded(*operands, idx, tile=tile, act=act, joined=joined)
    got = _tile_bounded(*operands, idx, tile=tile, act=act, joined=joined, forward_only=True)
    assert got.dtype == want.dtype and np.isfinite(np.asarray(got, np.float32)).all()
    assert float(jnp.abs(want.astype(jnp.float32)).max()) > 0.0
    assert np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("tile", [16, 32, 64, 128])
def test_forward_only_held_share_with_no_pair_held_runs_no_tile(tile, dtype):
    """``num_tiles`` 0: every grid step is skipped and names tile 0 (`used_tile`'s clamp;
    ``min(i, count - 1)`` alone names the block before the array); exact zeros come out."""
    from galvatron_tpu.ops.grouped_matmul import used_tile

    idx = _tile_choices("none_held")
    lay = moe.held_layout(idx, TILE_HELD, tile, TILE_FIRST, empty_tiles=False)
    assert int(lay.num_tiles[0]) == 0 and int(lay.row_valid.sum()) == 0
    assert [int(used_tile(i, lay.num_tiles)) for i in (0, 1, 7)] == [0, 0, 0]
    assert [int(used_tile(i, jnp.asarray([3]))) for i in (0, 2, 3, 7)] == [0, 2, 2, 2]
    operands, _ = _tile_operands(dtype)
    got = jax.jit(functools.partial(_tile_bounded, tile=tile, act="silu", joined=False,
                                    forward_only=True))(*operands, idx)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all() and not got.any()


def test_differentiating_a_forward_only_held_share_raises():
    """No VJP on the path (`moe.held_forward` and not `moe.held_experts`): a gradient is
    refused and never read out of weight blocks that no tile wrote."""
    cfg = ModelConfig(vocab_size=64, hidden_size=128, num_layers=1, num_heads=4, ffn_dim=128,
                      moe_ffn_dim=128, max_seq_len=16, dtype=jnp.float32, act_fn="swiglu",
                      moe_experts=8, moe_router="softmax_topk", moe_top_k=2, moe_share=(1, 2))
    assert moe.held_path_counts(cfg)["bounded"] == 1
    p = moe.init_moe_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 8, 128), jnp.float32)

    def loss(p_, forward_only):
        return jnp.sum(moe.moe_topk_block(x, p_, cfg, forward_only=forward_only)[0] ** 2)

    y = moe.moe_topk_block(x, p, cfg, forward_only=True)[0]
    assert np.array_equal(np.asarray(y), np.asarray(moe.moe_topk_block(x, p, cfg)[0]))
    grads = jax.grad(loss)(p, False)  # the default layout differentiates as ever
    assert float(jnp.abs(grads["w2"]).max()) > 0.0
    with pytest.raises(NotImplementedError):  # (Pallas has no JVP of a scalar-prefetch call)
        jax.grad(loss)(p, True)


# -- the PLAIN held path of a forward that is never differentiated (PR 69) ----------------
# `moe._topk_local` outside the bounded body (un-gated experts, a hidden size of an odd
# number of lane tiles, an expert width of no whole number: nemotron_h's 2688 x 1856 at
# 384 x 192): `forward_only` asks `held_layout(empty_tiles=False)` there too and runs the two
# products through `moe.forward_gemm`, the same kernels with no VJP.

PLAIN_T, PLAIN_E, PLAIN_HELD, PLAIN_FIRST = 24, 8, 4, 4


def _plain_cfg(dtype, act_fn):
    cfg = ModelConfig(vocab_size=64, hidden_size=384, num_layers=1, num_heads=4, ffn_dim=128,
                      moe_ffn_dim=192, max_seq_len=32, dtype=dtype, act_fn=act_fn,
                      moe_experts=PLAIN_E, moe_router="softmax_topk", moe_top_k=2,
                      moe_share=(1, 2))
    assert moe.held_path_counts(cfg) == {"bounded": 0, "worst_case": 1}
    assert (cfg.moe_first_held, cfg.moe_held) == (PLAIN_FIRST, PLAIN_HELD)
    return cfg


def _plain_scores(load):
    """(T, E) scores whose top-2 a token: ``empty`` leaves held experts 5 and 7 without a
    row, ``full`` gives every held expert rows (the first more than a tile's), ``none``
    names no held expert at all."""
    t = np.arange(PLAIN_T)
    first = {"empty": np.where(t % 3 == 0, 4, np.where(t % 3 == 1, 6, 0)),
             "full": np.where(t < 18, 4, 5 + t % 3), "none": t % 4}[load]
    second = {"empty": 1 + t % 3, "full": np.where(t % 2 == 0, 5 + t % 3, 3),
              "none": (t + 1) % 4}[load]
    scores = np.full((PLAIN_T, PLAIN_E), 0.01, np.float32)
    scores[t, first], scores[t, second] = 0.5, 0.3
    return jnp.asarray(scores)


@pytest.mark.parametrize("act_fn", ["relu2", "swiglu"], ids=["ungated", "gated"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("load", ["empty", "full", "none"])
def test_forward_only_plain_path_is_the_differentiated_body_to_the_bit(monkeypatch, load, dtype,
                                                                       act_fn):
    """The same row tiles against the same weights, only their places in the buffer move;
    the tiles below ``num_tiles`` name exactly the experts that got a row, and with no
    held expert chosen (``num_tiles`` 0) exact zeros come out."""
    cfg = _plain_cfg(dtype, act_fn)
    p = moe.init_moe_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, PLAIN_T // 2, 384), dtype)
    scores = _plain_scores(load)
    monkeypatch.setattr(moe, "router_scores", lambda xt, router, cfg_: scores)
    asked, real = [], moe.held_layout

    def recording(*args, empty_tiles=True):
        asked.append(empty_tiles)
        return real(*args, empty_tiles=empty_tiles)

    monkeypatch.setattr(moe, "held_layout", recording)
    want, want_stats = moe.moe_topk_block(x, p, cfg)
    got, got_stats = moe.moe_topk_block(x, p, cfg, forward_only=True)
    assert asked == [True, False]
    assert got.dtype == want.dtype == dtype
    assert np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    for g, w in zip(got_stats[:2], want_stats[:2]):
        assert np.array_equal(np.asarray(g), np.asarray(w))

    idx = jax.lax.top_k(scores, 2)[1]
    tile = moe.layer_row_tile(cfg, PLAIN_T, dtype)
    lay = real(idx, PLAIN_HELD, tile, PLAIN_FIRST, empty_tiles=False)
    sizes, count = np.asarray(lay.sizes), int(lay.num_tiles[0])
    assert {"empty": list(sizes > 0) == [True, False, True, False], "full": (sizes > 0).all(),
            "none": not sizes.any()}[load]
    named = np.asarray(lay.tile_group)[:count]
    assert sorted(set(named.tolist())) == np.flatnonzero(sizes).tolist()
    assert count == sum(-(-int(n) // tile) for n in sizes)
    # `moe_held_rows_share`, the third statistic: the tiles in use over the buffer's
    assert float(got_stats[2]) == pytest.approx(count * tile / lay.row_valid.shape[0])
    assert float(want_stats[2]) == pytest.approx(
        (count + int((sizes == 0).sum())) * tile / lay.row_valid.shape[0])
    if load == "full":
        assert sizes[0] > tile  # (consecutive tiles of one expert)
    if load == "none":
        assert count == 0 and not np.asarray(got, np.float32).any()
    else:
        assert np.abs(np.asarray(got, np.float32)).max() > 0.0


@pytest.mark.parametrize("act_fn", ["relu2", "swiglu"], ids=["ungated", "gated"])
def test_differentiating_a_forward_only_plain_path_raises(monkeypatch, act_fn):
    """`moe.forward_gemm` carries no VJP: over a layout without empty tiles `moe_tgmm`
    would write no weight-gradient block for an expert without a row, so a gradient is
    refused; the default layout differentiates as ever."""
    cfg = _plain_cfg(jnp.float32, act_fn)
    p = moe.init_moe_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, PLAIN_T // 2, 384), jnp.float32)
    scores = _plain_scores("empty")
    monkeypatch.setattr(moe, "router_scores", lambda xt, router, cfg_: scores)

    def loss(p_, forward_only):
        return jnp.sum(moe.moe_topk_block(x, p_, cfg, forward_only=forward_only)[0] ** 2)

    grads = jax.grad(loss)(p, False)
    moved = np.abs(np.asarray(grads["w2"])).max(axis=(1, 2)) > 0
    assert list(moved) == [True, False, True, False]  # (the held experts with a row)
    with pytest.raises(NotImplementedError):  # (Pallas has no JVP of a scalar-prefetch call)
        jax.grad(loss)(p, True)


# -- the column block of a grouped GEMM follows the width's divisors (PR 69) --------------

#: width -> the block `_tile(width, 1024)` gives it: every hidden size, expert width and
#: joined [gate | up] width the dropless presets feed `_gmm` / `_tgmm`. Only 2688 moved in
#: PR 69 (128 before: 21 column blocks; one whole block now, as 1856 always was); a width
#: with a power-of-two block above one lane tile keeps it.
COLUMN_BLOCKS = {512: 512, 768: 256, 1024: 1024, 1536: 512, 1856: 1856, 2048: 1024, 2560: 512,
                 2688: 2688, 3072: 1024, 4096: 1024, 5120: 1024, 6144: 1024}


@pytest.mark.parametrize("width,block", sorted(COLUMN_BLOCKS.items()))
def test_column_block_of_a_width(width, block):
    from galvatron_tpu.ops.grouped_matmul import _tile

    assert _tile(width, 1024) == block and width % block == 0
    assert block == width or block % 128 == 0
    # a power-of-two block above one lane tile, where one divides, is kept; else one block
    pow2 = [t for t in (1024, 512, 256) if width % t == 0]
    assert block == (pow2[0] if pow2 else width)


def test_column_block_table_covers_what_the_presets_feed_it():
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.ops.grouped_matmul import _tile

    fed = set()
    for cfg in PRESETS.values():
        if cfg.moe_dropless:
            fed |= {cfg.hidden_size, cfg.expert_ffn}
            if not moe.ungated(cfg):
                fed.add(2 * cfg.expert_ffn)
    assert fed <= set(COLUMN_BLOCKS) and {2688, 1856} <= fed
    # one lane tile times an odd number, or no whole lane tiles at all: one whole block
    assert [_tile(n, 1024) for n in (128, 384, 640, 1152)] == [128, 384, 640, 1152]
    assert [_tile(n, 1024) for n in (32, 96, 160)] == [32, 96, 160]
    assert [_tile(2688, want) for want in (128, 512, 2688)] == [2688, 2688, 2688]


@pytest.mark.parametrize("rows,tile_m,grid", [(912, 16, (1, 57)), (7200, 32, (1, 225))],
                         ids=["decode_step", "prompt_chunk"])
def test_the_grid_of_the_served_down_projection(rows, tile_m, grid):
    """`moe_gmm` at the nemotron cell's down projection, a decode step's buffer (64 x 6
    pairs + 33 groups x 16 rows) and a prompt chunk's: one column block where `_tile` gave
    21 (1,197 and 4,725 grid steps a layer), the grid of the up projection."""
    from galvatron_tpu.ops.grouped_matmul import _gmm

    assert rows == moe.buffer_rows((64 if tile_m == 16 else 1024) * 6, 33, tile_m)
    sd = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(functools.partial(
        _gmm, transpose_rhs=False, tile_m=tile_m, tile_n=1024))(
            sd((rows, 1856), jnp.bfloat16), sd((32, 1856, 2688), jnp.bfloat16),
            sd((rows // tile_m,), jnp.int32), sd((1,), jnp.int32))
    call, = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert tuple(call.params["grid_mapping"].grid) == grid
    assert grid[1] == rows // tile_m


@pytest.mark.parametrize("transpose_rhs", [False, True], ids=["moe_gmm", "moe_gmm_dlhs"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
def test_a_wider_column_block_is_the_lane_tile_blocks_product_to_the_bit(monkeypatch, dtype,
                                                                        transpose_rhs):
    """The contraction is whole in every block, so 2688 columns in one block
    or in 21 of 128 are the same products summed in float32 and rounded once: to the bit
    in bf16, the served type (in float32 the CPU's dot orders its sums by the block's
    width: an ulp). A ragged layout: a group of several tiles, one of part of a tile, one
    of none, tiles past ``num_tiles``."""
    from galvatron_tpu.ops import grouped_matmul

    tile_m, k, n, groups = 16, 64, 2688, 4
    idx = jnp.asarray([0] * 37 + [2] * 5 + [3] * 16, jnp.int32)[:, None]
    lay = moe.sorted_layout(idx, groups, tile_m, empty_tiles=False)
    count = int(lay.num_tiles[0])
    assert list(np.asarray(lay.tile_group)[:count]) == [0, 0, 0, 2, 3]
    assert count < lay.row_valid.shape[0] // tile_m
    ks = jax.random.split(jax.random.key(3), 2)
    lhs = jnp.where(lay.row_valid[:, None],
                    jax.random.normal(ks[0], (lay.row_valid.shape[0], k), dtype), 0)
    rhs = jax.random.normal(ks[1], (groups, n, k) if transpose_rhs else (groups, k, n), dtype)
    run = functools.partial(grouped_matmul._gmm, lhs, rhs, lay.tile_group, lay.num_tiles,
                            transpose_rhs=transpose_rhs, tile_m=tile_m, tile_n=1024)
    assert grouped_matmul._tile(n, 1024) == n
    got = np.asarray(run(), np.float32)
    monkeypatch.setattr(grouped_matmul, "_tile", lambda n_, want: 128)  # (as it was: 21 blocks)
    want = np.asarray(run(), np.float32)
    if dtype == jnp.bfloat16:
        assert np.array_equal(got, want)
    assert np.abs(got - want).max() <= 2e-6 * np.abs(want).max()
    assert np.abs(got[:count * tile_m]).max() > 0.0
    assert not got[count * tile_m:].any()  # (zeros past ``num_tiles``)


# -- the layout is counted, not sorted (PR 67) --------------------------------------------
# `moe.sorted_layout`: a pair's row = its group's first row + the pairs of its group before
# it, by comparison and sum, at any number of groups. The sort-based body it replaced stays as
# `moe._layout_by_sort`, the reference it is held to here, all six arrays, to the bit.

#: name: (tokens, top_k, groups, tile, load): each benchmark cell's group count and tile at a
#: scaled-down pair count, and the loads that empty a group or fill one
LAYOUT_CASES = {
    "33x256_qwen3_next": (512, 10, 33, 256, "even"),
    "33x256_skewed": (512, 10, 33, 256, "skewed"),
    "33x64_chunk": (128, 8, 33, 64, "even"),
    "33x16_step": (32, 8, 33, 16, "skewed"),
    "17x16_step": (32, 6, 17, 16, "even"),
    "17x64_chunk": (256, 6, 17, 64, "skewed"),
    "64x256_olmoe": (512, 8, 64, 256, "even"),
    "5x2": (4, 2, 5, 2, "even"),
    "128_groups_a_lane_row": (64, 4, 128, 8, "even"),
    "129_groups": (64, 4, 129, 8, "even"),
    "512_groups_every_expert_held": (96, 10, 512, 8, "skewed"),
    "first_group_empty": (96, 4, 9, 8, "first_empty"),
    "last_group_empty": (96, 4, 9, 8, "last_empty"),
    "inner_groups_empty": (96, 4, 9, 8, "inner_empty"),
    "every_pair_dropped": (64, 4, 5, 8, "last_alone"),
    "every_pair_in_one_group": (64, 4, 9, 8, "one"),
    "pairs_no_multiple_of_the_block": (100, 3, 33, 16, "even"),
    "pairs_one_past_a_block": (257, 1, 17, 16, "skewed"),
    "one_pair": (1, 1, 3, 8, "even"),
}


def _layout_choices(tokens, top_k, groups, load):
    rng = np.random.default_rng(tokens * 131 + groups)
    if load == "even":
        idx = rng.integers(0, groups, (tokens, top_k))
    elif load == "skewed":
        idx = np.minimum(rng.geometric(0.3, (tokens, top_k)) - 1, groups - 1)
    elif load == "first_empty":
        idx = rng.integers(2, groups, (tokens, top_k))
    elif load == "last_empty":
        idx = rng.integers(0, groups - 2, (tokens, top_k))
    elif load == "inner_empty":
        idx = rng.choice([0, 1, groups - 2, groups - 1], (tokens, top_k))
    else:
        idx = np.full((tokens, top_k), {"last_alone": groups - 1, "one": groups // 2}[load])
    return jnp.asarray(idx, jnp.int32)


@pytest.mark.parametrize("empty_tiles", [True, False], ids=["empty_tiles", "no_empty_tiles"])
@pytest.mark.parametrize("case", list(LAYOUT_CASES))
def test_counted_layout_is_the_sorted_layout_to_the_bit(case, empty_tiles):
    tokens, top_k, groups, tile, load = LAYOUT_CASES[case]
    idx = _layout_choices(tokens, top_k, groups, load)
    got = jax.jit(lambda i: moe.sorted_layout(i, groups, tile, empty_tiles))(idx)
    want = jax.jit(lambda i: moe._layout_by_sort(i, groups, tile, empty_tiles))(idx)
    for name, g, w in zip(got._fields, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert np.array_equal(np.asarray(g), np.asarray(w)), name
    # and the contract itself, on the counted body's own arrays
    flat, pair_row = np.asarray(idx).reshape(-1), np.asarray(got.pair_row)
    assert list(np.asarray(got.sizes)) == list(np.bincount(flat, minlength=groups))
    assert int(got.row_valid.sum()) == flat.size == len(set(pair_row))
    assert (np.asarray(got.row_pair)[pair_row] == np.arange(flat.size)).all()
    assert (np.asarray(got.tile_group)[pair_row // tile] == flat).all()
    if load == "last_alone":  # as `held_layout` sees it: no held expert got a pair
        held = moe.held_layout(idx, groups - 1, tile, 0, empty_tiles=empty_tiles)
        assert int(held.num_tiles[0]) == (groups - 1 if empty_tiles else 0)
        assert not np.asarray(held.row_valid).any()
        assert (np.asarray(held.pair_row) >= int(held.num_tiles[0]) * tile).all()


def _primitives(jaxpr):
    """Every primitive's name in a jaxpr, those of the jaxprs its equations hold too."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("held, tile", [(32, 256), (63, 256), (511, 256)],
                         ids=["qwen3_next_33", "64_as_olmoe", "512_every_expert_held"])
def test_a_training_layout_holds_no_sort_no_scatter_add_and_one_scatter(held, tile):
    """`held_layout` at the qwen3-next cell's shape (16,384 tokens x top-10, 32 held + the
    dropped, tiles of 256) and at wider ones, abstract values only: dense passes and the one
    scatter of the rows' pairs, whatever the number of groups."""
    idx = jax.ShapeDtypeStruct((16384, 10), jnp.int32)
    names = list(_primitives(jax.make_jaxpr(lambda i: moe.held_layout(i, held, tile, 0))(idx).jaxpr))
    assert not {"sort", "scatter-add", "scatter_add", "while", "gather"} & set(names), names
    assert names.count("scatter") == 1
    out = jax.eval_shape(lambda i: moe.held_layout(i, held, tile, 0), idx)
    assert out.row_pair.shape == (moe.buffer_rows(163840, held + 1, tile),)
    assert moe.buffer_rows(163840, 33, 256) == 172288


def test_every_benchmark_configuration_with_a_routed_layer_counts_its_layout():
    """Each at its own group count, top-k and a chunk's 1,024 tokens: no `sort` in the jaxpr."""
    import glob
    import json
    import os

    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args

    routed = {}
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for path in sorted(glob.glob(os.path.join(here, "benchmark", "configs", "*.json"))):
        flags = list(json.load(open(path))["program_flags"])
        mode = "serve" if "--param_dtype" in flags else "train"
        cfg = model_config_from_args(initialize_galvatron(mode, flags))
        if cfg.moe_dropless:
            groups = cfg.moe_held + 1 if cfg.moe_holds_share else cfg.moe_experts
            idx = jax.ShapeDtypeStruct((1024, cfg.moe_top_k), jnp.int32)
            tile = moe.layer_row_tile(cfg, 1024)
            names = set(_primitives(jax.make_jaxpr(
                lambda i: moe.sorted_layout(i, groups, tile))(idx).jaxpr))
            routed[os.path.basename(path)[:-5]] = groups, bool({"sort", "scatter-add"} & names)
    assert routed == {
        "dots3-note-prev": (33, False), "granite-4.0-h-small": (37, False),
        "lfm2-24b-a2b": (17, False),
        "nemotron-3-nano-30b-a3b": (33, False), "olmoe-1b-7b": (64, False),
        "qwen3-next-80b-a3b": (33, False), "sarvam-105b": (33, False),
        "smallthinker-21b-a3b": (17, False), "trinity-large-preview": (33, False)}
