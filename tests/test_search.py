"""Search engine tests (build plan steps 8-10): C++ DP core vs NumPy
equivalence, budget-driven strategy shifts, and search→train loop closure
(the emitted config must build and train in the runtime)."""

import json

import numpy as np
import pytest

from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.search.cost_model import (
    ProfiledHardware,
    ProfiledLayerType,
    ProfiledModelCosts,
)
from galvatron_tpu.search.dynamic_programming import dp_numpy, run_dp
from galvatron_tpu.search.native import dp_core_native, get_dp_core
from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace, generate_layer_strategies


def rand_dp_instance(seed, L=6, S=5, V=40):
    rng = np.random.RandomState(seed)
    mem = rng.randint(1, 12, (L, S)).astype(np.int32)
    intra = rng.uniform(1.0, 10.0, (L, S))
    inter = rng.uniform(0.0, 2.0, (S, S))
    np.fill_diagonal(inter, 0.0)
    return mem, intra, inter, V


def brute_force(mem, intra, inter, V):
    L, S = mem.shape
    best, best_choice = np.inf, None
    import itertools

    for combo in itertools.product(range(S), repeat=L):
        m = sum(mem[i, c] for i, c in enumerate(combo))
        if m > V:
            continue
        c = sum(intra[i, ci] for i, ci in enumerate(combo))
        c += sum(inter[combo[i], combo[i + 1]] for i in range(L - 1))
        if c < best:
            best, best_choice = c, combo
    return best, best_choice



def test_native_build_keyed_by_source_content(tmp_path, monkeypatch, capsys):
    """build/ is untracked: a fresh tree compiles from csrc/ on first use,
    staleness is the .cpp's content hash (in the file name) and never its
    mtime, and a failed build says so on stderr instead of failing silently."""
    import hashlib
    import os

    from galvatron_tpu.utils import native_build

    monkeypatch.setattr(native_build, "_BUILD_DIR", tmp_path / "build")
    assert native_build.load_native("dp_core") is not None
    (so,) = (tmp_path / "build").glob("libgalvatron_dp_core.*.so")
    src = native_build._REPO_ROOT / "csrc" / "dp_core.cpp"
    assert hashlib.sha256(src.read_bytes()).hexdigest()[:16] in so.name
    os.utime(so, (0, 0))  # older than the source: a copy resets mtimes
    assert native_build.load_native("dp_core") is not None
    assert so.stat().st_mtime == 0, "rebuilt on mtime"
    monkeypatch.setattr(native_build, "_REPO_ROOT", tmp_path)  # no csrc/ here
    assert native_build.load_native("dp_core") is None
    assert "native dp_core unavailable" in capsys.readouterr().err


def test_native_core_builds():
    assert get_dp_core() is not None, "C++ DP core failed to build/load"


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_dp_matches_brute_force(seed):
    mem, intra, inter, V = rand_dp_instance(seed, L=5, S=4, V=30)
    bf_cost, bf_choice = brute_force(mem, intra, inter, V)
    np_cost, np_res, _ = dp_numpy(mem, intra, inter, V)
    assert np.isclose(np_cost, bf_cost), (np_cost, bf_cost)
    nat = dp_core_native(mem, intra, inter, V)
    assert nat is not None
    nat_cost, nat_res, nat_mem = nat
    assert np.isclose(nat_cost, bf_cost), (nat_cost, bf_cost)
    # the chosen path must realize the claimed cost and fit the budget
    c = sum(intra[i, nat_res[i]] for i in range(len(nat_res)))
    c += sum(inter[nat_res[i], nat_res[i + 1]] for i in range(len(nat_res) - 1))
    assert np.isclose(c, nat_cost)
    assert sum(mem[i, nat_res[i]] for i in range(len(nat_res))) <= V
    assert nat_mem == sum(mem[i, nat_res[i]] for i in range(len(nat_res)))


def test_dp_infeasible():
    mem = np.full((3, 2), 50, np.int32)
    intra = np.ones((3, 2))
    inter = np.zeros((2, 2))
    cost, res, _ = run_dp(mem, intra, inter, 10)
    assert not np.isfinite(cost) and (res == -1).all()


def toy_costs(param_mb=80.0, act_mb=40.0):
    lt = ProfiledLayerType(
        fwd_ms_per_sample=2.0,
        parameter_mb=param_mb,
        activation_mb_per_sample={1: act_mb, 2: act_mb / 2, 4: act_mb / 4, 8: act_mb / 8},
        boundary_activation_mb_per_sample=4.0,
    )
    return ProfiledModelCosts(
        layer_types={0: lt}, other_param_mb=100.0, other_act_mb_per_sample=8.0,
        other_fwd_ms_per_sample=0.3,
    )


def toy_hw():
    return ProfiledHardware(
        allreduce_bw={
            "2_1": 150.0, "2_0": 30.0, "4_1": 140.0, "4_0": 25.0, "8_1": 120.0,
        },
        p2p_bw={2: 50.0, 4: 50.0},
        overlap_coe=1.1,
    )


def make_engine(budget_mb, **space_kw):
    space = SearchSpace(world_size=8, **space_kw)
    return SearchEngine(
        toy_costs(), toy_hw(), num_layers=8, space=space, memory_budget_mb=budget_mb
    )


def test_strategy_space_generation():
    space = SearchSpace(world_size=8)
    cands = generate_layer_strategies(space, pp=1)
    tags = {(s.tp, s.tp_consec, s.dp_type, s.ckpt, s.sp) for s in cands}
    assert (1, True, "ddp", False, False) in tags
    assert (8, True, "ddp", False, True) in tags  # full TP + SP
    # strided + fsdp + ckpt (ckpt=True normalizes to 'full', strategy.py)
    assert (2, False, "zero3", "full", False) in tags
    assert all(s.tp * s.cp <= 8 for s in cands)
    # pp=4: per-stage device budget shrinks
    cands4 = generate_layer_strategies(space, pp=4)
    assert all(s.tp * s.cp <= 2 for s in cands4)


def test_tp_overlap_enumeration_and_pricing():
    """tp_overlap is enumerated on every sequence-parallel tp>1 cell without
    being asked (never tp==1, which the plan checker rejects as GTA018, never
    without sp, never cp>1), and the cost model prices it from the ring's own
    shape test: strictly cheaper where a seam's piece GEMM covers its hop,
    the plain price where none does or the profile carries no shapes."""
    import dataclasses

    from galvatron_tpu.search.cost_model import layer_time_cost, tp_overlap_exposed

    space = SearchSpace(world_size=8)
    cands = generate_layer_strategies(space, pp=1)
    assert any(s.tp_overlap and s.tp > 1 for s in cands)
    assert not any(s.tp_overlap and (s.tp == 1 or s.cp > 1 or not s.sp) for s in cands)
    assert {(s.tp, s.tp_consec, s.dp_type, s.ckpt) for s in cands if s.sp and s.tp_overlap} == {
        (s.tp, s.tp_consec, s.dp_type, s.ckpt) for s in cands if s.sp and not s.tp_overlap}
    hw = toy_hw()
    seams = {  # (kind, width the tp axes divide, rows a sample, blockwise)
        "wide": (("ag", 6144, 2048, False), ("rs", 2048, 2048, False),
                 ("ag", 8192, 2048, True), ("rs", 8192, 2048, True)),
        "narrow": (("ag", 768, 2048, False), ("rs", 256, 2048, False),
                   ("ag", 1024, 2048, True), ("rs", 1024, 2048, True)),
        "none": (),
    }
    checked = 0
    for name, tp_seams in seams.items():
        lt = dataclasses.replace(toy_costs().layer_types[0], tp_seams=tp_seams)
        for s in cands:
            if not s.tp_overlap:
                continue
            plain = dataclasses.replace(s, tp_overlap=False)
            t_ov = layer_time_cost(lt, s, hw, world=8, pp=1, global_bsz=8)
            t_plain = layer_time_cost(lt, plain, hw, world=8, pp=1, global_bsz=8)
            share = tp_overlap_exposed(lt, s, 8 * s.tp / 8, 2)
            if name == "wide" and s.tp == 4:
                assert t_ov < t_plain and 0.0 <= share < 1.0, (s, t_ov, t_plain, share)
            if name != "wide":
                assert t_ov == t_plain and share == 1.0, (name, s, t_ov, t_plain)
            checked += 1
    assert checked > 0
    # the engine hands the DP one of each (plain, tp_overlap) pair: the ring
    # where the layer's seams take it, the plain layer where none does
    for name, tp_seams in seams.items():
        costs = toy_costs()
        costs.layer_types[0] = dataclasses.replace(costs.layer_types[0], tp_seams=tp_seams)
        eng = SearchEngine(costs, hw, num_layers=8, space=space, memory_budget_mb=20000.0)
        kept = eng._feasible_strategies(pp=1, global_bsz=8, chunks=1)
        sp_tp4 = [s for s in kept if s.sp and s.tp == 4]
        assert sp_tp4 and {s.tp_overlap for s in sp_tp4} == {name == "wide"}, (name, sp_tp4)
        assert len(kept) == len([s for s in cands if not s.tp_overlap
                                 and 8 % (8 // (s.tp * s.cp) * max(1, s.cp)) == 0])


def test_tight_budget_forces_sharded_strategies():
    """With a generous budget the search picks plain DP (fastest by the cost
    model); squeezing the budget must move it to ZeRO/TP/ckpt strategies."""
    roomy = make_engine(20000.0).search([8])
    tight = make_engine(900.0).search([8])
    assert roomy is not None and tight is not None
    roomy_s = roomy.config.layer_strategies[0]
    # compute-optimal: no TP splitting, no recompute. On exact cost ties the
    # DP prefers the lower-memory (sharded) variant — same bias as the
    # reference's fsdp-preferring tie-break (dynamic_programming.py:374-403)
    assert roomy_s.tp == 1 and not roomy_s.ckpt
    # tight budget: every layer must shave model states or activations
    assert all(
        s.dp_type != "ddp" or s.tp > 1 or s.ckpt for s in tight.config.layer_strategies
    )
    assert tight.cost_ms >= roomy.cost_ms
    # infeasible budget
    assert make_engine(40.0).search([8]) is None


def test_search_emits_runnable_config(tmp_path):
    """Search→train loop closure (reference: search_dist emits JSON,
    train_dist consumes it; search_engine.py:326-367)."""
    import jax
    import jax.numpy as jnp

    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.models.modeling import ModelConfig
    from galvatron_tpu.parallel.hybrid import build_runtime

    eng = make_engine(1500.0)
    res = eng.search([8])
    assert res is not None
    path = str(tmp_path / "galvatron_config.json")
    eng.save_result(res, path)
    hp = HybridParallelConfig.load(path)
    hp = HybridParallelConfig(
        pp=hp.pp, layer_strategies=hp.layer_strategies[:4], chunks=hp.chunks,
        pipeline_type=hp.pipeline_type, vocab_tp=hp.vocab_tp,
        embed_dp_type=hp.embed_dp_type, mixed_precision="fp32",
    )  # shrink to the 4-layer test model
    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, num_layers=4, num_heads=4, ffn_dim=128,
        max_seq_len=32, dtype=jnp.float32,
    )
    rt = build_runtime(cfg, hp, adam=AdamConfig(lr=1e-3), global_batch_size=8, seq_len=32)
    state = rt.init_state(jax.random.key(0))
    batch = jnp.asarray(np.random.RandomState(0).randint(0, 128, (8, 33)), jnp.int32)
    state, loss = rt.train_step(state, batch)
    assert np.isfinite(float(loss))


def test_pipeline_search_respects_stacking():
    """pp>1 results must satisfy the runtime's cross-stage stacking rule."""
    eng = make_engine(1200.0, pp_choices=[2, 4])
    res = eng.search([16])
    assert res is not None
    hp = res.config
    assert hp.pp in (2, 4)
    lps = len(hp.layer_strategies) // hp.pp
    for j in range(lps):
        base = hp.layer_strategies[j]
        for s in range(1, hp.pp):
            assert hp.layer_strategies[s * lps + j] == base


def test_vpp_searched_and_reduces_pipeline_cost():
    """Interleaved schedule in the search: the vpp>1 evaluation must beat the
    plain gpipe cost for the same (pp, chunks) — the bubble shrinks by vpp —
    and the winning config must carry vpp through the JSON codec."""
    eng = make_engine(3000.0, max_vpp=2, pipeline_types=("gpipe",))
    r1 = eng.evaluate(pp=2, global_bsz=16, chunks=4, pipeline_type="gpipe")
    r2 = eng.evaluate(pp=2, global_bsz=16, chunks=4, pipeline_type="gpipe", vpp=2)
    assert r1 is not None and r2 is not None
    assert r2.cost_ms < r1.cost_ms
    assert r2.config.vpp == 2 and len(r2.config.layer_strategies) == 8
    # constraints: chunks % pp and layers % (pp*vpp)
    assert eng.evaluate(2, 16, 2, "gpipe", vpp=8) is None  # 8 layers % 16 != 0
    assert eng.evaluate(2, 18, 3, "gpipe", vpp=2) is None  # chunks 3 % pp 2
    # vpp now composes with pipedream_flush (interleaved 1F1B)
    r3 = eng.evaluate(2, 16, 4, "pipedream_flush", vpp=2)
    assert r3 is not None and r3.config.vpp == 2
    r3.config.validate(8)
    # the full sweep explores vpp when enabled
    best = eng.search([16])
    assert best is not None
    d = best.config.to_json_dict()
    from galvatron_tpu.core.strategy import HybridParallelConfig

    assert HybridParallelConfig.from_json_dict(d).vpp == best.config.vpp


def test_vocab_strategy_searched():
    """vocab_tp x embed_dp_type is a searched dimension (reference:
    --vocab_tp/--embed_sdp): a huge embedding under a tight budget forces the
    search off vocab_tp=1/ddp; a roomy budget keeps the comm-free default."""
    lt = ProfiledLayerType(
        fwd_ms_per_sample=2.0,
        parameter_mb=80.0,
        activation_mb_per_sample={1: 40.0, 2: 20.0, 4: 10.0, 8: 5.0},
        boundary_activation_mb_per_sample=4.0,
    )
    big_embed = ProfiledModelCosts(
        layer_types={0: lt}, other_param_mb=4000.0, other_act_mb_per_sample=8.0,
        other_fwd_ms_per_sample=0.3,
    )
    hw = ProfiledHardware(
        allreduce_bw={"2_1": 150.0, "2_0": 30.0, "4_1": 140.0, "8_1": 120.0},
        overlap_coe=1.1,
    )
    space = SearchSpace(world_size=8, pp_choices=[1], max_tp=2)
    roomy = SearchEngine(big_embed, hw, 4, space, memory_budget_mb=50000.0).search([8])
    tight = SearchEngine(big_embed, hw, 4, space, memory_budget_mb=2600.0).search([8])
    assert roomy is not None and tight is not None
    # a 4 GB embedding's per-step grad allreduce dwarfs the vocab-TP
    # activation psums: sharding must win even with a roomy budget
    assert roomy.config.vocab_tp > 1 or roomy.config.embed_dp_type == "zero3"
    # 4 GB fp32 embedding states (~18 GB with grads+Adam) cannot fit 2.6 GB
    # unsharded: the searched vocab strategy must shard it
    t = tight.config
    assert t.vocab_tp > 1 or t.embed_dp_type == "zero3", (t.vocab_tp, t.embed_dp_type)
    assert tight.details["other_memory_mb"] <= roomy.details["other_memory_mb"]
    # small embedding + roomy budget: comm terms are minor either way, but
    # the sweep must price vocab_tp (details carry the searched choice)
    small = ProfiledModelCosts(
        layer_types={0: lt}, other_param_mb=10.0, other_act_mb_per_sample=8.0,
        other_fwd_ms_per_sample=0.3,
    )
    r2 = SearchEngine(small, hw, 4, space, memory_budget_mb=50000.0).search([8])
    assert "vocab_tp" in r2.details and "embed_dp_type" in r2.details


def test_transition_costs_ride_pipeline_ticks():
    """Inter-position resharding is paid per micro-batch stage pass: its
    contribution to a pp>1 prediction must carry the pipeline fill/steady
    amplification (~(chunks+pp-1)/chunks x the flat per-iteration volume),
    not be added flat (the old 1x under-count)."""
    import galvatron_tpu.search.search_engine as se

    lt = ProfiledLayerType(
        fwd_ms_per_sample=2.0,
        parameter_mb=80.0,
        activation_mb_per_sample={1: 40.0, 2: 20.0, 4: 10.0, 8: 5.0},
        boundary_activation_mb_per_sample=4.0,
    )
    costs = ProfiledModelCosts(
        layer_types={0: lt}, other_param_mb=100.0, other_act_mb_per_sample=8.0,
        other_fwd_ms_per_sample=0.0,
    )
    hw = ProfiledHardware(overlap_coe=1.0)
    space = SearchSpace(
        world_size=8, pp_choices=[2], max_tp=1, allow_sp=False, allow_ckpt=False,
        allow_zero2=False, allow_zero3=False, allow_strided=False,
    )
    eng = SearchEngine(costs, hw, 4, space, memory_budget_mb=50000.0)

    K = 7.0  # ms of resharding per boundary per iteration (global volume)
    orig = se.transition_cost_ms
    try:
        se.transition_cost_ms = lambda a, b, *r, **kw: K  # every boundary pays
        pp, chunks = 2, 4
        with_t = eng.evaluate(pp, 16, chunks, "gpipe")
        se.transition_cost_ms = lambda a, b, *r, **kw: 0.0
        without = eng.evaluate(pp, 16, chunks, "gpipe")
    finally:
        se.transition_cost_ms = orig
    assert with_t is not None and without is not None
    n_boundaries = 4 // pp - 1  # positions per stage - 1
    delta = with_t.cost_ms - without.cost_ms
    # per-tick share K/chunks, amplified by the (chunks + pp - 1) ticks every
    # stage's clock runs (pipeline_time_cost: sum + bottleneck*(chunks-1))
    expected = n_boundaries * K / chunks * (chunks + pp - 1)
    assert abs(delta - expected) < 1e-6, (delta, expected)
    assert delta > n_boundaries * K  # strictly more than the old flat count


def test_fallback_bandwidths_labeled(tmp_path):
    """Predictions priced from built-in default bandwidths (unprofiled
    single-chip hosts) are labeled in the result and the saved config."""
    import json as _json

    lt = ProfiledLayerType(
        fwd_ms_per_sample=2.0, parameter_mb=80.0,
        activation_mb_per_sample={1: 40.0, 2: 20.0},
        boundary_activation_mb_per_sample=4.0,
    )
    costs = ProfiledModelCosts(
        layer_types={0: lt}, other_param_mb=100.0, other_act_mb_per_sample=8.0,
        other_fwd_ms_per_sample=0.3,
    )
    eng = SearchEngine(
        costs, ProfiledHardware(), 4,
        SearchSpace(world_size=8, pp_choices=[2], max_tp=2),
        memory_budget_mb=20000.0,
    )
    r = eng.evaluate(2, 8, 2, "gpipe")
    assert set(r.details["fallback_bandwidths"]) == {"allreduce_bw", "p2p_bw"}
    path = tmp_path / "cfg.json"
    eng.save_result(r, str(path))
    assert "fallback_bandwidths" in _json.load(open(path))
    # measured hardware: no label
    hw = ProfiledHardware(allreduce_bw={"2_1": 100.0}, p2p_bw={2: 50.0})
    eng2 = SearchEngine(
        costs, hw, 4, SearchSpace(world_size=8, pp_choices=[2], max_tp=2),
        memory_budget_mb=20000.0,
    )
    assert eng2.evaluate(2, 8, 2, "gpipe").details["fallback_bandwidths"] == []


def test_homogeneity_gap_reference_shaped():
    """The cross-stage homogeneity restriction, QUANTIFIED (the reference
    places any strategy on any layer of any stage): per-stage DPs vs the
    position-restricted search on the LLaMA-7B-shape reference profile.

    Under the refit 1F1B memory model (round 5: the engine stashes stage
    INPUT boundaries and recomputes — pipeline_1f1b.py — so the old
    stage-varying in-flight activation bound 2(pp-1-s)+1 no longer exists;
    stash rings are stage-uniform) per-stage memory is IDENTICAL across
    stages, so the per-stage DPs solve the same subproblem as the
    restricted search and the gap is structurally zero — stronger than the
    old measured 0.00-0.04% band, and now true for the same reason as the
    multi-type engines."""
    from galvatron_tpu.search.cost_model import (
        ProfiledHardware,
        ProfiledLayerType,
        ProfiledModelCosts,
    )

    lt = ProfiledLayerType(
        fwd_ms_per_sample=4.64, parameter_mb=808.0,
        activation_mb_per_sample={1: 57.2, 2: 28.6, 4: 14.3, 8: 7.2},
        boundary_activation_mb_per_sample=16.8,
    )
    costs = ProfiledModelCosts(
        layer_types={0: lt}, other_param_mb=1049.0,
        other_act_mb_per_sample=262.0, other_fwd_ms_per_sample=0.4,
        hidden_size=4096,
    )
    hw = ProfiledHardware(
        allreduce_bw={"16_1": 45.7, "8_1": 153.5, "8_0": 32.1, "4_1": 152.4,
                      "4_0": 19.3, "2_1": 151.2, "2_0": 9.3},
        p2p_bw={2: 7.97, 4: 8.82, 8: 8.90, 16: 8.81}, overlap_coe=1.146,
    )
    for budget_gb in (9, 11, 30):
        eng = SearchEngine(
            costs, hw, num_layers=32,
            space=SearchSpace(world_size=16, pp_choices=[2]),
            memory_budget_mb=budget_gb * 1000.0,
        )
        g = eng.homogeneity_gap(2, 64, 16)
        assert g is not None, budget_gb
        assert abs(g["delta_pct"]) < 1e-6, (budget_gb, g)
        assert g["unrestricted_ms"] <= g["restricted_ms"] + 1e-6
        # stage-uniform memory → identical per-stage choices
        assert g["per_stage"][0] == g["per_stage"][-1], (budget_gb, g)


def test_recommend_min_bsz_prunes_sweep():
    """The bsz-sweep pruning (reference recommend_min_bsz): pure-strategy
    baselines bound the feasible batch range; the recommended start sits
    inside it, scales down with the budget, and degrades to `scale` when
    nothing fits."""
    from galvatron_tpu.search.cost_model import (
        ProfiledHardware,
        ProfiledLayerType,
        ProfiledModelCosts,
    )

    lt = ProfiledLayerType(
        fwd_ms_per_sample=1.0, parameter_mb=40.0,
        activation_mb_per_sample={1: 20.0, 2: 10.0, 4: 5.0, 8: 2.5},
        boundary_activation_mb_per_sample=2.0,
    )
    costs = ProfiledModelCosts(
        layer_types={0: lt}, other_param_mb=30.0,
        other_act_mb_per_sample=4.0, other_fwd_ms_per_sample=0.2,
    )
    hw = ProfiledHardware(allreduce_bw={"8_1": 120.0})

    def eng(budget_mb):
        return SearchEngine(
            costs, hw, num_layers=4,
            space=SearchSpace(world_size=8, pp_choices=[1]),
            memory_budget_mb=budget_mb,
        )

    rec_big = eng(4000.0).recommend_min_bsz(scale=8)
    rec_small = eng(900.0).recommend_min_bsz(scale=8)
    assert rec_big > rec_small >= 8
    assert rec_big % 8 == 0
    # a sweep starting at the recommendation still finds the optimum region
    res = eng(4000.0).search([rec_big])
    assert res is not None
    # nothing feasible -> degrade to scale (the sweep reports infeasibility)
    assert eng(1.0).recommend_min_bsz(scale=8) == 8


def test_search_restrictions_labeled_in_saved_config(tmp_path):
    """When a structural bail-out silently narrows the sweep (e.g. a
    K=3-section model whose group counts cannot pair-stack), the emitted
    config JSON records it in `search_restrictions` — the same provenance
    labeling fallback_bandwidths gives unmeasured bandwidths. (The former
    chunks-divisibility trigger is gone: the coupled engines run ANY chunk
    count — ring alignment is per-chunk, measured parity at chunks=3/pp=2.)"""
    import json

    from galvatron_tpu.search.cost_model import ProfiledLayerType, ProfiledModelCosts

    def lt(ms):
        return ProfiledLayerType(
            fwd_ms_per_sample=ms, parameter_mb=10.0,
            activation_mb_per_sample={1: 8.0}, boundary_activation_mb_per_sample=1.0,
        )

    # 3 layer-type groups with ODD counts: not an enc-dec pair, cannot
    # pair-stack as sections — pp>1 is structurally excluded
    costs3 = ProfiledModelCosts(
        layer_types={0: lt(1.0), 1: lt(1.5), 2: lt(2.0)},
        other_param_mb=5.0, other_act_mb_per_sample=1.0,
        other_fwd_ms_per_sample=0.1,
    )
    eng = SearchEngine(
        costs3, ProfiledHardware(), num_layers=3,
        space=SearchSpace(world_size=4, pp_choices=[1, 2], max_tp=1),
        memory_budget_mb=2000.0, mixed_precision="fp32",
    )
    r = eng.search([8], max_chunks=4)
    assert r is not None and r.config.pp == 1
    out = tmp_path / "cfg.json"
    eng.save_result(r, str(out))
    d = json.loads(out.read_text())
    assert "section_pipeline_odd_pair_count_pp1_only" in d["search_restrictions"]

    # an enc-dec 2-group model searches pp>1 across the whole chunk grid
    # (incl. chunks=1 and chunks not divisible by pp) — no restriction fires
    from galvatron_tpu.models.modeling import ModelConfig
    from galvatron_tpu.profiling.model import profile_model

    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4, ffn_dim=128,
        max_seq_len=16, enc_layers=2, enc_seq=16, pos_embed="learned",
        tie_word_embeddings=True,
    )
    costs = profile_model(cfg, bsz=8, measure_time=False)
    eng2 = SearchEngine(
        costs, ProfiledHardware(), num_layers=cfg.total_layers,
        space=SearchSpace(world_size=4, pp_choices=[1, 2], max_tp=1),
        memory_budget_mb=2000.0, mixed_precision="fp32",
    )
    assert eng2.evaluate(2, 8, 1, "gpipe") is not None  # chunks=1 at pp=2
    r2 = eng2.search([8], max_chunks=8)
    eng2.save_result(r2, str(out))
    assert "search_restrictions" not in json.loads(out.read_text())


def test_homogeneity_gap_multi_type_zero_by_construction():
    """Extend the homogeneity-gap quantification to multi-type models: for
    the tick-synchronous coupled schedules (enc-dec gpipe/1F1B, Swin
    sections) the per-stage-unrestricted optimum equals the restricted one
    BY CONSTRUCTION — the pipeline tick is bottlenecked by the max-position
    stage, whose per-stage subproblem is exactly the restricted DP; light
    stages' headroom cannot shave the bottleneck. Verified numerically on a
    ragged T5 (E=10/D=22, pp=4) and the Swin-large pyramid across budgets."""
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.search.theoretical import analytic_model_costs

    hw = ProfiledHardware(
        allreduce_bw={"16_1": 45.7, "8_1": 153.5, "8_0": 32.1, "4_1": 152.4,
                      "4_0": 19.3, "2_1": 151.2, "2_0": 9.3},
        p2p_bw={2: 7.97, 4: 8.82, 8: 8.90, 16: 8.81}, overlap_coe=1.146,
    )
    t5 = PRESETS["t5-3b"].replace(enc_layers=10, num_layers=22)
    costs = analytic_model_costs(t5)
    for ptype in ("gpipe", "pipedream_flush"):
        eng = SearchEngine(
            costs, hw, num_layers=t5.total_layers,
            space=SearchSpace(world_size=16, pp_choices=[4]),
            memory_budget_mb=8000.0,
        )
        g = eng.homogeneity_gap(4, 64, 16, ptype)
        assert g is not None, ptype
        assert abs(g["delta_pct"]) < 1e-6, (ptype, g)
        assert len(g["per_stage"]) == 4
    sw = PRESETS["swin-large"]
    eng = SearchEngine(
        analytic_model_costs(sw), hw, num_layers=sw.total_layers,
        space=SearchSpace(world_size=16, pp_choices=[4]),
        memory_budget_mb=4000.0, section_pipeline=True,
    )
    g = eng.homogeneity_gap(4, 64, 16, "gpipe")
    assert g is not None and abs(g["delta_pct"]) < 1e-6, g


def test_sweep_searches_uneven_layer_counts_at_vpp1():
    """Regression: the sweep's interleaving divisibility filter
    (L % (pp*vpp) == 0) must not exclude vpp=1 — evaluate() supports uneven
    divisions via pp_division_memory_balanced, but the sweep never reached
    pp=2 for L=3 (any L % pp != 0)."""
    lt = ProfiledLayerType(
        fwd_ms_per_sample=1.0, parameter_mb=10.0,
        activation_mb_per_sample={1: 8.0}, boundary_activation_mb_per_sample=1.0,
    )
    costs = ProfiledModelCosts(
        layer_types={0: lt}, other_param_mb=5.0, other_act_mb_per_sample=1.0,
        other_fwd_ms_per_sample=0.1,
    )
    eng = SearchEngine(
        costs, ProfiledHardware(), num_layers=3,
        space=SearchSpace(world_size=4, pp_choices=[2], max_tp=1, max_vpp=2),
        memory_budget_mb=2000.0, mixed_precision="fp32",
    )
    r = eng.search([8], max_chunks=4)
    assert r is not None and r.config.pp == 2 and r.config.vpp == 1
    assert sorted(r.config.pp_division) == [1, 2]


def _crash_cell(config):
    """True if a config matches the XLA SPMD CHECK-crash cell (BASELINE.md
    round 5): pp>1 × pipedream_flush × tp>1 × sp=False × vocab_tp>1."""
    return (
        config.pp > 1
        and config.pipeline_type == "pipedream_flush"
        and config.vocab_tp > 1
        and any(s.tp > 1 and not s.sp for s in config.layer_strategies)
    )


def test_spmd_crash_cell_structurally_unreachable():
    """NO flag combination may emit the pp>1 × pipedream_flush × tp>1 ×
    sp=False × vocab_tp>1 cell — it CHECK-crashes the XLA SPMD partitioner
    on real TPU (spmd_partitioner_util.cc:506). The sweep is exercised with
    sp allowed, sp disabled (--disable_sp: the crash-prone corner, since
    every tp>1 candidate then carries sp=False), and a tight budget that
    pushes the DP toward tp>1 strategies; every emitted candidate is
    checked, not just the winner."""
    for allow_sp in (True, False):
        for budget in (4000.0, 900.0):
            eng = make_engine(budget, allow_sp=allow_sp, pp_choices=[1, 2])
            results = eng.search_topk([8, 16], k=64, max_chunks=8)
            for r in results:
                assert not _crash_cell(r.config), (
                    allow_sp, budget, r.config.to_json_dict(),
                )
            # 1F1B × vocab_tp>1 pairs were evaluated with tp>1/sp=False
            # candidates present, so the standing exclusion must be reported
            if results and any(
                r.config.pp > 1 and r.config.pipeline_type == "pipedream_flush"
                for r in results
            ):
                assert any(
                    "spmd_crash_pp_1f1b_tp_no_sp_vocab_tp"
                    in r.details.get("search_restrictions", [])
                    for r in results
                )


def test_spmd_crash_guard_keeps_safe_vocab_tp_choices():
    """The guard must NOT delete vocab_tp>1 wholesale: under 1F1B the sp-safe
    candidate subset (tp=1 or tp>1+sp) still competes for vocab_tp>1, and a
    vocab-parallel winner with sp'd tp layers remains emittable."""
    eng = make_engine(4000.0, pp_choices=[2])
    r = eng.evaluate(2, 16, 4, "pipedream_flush")
    assert r is not None
    assert not _crash_cell(r.config)


# -- dropless top-k MoE (OLMoE-class): what the sorted path cannot run is left out ----


def test_cli_search_on_olmoe_returns_a_plan_without_ep_cp_or_pp(tmp_path, capsys):
    """`cli search --model_size olmoe-1b-7b --num_devices 4 --analytic_costs 1` returns a
    plan; with expert and context parallelism switched ON and every pp allowed, the plan
    still has none of them (build_runtime would refuse it), the exclusions are named on
    the console and in the plan, and the plan passes the plan checker. Six layers: what a
    four-chip host holds of this model (2.7 B parameters x 16 B = 44 GB of 64)."""
    from galvatron_tpu import cli

    path = str(tmp_path / "plan.json")
    rc = cli.main(["search", "--model_size", "olmoe-1b-7b", "--num_layers", "6",
                   "--num_devices", "4", "--analytic_costs", "1", "--enable_ep", "1",
                   "--enable_cp", "1", "--min_bsz", "8", "--max_bsz", "8",
                   "--output_config_path", path])
    assert rc in (0, None)
    said = capsys.readouterr().out
    assert "ep>1" in said and "cp>1" in said and "pp>1" in said
    plan = json.load(open(path))
    assert plan["pp_deg"] == 1
    assert set(plan["ep_sizes_enc"].split(",")) == {"1"}
    assert set(plan.get("cp_sizes_enc", "1").split(",")) == {"1"}
    assert plan["search_restrictions"] == [
        "dropless_topk_moe_no_cp", "dropless_topk_moe_no_ep", "dropless_topk_moe_no_pp"]
    assert plan["model_config"]["moe_experts"] == 64 and plan["model_config"]["num_layers"] == 6
    hp = HybridParallelConfig.load(path)
    hp.validate(4)
    assert cli.main(["check-plan", path]) in (0, None)


def test_enumeration_for_a_topk_moe_model_has_no_ep_cp_or_pp():
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.search.theoretical import analytic_model_costs

    cfg = PRESETS["olmoe-1b-7b"].replace(num_layers=2)
    space = SearchSpace(world_size=8, allow_ep=True, allow_cp=True, moe_experts=64)
    assert any(s.ep > 1 for s in generate_layer_strategies(space, 1))  # the space did allow it
    eng = SearchEngine(analytic_model_costs(cfg, seq_len=512), ProfiledHardware(), num_layers=2,
                       space=space, memory_budget_mb=64000.0, model_config=cfg)
    assert eng.space.pp_choices == [1] and not eng.space.allow_ep and not eng.space.allow_cp
    assert space.allow_ep  # the caller's space is copied, never mutated
    assert not any(s.ep > 1 or s.cp > 1 for s in generate_layer_strategies(eng.space, 1))
    results = eng.search_topk([8], k=20, max_chunks=4)
    assert results
    for r in results:
        assert r.config.pp == 1
        assert all(s.ep == 1 and s.cp == 1 for s in r.config.layer_strategies)
        assert "dropless_topk_moe_no_ep" in r.details["search_restrictions"]
