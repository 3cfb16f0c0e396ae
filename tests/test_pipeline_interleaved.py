"""Interleaved (virtual-pipeline-stage) schedule parity tests.

Same check_loss methodology as test_pipeline: unstack the (pp, vpp)-stacked
virtual-stage params into the flat layer list — entry [s, j] of position q is
layer (s + j*pp)*lpvs + q — and the pipeline loss must equal the plain
single-device loss. Reference analogue: vendored megatron interleaved 1F1B
(core/pipeline_parallel/schedules.py:367), unused by Galvatron's engine."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models import modeling
from galvatron_tpu.parallel.hybrid import build_runtime

from tests._train_common import ADAM, CFG, make_batch, unstack_params


def unstack_vparams(pipe_params, cfg, pp, vpp):
    lpvs = cfg.num_layers // (pp * vpp)
    layers = [None] * cfg.num_layers
    for q in range(lpvs):
        for s in range(pp):
            for j in range(vpp):
                layers[(s + j * pp) * lpvs + q] = jax.tree.map(
                    lambda a: np.asarray(a)[s, j], pipe_params["vstages"][q]
                )
    flat = {k: jax.tree.map(np.asarray, v) for k, v in pipe_params.items() if k != "vstages"}
    flat["layers"] = layers
    return flat


@pytest.mark.parametrize(
    "pp,vpp,chunks,tp,dp_type",
    [
        (2, 2, 2, 1, "ddp"),
        (2, 2, 4, 2, "zero3"),
        (4, 1, 4, 1, "ddp"),  # vpp=1 falls back to plain gpipe — sanity
    ],
)
def test_interleaved_loss_parity(pp, vpp, chunks, tp, dp_type):
    hp = HybridParallelConfig.uniform(
        4, pp=pp, vpp=vpp, tp=tp, dp_type=dp_type, chunks=chunks,
        mixed_precision="fp32", vocab_tp=1,
    )
    rt = build_runtime(CFG, hp, adam=ADAM, global_batch_size=8, seq_len=32)
    state = rt.init_state(jax.random.key(0))
    batch = make_batch()
    pipe_loss = float(rt.eval_loss(state, batch))
    if vpp > 1:
        flat = unstack_vparams(jax.device_get(state["params"]), CFG, pp, vpp)
    else:
        flat = unstack_params(jax.device_get(state["params"]), CFG, pp)
    ref_loss = float(jax.jit(lambda p, b: modeling.lm_loss(p, b, CFG))(flat, batch))
    np.testing.assert_allclose(pipe_loss, ref_loss, rtol=2e-5, atol=2e-5)


def test_interleaved_training_matches_reference_trajectory():
    from tests._stack_harness import tracks_the_flat_trajectory

    hp = HybridParallelConfig.uniform(
        4, pp=2, vpp=2, tp=1, chunks=2, mixed_precision="fp32", vocab_tp=1
    )
    rt = build_runtime(CFG, hp, adam=ADAM, global_batch_size=8, seq_len=32)
    state = rt.init_state(jax.random.key(0))
    flat = unstack_vparams(jax.device_get(state["params"]), CFG, 2, 2)
    tracks_the_flat_trajectory(rt, state, flat, CFG, [make_batch(seed=i) for i in range(3)], ADAM,
                               tol=2e-4)


def test_interleaved_constraint_errors():
    with pytest.raises(ValueError, match="divisible by pp"):
        HybridParallelConfig.uniform(4, pp=2, vpp=2, chunks=3).validate(8)
    with pytest.raises(ValueError, match="pp\\*vpp"):
        HybridParallelConfig.uniform(6, pp=2, vpp=4, chunks=2).validate(8)
    with pytest.raises(ValueError, match="requires pp>1"):
        HybridParallelConfig.uniform(4, pp=1, vpp=2).validate(8)
    # vpp now composes with pipedream_flush (interleaved 1F1B)
    HybridParallelConfig.uniform(
        4, pp=2, vpp=2, chunks=2, pipeline_type="pipedream_flush"
    ).validate(8)
    # strategies must repeat with period lpvs across virtual stages
    from galvatron_tpu.parallel.pipeline_interleaved import (
        validate_interleaved_strategies,
    )

    hp = HybridParallelConfig(
        pp=2, vpp=2, chunks=2,
        layer_strategies=[
            LayerStrategy(tp=1), LayerStrategy(tp=2),
            LayerStrategy(tp=1), LayerStrategy(tp=1),
        ],
    )
    with pytest.raises(ValueError, match="share one strategy"):
        validate_interleaved_strategies(CFG, hp)


def test_interleaved_cli_roundtrip(tmp_path):
    """vpp survives the strategy JSON codec and the CLI flag path."""
    hp = HybridParallelConfig.uniform(4, pp=2, vpp=2, chunks=4)
    p = str(tmp_path / "c.json")
    hp.save(p)
    hp2 = HybridParallelConfig.load(p)
    assert hp2.vpp == 2 and hp2.pp == 2


def test_interleaved_bf16_trains():
    """bf16 interleaved regression (same XLA:CPU pass workaround as
    test_gpipe_bf16_trains)."""
    cfg = CFG.replace(dtype=jnp.bfloat16)
    hp = HybridParallelConfig.uniform(
        4, pp=2, vpp=2, tp=2, sp=True, dp_type="zero3", chunks=2,
        mixed_precision="bf16", vocab_tp=2,
    )
    rt = build_runtime(cfg, hp, adam=ADAM, global_batch_size=8, seq_len=32)
    state = rt.init_state(jax.random.key(0))
    b = make_batch()
    losses = []
    for _ in range(3):
        state, loss = rt.train_step(state, b)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


@pytest.mark.parametrize(
    "pp,vpp,chunks,tp,dp_type,ckpt",
    [
        (2, 2, 4, 1, "ddp", False),
        (2, 2, 2, 2, "zero3", True),
        (4, 2, 4, 1, "zero2", False),
    ],
)
def test_interleaved_1f1b_loss_parity(pp, vpp, chunks, tp, dp_type, ckpt):
    """vpp + pipedream_flush (interleaved 1F1B, bounded activations): loss
    parity against the flat single-path model on identical weights."""
    L = pp * vpp * 2
    cfg = CFG.replace(num_layers=L)
    hp = HybridParallelConfig.uniform(
        L, pp=pp, tp=tp, dp_type=dp_type, ckpt=ckpt, chunks=chunks,
        vocab_tp=tp, mixed_precision="fp32", pipeline_type="pipedream_flush",
    )
    hp.vpp = vpp
    rt = build_runtime(cfg, hp, adam=ADAM, global_batch_size=8, seq_len=32)
    flat = modeling.init_model_params(jax.random.key(0), cfg)
    state = rt.init_state_from(flat)
    rng = np.random.RandomState(0)
    batch = jnp.asarray(rng.randint(0, 128, (8, 33)), jnp.int32)
    ref = float(jax.jit(lambda p, b: modeling.lm_loss(p, b, cfg))(flat, batch))
    np.testing.assert_allclose(float(rt.eval_loss(state, batch)), ref, rtol=3e-5, atol=3e-5)


def test_interleaved_1f1b_training_matches_flat_trajectory():
    """Two interleaved-1F1B steps track a manual flat AdamW loop — the
    hand-written mirrored backward wave must produce exact gradients."""
    from tests._stack_harness import tracks_the_flat_trajectory

    cfg = CFG.replace(num_layers=8)
    hp = HybridParallelConfig.uniform(
        8, pp=2, tp=1, chunks=4, vocab_tp=1, mixed_precision="fp32",
        pipeline_type="pipedream_flush",
    )
    hp.vpp = 2
    rt = build_runtime(cfg, hp, adam=ADAM, global_batch_size=8, seq_len=32)
    flat = modeling.init_model_params(jax.random.key(1), cfg)
    state = rt.init_state_from(flat)
    batches = [jnp.asarray(np.random.RandomState(i).randint(0, 128, (8, 33)), jnp.int32)
               for i in range(2)]
    tracks_the_flat_trajectory(rt, state, flat, cfg, batches, ADAM)


def test_interleaved_1f1b_bounded_stash_long_chunks():
    """chunks >> pp: the stash stays at min(chunks, 3pp+1) slots — the
    bounded-activation property the gpipe-ordered interleaved lacks."""
    cfg = CFG.replace(num_layers=4)
    hp = HybridParallelConfig.uniform(
        4, pp=2, tp=1, chunks=16, vocab_tp=1, mixed_precision="fp32",
        pipeline_type="pipedream_flush",
    )
    hp.vpp = 2
    rt = build_runtime(cfg, hp, adam=ADAM, global_batch_size=16, seq_len=32)
    flat = modeling.init_model_params(jax.random.key(2), cfg)
    state = rt.init_state_from(flat)
    batch = jnp.asarray(
        np.random.RandomState(3).randint(0, 128, (16, 33)), jnp.int32
    )
    ref = float(jax.jit(lambda p, b: modeling.lm_loss(p, b, cfg))(flat, batch))
    np.testing.assert_allclose(float(rt.eval_loss(state, batch)), ref, rtol=3e-5, atol=3e-5)


@pytest.mark.slow  # four pipeline compiles
def test_interleaved_1f1b_activation_footprint_measured():
    """The 3pp+1 stash bound, MEASURED on the compiled program (VERDICT: the
    bound rode the cost model as an assertion only): XLA's memory analysis of
    the actual train_step shows the interleaved-1F1B temp footprint plateaus
    as chunks grow (stash = min(chunks, 3pp+1) micro-batches), while the
    gpipe-ordered interleaved schedule's autodiff backward grows linearly."""
    from galvatron_tpu.core.checkpoint import abstract_state_of

    cfg = CFG.replace(num_layers=8, hidden_size=128, ffn_dim=256, max_seq_len=128)

    def temp_bytes(ptype, chunks):
        hp = HybridParallelConfig.uniform(
            8, pp=2, chunks=chunks, mixed_precision="fp32", pipeline_type=ptype
        )
        hp.vpp = 2
        rt = build_runtime(
            cfg, hp, adam=ADAM, global_batch_size=4 * chunks, seq_len=128
        )
        batch = jax.ShapeDtypeStruct(
            (4 * chunks, 129), jnp.int32, sharding=rt.batch_sharding
        )
        ma = rt.train_step.lower(abstract_state_of(rt), batch).compile().memory_analysis()
        if ma is None:  # backend without memory analysis (see profiling/model.py)
            pytest.skip("memory_analysis unavailable on this backend")
        return ma.temp_size_in_bytes

    r_1f1b = temp_bytes("pipedream_flush", 16) / temp_bytes("pipedream_flush", 4)
    r_gpipe = temp_bytes("gpipe", 16) / temp_bytes("gpipe", 4)
    # measured on the sim: ~1.38 (batch buffers only) vs ~3.24 (linear-ish)
    assert r_1f1b < 2.0 < r_gpipe, (r_1f1b, r_gpipe)
