"""Pipeline-parallel parity tests (build plan step 6).

Methodology: initialize the pipeline state, unstack the stage-stacked params
into the flat layers list, and run the plain single-device forward on the same
tokens — losses must agree (the reference's check_loss contract applied to the
pipeline engine, SURVEY §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models import modeling
from galvatron_tpu.parallel.hybrid import build_runtime

from tests._train_common import ADAM, CFG, make_batch, unstack_params


@pytest.mark.parametrize(
    "pp,chunks,tp,dp_type,ckpt",
    [
        (2, 2, 1, "ddp", False),
        (2, 4, 2, "ddp", False),
        (4, 4, 1, "zero3", True),
        (2, 2, 2, "zero2", False),
    ],
)
def test_gpipe_loss_parity(pp, chunks, tp, dp_type, ckpt):
    hp = HybridParallelConfig.uniform(
        4, pp=pp, tp=tp, dp_type=dp_type, ckpt=ckpt,
        chunks=chunks, mixed_precision="fp32", vocab_tp=tp, pipeline_type="gpipe",
    )
    rt = build_runtime(CFG, hp, adam=ADAM, global_batch_size=8, seq_len=32)
    state = rt.init_state(jax.random.key(0))
    batch = make_batch()
    flat = unstack_params(state["params"], CFG, pp)
    ref_loss = float(jax.jit(lambda p, b: modeling.lm_loss(p, b, CFG))(flat, batch))
    loss = float(rt.eval_loss(state, batch))
    np.testing.assert_allclose(loss, ref_loss, rtol=2e-5, atol=2e-5)


def test_gpipe_training_matches_reference_trajectory():
    """Train 3 steps with pp=2 and compare each step's loss against a manual
    single-device AdamW loop starting from the identical (unstacked) params."""
    from tests._stack_harness import tracks_the_flat_trajectory

    pp, chunks = 2, 2
    hp = HybridParallelConfig.uniform(
        4, pp=pp, tp=1, chunks=chunks, mixed_precision="fp32", vocab_tp=1
    )
    rt = build_runtime(CFG, hp, adam=ADAM, global_batch_size=8, seq_len=32)
    state = rt.init_state(jax.random.key(0))
    flat = jax.tree.map(jnp.asarray, unstack_params(state["params"], CFG, pp))
    tracks_the_flat_trajectory(rt, state, flat, CFG, [make_batch(seed=i) for i in range(3)], ADAM)


def test_pipeline_stage_param_placement():
    hp = HybridParallelConfig.uniform(
        4, pp=2, tp=2, dp_type="zero3", chunks=2, mixed_precision="fp32", vocab_tp=2
    )
    rt = build_runtime(CFG, hp, adam=ADAM, global_batch_size=8, seq_len=32)
    state = rt.init_state(jax.random.key(0))
    wq = state["params"]["stages"][0]["attn"]["wqkv"]
    assert wq.shape[0] == 2  # stacked over stages
    assert wq.sharding.spec[0] == "pp"
    assert wq.sharding.spec[3] in ("x1", ("x1",))  # tp on the per-slot head dim
    assert wq.sharding.spec[1] in ("x0", ("x0",))  # zero3 on in dim


def test_pipeline_rejects_invalid_division():
    # ragged divisions are supported (padded stacking, test_pipeline_uneven);
    # a division that does not cover the layer count is not
    hp = HybridParallelConfig.uniform(5, pp=2, chunks=2, mixed_precision="fp32")
    hp.pp_division = [1, 3]
    cfg = CFG.replace(num_layers=5)
    with pytest.raises(ValueError, match="sum"):
        build_runtime(cfg, hp, adam=ADAM, global_batch_size=8, seq_len=32)


def test_pipeline_rejects_cross_stage_heterogeneity():
    strategies = [
        LayerStrategy(tp=1),
        LayerStrategy(tp=2),
        LayerStrategy(tp=2),  # position 0 of stage 1 ≠ position 0 of stage 0
        LayerStrategy(tp=2),
    ]
    hp = HybridParallelConfig(pp=2, layer_strategies=strategies, chunks=2, mixed_precision="fp32")
    with pytest.raises(ValueError, match="share one strategy"):
        build_runtime(CFG, hp, adam=ADAM, global_batch_size=8, seq_len=32)


def test_gpipe_bf16_trains():
    """bf16 pipeline backward regression: XLA:CPU's all-reduce-promotion pass
    aborts on sub-f32 pipeline backwards (copy-reduction all-reduce,
    hlo_instruction.cc:1585); cpu_sim_compiler_options disables it per-compile
    so mixed-precision pipelines are testable on the CPU sim."""
    import jax.numpy as jnp_

    cfg = CFG.replace(dtype=jnp_.bfloat16)
    hp = HybridParallelConfig.uniform(
        4, pp=2, tp=2, dp_type="zero3", chunks=2, mixed_precision="bf16", vocab_tp=2
    )
    rt = build_runtime(cfg, hp, adam=ADAM, global_batch_size=8, seq_len=32)
    state = rt.init_state(jax.random.key(0))
    b = make_batch()
    losses = []
    for _ in range(3):
        state, loss = rt.train_step(state, b)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
