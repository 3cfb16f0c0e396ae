"""End-to-end loss-parity tests for the pp=1 hybrid runtime (build plan 3-5).

Mirrors the reference's `--check_loss` methodology (SURVEY §4): every hybrid
strategy must reproduce the single-device loss trajectory. fp32 throughout for
tight tolerances."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.core.optim import AdamConfig
from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models import modeling
from galvatron_tpu.parallel.hybrid import build_runtime

from tests._train_common import ADAM, CFG

GPT_CFG = CFG.replace(
    pos_embed="learned", norm_type="layernorm", act_fn="gelu", tie_word_embeddings=True
)
STEPS = 3


def make_batches(seed=0, n=STEPS, batch=8, seq=32, vocab=128):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randint(0, vocab, (batch, seq + 1)), jnp.int32) for _ in range(n)]


def reference_losses(cfg, batches):
    """Single-device fp32 training loop (the reference's train.py baseline,
    models/llama_hf/train.py:21-74)."""
    from tests._stack_harness import flat_losses

    return flat_losses(cfg, modeling.init_model_params(jax.random.key(0), cfg), batches, ADAM)


def run_hybrid(cfg, hp, batches):
    rt = build_runtime(cfg, hp, adam=ADAM, global_batch_size=8, seq_len=32)
    state = rt.init_state(jax.random.key(0))
    losses = []
    for b in batches:
        state, loss = rt.train_step(state, b)
        losses.append(float(loss))
    return losses


@pytest.fixture(scope="module")
def ref():
    batches = make_batches()
    return batches, reference_losses(CFG, batches)


STRATEGIES = {
    "pure_dp": HybridParallelConfig.uniform(4, tp=1, mixed_precision="fp32", vocab_tp=1),
    "tp2": HybridParallelConfig.uniform(4, tp=2, mixed_precision="fp32", vocab_tp=2),
    "tp4_sp": HybridParallelConfig.uniform(4, tp=4, sp=True, mixed_precision="fp32", vocab_tp=4),
    "tp2_strided": HybridParallelConfig.uniform(
        4, tp=2, tp_consec=False, mixed_precision="fp32", vocab_tp=1
    ),
    "zero3": HybridParallelConfig.uniform(
        4, tp=1, dp_type="zero3", mixed_precision="fp32", vocab_tp=1, embed_dp_type="zero3"
    ),
    "zero2": HybridParallelConfig.uniform(
        4, tp=1, dp_type="zero2", mixed_precision="fp32", vocab_tp=1
    ),
    "ckpt": HybridParallelConfig.uniform(4, tp=2, ckpt=True, mixed_precision="fp32", vocab_tp=2),
    "ckpt_selective": HybridParallelConfig.uniform(
        4, tp=2, ckpt="selective", mixed_precision="fp32", vocab_tp=2
    ),
    "accum2": HybridParallelConfig.uniform(4, tp=1, mixed_precision="fp32", vocab_tp=1, chunks=2),
    "hetero": HybridParallelConfig(
        pp=1,
        layer_strategies=[
            LayerStrategy(tp=1, dp_type="zero3"),
            LayerStrategy(tp=2, dp_type="ddp", ckpt=True),
            LayerStrategy(tp=4, sp=True, dp_type="ddp"),
            LayerStrategy(tp=2, tp_consec=False, dp_type="zero2"),
        ],
        vocab_tp=2,
        mixed_precision="fp32",
    ),
}


# bf16 compute: the plan and the single-device loop both run in bf16 and must
# agree to one bf16 ulp of the loss — the tolerance chip_smoke.py holds its
# multi-chip plans to on the chip
BF16_STRATEGIES = {
    "bf16_tp2_zero3_sp": HybridParallelConfig.uniform(
        4, tp=2, sp=True, dp_type="zero3", mixed_precision="bf16", vocab_tp=2
    ),
}


@pytest.mark.parametrize("name", list(STRATEGIES) + list(BF16_STRATEGIES))
def test_loss_parity(name, ref):
    batches, ref_losses = ref
    if name in BF16_STRATEGIES:
        from chip_smoke import BF16_LOSS_TOL

        cfg = CFG.replace(dtype=jnp.bfloat16)
        np.testing.assert_allclose(
            run_hybrid(cfg, BF16_STRATEGIES[name], batches),
            reference_losses(cfg, batches), **BF16_LOSS_TOL,
        )
        return
    losses = run_hybrid(CFG, STRATEGIES[name], batches)
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4, atol=2e-4)


def test_gpt_family_parity():
    batches = make_batches(seed=1)
    ref_losses = reference_losses(GPT_CFG, batches)
    hp = HybridParallelConfig.uniform(4, tp=2, mixed_precision="fp32", vocab_tp=2)
    losses = run_hybrid(GPT_CFG, hp, batches)
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4, atol=2e-4)


def test_accum_matches_unchunked_with_uneven_masks():
    """Gradient accumulation must reproduce the global token-mean even when
    ignore_index tokens are unevenly split across micro-batches."""
    batch = make_batches(seed=3, n=1)[0]
    # mask out most labels in the first half of the batch (first microbatch)
    batch = batch.at[:4, 1:25].set(-100)
    hp1 = HybridParallelConfig.uniform(4, tp=1, mixed_precision="fp32", vocab_tp=1, chunks=1)
    hp2 = HybridParallelConfig.uniform(4, tp=1, mixed_precision="fp32", vocab_tp=1, chunks=2)
    l1 = run_hybrid(CFG, hp1, [batch] * 2)
    l2 = run_hybrid(CFG, hp2, [batch] * 2)
    np.testing.assert_allclose(l1, l2, rtol=2e-5, atol=2e-5)


def test_training_memorizes_fixed_batch():
    """Real learning signal: repeated batch loss must drop substantially."""
    hp = HybridParallelConfig.uniform(4, tp=2, dp_type="zero3", mixed_precision="fp32", vocab_tp=2)
    rt = build_runtime(CFG, hp, adam=AdamConfig(lr=3e-3), global_batch_size=8, seq_len=32)
    state = rt.init_state(jax.random.key(0))
    batch = make_batches(seed=2, n=1)[0]
    first = None
    for _ in range(15):
        state, loss = rt.train_step(state, batch)
        first = first if first is not None else float(loss)
    assert float(loss) < first - 1.0, (first, float(loss))


def test_param_shardings_applied():
    hp = STRATEGIES["hetero"]
    rt = build_runtime(CFG, hp, adam=ADAM, global_batch_size=8, seq_len=32)
    state = rt.init_state(jax.random.key(0))
    # layer 0: zero3 → wq sharded over all data axes on dim 0
    wq0 = state["params"]["layers"][0]["attn"]["wqkv"]
    assert wq0.sharding.spec[0] == ("x0", "x1", "x2")
    # layer 2: tp4 → wq sharded over 2 tp axes on the per-slot head dim
    wq2 = state["params"]["layers"][2]["attn"]["wqkv"]
    assert wq2.sharding.spec[2] == ("x1", "x2")
    # layer 3: zero2 → param replicated, opt state sharded
    wq3 = state["params"]["layers"][3]["attn"]["wqkv"]
    assert wq3.sharding.spec[0] is None
    mu3 = state["opt"]["mu"]["layers"][3]["attn"]["wqkv"]
    assert mu3.sharding.spec[0] is not None


def test_shard_batch_places_global_batch():
    """rt.shard_batch device_puts with the batch sharding (single-process
    path; the multi-host path uses the same sharding via
    make_array_from_callback)."""
    import numpy as np_

    hp = HybridParallelConfig.uniform(4, tp=1, mixed_precision="fp32")
    rt = build_runtime(CFG, hp, adam=ADAM, global_batch_size=8, seq_len=32)
    b = np_.zeros((8, 33), np_.int32)
    arr = rt.shard_batch(b)
    assert arr.sharding == rt.batch_sharding
    assert arr.shape == (8, 33)


# --- mlp_recompute (activation-memory policy) parity ------------------------
# The saveable policy replays the SAME deterministic ops in the backward
# (norm statistics, silu·gate / gelu product, the cross-entropy cast), so
# gradients must match the no-recompute graph to reduction-order noise.
# DESIGN.md "Activation memory accounting".


def _loss_and_grads(cfg, batch):
    from tests._stack_harness import loss_and_gradients

    loss, grads = loss_and_gradients(lambda p: modeling.lm_loss(p, batch, cfg),
                                     modeling.init_model_params(jax.random.key(0), cfg))
    return float(loss), grads


@pytest.mark.parametrize("family_cfg", [CFG, GPT_CFG], ids=["swiglu", "gelu"])
def test_mlp_recompute_gradient_parity(family_cfg):
    """policy/gate gradients == off gradients, swiglu AND gelu families
    (atol pinned at fp32 reduction-order noise)."""
    batch = make_batches(seed=7, n=1)[0]
    base = family_cfg.replace(mlp_recompute="off")
    loss_off, g_off = _loss_and_grads(base, batch)
    for mode in ("gate", "policy"):
        loss_m, g_m = _loss_and_grads(base.replace(mlp_recompute=mode), batch)
        assert loss_m == pytest.approx(loss_off, abs=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
            g_off, g_m,
        )


def test_mlp_recompute_parity_under_selective_ckpt():
    """The policy composes with the 'selective' attention-core recompute:
    loss trajectories with policy on vs off are identical through the full
    hybrid runtime (tp2 + selective, fp32)."""
    batches = make_batches(seed=8)
    def run(mode):
        hp = HybridParallelConfig.uniform(
            4, tp=2, ckpt="selective", mixed_precision="fp32", vocab_tp=2,
            mlp_recompute=mode,
        )
        return run_hybrid(CFG, hp, batches)
    np.testing.assert_allclose(run("off"), run("policy"), rtol=2e-5, atol=2e-5)


def test_mlp_recompute_parity_in_pipeline_schedule():
    """The policy threads through the pipeline engines (build_runtime rides
    it on cfg): pp=2 1F1B loss trajectories with policy on vs off match."""
    batches = make_batches(seed=9, n=2)
    def run(mode):
        hp = HybridParallelConfig.uniform(
            4, pp=2, tp=1, chunks=2, pipeline_type="pipedream_flush",
            mixed_precision="fp32", vocab_tp=1, mlp_recompute=mode,
        )
        return run_hybrid(CFG, hp, batches)
    np.testing.assert_allclose(run("off"), run("policy"), rtol=2e-5, atol=2e-5)


def test_mlp_recompute_full_remat_still_wins():
    """ckpt='full' layers drop the nested policy (hybrid hook sets
    mlp_recompute='off' inside the remat region): the policy-on trajectory
    equals the policy-off one through the same remat'd runtime."""
    batches = make_batches(seed=10, n=2)
    def run(mode):
        hp = HybridParallelConfig.uniform(
            4, tp=2, ckpt=True, mixed_precision="fp32", vocab_tp=2,
            mlp_recompute=mode,
        )
        return run_hybrid(CFG, hp, batches)
    np.testing.assert_allclose(run("off"), run("policy"), rtol=2e-5, atol=2e-5)
