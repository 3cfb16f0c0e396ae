"""OLMoE-class layers (dropless softmax top-k MoE, qk-norm) on the normal path,
against the plain reference ``benchmark/references/olmoe.py`` on seeded random
weights, at a small size on the CPU; and tests that fail on the likely mistakes
(weights renormalised over the top-k, q/k normalised per head, a dropped pair,
top-1 in place of top-k)."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference
from galvatron_tpu.core.optim import AdamConfig
from galvatron_tpu.core.strategy import HybridParallelConfig
from galvatron_tpu.models import modeling, moe
from galvatron_tpu.models.modeling import PRESETS
from galvatron_tpu.parallel.hybrid import build_runtime
from galvatron_tpu.parallel.mesh import build_mesh
from tests import _stack_harness as harness
from tests._stack_harness import forward, highest_precision  # noqa: F401  (a fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = reference.load(ROOT, "olmoe")

# float32, the same arithmetic in another summation order (the program sums a
# token's experts after a sort and a grouped GEMM, the reference in expert
# order over a mask; the attention softmax is blocked or not): differences are
# a few float32 ulps of the largest element a sum went through, ~1e-6 of it;
# 1e-5 of a tensor's largest magnitude leaves room for depth 2 and would not
# pass any bf16 intermediate (2^-8 = 4e-3)
F32_TOL = 1e-5
# bf16 compute against the float32 reference: activations carry 8 bits, two
# layers and the head stack a few roundings: 2^-8 x ~8 = 3e-2 of the largest logit
BF16_TOL = 3e-2


def small_cfg(**kw):
    base = dict(vocab_size=96, hidden_size=64, num_layers=2, num_heads=4, ffn_dim=32,
                max_seq_len=32, moe_experts=8, moe_top_k=2, dtype=jnp.float32)
    base.update(kw)
    return PRESETS["olmoe-1b-7b"].replace(**base)


def ref_cfg(cfg, share=None):
    return {"num_attention_heads": cfg.num_heads, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "num_experts_per_tok": cfg.moe_top_k,
            "num_experts": cfg.moe_experts, "hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.ffn, "num_hidden_layers": cfg.num_layers,
            "vocab_size": cfg.vocab_size}


#: every learned scale 0.3 away from its initial 1 (a norm that ignores its scale must
#: show), and rows with the last position's target
seeded = functools.partial(harness.seeded, spread=0.3, targets=True)
#: differences as a share of the largest magnitude alone (gradients far under 1)
close = functools.partial(harness.close, floor=0.0)
pytestmark = pytest.mark.usefixtures("highest_precision")


def ref_logits(params, rows, cfg):
    return harness.reference(ARCH, ref_cfg, cfg).logits(params, rows)


def reference_objective(params, rows, cfg):
    """(cross entropy, auxiliary loss) of the plain reference, float32 highest."""
    return harness.reference(ARCH, ref_cfg, cfg).objective(params, rows)


def test_reference_imports_nothing_of_the_programs_models():
    src = open(os.path.join(ROOT, "benchmark", "references", "olmoe.py")).read()
    assert "galvatron_tpu" not in src


def test_logits_loss_and_aux_loss_match_the_reference_in_float32():
    cfg = small_cfg()
    params, rows = seeded(cfg)
    logits, stats = harness.forward_with_stats(params, rows[:, :-1], cfg)
    rc = ref_cfg(cfg)
    close(logits, ref_logits(params, rows[:, :-1], cfg), F32_TOL)
    s, n, aux = harness.moe_loss_sum(params, rows, cfg)
    ce, aux_ref = reference_objective(params, rows, cfg)
    close(s / n, ce, F32_TOL)
    close(aux["moe_aux_loss"], aux_ref, F32_TOL)
    assert len(stats) == cfg.num_layers
    load = np.asarray(ARCH.expert_load(ARCH.published_weights(params, rc), rows[:, :-1], rc))
    pairs = rows[:, :-1].size * cfg.moe_top_k
    close(aux["moe_load_max_over_mean"], load.max() / (pairs / cfg.moe_experts), F32_TOL)
    assert float(harness.lm_loss(params, rows, cfg)) == pytest.approx(float(s / n), rel=1e-6)


def test_every_gradient_matches_the_reference_in_float32():
    cfg = small_cfg()
    params, rows = seeded(cfg)
    got = harness.every_gradient_matches(params, rows, cfg, harness.reference(ARCH, ref_cfg, cfg),
                                         F32_TOL)
    assert len(jax.tree.leaves(got)) > 20


@pytest.mark.parametrize("chunks", [1, 2])
def test_runtime_differentiates_ce_plus_aux_and_logs_ce_alone(chunks):
    cfg = small_cfg()
    hp = HybridParallelConfig.uniform(cfg.num_layers, mixed_precision="fp32", chunks=chunks)
    adam = AdamConfig(lr=1e-3, grad_clip=None)
    mesh, axes = build_mesh(pp=1, devices=jax.devices()[:1])
    rt = build_runtime(cfg, hp, mesh=mesh, axes=axes, adam=adam, global_batch_size=4,
                       seq_len=cfg.max_seq_len)
    state = rt.init_state(jax.random.key(0))
    assert set(state["moe_stats"]) == {"moe_aux_loss", "moe_load_max_over_mean"}
    params = jax.tree.map(jnp.array, state["params"])
    rows = np.asarray(jax.random.randint(jax.random.key(5), (4, cfg.max_seq_len + 1), 0,
                                         cfg.vocab_size, jnp.int32))
    evaluated = float(rt.eval_loss(state, rt.shard_batch(rows)))
    state, loss = rt.train_step(state, rt.shard_batch(rows))
    # per micro-batch, as the program routes: the reference on each chunk
    parts = [reference_objective(params, jnp.asarray(mb), cfg) for mb in np.split(rows, chunks)]
    ce = np.mean([float(c) for c, _ in parts])
    aux = np.mean([float(a) for _, a in parts])
    assert float(loss) == pytest.approx(ce, rel=F32_TOL)  # the cross entropy alone
    assert evaluated == pytest.approx(float(reference_objective(params, jnp.asarray(rows), cfg)[0]),
                                      rel=F32_TOL)
    assert float(state["moe_stats"]["moe_aux_loss"]) == pytest.approx(aux, rel=F32_TOL)
    assert float(state["moe_stats"]["moe_load_max_over_mean"]) >= 1.0

    def plain(p):
        return sum(c + cfg.moe_aux_coef * a for c, a in
                   (reference_objective(p, jnp.asarray(mb), cfg) for mb in np.split(rows, chunks))
                   ) / chunks

    want = harness.loss_and_gradients(plain, params)[1]
    # Adam's first moment after one step from zero is (1 - b1) x the gradient
    got = jax.tree.map(lambda m: m / (1 - adam.b1), state["opt"]["mu"])
    harness.close_by_leaf(got, want, F32_TOL, floor=0.0)


def test_bf16_compute_stays_within_what_bf16_warrants():
    cfg = small_cfg(dtype=jnp.bfloat16)
    params, rows = seeded(cfg)
    harness.bf16_stays_within(cfg, params, rows[:, :-1], ref_logits, BF16_TOL, F32_TOL)


# -- the likely mistakes ------------------------------------------------------


def identical_experts(cfg, seed=3, router_bias=None):
    """An MoE layer whose experts all hold the same weights: its output is then
    (the sum of a token's combine weights) x FFN(y), so the sum can be read."""
    p = moe.init_moe_params(jax.random.key(seed), cfg)
    for name in ("w1", "w2", "w3"):
        p[name] = jnp.broadcast_to(p[name][:1], p[name].shape)
    p["router"]["w"] = p["router"]["w"] * 8.0  # uneven probabilities, none near 1
    if router_bias is not None:
        p["router"]["w"] = p["router"]["w"] + router_bias
    return p


def combine_weight_sums(cfg, p, x):
    y = moe.moe_topk_block(x, p, cfg)[0].reshape(-1, cfg.hidden_size)
    t = x.reshape(-1, cfg.hidden_size)
    ffn = (jax.nn.silu(t @ p["w1"][0]) * (t @ p["w3"][0])) @ p["w2"][0]
    probs = jax.nn.softmax(t @ p["router"]["w"], axis=-1)
    ratio = jnp.sum(y * ffn, axis=-1) / jnp.sum(ffn * ffn, axis=-1)
    return np.asarray(ratio), np.asarray(jnp.sort(probs, axis=-1)[:, ::-1])


def test_combine_weights_are_the_top_k_probabilities_not_renormalised_not_top_1():
    cfg = small_cfg()
    x = jax.random.normal(jax.random.key(4), (2, 32, cfg.hidden_size), jnp.float32)
    sums, probs = combine_weight_sums(cfg, identical_experts(cfg), x)
    want = probs[:, :cfg.moe_top_k].sum(-1)
    np.testing.assert_allclose(sums, want, rtol=1e-4)
    # the test has power: renormalised weights would sum to 1, top-1 to the largest alone
    assert want.max() < 0.98
    assert (want - probs[:, 0]).min() > 1e-3


def test_no_pair_is_dropped_when_one_expert_is_overloaded():
    cfg = small_cfg()
    x = jax.random.normal(jax.random.key(6), (2, 32, cfg.hidden_size), jnp.float32)
    # every token's mean activation points at expert 0: it is in every top-k
    bias = jnp.zeros((cfg.hidden_size, cfg.moe_experts)).at[:, 0].set(1.0)
    p = identical_experts(cfg, router_bias=bias)
    x = x + 3.0
    stats = moe.moe_topk_block(x, p, cfg)[1]
    tokens = x.shape[0] * x.shape[1]
    load = float(moe.load_max_over_mean([stats], cfg.moe_experts, cfg.moe_top_k))
    assert load >= 3.9, load  # top-2 of 8: one expert in every pair is 4x the even share
    assert float(stats[0][0]) * tokens == tokens  # expert 0 got a pair from every token
    sums, probs = combine_weight_sums(cfg, p, x)
    np.testing.assert_allclose(sums, probs[:, :cfg.moe_top_k].sum(-1), rtol=1e-4)
    # and the pairs an expert got add up to all of them
    assert float(jnp.sum(stats[0])) * tokens == tokens * cfg.moe_top_k


def test_qk_norm_is_over_the_whole_projection_not_per_head():
    cfg = small_cfg(num_layers=1)
    params, _ = seeded(cfg)
    p = params["layers"][0]["attn"]
    # head 0 of q ten times as large as the others: a per-head norm would hide it
    wqkv = p["wqkv"].at[:, 0, : cfg.head_dim].multiply(10.0)
    p = dict(p, wqkv=wqkv)
    x = jax.random.normal(jax.random.key(7), (2, 8, cfg.hidden_size), jnp.float32)
    q, k, _ = modeling.project_qkv_heads(x, p, cfg)
    raw = np.asarray(jnp.einsum("bsh,hd->bsd", x, wqkv[:, 0]), np.float64)
    whole = raw / np.sqrt((raw ** 2).mean(-1, keepdims=True) + cfg.norm_eps) * np.asarray(p["q_norm"])
    heads = raw.reshape(2, 8, cfg.num_heads, cfg.head_dim)
    per_head = (heads / np.sqrt((heads ** 2).mean(-1, keepdims=True) + cfg.norm_eps)
                ).reshape(raw.shape) * np.asarray(p["q_norm"])
    close(np.asarray(q).reshape(raw.shape), whole, F32_TOL)
    assert np.abs(whole - per_head).max() > 0.1 * np.abs(whole).max()


def test_qk_norm_on_the_stacked_flash_path_equals_the_einsum_path():
    """The head-major branch norms the stacked (b, 3, n, s, d) projection; the
    einsum branch norms (b, s, n, d): one model, two layouts."""
    cfg = small_cfg(num_layers=1, hidden_size=256, num_heads=2, max_seq_len=256, ffn_dim=128)
    params, rows = seeded(cfg, batch=1)
    xla = forward(params, rows[:, :-1], cfg)
    flash = forward(params, rows[:, :-1], cfg.replace(attn_impl="flash"))
    close(flash, xla, 1e-4)  # the kernels' own blocked softmax, float32


test_layouts_the_sorted_path_does_not_implement_are_refused_by_name = harness.refuses(
    [(field, {}, {field: 2}, field + ">1") for field in ("ep", "pp", "cp")], small_cfg,
    batch=8, devices=8)


def test_sharded_layouts_train_like_one_device():
    cfg = small_cfg()
    rows = np.asarray(jax.random.randint(jax.random.key(8), (4, cfg.max_seq_len + 1), 0,
                                         cfg.vocab_size, jnp.int32))
    losses = []
    for n, tp, dp_type, sp in ((1, 1, "ddp", False), (2, 1, "ddp", False), (2, 2, "ddp", False),
                               (2, 1, "zero3", False), (4, 2, "zero2", True)):
        mesh, axes = build_mesh(pp=1, devices=jax.devices()[:n])
        hp = HybridParallelConfig.uniform(cfg.num_layers, tp=tp, dp_type=dp_type, sp=sp,
                                          mixed_precision="fp32")
        rt = build_runtime(cfg, hp, mesh=mesh, axes=axes, global_batch_size=4,
                           seq_len=cfg.max_seq_len)
        state = rt.init_state(jax.random.key(0))
        for _ in range(2):
            state, loss = rt.train_step(state, rt.shard_batch(rows))
        losses.append(float(loss))
    # each device routes its own tokens; the loss is the same model's
    assert losses[1:] == pytest.approx(losses[:1] * 4, rel=1e-4)
