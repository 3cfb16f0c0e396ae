"""granite-4.0-h-small-class stacks (Granite 4.0-H with a ROUTED MLP in every layer: a
Mamba-2 mixer of ONE scan group or NoPE GQA attention, then the 10 largest of 72 router
logits under a softmax over those 10 beside a shared SwiGLU expert, every branch times
``residual_multiplier``) on the normal path, against the plain reference
``benchmark/references/granitemoehybrid_moe.py`` on seeded random weights, at a small size on
the CPU: the configuration, the no-cache forward and the gradients, the multipliers, the held
share, the refusals and one-device training. The state through the slot cache and the engine
are tests/test_granite_small_serving.py's (one file = one worker under ``--dist loadfile``)."""

import os

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import reference
from galvatron_tpu.models import generation, modeling, moe, ssm
from galvatron_tpu.models.modeling import PRESETS
from galvatron_tpu.ops import moe_held
from tests import _stack_harness as harness
from tests._stack_harness import close, forward, seeded, worst

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = reference.load(ROOT, "granitemoehybrid_moe")

# float32, the same arithmetic in another order (the program runs the scan in chunks with
# decay-masked score blocks and carries a state, counts the pairs into a layout and runs
# grouped GEMMs, and attends a block of keys at a time; the reference steps the recurrence a
# position at a time, loops over key/value heads and multiplies every held expert). The
# logits are small here (/ 16 over weights of 1/sqrt(fan_in): the largest is 0.04), so every
# comparison is by the largest magnitude itself (``floor=0``): the largest difference read
# over this file's cases is 1e-6 of it; bfloat16 reads 3e-2, a dropped multiplier 1 or more
F32_TOL = 2e-5
CHUNK, SLOT = 4, 64


def small_cfg(**kw):
    """The first 7 published layers (5 Mamba-2, attention at 5, 1 Mamba-2) at small widths:
    8 heads of 4 in ONE scan group, state 8, chunks of 8; 8 experts top-3, all held."""
    base = dict(vocab_size=96, hidden_size=32, num_layers=7, num_heads=4, num_kv_heads=2,
                ffn_dim=24, max_seq_len=SLOT, ssm_heads=8, ssm_head_dim=4, ssm_state=8,
                ssm_chunk=8, moe_experts=8, moe_top_k=3, moe_ffn_dim=24, moe_shared_ffn_dim=40,
                dtype=jnp.float32)
    base.update(kw)
    return PRESETS["granite-4.0-h-small"].replace(**base)


def ref_cfg(cfg, share=None):
    rank, of = share or cfg.moe_share
    return {"hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.kv_heads, "rms_norm_eps": cfg.norm_eps,
            "layer_types": ["attention" if k == "attention" else "mamba" for k in cfg.kinds],
            "num_hidden_layers": cfg.num_layers, "mamba_n_heads": cfg.ssm_heads,
            "mamba_d_head": cfg.ssm_head_dim, "mamba_n_groups": cfg.ssm_groups,
            "mamba_d_state": cfg.ssm_state, "mamba_d_conv": cfg.ssm_conv,
            "intermediate_size": cfg.expert_ffn,
            "shared_intermediate_size": cfg.moe_shared_ffn_dim,
            "num_local_experts": cfg.moe_experts // of, "num_experts_per_tok": cfg.moe_top_k,
            "attention_multiplier": cfg.attention_multiplier,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier, "logits_scaling": cfg.logits_scaling,
            "vocab_size": cfg.vocab_size, "expert_share": {"rank": rank, "of": of}}


def ref_logits(params, rows, cfg, share=None):
    return harness.reference(ARCH, ref_cfg, cfg, share).logits(params, jnp.asarray(rows))


# -- the configuration ------------------------------------------------------------------


def test_preset_runs_the_published_widths():
    cfg = PRESETS["granite-4.0-h-small"]
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (
        4096, 40, 32, 8, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv,
            cfg.ssm_chunk) == (128, 64, 128, 1, 4, 256)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.expert_ffn, cfg.moe_shared_ffn_dim, cfg.ffn) == (
        72, 10, 768, 1536, 768)
    assert cfg.moe_router == "softmax_topk" and cfg.moe_norm_topk and not cfg.moe_shared_gate
    assert cfg.act_fn == "swiglu" and not moe.ungated(cfg) and cfg.moe_dropless
    assert (cfg.attention_multiplier, cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (0.0078125, 12.0, 0.22, 16.0)
    assert cfg.pos_embed == "nope" and cfg.tie_word_embeddings and cfg.norm_eps == 1e-5
    assert (cfg.vocab_size, cfg.max_seq_len) == (100352, 131072)
    assert [i for i, k in enumerate(cfg.kinds) if k == "attention"] == [5, 15, 25, 35]
    assert ssm.ssm_dims(cfg) == (8192, 8448, 16768)
    assert ssm.state_part_bytes(cfg) == {"conv": 3 * 8448 * 2, "scan": 4 * 2**20}
    cut = cfg.replace(num_layers=10)
    assert generation.stack_layers(cut) == {"full": 1, "window": 0, "state": 9}
    from galvatron_tpu.models import granite

    assert granite.SIZES == ("granite-4.0-h-micro", "granite-4.0-h-small")


def test_parameter_counts_are_the_issues_arithmetic():
    cfg = PRESETS["granite-4.0-h-small"]
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    sizes = [sum(a.size for a in jax.tree.leaves(layer)) for layer in shapes["layers"]]
    mamba = 4096 * 16768 + 8448 * 5 + 3 * 128 + 8192 + 8192 * 4096 + 2 * 4096
    attn = 4096 * (4096 + 1024 + 1024) + 4096 * 4096 + 2 * 4096
    mlp = 4096 * 72 + 72 * 3 * 4096 * 768 + 3 * 4096 * 1536
    assert sizes[0] == mamba + mlp and sizes[5] == attn + mlp
    total = sum(a.size for a in jax.tree.leaves(shapes))
    assert total == 36 * mamba + 4 * attn + 40 * mlp + 100352 * 4096 + 4096
    assert round(total / 1e9, 1) == 32.2  # "32B-A9B"
    m = shapes["layers"][0]["mlp"]
    assert m["w1"].shape == m["w3"].shape == (72, 4096, 768) and m["w2"].shape == (72, 768, 4096)
    assert m["shared"]["w13"].shape == (4096, 3072) and "gate" not in m["shared"]
    # the cut of `benchmark/configs/granite-4.0-h-small.json`: one period, 36 held experts, half the vocabulary
    cut = cfg.replace(num_layers=10, vocab_size=50176, moe_share=(0, 2))
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cut), jax.random.key(0))
    total = sum(a.size for a in jax.tree.leaves(shapes))
    assert round(total / 1e9, 3) == 4.757
    from galvatron_tpu.search import theoretical as th

    assert th.total_param_count(cut) == total


def test_the_reference_holds_the_layer_types():
    cfg = small_cfg()
    params, _ = seeded(cfg)
    rc = ref_cfg(cfg)
    w = ARCH.published_weights(params, rc)
    assert ARCH.kinds(rc) == ["mamba"] * 5 + ["attention", "mamba"] and len(w["layers"]) == 7
    with pytest.raises(ValueError, match="the program's layers are"):
        ARCH.published_weights(params, dict(rc, layer_types=["mamba"] * 7))


# -- the forward against the reference -----------------------------------------------------


@pytest.mark.parametrize("share", [(0, 1), (1, 2)])
def test_no_cache_forward_matches_the_reference(share):
    cfg = small_cfg(moe_share=share)
    params, rows = seeded(cfg, length=40)
    close(forward(params, rows, cfg), ref_logits(params, rows, cfg), F32_TOL, floor=0.0)


def test_bf16_in_place_of_float32_fails_the_tolerance():
    cfg = small_cfg()
    params, rows = seeded(cfg, length=40)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a, params)
    got = forward(low, rows, cfg.replace(dtype=jnp.bfloat16))
    assert worst(got.astype(jnp.float32), ref_logits(params, rows, cfg), 0.0) > 100 * F32_TOL


@pytest.mark.parametrize("field,value", [
    ("residual_multiplier", 1.0), ("logits_scaling", 1.0), ("embedding_multiplier", 1.0),
    ("attention_multiplier", 4 ** -0.5), ("moe_norm_topk", False)])
def test_a_dropped_multiplier_fails_the_tolerance(field, value):
    """Each of Granite's four scalars, and the softmax over the chosen 10, is in the
    forward: the preset without it lands far outside the tolerance."""
    cfg = small_cfg()
    params, rows = seeded(cfg, length=40)
    got = forward(params, rows, cfg.replace(**{field: value}))
    assert worst(got, ref_logits(params, rows, cfg), 0.0) > 1000 * F32_TOL


def test_every_gradient_and_the_loss_match_the_reference():
    """The training path of the same preset: the cross entropy and its gradient by every
    parameter (the auxiliary coefficient is 0: the objective is the cross entropy)."""
    cfg = small_cfg(max_seq_len=24)
    assert cfg.moe_aux_coef == 0.0
    params, rows = seeded(cfg, length=24, targets=True)
    ref = harness.reference(ARCH, ref_cfg, cfg)
    loss, got = harness.loss_and_gradients(
        lambda p: modeling.moe_loss_sum(p, rows, cfg)[0] / (rows.shape[0] * 24), params)
    want_loss, want = harness.loss_and_gradients(lambda p: ref.objective(p, rows)[0], params)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for path, w in jax.tree.leaves_with_path(want):
        assert float(jnp.abs(w).max()) > 0, jax.tree_util.keystr(path)
    harness.close_by_leaf(got, want, 2e-4, floor=0.0)


# -- the model's own mechanisms -------------------------------------------------------------


def test_one_token_through_the_expert_layer_by_hand():
    """The 3 largest router logits, a softmax over those 3, ``down(silu(gate x) * up x)`` of
    the chosen experts, plus the shared SwiGLU expert as it is."""
    cfg = small_cfg()
    params, _ = seeded(cfg)
    mlp = params["layers"][0]["mlp"]
    x = jax.random.normal(jax.random.key(6), (1, 3, cfg.hidden_size))
    got = moe.moe_topk_block(x, mlp, cfg)[0]
    xt = x.reshape(3, -1)
    top, idx = jax.lax.top_k(xt @ mlp["router"]["w"], 3)
    gu = xt @ mlp["shared"]["w13"]
    want = (jax.nn.silu(gu[:, :40]) * gu[:, 40:]) @ mlp["shared"]["w2"]
    for t in range(3):
        for e, g in zip(idx[t], jax.nn.softmax(top[t])):
            mid = jax.nn.silu(xt[t] @ mlp["w1"][e]) * (xt[t] @ mlp["w3"][e])
            want = want.at[t].add(g * (mid @ mlp["w2"][e]))
    close(got[0], want, 1e-5, floor=0.0)
    with jax.default_matmul_precision("highest"):
        rc = ref_cfg(cfg)
        fw = ARCH.published_weights(params, rc)["layers"][0]
        close(got, ARCH.experts(x, fw, rc), 1e-5, floor=0.0)


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """THE SHARE: the routed parts of ranks 0/2 and 1/2 plus the shared expert counted ONCE
    add up to what the uncut reference gives for the whole layer; one rank's part is the
    reference's at that rank."""
    whole = small_cfg()
    params, _ = seeded(whole)
    mlp = params["layers"][0]["mlp"]
    y = jax.random.normal(jax.random.key(5), (2, 24, whole.hidden_size))
    want = moe.moe_topk_block(y, mlp, whole)[0]
    shared = moe._shared_expert(y.reshape(-1, whole.hidden_size), mlp["shared"]).reshape(y.shape)
    total = shared
    for rank in range(2):
        mine = dict(mlp, **{k: mlp[k][rank * 4:(rank + 1) * 4] for k in ("w1", "w2", "w3")})
        total = total + moe.moe_topk_block(y, mine, whole.replace(moe_share=(rank, 2)))[0] - shared
    close(total, want, F32_TOL, floor=0.0)
    rc = ref_cfg(whole)
    fw = ARCH.published_weights(params, rc)["layers"][0]
    with jax.default_matmul_precision("highest"):
        close(want[:1], ARCH.experts(y[:1], fw, rc), F32_TOL, floor=0.0)
        part = dict(fw, **{k: fw[k][4:8] for k in ("experts_gate", "experts_up", "experts_output")})
        mine = dict(mlp, **{k: mlp[k][4:8] for k in ("w1", "w2", "w3")})
        close(moe.moe_topk_block(y, mine, whole.replace(moe_share=(1, 2)))[0][:1],
              ARCH.experts(y[:1], part, ref_cfg(whole, (1, 2))), F32_TOL, floor=0.0)


def test_the_published_shapes_take_the_bounded_held_path():
    """36 of 72 held (neither a power of two), top-10, width 768 = 6 lane tiles on a hidden
    of 4096: the bounded body, the row tile the shape's own."""
    assert moe_held.held_path(4096, 768, jnp.bfloat16) == "bounded"
    cut = PRESETS["granite-4.0-h-small"].replace(num_layers=10, moe_share=(0, 2))
    assert (cut.moe_held, cut.moe_first_held) == (36, 0)
    assert moe.held_path_counts(cut) == {"bounded": 10, "worst_case": 0}
    # 32 tokens x 10 / 72 = 4.4 rows an expert, 1024 x 10 / 72 = 142
    assert moe.layer_row_tile(cut, 32) == 16 and moe.layer_row_tile(cut, 1024) == 128


# -- training ------------------------------------------------------------------------------


REFUSALS = [
    ("tp", {}, dict(tp=2), r"tensor parallelism \(tp>1\) is not implemented for state-space "
     "layers"),
    ("cp", {}, dict(cp=2), r"context parallelism \(cp>1\) is not implemented for a stack with "
     "state-space layers"),
    ("pack", dict(pack_sequences=True), {}, "pack_sequences is not implemented for state-space "
     "layers: the conv and the scan do not reset their state at segment boundaries"),
    ("pp", {}, dict(pp=2), r"pipeline parallelism \(pp>1\) over interleaved layer kinds is not "
     "implemented"),
    ("ep", {}, dict(ep=2), r"expert parallelism \(ep>1\)"),
]


test_build_runtime_refuses_by_name = harness.refuses(REFUSALS, small_cfg)


def test_the_runtime_trains_it_on_one_device():
    # (logits / 16 and branches x 0.22 make small gradients: 0.04 of loss in 8 steps, read)
    harness.trains_on_one_device(small_cfg(max_seq_len=32), steps=8, drop=0.03)
