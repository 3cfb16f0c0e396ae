"""Qwen3-Next-class layers (Gated DeltaNet mixers beside gated partial-RoPE
attention, zero-centred norms, a renormalised top-k expert layer that holds a
share of its experts beside a gated shared expert) on the normal path, against
the plain reference ``benchmark/references/qwen3_next.py`` on seeded random
weights, at a small size on the CPU; the chunked gated delta rule against the
recurrent form; the shares of the experts adding up to the uncut layer; and each
refusal by name."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference
from galvatron_tpu.core.optim import AdamConfig
from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models import gdn, modeling, moe
from galvatron_tpu.models.modeling import PRESETS
from galvatron_tpu.ops.gated_delta import gated_delta_chunked
from galvatron_tpu.parallel.hybrid import build_runtime
from galvatron_tpu.parallel.mesh import build_mesh
from tests import _stack_harness as harness
from tests._stack_harness import forward, highest_precision  # noqa: F401  (a fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = reference.load(ROOT, "qwen3_next")

# float32, the same arithmetic in another order (the program solves a chunk's
# triangular system and carries a state a chunk, the reference steps a token at a
# time; the experts after a sort and a grouped GEMM against a masked loop): a few
# float32 ulps of the largest element a sum went through, as tests/test_olmoe.py
F32_TOL = 5e-5
# bf16 compute against float32 ON THE SAME INPUT, one block: the delta rule's
# operands carry 8 bits into a triangular solve and a state carried over chunks
# (measured 2-10% of the largest output or gradient at these head sizes of 8), attention and
# the norms a few roundings (under 1%). Through the whole small model a rounding
# moves a top-k choice at some token (16 near-uniform probabilities) and the
# normalised mixers pass the change on, so single logits stray far: there the
# loss (a mean) is held to 1e-2 and the MEDIAN position's logits to BF16_TOL
BF16_TOL = 1.5e-1


def small_cfg(**kw):
    """One period (linear, linear, linear, full) at small widths, rank 1 of 4
    holding experts 4-7 of 16."""
    base = dict(vocab_size=96, hidden_size=32, num_layers=4, num_heads=4, num_kv_heads=2,
                attn_head_dim=16, ffn_dim=80, max_seq_len=100, gdn_key_heads=2,
                gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8, moe_experts=16, moe_top_k=4,
                moe_ffn_dim=24, moe_shared_ffn_dim=24, moe_share=(1, 4), dtype=jnp.float32)
    base.update(kw)
    return PRESETS["qwen3-next-80b-a3b"].replace(**base)


def ref_cfg(cfg, share=None):
    rank, of = share or cfg.moe_share
    return {"hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
            "partial_rotary_factor": cfg.rotary_fraction, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.norm_eps, "linear_num_key_heads": cfg.gdn_key_heads,
            "linear_num_value_heads": cfg.gdn_value_heads,
            "linear_key_head_dim": cfg.gdn_key_dim, "linear_value_head_dim": cfg.gdn_value_dim,
            "linear_conv_kernel_dim": cfg.gdn_conv, "full_attention_interval": 4,
            "num_hidden_layers": cfg.num_layers, "vocab_size": cfg.vocab_size,
            "moe_intermediate_size": cfg.expert_ffn,
            "shared_expert_intermediate_size": cfg.moe_shared_ffn_dim,
            "num_experts": cfg.moe_experts // of, "num_experts_per_tok": cfg.moe_top_k,
            "norm_topk_prob": cfg.moe_norm_topk, "published": {"num_experts": cfg.moe_experts},
            "expert_share": {"rank": rank, "of": of}}


#: rows with the last position's target
seeded = functools.partial(harness.seeded, targets=True)
#: differences as a share of the largest magnitude alone (gradients and blocks far under 1)
close = functools.partial(harness.close, floor=0.0)
pytestmark = pytest.mark.usefixtures("highest_precision")


def ref_logits(params, rows, cfg):
    return harness.reference(ARCH, ref_cfg, cfg).logits(params, rows)


def reference_objective(params, rows, cfg):
    return harness.reference(ARCH, ref_cfg, cfg).objective(params, rows)


# -- the preset, the reference file ---------------------------------------------


def test_reference_is_plain_and_recurrent():
    src = open(os.path.join(ROOT, "benchmark", "references", "qwen3_next.py")).read()
    assert "galvatron_tpu" not in src and "solve_triangular" not in src
    assert "jax.lax.scan(step" in src  # one position at a time


def test_preset_runs_the_published_widths():
    p = PRESETS["qwen3-next-80b-a3b"]
    assert (p.hidden_size, p.num_layers, p.num_heads, p.kv_heads, p.head_dim, p.rotary_dim,
            p.ffn, p.expert_ffn, p.moe_shared_ffn_dim, p.moe_experts, p.moe_top_k,
            p.vocab_size, p.rope_theta, p.norm_eps, p.tie_word_embeddings) == (
        2048, 48, 16, 2, 256, 64, 5120, 512, 512, 512, 10, 151936, 1e7, 1e-6, False)
    assert (p.gdn_key_heads, p.gdn_value_heads, p.gdn_key_dim, p.gdn_value_dim, p.gdn_conv,
            p.gdn_chunk) == (16, 32, 128, 128, 4, 64)
    assert [i for i, k in enumerate(p.kinds) if k == "attention"] == list(range(3, 48, 4))
    assert set(p.kinds) == {"attention", "gdn"} and p.moe_held == 512 and p.moe_norm_topk
    # head_dim is its own field only here, where latent attention gives the query a
    # head of its own (tests/test_mla.py) and in smallthinker-21b-a3b (28 heads of 128
    # on a hidden size of 2560: tests/test_smallthinker.py) and trinity-large-preview (48
    # heads of 128 on 3072: tests/test_trinity.py) and nemotron-3-nano-30b-a3b (32 heads of
    # 128 on 2688: tests/test_nemotron.py): every other preset keeps hidden / heads
    own_head = ("smallthinker-21b-a3b", "trinity-large-preview", "nemotron-3-nano-30b-a3b")
    for name, other in PRESETS.items():
        if name != "qwen3-next-80b-a3b" and not other.mla_kv_rank:
            assert (other.attn_head_dim is None) == (name not in own_head)
            assert other.rotary_dim == other.head_dim
            # (lfm2-24b-a2b's two leading dense layers have a width of their own, 11776
            # beside experts of 1536: tests/test_lfm2.py)
            assert other.expert_ffn == other.ffn or other.moe_dense_layers
            assert other.moe_held == other.moe_experts


def test_parameter_counts_are_the_configuration_files():
    from galvatron_tpu.search import theoretical as th

    cfg = PRESETS["qwen3-next-80b-a3b"].replace(num_layers=4, vocab_size=18992, moe_share=(0, 16))
    assert gdn.param_count(cfg) == 33_718_464
    assert th.layer_param_count(cfg, kind="gdn") == 138_582_208
    assert th.layer_param_count(cfg, kind="attention") == 132_127_232
    assert th.total_param_count(cfg) == 625_667_136
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == 625_667_136
    assert shapes["layers"][0]["mlp"]["w1"].shape == (32, 2048, 512)
    assert shapes["layers"][0]["mlp"]["router"]["w"].shape == (2048, 512)
    assert shapes["layers"][3]["attn"]["wqkv"].shape == (2048, 2 * 10 * 256)
    notes = modeling.model_annotations(cfg)
    is_note = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree.structure(shapes) == jax.tree.structure(
        jax.tree.map(lambda _: 0, notes, is_leaf=is_note))
    for a, note in zip(jax.tree.leaves(shapes), jax.tree.leaves(notes, is_leaf=is_note)):
        assert len(note) == len(a.shape)


def test_initialisation_is_the_published_codes():
    cfg = small_cfg()
    params = modeling.init_model_params(jax.random.key(0), cfg)
    for lp in params["layers"]:
        assert not np.any(np.asarray(lp["attn_norm"]["scale"]))  # (1 + w), w = 0
        assert not np.any(np.asarray(lp["mlp_norm"]["scale"]))
    assert not np.any(np.asarray(params["final_norm"]["scale"]))
    mixer = params["layers"][0]["gdn"]
    a = np.exp(np.asarray(mixer["A_log"]))
    assert np.all((a > 0) & (a < 16)) and np.all(np.asarray(mixer["dt_bias"]) == 1)
    assert np.all(np.asarray(mixer["norm"]) == 1)
    attn = params["layers"][3]["attn"]
    assert attn["q_norm"].shape == attn["k_norm"].shape == (cfg.head_dim,)
    assert not np.any(np.asarray(attn["q_norm"]))


# -- (a) the program against the reference ------------------------------------------


def test_logits_loss_and_aux_loss_match_the_reference_in_float32():
    cfg = small_cfg()
    params, rows = seeded(cfg)
    logits, stats = harness.forward_with_stats(params, rows[:, :-1], cfg)
    close(logits, ref_logits(params, rows[:, :-1], cfg), F32_TOL)
    s, n, aux = harness.moe_loss_sum(params, rows, cfg)
    ce, aux_ref = reference_objective(params, rows, cfg)
    close(s / n, ce, F32_TOL)
    close(aux["moe_aux_loss"], aux_ref, F32_TOL)  # over all 16 experts, held or not
    assert len(stats) == 4 and stats[0][0].shape == (16,)
    # a token's k pairs fall on all the experts; the held four get their share
    assert float(sum(jnp.sum(s_[0]) for s_ in stats)) == pytest.approx(4 * cfg.moe_top_k, rel=1e-6)
    held = float(np.mean([np.sum(np.asarray(s_[0])[4:8]) for s_ in stats]))
    assert float(aux["moe_held_pairs_per_token"]) == pytest.approx(held, rel=1e-6)
    assert 0.2 < held < 3.0
    # a held share hands up a third statistic: the share of the buffer's tiles in use
    assert float(aux["moe_held_rows_share"]) == pytest.approx(
        np.mean([float(s_[2]) for s_ in stats]), rel=1e-6)


def test_every_gradient_matches_the_reference_in_float32():
    cfg = small_cfg()
    params, rows = seeded(cfg)
    got = harness.every_gradient_matches(params, rows, cfg, harness.reference(ARCH, ref_cfg, cfg),
                                         5 * F32_TOL)
    assert len(jax.tree.leaves(got)) > 50


@pytest.mark.parametrize("seed", [0, 3])
def test_bf16_compute_stays_near_the_reference(seed):
    cfg = small_cfg(dtype=jnp.bfloat16)
    params, rows = seeded(cfg, seed=seed)
    logits = forward(params, rows[:, :-1], cfg)
    want = np.asarray(ref_logits(params, rows[:, :-1], cfg))
    assert logits.dtype == jnp.bfloat16
    row_err = np.abs(np.asarray(logits, np.float32) - want).max(axis=-1) / np.abs(want).max()
    assert np.median(row_err) <= BF16_TOL / 2, np.median(row_err)
    s, n, _ = harness.moe_loss_sum(params, rows, cfg)
    assert float(s / n) == pytest.approx(float(reference_objective(params, rows, cfg)[0]), rel=1e-2)


def test_bf16_blocks_stay_near_float32_on_the_same_input():
    cfg = small_cfg(dtype=jnp.bfloat16)
    f32 = cfg.replace(dtype=jnp.float32)
    params, _ = seeded(cfg)
    x = jax.random.normal(jax.random.key(5), (2, 100, cfg.hidden_size))
    weight = jax.random.normal(jax.random.key(6), x.shape)
    tables = modeling.rope_tables(cfg, 100)

    def mixer(c, p):
        return gdn.block(x.astype(c.dtype), p, c)

    def attn(c, p):
        return modeling.attn_block(x.astype(c.dtype), p, c, tables)

    def block_and_gradients(fn, c, p):  # one compiled program a block and a compute type
        def weighted(p_):
            y = fn(c, p_).astype(jnp.float32)
            return jnp.sum(y * weight), y

        (_, y), grads = jax.jit(jax.value_and_grad(weighted, has_aux=True))(p)
        return y, grads

    for fn, p, tol in ((mixer, params["layers"][0]["gdn"], BF16_TOL),
                       (attn, params["layers"][3]["attn"], BF16_TOL / 4)):
        (y, got), (y32, want) = block_and_gradients(fn, cfg, p), block_and_gradients(fn, f32, p)
        for name in want:
            if want[name].ndim == 2:  # the matrices: a vector's gradient is a sum of roundings
                try:
                    close(got[name], want[name], tol)
                except AssertionError as e:
                    raise AssertionError(f"{fn.__name__} d{name}: {e}") from None
        close(y, y32, tol)


def test_the_likely_mistakes_show():
    """Each departure from the published layer moves the logits far past the
    tolerance: weights not renormalised, a plain ``* w`` norm, rotary over the
    whole head, the gate left off, the shared expert left out."""
    cfg = small_cfg()
    params, rows = seeded(cfg)
    want = np.asarray(ref_logits(params, rows[:, :-1], cfg))
    for wrong in (dict(moe_norm_topk=False), dict(norm_zero_centered=False),
                  dict(rotary_fraction=1.0), dict(moe_share=(0, 4))):
        got = np.asarray(forward(params, rows[:, :-1], cfg.replace(**wrong)))
        assert np.abs(got - want).max() / np.abs(want).max() > 1e-3, wrong


# -- (b) the chunked delta rule against the recurrent form ------------------------


def _delta_inputs(s, seed=0, b=2, hk=2, r=2, dk=16, dv=8, decay=0.3):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = ARCH.l2norm(jax.random.normal(ks[0], (b, s, hk, dk))) / np.sqrt(dk)
    # keys that share a direction: the chunk's system is far from the identity
    k = ARCH.l2norm(jax.random.normal(ks[1], (b, s, hk, dk)) + 0.7)
    v = jax.random.normal(ks[2], (b, s, hk * r, dv))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, s, hk * r)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hk * r)))
    return q, k, v, g, beta


def _recurrent(q, k, v, g, beta):
    r = v.shape[2] // q.shape[2]
    return ARCH.delta_rule_recurrent(jnp.repeat(q, r, 2), jnp.repeat(k, r, 2), v, g, beta)


@pytest.mark.parametrize("s,chunk", [(64, 64), (128, 64), (192, 64), (100, 64), (7, 64),
                                     (65, 64), (96, 32), (40, 16)])
def test_chunked_delta_rule_is_the_recurrence(s, chunk):
    args = _delta_inputs(s, seed=s)
    close(gated_delta_chunked(*args, chunk), _recurrent(*args), F32_TOL)


@pytest.mark.parametrize("s", [128, 100])
def test_chunked_delta_rule_gradients_are_the_recurrences(s):
    args = _delta_inputs(s, seed=s + 1)
    weight = jax.random.normal(jax.random.key(9), (2, s, 4, 8))
    got = jax.grad(lambda *a: jnp.sum(gated_delta_chunked(*a, 64) * weight),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: jnp.sum(_recurrent(*a) * weight), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        try:
            close(a, b, 5 * F32_TOL)
        except AssertionError as e:
            raise AssertionError(f"d{name}: {e}") from None


def test_chunked_delta_rule_carries_its_state_across_chunks():
    """A write in the first chunk is read in the third: zeroing the first
    chunk's values moves the last chunk's output."""
    q, k, v, g, beta = _delta_inputs(192, seed=3, decay=0.02)
    out = gated_delta_chunked(q, k, v, g, beta, 64)
    cut = gated_delta_chunked(q, k, v.at[:, :64].set(0), g, beta, 64)
    assert float(jnp.abs(out[:, 128:] - cut[:, 128:]).max()) > 1e-3
    # and nothing later moves anything earlier
    later = gated_delta_chunked(q, k, v.at[:, 128:].set(0), g, beta, 64)
    np.testing.assert_array_equal(np.asarray(out[:, :128]), np.asarray(later[:, :128]))


def test_mixer_in_bf16_keeps_the_decays_and_the_state_in_float32():
    cfg = small_cfg(dtype=jnp.bfloat16)
    p = gdn.init_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 100, cfg.hidden_size), jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(lambda x_, p_: gdn.block(x_, p_, cfg))(x, p))
    assert "triangular_solve" in jaxpr and "f32[2,2,2,8,8]" in jaxpr  # the solve; the state
    y = gdn.block(x, p, cfg)
    want = gdn.block(x.astype(jnp.float32), p, cfg.replace(dtype=jnp.float32))
    assert y.dtype == jnp.bfloat16
    close(y.astype(jnp.float32), want, BF16_TOL)
    assert gdn.path_counts(cfg)["scan"] == {"fused": 0, "plain": 3}
    assert gdn.path_counts(cfg)["conv"] == {"fused": 0, "plain": 3}  # the CPU: the plain conv


# -- (c) the shares add up -----------------------------------------------------------


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """Over all R ranks the routed parts, plus the shared expert counted once,
    equal the layer that holds every expert: the reference's uncut layer, and the
    program's parts against it."""
    cfg = small_cfg(moe_share=(0, 1))
    full = moe.init_moe_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 50, cfg.hidden_size))
    fs = cfg.moe_shared_ffn_dim

    def ref_weights(p):
        return {"gate": p["router"]["w"], "gate_proj": p["w1"], "up_proj": p["w3"],
                "down_proj": p["w2"], "shared_gate_proj": p["shared"]["w13"][:, :fs],
                "shared_up_proj": p["shared"]["w13"][:, fs:], "shared_down_proj": p["shared"]["w2"],
                "shared_expert_gate": p["shared"]["gate"]}

    routed_all, shared_all, _ = ARCH.sparse_mlp(x, ref_weights(full), ref_cfg(cfg))
    uncut = routed_all + shared_all
    ranks = 4
    total_ref = total_prog = 0.0
    for rank in range(ranks):
        rcfg = cfg.replace(moe_share=(rank, ranks))
        lo, n = rcfg.moe_first_held, rcfg.moe_held
        part = dict(full, **{name: full[name][lo:lo + n] for name in ("w1", "w3", "w2")})
        routed, shared, _ = ARCH.sparse_mlp(x, ref_weights(part), ref_cfg(rcfg))
        close(shared, shared_all, 1e-6)  # every rank computes the shared expert alike
        total_ref = total_ref + routed
        y, _ = moe.moe_topk_block(x, part, rcfg, tile=8)
        close(y, routed + shared, F32_TOL)  # the program's share is the reference's
        total_prog = total_prog + (y - shared)
    close(total_ref + shared_all, uncut, F32_TOL)
    close(total_prog + shared_all, uncut, F32_TOL)
    # and a rank alone is NOT the layer: what the others hold is really left out
    assert float(jnp.abs(routed + shared_all - uncut).max()) > 1e-2


def test_held_layout_drops_the_pairs_it_does_not_hold():
    idx = jnp.asarray([[0, 5], [4, 7], [6, 2], [5, 4], [9, 5]], jnp.int32)  # experts 4-7 held
    lay = moe.held_layout(idx, 4, 4, 4)
    assert list(np.asarray(lay.sizes)) == [2, 3, 1, 1]  # experts 4, 5, 6, 7
    assert int(lay.num_tiles[0]) == 4  # a tile an expert; the dropped pairs' tiles lie past them
    valid = np.asarray(lay.row_valid)
    assert valid.sum() == 7 and not valid[16:].any()
    rows = np.asarray(lay.pair_row).reshape(5, 2)
    held = (np.asarray(idx) >= 4) & (np.asarray(idx) < 8)
    assert (rows[held] < 16).all() and (rows[~held] >= 16).all()
    assert list(np.asarray(lay.tile_group)[:4]) == [0, 1, 2, 3]
    assert np.asarray(lay.tile_group).max() == 3
    # all held: the layout there always was
    whole = moe.sorted_layout(idx % 4, 4, 4)
    again = moe.held_layout(idx % 4 + 4, 4, 4, 4)
    np.testing.assert_array_equal(np.asarray(whole.pair_row), np.asarray(again.pair_row))
    np.testing.assert_array_equal(np.asarray(whole.sizes), np.asarray(again.sizes))


def test_held_share_must_divide_the_experts():
    with pytest.raises(ValueError, match="moe_share"):
        moe.init_moe_params(jax.random.key(0), small_cfg(moe_share=(0, 3)))
    with pytest.raises(ValueError, match="moe_share"):
        moe.init_moe_params(jax.random.key(0), small_cfg(moe_share=(4, 4)))


# -- the bounded held path: work in proportion to the pairs held ---------------------
# `moe.held_experts` (kernels of ops/moe_held.py, interpreted here) against the plain
# path over the whole buffer (`_dispatch`, three `grouped_gemm`, `_combine`), which
# stays in the program for the shapes outside the kernels' envelope and is the
# reference: float32, the output and every gradient, over loads that hit the edges.

HELD_T, HELD_K, HELD_E, HELD_N, HELD_FIRST, HELD_H, HELD_F, HELD_TILE = 64, 4, 16, 4, 4, 128, 128, 16
HELD_NAMES = ("y", "dx", "dweights", "dw1", "dw3", "dw2")


def _held_choices(load, seed=0):
    """(T, k) expert choices; experts HELD_FIRST .. HELD_FIRST + 3 are held."""
    ks = jax.random.split(jax.random.key(seed), 3)
    inside = jax.random.randint(ks[0], (HELD_T, HELD_K), HELD_FIRST, HELD_FIRST + HELD_N)
    outside = jax.random.randint(ks[1], (HELD_T, HELD_K), HELD_FIRST + HELD_N, HELD_E)
    if load == "none_held":
        return outside
    if load == "every_pair_held":  # the buffer is as full as it gets
        return inside
    if load == "one_expert_every_pair_of_its_tokens":
        rows = jnp.arange(HELD_T)[:, None] % 3 == 0
        return jnp.where(rows, HELD_FIRST + 2, outside)
    if load == "an_expert_with_exactly_a_tile":
        flat = outside.reshape(-1).at[jnp.arange(HELD_TILE) * 5].set(HELD_FIRST + 1)
        return flat.reshape(HELD_T, HELD_K)
    assert load == "a_sixteenth"
    return jnp.where(jax.random.uniform(ks[2], (HELD_T, HELD_K)) < 1 / 16, inside, outside)


def _held_operands(dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(100 + seed), 6)
    hidden = HELD_H * (2 if dtype == jnp.bfloat16 else 1)  # a bf16 slab chunk is 256 columns
    x = jax.random.normal(ks[0], (HELD_T, hidden), dtype)
    weights = jax.nn.softmax(jax.random.normal(ks[1], (HELD_T, HELD_K)), axis=-1)
    w1, w3 = (jax.random.normal(k, (HELD_N, hidden, HELD_F), dtype) * 0.09 for k in ks[2:4])
    w2 = jax.random.normal(ks[4], (HELD_N, HELD_F, hidden), dtype) * 0.09
    cot = jax.random.normal(ks[5], (HELD_T, hidden), jnp.float32)
    return (x, weights, w1, w3, w2), cot


# the same sums in another order (a kernel's float32 accumulator a column block, XLA's
# reduction tree): two float32 ulps of the largest element
HELD_TOL = 2e-6


def _held_plain(x, weights, w1, w3, w2, idx):
    lay = moe.held_layout(idx, HELD_N, HELD_TILE, HELD_FIRST)
    rows = moe._dispatch(x, lay.row_pair // HELD_K, lay.row_valid, lay.pair_row)
    gate = moe.grouped_gemm(rows, w1, lay, HELD_TILE)
    up = moe.grouped_gemm(rows, w3, lay, HELD_TILE)
    out = moe.grouped_gemm(jax.nn.silu(gate) * up, w2, lay, HELD_TILE)
    return moe._combine(out, weights, lay.pair_row, lay.row_pair, lay.row_valid)


def _held_bounded(x, weights, w1, w3, w2, idx):
    lay = moe.held_layout(idx, HELD_N, HELD_TILE, HELD_FIRST)
    return moe.held_experts(x, weights, jnp.concatenate([w1, w3], axis=-1), w2, lay.pair_row,
                            lay.row_pair, lay.row_valid, lay.tile_group, lay.num_tiles, HELD_TILE)


def _output_and_gradients(body, operands, cot, idx):
    y, vjp = jax.vjp(lambda *t: body(*t, idx), *operands)
    return (y,) + vjp(cot.astype(y.dtype))


@pytest.mark.parametrize("load", ["none_held", "one_expert_every_pair_of_its_tokens",
                                  "every_pair_held", "an_expert_with_exactly_a_tile",
                                  "a_sixteenth"])
def test_bounded_held_path_is_the_plain_path_in_float32(load):
    idx = _held_choices(load)
    lay = moe.held_layout(idx, HELD_N, HELD_TILE, HELD_FIRST)
    held = int(((idx >= HELD_FIRST) & (idx < HELD_FIRST + HELD_N)).sum())
    assert int(lay.row_valid.sum()) == held  # the load is the one its name says
    if load == "none_held":
        assert held == 0 and int(lay.num_tiles[0]) == HELD_N  # a padding tile an expert
    if load == "every_pair_held":
        assert held == HELD_T * HELD_K
    if load == "an_expert_with_exactly_a_tile":
        assert list(np.asarray(lay.sizes)) == [0, HELD_TILE, 0, 0]
    operands, cot = _held_operands()
    want = _output_and_gradients(_held_plain, operands, cot, idx)
    got = _output_and_gradients(_held_bounded, operands, cot, idx)
    for name, g, w in zip(HELD_NAMES, got, want):
        assert np.isfinite(np.asarray(g)).all(), name
        if float(jnp.abs(w).max()) == 0.0:  # nothing held: exact zeros, not small numbers
            assert float(jnp.abs(g).max()) == 0.0, name
        else:
            close(g, w, HELD_TOL)


def _poisoned(fn, tile_arg):
    """``fn`` with every row past ``num_tiles`` of what it returns set to NaN (in a
    bf16 slab: both halves of every word): what "undefined" may hold."""
    def wrapped(*args, **kw):
        out = fn(*args, **kw)
        num_tiles = args[tile_arg] if isinstance(tile_arg, int) else kw[tile_arg]
        tile = kw.get("tile", kw.get("tile_m"))
        past = jnp.arange(out.shape[0]) >= num_tiles[0] * tile
        past = past.reshape((-1,) + (1,) * (out.ndim - 1))
        bad = jnp.uint32(0x7FC07FC0) if out.dtype == jnp.uint32 else jnp.asarray(jnp.nan, out.dtype)
        return jnp.where(past, bad, out)
    return wrapped


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
def test_rows_past_num_tiles_may_hold_anything(monkeypatch, dtype):
    """The poison case: every buffer of the bounded path gets NaN into the rows past
    ``num_tiles`` before its consumer runs; the output and every gradient are finite
    and the ones they were."""
    from galvatron_tpu.ops import grouped_matmul, moe_held

    idx = _held_choices("a_sixteenth", seed=1)
    operands, cot = _held_operands(dtype, seed=1)
    want = _output_and_gradients(_held_bounded, operands, cot, idx)
    monkeypatch.setattr(moe_held, "gather_rows", _poisoned(moe_held.gather_rows, 3))
    monkeypatch.setattr(moe_held, "swiglu", _poisoned(moe_held.swiglu, 1))
    monkeypatch.setattr(moe_held, "swiglu_bwd", _poisoned(moe_held.swiglu_bwd, 2))
    monkeypatch.setattr(grouped_matmul, "held_matmul", _poisoned(grouped_matmul.held_matmul, 3))
    got = _output_and_gradients(_held_bounded, operands, cot, idx)
    for name, g, w in zip(HELD_NAMES, got, want):
        assert np.isfinite(np.asarray(g, np.float32)).all(), name
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32), name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
def test_a_slab_is_its_rows(dtype):
    from galvatron_tpu.ops import moe_held

    x = jax.random.normal(jax.random.key(0), (24, 512), dtype)
    slab = moe_held.to_slab(x)
    assert slab.shape == (24, 512 // (128 * moe_held.per_word(dtype)), 128)
    assert slab.dtype == moe_held.slab_dtype(dtype)
    np.testing.assert_array_equal(np.asarray(moe_held.from_slab(slab, dtype), np.float32),
                                  np.asarray(x, np.float32))
    # the words' halves in a kernel's arithmetic: column blocks of 128, low half first
    pieces = moe_held.words_to_f32(slab[:, 1, :])
    at = 128 * moe_held.per_word(dtype)
    for part, piece in enumerate(pieces):
        np.testing.assert_array_equal(
            np.asarray(piece), np.asarray(x[:, at + part * 128:at + (part + 1) * 128], np.float32))
    np.testing.assert_array_equal(np.asarray(moe_held.f32_to_words(pieces, dtype)),
                                  np.asarray(slab[:, 1, :]))


@pytest.mark.parametrize("hidden,width,dtype,path", [
    (2048, 512, jnp.bfloat16, "bounded"),  # the published sizes
    (128, 128, jnp.float32, "bounded"),
    (256, 128, jnp.bfloat16, "bounded"),
    (128, 128, jnp.bfloat16, "worst_case"),  # half a slab chunk in bf16
    (32, 24, jnp.float32, "worst_case"),  # `small_cfg`
    (2048, 512, jnp.float16, "worst_case"),
    (2048, 96, jnp.bfloat16, "worst_case"),
])
def test_held_path_is_chosen_by_shape(hidden, width, dtype, path):
    from galvatron_tpu.ops import moe_held

    assert moe_held.held_path(hidden, width, dtype) == path
    cfg = small_cfg(hidden_size=hidden, moe_ffn_dim=width, dtype=dtype)
    other = "worst_case" if path == "bounded" else "bounded"
    assert moe.held_path_counts(cfg) == {path: cfg.num_layers, other: 0}
    assert moe.held_path_counts(cfg.replace(moe_share=(0, 1))) == {"bounded": 0, "worst_case": 0}


def test_the_block_takes_the_bounded_path_and_keeps_its_gradients(monkeypatch):
    """`moe_topk_block` at a shape inside the envelope: the layer against the
    reference's, and the output, the statistics and every gradient (the router's
    among them) against the same block held to the plain path."""
    from galvatron_tpu.ops import moe_held

    cfg = small_cfg(hidden_size=128, moe_ffn_dim=128, moe_shared_ffn_dim=128)
    assert moe.held_path_counts(cfg)["bounded"] == cfg.num_layers
    p = moe.init_moe_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.hidden_size))
    cot = jax.random.normal(jax.random.key(2), x.shape)
    calls = []
    real = moe.held_experts
    monkeypatch.setattr(moe, "held_experts", lambda *a: calls.append(1) or real(*a))

    def run():
        (y, stats), vjp = jax.vjp(lambda x_, p_: moe.moe_topk_block(x_, p_, cfg, tile=8), x, p)
        return y, stats, vjp((cot, jax.tree.map(jnp.zeros_like, stats)))

    y, stats, (dx, dp) = run()
    assert calls and len(stats) == 3
    tiles = int(moe.held_layout(jax.lax.top_k(jax.nn.softmax(
        x.reshape(-1, 128) @ p["router"]["w"]), cfg.moe_top_k)[1], 4, 8, 4).num_tiles[0])
    assert float(stats[2]) == pytest.approx(tiles * 8 / (64 * cfg.moe_top_k + 5 * 8))
    fs = cfg.moe_shared_ffn_dim
    routed, shared, _ = ARCH.sparse_mlp(x, {
        "gate": p["router"]["w"], "gate_proj": p["w1"], "up_proj": p["w3"], "down_proj": p["w2"],
        "shared_gate_proj": p["shared"]["w13"][:, :fs], "shared_up_proj": p["shared"]["w13"][:, fs:],
        "shared_down_proj": p["shared"]["w2"], "shared_expert_gate": p["shared"]["gate"]},
        ref_cfg(cfg))
    close(y, routed + shared, F32_TOL)
    calls.clear()
    monkeypatch.setattr(moe_held, "held_path", lambda *a: "worst_case")
    y0, stats0, (dx0, dp0) = run()
    assert not calls
    close(y, y0, HELD_TOL)
    close(dx, dx0, HELD_TOL)
    for s_, s0 in zip(stats, stats0):
        np.testing.assert_array_equal(np.asarray(s_), np.asarray(s0))
    for (path, g), g0 in zip(jax.tree_util.tree_leaves_with_path(dp), jax.tree.leaves(dp0)):
        assert float(jnp.abs(g0).max()) > 0, path
        close(g, g0, HELD_TOL)


# -- the runtime --------------------------------------------------------------------


def _runtime(cfg, hp, batch=4):
    mesh, axes = build_mesh(pp=1, devices=jax.devices()[:1])
    return build_runtime(cfg, hp, mesh=mesh, axes=axes, adam=AdamConfig(lr=1e-3, grad_clip=None),
                         global_batch_size=batch, seq_len=cfg.max_seq_len)


@pytest.mark.parametrize("chunks", [1, 2])
def test_runtime_steps_and_hands_up_the_held_pairs(chunks):
    cfg = small_cfg(max_seq_len=64)
    hp = HybridParallelConfig.uniform(cfg.num_layers, mixed_precision="fp32", chunks=chunks,
                                      ckpt="full")
    rt = _runtime(cfg, hp)
    state = rt.init_state(jax.random.key(0))
    rows = jax.random.randint(jax.random.key(1), (4, 65), 0, cfg.vocab_size, jnp.int32)
    want = float(reference_objective(state["params"], rows, cfg)[0])
    state, loss = rt.train_step(state, rt.shard_batch(rows))
    assert float(loss) == pytest.approx(want, rel=1e-5)
    assert set(state["moe_stats"]) == {"moe_aux_loss", "moe_load_max_over_mean",
                                       "moe_held_pairs_per_token", "moe_held_rows_share"}
    assert 0.2 < float(state["moe_stats"]["moe_held_pairs_per_token"]) < 3.0
    # a forward's 4 / chunks x 64 tokens x top-4 over 16 scored experts are 64 / 32 rows an
    # expert, the tile `row_tile` takes (PR 57; 256 until then); of the buffer's pairs + 5
    # tiles, the 4 held experts' tiles are in use whatever they hold, every held pair's row
    # is, and an expert wastes less than a tile (all linear: the microbatches' mean keeps them)
    from galvatron_tpu.ops.grouped_matmul import row_tile

    tokens, tile = 4 // chunks * 64, 64 // chunks
    assert row_tile(tokens, 4, 16, jnp.float32) == tile
    pairs = float(state["moe_stats"]["moe_held_pairs_per_token"]) * tokens
    rows = tokens * 4 + 5 * tile
    share = float(state["moe_stats"]["moe_held_rows_share"])
    assert max(4 * tile, pairs) / rows - 1e-6 <= share <= (pairs + 4 * tile) / rows
    # a model that holds all its experts keeps the two statistics it had
    whole = _runtime(small_cfg(max_seq_len=64, moe_share=(0, 1)), hp)
    assert set(whole.init_state(jax.random.key(0))["moe_stats"]) == {
        "moe_aux_loss", "moe_load_max_over_mean"}


# -- (d) each refusal by name -------------------------------------------------------


def by_layer(layers, **kw):
    return HybridParallelConfig(layer_strategies=layers, mixed_precision="fp32", **kw)


SHORT = dict(max_seq_len=64)
REFUSALS = [
    ("tp", SHORT, lambda c: by_layer([LayerStrategy(tp=2)] + [LayerStrategy()] * 3),
     "tensor parallelism .* Gated DeltaNet layers"),
    ("cp", SHORT, dict(cp=2), "context parallelism .* Gated\\s+DeltaNet"),
    ("pp", SHORT, dict(pp=2), "pipeline parallelism .* interleaved layer kinds"),
    ("ep", SHORT, dict(ep=2), "expert parallelism .* held share"),
]
test_build_runtime_refuses_by_name = harness.refuses(REFUSALS, small_cfg, seq_len=64)


def test_packing_and_generation_are_refused_by_name():
    cfg = small_cfg(max_seq_len=64, pack_sequences=True, attn_impl="xla")
    mesh, axes = build_mesh(pp=1, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="pack_sequences .* Gated DeltaNet"):
        build_runtime(cfg, harness.plan(cfg, mixed_precision="fp32"), mesh=mesh, axes=axes,
                      adam=AdamConfig(), global_batch_size=4, seq_len=64)
    from galvatron_tpu.models.generation import init_kv_cache

    with pytest.raises(ValueError, match="generation .* Gated DeltaNet"):
        init_kv_cache(small_cfg(), 1, 8)


def test_plan_check_names_the_same_refusals():
    from galvatron_tpu.analysis import plan_check

    cfg = small_cfg(max_seq_len=64)
    layers = [LayerStrategy(tp=2, cp=2)] + [LayerStrategy(ep=2)] * 3
    found = plan_check.check_plan(by_layer(layers, pp=2), cfg, 16)
    text = "\n".join(f"{d.code} {d.message}" for d in found)
    assert "GTA019 layer 0: tp=2 on a Gated DeltaNet layer" in text
    assert "GTA019 layer 0: cp=2 on a Gated DeltaNet layer" in text
    assert "GTA020 pp=2 over interleaved layer kinds" in text
    assert "GTA014 layer 3: ep=2 on a held share of the experts" in text


def test_search_leaves_out_what_the_runtime_refuses_and_prices_each_kind():
    from galvatron_tpu.search import theoretical as th
    from galvatron_tpu.search.cost_model import ProfiledHardware
    from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace

    cfg = PRESETS["qwen3-next-80b-a3b"].replace(num_layers=4, vocab_size=18992,
                                                moe_share=(0, 16), max_seq_len=4096)
    costs = th.analytic_model_costs(cfg, seq_len=4096)
    assert len(costs.layer_types) == 4
    linear, full = costs.layer_types[0], costs.layer_types[3]
    assert linear.parameter_mb == pytest.approx(138_582_208 * 4 / 1e6)
    assert full.parameter_mb == pytest.approx(132_127_232 * 4 / 1e6)
    # at s 4096 the full layer is the dearer one (the search counts all s x s pairs)
    assert 1.0 < full.fwd_ms_per_sample / linear.fwd_ms_per_sample < 2.0
    # the held share: 0.625 of an expert a token is active, not 10
    assert th.layer_active_param_count(cfg, "gdn") == pytest.approx(
        138_582_208 - (32 - 0.625) * 3 * 2048 * 512)
    engine = SearchEngine(costs, ProfiledHardware(), num_layers=4, space=SearchSpace(world_size=4),
                          memory_budget_mb=15360.0, model_config=cfg)
    assert {"gated_delta_layers_no_tp", "gated_delta_layers_no_cp",
            "interleaved_layer_kinds_no_pp", "dropless_topk_moe_no_ep"} <= set(engine._standing)
    assert engine.space.max_tp == 1 and engine.space.pp_choices == [1]


def test_cli_flag_names_the_share():
    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args

    ns = initialize_galvatron("train", ["--model_size", "qwen3-next-80b-a3b", "--num_layers", "4",
                                        "--vocab_size", "18992", "--moe_share", "3/16"])
    cfg = model_config_from_args(ns)
    assert cfg.moe_share == (3, 16) and (cfg.moe_first_held, cfg.moe_held) == (96, 32)
    assert cfg.kinds == ("gdn", "gdn", "gdn", "attention") and cfg.vocab_size == 18992
