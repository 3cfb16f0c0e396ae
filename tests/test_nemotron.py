"""nemotron_h-class stacks (published blocks of one norm and one sublayer; Mamba-2 mixers
whose per-row STATE of two parts, a conv tail and a float32 scan state, lives beside the
attention layers' keys and values in one slot cache; a single-step body and a chunk form
that takes and hands on its state; the gate norm within each scan group; layers of a mixer
alone; un-gated ``relu(x)^2`` experts beside a shared one) on the normal path, against the
plain reference ``benchmark/references/nemotron_h.py`` on seeded random weights, at a small
size on the CPU: the configuration and the block-to-layer mapping, the no-cache forward and
the gradients, the model's own mechanisms, the refusals and one-device training. The state
through the slot cache and the engine are tests/test_nemotron_serving.py's (one file = one
worker under ``--dist loadfile``: two files keep each under 90 s)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference
from galvatron_tpu.models import generation, modeling, moe, ssm
from galvatron_tpu.models.modeling import NEMOTRON_3_NANO_PATTERN, PRESETS, blocks_to_layers
from galvatron_tpu.ops import moe_held, ssd
from tests import _stack_harness as harness
from tests._stack_harness import close, forward, seeded

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = reference.load(ROOT, "nemotron_h")

# float32, the same arithmetic in another order (the program runs the scan in chunks with
# decay-masked score blocks and carries a state, sorts the pairs and runs grouped GEMMs, and
# attends a block of keys at a time; the reference steps the recurrence a position at a
# time, loops over key/value heads and multiplies every expert): the largest difference
# read over this file's cases is 3e-6 of the largest logit; a scan state rounded to bfloat16
# every step reads 1.5e-4
F32_TOL = 2e-5
CHUNK, SLOT = 4, 64


def small_cfg(**kw):
    """The first 5 published LAYERS (9 blocks, M E M E M * E M E: two Mamba-2 layers with
    experts, one Mamba-2 mixer alone, an attention layer and a Mamba-2 layer with experts)
    at small widths: 8 heads of 4 in 4 scan groups, state 8, chunks of 8; 8 experts top-2,
    all held."""
    base = dict(vocab_size=96, hidden_size=32, num_layers=5, num_heads=4, num_kv_heads=2,
                attn_head_dim=8, ffn_dim=24, max_seq_len=SLOT, ssm_heads=8, ssm_head_dim=4,
                ssm_state=8, ssm_groups=4, ssm_chunk=8, moe_experts=8, moe_top_k=2,
                moe_ffn_dim=24, moe_shared_ffn_dim=40, dtype=jnp.float32)
    base.update(kw)
    return PRESETS["nemotron-3-nano-30b-a3b"].replace(**base)


def ref_cfg(cfg, share=None):
    rank, of = share or cfg.moe_share
    return {"hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
            "layer_norm_epsilon": cfg.norm_eps, "hybrid_override_pattern": NEMOTRON_3_NANO_PATTERN,
            "num_hidden_layers": cfg.num_layers,
            "published_blocks": cfg.num_layers + sum(cfg.mlp_layers),
            "mamba_num_heads": cfg.ssm_heads, "mamba_head_dim": cfg.ssm_head_dim,
            "n_groups": cfg.ssm_groups, "ssm_state_size": cfg.ssm_state,
            "conv_kernel": cfg.ssm_conv, "moe_intermediate_size": cfg.expert_ffn,
            "moe_shared_expert_intermediate_size": cfg.moe_shared_ffn_dim, "n_shared_experts": 1,
            "n_routed_experts": cfg.moe_experts // of, "num_experts_per_tok": cfg.moe_top_k,
            "routed_scaling_factor": cfg.moe_route_scale, "norm_topk_prob": cfg.moe_norm_topk,
            "vocab_size": cfg.vocab_size, "expert_share": {"rank": rank, "of": of}}


def ref_logits(params, rows, cfg, share=None):
    return harness.reference(ARCH, ref_cfg, cfg, share).logits(params, jnp.asarray(rows))


def scan_state(cache, row):
    return np.asarray(cache.state.scan[:, row])


# -- the configuration ------------------------------------------------------------------


def test_preset_runs_the_published_widths():
    cfg = PRESETS["nemotron-3-nano-30b-a3b"]
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (
        2688, 29, 32, 2, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv,
            cfg.ssm_chunk) == (64, 64, 128, 8, 4, 128)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.expert_ffn, cfg.moe_shared_ffn_dim) == (
        128, 6, 1856, 3712)
    assert cfg.moe_router == "sigmoid_topk" and cfg.moe_route_scale == 2.5 and cfg.moe_norm_topk
    assert cfg.act_fn == "relu2" and not cfg.moe_shared_gate and moe.ungated(cfg)
    assert cfg.pos_embed == "nope" and not cfg.tie_word_embeddings and cfg.norm_eps == 1e-5
    assert (cfg.vocab_size, cfg.max_seq_len) == (131072, 262144)
    assert ssm.ssm_dims(cfg) == (4096, 6144, 10304)
    cut = cfg.replace(num_layers=15)
    assert cut.kinds.count("ssm") == 12 and cut.kinds.count("attention") == 3
    assert sum(cut.mlp_layers) == 11
    assert generation.stack_layers(cut) == {"full": 3, "window": 0, "state": 12}


def test_the_whole_pattern_maps_onto_29_layers():
    """52 published blocks = 29 program layers: a mixer block and the E behind it are one
    pre-norm layer, the M in front of every * is a layer of its mixer alone."""
    kinds, mlps = blocks_to_layers(NEMOTRON_3_NANO_PATTERN)
    assert len(NEMOTRON_3_NANO_PATTERN) == 52
    assert (len(kinds), kinds.count("ssm"), kinds.count("attention"), sum(mlps)) == (29, 23, 6, 23)
    # read back: the layers spell the pattern
    spelt = "".join(("M" if k == "ssm" else "*") + "E" * m for k, m in zip(kinds, mlps))
    assert spelt == NEMOTRON_3_NANO_PATTERN
    alone = [i for i, m in enumerate(mlps) if not m]
    assert len(alone) == 6 and all(kinds[i] == "ssm" and kinds[i + 1] == "attention" for i in alone)
    # the cell's cut: blocks 0-25 are layers 0-14, 4 of them a mixer alone (block 25's * is
    # block 26, on the next stage)
    assert blocks_to_layers(NEMOTRON_3_NANO_PATTERN, 26) == (kinds[:15], mlps[:15])
    assert NEMOTRON_3_NANO_PATTERN[:26].count("E") == sum(mlps[:15]) == 11
    assert [i for i, m in enumerate(mlps[:15]) if not m] == [2, 6, 10, 14]
    cfg = PRESETS["nemotron-3-nano-30b-a3b"]
    assert (cfg.layer_kinds, cfg.mlp_layout) == (kinds, mlps)


@pytest.mark.parametrize("pattern,message", [
    ("EM", "an expert block without a mixer block in front of it"),
    ("MEE", "an expert block without a mixer block in front of it"),
    ("M-E", "is none of 'M', '\\*', 'E'")])
def test_a_pattern_the_program_cannot_run_is_refused(pattern, message):
    with pytest.raises(ValueError, match=message):
        blocks_to_layers(pattern)


def test_the_reference_holds_the_mapping_block_by_block():
    """`published_weights` spells the program's layers as blocks and refuses a program
    whose layers were another reading of the pattern."""
    cfg = small_cfg()
    params, _ = seeded(cfg)
    rc = ref_cfg(cfg)
    w = ARCH.published_weights(params, rc)
    assert len(w["blocks"]) == 9 == rc["published_blocks"] and ARCH.pattern(rc) == "MEMEM*EME"
    assert set(params["layers"][2]) == {"attn_norm", "ssm"}  # a mixer alone: one norm
    assert set(params["layers"][3]) == {"attn_norm", "attn", "mlp_norm", "mlp"}
    other = small_cfg(mlp_layout=(1, 1, 1, 1, 1))  # every layer with an MLP: M E M E M E ...
    with pytest.raises(ValueError, match="the program's layers are the blocks 'MEMEME\\*EME'"):
        ARCH.published_weights(seeded(other)[0], dict(rc, published_blocks=10))


def test_parameter_counts_are_the_issues_arithmetic():
    cfg = PRESETS["nemotron-3-nano-30b-a3b"]
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    sizes = [sum(a.size for a in jax.tree.leaves(layer)) for layer in shapes["layers"]]
    mamba = 2688 * 10304 + 6144 * 5 + 3 * 64 + 4096 + 4096 * 2688 + 2688
    attn = 2688 * (4096 + 256 + 256) + 4096 * 2688 + 2688
    experts = 2688 * 128 + 128 + 128 * 2 * 2688 * 1856 + 2 * 2688 * 3712 + 2688
    assert sizes[0] == mamba + experts and sizes[2] == mamba and sizes[3] == attn + experts
    total = sum(a.size for a in jax.tree.leaves(shapes))
    assert total == 23 * mamba + 6 * attn + 23 * experts + 2 * 131072 * 2688 + 2688
    assert round(total / 1e9, 1) == 31.6
    m = shapes["layers"][0]["mlp"]
    assert m["w1"].shape == (128, 1856, 2688) == m["w2"].shape and "w3" not in m  # out-major
    assert set(m["shared"]) == {"w1", "w2"} and m["shared"]["w1"].shape == (2688, 3712)
    # the cell's cut: 26 blocks = 15 layers, 32 held experts, a quarter of the vocabulary
    cut = cfg.replace(num_layers=15, vocab_size=32768, moe_share=(0, 4))
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cut), jax.random.key(0))
    total = sum(a.size for a in jax.tree.leaves(shapes))
    assert round(total / 1e9, 3) == 4.447
    from galvatron_tpu.search import theoretical as th

    assert th.total_param_count(cut) == total


# -- the forward against the reference -----------------------------------------------------


@pytest.mark.parametrize("share", [(0, 1), (1, 4)])
def test_no_cache_forward_matches_the_reference(share):
    cfg = small_cfg(moe_share=share)
    params, rows = seeded(cfg, length=40)
    close(forward(params, rows, cfg), ref_logits(params, rows, cfg), F32_TOL)


def test_bf16_in_place_of_float32_fails_the_tolerance():
    harness.bf16_fails_the_tolerance(small_cfg(), ref_logits, F32_TOL)


def test_every_gradient_matches_the_reference():
    cfg = small_cfg(max_seq_len=24)
    params, rows = seeded(cfg, length=24, targets=True)
    # (the selection bias selects only: no gradient reaches it, in either)
    ref = harness.reference(ARCH, ref_cfg, cfg)
    router_bias = lambda tree: [layer["mlp"]["router"].pop("bias") for layer in tree["layers"]
                                if "mlp" in layer]  # noqa: E731
    got = harness.loss_and_gradients(
        lambda p: modeling.moe_loss_sum(p, rows, cfg)[0] / (rows.shape[0] * 24), params)[1]
    want = harness.loss_and_gradients(lambda p: ref.objective(p, rows)[0], params)[1]
    assert all(float(jnp.abs(b).max()) == 0 for b in router_bias(got) + router_bias(want))
    for path, w in jax.tree.leaves_with_path(want):
        assert float(jnp.abs(w).max()) > 0, jax.tree_util.keystr(path)
    harness.close_by_leaf(got, want, 2e-4, floor=0.0)


# -- the model's own mechanisms -------------------------------------------------------------


def test_the_gate_norm_is_each_groups_own():
    """8 groups of 512 (here 4 of 8) each have their own statistics: scaling ONE group's
    gate input moves that group's output alone, and the grouped norm differs from the
    ungrouped one Granite's single group takes."""
    cfg = small_cfg()
    p = ssm.init_params(jax.random.key(0), cfg)
    d_inner = cfg.ssm_heads * cfg.ssm_head_dim
    y, z = jax.random.normal(jax.random.key(1), (2, 1, 3, d_inner))
    eye = dict(p, out_proj=jnp.eye(d_inner))
    base = ssm._gate_out(y, z, eye, cfg)
    scaled = ssm._gate_out(y.at[..., :8].multiply(5.0), z, eye, cfg)
    assert float(jnp.abs(scaled[..., 8:] - base[..., 8:]).max()) < 1e-6
    assert float(jnp.abs(scaled[..., :8] - base[..., :8]).max()) < 1e-3  # scale-free but for eps
    whole = ssm._gate_out(y, z, eye, cfg.replace(ssm_groups=1))
    assert float(jnp.abs(whole - base).max()) > 1e-2


def _scan_inputs(cfg, s, rows=2, seed=3):
    ks = jax.random.split(jax.random.key(seed), 5)
    h, p, g, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    x = jax.random.normal(ks[0], (rows, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (rows, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    return x, dt, a, jax.random.normal(ks[3], (rows, s, g, n)), jax.random.normal(ks[4], (rows, s, g, n))


@pytest.mark.parametrize("groups", [1, 2, 4, 8])
def test_the_chunked_scan_is_the_recurrence_at_any_number_of_groups(groups):
    cfg = small_cfg(ssm_groups=groups)
    x, dt, a, b, c = _scan_inputs(cfg, 21)
    got = ssd.ssd_scan_plain(x, dt, a, b, c, cfg.ssm_chunk)
    want = jnp.stack([ARCH.recurrence(x[r], dt[r], a, b[r], c[r]) for r in range(2)])
    close(got, want, 1e-5)


@pytest.mark.parametrize("cut", [1, 8, 13])
def test_a_chunk_with_an_entering_state_is_the_whole_sequence(cut):
    """The scan over [0, cut) hands on its state and the scan over [cut, 21) takes it: the
    two are the scan over the whole sequence, output and leaving state."""
    cfg = small_cfg()
    x, dt, a, b, c = _scan_inputs(cfg, 21)
    zero = jnp.zeros((2,) + ssd.state_shape(cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state))
    whole, end = ssd.ssd_scan_plain(x, dt, a, b, c, cfg.ssm_chunk, state=zero)
    close(whole, ssd.ssd_scan_plain(x, dt, a, b, c, cfg.ssm_chunk), 1e-6)
    first, mid = ssd.ssd_scan_plain(x[:, :cut], dt[:, :cut], a, b[:, :cut], c[:, :cut],
                                    cfg.ssm_chunk, state=zero)
    second, last = ssd.ssd_scan_plain(x[:, cut:], dt[:, cut:], a, b[:, cut:], c[:, cut:],
                                      cfg.ssm_chunk, state=mid)
    close(jnp.concatenate([first, second], axis=1), whole, 1e-5)
    close(last, end, 1e-5)


def test_positions_without_a_time_step_do_not_reach_the_state():
    cfg = small_cfg()
    x, dt, a, b, c = _scan_inputs(cfg, 12)
    zero = jnp.zeros((2,) + ssd.state_shape(cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state))
    _, exact = ssd.ssd_scan_plain(x[:, :5], dt[:, :5], a, b[:, :5], c[:, :5], cfg.ssm_chunk,
                                  state=zero)
    _, padded = ssd.ssd_scan_plain(x, dt.at[:, 5:].set(0.0), a, b, c, cfg.ssm_chunk, state=zero)
    close(padded, exact, 1e-6)


def test_the_single_step_is_the_chunk_of_one_and_the_recurrence():
    """`ssd_step` over a stack in place: the same output and state as the chunk form of
    length 1 from the same entering state, step after step the reference's recurrence; a
    row that has not started reads zero whatever it holds; other layers' states stay."""
    cfg = small_cfg()
    h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    x, dt, a, b, c = _scan_inputs(cfg, 6)
    stack = jax.random.normal(jax.random.key(9), (3, 2) + ssd.state_shape(h, p, n))
    started = jnp.array([True, False])
    entering = jnp.where(started[:, None, None], stack[1], 0.0)
    want_y, want_state = ssd.ssd_scan_plain(x[:, :1], dt[:, :1], a, b[:, :1], c[:, :1],
                                            cfg.ssm_chunk, state=entering)
    y, new = ssd.ssd_step(stack, 1, x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], started)
    close(y, want_y[:, 0], 1e-5)
    close(new[1], want_state, 1e-5)
    assert np.array_equal(np.asarray(new[0]), np.asarray(stack[0]))
    assert np.array_equal(np.asarray(new[2]), np.asarray(stack[2]))
    # six steps from zero are the recurrence
    stack, ys = jnp.zeros_like(stack), []
    for t in range(6):
        y, stack = ssd.ssd_step(stack, 2, x[:, t], dt[:, t], a, b[:, t], c[:, t],
                                jnp.array([t > 0, t > 0]))
        ys.append(y)
    want = jnp.stack([ARCH.recurrence(x[r], dt[r], a, b[r], c[r]) for r in range(2)])
    close(jnp.stack(ys, axis=1), want, 1e-5)


def test_the_step_kernel_is_the_plain_step(monkeypatch):
    """The Pallas kernel `ssm_step` (interpreted here) against the plain body, over a stack
    of three layers in place, at a size inside `step_path`'s rule."""
    cfg = small_cfg(ssm_heads=8, ssm_head_dim=64, ssm_groups=4, ssm_state=16)
    assert ssd.step_path(8, 64, 4, 16) == "plain"  # the CPU's answer
    harness.on_a_chip(monkeypatch)
    assert ssd.step_path(8, 64, 4, 16) == "kernel" and ssd.step_path(64, 64, 8, 128) == "kernel"
    assert ssd.step_path(8, 4, 4, 8) == "plain"  # a group's heads fill no lane tile
    assert ssd._step_groups(8, 512, 128) == 4
    monkeypatch.undo()
    x, dt, a, b, c = _scan_inputs(cfg, 1, rows=3)
    stack = jax.random.normal(jax.random.key(9), (3, 3) + ssd.state_shape(8, 64, 16))
    started = jnp.array([True, False, True])
    decay = jnp.repeat(jnp.where(started[:, None], jnp.exp(dt[:, 0] * a[None]), 0.0), 64, axis=1)
    dtx = (dt[:, 0, :, None] * x[:, 0]).reshape(3, -1)
    want_y, want = ssd.ssd_step_plain(stack, 1, decay, dtx, b[:, 0], c[:, 0])
    got_y, got = ssd._step_call(stack, 1, decay, dtx, b[:, 0], c[:, 0])
    close(got_y, want_y, 1e-6)
    close(got, want, 1e-6)


@pytest.mark.parametrize("slot", [None, 2])
def test_the_row_kernels_are_the_slices(monkeypatch, slot):
    """`ssm_state_read` / `ssm_state_write` (interpreted here, called as a chip calls them)
    against ``dynamic_slice`` / ``dynamic_update_slice``: rows of one layer out and in,
    every other row and layer as it was."""
    stack = jax.random.normal(jax.random.key(9), (3, 4, 16, 512))
    rows = 4 if slot is None else 1
    at = 0 if slot is None else slot
    want = stack[1, at:at + rows]
    plain = ssd.read_rows(stack, 1, None if slot is None else jnp.int32(slot), rows)
    assert np.array_equal(np.asarray(plain), np.asarray(want))
    assert ssd.rows_path(512, 16) == "plain"  # the CPU's answer
    monkeypatch.setattr(ssd, "rows_path", lambda *a: "kernel")
    got = ssd.read_rows(stack, 1, None if slot is None else jnp.int32(slot), rows)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    new = jax.random.normal(jax.random.key(10), (rows, 16, 512))
    out = ssd.write_rows(stack, 1, None if slot is None else jnp.int32(slot), new)
    assert np.array_equal(np.asarray(out), np.asarray(stack.at[1, at:at + rows].set(new)))


def test_a_layer_of_its_mixer_alone_is_one_norm_and_one_sublayer():
    cfg = small_cfg()
    params, rows = seeded(cfg, length=8)
    p = params["layers"][2]
    x = jax.random.normal(jax.random.key(4), (2, 8, cfg.hidden_size))
    got, stats = modeling.decoder_layer(x, p, cfg)
    assert stats is None
    want = x + ssm.block(modeling.norm(x, p["attn_norm"], cfg), p["ssm"], cfg)
    close(got, want, 1e-6)
    note = modeling.layer_annotations(cfg, kind="ssm", mlp=False)
    assert set(note) == {"attn_norm", "ssm"}


def test_relu2_experts_are_down_of_relu_up_squared():
    """One token through the expert layer by hand: the chosen experts'
    ``down(relu(up x)^2)`` under their weights, plus the shared expert as it is."""
    cfg = small_cfg()
    params, _ = seeded(cfg)
    mlp = params["layers"][0]["mlp"]
    assert mlp["w1"].shape == (8, 24, 32) == mlp["w2"].shape  # both (E, f, h)
    x = jax.random.normal(jax.random.key(6), (1, 3, cfg.hidden_size))
    got = moe.moe_topk_block(x, mlp, cfg)[0]
    xt = x.reshape(3, -1)
    s = jax.nn.sigmoid(xt @ mlp["router"]["w"])
    _, idx = jax.lax.top_k(s + mlp["router"]["bias"], 2)
    want = jnp.square(jax.nn.relu(xt @ mlp["shared"]["w1"])) @ mlp["shared"]["w2"]
    for t in range(3):
        picked = s[t, idx[t]]
        for e, w in zip(idx[t], 2.5 * picked / picked.sum()):
            want = want.at[t].add(w * (jnp.square(jax.nn.relu(mlp["w1"][e] @ xt[t])) @ mlp["w2"][e]))
    close(got[0], want, 1e-5)
    with jax.default_matmul_precision("highest"):
        fw = ARCH.published_weights(params, ref_cfg(cfg))["blocks"][1]
        close(got, ARCH.experts(x, fw, ref_cfg(cfg)), 1e-5)


def test_these_shapes_take_the_plain_held_path():
    """Un-gated experts, and nemotron_h's sizes twice over, lie outside the bounded body."""
    assert moe_held.held_path(2688, 1856, jnp.bfloat16, gated=False) == "worst_case"
    assert moe_held.held_path(2688, 1856, jnp.bfloat16) == "worst_case"  # 21 lane tiles; 14.5
    assert moe_held.held_path(2048, 1536, jnp.bfloat16, gated=False) == "worst_case"
    assert moe_held.held_path(2048, 1536, jnp.bfloat16) == "bounded"
    cut = PRESETS["nemotron-3-nano-30b-a3b"].replace(num_layers=15, moe_share=(0, 4))
    assert moe.held_path_counts(cut) == {"bounded": 0, "worst_case": 11}
    assert moe.layer_row_tile(cut, 64) == 16 and moe.layer_row_tile(cut, 1024) == 32


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """The four shares' routed parts plus the shared expert counted ONCE add up to what
    the uncut reference gives for the whole layer."""
    whole = small_cfg()
    params, _ = seeded(whole)
    mlp = params["layers"][0]["mlp"]
    y = jax.random.normal(jax.random.key(5), (2, 24, whole.hidden_size))
    want = moe.moe_topk_block(y, mlp, whole)[0]
    shared = moe._shared_ungated(y.reshape(-1, whole.hidden_size), mlp["shared"]).reshape(y.shape)
    total = shared
    for rank in range(4):
        cut = whole.replace(moe_share=(rank, 4))
        mine = dict(mlp, **{k: mlp[k][rank * 2:(rank + 1) * 2] for k in ("w1", "w2")})
        total = total + moe.moe_topk_block(y, mine, cut)[0] - shared
    close(total, want, F32_TOL)
    rc = ref_cfg(whole)
    fw = ARCH.published_weights(params, rc)["blocks"][1]
    with jax.default_matmul_precision("highest"):
        close(want[:1], ARCH.experts(y[:1], fw, rc), F32_TOL)
        # and one rank's part is the reference's at that rank
        part = dict(fw, up_proj=fw["up_proj"][4:6], down_proj=fw["down_proj"][4:6])
        mine = dict(mlp, **{k: mlp[k][4:6] for k in ("w1", "w2")})
        close(moe.moe_topk_block(y, mine, whole.replace(moe_share=(2, 4)))[0][:1],
              ARCH.experts(y[:1], part, ref_cfg(whole, (2, 4))), F32_TOL)


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
]


test_build_runtime_refuses_by_name = harness.refuses(REFUSALS, small_cfg)


def test_the_runtime_trains_it_on_one_device():
    harness.trains_on_one_device(small_cfg(max_seq_len=32), steps=8, drop=0.1)
