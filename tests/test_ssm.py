"""Hybrid stacks (granitemoehybrid-class: Mamba-2 layers beside NoPE GQA
attention, the scalar multipliers) on the normal path, against the plain
reference ``benchmark/references/granitemoehybrid.py`` on seeded random weights,
at a small size on the CPU; the chunked SSD scan against the recurrence it
computes; and tests that fail on the likely mistakes (a decay in bf16, a conv
that sees the future, a scan that forgets its state at a chunk boundary, a
multiplier left out)."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference
from galvatron_tpu.analysis.plan_check import check_plan
from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models import modeling, ssm
from galvatron_tpu.models.modeling import PRESETS
from galvatron_tpu.ops import ssd
from galvatron_tpu.parallel.hybrid import build_runtime
from galvatron_tpu.parallel.mesh import build_mesh
from tests import _stack_harness as harness
from tests._stack_harness import forward, highest_precision, on_a_chip  # noqa: F401  (a fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = reference.load(ROOT, "granitemoehybrid")

# float32, the same arithmetic in another order: the program computes the scan
# in chunks (a masked (L, L) product, one carried state a chunk), the reference
# one position at a time; the program's softmax is over whole rows, the
# reference's by blocks of queries. Differences are a few float32 ulps of the
# largest element a sum went through. 2e-5 of a tensor's largest magnitude
# leaves room for seven layers and would not pass any bf16 intermediate, decay
# or state (2^-8 = 4e-3; test_a_bf16_decay_or_state_fails_the_tolerance)
F32_TOL = 2e-5
# gradients go through every layer twice and sum over tokens: ten times that
GRAD_TOL = 2e-4
# bf16 compute against the float32 reference: 8 bits an activation, seven
# layers and the head stack a few roundings
BF16_TOL = 3e-2

KINDS = ("ssm",) * 5 + ("attention", "ssm")


def small_cfg(**kw):
    """Seven layers of the published pattern (so an attention layer sits between
    state-space layers), 4 query / 2 key-value heads, 8 state-space heads of 16,
    state 16, chunks of 16 over 72 positions: 4.5 chunks, so the carry crosses
    four boundaries and the sequence is padded."""
    base = dict(vocab_size=96, hidden_size=64, num_layers=7, num_heads=4, num_kv_heads=2,
                ffn_dim=96, max_seq_len=72, ssm_heads=8, ssm_head_dim=16, ssm_state=16,
                ssm_chunk=16, dtype=jnp.float32)
    base.update(kw)
    return PRESETS["granite-4.0-h-micro"].replace(**base)


def ref_cfg(cfg, share=None):
    return {"hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.kv_heads, "attention_multiplier": cfg.attention_multiplier,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier, "logits_scaling": cfg.logits_scaling,
            "rms_norm_eps": cfg.norm_eps, "shared_intermediate_size": cfg.ffn,
            "mamba_n_heads": cfg.ssm_heads, "mamba_d_head": cfg.ssm_head_dim,
            "mamba_d_state": cfg.ssm_state, "mamba_n_groups": cfg.ssm_groups,
            "mamba_d_conv": cfg.ssm_conv, "mamba_chunk_size": cfg.ssm_chunk,
            "num_hidden_layers": cfg.num_layers, "vocab_size": cfg.vocab_size,
            "layer_types": ["mamba" if k == "ssm" else "attention" for k in cfg.kinds]}


#: every vector (norm scales, conv bias, A_log, D, dt_bias) 0.3 off its initial value, and
#: rows with the last position's target
seeded = functools.partial(harness.seeded, spread=0.3, targets=True)
#: differences as a share of the largest magnitude alone (gradients and blocks far under 1)
close = functools.partial(harness.close, floor=0.0)
pytestmark = pytest.mark.usefixtures("highest_precision")


def ref_logits(params, rows, cfg):
    return harness.reference(ARCH, ref_cfg, cfg).logits(params, rows)


def reference_loss(params, rows, cfg):
    return harness.reference(ARCH, ref_cfg, cfg).objective(params, rows)[0]


# -- the model against the reference -----------------------------------------


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmark", "references", "granitemoehybrid.py")).read()
    assert "galvatron_tpu" not in src and "import ssd" not in src


def test_preset_is_the_published_configuration():
    cfg = PRESETS["granite-4.0-h-micro"]
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.head_dim, cfg.ffn, cfg.vocab_size,
            cfg.num_layers, cfg.max_seq_len) == (2048, 32, 8, 64, 8192, 100352, 40, 131072)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv,
            cfg.ssm_chunk) == (64, 64, 128, 1, 4, 256)
    assert (cfg.attention_multiplier, cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.norm_eps) == (0.015625, 12.0, 0.22, 8.0, 1e-5)
    assert (cfg.pos_embed, cfg.tie_word_embeddings, cfg.act_fn, cfg.norm_type, cfg.use_bias) == (
        "nope", True, "swiglu", "rms", False)
    assert [i for i, k in enumerate(cfg.kinds) if k == "attention"] == [5, 15, 25, 35]
    assert ssm.ssm_dims(cfg) == (4096, 4352, 8512)
    from galvatron_tpu.models import granite  # the family's module entry

    assert granite.DEFAULT_MODEL == "granite-4.0-h-micro" and set(granite.SIZES) <= set(PRESETS)


def test_num_layers_truncates_the_pattern_and_the_parameters_count_as_reckoned():
    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args
    from galvatron_tpu.search.theoretical import layer_param_count, total_param_count

    ns = initialize_galvatron("train", ["--model_size", "granite-4.0-h-micro", "--num_layers",
                                        "10", "--vocab_size", "25088"])
    cfg = model_config_from_args(ns)
    assert cfg.kinds == ("ssm",) * 5 + ("attention",) + ("ssm",) * 4
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))  # noqa: E731
    assert (count(shapes["layers"][0]), count(shapes["layers"][5])) == (76182976, 60821504)
    assert (count(shapes["layers"][0]["ssm"]), count(shapes["layers"][5]["attn"])) == (
        25847232, 10485760)
    assert count(shapes) == 797850560 == total_param_count(cfg)
    assert layer_param_count(cfg, kind="ssm") == 76182976
    assert "pos" not in shapes["embed"] and "head" not in shapes  # no table, tied head
    with pytest.raises(ValueError, match="layer_kinds has 40 entries for 41 layers"):
        cfg.replace(num_layers=41).kinds


def test_logits_and_loss_match_the_reference_in_float32():
    cfg = small_cfg()
    assert cfg.kinds == KINDS
    params, rows = seeded(cfg)
    close(forward(params, rows[:, :-1], cfg), ref_logits(params, rows[:, :-1], cfg), F32_TOL)
    assert float(harness.lm_loss(params, rows, cfg)) == pytest.approx(
        float(reference_loss(params, rows, cfg)), rel=F32_TOL)


def test_every_gradient_matches_the_reference_in_float32():
    cfg = small_cfg()
    params, rows = seeded(cfg, seed=5)
    got = harness.every_gradient_matches(params, rows, cfg, harness.reference(ARCH, ref_cfg, cfg),
                                         GRAD_TOL)
    # every parameter of both kinds of layer is reached
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(got))


def test_bf16_compute_stays_within_what_bf16_warrants():
    cfg = small_cfg(dtype=jnp.bfloat16)
    params, rows = seeded(cfg)
    harness.bf16_stays_within(cfg, params, rows[:, :-1], ref_logits, BF16_TOL, F32_TOL)


@pytest.mark.parametrize("field,value", [
    ("attention_multiplier", None), ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("logits_scaling", 1.0), ("pos_embed", "rope")])
def test_each_departure_from_a_plain_decoder_shows(field, value):
    """Dropping one multiplier, or adding rotary positions (one attention layer
    of seven, its branch x 0.22: the smallest of the five, 7e-4), moves the
    logits far outside the tolerance: the parity above holds the program to each."""
    cfg = small_cfg()
    params, rows = seeded(cfg)
    want = forward(params, rows[:, :-1], cfg)
    got = forward(params, rows[:, :-1], cfg.replace(**{field: value}))
    err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert err > 20 * F32_TOL


def test_one_sequence_loss_picks_by_select_and_equals_the_gather():
    """A batch of one sequence takes the select-and-sum pick (no flattened
    scatter in its backward): same loss, same gradient, same count as the gather
    the other batch sizes take, ignored labels included."""
    logits = jax.random.normal(jax.random.key(0), (2, 24, 40))
    labels = jax.random.randint(jax.random.key(1), (2, 24), 0, 40).at[:, 5].set(-100)

    def both(lg):
        return sum(modeling.cross_entropy_sum(lg[i:i + 1], labels[i:i + 1])[0] for i in range(2))

    one, grad_one = jax.value_and_grad(both)(logits)
    two, grad_two = jax.value_and_grad(lambda lg: modeling.cross_entropy_sum(lg, labels)[0])(logits)
    assert float(one) == pytest.approx(float(two), rel=1e-6)
    close(grad_one, grad_two, 1e-6)
    assert int(modeling.cross_entropy_sum(logits[:1], labels[:1])[1]) == 23


# -- the scan and the conv ------------------------------------------------------


def ssd_sequential(x, dt, a, b_mat, c_mat):
    """The recurrence itself, one position at a time in float32: what
    ``ssd.ssd_scan`` must equal."""
    f32 = jnp.float32
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2:]
    r = h // g
    x32 = (x.astype(f32) * dt.astype(f32)[..., None]).reshape(bsz, s, g, r, p)
    dec = jnp.exp(dt.astype(f32) * a.astype(f32)).reshape(bsz, s, g, r)

    def step(state, inp):
        d_t, x_t, b_t, c_t = inp
        state = state * d_t[..., None, None] + x_t[..., None] * b_t[:, :, None, None, :]
        return state, jnp.einsum("bgrpn,bgn->bgrp", state, c_t)

    _, y = jax.lax.scan(
        step, jnp.zeros((bsz, g, r, p, n), f32),
        tuple(jnp.moveaxis(t_, 1, 0) for t_ in (dec, x32, b_mat.astype(f32), c_mat.astype(f32))))
    return jnp.moveaxis(y, 0, 1).reshape(bsz, s, h, p).astype(x.dtype)


def scan_inputs(seed=0, b=2, s=72, h=4, p=8, g=2, n=16, slow=1.0):
    """``slow`` scales the decay rates: over chunks of 128 and 256 positions the
    summed log-decays of a = -1..-4 reach hundreds, where float32 itself
    resolves a decay to 1e-4 and every order of summation differs by that."""
    k = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(k[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, h)) + 1.0)
    a = -slow * jnp.arange(1, h + 1, dtype=jnp.float32)
    return x, dt, a, jax.random.normal(k[2], (b, s, g, n)), jax.random.normal(k[3], (b, s, g, n))


@pytest.mark.parametrize("chunk", [8, 16, 72, 256])
def test_chunked_scan_equals_the_recurrence_across_chunk_boundaries(chunk):
    """Chunks of 8 and 16 over 72 positions (9 and 4.5 chunks: the carry and
    the padding), one chunk exactly, and a chunk longer than the sequence."""
    args = scan_inputs()
    close(ssd.ssd_scan(*args, chunk), ssd_sequential(*args), F32_TOL)


def test_chunked_scan_gradients_equal_the_recurrences():
    args = scan_inputs(seed=3)
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    got = jax.grad(lambda *t: jnp.sum(ssd.ssd_scan(*t, 16) * w), argnums=range(5))(*args)
    want = jax.grad(lambda *t: jnp.sum(ssd_sequential(*t) * w), argnums=range(5))(*args)
    for g, v in zip(got, want):
        close(g, v, F32_TOL)


def test_scan_carries_its_state_over_a_chunk_boundary():
    """An impulse in the first chunk is still read in the last one (slow decay),
    and an output never depends on a later input."""
    x, dt, a, b_mat, c_mat = scan_inputs(h=2, g=1)
    a = jnp.full_like(a, -0.01)
    x0 = jnp.zeros_like(x).at[:, 3].set(1.0)
    y = ssd.ssd_scan(x0, dt, a, b_mat, c_mat, 8)
    assert float(jnp.abs(y[:, :3]).max()) == 0.0  # nothing before the impulse
    assert float(jnp.abs(y[:, 64:]).max()) > 1e-3  # eight chunks later
    moved = ssd.ssd_scan(x.at[:, 40:].add(1.0), dt, a, b_mat, c_mat, 8)
    assert float(jnp.abs(moved[:, :40] - ssd.ssd_scan(x, dt, a, b_mat, c_mat, 8)[:, :40]).max()) == 0.0


@pytest.mark.parametrize("scan,sizes,chunk", [
    ("plain", {}, 16), ("fused", dict(s=256, p=64, n=128, slow=0.25), 128)])
def test_a_bf16_decay_or_state_fails_the_tolerance(scan, sizes, chunk):
    """The tolerance has power over what the issue names: a decay exponent or a
    carried state rounded to bf16 lands far outside it. Both bodies: the plain
    one at the small sizes, the fused kernels at sizes inside their envelope."""
    fn = {"plain": ssd.ssd_scan_plain, "fused": ssd.ssd_scan_fused}[scan]
    x, dt, a, b_mat, c_mat = scan_inputs(**sizes)
    want = np.asarray(ssd_sequential(x, dt, a, b_mat, c_mat))
    close(fn(x, dt, a, b_mat, c_mat, chunk), want, F32_TOL)
    lossy = fn(x, dt.astype(jnp.bfloat16).astype(jnp.float32), a, b_mat, c_mat, chunk)
    assert np.abs(np.asarray(lossy) - want).max() / np.abs(want).max() > 50 * F32_TOL
    # bf16 compute keeps float32 decays and states: its error is the operands' 8 bits
    half = fn(x.astype(jnp.bfloat16), dt, a, b_mat.astype(jnp.bfloat16),
              c_mat.astype(jnp.bfloat16), chunk)
    assert half.dtype == jnp.bfloat16
    close(half.astype(jnp.float32), want, BF16_TOL)


# sizes inside the fused kernels' envelope, small enough for the interpreter:
# a state carried over chunk boundaries, a sequence the chunk does not divide
# (padded), two groups (head blocks of 2, two heads a lane tile), one group of
# four heads, heads of 128 (one a tile), chunks of 128 and 256
FUSED_CASES = {
    "3_chunks_2_groups": (dict(s=384, h=4, p=64, g=2, n=128), 128),
    "padded_300": (dict(s=300, h=4, p=64, g=2, n=128), 128),
    "chunk_256_1_group": (dict(s=512, h=4, p=64, g=1, n=128), 256),
    "heads_of_128": (dict(s=256, h=2, p=128, g=1, n=128), 128),
}


def fused_case(name, seed=0):
    sizes, chunk = FUSED_CASES[name]
    return scan_inputs(seed=seed, slow=0.05, **sizes), chunk


def test_the_fused_cases_lie_inside_the_envelope(monkeypatch):
    on_a_chip(monkeypatch)
    for sizes, chunk in FUSED_CASES.values():
        assert ssd.scan_path(sizes["h"], sizes["p"], sizes["g"], sizes["n"], chunk,
                             jnp.float32) == "fused", sizes


@pytest.mark.parametrize("case", list(FUSED_CASES))
def test_fused_scan_equals_the_recurrence_and_the_plain_scan(case):
    args, chunk = fused_case(case)
    got = ssd.ssd_scan_fused(*args, chunk)
    assert got.shape == args[0].shape and got.dtype == jnp.float32
    close(got, ssd_sequential(*args), F32_TOL)
    close(got, ssd.ssd_scan_plain(*args, chunk), F32_TOL)


@pytest.mark.parametrize("case", list(FUSED_CASES))
def test_fused_scan_gradients_equal_the_recurrences(case):
    """x, dt, a, B and C (D: test_the_mixer_through_the_kernels...), plain and
    under ``jax.checkpoint``, whose replayed forward keeps the same residuals."""
    args, chunk = fused_case(case, seed=3)
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    loss = lambda *t: jnp.sum(ssd.ssd_scan_fused(*t, chunk) * w)  # noqa: E731
    got = jax.grad(loss, argnums=range(5))(*args)
    want = jax.grad(lambda *t: jnp.sum(ssd_sequential(*t) * w), argnums=range(5))(*args)
    for name, g, v in zip("x dt a B C".split(), got, want):
        try:
            close(g, v, F32_TOL)
        except AssertionError as e:
            raise AssertionError(f"d {name}: {e}") from None
    again = jax.grad(jax.checkpoint(loss), argnums=range(5))(*args)
    for g, v in zip(again, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(v))


def test_fused_scan_in_bf16_stays_within_what_bf16_warrants():
    """y and every gradient of the bf16 kernels against the float32 recurrence
    on the same (rounded) operands; no worse than the plain body's by more
    than a factor of two (d a once was 18 times worse: a cancelling pair of sums
    taken from differently rounded operands)."""
    args, chunk = fused_case("chunk_256_1_group", seed=4)
    half = lambda t: t.astype(jnp.bfloat16)  # noqa: E731
    lo = (half(args[0]), args[1], args[2], half(args[3]), half(args[4]))
    ref = tuple(t.astype(jnp.float32) for t in lo)
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    want = jax.grad(lambda *t: jnp.sum(ssd_sequential(*t) * w), argnums=range(5))(*ref)

    def errors(fn):
        got = jax.grad(lambda *t: jnp.sum(fn(*t, chunk).astype(jnp.float32) * w),
                       argnums=range(5))(*lo)
        return [float(np.abs(np.asarray(g, np.float64) - np.asarray(v, np.float64)).max()
                      / np.abs(np.asarray(v)).max()) for g, v in zip(got, want)]

    fused, plain = errors(ssd.ssd_scan_fused), errors(ssd.ssd_scan_plain)
    assert max(fused) < BF16_TOL and all(f < 2 * p_ + 1e-3 for f, p_ in zip(fused, plain)), (
        fused, plain)
    close(ssd.ssd_scan_fused(*lo, chunk).astype(jnp.float32), ssd_sequential(*ref), BF16_TOL)


def test_outside_the_envelope_the_plain_scan_runs_bit_for_bit(monkeypatch):
    """Heads of 8 and states of 16 (the small configurations of this file), a
    chunk of 72, float16: `scan_path` says plain even where a chip is there, and
    `ssd_scan` is then `ssd_scan_plain` to the bit."""
    on_a_chip(monkeypatch)
    assert ssd.scan_path(4, 8, 2, 16, 16, jnp.float32) == "plain"
    assert ssd.scan_path(64, 64, 1, 128, 72, jnp.bfloat16) == "plain"  # chunk
    assert ssd.scan_path(64, 64, 1, 64, 256, jnp.bfloat16) == "plain"  # state
    assert ssd.scan_path(64, 32, 1, 128, 256, jnp.bfloat16) == "plain"  # head size
    assert ssd.scan_path(6, 64, 2, 128, 256, jnp.bfloat16) == "plain"  # 3 heads a group
    assert ssd.scan_path(64, 64, 1, 128, 256, jnp.float16) == "plain"  # dtype
    assert ssd.scan_path(64, 64, 1, 128, 256, jnp.bfloat16) == "fused"
    args = scan_inputs()
    np.testing.assert_array_equal(np.asarray(ssd.ssd_scan(*args, 16)),
                                  np.asarray(ssd.ssd_scan_plain(*args, 16)))


def test_scan_path_counts_the_layers_of_a_configuration(monkeypatch):
    """What the trainer writes as ``ssm_scan_path`` (fingerprint and the
    ``build_runtime`` span): 9 / 0 for one granite period on a TPU, every
    state-space layer plain on the CPU and for the small configurations, 0 / 0
    for a stack without such layers."""
    granite = PRESETS["granite-4.0-h-micro"].replace(num_layers=10, max_seq_len=8192,
                                                     dtype=jnp.bfloat16)
    assert ssm.path_counts(granite)["scan"] == {"fused": 0, "plain": 9}  # no chip here
    on_a_chip(monkeypatch)
    assert ssm.path_counts(granite)["scan"] == {"fused": 9, "plain": 0}
    assert ssm.path_counts(small_cfg())["scan"] == {"fused": 0, "plain": 6}
    assert ssm.path_counts(PRESETS["llama-7b"])["scan"] == {"fused": 0, "plain": 0}


# -- the served state's kernels at one wide group (PR 70) -----------------------------------

#: (heads, head size, groups, state): Granite 4.0-H Small's ONE group of 8192 lanes (4 MiB a
#: row and layer) and nemotron_h's 8 groups of 512 (2 MiB)
STATE_SHAPES = {"one_group_of_8192": (128, 64, 1, 128), "eight_groups_of_512": (64, 64, 8, 128)}


def test_one_wide_group_is_one_block_of_the_step():
    """A grid step of `ssm_step` takes whole groups: 4 of nemotron_h's 8 (1 MiB), and the
    ONE group of 8192 lanes whole (4 MiB: read on the chip as fast as lane blocks inside
    the group, PERF.md 6, PR 70, so no lane-block axis exists)."""
    assert ssd._step_groups(8, 512, 128) == 4 and ssd._step_groups(1, 8192, 128) == 1
    assert ssd._step_groups(1, 4096, 128) == 1  # granite-4.0-h-micro's, were it served
    assert [ssd.step_path(*sizes) for sizes in STATE_SHAPES.values()] == ["plain"] * 2  # the CPU's


@pytest.mark.parametrize("shape", list(STATE_SHAPES))
def test_the_step_kernel_is_the_plain_step_at_the_published_shapes(shape):
    """`ssm_step` (interpreted here, called as a chip calls it) against the plain body at
    the two published shapes, over a stack of two layers in place: a row that has not
    started reads zero, the other layer's states stay."""
    h, p, g, n = STATE_SHAPES[shape]
    ks = jax.random.split(jax.random.key(7), 5)
    stack = jax.random.normal(ks[0], (2, 2) + ssd.state_shape(h, p, n))
    decay = jnp.repeat(jnp.where(jnp.array([True, False])[:, None], jnp.exp(
        -jax.nn.softplus(jax.random.normal(ks[1], (2, h)))), 0.0), p, axis=1)
    dtx = jax.random.normal(ks[2], (2, h * p))
    b, c = jax.random.normal(ks[3], (2, g, n)), jax.random.normal(ks[4], (2, g, n))
    want_y, want = ssd.ssd_step_plain(stack, 1, decay, dtx, b, c)
    got_y, got = ssd._step_call(stack, 1, decay, dtx, b, c)
    harness.close(got_y, want_y, 1e-6)
    harness.close(got, want, 1e-6)
    assert np.array_equal(np.asarray(got[0]), np.asarray(stack[0]))


@pytest.mark.parametrize("shape", list(STATE_SHAPES))
def test_the_row_kernels_are_the_slices_at_the_published_shapes(monkeypatch, shape):
    """`ssm_state_read` / `ssm_state_write` (interpreted) against ``dynamic_slice`` /
    ``dynamic_update_slice`` at the two published shapes: a row out and in, bit for bit,
    every other row and layer as it was."""
    h, p, _, n = STATE_SHAPES[shape]
    stack = jax.random.normal(jax.random.key(9), (2, 3, n, h * p))
    monkeypatch.setattr(ssd, "rows_path", lambda *a: "kernel")
    got = ssd.read_rows(stack, 1, jnp.int32(2), 1)
    assert np.array_equal(np.asarray(got), np.asarray(stack[1, 2:3]))
    new = jax.random.normal(jax.random.key(10), (1, n, h * p))
    out = ssd.write_rows(stack, 1, jnp.int32(2), new)
    assert np.array_equal(np.asarray(out), np.asarray(stack.at[1, 2:3].set(new)))


def test_step_path_counts_the_layers_of_a_configuration(monkeypatch):
    """``ssm_step_path`` (fingerprint, the ``build_runtime`` span, `Engine.stats`): which
    body a served row's single step takes, by `ops/ssd.step_path`."""
    small = PRESETS["granite-4.0-h-small"].replace(num_layers=10)
    nemotron = PRESETS["nemotron-3-nano-30b-a3b"].replace(num_layers=15)
    assert ssm.path_counts(small)["step"] == {"fused": 0, "plain": 9}  # no chip here
    on_a_chip(monkeypatch)
    assert ssm.path_counts(small)["step"] == {"fused": 9, "plain": 0}
    assert ssm.path_counts(nemotron)["step"] == {"fused": 12, "plain": 0}
    assert ssm.path_counts(small_cfg())["step"] == {"fused": 6, "plain": 0}  # 8 heads of 16
    assert ssm.path_counts(small_cfg(ssm_head_dim=8))["step"] == {"fused": 0, "plain": 6}
    from galvatron_tpu.models import mixers

    assert mixers.path_counts(PRESETS["llama-7b"])["ssm_step_path"] == {"fused": 0, "plain": 0}


def test_the_mixer_through_the_kernels_equals_the_mixer_through_the_plain_scan(monkeypatch):
    """`ssm.block` at sizes inside the envelope: output and the gradient of every
    parameter (D's skip and the conv in front included) agree between the two
    bodies, in float32."""
    cfg = small_cfg(num_layers=1, ssm_heads=4, ssm_head_dim=64, ssm_state=128, ssm_chunk=128,
                    max_seq_len=256)
    p = ssm.init_params(jax.random.key(0), cfg)
    p = {k: v + 0.3 * jax.random.normal(jax.random.key(i), v.shape) if v.ndim == 1 else v
         for i, (k, v) in enumerate(p.items())}
    x = jax.random.normal(jax.random.key(7), (2, 256, cfg.hidden_size))
    w = jax.random.normal(jax.random.key(8), x.shape)
    run = jax.value_and_grad(lambda x_, p_: jnp.sum(ssm.block(x_, p_, cfg) * w), argnums=(0, 1))
    plain = run(x, p)
    monkeypatch.setattr(ssm, "ssd_scan", ssd.ssd_scan_fused)
    fused = run(x, p)
    assert float(fused[0]) == pytest.approx(float(plain[0]), rel=F32_TOL)
    harness.close_by_leaf(fused[1], plain[1], GRAD_TOL, floor=0.0)
    assert float(jnp.abs(fused[1][1]["D"]).max()) > 0


def test_causal_conv_is_the_published_conv1d_and_leaks_nothing_from_the_future():
    k = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(k[0], (2, 20, 6))
    w, b = jax.random.normal(k[1], (4, 6)), jax.random.normal(k[2], (6,))
    y = ssd.causal_conv1d(x, w, b)
    # torch's conv1d(padding=3)[..., :s] with weight (C, 1, 4): a correlation
    xp = np.pad(np.asarray(x), ((0, 0), (3, 0), (0, 0)))
    want = sum(xp[:, j:j + 20] * np.asarray(w)[j] for j in range(4)) + np.asarray(b)
    close(y, want, 1e-6)
    later = ssd.causal_conv1d(x.at[:, 11:].add(5.0), w, b)
    assert float(jnp.abs(later[:, :11] - y[:, :11]).max()) == 0.0
    assert float(jnp.abs(later[:, 11] - y[:, 11]).max()) > 0.0
    # the first position sees itself through the last tap only
    close(y[:, 0], np.asarray(x)[:, 0] * np.asarray(w)[3] + np.asarray(b), 1e-6)


# -- the conv + SiLU as one op (`ssd.conv_silu_fused`, interpreted here) ---------

# (batch, positions, width of the array, first column, channels, taps): one block
# of three strips; three blocks of 1024 with the last one padded (the halo crosses
# two boundaries, forward and backward); whole blocks exactly; batch 2 with two
# taps; a window in the middle of a wider array, as the mixer reads in_proj's output
CONV_CASES = {
    "one_block": (1, 96, 128, 0, 128, 4),
    "three_blocks_padded": (1, 2100, 128, 0, 128, 4),
    "two_whole_blocks": (1, 2048, 128, 0, 128, 4),
    "batch_2_two_taps": (2, 1100, 256, 0, 256, 2),
    "window_of_a_wider_array": (2, 160, 900, 256, 512, 4),
}


def conv_case(name, dtype=jnp.float32, seed=0):
    bsz, s, width, col0, channels, k = CONV_CASES[name]
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (bsz, s, width), dtype)
    w = jax.random.uniform(ks[1], (k, channels), jnp.float32, -0.5, 0.5)
    b = 0.3 * jax.random.normal(ks[2], (channels,))
    return (x, w, b), col0, jax.random.normal(ks[3], (bsz, s, channels))


def plain_conv_silu(x, w, b, col0=0):
    return jax.nn.silu(ssd.causal_conv1d(x[..., col0:col0 + w.shape[1]], w, b))


def published_conv_silu(x, w, b, col0=0):
    """torch's ``silu(conv1d(x, weight (C, 1, K), bias, padding=K-1)[..., :s])`` in
    float64 numpy: a correlation over the sequence."""
    x, w, b = (np.asarray(t, np.float64) for t in (x, w, b))
    k, s = w.shape[0], x.shape[1]
    xp = np.pad(x[..., col0:col0 + w.shape[1]], ((0, 0), (k - 1, 0), (0, 0)))
    pre = sum(xp[:, j:j + s] * w[j] for j in range(k)) + b
    return pre / (1.0 + np.exp(-pre))


@pytest.mark.parametrize("case", list(CONV_CASES))
def test_fused_conv_equals_the_plain_conv_and_the_published_conv1d(case):
    args, col0, _ = conv_case(case)
    got = ssd.conv_silu_fused(*args, col0)
    assert got.shape == (*args[0].shape[:2], args[1].shape[1]) and got.dtype == jnp.float32
    close(got, plain_conv_silu(*args, col0), 1e-6)
    close(got, published_conv_silu(*args, col0), 1e-6)


@pytest.mark.parametrize("case", list(CONV_CASES))
def test_fused_conv_gradients_equal_the_plain_convs(case):
    """x (set into the wider array with zeros around it), w and b, to 1e-5 in
    float32; the same bits again under ``jax.checkpoint``."""
    args, col0, cot = conv_case(case, seed=3)
    loss = lambda *t: jnp.sum(ssd.conv_silu_fused(*t, col0) * cot)  # noqa: E731
    got = jax.grad(loss, argnums=(0, 1, 2))(*args)
    want = jax.grad(lambda *t: jnp.sum(plain_conv_silu(*t, col0) * cot), argnums=(0, 1, 2))(*args)
    for name, g, v in zip("x w b".split(), got, want):
        assert g.shape == v.shape and g.dtype == v.dtype
        try:
            close(g, v, 1e-5)
        except AssertionError as e:
            raise AssertionError(f"d {name}: {e}") from None
    again = jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2))(*args)
    for g, v in zip(again, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(v))


def test_fused_conv_in_bf16_stays_within_what_bf16_warrants():
    """y and the three gradients of the bf16 kernels against float32 on the same
    (rounded) operands: inside the bf16 tolerance and no worse than the plain
    path, which rounds the conv's result before the SiLU where the op rounds once."""
    args, col0, cot = conv_case("three_blocks_padded", jnp.bfloat16, seed=4)
    ref = (args[0].astype(jnp.float32), *args[1:])
    grads = lambda fn, a: jax.grad(  # noqa: E731
        lambda *t: jnp.sum(fn(*t, col0).astype(jnp.float32) * cot), argnums=(0, 1, 2))(*a)
    want = [plain_conv_silu(*ref)] + list(grads(plain_conv_silu, ref))

    def errors(fn):
        got = [fn(*args, col0)] + list(grads(fn, args))
        assert got[0].dtype == got[1].dtype == jnp.bfloat16
        return [float(np.abs(np.asarray(g, np.float64) - np.asarray(v, np.float64)).max()
                      / np.abs(np.asarray(v)).max()) for g, v in zip(got, want)]

    fused, plain = errors(ssd.conv_silu_fused), errors(plain_conv_silu)
    assert max(fused) < BF16_TOL and all(f < 1.5 * p_ + 1e-3 for f, p_ in zip(fused, plain)), (
        fused, plain)
    assert fused[0] > 1e-5, "a bf16 result inside the float32 tolerance: nothing was rounded"


@pytest.mark.parametrize("conv", ["plain", "fused"])
def test_neither_conv_leaks_from_the_future_across_a_block_boundary(conv):
    """`test_causal_conv_is_the_published_conv1d_...` on both paths at 128
    channels and 1100 positions: a change at position 1024 (the first row of the
    fused op's second block) moves nothing before it and moves that row; its
    gradient reaches back exactly K - 1 rows, over the boundary."""
    fn = {"plain": plain_conv_silu, "fused": ssd.conv_silu_fused}[conv]
    k = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(k[0], (2, 1100, 128))
    w, b = jax.random.normal(k[1], (4, 128)), jax.random.normal(k[2], (128,))
    y = fn(x, w, b)
    later = fn(x.at[:, 1024:].add(5.0), w, b)
    assert float(jnp.abs(later[:, :1024] - y[:, :1024]).max()) == 0.0
    assert float(jnp.abs(later[:, 1024] - y[:, 1024]).min()) > 0.0
    # the first position sees itself through the last tap only
    pre = np.asarray(x)[:, 0] * np.asarray(w)[3] + np.asarray(b)
    close(y[:, 0], pre / (1.0 + np.exp(-pre)), 1e-6)
    dx = jax.grad(lambda x_: jnp.sum(fn(x_, w, b)[:, 1024:1026]))(x)
    rows = np.flatnonzero(np.abs(np.asarray(dx)).max(axis=(0, 2)))
    assert rows.tolist() == list(range(1021, 1026))


def test_outside_the_envelope_the_plain_conv_runs_bit_for_bit(monkeypatch):
    """The small configurations of this file (64 + 16 + 16 channels), five taps,
    float16, a window that is no whole lane tile: `conv_path` says plain even
    where a chip is there, and `ssm.conv_split` is then `causal_conv1d` +
    ``jax.nn.silu`` on the sliced channels to the bit."""
    on_a_chip(monkeypatch)
    granite = ssm.conv_windows(PRESETS["granite-4.0-h-micro"])
    assert granite == (4096, 128, 128)
    assert ssd.conv_path(granite, 4, jnp.bfloat16) == "fused"
    assert ssd.conv_path(granite, 2, jnp.float32) == "fused"
    assert ssd.conv_path((8448, 256, 256), 4, jnp.float32) == "fused"
    assert ssd.conv_path(granite, 5, jnp.bfloat16) == "plain"  # taps
    assert ssd.conv_path(granite, 4, jnp.float16) == "plain"  # dtype
    assert ssd.conv_path((4096, 64, 64), 4, jnp.bfloat16) == "plain"  # B and C half a tile
    cfg = small_cfg()
    assert ssd.conv_path(ssm.conv_windows(cfg), cfg.ssm_conv, cfg.dtype) == "plain"
    d_inner, conv_dim, width = ssm.ssm_dims(cfg)
    k = jax.random.split(jax.random.key(2), 3)
    zxbcdt = jax.random.normal(k[0], (2, 72, width))
    w, b = jax.random.normal(k[1], (4, conv_dim)), jax.random.normal(k[2], (conv_dim,))
    xbc = jax.nn.silu(ssd.causal_conv1d(zxbcdt[..., d_inner:d_inner + conv_dim], w, b))
    got = ssm.conv_split(zxbcdt, w, b, cfg)
    assert [t.shape[-1] for t in got] == [128, 16, 16]
    np.testing.assert_array_equal(np.asarray(jnp.concatenate(got, axis=-1)), np.asarray(xbc))


def test_conv_path_counts_the_layers_of_a_configuration(monkeypatch):
    """What the trainer writes as ``ssm_conv_path`` beside ``ssm_scan_path``: 9 / 0
    for one granite period on a TPU, plain on the CPU and for the small
    configurations, 0 / 0 for a stack without state-space layers."""
    granite = PRESETS["granite-4.0-h-micro"].replace(num_layers=10, max_seq_len=8192,
                                                     dtype=jnp.bfloat16)
    assert ssm.path_counts(granite)["conv"] == {"fused": 0, "plain": 9}  # no chip here
    on_a_chip(monkeypatch)
    assert ssm.path_counts(granite)["conv"] == {"fused": 9, "plain": 0}
    assert ssm.path_counts(small_cfg())["conv"] == {"fused": 0, "plain": 6}
    assert ssm.path_counts(PRESETS["llama-7b"])["conv"] == {"fused": 0, "plain": 0}


def test_the_mixer_through_the_fused_conv_equals_the_mixer_through_the_plain_one(monkeypatch):
    """`ssm.block` at sizes inside the conv's envelope (256 + 128 + 128 channels
    read as three windows out of in_proj's 772 columns): output and the gradient
    of every parameter agree between the two convs, in float32."""
    cfg = small_cfg(num_layers=1, ssm_heads=4, ssm_head_dim=64, ssm_state=128, ssm_chunk=128,
                    max_seq_len=256)
    assert ssm.ssm_dims(cfg) == (256, 512, 772)
    p = ssm.init_params(jax.random.key(0), cfg)
    p = {k: v + 0.3 * jax.random.normal(jax.random.key(i), v.shape) if v.ndim == 1 else v
         for i, (k, v) in enumerate(p.items())}
    x = jax.random.normal(jax.random.key(7), (2, 256, cfg.hidden_size))
    w = jax.random.normal(jax.random.key(8), x.shape)
    run = jax.value_and_grad(lambda x_, p_: jnp.sum(ssm.block(x_, p_, cfg) * w), argnums=(0, 1))
    plain = run(x, p)
    calls = []
    monkeypatch.setattr(ssm, "conv_path", lambda *a: "fused")
    monkeypatch.setattr(ssm, "conv_silu_fused", lambda *a, **kw: (
        calls.append(kw["col0"]), ssd.conv_silu_fused(*a, **kw))[1])
    fused = run(x, p)
    assert calls == [256, 512, 640]
    assert float(fused[0]) == pytest.approx(float(plain[0]), rel=F32_TOL)
    harness.close_by_leaf(fused[1], plain[1], GRAD_TOL, floor=0.0)
    assert float(jnp.abs(fused[1][1]["conv_b"]).max()) > 0


# -- the runtime: layouts, refusals, the search ---------------------------------


def test_runtime_trains_and_full_remat_changes_nothing():
    cfg = small_cfg()
    rows = np.asarray(jax.random.randint(jax.random.key(8), (4, cfg.max_seq_len + 1), 0,
                                         cfg.vocab_size, jnp.int32))
    runs = {}
    for name, n, strat in (
        ("one", 1, LayerStrategy()), ("remat", 1, LayerStrategy(ckpt="full")),
        ("zero3", 4, LayerStrategy(dp_type="zero3")),
    ):
        mesh, axes = build_mesh(pp=1, devices=jax.devices()[:n])
        hp = HybridParallelConfig(pp=1, layer_strategies=[strat] * cfg.num_layers,
                                  mixed_precision="fp32")
        rt = build_runtime(cfg, hp, mesh=mesh, axes=axes, global_batch_size=4,
                           seq_len=cfg.max_seq_len)
        state = rt.init_state(jax.random.key(0))
        losses = []
        for _ in range(3):
            state, loss = rt.train_step(state, rt.shard_batch(rows))
            losses.append(float(loss))
        runs[name] = losses
    assert runs["one"][2] < runs["one"][0]
    assert runs["remat"] == pytest.approx(runs["one"], rel=1e-6)
    assert runs["zero3"] == pytest.approx(runs["one"], rel=1e-4)


def test_tp_on_the_attention_layer_alone_trains_like_one_device():
    cfg = small_cfg()
    rows = np.asarray(jax.random.randint(jax.random.key(8), (4, cfg.max_seq_len + 1), 0,
                                         cfg.vocab_size, jnp.int32))
    losses = []
    for n, tp in ((1, 1), (4, 2)):
        mesh, axes = build_mesh(pp=1, devices=jax.devices()[:n])
        strat = [LayerStrategy(tp=tp) if k == "attention" else LayerStrategy() for k in cfg.kinds]
        rt = build_runtime(cfg, HybridParallelConfig(pp=1, layer_strategies=strat,
                                                     mixed_precision="fp32"),
                           mesh=mesh, axes=axes, global_batch_size=4, seq_len=cfg.max_seq_len)
        state = rt.init_state(jax.random.key(0))
        state, loss = rt.train_step(state, rt.shard_batch(rows))
        losses.append(float(loss))
    assert losses[1] == pytest.approx(losses[0], rel=1e-4)


EIGHT = dict(num_layers=8)  # 8 layers so that pp 2 divides them
REFUSALS = [
    ("tp", EIGHT, dict(tp=2, vocab_tp=1),
     r"tp>1.*state-space layers \(layers \[0, 1, 2, 3, 4, 6, 7\]"),
    ("cp", EIGHT, dict(cp=2), r"cp>1.*state-space"),
    ("pp", EIGHT, dict(pp=2, chunks=2), r"pp>1.*interleaved layer kinds"),
]
test_build_runtime_refuses_by_name = harness.refuses(REFUSALS, small_cfg, seq_len=72, batch=8,
                                                     devices=8)


@pytest.mark.parametrize("kw,code,named", [
    ({"tp": 2, "vocab_tp": 1}, "GTA019", "tp=2 on a state-space layer"),
    ({"cp": 2}, "GTA019", "cp=2 on a state-space layer"),
    ({"pp": 2, "chunks": 2}, "GTA020", "interleaved layer kinds"),
])
def test_check_plan_names_the_same_refusals(kw, code, named):
    cfg = small_cfg(num_layers=8)
    plan = functools.partial(harness.plan, cfg, mixed_precision="fp32")
    diags = check_plan(plan(**kw), model_config=cfg, world_size=8, global_bsz=8)
    hits = [d for d in diags if d.code == code]
    assert hits and all(named in d.message for d in hits)
    if code == "GTA019":  # one a state-space layer, none for the attention layer
        assert len(hits) == 7 and not any("layer 5:" in d.message for d in hits)
    # and a plan the runtime takes has neither
    ok = check_plan(plan(dp_type="zero3"), model_config=cfg, world_size=8, global_bsz=8)
    assert not [d for d in ok if d.code in ("GTA019", "GTA020")]


def test_serving_cache_keeps_the_recurrent_state_a_row():
    """(Until PR 68 `init_kv_cache` refused a state-space stack by name.)"""
    from galvatron_tpu.models.generation import init_kv_cache

    cfg = small_cfg()
    cache = init_kv_cache(cfg, 1, 16)
    layers = cfg.kinds.count("ssm")
    assert cache.state.scan.shape == (layers, 1, cfg.ssm_state, cfg.ssm_heads * cfg.ssm_head_dim)
    assert cache.state.scan.dtype == jnp.float32 and cache.state.conv.shape[:2] == (layers, 1)


def test_analytic_costs_price_the_two_kinds():
    from galvatron_tpu.search.theoretical import analytic_model_costs

    cfg = PRESETS["granite-4.0-h-micro"].replace(num_layers=10, vocab_size=25088,
                                                 attn_impl="flash")
    at = {s: analytic_model_costs(cfg, seq_len=s).layer_types for s in (4096, 8192)}
    assert sorted(at[8192]) == list(range(10))
    ssm8, attn8, ssm4, attn4 = at[8192][0], at[8192][5], at[4096][0], at[4096][5]
    assert at[8192][9] == ssm8 and ssm8 != attn8
    assert (round(ssm8.parameter_mb, 1), round(attn8.parameter_mb, 1)) == (304.7, 243.3)
    # a state-space layer's time is linear in the sequence, the attention layer's is not
    assert ssm8.fwd_ms_per_sample == pytest.approx(2 * ssm4.fwd_ms_per_sample, rel=1e-9)
    assert attn8.fwd_ms_per_sample > 2.1 * attn4.fwd_ms_per_sample
    # the kept score blocks (64 heads x 256 x 6 B a token) are in the activation count
    assert ssm8.activation_mb_per_sample[1] > attn8.activation_mb_per_sample[1] + 8192 * 64 * 256 * 5 / 1e6


def test_cli_search_prices_both_kinds_at_pp1_and_its_plan_passes_the_checker(tmp_path, capsys):
    """`cli search --model_size granite-4.0-h-micro --num_devices 4` on the whole
    40-layer model: a plan comes back with pp 1 and tp 1 on every layer, names what
    was left out, carries the two kinds' prices (remat differs by kind or not, but
    both types were in the tables), and passes `check-plan`."""
    from galvatron_tpu import cli
    from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace
    from galvatron_tpu.search.cost_model import ProfiledHardware
    from galvatron_tpu.search.theoretical import analytic_model_costs

    path = str(tmp_path / "plan.json")
    rc = cli.main(["search", "--model_size", "granite-4.0-h-micro", "--num_devices", "4",
                   "--seq_length", "8192", "--settle_bsz", "4", "--memory_constraint_gb", "15",
                   "--enable_cp", "1", "--output_config_path", path])
    assert rc in (0, None)
    said = capsys.readouterr().out
    assert "tp>1" in said and "cp>1" in said and "pp>1" in said and "analytically" in said
    plan = json.load(open(path))
    assert plan["pp_deg"] == 1 and set(plan["tp_sizes_enc"].split(",")) == {"1"}
    assert len(plan["tp_sizes_enc"].split(",")) == 40
    assert plan["search_restrictions"] == [
        "interleaved_layer_kinds_no_pp", "state_space_layers_no_cp", "state_space_layers_no_tp"]
    assert plan["model_config"]["num_layers"] == 40
    HybridParallelConfig.load(path).validate(4)
    assert cli.main(["check-plan", path]) in (0, None)
    # the engine's groups follow the published interleaving
    cfg = PRESETS["granite-4.0-h-micro"].replace(max_seq_len=8192)
    eng = SearchEngine(analytic_model_costs(cfg), ProfiledHardware(), num_layers=40,
                       space=SearchSpace(world_size=4), memory_budget_mb=15360.0, model_config=cfg)
    assert [(start, count) for start, count, _ in eng._type_groups()] == [
        (0, 5), (5, 1), (6, 9), (15, 1), (16, 9), (25, 1), (26, 9), (35, 1), (36, 4)]
    assert eng.space.max_tp == 1 and eng.space.pp_choices == [1] and not eng.space.allow_cp


def test_a_searched_plan_runs(tmp_path):
    """The plan `cli search` emits for a small hybrid stack on 4 devices goes
    through `build_runtime` and trains."""
    from galvatron_tpu import cli

    path = str(tmp_path / "plan.json")
    flags = ["--model_size", "granite-4.0-h-micro", "--num_layers", "7", "--hidden_size", "64",
             "--num_heads", "4", "--num_kv_heads", "2", "--ffn_dim", "96", "--vocab_size", "96",
             "--seq_length", "256"]
    assert cli.main(["search", *flags, "--num_devices", "4", "--settle_bsz", "4",
                     "--memory_constraint_gb", "15", "--output_config_path", path]) in (0, None)
    hp = HybridParallelConfig.load(path)
    cfg = PRESETS["granite-4.0-h-micro"].replace(
        num_layers=7, hidden_size=64, num_heads=4, num_kv_heads=2, ffn_dim=96, vocab_size=96,
        max_seq_len=256, ssm_heads=4, ssm_head_dim=16, ssm_state=16, ssm_chunk=64)
    mesh, axes = build_mesh(pp=1, devices=jax.devices()[:4])
    rt = build_runtime(cfg, hp, mesh=mesh, axes=axes, global_batch_size=4, seq_len=256)
    state = rt.init_state(jax.random.key(0))
    rows = np.asarray(jax.random.randint(jax.random.key(1), (4, 257), 0, 96, jnp.int32))
    state, first = rt.train_step(state, rt.shard_batch(rows))
    state, second = rt.train_step(state, rt.shard_batch(rows))
    assert np.isfinite(float(first)) and float(second) < float(first)
