"""sarvam_mla-class layers (latent attention over a latent slot cache, a sigmoid
router with a selection bias over a held share of the experts beside an ungated
shared expert, a leading dense layer, YaRN) on the normal path, against the plain
reference ``benchmark/references/sarvam_mla.py`` on seeded random weights, at a
small size on the CPU; the absorbed against the non-absorbed form; the shares of
the experts adding up to the uncut layer; bf16 weights held once; the engine end
to end; and each refusal by name."""

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference
from galvatron_tpu.core.optim import AdamConfig
from galvatron_tpu.models import generation, mixers, mla, modeling, moe
from galvatron_tpu.models.modeling import PRESETS
from galvatron_tpu.ops import mla_decode, mla_prefill
from galvatron_tpu.parallel.hybrid import build_runtime
from galvatron_tpu.parallel.mesh import build_mesh
from tests import _stack_harness as harness
from tests._stack_harness import (  # noqa: F401  (`retraced`: a fixture)
    close, forward, on_a_chip, retraced, seeded, small_tiles, through_the_cache)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = reference.load(ROOT, "sarvam_mla")

# float32, the same arithmetic in another order (the program sorts the pairs and
# runs grouped GEMMs, attends a block of keys at a time with a running softmax or
# absorbs W_kvb into the queries; the reference expands every key and loops over
# heads and queries): a few float32 ulps of the largest element a sum went through
F32_TOL = 5e-5


def small_cfg(**kw):
    """A leading dense layer and two expert layers at small widths, rank 1 of 2
    holding experts 4-7 of 8; YaRN over 16 original positions so that the 64 the
    tests use lie past them."""
    base = dict(vocab_size=96, hidden_size=32, num_layers=3, num_heads=4, attn_head_dim=24,
                ffn_dim=80, max_seq_len=64, mla_kv_rank=16, mla_nope_dim=16, mla_rope_dim=8,
                mla_v_dim=12, moe_experts=8, moe_top_k=2, moe_ffn_dim=24, moe_shared_ffn_dim=24,
                moe_share=(1, 2), rope_yarn=(40.0, 16, 32.0, 1.0, 1.0, 1.0), dtype=jnp.float32)
    base.update(kw)
    return PRESETS["sarvam-105b"].replace(**base)


def ref_cfg(cfg, share=None):
    rank, of = share or cfg.moe_share
    factor, original, fast, slow, mscale, all_dim = cfg.rope_yarn
    return {"hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
            "qk_nope_head_dim": cfg.mla_nope_dim, "qk_rope_head_dim": cfg.mla_rope_dim,
            "v_head_dim": cfg.mla_v_dim, "kv_lora_rank": cfg.mla_kv_rank,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "rope_scaling": {"factor": factor, "original_max_position_embeddings": original,
                             "beta_fast": fast, "beta_slow": slow, "mscale": mscale,
                             "mscale_all_dim": all_dim, "type": "deepseek_yarn"},
            "num_hidden_layers": cfg.num_layers, "first_k_dense_replace": cfg.moe_dense_layers,
            "intermediate_size": cfg.ffn, "moe_intermediate_size": cfg.expert_ffn,
            "num_experts": cfg.moe_experts // of, "num_experts_per_tok": cfg.moe_top_k,
            "num_shared_experts": 1, "routed_scaling_factor": cfg.moe_route_scale,
            "vocab_size": cfg.vocab_size, "expert_share": {"rank": rank, "of": of}}


def ref_logits(params, rows, cfg):
    return harness.reference(ARCH, ref_cfg, cfg).logits(params, jnp.asarray(rows))


# --- the preset and the parameters ----------------------------------------------------


def test_preset_runs_the_published_widths():
    cfg = PRESETS["sarvam-105b"]
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.head_dim, cfg.ffn) == (
        4096, 32, 64, 192, 16384)
    assert mla.dims(cfg) == (64, 128, 64, 128, 512) and cfg.rotary_dim == 64
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.expert_ffn, cfg.moe_shared_ffn_dim,
            cfg.moe_route_scale, cfg.moe_dense_layers) == (128, 8, 2048, 2048, 2.5, 1)
    assert cfg.kinds == ("mla",) * 32 and cfg.moe_dropless and not cfg.moe_shared_gate
    assert cfg.vocab_size == 262144 and not cfg.tie_word_embeddings and cfg.norm_eps == 1e-6
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    assert mla.cache_bytes_per_position(cfg) == 1152  # against 2 x 64 x 128 x 2 = 32,768


def test_parameter_counts_are_the_configuration_files():
    """The byte arithmetic of ``benchmark/configs/sarvam-105b.json``, from shapes."""
    from galvatron_tpu.search import theoretical as th

    cfg = PRESETS["sarvam-105b"].replace(num_layers=5, vocab_size=65536, moe_share=(0, 4),
                                         param_dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))  # noqa: E731
    dense, expert = shapes["layers"][0], shapes["layers"][1]
    assert count(dense["mla"]) == mla.param_count(cfg) == 94_634_496
    assert count(dense) == 94_634_496 + 3 * 4096 * 16384 + 2 * 4096 == 295_969_280
    assert count(expert) == th.layer_param_count(cfg, kind="mla") == 925_639_296
    assert count(expert["mlp"]["shared"]) == 3 * 4096 * 2048  # no gate
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert nbytes == pytest.approx(9.075e9, rel=1e-3)
    cache = jax.eval_shape(lambda: generation.init_kv_cache(cfg.replace(max_seq_len=16384), 32,
                                                            16384))
    assert cache.latent.shape == (5, 32, 16384, 576) and cache.latent.dtype == jnp.bfloat16
    assert generation.cache_layout(cfg) == {"kind": "latent", "bytes_per_position": 5760}


def test_bf16_parameters_leave_no_float32_weight_but_the_routers():
    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args

    ns = initialize_galvatron("serve", ["--model_size", "sarvam-105b", "--num_layers", "3",
                                        "--moe_share", "0/4", "--param_dtype", "bf16"])
    cfg = model_config_from_args(ns)
    assert cfg.param_dtype == jnp.bfloat16 and cfg.moe_share == (0, 4)
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    f32 = {jax.tree_util.keystr(path) for path, leaf in
           jax.tree_util.tree_flatten_with_path(shapes)[0] if leaf.dtype == jnp.float32}
    assert f32 == {f"['layers'][{i}]['mlp']['router']['{name}']" for i in (1, 2)
                   for name in ("w", "bias")}
    # the default keeps float32 (opt-1.3b's cell keeps its program)
    default = model_config_from_args(initialize_galvatron("serve", ["--model_size", "opt-1.3b"]))
    assert default.param_dtype == jnp.float32


def test_leading_layers_are_dense_and_the_rest_expert_layers():
    cfg = small_cfg(moe_dense_layers=2, num_layers=4)
    params, rows = seeded(cfg)
    assert [("router" in lp["mlp"]) for lp in params["layers"]] == [False, False, True, True]
    assert params["layers"][0]["mlp"]["w13"].shape == (32, 2 * 80)
    notes = modeling.model_annotations(cfg)["layers"]
    assert "w13" in notes[1]["mlp"] and "router" in notes[2]["mlp"]
    logits, stats = harness.forward_with_stats(params, rows, cfg)
    assert len(stats) == 2  # the dense layers hand no router statistics up
    close(logits, ref_logits(params, rows, cfg), F32_TOL)


# --- the program against the reference ------------------------------------------------


@pytest.mark.parametrize("share", [(1, 2), (0, 1), (3, 4)])
def test_no_cache_forward_matches_the_reference(share):
    cfg = small_cfg(moe_share=share)
    params, rows = seeded(cfg)
    close(forward(params, rows, cfg), ref_logits(params, rows, cfg), F32_TOL)


def test_the_loss_and_every_gradient_are_finite_and_the_bias_takes_none():
    cfg = small_cfg()
    params, rows = seeded(cfg, length=cfg.max_seq_len + 1)
    loss, grads = harness.loss_and_gradients(lambda p: modeling.lm_loss(p, rows, cfg), params)
    assert np.isfinite(float(loss))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
    for lp in grads["layers"][1:]:
        assert float(jnp.abs(lp["mlp"]["router"]["bias"]).max()) == 0.0
        assert float(jnp.abs(lp["mlp"]["router"]["w"]).max()) > 0.0


@pytest.mark.parametrize("key_block,path", [(64, "plain"), (16, "plain"), (16, "kernel")])
def test_chunked_prefill_then_decoding_through_the_latent_cache_matches_the_reference(
        monkeypatch, retraced, key_block, path):
    """One request in row 2 of a three-row latent slot cache: its prompt in chunks of
    16 (the chunk form, several blocks of keys where ``key_block`` is 16; the plain
    body, and the kernel `mla_chunk` interpreted), then token by token at per-row
    offsets (the absorbed form), logits against ONE full forward of the reference."""
    monkeypatch.setattr(mla, "KEY_BLOCK", key_block)
    monkeypatch.setattr(mla, "key_block", lambda positions: min(positions, key_block))
    if path == "kernel":
        small_tiles(monkeypatch, mla_prefill, key_block=key_block)
    retraced()  # (the key blocks are bound when a forward is traced)
    cfg = small_cfg()
    assert mla._chunk_path(cfg, 16, 64) == path
    params, rows = seeded(cfg, batch=1)
    want = ref_logits(params, rows, cfg)
    # (rows 0 and 1 hold no request: they decode at (0, 0))
    got, cache = through_the_cache(params, cfg, {2: (rows[0].tolist(), 48)}, {2: 64}, chunk=16)
    assert isinstance(cache, mla.LatentCache) and cache.latent.shape == (3, 3, 64, 24)
    close(got[2], want[0], F32_TOL)
    # rows 0 and 1 took only their own position 0
    assert float(jnp.abs(cache.latent[:, :2, 1:]).max()) == 0.0


def test_absorbed_and_non_absorbed_forms_agree():
    cfg = small_cfg()
    p = mla.init_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 40, cfg.hidden_size))
    cos_sin = modeling.rope_tables(cfg, 40)
    q_nope, q_rope, latent = mla.project(x, p, cfg, cos_sin)
    q_pos = jnp.arange(40)[None]
    expanded = mla.attend_expanded(q_nope, q_rope, latent, p, cfg, q_pos)
    absorbed = mla.attend_absorbed(q_nope, q_rope, latent, p, cfg, q_pos)
    assert expanded.shape == (2, 40, 4, 12)
    close(absorbed, expanded, F32_TOL)
    # a decode step's query (the last position) over the same cache
    one = mla.attend_absorbed(q_nope[:, -1:], q_rope[:, -1:], latent, p, cfg,
                              jnp.full((2, 1), 39))
    close(one, expanded[:, -1:], F32_TOL)


def test_lockstep_generation_runs_over_the_latent_cache():
    cfg = small_cfg()
    params, rows = seeded(cfg)
    out = generation.generate(params, rows[:, :8], jnp.asarray([8, 8]), cfg, jax.random.key(0),
                              max_new_tokens=6)
    assert out.shape == (2, 14)
    logits = forward(params, out[:, :-1], cfg)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(logits[:, 7:], -1)), np.asarray(out[:, 8:]))


# --- the decode kernel (ops/mla_decode.py; interpreted on the CPU) -------------------------


def _window_case(cfg, window, lengths, positions=64, seed=0):
    """A layer's parameters, a stacked cache of 3 layers x len(lengths) rows, and the
    projected queries of a window of ``window`` tokens ending each row's length."""
    p = mla.init_params(jax.random.key(seed), cfg)
    rows, width = len(lengths), cfg.mla_kv_rank + cfg.mla_rope_dim
    stacked = jax.random.normal(jax.random.key(seed + 1), (3, rows, positions, width), cfg.dtype)
    x = jax.random.normal(jax.random.key(seed + 2), (rows, window, cfg.hidden_size), cfg.dtype)
    first = jnp.asarray([max(n - window, 0) for n in lengths], jnp.int32)
    pos = first[:, None] + jnp.arange(window)[None]
    cos_all, sin_all = modeling.rope_tables(cfg, positions)
    q_nope, q_rope, _ = mla.project(x, p, cfg, (cos_all[pos], sin_all[pos]))
    return p, stacked, q_nope, q_rope, first


@pytest.mark.parametrize("block", [16, 32])
@pytest.mark.parametrize("window", [1, 4], ids=["decode", "verify4"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_decode_kernel_is_the_plain_absorbed_body(monkeypatch, dtype, window, block):
    """`attend_window` through the kernel against `attend_absorbed` over the layer's
    slab: rows of length 1 (the window's own where it is longer), a key block less
    one, a block, a block plus one and the slot's capacity, in one batch."""
    small_tiles(monkeypatch, mla_decode, key_block=block)
    cfg = small_cfg(dtype=dtype)
    lengths = [1, block - 1, block, block + 1, 64]
    p, stacked, q_nope, q_rope, first = _window_case(cfg, window, lengths)
    assert mla_decode.decode_path(64, 24, window * cfg.num_heads, 16, dtype) == "kernel"
    got = jax.jit(lambda *t: mla.attend_window(*t, 1, first, p, cfg))(q_nope, q_rope, stacked)
    want = mla.attend_absorbed(q_nope, q_rope, stacked[1], p, cfg,
                               first[:, None] + jnp.arange(window)[None])
    assert got.shape == (5, window, 4, 12) and got.dtype == dtype
    close(got.astype(jnp.float32), want.astype(jnp.float32), F32_TOL if dtype == jnp.float32 else 2e-2)


@pytest.mark.parametrize("window", [1, 4], ids=["decode", "verify4"])
def test_what_lies_past_a_rows_length_is_never_read_or_never_counted(monkeypatch, window):
    """Every position past each row's window filled with NaN: the kernel's outputs are
    bit for bit the clean cache's (blocks past the last live one are not fetched, and
    in the last live one such keys are masked and such values zeroed)."""
    small_tiles(monkeypatch, mla_decode)
    cfg = small_cfg()
    lengths = [window, 15, 16, 17, 33, 64]
    p, stacked, q_nope, q_rope, first = _window_case(cfg, window, lengths)
    past = jnp.arange(64)[None, :] >= (first + window)[:, None]
    dirty = jnp.where(past[None, :, :, None], jnp.nan, stacked)
    assert bool(jnp.isnan(dirty[1, 0, window:]).all()) and not bool(jnp.isnan(dirty[:, 5]).any())
    attend = jax.jit(lambda c: mla.attend_window(q_nope, q_rope, c, 1, first, p, cfg))
    got, clean = attend(dirty), attend(stacked)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


@pytest.mark.parametrize("why", ["capacity", "backend", "query_rows", "layout"])
def test_outside_the_kernels_envelope_the_plain_body_runs_and_the_counter_says_so(monkeypatch, why):
    """A capacity that is no multiple of the key block, a backend without the kernel,
    a window of more query rows than the accumulator holds, compiled a width the chip
    keeps row-major: the plain body over the whole slab (the kernel is not called),
    `latent_read_positions` rows x capacity."""
    small_tiles(monkeypatch, mla_decode)
    cfg, positions, window = small_cfg(), 64, 1
    if why == "capacity":
        positions = 40
    elif why == "backend":
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    elif why == "query_rows":
        window = 4
        monkeypatch.setattr(mla_decode, "MAX_QUERY_ROWS", 8)
    else:
        on_a_chip(monkeypatch)
        small_tiles(monkeypatch, mla_decode, key_block=1024)
        # (the cell's slots take the kernel compiled; 640 wide they would lie row-major)
        assert mla_decode.decode_path(16384, 576, 64, 512, jnp.bfloat16) == "kernel"
        assert mla_decode.decode_path(16384, 640, 64, 512, jnp.bfloat16) == "plain"
        positions = 1024
    lengths = [5, 17, 33]
    assert mla_decode.decode_path(positions, 24, window * 4, 16, cfg.dtype) == "plain"
    assert mla.cache_read_positions(cfg, lengths, 4, positions, window) == 4 * positions

    def refuse(*a, **k):
        raise AssertionError("the kernel was called outside its envelope")

    monkeypatch.setattr(mla_decode, "latent_attention", refuse)
    p, stacked, q_nope, q_rope, first = _window_case(cfg, window, lengths, positions)
    got = mla.attend_window(q_nope, q_rope, stacked, 2, first, p, cfg)
    want = mla.attend_absorbed(q_nope, q_rope, stacked[2], p, cfg,
                               first[:, None] + jnp.arange(window)[None])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_read_positions_round_each_row_up_to_the_key_block(monkeypatch):
    small_tiles(monkeypatch, mla_decode)
    cfg = small_cfg()
    # three rows in use (1, 2 and 3 blocks) and a free one, which attends its position 0
    assert mla.cache_read_positions(cfg, [1, 17, 48], 4, 64) == (1 + 2 + 3 + 1) * 16
    assert mla.cache_read_positions(cfg, [64] * 4, 4, 64) == 4 * 64
    assert generation.cache_read_positions(cfg, [5], 2, 64) == 2 * 16
    assert generation.cache_read_positions(PRESETS["opt-125m"], [5], 2, 64) is None


# --- the chunk kernel (ops/mla_prefill.py; interpreted on the CPU) --------------------------


def _chunk_case(cfg, rows, offset, positions=64, seed=0):
    """A layer's parameters, a stacked cache of 3 layers x 4 rows of random latents
    and the projected queries of a chunk of ``rows`` tokens at ``offset``."""
    p = mla.init_params(jax.random.key(seed), cfg)
    width = cfg.mla_kv_rank + cfg.mla_rope_dim
    stacked = jax.random.normal(jax.random.key(seed + 1), (3, 4, positions, width), cfg.dtype)
    x = jax.random.normal(jax.random.key(seed + 2), (1, rows, cfg.hidden_size), cfg.dtype)
    pos = offset + jnp.arange(rows)
    cos_all, sin_all = modeling.rope_tables(cfg, positions)
    q_nope, q_rope, _ = mla.project(x, p, cfg, (cos_all[pos][None], sin_all[pos][None]))
    return p, stacked, q_nope, q_rope


# (rows, offset, real rows, what lies from position offset + real rows on): key blocks
# of 16, slots of 64
CHUNKS = {
    "offset_0": (16, 0, 16, None),  # one live key block, the diagonal's
    "inside_a_key_block": (16, 5, 16, None),  # two blocks cross the diagonal
    "on_a_block_boundary": (16, 16, 16, None),  # a whole block, then the diagonal's
    "several_key_blocks": (16, 48, 16, None),  # four live blocks, up to the slot's end
    "slid_left": (24, 40, 24, None),  # 64 - 24: no multiple of the chunk
    "padded_tail": (16, 32, 10, 1e4),  # a final chunk: 10 tokens, then padding's latents
    "nan_past_the_end": (16, 21, 16, float("nan")),  # never fetched or never counted
}


@pytest.mark.parametrize("case", list(CHUNKS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_chunk_kernel_is_the_plain_chunk_body(monkeypatch, dtype, case):
    """`attend_chunk` through the kernel `mla_chunk` against `_plain_chunk` over a
    CLEAN cache: the real rows' outputs are the plain body's whatever lies past them
    (padding's latents; NaN past the chunk's end, where the output is bit for bit the
    clean cache's)."""
    small_tiles(monkeypatch, mla_prefill)
    rows, offset, real, dirt = CHUNKS[case]
    cfg = small_cfg(dtype=dtype)
    p, stacked, q_nope, q_rope = _chunk_case(cfg, rows, offset)
    assert mla._chunk_path(cfg, rows, 64) == "kernel"
    attend = jax.jit(lambda c, o: mla.attend_chunk(q_nope, q_rope, c, 1, jnp.int32(2), o, p, cfg))
    want = mla._plain_chunk(q_nope, q_rope, stacked, 1, jnp.int32(2), jnp.int32(offset), p, cfg)
    cache = stacked
    if dirt is not None:
        past = (jnp.arange(64) >= offset + real)[None, None, :, None]
        cache = jnp.where(past, jnp.asarray(dirt, dtype), stacked)
    got = attend(cache, jnp.int32(offset))
    assert got.shape == (1, rows, 4, 12) and got.dtype == dtype
    assert bool(jnp.isfinite(got).all())
    close(got[:, :real].astype(jnp.float32), want[:, :real].astype(jnp.float32),
          F32_TOL if dtype == jnp.float32 else 2e-2)
    if case == "nan_past_the_end":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(attend(stacked, jnp.int32(offset))))


PUBLISHED = (64, 128, 64, 128, 512)  # heads, dn, dr, dv, r
# (positions, width, rows, dims, dtype, what is patched) inside the envelope | just outside it
ENVELOPE = {
    "capacity": ((64, 24, 16, (4, 16, 8, 12, 16), jnp.float32, "tiles"),
                 (40, 24, 16, (4, 16, 8, 12, 16), jnp.float32, "tiles")),
    "backend": ((64, 24, 16, (4, 16, 8, 12, 16), jnp.float32, "tiles"),
                (64, 24, 16, (4, 16, 8, 12, 16), jnp.float32, "tiles,gpu")),
    "chunk_rows": ((16384, 576, 1024, PUBLISHED, jnp.bfloat16, "compiled"),
                   (16384, 576, 1040, PUBLISHED, jnp.bfloat16, "compiled")),
    "row_sublanes": ((16384, 576, 1008, PUBLISHED, jnp.bfloat16, "compiled"),
                     (16384, 576, 1000, PUBLISHED, jnp.bfloat16, "compiled")),
    "dtype": ((64, 24, 16, (4, 16, 8, 12, 16), jnp.bfloat16, "tiles"),
              (64, 24, 16, (4, 16, 8, 12, 16), jnp.float16, "tiles")),
    "latent_layout": ((16384, 576, 1024, PUBLISHED, jnp.bfloat16, "compiled"),
                      (16384, 640, 1024, (64, 128, 128, 128, 512), jnp.bfloat16, "compiled")),
    "lane_tiles": ((16384, 576, 1024, PUBLISHED, jnp.bfloat16, "compiled"),
                   (16384, 576, 1024, (64, 96, 64, 128, 512), jnp.bfloat16, "compiled")),
    "rope_sublanes": ((16384, 520, 1024, (64, 128, 8, 128, 512), jnp.float32, "compiled"),
                      (16384, 520, 1024, (64, 128, 8, 128, 512), jnp.bfloat16, "compiled")),
}


@pytest.mark.parametrize("edge", list(ENVELOPE))
def test_chunk_path_answers_from_shapes_and_the_backend_each_side_of_every_edge(monkeypatch, edge):
    for want, (positions, width, rows, dims, dtype, patched) in zip(("kernel", "plain"), ENVELOPE[edge]):
        with monkeypatch.context() as m:
            if "tiles" in patched:
                small_tiles(m, mla_prefill)
            if "gpu" in patched:
                m.setattr(jax, "default_backend", lambda: "gpu")
            if "compiled" in patched:
                on_a_chip(m)
            assert mla_prefill.chunk_path(positions, width, rows, dims, dtype) == want, (edge, want)


def test_outside_the_chunk_kernels_envelope_the_plain_body_runs(monkeypatch):
    """Slots of 64 under the real key block of 1,024: `attend_chunk` IS `_plain_chunk`
    (the kernel is not called) and the host's count says so."""
    def refuse(*a, **k):
        raise AssertionError("the kernel was called outside its envelope")

    monkeypatch.setattr(mla_prefill, "latent_chunk_attention", refuse)
    cfg = small_cfg()
    p, stacked, q_nope, q_rope = _chunk_case(cfg, 16, 16)
    args = (q_nope, q_rope, stacked, 1, jnp.int32(2), jnp.int32(16), p, cfg)
    np.testing.assert_array_equal(np.asarray(mla.attend_chunk(*args)),
                                  np.asarray(mla._plain_chunk(*args)))
    # all 64 keys are one block of the plain body
    assert mla.chunk_layout(cfg, 16, 64) == {"chunk_path": "plain", "chunk_key_block": 64}
    assert generation.chunk_layout(PRESETS["opt-125m"], 16, 64) == {}
    small_tiles(monkeypatch, mla_prefill)
    assert generation.chunk_layout(cfg, 16, 64) == {"chunk_path": "kernel", "chunk_key_block": 16}


# --- YaRN -----------------------------------------------------------------------------


def test_yarn_table_is_the_formula():
    """At the published numbers: pairs below the one that makes 32 turns over 4096
    positions keep theta^(-2i/64), pairs past the one that makes 1 turn take it over
    40, a linear ramp between; the table's own factor is 1."""
    cfg = PRESETS["sarvam-105b"]
    inv = modeling.rope_inv_freq(cfg)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)

    def pair(turns):
        return 64 * math.log(4096 / (turns * 2 * math.pi)) / (2 * math.log(10000.0))

    low, high = math.floor(pair(32)), math.ceil(pair(1))
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(inv[:low + 1], plain[:low + 1])
    np.testing.assert_allclose(inv[high:], plain[high:] / 40)
    i = 15
    ramp = (i - low) / (high - low)
    assert inv[i] == pytest.approx(plain[i] / 40 * ramp + plain[i] * (1 - ramp))
    cos, sin = modeling.rope_tables(cfg, 5000)
    np.testing.assert_allclose(np.asarray(cos[4999]), np.cos(4999 * inv), atol=2e-4)
    ref_cos, ref_sin = ARCH.yarn_tables(ref_cfg(cfg), 5000)
    np.testing.assert_allclose(np.asarray(cos), np.asarray(ref_cos), atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin), np.asarray(ref_sin), atol=1e-6)
    # without scaling the table is the plain one
    np.testing.assert_allclose(modeling.rope_inv_freq(cfg.replace(rope_yarn=())), plain)
    assert modeling.yarn_mscale(40.0, 1.0) == pytest.approx(1.3689, abs=1e-4)


# --- the router -----------------------------------------------------------------------


def test_the_bias_changes_the_choice_and_never_the_weight():
    cfg = small_cfg(moe_share=(0, 1))
    p = moe.init_moe_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (1, 30, cfg.hidden_size))
    scores = jax.nn.sigmoid(x.reshape(30, -1) @ p["router"]["w"])

    def weights_of(bias):
        w = ARCH.route(x.reshape(30, -1), {"gate": p["router"]["w"], "expert_bias": bias},
                       ref_cfg(cfg, (0, 1)))
        return np.asarray(w)

    plain = weights_of(jnp.zeros(8))
    # renormalised over the chosen two, times 2.5
    np.testing.assert_allclose(plain.sum(-1), 2.5, rtol=1e-6)
    assert ((plain > 0).sum(-1) == 2).all()
    pushed = weights_of(jnp.zeros(8).at[5].set(10.0))  # expert 5 is now always chosen
    assert (pushed[:, 5] > 0).all() and not (plain[:, 5] > 0).all()
    # ... at the weight its own score gives, never the bias's: s_5 / (s_5 + s_other) x 2.5
    other = np.where(pushed > 0, np.asarray(scores), 0.0)
    np.testing.assert_allclose(pushed[:, 5], 2.5 * other[:, 5] / other.sum(-1), rtol=1e-5)
    # and the program's block is the reference's layer under both
    for bias in (jnp.zeros(8), jnp.zeros(8).at[5].set(10.0)):
        q = dict(p, router={"w": p["router"]["w"], "bias": bias})
        mw = {"gate": q["router"]["w"], "expert_bias": bias,
              "experts": {"gate_proj": q["w1"], "up_proj": q["w3"], "down_proj": q["w2"]},
              "shared_experts": {"gate_up_proj": q["shared"]["w13"],
                                 "down_proj": q["shared"]["w2"]}}
        with jax.default_matmul_precision("highest"):
            want = ARCH.moe(x, mw, ref_cfg(cfg, (0, 1)))
        close(moe.moe_topk_block(x, q, cfg, tile=8)[0], want, F32_TOL)


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """Over all 4 ranks the routed parts, plus the shared expert counted once, equal
    the layer that holds every expert: the reference's uncut layer, and the
    program's parts against it."""
    cfg = small_cfg(moe_share=(0, 1))
    full = moe.init_moe_params(jax.random.key(0), cfg)
    full["router"]["bias"] = 0.3 * jax.random.normal(jax.random.key(3), (8,))
    x = jax.random.normal(jax.random.key(1), (2, 25, cfg.hidden_size))

    def ref_weights(p):
        return {"gate": p["router"]["w"], "expert_bias": p["router"]["bias"],
                "experts": {"gate_proj": p["w1"], "up_proj": p["w3"], "down_proj": p["w2"]},
                "shared_experts": {"gate_up_proj": p["shared"]["w13"],
                                   "down_proj": p["shared"]["w2"]}}

    with jax.default_matmul_precision("highest"):
        uncut = ARCH.moe(x, ref_weights(full), ref_cfg(cfg, (0, 1)))
        shared = ARCH.swiglu(x, full["shared"]["w13"], full["shared"]["w2"])
    ranks = 4
    total_ref = total_prog = 0.0
    for rank in range(ranks):
        rcfg = cfg.replace(moe_share=(rank, ranks))
        lo, n = rcfg.moe_first_held, rcfg.moe_held
        part = dict(full, **{name: full[name][lo:lo + n] for name in ("w1", "w3", "w2")})
        with jax.default_matmul_precision("highest"):
            want = ARCH.moe(x, ref_weights(part), ref_cfg(rcfg))
        y, stats = moe.moe_topk_block(x, part, rcfg, tile=8)
        close(y, want, F32_TOL)  # the program's share is the reference's
        total_ref = total_ref + (want - shared)
        total_prog = total_prog + (y - shared)
        # the statistics are over ALL the experts the router scores: k pairs a token
        assert float(jnp.sum(stats[0])) == pytest.approx(cfg.moe_top_k)
    close(total_ref + shared, uncut, F32_TOL)
    close(total_prog + shared, uncut, F32_TOL)
    # and a rank alone is NOT the layer: what the others hold is really left out
    assert float(jnp.abs(want - uncut).max()) > 1e-2


def test_the_shared_expert_is_ungated_and_a_gated_one_still_is():
    cfg = small_cfg()
    p = moe.init_moe_params(jax.random.key(0), cfg)
    assert set(p["shared"]) == {"w13", "w2"} and set(p["router"]) == {"w", "bias"}
    assert set(p) == {"router", "w1", "w3", "w2", "shared"} and p["w1"].shape == (4, 32, 24)
    assert set(moe.moe_annotations(cfg)["shared"]) == {"w13", "w2"}
    gated = moe.init_moe_params(jax.random.key(0), cfg.replace(
        moe_shared_gate=True, moe_router="softmax_topk"))
    assert set(gated["shared"]) == {"w13", "w2", "gate"} and set(gated["router"]) == {"w"}
    x = jax.random.normal(jax.random.key(1), (6, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        close(moe._shared_expert(x, p["shared"]),
              ARCH.swiglu(x, p["shared"]["w13"], p["shared"]["w2"]), 1e-6)


# --- what the kind does not implement, by the table -----------------------------------


REFUSALS = [
    ("tp", {}, dict(tp=2), "tensor parallelism .* latent-attention layers"),
    ("cp", {}, dict(cp=2), "context parallelism .* latent-attention"),
    ("pp", {}, dict(pp=2), "pipeline parallelism .* dropless top-k MoE"),
]
test_build_runtime_refuses_by_name = harness.refuses(REFUSALS, small_cfg, seq_len=64)


def test_limits_refuse_packing_and_accept_the_cache():
    cfg = small_cfg()
    found = {(limit.what, limit.tag) for limit in mixers.limits(cfg)}
    assert {("tp", "latent_attention_layers_no_tp"), ("cp", "latent_attention_layers_no_cp"),
            ("pack_sequences", None), ("pp", "dropless_topk_moe_no_pp")} <= found
    assert not any(limit.what == "kv_cache" for limit in mixers.limits(cfg))
    assert mixers.cache_kind(cfg) == "mla" and mixers.cache_kind(PRESETS["opt-1.3b"]) is None
    mesh, axes = build_mesh(pp=1, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="pack_sequences .* latent-attention"):
        build_runtime(cfg.replace(pack_sequences=True),
                      harness.plan(cfg, mixed_precision="fp32"), mesh=mesh, axes=axes,
                      adam=AdamConfig(), global_batch_size=4, seq_len=64)
    # a stack that interleaves cache layouts has no slot cache
    mixed = cfg.replace(layer_kinds=("mla", "attention", "mla"))
    with pytest.raises(ValueError, match="interleaves cache layouts"):
        generation.init_kv_cache(mixed, 1, 8)
    # an attention stack's cache is K and V as it was
    kv = generation.init_kv_cache(PRESETS["opt-125m"].replace(num_layers=2), 3, 16)
    assert isinstance(kv, generation.KVCache) and kv.k.shape == (2, 3, 16, 12, 64)
    assert generation.cache_layout(PRESETS["opt-125m"]) == {
        "kind": "kv", "bytes_per_position": 12 * 2 * 12 * 64 * 2}


def test_the_runtime_trains_it_on_one_device():
    before, state = harness.trains_on_one_device(small_cfg(), steps=4, drop=0.0)
    # no gradient reaches the selection bias (and no weight decay here): left constant
    np.testing.assert_array_equal(
        np.asarray(state["params"]["layers"][1]["mlp"]["router"]["bias"]),
        before["layers"][1]["mlp"]["router"]["bias"])


def test_the_search_prices_the_kind_and_leaves_out_what_it_lacks():
    from galvatron_tpu.search import theoretical as th
    from galvatron_tpu.search.cost_model import ProfiledHardware
    from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace

    cfg = PRESETS["sarvam-105b"].replace(num_layers=5, vocab_size=65536, moe_share=(0, 4),
                                         max_seq_len=4096)
    costs = th.analytic_model_costs(cfg, seq_len=4096)
    layer = costs.layer_types[0]
    assert layer.parameter_mb == pytest.approx(925_639_296 * 4 / 1e6)
    assert th.layer_active_param_count(cfg, "mla") == pytest.approx(
        925_639_296 - (32 - 2.0) * 3 * 4096 * 2048)
    engine = SearchEngine(costs, ProfiledHardware(), num_layers=5, space=SearchSpace(world_size=4),
                          memory_budget_mb=15360.0, model_config=cfg)
    assert {"latent_attention_layers_no_tp", "latent_attention_layers_no_cp",
            "dropless_topk_moe_no_ep", "dropless_topk_moe_no_pp"} <= set(engine._standing)
    assert engine.space.max_tp == 1 and engine.space.pp_choices == [1]


# --- the engine -----------------------------------------------------------------------

#: two slots, prompts in chunks of 8, no request expiring: this file's engine
TWO_SLOTS = functools.partial(harness.engine, num_slots=2, prefill_chunk=8, request_ttl_s=None)


def test_engine_serves_an_mla_stack_end_to_end():
    """More requests than slots through ``serving.Engine``: every slot is reused,
    greedy tokens are the no-cache forward's, nothing leaks, and the stats name the
    latent cache and the step's expert counters."""
    from galvatron_tpu.serving import Engine

    cfg = small_cfg()
    params, rows = seeded(cfg, batch=5, length=20)
    engine = Engine(params, cfg, num_slots=2, prefill_chunk=8, max_queue=16, request_ttl_s=None)
    try:
        prompts = [np.asarray(r).tolist()[:n] for r, n in zip(rows, (20, 9, 13, 17, 8))]
        outs = engine.generate(prompts, max_new_tokens=6)
        stats = engine.stats()
    finally:
        audit = engine.drain(timeout_s=10.0)
    for prompt, out in zip(prompts, outs):
        assert out[:len(prompt)] == prompt and len(out) == len(prompt) + 6
        logits = forward(params, jnp.asarray([out[:-1]]), cfg)[0]
        assert np.asarray(jnp.argmax(logits[len(prompt) - 1:], -1)).tolist() == out[len(prompt):]
    assert not audit["leaked"] and stats["completed"] == 5 and stats["engine_restarts"] == 0
    assert stats["kv_backend"] == "slot" and stats["cache_kind"] == "latent"
    assert stats["latent_cache_bytes_per_position"] == 3 * 24 * 4
    assert stats["cache_bytes"] == 3 * 24 * 4 * 2 * 64
    # the expert counters are the tracer's: off, they stay on the device and none is read
    assert stats["latent_live_positions"] == 0 and "moe_held_pairs_per_token" not in stats
    # 64 positions are no whole key block: the plain body, which reads every slot's capacity
    assert stats["latent_read_positions"] == 2 * 64


def test_the_decode_span_carries_the_iterations_counters():
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=12)
    _, stats, spans = harness.serve(TWO_SLOTS(cfg, params), [np.asarray(r).tolist() for r in rows],
                                    4, traced=True)
    chunks = spans["prefill"]
    # (a step's expert counters are read with its ids, one iteration late: they ride the
    # NEXT step's span, so the first step behind an idle engine carries none)
    assert "moe_held_pairs_per_token" not in spans["decode"][0]
    args = spans["decode"][1]
    assert args["latent_cache_bytes_per_position"] == 3 * 24 * 4
    assert args["latent_live_positions"] >= 2 * 12
    assert {"moe_held_pairs_per_token", "moe_load_imbalance"} <= set(args)
    # and `stats` repeats the last iteration's, as host numbers
    assert isinstance(stats["moe_load_imbalance"], float)
    assert 0.0 <= stats["moe_held_pairs_per_token"] <= 2.0
    # the row tile each forward compiled with (float32's floor: 2 rows x top-2 over 8
    # scored experts, and a chunk's 8 x 2 / 8, are under 8 rows an expert) and the share
    # of the rows its held experts multiplied that hold a pair: a cached forward gives a
    # tile of 8 rows to each held expert that got a row and to no other, on the plain path
    # too (PR 69), and they hold the step's at most 2 x 2 pairs, a chunk's at most 8 x 2
    assert args["moe_row_tile"] == stats["moe_row_tile"] == stats["moe_row_tile_prefill"] == 8
    held = args["moe_held_pairs_per_token"] * 2  # pairs of the step's 2 rows on the 4 held
    touched = args["moe_held_experts_touched"]
    assert 0 < touched <= min(held, 4)
    assert args["moe_live_rows_share"] == pytest.approx(held / (touched * 8), rel=1e-5)
    assert stats["moe_live_rows_share"] <= 4 / 8
    assert len(chunks) == 2  # (a prompt's 12 tokens: two chunks; the span carries the last's)
    for chunk in chunks:
        assert chunk["moe_row_tile"] == 8
        assert 0.0 <= chunk["moe_live_rows_share"] <= 1.0


def test_the_engine_counts_the_positions_its_decode_kernel_fetches(monkeypatch):
    """A small engine whose slots are whole key blocks decodes through the kernel: the
    greedy tokens are the model's, and every `decode` span (and `stats()`) carries
    `latent_read_positions`: the rows' lengths rounded up to the key block, one block
    for the free row."""
    small_tiles(monkeypatch, mla_decode)
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=1, length=12)
    prompt = np.asarray(rows[0]).tolist()
    (out,), stats, spans = harness.serve(TWO_SLOTS(cfg, params), [prompt], 8, traced=True)
    spans = spans["decode"]
    logits = forward(params, jnp.asarray([out[:-1]]), cfg)[0]
    assert np.asarray(jnp.argmax(logits[11:], -1)).tolist() == out[12:]
    lives = [a["latent_live_positions"] for a in spans]
    assert min(lives) <= 16 < max(lives)  # the row grows past its first key block
    for args in spans:
        assert args["latent_read_positions"] == -(-args["latent_live_positions"] // 16) * 16 + 16
    assert stats["latent_read_positions"] == 2 * 16  # no row in use: a block each


@pytest.mark.parametrize("path", ["kernel", "plain"])
def test_the_engine_counts_the_chunks_its_chunk_kernel_takes(monkeypatch, path):
    """A prompt of 20 tokens in chunks of 8 (at 0, 8 and 16) through the chunk kernel
    (slots of whole key blocks of 16) and outside its envelope (the real key block):
    the greedy tokens are the model's; `latent_chunks_kernel / prefill_chunks` is 1.0 |
    0.0; the request's `prefill` span carries the count and the key blocks a layer's
    chunk attention fetched (1 + 1 + 2 of 16 keys | 3 times all 64)."""
    if path == "kernel":
        small_tiles(monkeypatch, mla_prefill)
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=1, length=20)
    prompt = np.asarray(rows[0]).tolist()
    (out,), stats, spans = harness.serve(TWO_SLOTS(cfg, params), [prompt], 4, traced=True)
    span, = spans["prefill"]
    logits = forward(params, jnp.asarray([out[:-1]]), cfg)[0]
    assert np.asarray(jnp.argmax(logits[19:], -1)).tolist() == out[20:]
    assert stats["prefill_chunks"] == 3 and stats["chunk_path"] == path
    share = stats["latent_chunks_kernel"] / stats["prefill_chunks"]
    assert share == (1.0 if path == "kernel" else 0.0)
    assert span["latent_chunks_kernel"] == (3 if path == "kernel" else 0)
    assert span["latent_chunk_key_blocks"] == (4 if path == "kernel" else 3)


def test_a_prefill_that_ends_early_keeps_the_kernels_share_whole(monkeypatch):
    """A prompt of three chunks whose second chunk fails (`faults.prefill_fail_at`):
    the chunk that ran is counted on both sides, so `latent_chunks_kernel /
    prefill_chunks` stays 1.0 (the count is made a chunk, inside the loop)."""
    from galvatron_tpu.core import faults
    from galvatron_tpu.serving import Engine

    small_tiles(monkeypatch, mla_prefill)
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=1, length=20)
    engine = Engine(params, cfg, num_slots=2, prefill_chunk=8, start_loop=False)
    faults.configure(prefill_fail_at=1)
    try:
        doomed = engine.submit_request(np.asarray(rows[0]).tolist(), 4)
        engine.step_once()
        with pytest.raises(faults.FaultInjected):
            doomed.future.result(timeout=1)
    finally:
        faults.reset()
    stats = engine.stats()
    engine.close()
    assert stats["chunk_path"] == "kernel"
    assert stats["prefill_chunks"] == 1 == stats["latent_chunks_kernel"]


def test_an_attention_engine_reports_no_latent_chunk():
    cfg = PRESETS["opt-125m"].replace(num_layers=2, hidden_size=64, num_heads=4, ffn_dim=128,
                                      vocab_size=128, max_seq_len=32, dtype=jnp.float32)
    params = modeling.init_model_params(jax.random.key(0), cfg)
    _, stats, spans = harness.serve(TWO_SLOTS(cfg, params), [list(range(1, 13))], 2, traced=True)
    span, = spans["prefill"]
    assert stats["prefill_chunks"] == 2
    assert not {"chunk_path", "latent_chunks_kernel"} & set(stats)
    assert not {"latent_chunks_kernel", "latent_chunk_key_blocks"} & set(span)


def test_an_attention_engines_stats_name_its_kv_cache():
    cfg = PRESETS["opt-125m"].replace(num_layers=2, hidden_size=64, num_heads=4, ffn_dim=128,
                                      vocab_size=128, max_seq_len=32, dtype=jnp.float32)
    params = modeling.init_model_params(jax.random.key(0), cfg)
    _, stats, _ = harness.serve(TWO_SLOTS(cfg, params), [[1, 2, 3]], 2)
    assert stats["cache_kind"] == "kv" and stats["kv_cache_bytes_per_position"] == 2 * 2 * 64 * 4
    assert stats["cache_bytes"] == 2 * 2 * 64 * 4 * 2 * 32 and "moe_load_imbalance" not in stats


def test_the_paged_backend_refuses_a_latent_cache():
    from galvatron_tpu.serving import Engine

    cfg = small_cfg()
    params, _ = seeded(cfg)
    with pytest.raises(ValueError, match="paged backend .* latent cache"):
        Engine(params, cfg, num_slots=2, kv_num_blocks=-1, start_loop=False)


def test_the_slot_length_warning_names_the_flag():
    from galvatron_tpu.serving.kv_slots import effective_max_seq_len

    with pytest.warns(RuntimeWarning, match="--seq_length"):
        assert effective_max_seq_len(small_cfg(), 4096) == 64


def test_cli_serve_parses_the_cells_flags():
    harness.cli_serve_parses([
        "--model_size", "sarvam-105b", "--num_layers", "5", "--vocab_size", "65536",
        "--moe_share", "0/4", "--seq_length", "16384", "--param_dtype", "bf16",
        "--num_slots", "32", "--prefill_chunk", "1024"],
        dict(num_layers=5, vocab_size=65536, max_seq_len=16384, moe_held=32, kinds=("mla",) * 5,
             ffn=16384, param_dtype=jnp.bfloat16))


def test_weights_held_in_the_compute_type_take_the_bounded_held_path_unjoined(monkeypatch):
    """At a shape `ops/moe_held.held_path` calls bounded (hidden and expert width
    multiples of 128 in float32) the held share's gate and up, held in the compute
    type, go to the kernels as they are stored (interpreted here): a GEMM each and
    no stack of weights joined; and the layer is the reference's."""
    from galvatron_tpu.ops import moe_held

    cfg = small_cfg(hidden_size=128, moe_ffn_dim=128, moe_shared_ffn_dim=128, moe_share=(1, 2))
    assert moe_held.held_path(cfg.hidden_size, cfg.expert_ffn, cfg.dtype) == "bounded"
    assert moe.held_path_counts(cfg) == {"bounded": 3, "worst_case": 0}
    p = moe.init_moe_params(jax.random.key(0), cfg)
    p["router"]["bias"] = 0.3 * jax.random.normal(jax.random.key(3), (8,))
    x = jax.random.normal(jax.random.key(1), (1, 12, cfg.hidden_size))
    seen = []
    real = jnp.concatenate
    monkeypatch.setattr(moe.jnp, "concatenate", lambda arrays, **kw: (
        seen.append([a.shape for a in arrays]), real(arrays, **kw))[1])
    y, _ = moe.moe_topk_block(x, p, cfg, tile=8)
    monkeypatch.undo()
    # no weight stack was joined
    assert not any(len(shapes[0]) == 3 and shapes[0][0] == 4 for shapes in seen)
    mw = {"gate": p["router"]["w"], "expert_bias": p["router"]["bias"],
          "experts": {"gate_proj": p["w1"], "up_proj": p["w3"], "down_proj": p["w2"]},
          "shared_experts": {"gate_up_proj": p["shared"]["w13"], "down_proj": p["shared"]["w2"]}}
    with jax.default_matmul_precision("highest"):
        close(y, ARCH.moe(x, mw, ref_cfg(cfg)), F32_TOL)


@pytest.mark.parametrize("what", ["forward", "gradients"])
def test_a_pair_of_stacks_and_their_join_are_one_layer(what):
    """`held_experts` given (w1, w3) as stored (weights held in the compute type)
    and given the joined stack (weights converted every step: the training cells)
    is the same function, forward and backward."""
    from galvatron_tpu.ops import moe_held

    cfg = small_cfg(hidden_size=128, moe_ffn_dim=128, moe_shared_ffn_dim=128, moe_share=(1, 2))
    assert moe_held.held_path(cfg.hidden_size, cfg.expert_ffn, cfg.dtype) == "bounded"
    p = moe.init_moe_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (24, cfg.hidden_size))
    idx = jax.random.randint(jax.random.key(2), (24, cfg.moe_top_k), 0, cfg.moe_experts)
    weights = jax.random.uniform(jax.random.key(4), idx.shape)
    lay = moe.held_layout(idx, cfg.moe_held, 8, cfg.moe_first_held)

    def layer(w13, w2, x_):
        return moe.held_experts(x_, weights, w13, w2, lay.pair_row, lay.row_pair, lay.row_valid,
                                lay.tile_group, lay.num_tiles, 8)

    pair, joined = (p["w1"], p["w3"]), jnp.concatenate([p["w1"], p["w3"]], axis=-1)
    if what == "forward":
        close(layer(pair, p["w2"], x), layer(joined, p["w2"], x), 1e-6)
        return
    loss = lambda *a: jnp.sum(layer(*a) ** 2)  # noqa: E731
    (d1, d3), d2, dx = jax.grad(loss, argnums=(0, 1, 2))(pair, p["w2"], x)
    j13, j2, jx = jax.grad(loss, argnums=(0, 1, 2))(joined, p["w2"], x)
    close(jnp.concatenate([d1, d3], axis=-1), j13, 1e-5)
    close(d2, j2, 1e-5)
    close(dx, jx, 1e-5)


def test_the_router_stays_float32_under_bf16_compute(monkeypatch):
    """The configuration states a float32 router; `correct`'s divergence cannot tell
    one computed in bf16 (PERF.md section 6), so it is held here: under bf16 compute
    and bf16 weights the block's choice over 2,048 tokens is EXACTLY the float32
    reference router's on the same rows (every expert's pair count), its lowered
    GEMM has float32 operands at ``highest``, and the same router computed in bf16
    is told from it (the counts move), so the comparison can see the mistake."""
    cfg = small_cfg(hidden_size=64, moe_experts=64, moe_top_k=8, moe_share=(0, 4),
                    dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    p = moe.init_moe_params(jax.random.key(0), cfg)
    assert p["router"]["w"].dtype == jnp.float32 and p["w1"].dtype == jnp.bfloat16
    p["router"]["bias"] = 0.05 * jax.random.normal(jax.random.key(3), (64,))
    tokens = 2048
    x = jax.random.normal(jax.random.key(1), (1, tokens, 64), jnp.bfloat16)

    def counts_of_block():
        _, stats = moe.moe_topk_block(x, p, cfg, tile=8)
        return np.rint(np.asarray(stats[0], np.float64) * tokens).astype(int)

    with jax.default_matmul_precision("highest"):
        want = ARCH.route(x[0].astype(jnp.float32),
                          {"gate": p["router"]["w"], "expert_bias": p["router"]["bias"]},
                          ref_cfg(cfg))
    want = np.asarray((want > 0).sum(0))
    assert want.sum() == tokens * 8
    np.testing.assert_array_equal(counts_of_block(), want)
    text = jax.jit(lambda x_, p_: moe.moe_topk_block(x_, p_, cfg, tile=8)[0]).lower(x, p).as_text()
    router = [line for line in text.splitlines()
              if "dot_general" in line and "tensor<2048x64xf32>, tensor<64x64xf32>" in line]
    assert len(router) == 1 and "HIGHEST" in router[0], router

    def in_bf16(xt, router_, cfg_):
        s = jax.nn.sigmoid(xt.astype(jnp.bfloat16) @ router_["w"].astype(jnp.bfloat16))
        return s.astype(jnp.float32)

    monkeypatch.setattr(moe, "router_scores", in_bf16)
    assert np.abs(counts_of_block() - want).sum() >= 20  # (a flipped pair moves two counts)
