"""dots3_note-class stacks (dots3-note-prev: latent attention of TWO widths in one stack, full
layers whose DSA indexer keeps the best keys a query over whole slots with an index-key
cache, sliding layers over a latent RING; low-rank queries, the low-rank rescale, a
headwise gate; a biased sigmoid router over experts of which this copy holds a share) on
the normal path, against the plain reference ``benchmark/references/dots3_note.py`` on seeded
random weights, at a small size on the CPU: the full forward; chunked prefill then decoding
through the three stacks, the context passing ``index_topk`` and the ring lapping DURING
decode and inside a chunk; the selection held EXACT in both forms; dense attention, an
approximate top-k and bfloat16 each failing the tolerance; the engine's tap rows and
counters; the shares adding up; training; the refusals."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference
from galvatron_tpu.models import generation, mixers, mla, modeling, moe
from galvatron_tpu.models.modeling import PRESETS
from tests import _stack_harness as harness
from tests._stack_harness import (  # noqa: F401  (`retraced`: a fixture)
    close, forward, retraced, seeded, through_the_cache, worst)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = reference.load(ROOT, "dots3_note")

# float32, the same arithmetic in another order (the program scores index keys and attends
# a block of keys at a time with a running softmax, absorbed in a decode step and expanded
# in a chunk; the reference masks every key at once and loops over head groups)
F32_TOL = 5e-5
WINDOW, CHUNK, SLOT, TOPK = 9, 4, 64, 16


def small_cfg(**kw):
    """The cell's layer pattern at small widths: F (dense) F S S S, a window of 9 (a ring
    of 16 under chunks of 4), the 16 best of up to 64 keys, 8 experts top-2, all held."""
    base = dict(vocab_size=96, hidden_size=32, num_layers=5, num_heads=4, attn_head_dim=12,
                ffn_dim=48, max_seq_len=SLOT, mla_kv_rank=16, mla_nope_dim=8, mla_rope_dim=4,
                mla_v_dim=8, mla_q_rank=24, mla_index_heads=4, mla_index_dim=8,
                mla_index_topk=TOPK, sliding_window_size=WINDOW, swa_num_heads=2,
                swa_nope_dim=12, swa_rope_dim=4, swa_v_dim=8, swa_kv_rank=24, swa_q_rank=20,
                moe_experts=8, moe_top_k=2, moe_ffn_dim=24, moe_shared_ffn_dim=24,
                dtype=jnp.float32)
    base.update(kw)
    return PRESETS["dots3-note-prev"].replace(**base)


def ref_cfg(cfg, share=None):
    rank, of = share or cfg.moe_share
    return {"hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
            "qk_nope_head_dim": cfg.mla_nope_dim, "qk_rope_head_dim": cfg.mla_rope_dim,
            "v_head_dim": cfg.mla_v_dim, "kv_lora_rank": cfg.mla_kv_rank,
            "q_lora_rank": cfg.mla_q_rank, "rope_theta": cfg.rope_theta,
            "swa_num_attention_heads": cfg.swa_num_heads, "swa_qk_nope_head_dim": cfg.swa_nope_dim,
            "swa_qk_rope_head_dim": cfg.swa_rope_dim, "swa_v_head_dim": cfg.swa_v_dim,
            "swa_kv_lora_rank": cfg.swa_kv_rank, "swa_q_lora_rank": cfg.swa_q_rank,
            "swa_rope_theta": cfg.swa_rope_theta, "sliding_window_size": cfg.sliding_window_size,
            "index_n_heads": cfg.mla_index_heads, "index_head_dim": cfg.mla_index_dim,
            "index_topk": cfg.mla_index_topk, "apply_mla_qkv_lora_rescale": cfg.mla_rescale,
            "rms_norm_eps": cfg.norm_eps, "num_hidden_layers": cfg.num_layers,
            "first_k_dense_replace": cfg.moe_dense_layers, "intermediate_size": cfg.ffn,
            "moe_intermediate_size": cfg.expert_ffn,
            "layer_types": ["sliding_attention" if w else "full_attention"
                            for w in cfg.sliding_window_layout],
            "n_routed_experts": cfg.moe_experts // of, "num_experts_per_tok": cfg.moe_top_k,
            "n_shared_experts": 1, "routed_scaling_factor": cfg.moe_route_scale,
            "vocab_size": cfg.vocab_size, "expert_share": {"rank": rank, "of": of},
            "program_flags": ["--seq_length", str(cfg.max_seq_len)]}


def held_by(params, cfg, share):
    """``params`` as rank ``share[0]`` of ``share[1]`` holds them: its experts' stacks."""
    rank, of = share
    n = cfg.moe_experts // of
    layers = [dict(lp, mlp=dict(lp["mlp"], **{k: lp["mlp"][k][rank * n:(rank + 1) * n]
                                               for k in ("w1", "w2", "w3")}))
              if "router" in lp["mlp"] else lp for lp in params["layers"]]
    return dict(params, layers=layers)


def ref_logits(params, rows, cfg, share=None):
    return harness.reference(ARCH, ref_cfg, cfg, share).logits(params, jnp.asarray(rows))


# -- the configuration ------------------------------------------------------------------


def test_preset_runs_the_published_widths():
    cfg = PRESETS["dots3-note-prev"]
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.head_dim, cfg.vocab_size,
            cfg.max_seq_len) == (5120, 46, 128, 192, 152064, 524288)
    assert mla.dims(cfg) == (128, 128, 64, 128, 512) and cfg.mla_q_rank == 1024
    assert (cfg.mla_index_heads, cfg.mla_index_dim, cfg.mla_index_topk) == (64, 128, 2048)
    assert cfg.mla_rescale and cfg.mla_head_gate and not cfg.attn_gate
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.expert_ffn, cfg.ffn, cfg.moe_shared_ffn_dim,
            cfg.moe_dense_layers, cfg.moe_route_scale) == (256, 8, 1536, 13824, 1536, 1, 1.0)
    assert cfg.moe_router == "sigmoid_topk" and not cfg.moe_shared_gate
    assert not cfg.tie_word_embeddings and (cfg.rope_theta, cfg.norm_eps) == (8e7, 1e-5)
    full = [i for i, w in enumerate(cfg.window_layers) if not w]
    assert full == [0, 1] + list(range(5, 46, 4)) and len(full) == 13 and set(cfg.kinds) == {"mla"}
    win = cfg.layer_view(2)
    assert mla.dims(win) == (64, 192, 64, 128, 1024) and win.mla_q_rank == 1024
    assert (win.attn_window, win.rope_theta, win.mla_index_topk, win.head_dim) == (
        513, 5e4, 0, 256)
    assert mla.softmax_scale(win) == 256 ** -0.5 and mla.softmax_scale(cfg.layer_view(1)) == 192 ** -0.5
    assert (cfg.layer_view(5).attn_window, cfg.layer_view(5).mla_index_topk) == (0, 2048)
    cut = cfg.replace(num_layers=5)
    assert generation.layer_stacks(cut) == [("full", 0), ("full", 1), ("window", 0),
                                            ("window", 1), ("window", 2)]
    from galvatron_tpu.models import dots3
    assert dots3.DEFAULT_MODEL == "dots3-note-prev" and set(dots3.SIZES) <= set(PRESETS)


def _cell_cfg():
    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args

    with open(os.path.join(ROOT, "benchmark", "configs", "dots3-note-prev.json")) as f:
        config = json.load(f)
    ns = initialize_galvatron("serve", [*config["program_flags"], "--num_slots", "32",
                                        "--prefill_chunk", "1024"])
    return model_config_from_args(ns), config


def test_parameter_counts_and_the_three_stacks_bytes_are_the_files():
    """The cut's parameters and cache from shapes, nothing allocated: the file's numbers
    part by part, `theoretical.total_param_count` within the dense layer's pricing, and the
    reference's served counts within them."""
    from galvatron_tpu.search import theoretical as th

    cfg, config = _cell_cfg()
    assert (cfg.num_layers, cfg.moe_dense_layers, cfg.vocab_size, cfg.moe_share, cfg.moe_held,
            cfg.max_seq_len, cfg.param_dtype) == (5, 1, 19008, (0, 8), 32, 20480, jnp.bfloat16)
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    size = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa: E731
    counts = config["counts"]
    full, win = shapes["layers"][1]["mla"], shapes["layers"][2]["mla"]
    assert size(full) == mla.param_count(cfg.layer_view(1)) == counts["full_mixer"] == (
        5120 * 1024 + 1024 + 1024 * 24576 + 5120 * 576 + 512 + 512 * 32768 + 16384 * 5120
        + 5120 * 128 + 1024 * 8192 + 5120 * 128 + 256 + 5120 * 64)
    assert size(win) == mla.param_count(cfg.layer_view(2)) == counts["window_mixer"] == (
        5120 * 1024 + 1024 + 1024 * 16384 + 5120 * 1088 + 1024 + 1024 * 20480 + 8192 * 5120
        + 5120 * 64)
    assert size(full) == ARCH.mixer_weights(config, False) + ARCH.mixer_vectors(config, False)
    assert size(win) == ARCH.mixer_weights(config, True) + ARCH.mixer_vectors(config, True)
    mlp = shapes["layers"][1]["mlp"]
    assert size([mlp["w1"], mlp["w2"], mlp["w3"]]) == 32 * 23_592_960 == counts["held_experts"]
    assert size(mlp["shared"]) == 23_592_960 and size(mlp["router"]) == 5120 * 256 + 256
    assert size(shapes["layers"][0]["mlp"]) == 3 * 5120 * 13824 == counts["dense_mlp"]
    assert [size(lp) for lp in shapes["layers"]] == counts["layers"]
    assert size(shapes["embed"]) == size(shapes["head"]) == 19008 * 5120
    assert size(shapes) == counts["parameters"] and 4.0e9 < size(shapes) < 4.2e9
    expert_layers = sum(th.layer_param_count(cfg.layer_view(i), kind="mla") for i in range(1, 5))
    assert expert_layers == sum(counts["layers"][1:])
    assert th.total_param_count(cfg) - size(shapes) == (  # (a dense layer priced as an expert one)
        th.layer_param_count(cfg.layer_view(0), kind="mla") - counts["layers"][0])
    # the three stacks: whole slots of 576 and of 128 for two layers, a ring of 2,048 x 1,088
    layout = generation.cache_layout(cfg, 20480, 1024)
    assert (layout["latent_bytes_per_position"], layout["index_bytes_per_position"],
            layout["ring_bytes_per_position"], layout["ring_positions"]) == (1152, 256, 2176, 2048)
    assert (layout["full_layers"], layout["window_layers"]) == (2, 3)
    assert 32 * layout["bytes_per_slot"] == counts["cache_bytes"] == 32 * (
        2 * 20480 * (1152 + 256) + 3 * 2048 * 2176)
    cache = jax.eval_shape(lambda: generation.init_kv_cache(cfg, 32, 20480, tokens=1024))
    assert (cache.latent.shape, cache.index.shape, cache.ring.shape) == (
        (2, 32, 20480, 576), (2, 32, 20480, 128), (3, 32, 2048, 1088))
    assert sum(a.size * a.dtype.itemsize for a in cache) == counts["cache_bytes"]
    served = ARCH.served_params(config)
    # a forward reads at least the top-8 of a layer's 32 held experts, never fewer weights
    assert served["a_forward"] == size(shapes) - size(shapes["embed"]) - 4 * 24 * 23_592_960
    assert served["a_token"] == 5120 and ARCH.expert_layers(config) == 4
    assert ARCH.expert_step_bytes(config, 20.0) == 2 * 20.0 * 4 * 23_592_960


def test_cli_serve_parses_the_cells_flags():
    cfg, config = _cell_cfg()
    assert config["expert_share"] == {"rank": cfg.moe_share[0], "of": cfg.moe_share[1]}
    assert config["n_routed_experts"] == cfg.moe_held
    assert config["published"]["n_routed_experts"] == cfg.moe_experts
    assert [t == "sliding_attention" for t in config["layer_types"][:5]] == list(cfg.window_layers)
    assert ARCH.slot_positions(config) == cfg.max_seq_len == 20480
    assert cfg.dtype == jnp.bfloat16


# -- the full forward -------------------------------------------------------------------


@pytest.mark.parametrize("share", [(0, 1), (1, 2)])
def test_no_cache_forward_matches_the_reference(share):
    cfg = small_cfg(moe_share=share)
    params, rows = seeded(small_cfg(), length=40)
    params = held_by(params, cfg, share)
    close(forward(params, rows, cfg), ref_logits(params, rows, cfg), F32_TOL)


def test_bf16_in_place_of_float32_fails_the_tolerance():
    harness.bf16_fails_the_tolerance(small_cfg(), ref_logits, F32_TOL)


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """The eight shares' routed parts, the shared expert counted ONCE, add up to what the
    uncut layer gives."""
    whole = small_cfg()
    params, _ = seeded(whole)
    mlp = params["layers"][2]["mlp"]
    y = jax.random.normal(jax.random.key(5), (2, 24, whole.hidden_size))
    want = moe.moe_topk_block(y, mlp, whole)[0]
    shared = moe.moe_topk_block(y, mlp, whole.replace(moe_route_scale=0.0))[0]
    total = shared
    for rank in range(8):
        cut = whole.replace(moe_share=(rank, 8))
        mine = held_by(params, whole, (rank, 8))["layers"][2]["mlp"]
        total = total + moe.moe_topk_block(y, mine, cut)[0] - shared
    close(total, want, F32_TOL)
    assert worst(total + shared, want) > F32_TOL


# -- the three stacks ---------------------------------------------------------------------


def _served(params, cfg, rows, cache=None):
    """Row 0 through slot 2 (a prompt of 26 = 6 chunks and 2 tokens: chunks end inside the
    window of 9 and the 16 best keys and past both, the chunk at 16 begins the ring's second
    lap) decoded to 60: over three laps of the ring of 16, 16 of up to 60 keys selected;
    row 1 through slot 0 (a prompt of 7) decoded to 30: its context passes ``index_topk``
    and its ring laps DURING decode."""
    prompts = {2: (rows[0].tolist(), 26), 0: (rows[1].tolist(), 7)}
    return through_the_cache(params, cfg, prompts, {2: 60, 0: 30}, capacity=SLOT, cache=cache)


def test_chunked_prefill_then_decoding_through_the_three_stacks_matches_the_reference():
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=60)
    want = np.asarray(ref_logits(params, rows, cfg))
    assert generation.ring_positions(cfg, SLOT, CHUNK) == 16 and 7 < TOPK < 30 and 60 > 3 * 16
    cache = generation.init_kv_cache(cfg, 3, SLOT, tokens=CHUNK)
    assert isinstance(cache, mla.LatentCache)
    assert (cache.latent.shape, cache.index.shape, cache.ring.shape) == (
        (2, 3, SLOT, 20), (2, 3, SLOT, 8), (3, 3, 16, 28))
    got, _ = _served(params, cfg, rows)
    close(got[2], want[0], F32_TOL)
    close(got[0], want[1, :30], F32_TOL)


def test_several_key_blocks_a_row_match_the_reference(monkeypatch, retraced):
    """The loops the cell's sizes take (a slot is 20 key blocks of 1,024, a chunk's index
    scores 80 blocks of 256, a ring two): at key and index blocks of 8 a slot of 64 is eight
    blocks and the ring of 16 two, and the index scores, the decode core and a chunk's
    attention over slots and ring each go round several times, stop at the longest row's
    end, and slice the selection's mask a block at a time."""
    harness.small_tiles(monkeypatch, mla, key_block=8)
    monkeypatch.setattr(mla, "INDEX_BLOCK", 8)
    retraced()
    cfg = small_cfg()
    assert mla.key_block(SLOT) == mla.key_block(16) == mla._index_block(12, SLOT) == 8
    params, rows = seeded(cfg, batch=2, length=60)
    want = np.asarray(ref_logits(params, rows, cfg))
    got, _ = _served(params, cfg, rows)
    close(got[2], want[0], F32_TOL)
    close(got[0], want[1, :30], F32_TOL)


def test_the_decode_kernel_under_the_selection_matches_the_reference(monkeypatch, retraced):
    """Slots of whole key blocks (16 here, interpreted; 1,024 on the chip): a decode step's
    attention over the selected keys is the kernel `mla_decode` with the selection as its
    fifth operand, a row read up to its own length; the chunk form and the ring keep XLA's
    bodies.  The logits are the reference's as before."""
    from galvatron_tpu.ops import mla_decode

    harness.small_tiles(monkeypatch, mla, mla_decode)
    retraced()
    cfg = small_cfg()
    assert mla_decode.decode_path(SLOT, 20, cfg.num_heads, 16, jnp.float32) == "kernel"
    called = []
    real = mla_decode.latent_attention
    monkeypatch.setattr(mla_decode, "latent_attention",
                        lambda *a, **kw: called.append(kw["selected"].shape) or real(*a, **kw))
    params, rows = seeded(cfg, batch=2, length=60)
    want = np.asarray(ref_logits(params, rows, cfg))
    got, _ = _served(params, cfg, rows)
    close(got[2], want[0], F32_TOL)
    close(got[0], want[1, :30], F32_TOL)
    assert called and set(called) == {(3, 1, SLOT)}
    # what a step then fetches: a row's own length in whole blocks, a row out of use one
    layout = generation.cache_layout(cfg, SLOT, CHUNK)
    read = mla.step_counters(layout, [40, 9], 3, SLOT)
    assert read["dsa_read_positions"] == 48 + 16 + 16 and read["dsa_index_read_positions"] == 3 * SLOT


# -- the ring through the decode kernel (ops/mla_decode.py, span > 0; interpreted on the CPU) --

RING = 2048  # (the cell's ring: a window of 513 under prompt chunks of 1,024)


def _ring_rows(span, block, s):
    """(name, the first query's position) of the rows one batch holds: short of the window,
    past it, ending exactly at the ring's end, starting the second lap, lapped many times
    with the arc's newest place early in a block (a span below the block: the arc inside
    ONE block), with it just across a block's edge, with the arc WRAPPING the ring's end, a
    row out of use, and a slot a shorter request took over."""
    return [("short_of_the_window", span // 2), ("past_the_window", span + RING // 4),
            ("ends_at_the_rings_end", RING - s), ("starts_the_second_lap", RING),
            ("lapped_newest_late_in_a_block", 7 * RING + 4 * block - 2 - s),
            ("lapped_across_an_edge", 9 * RING + 2 * block + 3),
            ("lapped_and_wrapping", 5 * RING + 20), ("out_of_use", 0),
            ("taken_over_by_a_shorter_request", 300)]


@pytest.mark.parametrize("s", [1, 3], ids=["decode", "verify3"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("span,block", [(513, 128), (513, 256), (513, 512), (100, 128)])
def test_the_ring_through_the_decode_kernel_is_the_plain_ring_body(monkeypatch, span, block, dtype, s):
    """`attend_ring` through `mla_decode` (the arc's key blocks alone) against its plain
    body (every place by the position it holds) on seeded latents, `_ring_rows` in one
    batch: equal to a rounding; and with every place NO query of a row's window sees
    (never written, a lap ago, the slot's previous request) filled with NaN the kernel's
    output is bit for bit the clean ring's."""
    from galvatron_tpu.ops import mla_decode, pallas_common

    cfg = small_cfg(sliding_window_size=span, dtype=dtype)
    view = next(v for v in map(cfg.layer_view, range(cfg.num_layers)) if v.attn_window)
    assert view.attn_window == span
    names, firsts = zip(*_ring_rows(span, block, s))
    rows, (n, dn, dr, _, r) = len(firsts), mla.dims(view)
    first = jnp.asarray(firsts, jnp.int32)
    ks = jax.random.split(jax.random.key(span + block + s), 4)
    p = mla.init_params(ks[0], view)
    ring = jax.random.normal(ks[1], (3, rows, RING, r + dr), dtype)
    q_nope = jax.random.normal(ks[2], (rows, s, n, dn), dtype)
    q_rope = jax.random.normal(ks[3], (rows, s, n, dr), dtype)
    # the arcs are what their names say (host arithmetic: what the index map walks)
    arcs = {name: pallas_common.ring_arc(f, s, span, RING, block) for name, f in zip(names, firsts)}
    steps = pallas_common.ring_steps(s, span, RING, block)
    assert all(1 <= int(blocks) <= steps for _, blocks in arcs.values()) and steps < RING // block
    assert tuple(map(int, arcs["out_of_use"])) == (0, 1)
    b0, blocks = map(int, arcs["lapped_and_wrapping"])
    assert b0 + blocks > RING // block  # (the walk goes round the ring's end)
    if span < block:
        assert int(arcs["lapped_newest_late_in_a_block"][1]) == 1
        assert int(arcs["lapped_across_an_edge"][1]) == 2

    monkeypatch.setattr(mla_decode, "RING_BLOCKS", ())
    assert mla._ring_path(view, RING, s) == "plain"
    want = mla.attend_ring(q_nope, q_rope, ring, 1, first, p, view)
    monkeypatch.setattr(mla_decode, "RING_BLOCKS", (block,))
    assert mla._ring_path(view, RING, s) == "kernel" and mla_decode.ring_block(RING, span) == block
    attend = jax.jit(lambda c: mla.attend_ring(q_nope, q_rope, c, 1, first, p, view))
    got = attend(ring)
    assert got.shape == want.shape and got.dtype == dtype
    close(got.astype(jnp.float32), want.astype(jnp.float32), F32_TOL if dtype == jnp.float32 else 2e-2)
    held = generation._ring_key_positions(first + s - 1, jnp.arange(RING), RING)
    unseen = (held <= (first - span)[:, None]) | (held < 0)
    assert bool(unseen[names.index("taken_over_by_a_shorter_request"), 301 + s:].all())
    dirty = attend(jnp.where(unseen[None, :, :, None], jnp.nan, ring))
    assert bool(jnp.isfinite(dirty).all())
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(got))


@pytest.mark.parametrize("selected", [False, True], ids=["causal", "selected"])
def test_without_a_span_the_decode_kernel_walks_the_prefix_as_before(selected):
    """``span`` 0 (a full layer's slots, the sarvam cell's and this stack's two): the prefix
    walk, its grid the slot's blocks. Its values are to the bit those of a ring that never
    laps under a span as long as the slot (the same blocks in the same order, the masked
    body where the prefix walk takes a block whole), with and without a selection's values
    (`experiments/step_text_digest.py` holds the lowered TEXT to the parent's)."""
    from galvatron_tpu.ops import mla_decode

    ks = jax.random.split(jax.random.key(3), 3)
    stacked = jax.random.normal(ks[0], (2, 4, SLOT, 20), jnp.float32)
    q_cat = jax.random.normal(ks[1], (4, 1, 4, 20), jnp.float32)
    first = jnp.asarray([0, 15, 33, SLOT - 1], jnp.int32)
    seen = None
    if selected:
        seen = (jax.random.uniform(ks[2], (4, 1, SLOT)) < 0.5) & (
            jnp.arange(SLOT)[None, None] <= first[:, None, None])
        seen = seen.at[:, :, 0].set(True)
    kw = dict(rank=16, scale=0.25, block_k=16)
    got = mla_decode.latent_attention(q_cat, stacked, 1, first, selected=seen, **kw)
    same = mla_decode.latent_attention(q_cat, stacked, 1, first, selected=seen, span=0, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(same))
    if selected:
        with pytest.raises(ValueError, match="a selection is a full layer's"):
            mla_decode.latent_attention(q_cat, stacked, 1, first, selected=seen, span=SLOT, **kw)
    else:
        ring = mla_decode.latent_attention(q_cat, stacked, 1, first, span=SLOT, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ring))


def test_the_ring_through_the_decode_kernel_serves_the_references_rows(monkeypatch, retraced):
    """A ring of whole key blocks (4 here, interpreted; `ring_block`'s on the chip): a decode
    step's attention of the three sliding layers is the kernel `mla_decode` over the ring
    with the layer's window as its span, the rows lapping the ring during decode; a prompt
    chunk keeps XLA's body. The logits are the reference's as before, and the layout and the
    counters say which path read the ring and what it fetched."""
    from galvatron_tpu.ops import mla_decode

    monkeypatch.setattr(mla_decode, "RING_BLOCKS", (4,))
    retraced()
    cfg = small_cfg()
    called = []
    real = mla_decode.latent_attention
    monkeypatch.setattr(mla_decode, "latent_attention", lambda *a, **kw: called.append(
        (a[1].shape, kw.get("span", 0), kw.get("block_k"))) or real(*a, **kw))
    params, rows = seeded(cfg, batch=2, length=60)
    want = np.asarray(ref_logits(params, rows, cfg))
    got, _ = _served(params, cfg, rows)
    close(got[2], want[0], F32_TOL)
    close(got[0], want[1, :30], F32_TOL)
    assert set(called) == {((3, 3, 16, 28), WINDOW, 4)}
    layout = generation.cache_layout(cfg, SLOT, CHUNK)
    assert (layout["ring_decode_path"], layout["ring_key_block"]) == ("kernel", 4)
    # an arc of 9 places touches 3 blocks of 4 wherever it starts, a row out of use one
    read = mla.step_counters(layout, [40, 9], 3, SLOT)
    assert read["ring_decode_path"] == "kernel"
    assert read["latent_ring_read_positions"] == 12 + 12 + 4 < 3 * 16
    assert read["latent_ring_live_positions"] == 9 + 9


@pytest.mark.parametrize("path", ["kernel", "plain"])
def test_the_rings_read_positions_are_the_arithmetic_of_the_path_in_force(path):
    """`step_counters` at the cell's sizes (32 slots x 20,480, a window of 513), host
    arithmetic: under prompt chunks of 1,024 the ring is 2,048 places, whole key blocks, and
    the kernel fetches of each row the blocks its arc touches (its length rounded up to the
    block until it has passed the window, `ring_steps` blocks from then on, lapped or not;
    a row out of use one block); under chunks of 1,000 the ring is 2,000 places, which no
    block divides, and the plain body reads every row's whole ring. ``ring_decode_path`` in
    the layout and in the counters says which."""
    from galvatron_tpu.ops import mla_decode, pallas_common

    cfg = PRESETS["dots3-note-prev"].replace(num_layers=5, max_seq_len=20480, dtype=jnp.bfloat16)
    chunk = {"kernel": 1024, "plain": 1000}[path]
    layout = generation.cache_layout(cfg, 20480, chunk)
    ring = layout["ring_positions"]
    assert ring == {"kernel": 2048, "plain": 2000}[path] and layout["ring_decode_path"] == path
    lengths = [300, 513, 1500, 2048, 2049, 5000, 10870]
    got = mla.step_counters(layout, lengths, 32, 20480)
    assert got["ring_decode_path"] == path
    assert got["latent_ring_live_positions"] == 300 + 6 * 513
    if path == "plain":
        assert layout["ring_key_block"] == 0
        assert got["latent_ring_read_positions"] == 32 * ring
        return
    block = layout["ring_key_block"]
    assert block == mla_decode.ring_block(ring, 513) and block in (128, 256, 512)
    steps = pallas_common.ring_steps(1, 513, ring, block)
    assert steps == 512 // block + 1
    want = sum(min(steps, -(-n // block)) for n in lengths) + (32 - len(lengths))
    assert got["latent_ring_read_positions"] == want * block
    # every row lapped: the whole arc a row and nothing else, whatever the lengths
    lapped = mla.step_counters(layout, [4096 + 97 * i for i in range(32)], 32, 20480)
    assert lapped["latent_ring_read_positions"] == 32 * steps * block
    assert lapped["latent_ring_read_positions"] / lapped["latent_ring_live_positions"] <= 2.0
    # a verify window of 3 queries widens the arc by 2 places: the same path, asked again
    wide = mla.step_counters(layout, [n + 2 for n in lengths], 32, 20480, window=3)
    assert wide["ring_decode_path"] == "kernel"
    assert got["latent_ring_read_positions"] <= wide["latent_ring_read_positions"] <= (
        got["latent_ring_read_positions"] + len(lengths) * block)


def test_a_slot_used_again_serves_the_new_request():
    """The slots are not zeroed: the second request reads nothing the first one left in
    the latent, in the index keys or on the ring."""
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=4, length=60)
    want = np.asarray(ref_logits(params, rows, cfg))
    _, cache = _served(params, cfg, rows[:2])
    got, _ = _served(params, cfg, rows[2:], cache=cache)
    close(got[2], want[2], F32_TOL)
    close(got[0], want[3, :30], F32_TOL)


def _reference_selection(params, rows, cfg):
    """The reference's selected keys of layer 0 (a full layer whose input is the embedding:
    both sides have it exactly) -> [(s, s) bool a row]."""
    rc = ref_cfg(cfg)
    w = ARCH.published_weights(params, rc)
    lw = w["layers"][0]
    out = []
    with jax.default_matmul_precision("highest"):
        cos, sin = ARCH.rope_tables(cfg.mla_rope_dim, cfg.rope_theta, rows.shape[1])
        for row in np.asarray(rows):
            h = reference.rms_norm(w["embed_tokens"][row][None], lw["input_layernorm"], cfg.norm_eps)
            c_q = reference.rms_norm(h @ lw["q_a_proj"], lw["q_a_layernorm"], cfg.norm_eps)
            scores = ARCH.index_scores(h, c_q, lw["indexer"], rc, cos, sin)
            out.append(np.asarray(ARCH.selected_keys(scores, cfg.mla_index_topk)))
    return out


def _recorded_selection(monkeypatch, retraced):
    """Every selection a traced forward makes, in order, through the program's one seam,
    `mla.select_mask` (a decode step's mask over its row's slot, a chunk's over its
    queries): [[the keys each query of a row attends, as a set] a row]."""
    seen = []
    mask = mla.select_mask

    def note(m):
        seen.append([[set(np.flatnonzero(q).tolist()) for q in row] for row in m])

    def select_mask(scores, topk):
        m = mask(scores, topk)
        jax.debug.callback(note, m, ordered=True)
        return m

    monkeypatch.setattr(mla, "select_mask", select_mask)
    retraced()
    return seen


@pytest.mark.parametrize("form", ["decode", "chunk"])
def test_the_float32_program_selects_exactly_the_references_keys(monkeypatch, retraced, form):
    """``index_topk`` a quarter of the context: at every query row of layer 0 the program's
    selected set IS the reference's, in the decode form (a step's mask over the row's slot)
    and in the chunk form (a chunk's over its queries)."""
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=60)
    want = _reference_selection(params, rows, cfg)
    seen = _recorded_selection(monkeypatch, retraced)
    prompt = 8 if form == "decode" else 60
    _, _ = through_the_cache(params, cfg, {1: (rows[0].tolist(), prompt)}, {1: 60}, capacity=SLOT)
    jax.effects_barrier()
    layer0 = seen[::2]  # (two full layers a forward: layer 0's record, then layer 1's)
    # (a chunk that ends within the first TOPK positions selects every key and asks no mask)
    if form == "chunk":
        assert len(layer0) == (60 - TOPK) // CHUNK and all(len(rec) == 1 for rec in layer0)
        got = [keys for rec in layer0 for keys in rec[0]]
        first = TOPK
    else:
        assert len(layer0) == 60 - prompt and all(len(rec) == 3 for rec in layer0)
        got, first = [rec[1][0] for rec in layer0], prompt  # (a step: all three rows)
    assert len(got) == 60 - first
    for t, keys in enumerate(got, start=first):
        assert keys == set(np.flatnonzero(want[0][t]).tolist()), t
        assert len(keys) == min(t + 1, TOPK)
    assert any(keys != set(range(t - TOPK + 1, t + 1)) for t, keys in enumerate(got, start=first)
               if t >= TOPK)  # (a learned selection, not a window)


def _bucketed_top_k(scores, k):
    """What an approximate top-k does (``approx_max_k`` on the chip: ONE candidate a
    bucket, then the best k of the candidates): two of the true best in one bucket lose one."""
    p = scores.shape[-1]
    buckets = 2 * k
    if p % buckets or p <= buckets:
        return jax.lax.top_k(scores, k)
    parts = scores.reshape(*scores.shape[:-1], buckets, p // buckets)
    best = jnp.max(parts, axis=-1)
    where = jnp.argmax(parts, axis=-1) + jnp.arange(buckets) * (p // buckets)
    vals, pick = jax.lax.top_k(best, k)
    return vals, jnp.take_along_axis(where, pick, axis=-1)


def _dense(cfg, params, monkeypatch):
    monkeypatch.setattr(mla, "select_mask", lambda scores, topk: scores > -jnp.inf)
    return cfg, params


def _approximate(cfg, params, monkeypatch):
    def select_mask(scores, topk):
        vals, at = _bucketed_top_k(scores, min(topk, scores.shape[-1]))
        rows = jnp.arange(scores.shape[1])[None, :, None]
        hit = jnp.zeros(scores.shape, bool).at[jnp.arange(scores.shape[0])[:, None, None], rows,
                                               at].set(vals > -jnp.inf)
        return hit & (scores > -jnp.inf)

    monkeypatch.setattr(mla, "select_mask", select_mask)
    return cfg, params


def _without(name):
    def plant(cfg, params, monkeypatch):
        return cfg.replace(**{name: False}), params
    return plant


def _window_off_by_one(cfg, params, monkeypatch):
    return cfg.replace(sliding_window_size=WINDOW - 1), params


def _one_theta(cfg, params, monkeypatch):
    return cfg.replace(swa_rope_theta=cfg.rope_theta), params


#: fault -> (cfg, params, monkeypatch) -> the (cfg, params) the program then runs
FAULTS = {
    "dense_attention": _dense,
    "approximate_top_k": _approximate,
    "head_gate_dropped": _without("mla_head_gate"),
    "rescale_dropped": _without("mla_rescale"),
    "window_without_the_querys_own_position": _window_off_by_one,
    "the_full_layers_theta_on_the_window_layers": _one_theta,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_tolerance(monkeypatch, retraced, fault):
    """Dense attention over all keys "because it is inside the tolerance", an approximate
    top-k, and each part of the layer a plain latent stack lacks, in the CACHED forwards:
    the tolerance these tests compare by tells each from the sound program."""
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=60)
    want = np.asarray(ref_logits(params, rows, cfg))
    cfg, params = FAULTS[fault](cfg, params, monkeypatch)
    retraced()
    got, _ = _served(params, cfg, rows)
    assert worst(got[2], want[0]) > 4 * F32_TOL and worst(got[0], want[1, :30]) > 4 * F32_TOL


def test_lockstep_generation_is_the_references_greedy_choice():
    harness.lockstep_generation_is_greedy(small_cfg(), ref_logits, max_new_tokens=24)


# -- the engine ---------------------------------------------------------------------------


def test_the_engines_tap_rows_are_the_references_and_its_spans_count_the_three_stacks(monkeypatch):
    """Three requests through the engine (prompts past and inside the window and the
    selection, answers that lap the ring), every token's logits row kept by the tap: the
    rows equal the reference's full forward over prompt + served tokens; the ``decode``
    spans carry the stack's OWN counters and none a dense latent's or a K/V ring's reader
    takes."""
    from galvatron_tpu.obs.tracing import tracer

    harness.first_request_ids(monkeypatch)  # (a nearly greedy draw held to the arg-max)
    cfg = small_cfg(moe_share=(1, 2))
    params, rows = seeded(small_cfg(), batch=3, length=30)
    params = held_by(params, cfg, (1, 2))
    prompts = [rows[0, :26].tolist(), rows[1, :5].tolist(), rows[2, :13].tolist()]
    new = [30, 40, 12]
    engine = harness.engine(cfg, params)
    tracer.enable(capacity=1 << 13)
    tracer.clear()
    try:
        bufs = [np.zeros((n, cfg.vocab_size), np.float32) for n in new]
        reqs = [engine.submit_request(p, n, temperature=1e-4, capture_logits=b)
                for p, n, b in zip(prompts, new, bufs)]
        for r in reqs:
            r.future.result(timeout=120)
        served = [list(r.generated) for r in reqs]
        spans = [e for e in tracer.snapshot() if e.get("ph") == "X"]
        stats, layout = engine.stats(), engine.cache_layout
    finally:
        tracer.disable()
        engine.close()
    for prompt, got, buf, req in zip(prompts, served, bufs, reqs):
        assert req.logits_rows == len(got)
        seq = jnp.asarray([prompt + got[:-1]], jnp.int32)
        want = np.asarray(ref_logits(params, seq, cfg))[0, len(prompt) - 1:]
        close(buf, want, F32_TOL)
        assert [int(np.argmax(r)) for r in buf] == got
    decode = [e["args"] for e in spans if e["name"] == "decode"]
    assert decode and all(a["moe_held_experts"] == 4 for a in decode)
    for a in decode:
        assert not [k for k in a if k.startswith(("kv_", "latent_live", "latent_read",
                                                  "latent_cache"))], a
        assert (a["dsa_full_layers"], a["latent_ring_layers"]) == (2, 3)
        assert (a["dsa_latent_bytes_per_position"], a["dsa_index_bytes_per_position"],
                a["latent_ring_bytes_per_position"]) == (80, 32, 112)
        assert 0 < a["dsa_selected_positions"] <= a["dsa_live_positions"]
        # (every row's slot read up to the longest row's end, in whole key blocks)
        assert a["dsa_live_positions"] <= a["dsa_read_positions"] == 3 * SLOT
        assert a["dsa_index_read_positions"] == 3 * SLOT
        # (a ring of 16 places is no whole key block of `mla_decode.RING_BLOCKS`: the plain
        # body's arithmetic, every row's whole ring; the span says which path counted)
        assert a["ring_decode_path"] == layout["ring_decode_path"] == "plain"
        assert 0 < a["latent_ring_live_positions"] <= a["latent_ring_read_positions"] == (
            engine.slots.num_slots * layout["ring_positions"]) == 3 * 16
    # (the rows grow: the selection stops at 16 a row, the ring's live part at 9)
    assert max(a["dsa_live_positions"] for a in decode) > 3 * TOPK
    assert max(a["dsa_selected_positions"] for a in decode) == 3 * TOPK
    assert max(a["latent_ring_live_positions"] for a in decode) == 3 * WINDOW
    assert stats["cache_kind"] == "latent" and stats["cache_stacks"] == {
        "full": 2, "window": 3, "state": 0}
    assert stats["cache_bytes"] == 3 * 4 * (2 * SLOT * (20 + 8) + 3 * 16 * 28)
    assert stats["chunk_path"] == "plain"


def test_the_engine_serves_what_plain_generation_gives():
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=3, length=30)
    prompts = [rows[0, :21].tolist(), rows[1, :6].tolist(), rows[2, :13].tolist()]
    want = harness.generations(params, cfg, prompts, max_new_tokens=20)
    served, stats, _ = harness.serve(harness.engine(cfg, params, num_slots=2), prompts, 20)
    assert served == want and stats["kv_backend"] == "slot"


def test_the_engine_serves_it_under_int8_weights():
    """`--serve_quant int8` (the benchmark's control below the stated precision): every plain
    GEMM of the mixer is quantized, the gate's and the indexer's three among them (a
    selection hangs on those); W_kvb, absorbed a head at a time, is not."""
    from galvatron_tpu.ops import quant

    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=24)
    qparams = quant.quantize_params(params, cfg)
    q1 = qparams["layers"][1]["mla"]
    assert all(isinstance(q1[k], quant.QuantTensor) for k in ("wqa", "wqb", "wkva", "wo", "wgate"))
    assert all(isinstance(q1["index"][k], quant.QuantTensor) for k in ("wq", "wk", "ww"))
    assert not isinstance(q1["wkvb"], quant.QuantTensor)
    assert not isinstance(q1["index"]["k_norm"]["scale"], quant.QuantTensor)
    want = forward(qparams, rows, cfg)
    cache = generation.init_kv_cache(cfg, 2, SLOT, tokens=24)
    got, _ = harness.step_forward(qparams, cfg, cache, rows, jnp.zeros((2,), jnp.int32))
    close(got, want, 1e-4)
    assert worst(want, forward(params, rows, cfg)) > 1e-4  # int8 is not float32
    with harness.engine(cfg, params, serve_quant="int8", quant_drift_max=1e9) as engine:
        assert engine.quant_parity["max_abs_logit_drift"] > 0
        out = engine.generate([rows[0, :9].tolist()], max_new_tokens=4)
    assert len(out[0]) == 9 + 4


@pytest.mark.parametrize("what,kw,message", [
    ("paged_backend", dict(kv_num_blocks=-1, kv_block_size=8), "paged backend"),
    ("speculation", dict(spec_decode_k=2, spec_drafter="prompt_lookup"),
     "speculative decoding .* latent-attention stack with an indexer"),
])
def test_the_engine_refuses_by_name(what, kw, message):
    cfg = small_cfg()
    params, _ = seeded(cfg)
    with pytest.raises(ValueError, match=message):
        harness.engine(cfg, params, **kw)
    assert {"paged_kv", "spec_decode"} <= {limit.what for limit in mixers.limits(cfg)}
    assert "kv_cache" not in {limit.what for limit in mixers.limits(cfg)}


# -- training -----------------------------------------------------------------------------


def test_every_gradient_matches_the_references():
    """The gradient of the training objective by every parameter; the selection is a
    constant of the backward pass on both sides, so the indexer's weights get none."""
    cfg = small_cfg(max_seq_len=32)  # (``noaux_tc``: the objective has no auxiliary loss)
    assert cfg.moe_aux_coef == 0.0
    params, rows = seeded(cfg, length=32, targets=True)
    ref = harness.reference(ARCH, ref_cfg, cfg)

    def program(p):
        s, n, aux = modeling.moe_loss_sum(p, rows, cfg)
        return s / n + cfg.moe_aux_coef * aux["moe_aux_loss"]

    def plain(p):
        ce, aux = ref.objective(p, rows)
        return ce + cfg.moe_aux_coef * aux

    got = harness.loss_and_gradients(program, params)[1]
    want = harness.loss_and_gradients(plain, params)[1]
    harness.close_by_leaf(got, want, 2e-4, floor=0.0)
    for path, w in jax.tree.leaves_with_path(want):
        name = jax.tree_util.keystr(path)
        zero = float(jnp.abs(w).max()) == 0
        # (no gradient: the indexer, and the router's bias, which SELECTS only)
        assert zero == ("'index'" in name or name.endswith("['router']['bias']")), name
    for lp in got["layers"][:2]:
        assert all(float(jnp.abs(g).max()) == 0 for g in jax.tree.leaves(lp["mla"]["index"]))


def test_the_runtime_trains_it_on_one_device():
    _, state = harness.trains_on_one_device(small_cfg(max_seq_len=32), steps=6, drop=0.2)
    layers = state["params"]["layers"]
    assert {"wqa", "wqb", "wgate", "index"} <= set(layers[0]["mla"])
    assert "index" not in layers[2]["mla"] and layers[2]["mla"]["wkva"].shape == (32, 28)


REFUSALS = [
    ("tp", {}, dict(tp=2), "tensor parallelism .* latent-attention layers"),
    ("cp", {}, dict(cp=2), "context parallelism"),
    ("pp", {}, lambda cfg: harness.plan(cfg, pp=2, mixed_precision="fp32"),
     "pipeline parallelism"),
    ("pack_sequences", dict(pack_sequences=True), {}, "pack_sequences is not implemented"),
    ("flash", dict(attn_impl="flash"), {}, "attention path other than XLA's"),
]
test_build_runtime_refuses_by_name = harness.refuses(REFUSALS, lambda **kw: small_cfg(
    max_seq_len=32, **kw))
