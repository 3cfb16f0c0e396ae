"""smallthinker-class stacks (sliding-window layers with rotary over a RING cache
beside full NoPE layers over whole slots in one slot cache, ReGLU experts routed
from the attention block's input) on the normal path, against the plain reference
``benchmark/references/smallthinker.py`` on seeded random weights, at a small size
on the CPU: the full forward; chunked prefill then decoding through the ring, past
two laps of it; the window's edge; the shares of the experts adding up to the uncut
layer; the engine end to end; each refusal by name; and the other expert models'
lowered programs left as they were."""

import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference
from galvatron_tpu.models import generation, modeling, moe
from galvatron_tpu.models.modeling import PRESETS
from galvatron_tpu.ops import kv_decode
from tests import _stack_harness as harness
from tests._stack_harness import close, forward, seeded, through_the_cache, worst

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = reference.load(ROOT, "smallthinker")

# float32, the same arithmetic in another order (the program sorts the pairs and runs
# grouped GEMMs, attends over a ring or a block of keys at a time with a running
# softmax; the reference loops over key/value heads and query blocks)
F32_TOL = 5e-5
WINDOW, CHUNK, SLOT = 8, 4, 64


def small_cfg(**kw):
    """Two periods ``F W W W`` at small widths: window 8, 16 experts top-3, all held."""
    base = dict(vocab_size=96, hidden_size=32, num_layers=8, num_heads=4, num_kv_heads=2,
                attn_head_dim=8, ffn_dim=24, max_seq_len=SLOT, sliding_window_size=WINDOW,
                moe_experts=16, moe_top_k=3, moe_ffn_dim=24, dtype=jnp.float32)
    base.update(kw)
    return PRESETS["smallthinker-21b-a3b"].replace(**base)


def ref_cfg(cfg, share=None):
    rank, of = share or cfg.moe_share
    return {"hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "num_hidden_layers": cfg.num_layers, "max_position_embeddings": cfg.max_seq_len,
            "sliding_window_size": cfg.sliding_window_size,
            "sliding_window_layout": list(cfg.sliding_window_layout),
            "rope_layout": list(cfg.rope_layout), "moe_ffn_hidden_size": cfg.expert_ffn,
            "moe_num_primary_experts": cfg.moe_experts // of,
            "moe_num_active_primary_experts": cfg.moe_top_k, "vocab_size": cfg.vocab_size,
            "expert_share": {"rank": rank, "of": of}}


def ref_logits(params, rows, cfg, share=None):
    return harness.reference(ARCH, ref_cfg, cfg, share).logits(params, jnp.asarray(rows))


# -- the configuration ------------------------------------------------------------------


def test_preset_runs_the_published_widths():
    cfg = PRESETS["smallthinker-21b-a3b"]
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (
        2560, 52, 28, 4, 128)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.expert_ffn, cfg.ffn) == (64, 6, 768, 768)
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.sliding_window_size) == (151936, 16384, 4096)
    assert cfg.sliding_window_layout == cfg.rope_layout == (0, 1, 1, 1) * 13
    assert cfg.glu_act == "relu" and cfg.moe_router_input == "attn" and cfg.moe_norm_topk
    assert cfg.moe_router_precision == "highest"
    assert not cfg.tie_word_embeddings and cfg.rope_theta == 1.5e6 and cfg.norm_eps == 1e-6
    # a window layer is attention: no kind of its own, attention's parameters
    assert set(cfg.kinds) == {"attention"} and cfg.windowed
    cut = cfg.replace(num_layers=16)
    assert cut.window_layers == (False, True, True, True) * 4
    view = cut.layer_view(5)
    assert (view.attn_window, view.pos_embed) == (4096, "rope")
    assert (cut.layer_view(4).attn_window, cut.layer_view(4).pos_embed) == (0, "nope")
    # a model without layouts runs every layer under the model's own configuration
    plain = PRESETS["olmoe-1b-7b"]
    assert plain.layer_view(3) is plain and not plain.windowed


def test_parameter_counts_are_the_issues_arithmetic():
    cfg = PRESETS["smallthinker-21b-a3b"].replace(num_layers=16, vocab_size=37984,
                                                  moe_share=(0, 4))
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    layer = sum(a.size for a in jax.tree.leaves(shapes["layers"][0]))
    assert layer == 2560 * (3584 + 1024) + 3584 * 2560 + 2560 * 64 + 16 * 3 * 2560 * 768 + 2 * 2560
    assert set(shapes["layers"][3]["attn"]) == {"wqkv", "wo"}  # a window layer's: attention's
    rc = ref_cfg(cfg)
    served = ARCH.served_params(rc)
    total = sum(a.size for a in jax.tree.leaves(shapes))
    assert served["a_forward"] + cfg.vocab_size * cfg.hidden_size == total
    assert served["a_token"] == 2560


# -- the full forward -------------------------------------------------------------------


@pytest.mark.parametrize("share", [(0, 1), (1, 4)])
def test_no_cache_forward_matches_the_reference(share):
    cfg = small_cfg(moe_share=share)
    params, rows = seeded(cfg, length=40)
    close(forward(params, rows, cfg), ref_logits(params, rows, cfg), F32_TOL)


def test_the_windows_edge():
    """Key ``p - window`` is unseen and ``p - window + 1`` seen: moving a token the
    last query cannot see through any window layer moves nothing there, where every
    layer is a window layer; the next token on does."""
    cfg = small_cfg(num_layers=1, sliding_window_layout=(1,), rope_layout=(1,))
    params, rows = seeded(cfg, batch=1, length=20)
    base = forward(params, rows, cfg)[0, -1]
    p = rows.shape[1] - 1
    moved = lambda j: forward(  # noqa: E731
        params, rows.at[0, j].set((rows[0, j] + 1) % cfg.vocab_size), cfg)[0, -1]
    assert np.array_equal(np.asarray(moved(p - WINDOW)), np.asarray(base))
    assert not np.array_equal(np.asarray(moved(p - WINDOW + 1)), np.asarray(base))
    # a full layer sees it
    full = cfg.replace(sliding_window_layout=(0,))
    seen = forward(params, rows.at[0, p - WINDOW].set(
        (rows[0, p - WINDOW] + 1) % cfg.vocab_size), full)[0, -1]
    assert not np.array_equal(np.asarray(seen), np.asarray(forward(params, rows, full)[0, -1]))


def test_a_nope_layer_is_blind_to_rope_theta():
    nope = small_cfg(num_layers=2, rope_layout=(0, 0), sliding_window_layout=(0, 1))
    params, rows = seeded(nope, length=24)
    a = forward(params, rows, nope)
    assert np.array_equal(np.asarray(a), np.asarray(
        forward(params, rows, nope.replace(rope_theta=100.0))))
    roped = nope.replace(rope_layout=(0, 1))
    assert not np.array_equal(np.asarray(forward(params, rows, roped)), np.asarray(
        forward(params, rows, roped.replace(rope_theta=100.0))))


def test_the_routers_choice_ignores_its_own_layers_attention():
    """Layer i's router reads the attention block's INPUT: with other attention
    weights in layer i the layer's (f, P) statistics stay, bit for bit."""
    cfg = small_cfg(num_layers=1, sliding_window_layout=(1,), rope_layout=(1,))
    params, rows = seeded(cfg, length=24)
    other = jax.tree.map(lambda a: a, params)
    other["layers"][0]["attn"] = jax.tree.map(lambda a: a * 1.5, params["layers"][0]["attn"])
    stats = [modeling.forward_with_stats(p, rows, cfg)[1][0] for p in (params, other)]
    for a, b in zip(*stats):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # and it would not, were the router fed from the MLP block's input
    late = cfg.replace(moe_router_input="mlp")
    stats = [modeling.forward_with_stats(p, rows, late)[1][0] for p in (params, other)]
    assert not np.array_equal(np.asarray(stats[0][1]), np.asarray(stats[1][1]))


def test_reglu_is_relu_on_the_gate():
    cfg = small_cfg(num_layers=1, sliding_window_layout=(0,), rope_layout=(1,))
    params, rows = seeded(cfg, length=16)
    relu = forward(params, rows, cfg)
    silu = forward(params, rows, cfg.replace(glu_act="silu"))
    close(relu, ref_logits(params, rows, cfg), F32_TOL)
    assert worst(silu, relu) > 1e-3
    with pytest.raises(ValueError, match="glu_act"):
        forward(params, rows, cfg.replace(glu_act="gelu"))


def test_the_held_shares_kernels_run_reglu():
    """The bounded path of a held share (`moe.held_experts`, the shape the cell's
    widths take: hidden and expert width multiples of 128) with ``act`` "relu", its
    kernels interpreted here: output and every gradient are the plain grouped path's
    with relu on the gate, and not SwiGLU's."""
    tokens, k, held, first, hidden, width, tile = 64, 3, 4, 4, 128, 128, 16
    ks = jax.random.split(jax.random.key(7), 7)
    idx = jax.random.randint(ks[0], (tokens, k), 0, 16)  # experts 4..7 are held
    x = jax.random.normal(ks[1], (tokens, hidden), jnp.float32)
    weights = jax.nn.softmax(jax.random.normal(ks[2], (tokens, k)), axis=-1)
    w1, w3 = (jax.random.normal(key, (held, hidden, width), jnp.float32) * 0.09 for key in ks[3:5])
    w2 = jax.random.normal(ks[5], (held, width, hidden), jnp.float32) * 0.09
    cot = jax.random.normal(ks[6], (tokens, hidden), jnp.float32)
    lay = moe.held_layout(idx, held, tile, first)

    def plain(x, weights, w1, w3, w2):
        rows = moe._dispatch(x, lay.row_pair // k, lay.row_valid, lay.pair_row)
        mid = jax.nn.relu(moe.grouped_gemm(rows, w1, lay, tile)) * moe.grouped_gemm(
            rows, w3, lay, tile)
        return moe._combine(moe.grouped_gemm(mid, w2, lay, tile), weights, lay.pair_row,
                            lay.row_pair, lay.row_valid)

    def bounded(act):
        def body(x, weights, w1, w3, w2):
            return moe.held_experts(x, weights, jnp.concatenate([w1, w3], axis=-1), w2,
                                    lay.pair_row, lay.row_pair, lay.row_valid, lay.tile_group,
                                    lay.num_tiles, tile, act)
        return body

    def with_gradients(body):
        y, vjp = jax.vjp(body, x, weights, w1, w3, w2)
        return (y,) + vjp(cot)

    want, got, silu = (with_gradients(f) for f in (plain, bounded("relu"), bounded("silu")))
    for name, g, w in zip(("y", "dx", "dweights", "dw1", "dw3", "dw2"), got, want):
        assert worst(g, w) <= 2e-6, name
    assert worst(silu[0], want[0]) > 1e-3


def test_bf16_in_place_of_float32_fails_the_tolerance():
    harness.bf16_fails_the_tolerance(small_cfg(), ref_logits, F32_TOL)


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """The four shares' expert parts add up to what the uncut reference gives for the
    whole layer, nothing counted twice: the residual stream after one layer."""
    whole = small_cfg(num_layers=1, sliding_window_layout=(1,), rope_layout=(1,))
    params, rows = seeded(whole, length=24)
    h = params["layers"][0]
    x = modeling.embed(rows, params, whole)
    normed = modeling.norm(x, h["attn_norm"], whole)
    y = modeling.norm(x + modeling.attn_block(
        normed, h["attn"], whole.layer_view(0), modeling.rope_tables(whole, rows.shape[1])),
        h["mlp_norm"], whole)
    want = moe.moe_topk_block(y, h["mlp"], whole, router_x=normed)[0]
    total = 0.0
    for rank in range(4):
        cut = whole.replace(moe_share=(rank, 4))
        mine = dict(h["mlp"], **{k: h["mlp"][k][rank * 4:(rank + 1) * 4] for k in ("w1", "w2", "w3")})
        total = total + moe.moe_topk_block(y, mine, cut, router_x=normed)[0]
    close(total, want, F32_TOL)
    # and the uncut reference's routed sum is the same numbers
    rc = ref_cfg(whole)
    lw = ARCH.published_weights(params, rc)["layers"][0]
    with jax.default_matmul_precision("highest"):
        ref = ARCH.moe(y[:1], (normed @ h["mlp"]["router"]["w"])[:1], lw, rc)
    close(want[:1], ref, F32_TOL)


# -- the ring ---------------------------------------------------------------------------


@pytest.mark.parametrize("verify", [0, 3], ids=["decode", "verify4"])
def test_chunked_prefill_then_decoding_through_the_ring_matches_the_reference(verify):
    """Logits of every position, prefilled in chunks of 4 (a chunk that wraps the
    ring of 8 + 4 among them) and decoded in shared steps with rows at different
    depths, past two laps of the ring, equal the reference's full forward."""
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=44)
    want = np.asarray(ref_logits(params, rows, cfg))
    ring = generation.ring_positions(cfg, SLOT, max(CHUNK, 1 + verify))
    assert ring == WINDOW + CHUNK
    prompts = {2: (rows[0].tolist(), 26), 0: (rows[1].tolist(), 7)}
    # 26 = 6 whole chunks and 2 tokens: the chunk at 8 ends the first lap, the one at
    # 12 begins the second; the verify windows cross the ring's end where they fall
    got, _ = through_the_cache(params, cfg, prompts, {2: 44, 0: 30}, verify=verify)
    assert 44 > 3 * ring
    close(got[2], want[0], F32_TOL)
    close(got[0], want[1, :30], F32_TOL)


def test_chunks_lap_the_ring_and_a_verify_window_crosses_its_end():
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=1, length=40)
    want = np.asarray(ref_logits(params, rows, cfg))
    # chunks of 5: the ring is 8 + 5 rounded up to 15, three chunks a lap, none crosses
    got, cache = through_the_cache(params, cfg, {1: (rows[0].tolist(), 33)}, {1: 40}, chunk=5)
    assert cache.wk.shape[3] == 15
    close(got[1], want[0], F32_TOL)
    ring = jnp.zeros((1, 2, 1, 15, 1), jnp.float32)
    new = jnp.arange(1, 6, dtype=jnp.float32).reshape(1, 1, 5, 1)
    # one start: a chunk at a multiple of 5 lands whole
    out = generation.write_ring(ring, 0, new, [(1, jnp.int32(25))], aligned=True)
    assert np.asarray(out)[0, 1].ravel().tolist() == [0] * 10 + [1, 2, 3, 4, 5]
    # a start a row: a verify window of 3 from position 28 takes places 13, 14, 0
    both = jnp.stack([new[0, :, :3], 10 * new[0, :, :3]])
    out = generation.write_ring(ring, 0, both, [(0, jnp.int32(2)), (1, jnp.int32(28))],
                                aligned=False)
    assert np.asarray(out)[0, 0].ravel().tolist() == [0, 0, 1, 2, 3] + [0] * 10
    assert np.asarray(out)[0, 1].ravel().tolist() == [30] + [0] * 12 + [10, 20]
    # ONE row with an offset of its own (an engine of one slot) wraps the same way
    out = generation.write_ring(ring[:, :1], 0, new[:, :, :3], [(0, jnp.int32(28))],
                                aligned=False)
    assert np.asarray(out)[0, 0].ravel().tolist() == [3] + [0] * 12 + [1, 2]
    with pytest.raises(ValueError, match="does not fit a ring"):
        generation.write_ring(ring[:, :, :, :4], 0, new, [(0, jnp.int32(0))], aligned=True)


def test_one_slots_verify_window_crosses_the_rings_end():
    """A cache of ONE row decoding in windows of 1 + 3 at a (1,) offset: the window
    from position 11 takes places 11, 0, 1, 2 of the ring of 12 (one update a
    position; one update of four would be clamped back to places 8..11)."""
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=1, length=40)
    want = np.asarray(ref_logits(params, rows, cfg))
    got, cache = through_the_cache(params, cfg, {0: (rows[0].tolist(), 7)}, {0: 40}, slots=1,
                                   verify=3)
    assert cache.wk.shape[3] == 12
    close(got[0], want[0], F32_TOL)


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kv_decode"])
def test_an_engine_of_one_slot_verifies_across_the_rings_end(monkeypatch, kernel):
    """``num_slots=1`` with speculative decoding: the verify windows of 1 + 3 start
    wherever the accepted tokens left the row, across the ring's end among them;
    greedy, the tokens equal plain generation's. With heads of 128, slots of four key
    blocks and a ring of 28 + 4 places = two, the windows of both stacks go through the
    kernel `kv_decode` (and `generate`'s steps with them)."""
    if kernel:
        harness.small_tiles(monkeypatch, kv_decode)
        cfg = small_cfg(attn_head_dim=128, num_layers=4, sliding_window_size=28)
        assert kv_decode.decode_path(32, 128, 4 * 2, cfg.dtype) == "kernel"
    else:
        cfg = small_cfg()
    params, rows = seeded(cfg, seed=1 if kernel else 5, batch=1, length=30)
    prompt = rows[0, :7].tolist()
    want = generation.generate_np(params, cfg, [prompt], max_new_tokens=40, length_bucket=1)
    assert len(set(want[0][7:])) >= 8  # (seeds whose greedy answers move about)
    class Oracle:
        """Drafts plain generation's own tokens: every window is accepted whole."""

        name = "oracle"

        def draft(self, tokens, k):
            return want[0][len(tokens):len(tokens) + k]

    engine = harness.engine(cfg, params, num_slots=1, spec_decode_k=3)
    engine.drafter = Oracle()
    served, stats, _ = harness.serve(engine, [prompt], 40)
    assert served[0] == want[0] and stats["draft_accepted"] > 12


def test_a_slot_reused_by_a_shorter_request_never_sees_the_longer_ones_keys():
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=44)
    want = np.asarray(ref_logits(params, rows, cfg))
    _, cache = through_the_cache(params, cfg, {1: (rows[0].tolist(), 40)}, {1: 44})
    # the same slot, the same cache (not zeroed), a request of 10 positions
    got, _ = through_the_cache(params, cfg, {1: (rows[1].tolist(), 4)}, {1: 10}, cache=cache)
    close(got[1], want[1, :10], F32_TOL)


def _five_block_ring(monkeypatch):
    """A ring of five key blocks as the cell's is (4,096 + 1,024 in blocks of 1,024):
    window 32, chunks and key blocks of 8, slots of 64."""
    harness.small_tiles(monkeypatch, generation, key_block=8)
    cfg = small_cfg(sliding_window_size=32)
    assert generation.chunk_key_blocks(generation.ring_positions(cfg, SLOT, 8), 8) == (8, 5, 1)
    return cfg


def test_a_chunk_reads_the_ring_up_to_its_end_until_the_ring_has_lapped(monkeypatch):
    """A prompt of 7 chunks in a slot that an earlier, LONGER request filled with other
    values: every chunk's logits with the ring read up to the chunk's end (1, 2, 3, 4,
    then all 5 blocks once the ring has lapped) are those of the whole-ring read, bit
    for bit: the blocks left out held only what the mask reads as negative positions."""
    cfg = _five_block_ring(monkeypatch)
    params, rows = seeded(cfg, batch=2, length=SLOT)
    bounded = generation.chunk_key_blocks

    def prefill(row, cache, whole_ring):
        def every_block(positions, end):
            block, whole, _ = bounded(positions, end)
            return block, whole, whole

        monkeypatch.setattr(generation, "chunk_key_blocks", every_block if whole_ring else bounded)
        chunk = jax.jit(lambda cache, tokens, start: generation.forward_with_cache(
            params, tokens, cfg, cache, start, slot=jnp.int32(1)))  # (traced under this patch)
        out = []
        for start in range(0, len(row), 8):
            lg, cache = chunk(cache, row[None, start:start + 8], jnp.int32(start))
            out.append(np.asarray(lg[0]))
        return np.stack(out), cache

    cache = generation.init_kv_cache(cfg, 2, SLOT, tokens=8)
    _, dirty = prefill(rows[0], cache, whole_ring=True)  # 64 positions: the ring lapped
    assert float(jnp.abs(dirty.wk[:, 1]).min()) > 0
    got, after = prefill(rows[1, :56], dirty, whole_ring=False)
    want, after_whole = prefill(rows[1, :56], dirty, whole_ring=True)
    np.testing.assert_array_equal(got, want)
    for a, b in zip(after, after_whole):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and both are the reference's full forward of the shorter prompt
    close(got.reshape(56, -1), np.asarray(ref_logits(params, rows[1:2, :56], cfg))[0], F32_TOL)


def test_the_prefill_span_counts_the_ring_blocks_a_request_read(monkeypatch):
    """An engine of one slot serves a prompt of 8 chunks, then one of 7 in the same
    slot: greedy, the tokens are plain generation's, and the second `prefill` span
    says its chunks read 1 + 2 + 3 + 4 + 5 + 5 + 5 of the ring's 7 x 5 key blocks."""
    cfg = _five_block_ring(monkeypatch)
    params, rows = seeded(cfg, seed=3, batch=2, length=60)
    prompts = [rows[0].tolist(), rows[1, :53].tolist()]
    engine = harness.engine(cfg, params, num_slots=1, prefill_chunk=8)
    served, _, spans = harness.serve(engine, prompts, 4, traced=True)
    assert served == harness.generations(params, cfg, prompts, 4)
    by_tokens = {a["tokens"]: a for a in spans["prefill"]}
    assert (by_tokens[53]["kv_window_chunk_blocks_read"], by_tokens[53]["kv_window_chunk_blocks"]) == (25, 35)
    assert (by_tokens[60]["kv_window_chunk_blocks_read"], by_tokens[60]["kv_window_chunk_blocks"]) == (30, 40)


def test_lockstep_generation_runs_over_the_ring():
    harness.lockstep_generation_is_greedy(small_cfg(), ref_logits, max_new_tokens=16)


def test_cache_bytes_are_the_formula():
    cfg = small_cfg()
    cache = generation.init_kv_cache(cfg, 3, SLOT, tokens=CHUNK)
    assert cache.state is None  # (no layer of this stack keeps a state: PR 58's third stack)
    cache = cache[:4]
    assert [a.shape for a in cache] == [(2, 3, 2, SLOT, 8)] * 2 + [(6, 3, 2, WINDOW + CHUNK, 8)] * 2
    layout = generation.cache_layout(cfg, SLOT, CHUNK)
    per = 2 * 2 * 8 * 4
    assert layout == {"kind": "kv", "bytes_per_position_per_layer": per, "full_layers": 2,
                      "window_layers": 6, "window": WINDOW, "ring_positions": WINDOW + CHUNK,
                      "bytes_per_slot": per * (2 * SLOT + 6 * (WINDOW + CHUNK))}
    assert 3 * layout["bytes_per_slot"] == sum(a.nbytes for a in cache)
    # the cell's: 4 full layers of 16,384 and 12 rings of 4,096 + 1,024, 2,048 B a position
    big = PRESETS["smallthinker-21b-a3b"].replace(num_layers=16)
    at = generation.cache_layout(big, 16384, 1024)
    assert at["ring_positions"] == 5120 and at["bytes_per_position_per_layer"] == 2048
    assert 32 * at["bytes_per_slot"] == 32 * 2048 * (4 * 16384 + 12 * 5120) == 8_321_499_136
    # a ring is a whole number of the forwards' positions and never outgrows the slot
    assert generation.ring_positions(cfg, SLOT, 5) == 15
    assert generation.ring_positions(cfg, 10, 4) == 10
    assert generation.cache_read_positions(cfg, [5, 9], 3, SLOT, ring=12) == {
        "full": 3 * SLOT, "window": 3 * 12}
    assert generation.cache_read_positions(PRESETS["olmoe-1b-7b"], [5], 3, SLOT) is None


# -- the engine ---------------------------------------------------------------------------


def test_engine_serves_a_windowed_stack_end_to_end():
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=4, length=30)
    prompts = [rows[0, :26].tolist(), rows[1, :5].tolist(), rows[2, :13].tolist(),
               rows[3, :30].tolist()]
    served, stats, _ = harness.serve(harness.engine(cfg, params), prompts, 20)
    assert served == harness.generations(params, cfg, prompts, 20)
    per = 2 * 2 * 8 * 4
    assert stats["cache_kind"] == "kv" and stats["kv_ring_positions"] == WINDOW + CHUNK
    assert stats["cache_bytes"] == 3 * per * (2 * SLOT + 6 * (WINDOW + CHUNK))
    assert stats["kv_cache_bytes_per_position"] == per
    assert stats["kv_full_layers"] == 2 and stats["kv_window_layers"] == 6


def test_the_decode_span_carries_the_stacks_counters():
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=30)
    _, _, spans = harness.serve(harness.engine(cfg, params),
                                [rows[0, :26].tolist(), rows[1, :6].tolist()], 6, traced=True)
    both = [a for a in spans["decode"] if a["active"] == 2]
    assert both
    for a in both:
        n = a["kv_live_positions"]
        assert a["kv_full_live_positions"] == n
        assert a["kv_window_live_positions"] < n  # the long row is past the window
        assert a["kv_full_read_positions"] == 3 * SLOT
        assert a["kv_window_read_positions"] == 3 * (WINDOW + CHUNK)
        assert (a["kv_full_layers"], a["kv_window_layers"]) == (2, 6)
        assert a["kv_cache_bytes_per_position"] == 2 * 2 * 8 * 4
    # (a step's expert counters ride the NEXT step's span: read with its ids, a step late)
    carried = [a for a in spans["decode"] if "moe_held_pairs_per_token" in a]
    assert len(carried) == len(spans["decode"]) - 1
    assert all(0 < a["moe_held_pairs_per_token"] <= 3 for a in carried)
    # prompts of 26 and 6 in chunks of 4 over a ring of 12: the long one's chunks at 12
    # and 24 begin a lap (none crosses the ring's end: 12 is 3 chunks)
    prefill = spans["prefill"]
    assert sorted(a.get("ring_wraps") for a in prefill) == [0, 2]


@pytest.mark.parametrize("window", [WINDOW, 28], ids=["ring_plain", "ring_kernel"])
def test_the_decode_span_counts_what_the_kernel_fetches(monkeypatch, window):
    """Heads of 128 and slots of whole key blocks: the full layers decode through
    `kv_decode`, and `kv_full_read_positions` is the rows' lengths rounded up to the
    key block plus a block a free row (the plain path above: rows x positions). A ring
    of 8 + 4 places is no whole number of key blocks and stays rows x ring; one of 28 +
    4 = two key blocks goes through the kernel too and is read up to the row's last
    write until the row has lapped it. The tokens are plain generation's."""
    harness.small_tiles(monkeypatch, kv_decode)
    cfg = small_cfg(attn_head_dim=128, num_layers=4, sliding_window_size=window)
    params, rows = seeded(cfg, batch=1, length=10)
    prompt = rows[0].tolist()
    served, stats, spans = harness.serve(harness.engine(cfg, params), [prompt], 28, traced=True)
    assert served == harness.generations(params, cfg, [prompt], 28)
    decode = spans["decode"]
    lives = [a["kv_full_live_positions"] for a in decode]
    assert min(lives) <= 16 and 32 < max(lives)  # the row grows past two key blocks' ends
    ring = window + CHUNK
    for a in decode:
        # the row in use rounded up to the key block, and a block for each of the two free rows
        blocks = -(-a["kv_full_live_positions"] // 16)
        assert a["kv_full_read_positions"] == (blocks + 2) * 16
        assert a["kv_window_read_positions"] == ((min(blocks, 2) + 2) * 16 if ring == 32 else 3 * ring)
    assert stats["kv_full_read_positions"] == 3 * 16  # no row in use: a block each
    assert stats["kv_window_read_positions"] == (3 * 16 if ring == 32 else 3 * ring)


def test_slots_hold_a_whole_number_of_chunks():
    cfg = small_cfg()
    params, _ = seeded(cfg)
    with pytest.raises(ValueError, match="max_seq_len 64 is no multiple of prefill_chunk 5"):
        harness.engine(cfg, params, prefill_chunk=5)


def test_an_attention_engines_counters_stay_as_they_were():
    cfg = PRESETS["opt-1.3b"].replace(num_layers=2, hidden_size=32, num_heads=4, ffn_dim=64,
                                      vocab_size=96, max_seq_len=32, dtype=jnp.float32)
    params = modeling.init_model_params(jax.random.key(0), cfg)
    _, stats, _ = harness.serve(harness.engine(cfg, params), [], 1)
    assert "kv_ring_positions" not in stats and "kv_full_layers" not in stats
    assert stats["cache_bytes"] == stats["kv_cache_bytes_per_position"] * 3 * 32


def test_the_paged_backend_refuses_a_windowed_stack():
    cfg = small_cfg()
    params, _ = seeded(cfg)
    with pytest.raises(ValueError, match="paged backend.*sliding-window layers.*no ring"):
        harness.engine(cfg, params, kv_num_blocks=-1)


def test_cli_serve_parses_the_cells_flags():
    cfg = harness.cli_serve_parses([
        "--model_size", "smallthinker-21b-a3b", "--num_layers", "16", "--vocab_size", "37984",
        "--moe_share", "0/4", "--seq_length", "16384", "--param_dtype", "bf16",
        "--num_slots", "32", "--prefill_chunk", "1024"],
        dict(num_layers=16, vocab_size=37984, moe_share=(0, 4), moe_held=16,
             param_dtype=jnp.bfloat16, attn_impl="xla"))
    assert sum(cfg.window_layers) == 12


# -- what the stack does not implement --------------------------------------------------------


REFUSALS = [
    ("flash", dict(attn_impl="flash"), {}, r"an attention path other than XLA's \(attn_impl "
     r"'flash' or 'ring'\) is not implemented for a stack with sliding-window layers"),
    ("cp", {}, dict(cp=2), r"context parallelism \(cp>1\) is not implemented for a stack with "
     "sliding-window layers"),
    ("pack", dict(pack_sequences=True), {}, "pack_sequences is not implemented for a stack with "
     "sliding-window layers"),
    ("pp", dict(moe_experts=0), dict(pp=2), r"pipeline parallelism \(pp>1\) is not implemented "
     "for a stack with sliding-window layers"),
]


test_build_runtime_refuses_by_name = harness.refuses(REFUSALS, small_cfg)


def test_a_window_layer_outside_the_runtime_refuses_another_attention_path():
    cfg = small_cfg(attn_impl="flash")
    params, rows = seeded(cfg, length=16)
    with pytest.raises(ValueError, match="attention path other than XLA's.*sliding-window layers"):
        forward(params, rows, cfg)


def test_the_runtime_trains_it_on_one_device():
    # (the runtime's forward is the model's: each layer under its own view)
    harness.trains_on_one_device(small_cfg(num_layers=4, max_seq_len=32), steps=8, drop=0.1)


def test_the_runtime_routes_from_the_attention_input_on_a_mesh():
    """The router's input rides `route_tokens` beside the block's, split over the mesh
    like it."""
    harness.one_device_loss_on_a_mesh(small_cfg(num_layers=4, max_seq_len=32))


# -- the other expert models' programs stay the parent's ----------------------------------------

#: sha256 of the lowered text of one expert layer's forward at a small size, read on
#: the PARENT commit (337372a) by this very function: SwiGLU beside ReGLU is a
#: trace-time branch, the router's input an argument that is None
PARENT_TEXT = {
    "olmoe-1b-7b": "293e21076ed2c821281e40bb2e5d0fc3e264de66c5c0d20d6c983a8801d2c26a",
    "qwen3-next-80b-a3b": "01a62554f0d17034f416d73161f206dad9357da6cea206e6b46e183012ae4e4b",
    "sarvam-105b": "37c22bc06aa4f9fdde230fd2fce0beca63247561f700ee267c394570dfc2350f",
}


def _expert_layer_text(name):
    small = dict(hidden_size=256, moe_experts=8, moe_top_k=2, dtype=jnp.bfloat16)
    if name == "olmoe-1b-7b":
        cfg = PRESETS[name].replace(ffn_dim=128, **small)
    elif name == "qwen3-next-80b-a3b":
        cfg = PRESETS[name].replace(moe_ffn_dim=128, moe_shared_ffn_dim=128, moe_share=(0, 2),
                                    **small)
    else:
        cfg = PRESETS[name].replace(moe_ffn_dim=128, moe_shared_ffn_dim=128, moe_share=(1, 2),
                                    **small)
    p = jax.eval_shape(lambda k: moe.init_moe_params(k, cfg), jax.random.key(0))
    x = jax.ShapeDtypeStruct((2, 16, 256), jnp.bfloat16)

    def loss(x_, p_):
        # (the parent's tile, handed in: since PR 57 the block names its own from the
        # shape, 16 rows for these 32 tokens, and an explicit one still wins)
        y, stats = moe.moe_topk_block(x_, p_, cfg, tile=256)
        return jnp.sum(y.astype(jnp.float32)) + sum(jnp.sum(s) for s in stats)

    text = jax.jit(jax.grad(loss, argnums=(0, 1), allow_int=True)).lower(x, p).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("name", sorted(PARENT_TEXT))
def test_the_other_expert_models_lower_to_the_parents_program(name, monkeypatch):
    """But for TWO things: since PR 62 the kernels' shared index-map helper `used_tile`
    clamps at tile 0 (a served share's ``num_tiles`` can be 0), a scalar ``max`` in every
    block map; since PR 67 the layout is counted where the parent sorted it and a held
    share's statistics are counted by comparison where the parent's were a `bincount`
    (the same integers: tests/test_moe.py). With the helper, the layout's body and the
    count as the parent had them the text is the parent's, sha for sha: a
    differentiated expert layer changed in nothing else."""
    from galvatron_tpu.ops import grouped_matmul, moe_held, pallas_common

    clamped = _expert_layer_text(name)
    for module in (grouped_matmul, moe_held):
        monkeypatch.setattr(module, "used_tile", lambda i, count: jnp.minimum(i, count[0] - 1))
    monkeypatch.setattr(moe, "sorted_layout", moe._layout_by_sort)
    monkeypatch.setattr(moe, "_pairs_an_expert", lambda idx, e: jnp.bincount(
        idx.reshape(-1), length=e).astype(jnp.int32))
    pallas_common._traced.cache_clear()  # (kernels traced once a signature: `traced_once`)
    try:
        assert _expert_layer_text(name) == PARENT_TEXT[name] != clamped
    finally:
        pallas_common._traced.cache_clear()
