"""lfm2_moe-class stacks (gated short convolutions whose per-row STATE lives beside the
attention layers' keys and values in one slot cache, per-head q/k norms, two leading
dense layers, sigmoid-routed experts with a selection bias) on the normal path, against
the plain reference ``benchmark/references/lfm2_moe.py`` on seeded random weights, at a
small size on the CPU: the full forward and ``generate``; chunked prefill then decoding
against the reference's ONE forward; a slot used twice, idle decode steps and ``reset``;
rows at different depths; the shares of the experts adding up to the uncut layer; a
state held too low or left unreset failing the tolerance; the engine end to end; each
refusal by its sentence."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference
from galvatron_tpu.models import generation, mixers, modeling, moe, shortconv
from galvatron_tpu.models.modeling import PRESETS
from galvatron_tpu.ops import kv_decode
from tests import _stack_harness as harness
from tests._stack_harness import (  # noqa: F401  (`retraced`: a fixture)
    close, decode, forward, prefill, retraced, seeded, through_the_cache, worst)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = reference.load(ROOT, "lfm2_moe")

# float32, the same arithmetic in another order (the program sorts the pairs and runs
# grouped GEMMs, attends a block of keys at a time with a running softmax and carries the
# conv's last inputs from forward to forward; the reference loops over key/value heads
# and query blocks and convolves the whole sequence at once): the largest difference read
# over this file's cases is 2e-6 of the largest logit
F32_TOL = 5e-5
CHUNK, SLOT = 4, 64


def small_cfg(**kw):
    """The first 8 published layers at small widths: conv, conv (both with the dense
    MLP), then A C C C, A C; 8 experts top-2, all held."""
    base = dict(vocab_size=96, hidden_size=32, num_layers=8, num_heads=4, num_kv_heads=2,
                ffn_dim=48, max_seq_len=SLOT, moe_experts=8, moe_top_k=2, moe_ffn_dim=24,
                dtype=jnp.float32)
    base.update(kw)
    return PRESETS["lfm2-24b-a2b"].replace(**base)


def ref_cfg(cfg, share=None):
    rank, of = share or cfg.moe_share
    return {"hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.kv_heads, "norm_eps": cfg.norm_eps,
            "rope_parameters": {"rope_theta": cfg.rope_theta},
            "conv_L_cache": cfg.shortconv_taps, "num_hidden_layers": cfg.num_layers,
            "layer_types": ["conv" if k == "shortconv" else "full_attention"
                            for k in cfg.layer_kinds],
            "intermediate_size": cfg.ffn, "moe_intermediate_size": cfg.expert_ffn,
            "num_dense_layers": cfg.moe_dense_layers, "num_experts": cfg.moe_experts // of,
            "num_experts_per_tok": cfg.moe_top_k, "routed_scaling_factor": cfg.moe_route_scale,
            "vocab_size": cfg.vocab_size, "expert_share": {"rank": rank, "of": of}}


def ref_logits(params, rows, cfg, share=None):
    return harness.reference(ARCH, ref_cfg, cfg, share).logits(params, jnp.asarray(rows))


# -- the configuration ------------------------------------------------------------------


def test_preset_runs_the_published_widths():
    cfg = PRESETS["lfm2-24b-a2b"]
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (
        2048, 40, 32, 8, 64)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.expert_ffn, cfg.ffn) == (64, 4, 1536, 11776)
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.shortconv_taps) == (65536, 128000, 3)
    assert cfg.moe_router == "sigmoid_topk" and cfg.moe_route_scale == 1.0 and cfg.moe_norm_topk
    assert cfg.moe_dense_layers == 2 and cfg.qk_norm and cfg.qk_norm_per_head
    assert cfg.tie_word_embeddings and cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-5
    # layer_types as published: conv, conv, then (full_attention, conv, conv, conv) x 9,
    # then full_attention, conv
    attn = [i for i, k in enumerate(cfg.kinds) if k == "attention"]
    assert attn == [2, 6, 10, 14, 18, 22, 26, 30, 34, 38]
    assert cfg.kinds.count("shortconv") == 30 and not cfg.windowed
    cut = cfg.replace(num_layers=22)
    assert cut.kinds.count("attention") == 5 and cut.kinds.count("shortconv") == 17
    assert generation.stack_layers(cut) == {"full": 5, "window": 0, "state": 17}
    assert generation.stacked(cut) and not generation.stacked(PRESETS["olmoe-1b-7b"])


def test_parameter_counts_are_the_issues_arithmetic():
    cfg = PRESETS["lfm2-24b-a2b"]
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    sizes = [sum(a.size for a in jax.tree.leaves(layer)) for layer in shapes["layers"]]
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    attn = 2048 * (2048 + 512 + 512) + 2048 * 2048 + 2 * 64
    experts = 2048 * 64 + 64 + 64 * 3 * 2048 * 1536
    assert sizes[0] == sizes[1] == conv + 3 * 2048 * 11776 + 2 * 2048
    assert sizes[2] == attn + experts + 2 * 2048 and sizes[3] == conv + experts + 2 * 2048
    total = sum(a.size for a in jax.tree.leaves(shapes))
    assert total == sum(sizes) + 65536 * 2048 + 2048
    assert round(total / 1e9, 2) == 23.84  # tied; the catalog's untied reading adds 0.134 B
    assert set(shapes["layers"][0]["shortconv"]) == {"in_proj", "conv_w", "out_proj"}
    assert shapes["layers"][2]["attn"]["q_norm"].shape == (64,)
    assert "head" not in shapes
    # the cell's cut: 22 layers, 16 held experts, a quarter of the vocabulary
    cut = cfg.replace(num_layers=22, vocab_size=16384, moe_share=(0, 4))
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cut), jax.random.key(0))
    total = sum(a.size for a in jax.tree.leaves(shapes))
    assert round(total / 1e9, 2) == 3.54
    served = ARCH.served_params(ref_cfg(cut))
    assert served["a_forward"] == total and served["a_token"] == 2048


def test_the_theoretical_count_knows_the_kind():
    from galvatron_tpu.search import theoretical as th

    cfg = small_cfg()
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    for i in (3, 2):  # an expert layer of each kind (a dense layer is priced as one: th's note)
        got = th.layer_param_count(cfg, kind=cfg.kinds[i])
        assert got == sum(a.size for a in jax.tree.leaves(shapes["layers"][i])), cfg.kinds[i]
    assert shortconv.param_count(cfg) == sum(
        a.size for a in jax.tree.leaves(shapes["layers"][0]["shortconv"]))


# -- the full forward -------------------------------------------------------------------


@pytest.mark.parametrize("share", [(0, 1), (1, 4)])
def test_no_cache_forward_matches_the_reference(share):
    cfg = small_cfg(moe_share=share)
    params, rows = seeded(cfg, length=40)
    close(forward(params, rows, cfg), ref_logits(params, rows, cfg), F32_TOL)


def test_the_conv_is_causal_and_three_taps_wide():
    """Position p of a conv layer sees z at p - 2, p - 1 and p: moving token p - 3 moves
    nothing at p where every layer is a conv layer; p - 2 does."""
    cfg = small_cfg(num_layers=1, moe_dense_layers=1)
    params, rows = seeded(cfg, batch=1, length=20)
    base = forward(params, rows, cfg)[0]
    moved = lambda j: forward(  # noqa: E731
        params, rows.at[0, j].set((rows[0, j] + 1) % cfg.vocab_size), cfg)[0]
    assert np.array_equal(np.asarray(moved(10)[13]), np.asarray(base[13]))
    assert not np.array_equal(np.asarray(moved(10)[12]), np.asarray(base[12]))
    assert np.array_equal(np.asarray(moved(10)[:10]), np.asarray(base[:10]))  # causal


def test_the_qk_norm_is_each_heads_own():
    cfg = small_cfg(num_layers=3)
    params, rows = seeded(cfg, length=16)
    per_head = forward(params, rows, cfg)
    close(per_head, ref_logits(params, rows, cfg), F32_TOL)
    whole = cfg.replace(qk_norm_per_head=False)
    wide = jax.tree.map(lambda a: a, params)
    a = wide["layers"][2]["attn"]
    a["q_norm"], a["k_norm"] = jnp.tile(a["q_norm"], 4), jnp.tile(a["k_norm"], 2)
    assert worst(forward(wide, rows, whole), per_head) > 1e-3


def test_bf16_in_place_of_float32_fails_the_tolerance():
    harness.bf16_fails_the_tolerance(small_cfg(), ref_logits, F32_TOL)


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """The four shares' expert parts add up to what the uncut reference gives for the
    whole layer, nothing counted twice. (Each held-share model has this case against its
    own reference's layer: tests/test_mla.py, test_qwen3_next.py, test_smallthinker.py.)"""
    whole = small_cfg()
    params, rows = seeded(whole, length=24)
    mlp = params["layers"][3]["mlp"]
    y = jax.random.normal(jax.random.key(5), (2, 24, whole.hidden_size))
    want = moe.moe_topk_block(y, mlp, whole)[0]
    total = 0.0
    for rank in range(4):
        cut = whole.replace(moe_share=(rank, 4))
        mine = dict(mlp, **{k: mlp[k][rank * 2:(rank + 1) * 2] for k in ("w1", "w2", "w3")})
        total = total + moe.moe_topk_block(y, mine, cut)[0]
    close(total, want, F32_TOL)
    rc = ref_cfg(whole)
    fw = ARCH.published_weights(params, rc)["layers"][3]["feed_forward"]
    with jax.default_matmul_precision("highest"):
        close(want[:1], ARCH.moe(y[:1], fw, rc), F32_TOL)
        # and one rank's part is the reference's at that rank
        cut = ref_cfg(whole, (2, 4))
        part = dict(fw, experts={k: v[4:6] for k, v in fw["experts"].items()})
        mine = dict(mlp, **{k: mlp[k][4:6] for k in ("w1", "w2", "w3")})
        close(moe.moe_topk_block(y, mine, whole.replace(moe_share=(2, 4)))[0][:1],
              ARCH.moe(y[:1], part, cut), F32_TOL)


# -- the state beside the keys and values ------------------------------------------------


@pytest.mark.parametrize("chunk,prompt_len", [
    (4, 20), (8, 16), (8, 21), (5, 13), (8, 3), (16, 16), (4, 1), (4, 2), (16, 17)],
    ids=["divides", "two_whole_chunks", "padded_last", "chunk_of_5", "under_a_chunk",
         "one_whole_chunk", "one_token", "two_tokens", "one_past_a_chunk"])
def test_chunked_prefill_then_decode_matches_the_reference_at_every_position(chunk, prompt_len):
    """Logits at every served position, prompt prefilled in chunks (the last one padded
    where the chunk does not divide the prompt) and then decoded a token a step, equal
    the reference's ONE full forward without cache or state."""
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=1, length=44)
    want = np.asarray(ref_logits(params, rows, cfg))[0]
    row = rows[0].tolist()
    cache = generation.init_kv_cache(cfg, 3, SLOT, tokens=chunk)
    got, _ = through_the_cache(params, cfg, {1: (row, prompt_len)}, {1: 44}, chunk=chunk,
                               cache=cache)
    close(got[1][:prompt_len], want[:prompt_len], F32_TOL)
    close(got[1][prompt_len:], want[prompt_len:], F32_TOL)


def test_pad_rows_of_a_chunk_do_not_reach_the_state():
    """A chunk padded to 8 after 3 real rows leaves the state of row 2, whatever the pad
    rows hold: without ``last`` the state would be the pad rows'."""
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=1, length=12)
    row = rows[0].tolist()
    cache = generation.init_kv_cache(cfg, 2, SLOT, tokens=8)
    _, padded = prefill(params, cfg, cache, 0, row[:3], chunk=8)
    _, exact = prefill(params, cfg, cache, 0, row[:3], chunk=3)
    assert np.array_equal(np.asarray(padded.state[:, 0]), np.asarray(exact.state[:, 0]))
    buf = np.full((1, 8), 7, np.int32)
    buf[0, :3] = row[:3]
    _, unled = harness.chunk_forward(params, cfg, cache, jnp.asarray(buf), jnp.int32(0), jnp.int32(0),
                              jnp.int32(7))  # as if the chunk were whole
    assert not np.array_equal(np.asarray(unled.state[:, 0]), np.asarray(exact.state[:, 0]))


@pytest.mark.parametrize("between", ["nothing", "idle_decode_steps", "reset"])
def test_a_slot_used_twice_leaves_no_trace_in_the_next_request(between):
    """Request A to its end in slot 1, then request B in the same slot (the cache not
    zeroed; idle decode steps over the empty slot in between; the slots' ``reset``):
    B's logits are B's alone."""
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=30)
    want = np.asarray(ref_logits(params, rows, cfg))
    a, b = rows[0].tolist(), rows[1].tolist()
    if between == "reset":
        from galvatron_tpu.serving.kv_slots import SlotKVCache

        slots = SlotKVCache(cfg, 3, SLOT, tokens=CHUNK)
        cache = slots.cache
    else:
        cache = generation.init_kv_cache(cfg, 3, SLOT, tokens=CHUNK)
    _, cache = through_the_cache(params, cfg, {1: (a, 22)}, {1: 30}, cache=cache)
    assert float(jnp.max(jnp.abs(cache.state[:, 1]))) > 0
    if between == "idle_decode_steps":  # the slot free, other rows decoding: (0, 0) rows
        _, cache = decode(params, cfg, cache, {}, steps=3)
        assert float(jnp.max(jnp.abs(cache.state[:, 1]))) > 0  # an idle row wrote its own state
    if between == "reset":
        slots.cache = cache
        slots.reset()
        cache = slots.cache
        assert float(jnp.max(jnp.abs(cache.state))) == 0 and slots.free_slots == 3
    got, _ = through_the_cache(params, cfg, {1: (b, 9)}, {1: 21}, cache=cache)
    close(got[1][:9], want[1, :9], F32_TOL)
    close(got[1][9:], want[1, 9:21], F32_TOL)


def test_rows_at_different_depths_in_one_step_equal_each_row_alone():
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=40)
    want = np.asarray(ref_logits(params, rows, cfg))
    a, b = rows[0].tolist(), rows[1].tolist()
    cache = generation.init_kv_cache(cfg, 3, SLOT, tokens=CHUNK)
    _, cache = prefill(params, cfg, cache, 2, a[:26])
    _, cache = prefill(params, cfg, cache, 0, b[:7])
    both, _ = decode(params, cfg, cache, {2: (a, 26, 36), 0: (b, 7, 17)})
    close(both[2], want[0, 26:36], F32_TOL)
    close(both[0], want[1, 7:17], F32_TOL)
    alone, _ = decode(params, cfg, cache, {2: (a, 26, 36)})
    assert np.allclose(alone[2], both[2], atol=1e-6)


@pytest.fixture
def plant(monkeypatch, retraced):
    """Plants a fault under the cached conv layer; the jitted forwards traced before and
    after it are dropped (`retraced`: they keep the body they were traced with)."""
    def planted(how):
        if how == "state_in_bf16":  # a state held below the float32 the configuration states
            monkeypatch.setattr(shortconv, "stored",
                                lambda new, dtype: new.astype(jnp.bfloat16).astype(dtype))
        elif how == "kv_in_bf16":  # the attention layers' keys and values likewise
            real = generation._project_qkv_at

            def rounded(x, p, cfg, cos_sin):
                q, k, v = real(x, p, cfg, cos_sin)
                return q, *(t.astype(jnp.bfloat16).astype(t.dtype) for t in (k, v))

            monkeypatch.setattr(generation, "_project_qkv_at", rounded)
        else:  # a state not reset: the slot's previous request reaches the next one
            monkeypatch.setattr(shortconv, "fresh", lambda prev, offsets: prev)
        retraced()

    return planted


@pytest.mark.parametrize("how", ["state_in_bf16", "kv_in_bf16", "state_not_reset"])
def test_a_state_held_too_low_or_left_unreset_fails_the_tolerance(plant, how):
    """The tolerance these tests compare by (5e-5 of the largest logit: float32
    reordering reads 2e-6) tells a state rounded to bfloat16 (8 mantissa bits where the
    stack states 24), keys and values rounded the same way, and a state left from the
    slot's previous request, each from a sound one. (The benchmark cell's statistic tells
    the first of the three alone: PERF.md section 7.)"""
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=30)
    want = np.asarray(ref_logits(params, rows, cfg))
    a, b = rows[0].tolist(), rows[1].tolist()

    def served():
        cache = generation.init_kv_cache(cfg, 3, SLOT, tokens=CHUNK)
        _, cache = prefill(params, cfg, cache, 1, a[:22])
        return through_the_cache(params, cfg, {1: (b, 9)}, {1: 21}, cache=cache)[0][1]

    sound = worst(served(), want[1, :21])
    assert sound <= F32_TOL
    plant(how)
    assert worst(served(), want[1, :21]) > 4 * F32_TOL


def test_lockstep_generation_carries_the_state():
    harness.lockstep_generation_is_greedy(small_cfg(), ref_logits, max_new_tokens=8)


def test_cache_bytes_are_the_formula():
    cfg = small_cfg()
    cache = generation.init_kv_cache(cfg, 3, SLOT, tokens=CHUNK)
    assert [None if a is None else a.shape for a in cache] == [
        (2, 3, 2, SLOT, 8), (2, 3, 2, SLOT, 8), None, None, (6, 3, 2 * 32)]
    layout = generation.cache_layout(cfg, SLOT, CHUNK)
    per, state = 2 * 2 * 8 * 4, 2 * 32 * 4
    assert layout == {"kind": "kv", "bytes_per_position_per_layer": per, "full_layers": 2,
                      "window_layers": 0, "window": 0, "state_layers": 6,
                      "state_bytes_per_row": state, "bytes_per_slot": per * 2 * SLOT + 6 * state}
    assert 3 * layout["bytes_per_slot"] == sum(a.nbytes for a in cache if a is not None)
    # the cell's: 5 attention layers of 16,384 positions x 2,048 B and 17 states of 8,192 B
    big = PRESETS["lfm2-24b-a2b"].replace(num_layers=22)
    at = generation.cache_layout(big, 16384, 1024)
    assert (at["bytes_per_position_per_layer"], at["state_bytes_per_row"]) == (2048, 8192)
    assert 32 * at["bytes_per_slot"] == 32 * (5 * 16384 * 2048 + 17 * 8192) == 5_373_165_568
    # a decode step's attention reads every slot's capacity where a slot is no whole
    # key block (the plain body); a state layer reads no position
    assert generation.cache_read_positions(cfg, [5, 9], 3, SLOT) == {"full": 3 * SLOT, "window": 0}
    # at the cell's size the kernel `kv_decode` takes the head of 64 (read transposed, as
    # the chip keeps it): a row is read up to its length
    lengths = [1300, 5000, 12288, 16384]
    assert generation.cache_read_positions(big, lengths, 32, 16384) == {
        "full": kv_decode.read_positions(lengths, 32, 16384), "window": 0}
    assert kv_decode.read_positions(lengths, 32, 16384) == (2 + 5 + 12 + 16 + 28) * 1024
    assert generation.layer_stacks(cfg) == [
        ("state", 0), ("state", 1), ("full", 0), ("state", 2), ("state", 3), ("state", 4),
        ("full", 1), ("state", 5)]


# -- the engine ---------------------------------------------------------------------------


def test_engine_serves_the_stack_end_to_end():
    """Five requests through three slots (two slots are used twice, with a prompt that is
    no whole number of chunks among them): every served token is `generate`'s."""
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=5, length=30)
    prompts = [rows[0, :26].tolist(), rows[1, :5].tolist(), rows[2, :13].tolist(),
               rows[3, :30].tolist(), rows[4, :2].tolist()]
    served, stats, _ = harness.serve(harness.engine(cfg, params), prompts, 16)
    assert served == harness.generations(params, cfg, prompts, 16)
    per, state = 2 * 2 * 8 * 4, 2 * 32 * 4
    assert stats["cache_kind"] == "kv" and "kv_ring_positions" not in stats
    assert stats["cache_bytes"] == 3 * (per * 2 * SLOT + 6 * state)
    assert stats["cache_stacks"] == {"full": 2, "window": 0, "state": 6}
    assert stats["shortconv_conv_path"] == {"fused": 0, "plain": 6}
    assert (stats["kv_full_layers"], stats["kv_window_layers"]) == (2, 0)
    assert (stats["state_layers"], stats["state_bytes_per_row"]) == (6, state)


def test_the_spans_carry_the_state_counters():
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=30)
    _, _, spans = harness.serve(harness.engine(cfg, params),
                                [rows[0, :26].tolist(), rows[1, :6].tolist()], 6, traced=True)
    both = [a for a in spans["decode"] if a["active"] == 2]
    assert both
    for a in both:
        assert a["kv_full_live_positions"] == a["kv_live_positions"]
        assert a["kv_full_read_positions"] == 3 * SLOT
        assert (a["kv_window_live_positions"], a["kv_window_read_positions"]) == (0, 0)
        assert (a["kv_full_layers"], a["kv_window_layers"]) == (2, 0)
        assert (a["state_layers"], a["state_bytes_per_row"]) == (6, 2 * 32 * 4)
        assert a["kv_cache_bytes_per_position"] == 2 * 2 * 8 * 4
    # (a step's expert counters ride the NEXT step's span: read with its ids, a step late)
    carried = [a for a in spans["decode"] if "moe_held_pairs_per_token" in a]
    assert len(carried) == len(spans["decode"]) - 1
    assert all(0 < a["moe_held_pairs_per_token"] <= 2 for a in carried)
    admit = spans["admit"]
    assert admit and sum(a["state_rows_zeroed"] for a in admit) == 2
    assert all(a["state_rows_zeroed"] == a["admitted"] for a in admit)


def test_slots_hold_a_whole_number_of_chunks():
    cfg = small_cfg()
    params, _ = seeded(cfg)
    with pytest.raises(ValueError, match="layers that keep a state needs slots of a whole "
                       "number of prompt chunks: max_seq_len 64 is no multiple of prefill_chunk 5"):
        harness.engine(cfg, params, prefill_chunk=5)


@pytest.mark.parametrize("over,message", [
    (dict(kv_num_blocks=-1), r"the paged backend \(--kv_num_blocks\) is not implemented for a "
     r"stack with gated short-convolution layers.*a block pool holds positions"),
    (dict(spec_decode_k=2), r"speculative decoding \(spec_decode_k > 0\) is not implemented for "
     r"a stack with gated short-convolution layers.*a rejected draft has already advanced"),
], ids=["paged", "speculation"])
def test_the_engine_refuses_by_sentence(over, message):
    cfg = small_cfg()
    params, _ = seeded(cfg)
    with pytest.raises(ValueError, match=message):
        harness.engine(cfg, params, **over)


def test_other_kinds_beside_a_state_are_still_refused():
    cfg = small_cfg()
    # a window beside a state: the ring's refusal
    ringed = cfg.replace(sliding_window_size=8, sliding_window_layout=(0, 0, 1) + (0,) * 5)
    with pytest.raises(ValueError, match="both sliding-window layers and layers of another kind"):
        generation.init_kv_cache(ringed, 1, 16)
    # a latent cache beside a state: the slot cache is one kind's
    mixed = cfg.replace(layer_kinds=("shortconv", "mla") * 4, mla_kv_rank=8)
    with pytest.raises(ValueError, match="interleaves cache layouts"):
        generation.init_kv_cache(mixed, 1, 16)


def test_cli_serve_parses_the_cells_flags():
    cfg = harness.cli_serve_parses([
        "--model_size", "lfm2-24b-a2b", "--num_layers", "22", "--vocab_size", "16384",
        "--moe_share", "0/4", "--seq_length", "16384", "--param_dtype", "bf16",
        "--num_slots", "32", "--prefill_chunk", "1024"],
        dict(num_layers=22, vocab_size=16384, moe_share=(0, 4), moe_held=16,
             param_dtype=jnp.bfloat16, max_seq_len=16384, tie_word_embeddings=True))
    assert cfg.kinds.count("shortconv") == 17


# -- training ------------------------------------------------------------------------------


REFUSALS = [
    ("tp", {}, dict(tp=2), r"tensor parallelism \(tp>1\) is not implemented for gated "
     "short-convolution layers"),
    ("cp", {}, dict(cp=2), r"context parallelism \(cp>1\) is not implemented for a stack with "
     "gated short-convolution layers"),
    ("pack", dict(pack_sequences=True), {}, "pack_sequences is not implemented for gated "
     "short-convolution layers: the conv does not reset its state at segment boundaries"),
    ("pp", dict(moe_experts=0), dict(pp=2), r"pipeline parallelism \(pp>1\) over interleaved "
     "layer kinds is not implemented"),
]


test_build_runtime_refuses_by_name = harness.refuses(REFUSALS, small_cfg)


def test_the_runtime_trains_it_on_one_device():
    harness.trains_on_one_device(small_cfg(num_layers=4, max_seq_len=32), steps=8, drop=0.1)


def test_the_runtime_partitions_it_on_a_mesh():
    """(GSPMD partitions the plain conv by itself.)"""
    harness.one_device_loss_on_a_mesh(small_cfg(num_layers=4, max_seq_len=32))


def test_the_fingerprint_names_the_conv_body():
    cfg = PRESETS["lfm2-24b-a2b"].replace(num_layers=22)
    assert mixers.path_counts(cfg)["shortconv_conv_path"] == {"fused": 0, "plain": 17}
    assert mixers.path_counts(PRESETS["opt-1.3b"])["shortconv_conv_path"] == {"fused": 0, "plain": 0}
    assert mixers.state_kinds(cfg) == ("shortconv",) and mixers.state_kinds(PRESETS["opt-1.3b"]) == ()
