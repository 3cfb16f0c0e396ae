"""nemotron_h-class stacks SERVED: a Mamba-2 layer's state of two parts (a conv tail, a
float32 scan state) beside the attention layers' keys and values in one slot cache, against
the plain reference's ONE full forward: chunked prefill then decode, a padded chunk's tail,
a slot used again, idle decode steps and ``reset``, rows at different depths, a state held
too low or left unreset failing the tolerance, ``generate``, the cache's bytes, the engine
end to end with its counters and its refusals, the cell's ``cli serve`` flags. The
configuration, ``small_cfg`` and the tolerance are tests/test_nemotron.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.models import generation, mixers, ssm
from galvatron_tpu.models.modeling import PRESETS
from tests import _stack_harness as harness
from tests._stack_harness import (  # noqa: F401  (`retraced`: a fixture)
    close, decode, forward, prefill, retraced, seeded, through_the_cache, worst)
from tests.test_nemotron import ARCH, CHUNK, F32_TOL, SLOT, ref_logits, scan_state, small_cfg


# -- the state beside the keys and values ------------------------------------------------


@pytest.mark.parametrize("chunk,prompt_len", [
    (4, 20), (8, 16), (8, 21), (16, 13), (8, 3), (16, 16), (4, 1), (16, 17)],
    ids=["divides", "two_whole_chunks", "padded_last", "under_a_chunk_of_two_scan_chunks",
         "under_a_chunk", "one_whole_chunk", "one_token", "one_past_a_chunk"])
def test_chunked_prefill_then_decode_matches_the_reference_at_every_position(chunk, prompt_len):
    """Logits at every served position, prompt prefilled in chunks (the state handed from
    chunk to chunk, the last one padded where the chunk does not divide the prompt) and
    then decoded a token a step, equal the reference's ONE full forward."""
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=1, length=44)
    want = np.asarray(ref_logits(params, rows, cfg))[0]
    row = rows[0].tolist()
    cache = generation.init_kv_cache(cfg, 3, SLOT, tokens=chunk)
    got, _ = through_the_cache(params, cfg, {1: (row, prompt_len)}, {1: 44}, chunk=chunk,
                               cache=cache)
    close(got[1][:prompt_len], want[:prompt_len], F32_TOL)
    close(got[1][prompt_len:], want[prompt_len:], F32_TOL)


def test_pad_rows_of_a_chunk_reach_neither_part_of_the_state():
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=1, length=12)
    row = rows[0].tolist()
    cache = generation.init_kv_cache(cfg, 2, SLOT, tokens=8)
    _, padded = prefill(params, cfg, cache, 0, row[:3], chunk=8)
    _, exact = prefill(params, cfg, cache, 0, row[:3], chunk=3)
    # (to rounding: a chunk of 8 rows and one of 3 multiply in_proj in another order)
    close(padded.state.conv[:, 0], exact.state.conv[:, 0], 1e-6, floor=0.0)
    close(scan_state(padded, 0), scan_state(exact, 0), 1e-6, floor=0.0)
    buf = np.full((1, 8), harness.PAD, np.int32)
    buf[0, :3] = row[:3]
    _, unled = harness.chunk_forward(params, cfg, cache, jnp.asarray(buf), jnp.int32(0),
                                     jnp.int32(0), jnp.int32(7))  # as if the chunk were whole
    assert worst(scan_state(unled, 0), scan_state(exact, 0), 0.0) > 1e-2
    assert worst(unled.state.conv[:, 0], exact.state.conv[:, 0], 0.0) > 1e-2


@pytest.mark.parametrize("between", ["nothing", "idle_decode_steps", "reset"])
def test_a_slot_used_twice_leaves_no_trace_in_the_next_request(between):
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
    assert np.abs(scan_state(cache, 1)).max() > 0
    if between == "idle_decode_steps":  # the slot free, other rows decoding: (0, 0) rows
        _, cache = decode(params, cfg, cache, {}, steps=3)
        assert np.abs(scan_state(cache, 1)).max() > 0  # an idle row wrote its own state
    if between == "reset":
        slots.cache = cache
        slots.reset()
        cache = slots.cache
        assert all(float(jnp.abs(part).max()) == 0 for part in cache.state)
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
    """Plants a fault under the cached Mamba-2 layer; the jitted forwards traced before and
    after it are dropped (`retraced`)."""
    def planted(how):
        if how == "scan_state_in_bf16":  # held below the float32 the configuration states
            real_shapes = ssm.state_shapes

            def low(cfg):
                shapes = real_shapes(cfg)
                return dict(shapes, scan=(shapes["scan"][0], jnp.dtype(jnp.bfloat16)))

            monkeypatch.setattr(ssm, "state_shapes", low)
        elif how == "state_not_reset":  # the slot's previous request reaches the next one
            real = ssm.cached_block

            def unreset(x, p, cfg, state, layer, slot, offsets, last):
                return real(x, p, cfg, state, layer, slot, jnp.maximum(offsets, 1), last)

            monkeypatch.setattr(ssm, "cached_block", unreset)
        else:  # the conv's tail dropped: every forward convolves from zeros
            real = ssm._row_of
            monkeypatch.setattr(ssm, "_row_of", lambda stack, layer, slot: (
                real(stack, layer, slot) if stack.ndim == 4 else 0 * real(stack, layer, slot)))
        retraced()

    return planted


@pytest.mark.parametrize("how", ["scan_state_in_bf16", "state_not_reset", "conv_tail_dropped"])
def test_a_state_held_too_low_or_left_unreset_fails_the_tolerance(plant, how):
    """The tolerance these tests compare by tells a scan state rounded to bfloat16 every
    step (8 mantissa bits where the configuration states 24), a state left from the slot's
    previous request and a dropped conv tail, each from a sound one."""
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=30)
    want = np.asarray(ref_logits(params, rows, cfg))
    a, b = rows[0].tolist(), rows[1].tolist()

    def served():
        cache = generation.init_kv_cache(cfg, 3, SLOT, tokens=CHUNK)
        _, cache = prefill(params, cfg, cache, 1, a[:22])
        return through_the_cache(params, cfg, {1: (b, 9)}, {1: 21}, cache=cache)[0][1]

    assert worst(served(), want[1, :21]) <= F32_TOL
    plant(how)
    assert worst(served(), want[1, :21]) > 4 * F32_TOL


def test_lockstep_generation_carries_the_state():
    harness.lockstep_generation_is_greedy(small_cfg(), ref_logits, max_new_tokens=8)


def test_cache_bytes_are_the_formula():
    cfg = small_cfg()
    cache = generation.init_kv_cache(cfg, 3, SLOT, tokens=CHUNK)
    conv_dim = 32 + 2 * 4 * 8
    assert cache.k.shape == (1, 3, 2, SLOT, 8) and cache.wk is None
    assert cache.state.conv.shape == (4, 3, 3 * conv_dim) and cache.state.scan.shape == (4, 3, 8, 32)
    assert cache.state.scan.dtype == jnp.float32
    layout = generation.cache_layout(cfg, SLOT, CHUNK)
    per, parts = 2 * 2 * 8 * 4, {"conv": 3 * conv_dim * 4, "scan": 8 * 32 * 4}
    assert layout == {"kind": "kv", "bytes_per_position_per_layer": per, "full_layers": 1,
                      "window_layers": 0, "window": 0, "state_layers": 4,
                      "state_bytes_per_row": sum(parts.values()), "state_part_bytes": parts,
                      "bytes_per_slot": per * SLOT + 4 * sum(parts.values())}
    assert 3 * layout["bytes_per_slot"] == sum(a.nbytes for a in jax.tree.leaves(cache))
    # the cell's: 3 attention layers of 8,192 positions x 1,024 B and 12 states of 2,134,016 B
    big = PRESETS["nemotron-3-nano-30b-a3b"].replace(num_layers=15)
    at = generation.cache_layout(big, 8192, 1024)
    assert at["state_part_bytes"] == {"conv": 36864, "scan": 2097152}
    assert (at["bytes_per_position_per_layer"], at["state_bytes_per_row"]) == (1024, 2134016)
    assert 64 * at["bytes_per_slot"] == 64 * (3 * 8192 * 1024 + 12 * 2134016) == 3_249_537_024
    rc = {"mamba_num_heads": 64, "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
          "conv_kernel": 4}
    assert ARCH.ssm_state_bytes(rc) == at["state_part_bytes"]
    assert generation.layer_stacks(cfg) == [
        ("state", 0), ("state", 1), ("state", 2), ("full", 0), ("state", 3)]


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
    assert stats["cache_kind"] == "kv" and stats["cache_stacks"] == {"full": 1, "window": 0, "state": 4}
    assert stats["ssm_scan_path"] == {"fused": 0, "plain": 4}
    assert (stats["state_layers"], stats["state_bytes_per_row"]) == (4, (3 * 96 + 8 * 32) * 4)
    assert stats["state_scan_bytes_per_row"] == 8 * 32 * 4
    assert stats["state_step_bytes"] == 2 * 3 * 4 * (3 * 96 + 8 * 32) * 4


def test_the_spans_carry_the_state_counters():
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=30)
    _, _, spans = harness.serve(harness.engine(cfg, params),
                                [rows[0, :26].tolist(), rows[1, :6].tolist()], 6, traced=True)
    both = [a for a in spans["decode"] if a["active"] == 2]
    assert both
    for a in both:
        assert (a["kv_full_layers"], a["kv_window_layers"], a["state_layers"]) == (1, 0, 4)
        assert a["state_bytes_per_row"] == a["state_conv_bytes_per_row"] + a["state_scan_bytes_per_row"]
        assert a["state_step_bytes"] == 2 * 3 * 4 * a["state_bytes_per_row"]
    admit = spans["admit"]
    assert admit and sum(a["state_rows_zeroed"] for a in admit) == 2


@pytest.mark.parametrize("over,message", [
    (dict(kv_num_blocks=-1), r"the paged backend \(--kv_num_blocks\) is not implemented for a "
     r"stack with state-space layers.*the conv \+ scan state of a row is none"),
    (dict(spec_decode_k=2), r"speculative decoding \(spec_decode_k > 0\) is not implemented for "
     r"a stack with state-space layers.*a rejected draft has already advanced"),
    (dict(prefill_chunk=5), "layers that keep a state needs slots of a whole number of prompt "
     "chunks"),
], ids=["paged", "speculation", "chunk"])
def test_the_engine_refuses_by_sentence(over, message):
    cfg = small_cfg()
    params, _ = seeded(cfg)
    with pytest.raises(ValueError, match=message):
        harness.engine(cfg, params, **over)


def test_the_ssm_row_no_longer_lacks_a_cache():
    row = mixers.MIXERS["ssm"]
    assert "kv_cache" not in row.lacks and row.state == "conv + scan"
    assert set(row.lacks) == {"tp", "cp", "pack_sequences"}
    cfg = PRESETS["nemotron-3-nano-30b-a3b"]
    assert mixers.state_kinds(cfg) == ("ssm",)
    assert {limit.what for limit in mixers.limits(cfg)} >= {"tp", "cp", "pack_sequences",
                                                            "paged_kv", "spec_decode", "pp"}
    assert not [limit for limit in mixers.limits(cfg) if limit.what == "kv_cache"]
    # the delta rule's layers are still train-only
    assert "kv_cache" in mixers.MIXERS["gdn"].lacks


def test_cli_serve_parses_the_cells_flags():
    cfg = harness.cli_serve_parses([
        "--model_size", "nemotron-3-nano-30b-a3b", "--num_layers", "15", "--vocab_size", "32768",
        "--moe_share", "0/4", "--seq_length", "8192", "--param_dtype", "bf16",
        "--num_slots", "64", "--prefill_chunk", "1024"],
        dict(num_layers=15, vocab_size=32768, moe_share=(0, 4), moe_held=32,
             param_dtype=jnp.bfloat16, max_seq_len=8192, tie_word_embeddings=False,
             act_fn="relu2", pos_embed="nope"))
    assert cfg.kinds.count("ssm") == 12 and sum(cfg.mlp_layers) == 11


def test_the_int8_control_reaches_the_mixer_and_the_shared_expert():
    """``--serve_quant int8`` (the benchmark's control: the nearest precision below the
    bf16 the cell serves in) quantises this stack's plain GEMMs: the Mamba-2 projections and
    the un-gated shared expert beside the attention's and the head; the routed experts'
    stacks and the router stay as they are; the cached forwards run on the tree."""
    from galvatron_tpu.ops import quant

    cfg = small_cfg()
    params, rows = seeded(cfg, length=12)
    q = quant.quantize_params(params, cfg)
    is_q = lambda w: isinstance(w, quant.QuantTensor)  # noqa: E731
    assert is_q(q["layers"][0]["ssm"]["in_proj"]) and is_q(q["layers"][0]["ssm"]["out_proj"])
    assert all(is_q(q["layers"][0]["mlp"]["shared"][k]) for k in ("w1", "w2"))
    assert is_q(q["layers"][3]["attn"]["wqkv"]) and is_q(q["head"]["w"])
    assert not is_q(q["layers"][0]["mlp"]["w1"]) and not is_q(q["layers"][0]["mlp"]["router"]["w"])
    drift = worst(forward(q, rows, cfg), forward(params, rows, cfg))
    assert 1e-4 < drift < 5e-2, drift  # int8 shows, and is no other model
    cache = generation.init_kv_cache(cfg, 2, SLOT, tokens=4)
    got, _ = through_the_cache(q, cfg, {0: (rows[0].tolist(), 8)}, {0: 12}, slots=2, cache=cache)
    close(got[0], forward(q, rows, cfg)[0], 1e-4)
