"""granite-4.0-h-small-class stacks SERVED: a Mamba-2 layer's state of ONE scan group beside
the attention layer's keys and values in one slot cache, a routed MLP behind every mixer,
every branch of the cached forwards times ``residual_multiplier``, against the plain
reference's ONE full forward: chunked prefill then decode, a slot used again, rows at
different depths, a scan state held too low failing the tolerance, ``generate``, the cache's
bytes, the engine end to end with its counters and its refusals, the ``cli serve`` flags of
``benchmark/configs/granite-4.0-h-small.json``'s cut; and the regression of the multiplier on
the stack the benchmark already trains, granite-4.0-h-micro. The configuration, ``small_cfg``
and the tolerance are tests/test_granite_small.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.models import generation, mixers, ssm
from galvatron_tpu.models.modeling import PRESETS
from tests import _stack_harness as harness
from tests._stack_harness import (  # noqa: F401  (`retraced`: a fixture)
    close, decode, forward, prefill, retraced, seeded, through_the_cache, worst)
from tests.test_granite_small import (
    ARCH, CHUNK, F32_TOL, SLOT, ref_cfg, ref_logits, small_cfg)


# -- the state beside the keys and values ------------------------------------------------


@pytest.mark.parametrize("chunk,prompt_len", [(4, 20), (8, 21), (16, 13), (16, 17)],
                         ids=["divides", "padded_last", "under_a_chunk", "one_past_a_chunk"])
def test_chunked_prefill_then_decode_matches_the_reference_at_every_position(chunk, prompt_len):
    """Logits at every served position, prompt prefilled in chunks (the state handed from
    chunk to chunk, the last one padded where the chunk does not divide the prompt) and
    then decoded a token a step, equal the reference's ONE full forward."""
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=1, length=44)
    want = np.asarray(ref_logits(params, rows, cfg))[0]
    cache = generation.init_kv_cache(cfg, 3, SLOT, tokens=chunk)
    got, _ = through_the_cache(params, cfg, {1: (rows[0].tolist(), prompt_len)}, {1: 44},
                               chunk=chunk, cache=cache)
    close(got[1][:prompt_len], want[:prompt_len], F32_TOL, floor=0.0)
    close(got[1][prompt_len:], want[prompt_len:], F32_TOL, floor=0.0)


def test_a_held_share_through_the_cache_matches_the_reference_at_that_share():
    cfg = small_cfg(moe_share=(1, 2))
    params, rows = seeded(cfg, batch=1, length=30)
    want = np.asarray(ref_logits(params, rows, cfg))[0]
    got, _ = through_the_cache(params, cfg, {0: (rows[0].tolist(), 9)}, {0: 30})
    close(got[0], want, F32_TOL, floor=0.0)


def test_a_slot_used_twice_leaves_no_trace_in_the_next_request():
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=30)
    want = np.asarray(ref_logits(params, rows, cfg))
    a, b = rows[0].tolist(), rows[1].tolist()
    cache = generation.init_kv_cache(cfg, 3, SLOT, tokens=CHUNK)
    _, cache = through_the_cache(params, cfg, {1: (a, 22)}, {1: 30}, cache=cache)
    assert np.abs(np.asarray(cache.state.scan[:, 1])).max() > 0
    _, cache = decode(params, cfg, cache, {}, steps=2)  # the slot free: (0, 0) rows
    got, _ = through_the_cache(params, cfg, {1: (b, 9)}, {1: 21}, cache=cache)
    close(got[1], want[1, :21], F32_TOL, floor=0.0)


def test_rows_at_different_depths_in_one_step_equal_each_row_alone():
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=40)
    want = np.asarray(ref_logits(params, rows, cfg))
    a, b = rows[0].tolist(), rows[1].tolist()
    cache = generation.init_kv_cache(cfg, 3, SLOT, tokens=CHUNK)
    _, cache = prefill(params, cfg, cache, 2, a[:26])
    _, cache = prefill(params, cfg, cache, 0, b[:7])
    both, _ = decode(params, cfg, cache, {2: (a, 26, 36), 0: (b, 7, 17)})
    close(both[2], want[0, 26:36], F32_TOL, floor=0.0)
    close(both[0], want[1, 7:17], F32_TOL, floor=0.0)


def test_a_scan_state_in_bf16_fails_the_tolerance(monkeypatch, retraced):
    """The tolerance tells a scan state rounded to bfloat16 every step (8 mantissa bits
    where the configuration states 24) from a sound one."""
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=1, length=30)
    want = np.asarray(ref_logits(params, rows, cfg))[0]
    real = ssm.state_shapes
    monkeypatch.setattr(ssm, "state_shapes", lambda c: dict(
        real(c), scan=(real(c)["scan"][0], jnp.dtype(jnp.bfloat16))))
    retraced()
    got, _ = through_the_cache(params, cfg, {1: (rows[0].tolist(), 9)}, {1: 30})
    assert worst(got[1], want, 0.0) > 2 * F32_TOL  # (reads 6.6e-5; a sound state 4e-7)


def test_lockstep_generation_carries_the_state():
    harness.lockstep_generation_is_greedy(small_cfg(), ref_logits, max_new_tokens=8)


def test_cache_bytes_are_the_formula():
    cfg = small_cfg()
    cache = generation.init_kv_cache(cfg, 3, SLOT, tokens=CHUNK)
    conv_dim = 32 + 2 * 8
    assert cache.k.shape == (1, 3, 2, SLOT, 8) and cache.wk is None
    assert cache.state.conv.shape == (6, 3, 3 * conv_dim) and cache.state.scan.shape == (6, 3, 8, 32)
    assert cache.state.scan.dtype == jnp.float32
    assert generation.layer_stacks(cfg) == [("state", i) for i in range(5)] + [
        ("full", 0), ("state", 5)]
    # at that cut: one attention layer of 16,384 positions x 4,096 B and 9 states of 4,244,992 B
    big = PRESETS["granite-4.0-h-small"].replace(num_layers=10)
    at = generation.cache_layout(big, 16384, 1024)
    assert at["state_part_bytes"] == {"conv": 50688, "scan": 4194304}
    assert (at["bytes_per_position_per_layer"], at["state_bytes_per_row"]) == (4096, 4244992)
    assert 32 * at["bytes_per_slot"] == 32 * (16384 * 4096 + 9 * 4244992) == 3_370_041_344
    rc = {"mamba_n_heads": 128, "mamba_d_head": 64, "mamba_n_groups": 1, "mamba_d_state": 128,
          "mamba_d_conv": 4}
    assert ARCH.ssm_state_bytes(rc) == at["state_part_bytes"]


# -- the multiplier on every branch (the regression of ISSUE 70 (2)) ------------------------


def micro_cfg(**kw):
    """granite-4.0-h-micro, the stack the benchmark already TRAINS, at a small size: the
    dense shared MLP, 5 Mamba-2 layers, attention at 5, 1 Mamba-2 layer."""
    base = dict(vocab_size=96, hidden_size=32, num_layers=7, num_heads=4, num_kv_heads=2,
                ffn_dim=48, max_seq_len=SLOT, ssm_heads=8, ssm_head_dim=4, ssm_state=8,
                ssm_chunk=8, dtype=jnp.float32)
    base.update(kw)
    return PRESETS["granite-4.0-h-micro"].replace(**base)


def test_a_served_granite_stack_scales_every_branch():
    """`forward_with_cache` of granite-4.0-h-micro equals its no-cache forward: the state
    layers', the attention layer's and every MLP's branch times ``residual_multiplier``
    (before PR 70 only a state layer's was: the served logits of any Granite stack were
    wrong and no test said so)."""
    cfg = micro_cfg()
    assert cfg.residual_multiplier == 0.22
    params, rows = seeded(cfg, batch=1, length=30)
    want = np.asarray(forward(params, rows, cfg))[0]
    got, _ = through_the_cache(params, cfg, {1: (rows[0].tolist(), 9)}, {1: 30})
    close(got[1], want, F32_TOL, floor=0.0)


@pytest.mark.parametrize("branch", ["attention", "mlp"])
def test_the_multiplier_forced_to_one_on_a_branch_fails(monkeypatch, retraced, branch):
    """The comparison above has power: the attention layer's branch, or every MLP's, joined
    as it is (what the parent did: the branch handed over divided by the multiplier its
    join applies) lands far outside the tolerance."""
    cfg = micro_cfg()
    params, rows = seeded(cfg, batch=1, length=30)
    want = np.asarray(forward(params, rows, cfg))[0]
    if branch == "attention":
        real = generation._windowed_attention

        def unscaled(*args, **kw):
            y, cache = real(*args, **kw)
            return y / cfg.residual_multiplier, cache

        monkeypatch.setattr(generation, "_windowed_attention", unscaled)
    else:
        real = generation._mlp_at
        monkeypatch.setattr(generation, "_mlp_at",
                            lambda *args, **kw: real(*args, **kw) / cfg.residual_multiplier)
    retraced()
    got, _ = through_the_cache(params, cfg, {1: (rows[0].tolist(), 9)}, {1: 30})
    assert worst(got[1], want, 0.0) > 1000 * F32_TOL


# -- the controls the cell's limit stands on ---------------------------------------------


def _f32(tree):
    return [np.asarray(a, np.float32) for a in jax.tree.leaves(tree)]


@pytest.mark.parametrize("mode", ["weights_e4m3", "residual_one", "logits_one"])
def test_a_planted_control_moves_what_it_names(mode):
    """`experiments/serve_precision_controls.planted`, the three modes PR 70 added (the chip
    reads them through the benchmark's own runner; here, what each plants): the engine's
    matrices lose the last 4 of bfloat16's 7 mantissa bits while the reference's module is
    handed the tree as drawn; a branch joins the stream unscaled; the logits come undivided.
    Nothing stays planted."""
    from benchmark.lib import reference, serve
    from experiments import serve_precision_controls as controls
    from galvatron_tpu.models import modeling

    cfg = small_cfg(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    make_weights = serve.make_weights
    drawn = _f32(make_weights(cfg, 7))
    x = jnp.ones((1, 2, cfg.hidden_size), jnp.float32)
    table = {"embed": {"tok": jnp.ones((cfg.vocab_size, cfg.hidden_size), jnp.float32)}}
    with controls.planted(mode):
        if mode == "weights_e4m3":
            tree = serve.make_weights(cfg, 7)
            for a, b in zip(drawn, _f32(tree)):
                if a.ndim < 2:
                    assert np.array_equal(a, b)
                    continue
                assert not (b.view(np.uint32) & 0x000FFFFF).any()  # 3 mantissa bits left
                assert 0 < np.abs(b - a).max() <= 2.0 ** -4 * np.abs(a).max()
            arch = reference.load(controls.ROOT, "granitemoehybrid_moe")
            assert hasattr(arch, "logits") and hasattr(arch, "expert_chunk_work")
            want = ARCH.published_weights(make_weights(cfg, 7), ref_cfg(cfg))
            got = arch.published_weights(tree, ref_cfg(cfg))
            assert all(leaf.is_deleted() for leaf in jax.tree.leaves(tree))  # the rounded tree
            for a, b in zip(_f32(want), _f32(got)):
                assert np.array_equal(a, b)  # the reference reads the weights as drawn
        elif mode == "residual_one":
            assert np.allclose(modeling.residual_add(x, x, cfg), 2 * x)
        else:
            assert float(modeling.lm_head(x, table, cfg).max()) == cfg.hidden_size
    assert np.allclose(modeling.residual_add(x, x, cfg), 1.22 * x)
    assert float(modeling.lm_head(x, table, cfg).max()) == cfg.hidden_size / 16
    assert all(np.array_equal(a, b) for a, b in zip(drawn, _f32(serve.make_weights(cfg, 7))))


# -- the engine ---------------------------------------------------------------------------


def test_engine_serves_the_stack_end_to_end():
    """Four requests through three slots (one slot is used twice, a prompt that is no whole
    number of chunks among them): every served token is `generate`'s; the stats and the
    spans carry the state's and the held experts' counters, the ``prefill`` span the
    chunk's pairs."""
    cfg = small_cfg(moe_share=(0, 2))
    params, rows = seeded(cfg, batch=4, length=30)
    prompts = [rows[0, :26].tolist(), rows[1, :5].tolist(), rows[2, :13].tolist(),
               rows[3, :2].tolist()]
    served, stats, spans = harness.serve(harness.engine(cfg, params), prompts, 12, traced=True)
    assert served == harness.generations(params, cfg, prompts, 12)
    assert stats["cache_kind"] == "kv" and stats["cache_stacks"] == {"full": 1, "window": 0, "state": 6}
    assert stats["ssm_scan_path"] == {"fused": 0, "plain": 6}
    assert stats["ssm_step_path"] == {"fused": 0, "plain": 6}
    per_row = (3 * 48 + 8 * 32) * 4
    assert (stats["state_layers"], stats["state_bytes_per_row"]) == (6, per_row)
    assert stats["state_scan_bytes_per_row"] == 8 * 32 * 4
    assert stats["state_step_bytes"] == 2 * 3 * 6 * per_row
    assert stats["moe_held_experts"] == 4
    for a in spans["decode"]:
        assert (a["kv_full_layers"], a["state_layers"]) == (1, 6)
        assert a["state_bytes_per_row"] == a["state_conv_bytes_per_row"] + a["state_scan_bytes_per_row"]
    routed = [a for a in spans["decode"] if "moe_held_experts_touched" in a]
    assert routed  # (a step dispatched ahead hands its counters to the next span)
    for a in routed:
        assert 0 <= a["moe_held_experts_touched"] <= 4 and a["moe_held_pairs_per_token"] <= 3
        assert a["moe_held_experts"] == 4 and 0 < a["moe_live_rows_share"] <= 1
    # a chunk of 4 rows x top-3: at most 12 pairs, about half of them on the held half; the
    # span's pairs and touched experts are the MEAN over the prompt's chunks of 4 real rows
    # (26 tokens: 6 of its 7; 5: 1 of 2; 13: 3 of 4), and a prompt shorter than a chunk (2
    # tokens) has its one ragged chunk, which is then also the last chunk's own counters
    assert sorted(a["moe_chunks_counted"] for a in spans["prefill"]) == [0, 1, 3, 6]
    for a in spans["prefill"]:
        assert 0 < a["moe_held_pairs"] <= 12 and 0 < a["moe_held_experts_touched_a_chunk"] <= 4
        assert 0 < a["moe_held_experts_touched"] <= 4
        if not a["moe_chunks_counted"]:
            assert a["moe_held_pairs"] == pytest.approx(a["moe_held_pairs_per_token"] * 4)
            assert a["moe_held_experts_touched_a_chunk"] == a["moe_held_experts_touched"]


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


def test_the_limits_name_what_a_served_granite_stack_refuses():
    cfg = PRESETS["granite-4.0-h-small"]
    assert mixers.state_kinds(cfg) == ("ssm",)
    assert {limit.what for limit in mixers.limits(cfg)} >= {
        "tp", "cp", "pack_sequences", "paged_kv", "spec_decode", "pp", "ep"}
    assert not [limit for limit in mixers.limits(cfg) if limit.what == "kv_cache"]


def test_cli_serve_parses_the_cuts_flags():
    cfg = harness.cli_serve_parses([
        "--model_size", "granite-4.0-h-small", "--num_layers", "10", "--vocab_size", "50176",
        "--moe_share", "0/2", "--seq_length", "16384", "--param_dtype", "bf16",
        "--num_slots", "32", "--prefill_chunk", "1024", "--max_queue", "4096",
        "--request_ttl_s", "0"],
        dict(num_layers=10, vocab_size=50176, moe_share=(0, 2), moe_held=36,
             param_dtype=jnp.bfloat16, max_seq_len=16384, tie_word_embeddings=True,
             residual_multiplier=0.22, logits_scaling=16.0, pos_embed="nope"))
    assert cfg.kinds.count("ssm") == 9 and cfg.kinds[5] == "attention"
