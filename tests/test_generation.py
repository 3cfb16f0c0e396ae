"""KV-cache generation: cached decode == full recompute, ragged prompts, sampling."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.models import generation, modeling
from tests._serving_common import CFG


def _greedy_uncached(params, cfg, prompt, n_new):
    """Greedy tokens by the no-cache forward, ONE compiled program over the whole length:
    the model is causal, so what lies past the tokens so far moves no logit before it."""
    from tests._stack_harness import forward

    n = prompt.shape[1]
    toks = jnp.zeros((prompt.shape[0], n + n_new), jnp.int32).at[:, :n].set(prompt)
    for t in range(n, n + n_new):
        logits = forward(params, toks, cfg)
        toks = toks.at[:, t].set(jnp.argmax(logits[:, t - 1], axis=-1).astype(jnp.int32))
    return toks


@pytest.mark.parametrize("pos_embed,norm_type", [("rope", "rms"), ("learned", "layernorm"), ("alibi", "rms")])
def test_cached_greedy_matches_full_forward(pos_embed, norm_type):
    cfg = CFG.replace(pos_embed=pos_embed, norm_type=norm_type,
                      act_fn="gelu" if norm_type == "layernorm" else "swiglu")
    params = modeling.init_model_params(jax.random.key(0), cfg)
    prompt = jnp.asarray(np.random.RandomState(0).randint(1, cfg.vocab_size, (2, 7)), jnp.int32)
    ref = _greedy_uncached(params, cfg, prompt, 6)
    lengths = jnp.full((2,), 7, jnp.int32)
    out = generation.generate(params, prompt, lengths, cfg, jax.random.key(1),
                              max_new_tokens=6, min_prompt_len=7, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_ragged_prompts_teacher_forced():
    cfg = CFG
    params = modeling.init_model_params(jax.random.key(0), cfg)
    rng = np.random.RandomState(1)
    p_long = rng.randint(1, cfg.vocab_size, (9,)).tolist()
    p_short = rng.randint(1, cfg.vocab_size, (4,)).tolist()
    outs = generation.generate_np(params, cfg, [p_long, p_short], max_new_tokens=5)
    # each row must agree with generating it alone (same greedy path)
    for p, got in zip([p_long, p_short], outs):
        solo = generation.generate_np(params, cfg, [p], max_new_tokens=5)[0]
        assert got == solo, (p, got, solo)
        assert got[: len(p)] == p


def test_eos_stops_row():
    cfg = CFG
    params = modeling.init_model_params(jax.random.key(0), cfg)
    prompt = jnp.asarray(np.random.RandomState(2).randint(1, cfg.vocab_size, (1, 5)), jnp.int32)
    # find what greedy emits first, use it as eos → generation should stop at it
    ref = _greedy_uncached(params, cfg, prompt, 1)
    eos = int(ref[0, -1])
    out = generation.generate(params, prompt, jnp.asarray([5], jnp.int32), cfg,
                              jax.random.key(0), max_new_tokens=4, min_prompt_len=5,
                              temperature=0.0, eos_id=eos, pad_id=0)
    row = np.asarray(out)[0, 5:]
    assert row[0] == eos and (row[1:] == 0).all()


def test_top_k_top_p_filters():
    logits = jnp.asarray([[1.0, 2.0, 3.0, 4.0]])
    # top_k=1 → always argmax regardless of key
    for seed in range(4):
        t = generation.sample_logits(jax.random.key(seed), logits, temperature=1.0, top_k=1)
        assert int(t[0]) == 3
    # top_p tiny → only the argmax survives the nucleus
    for seed in range(4):
        t = generation.sample_logits(jax.random.key(seed), logits, temperature=1.0, top_p=0.05)
        assert int(t[0]) == 3
    # temperature sampling with no filters covers support
    seen = {int(generation.sample_logits(jax.random.key(s), logits, temperature=5.0)[0])
            for s in range(64)}
    assert len(seen) > 1


def test_top_p_one_keeps_full_distribution():
    """top_p=1.0 must be a no-op filter: every token stays in the nucleus,
    so the draw equals the unfiltered draw for the same key."""
    logits = jnp.asarray([[2.0, -1.0, 0.5, 0.0, -3.0]])
    for seed in range(16):
        key = jax.random.key(seed)
        with_p = generation.sample_logits(key, logits, temperature=1.0, top_p=1.0)
        without = generation.sample_logits(key, logits, temperature=1.0)
        assert int(with_p[0]) == int(without[0])
    # unfiltered temperature sampling reaches the whole support
    seen = {int(generation.sample_logits(jax.random.key(s), logits,
                                         temperature=5.0, top_p=1.0)[0])
            for s in range(256)}
    assert seen == set(range(5))


def test_top_k_one_is_greedy_at_any_temperature():
    logits = jnp.asarray([[1.0, 4.0, 2.0, 3.0]])
    greedy = generation.sample_logits(jax.random.key(0), logits, temperature=0.0)
    for seed in range(8):
        for temp in (0.5, 1.0, 10.0):
            t = generation.sample_logits(jax.random.key(seed), logits,
                                         temperature=temp, top_k=1)
            assert int(t[0]) == int(greedy[0]) == 1


def test_traced_sampling_params_do_not_recompile():
    """temperature/top_p are traced operands of the jitted generate: sweeping
    them must hit the jit cache, not grow it (a serving engine sweeping
    per-request params would otherwise compile per value)."""
    cfg = CFG
    params = modeling.init_model_params(jax.random.key(0), cfg)
    prompt = [1, 2, 3, 4, 5]
    kw = dict(max_new_tokens=3, top_k=2)
    from galvatron_tpu.analysis import recompile_guard

    generation.generate_np(params, cfg, [prompt], temperature=0.5, top_p=0.5, **kw)
    with recompile_guard(generation.generate, label="nucleus param sweep"):
        for temp, top_p in [(0.1, 0.3), (0.9, 0.95), (2.0, 0.5), (0.7, 0.2)]:
            generation.generate_np(params, cfg, [prompt], temperature=temp,
                                   top_p=top_p, **kw)
    # the greedy/no-nucleus program is a second entry (use_top_p is static),
    # but sweeping temperature within it stays flat too
    generation.generate_np(params, cfg, [prompt], temperature=0.5, **kw)
    with recompile_guard(generation.generate, label="greedy temp sweep"):
        for temp in (0.0, 0.3, 1.5):
            generation.generate_np(params, cfg, [prompt], temperature=temp, **kw)


def test_dataloader_start_batch_equivalence():
    from galvatron_tpu.core.dataloader import RandomTokenDataset

    ds = RandomTokenDataset(vocab_size=50, seq_len=8, size=64, seed=7)
    full = [b.copy() for _, b in zip(range(20), ds.batch_iterator(4))]
    resumed = [b.copy() for _, b in zip(range(5), ds.batch_iterator(4, start_batch=15))]
    for a, b in zip(full[15:], resumed):
        np.testing.assert_array_equal(a, b)


def test_moe_eval_routing_not_degenerate():
    from galvatron_tpu.models import moe

    cfg = CFG.replace(moe_experts=4, hidden_size=32, ffn_dim=64, num_heads=2)
    params = moe.init_moe_params(jax.random.key(0), cfg)
    # single token (batch-1 decode): train-mode sinkhorn is uniform → expert 0;
    # eval mode must follow the router logits instead
    x = jax.random.normal(jax.random.key(1), (1, 1, 32))
    logits = x.reshape(1, 32) @ params["router"]["w"]
    want = int(jnp.argmax(logits, axis=-1)[0])
    dispatch, _ = moe.route_top1(logits, capacity=8, train=False)
    got = int(jnp.argmax(dispatch.sum(-1), axis=-1)[0])
    assert got == want


# ---------------------------------------------------------------------------
# The cached forward writes the stacked cache in place (PR 38): held bit for
# bit to the forwards it replaced (tests/_cached_forward_reference.py), which
# sliced every layer's slab out, rewrote it whole and re-stacked
# ---------------------------------------------------------------------------

SMAX = 32

#: name -> (rows of the cache, tokens' shape, offsets, slot): scalar offsets
#: (generate's lockstep rows; one prefill chunk into row ``slot`` of many) and
#: per-row ones (the engine's decode step and its 1 + k verify window; an
#: inactive row carries (0, 0)); "clamped" windows would cross the row's end
CASES = {
    "lockstep_prefill": (3, (3, 7), 0, None),
    "lockstep_decode": (3, (3, 1), 9, None),
    "lockstep_clamped": (2, (2, 5), 30, None),
    "slot_chunk": (4, (1, 6), 11, 2),
    "slot_chunk_clamped": (4, (1, 6), 29, 3),
    "rows_decode_ragged": (4, (4, 1), [5, 0, 17, 31], None),
    "rows_verify_window": (4, (4, 4), [5, 0, 17, 28], None),
    "rows_clamped": (3, (3, 4), [30, 0, 31], None),
    "rows_single": (1, (1, 3), [6], None),
}


def _reference_forward(params, tokens, cfg, cache, offsets, slot):
    """What the replaced forwards (and the engine's old ``_prefill_chunk``)
    computed."""
    import _cached_forward_reference as ref

    if slot is not None:
        return ref.prefill_chunk(params, tokens, cfg, cache, slot, offsets)
    if jnp.ndim(offsets) == 0:
        return ref.forward_with_cache(params, tokens, cfg, cache, offsets)
    return ref.forward_with_cache_slots(params, tokens, cfg, cache, offsets)


@pytest.mark.parametrize("pos_embed", ["rope", "learned", "alibi"])
@pytest.mark.parametrize("case", list(CASES))
def test_inplace_cached_forward_matches_the_replaced_forwards_bitwise(case, pos_embed):
    """Logits and cache equal the replaced forwards' bit for bit, jitted with
    traced offsets and slot as the engine and ``generate`` call them; exactly
    positions [offset, offset + s) of each written row change, in every layer
    (the window clamped back where it would cross the row's end), and every
    other element of the cache keeps its bits."""
    from _cached_forward_reference import random_cache

    rows, tok_shape, offsets, slot = CASES[case]
    cfg = CFG.replace(pos_embed=pos_embed, max_seq_len=SMAX)
    params = modeling.init_model_params(jax.random.key(0), cfg)
    cache = random_cache(cfg, rows, SMAX, seed=3)
    tokens = jnp.asarray(
        np.random.RandomState(4).randint(1, cfg.vocab_size, tok_shape), jnp.int32)
    offsets = jnp.asarray(offsets, jnp.int32)
    slot_arg = None if slot is None else jnp.asarray(slot, jnp.int32)

    new_fn = jax.jit(lambda t, c, o, sl: generation.forward_with_cache(
        params, t, cfg, c, o, slot=sl))
    ref_fn = jax.jit(lambda t, c, o, sl: _reference_forward(params, t, cfg, c, o, sl))
    logits, out = new_fn(tokens, cache, offsets, slot_arg)
    ref_logits, ref_out = ref_fn(tokens, cache, offsets, slot_arg)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    np.testing.assert_array_equal(np.asarray(out.k), np.asarray(ref_out.k))
    np.testing.assert_array_equal(np.asarray(out.v), np.asarray(ref_out.v))

    s = tok_shape[1]
    written = np.zeros((rows, SMAX), bool)
    starts = np.broadcast_to(np.asarray(offsets), (tok_shape[0],))
    for b, start in enumerate(starts):
        start = min(int(start), SMAX - s)  # dynamic_update_slice clamps the start
        written[b if slot is None else slot, start:start + s] = True
    for old, new in ((cache.k, out.k), (cache.v, out.v)):
        changed = np.asarray(old != new)  # (L, rows, SMAX, kv, hd)
        assert not changed[:, ~written].any(), "an element outside the windows changed"
        assert changed[:, written].any(axis=(-1, -2)).all(), "a window position kept its bits"
