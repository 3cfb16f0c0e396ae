"""The one property ``tests/_stack_harness.py`` is for: what a model's tests run is
COMPILED once a (``cfg``, shapes) and run again by every later call, the reference's
program likewise, so that a file's cases share their programs."""

import os

import jax.numpy as jnp

from benchmark.lib import reference
from galvatron_tpu.models.modeling import PRESETS
from tests import _stack_harness as harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = reference.load(ROOT, "opt")


def small_cfg():
    # (a vocabulary no other file uses: this file's first call of a forward is a trace)
    return PRESETS["opt-1.3b"].replace(vocab_size=61, hidden_size=32, num_layers=2, num_heads=4,
                                       ffn_dim=48, max_seq_len=32, dtype=jnp.float32)


def ref_cfg(cfg, share=None):
    return {"num_attention_heads": cfg.num_heads}


def test_two_calls_of_through_the_cache_trace_each_forward_once():
    cfg = small_cfg()
    params, rows = harness.seeded(cfg, batch=2, length=24)
    want = harness.reference(ARCH, ref_cfg, cfg).logits(params, rows)
    before = harness.chunk_forward._cache_size(), harness.step_forward._cache_size()
    for row in (0, 1):  # other tokens, another prompt length and slot: the same shapes
        n, slot = (13, 2) if row else (6, 0)
        got, _ = harness.through_the_cache(params, cfg, {slot: (rows[row].tolist(), n)}, {slot: 24})
        harness.close(got[slot], want[row], 2e-5)
    after = harness.chunk_forward._cache_size(), harness.step_forward._cache_size()
    assert (after[0] - before[0], after[1] - before[1]) == (1, 1)
    # `forward` and `seeded` likewise: a second call is the first one's
    harness.close(harness.forward(params, rows, cfg), want, 2e-5)
    size = harness.forward._cache_size()
    harness.forward(params, rows[::-1], cfg)
    assert harness.forward._cache_size() == size
    again, _ = harness.seeded(small_cfg(), batch=2, length=24)
    assert again is not params and again["embed"]["tok"] is params["embed"]["tok"]


def test_a_second_reference_of_the_same_cfg_and_share_is_the_first_ones_program():
    cfg = small_cfg()
    first = harness.reference(ARCH, ref_cfg, cfg)
    assert harness.reference(ARCH, ref_cfg, small_cfg()) is first  # (an EQUAL cfg: by value)
    assert harness.reference(ARCH, ref_cfg, cfg, (0, 2)) is not first
    params, rows = harness.seeded(cfg, batch=2, length=24)
    first.logits(params, rows)
    size = first.logits._cache_size()
    harness.reference(ARCH, ref_cfg, cfg).logits(params, rows[::-1])
    assert first.logits._cache_size() == size >= 1
