"""The decode kernel over head-major key/value stacks (``ops/kv_decode.py``;
interpreted on the CPU) against the plain body ``generation._attend_rows`` on the
same stacks, the rule that picks between them (`kv_decode.decode_path`), and the
counter that says what the picked body fetches (`generation.cache_read_positions`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.models import generation
from galvatron_tpu.models.modeling import PRESETS
from galvatron_tpu.ops import kv_decode
from tests._stack_harness import (  # noqa: F401  (`retraced`: a fixture)
    close, prefill, retraced, small_tiles, step_forward)

BLOCK, POSITIONS, KV, D = 16, 64, 2, 128


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """The kernel's key block at the tests' sizes: slots of 64 are four blocks."""
    small_tiles(monkeypatch, kv_decode, key_block=BLOCK)


def _case(lengths, s, g, dtype, layers=3, seed=0, d=D):
    """Head-major stacks of ``layers`` x len(lengths) rows, and the grouped queries of
    a window of ``s`` positions that ends each row's length (a row out of use: 0,
    whose window starts at position 0)."""
    rows = len(lengths)
    keys = jax.random.split(jax.random.key(seed), 3)
    ks = jax.random.normal(keys[0], (layers, rows, KV, POSITIONS, d), jnp.float32).astype(dtype)
    vs = jax.random.normal(keys[1], (layers, rows, KV, POSITIONS, d), jnp.float32).astype(dtype)
    qg = jax.random.normal(keys[2], (rows, s, KV, g, d), jnp.float32).astype(dtype)
    first = jnp.asarray([max(n - s, 0) for n in lengths], jnp.int32)
    return qg, ks, vs, first


def _held(first, s, span):
    """The absolute position each place holds for each row: its own index in whole
    rows, `generation._ring_key_positions` in a ring (``span`` > 0)."""
    places = jnp.arange(POSITIONS)
    return (generation._ring_key_positions(first + s - 1, places, POSITIONS) if span
            else places[None])


def _plain(qg, ks, vs, layer, first, scale, span=0):
    s = qg.shape[1]
    return generation._attend_rows(qg, ks[layer], vs[layer], first[:, None] + jnp.arange(s)[None],
                                   _held(first, s, span), span, scale)


def _close(got, want, dtype):
    assert got.shape == want.shape
    close(got, want, 2e-5 if dtype == jnp.float32 else 2e-2)


# rows of unequal lengths: 1, a block less one, a whole number of blocks, a block plus
# one, the slot's capacity, and a row out of use (length 0: its window is at 0)
LENGTHS = [1, BLOCK - 1, BLOCK, 2 * BLOCK, BLOCK + 1, POSITIONS, 0]


# grouped query heads a key/value head and the head's size: whole lane tiles (the stacks
# read as they are handed), and a head of 64, half a lane tile (read TRANSPOSED, the keys
# on the lanes); 6 query heads a key/value head are Trinity-Large's 48 / 8 (PR 61)
HEADS = pytest.mark.parametrize("g,d", [(7, D), (6, D), (1, D), (4, 64)],
                                ids=["7", "6", "1", "g4_d64"])


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("s", [1, 4], ids=["decode", "verify4"])
@HEADS
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_kernel_is_the_plain_body(dtype, g, d, s, layer):
    qg, ks, vs, first = _case(LENGTHS, s, g, dtype, d=d)
    scale = d ** -0.5
    assert kv_decode.decode_path(POSITIONS, d, s * g, dtype) == "kernel"
    got = jax.jit(lambda q, k, v: kv_decode.attend_rows(q, k, v, layer, first, scale=scale))(qg, ks, vs)
    assert got.shape == qg.shape and got.dtype == dtype
    _close(got, _plain(qg, ks, vs, layer, first, scale), dtype)
    if layer:  # another layer's slab gives another answer: the prefetched index is read
        other = kv_decode.attend_rows(qg, ks, vs, 0, first, scale=scale)
        assert not np.allclose(np.asarray(got, np.float32), np.asarray(other, np.float32), atol=1e-2)


# a ring of 64 places under a window of 40: rows that have not lapped it (read up to
# their last write), one that fills it, and rows one to three laps on, whose block 0
# may hold no key the window sees; a row out of use
RING_LENGTHS = [1, BLOCK - 1, BLOCK, BLOCK + 1, POSITIONS, POSITIONS + 1, 100, 2 * POSITIONS + BLOCK, 200, 0]
SPAN = 40


@pytest.mark.parametrize("s", [1, 4], ids=["decode", "verify4"])
@HEADS
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_kernel_over_a_ring_is_the_plain_body(dtype, g, d, s):
    qg, ks, vs, first = _case(RING_LENGTHS, s, g, dtype, d=d)
    scale = d ** -0.5
    got = jax.jit(lambda q, k, v: kv_decode.attend_rows(q, k, v, 1, first, scale=scale, span=SPAN))(
        qg, ks, vs)
    assert got.shape == qg.shape and got.dtype == dtype
    _close(got, _plain(qg, ks, vs, 1, first, scale, SPAN), dtype)
    # the window counts: the same ring under the whole-row rule reads otherwise
    whole = kv_decode.attend_rows(qg[:4], ks[:, :4], vs[:, :4], 1, first[:4], scale=scale)
    _close(got[:4], whole, dtype)  # (rows inside one window and one lap: the same keys)
    assert not np.allclose(np.asarray(got[6], np.float32), np.asarray(
        kv_decode.attend_rows(qg, ks, vs, 1, first, scale=scale, span=SPAN + 8)[6], np.float32), atol=1e-3)


@pytest.mark.parametrize("span", [0, SPAN], ids=["rows", "ring"])
@pytest.mark.parametrize("s", [1, 4], ids=["decode", "verify4"])
@pytest.mark.parametrize("g,d", [(7, D), (4, 64)], ids=["7", "g4_d64"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_what_no_query_of_a_row_sees_never_reaches_the_output(dtype, g, d, s, span):
    """NaN, keys and values, in every place past each row's window (a ring: every
    place never written, a lap old or older than the window of the row's first
    query): bit for bit the clean stacks' output (blocks past the last live one are not
    fetched; elsewhere such keys are masked and such values zeroed)."""
    qg, ks, vs, first = _case(RING_LENGTHS if span else LENGTHS, s, g, dtype, d=d)
    held = jnp.broadcast_to(_held(first, s, span), (len(first), POSITIONS))
    past = (held >= (first + s)[:, None]) | (held < 0) | ((held <= (first - span)[:, None]) & bool(span))
    dirty = [jnp.where(past[None, :, None, :, None], jnp.nan, a) for a in (ks, vs)]
    assert bool(jnp.isnan(dirty[0][1, 0, :, s:]).all())
    # what stays clean: the positions from the first query's oldest key to the last write
    oldest = jnp.maximum(first - span + 1, 0) if span else jnp.zeros_like(first)
    assert np.array_equal(np.asarray((~past).sum(1)), np.asarray(first + s - oldest))
    attend = jax.jit(lambda k, v: kv_decode.attend_rows(qg, k, v, 1, first, scale=d ** -0.5, span=span))
    got, clean = attend(*dirty), attend(ks, vs)
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(clean, np.float32))


def test_a_scalar_offset_is_every_rows_offset():
    """``generate``'s lockstep step: one offset for all rows."""
    qg, ks, vs, _ = _case([9, 9, 9], 1, 7, jnp.float32)
    first = jnp.full((3,), 8, jnp.int32)
    got = kv_decode.attend_rows(qg, ks, vs, 1, first, scale=0.1)
    _close(got, _plain(qg, ks, vs, 1, first, 0.1), jnp.float32)


def _cfg(**kw):
    """A windowed stack at the smallest sizes the kernel takes: heads of 128."""
    base = dict(vocab_size=96, hidden_size=32, num_layers=4, num_heads=4, num_kv_heads=2,
                attn_head_dim=D, ffn_dim=24, max_seq_len=POSITIONS, sliding_window_size=8,
                moe_experts=4, moe_top_k=2, moe_ffn_dim=24, dtype=jnp.float32)
    base.update(kw)
    return PRESETS["smallthinker-21b-a3b"].replace(**base)


# the rule's cases: (what differs, positions, head_dim, query rows, dtype, backend) -> path
RULE = {
    "inside": (POSITIONS, D, 7, jnp.bfloat16, "cpu", "kernel"),
    "inside_f32_verify": (POSITIONS, 2 * D, 4 * 7, jnp.float32, "tpu", "kernel"),
    "head_dim_64": (POSITIONS, 64, 7, jnp.bfloat16, "cpu", "kernel"),
    "head_dim_64_f32_verify": (POSITIONS, 64, 4 * 7, jnp.float32, "tpu", "kernel"),
    "head_dim_96": (POSITIONS, 96, 7, jnp.bfloat16, "cpu", "plain"),
    "head_dim_32": (POSITIONS, 32, 7, jnp.bfloat16, "tpu", "plain"),
    "capacity": (POSITIONS + 8, D, 7, jnp.bfloat16, "cpu", "plain"),
    "query_rows": (POSITIONS, D, kv_decode.MAX_QUERY_ROWS + 1, jnp.bfloat16, "cpu", "plain"),
    "dtype": (POSITIONS, D, 7, jnp.float16, "cpu", "plain"),
    "backend": (POSITIONS, D, 7, jnp.bfloat16, "gpu", "plain"),
}


@pytest.mark.parametrize("case", list(RULE))
def test_the_rule_answers_from_shapes_and_the_backend_and_the_counter_agrees(monkeypatch, case):
    positions, head_dim, query_rows, dtype, backend, path = RULE[case]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert kv_decode.decode_path(positions, head_dim, query_rows, dtype) == path
    # the same question through a configuration: 2 grouped heads a key/value head
    cfg = _cfg(attn_head_dim=head_dim, dtype=dtype)
    window = -(-query_rows // 2)
    lengths, rows = [1, BLOCK, BLOCK + 1, POSITIONS], 6
    read = generation.cache_read_positions(cfg, lengths, rows, positions, window, ring=12)
    assert kv_decode.decode_path(positions, head_dim, 2 * window, dtype) == path
    # four rows in use of 1, 1, 2 and 4 blocks and two free ones, a block each
    assert read["full"] == ((1 + 1 + 2 + 4 + 2) * BLOCK if path == "kernel" else rows * positions)
    assert read["window"] == rows * 12  # (a ring of 12 is no whole number of key blocks)
    # a ring of two key blocks under the same rule: a row is read up to its last write
    # until it has lapped the ring, whole from then on
    ringed = generation.cache_read_positions(cfg, lengths, rows, positions, window, ring=2 * BLOCK)
    in_rule = kv_decode.decode_path(2 * BLOCK, head_dim, 2 * window, dtype) == "kernel"
    assert ringed["window"] == ((1 + 1 + 2 + 2 + 2) * BLOCK if in_rule else rows * 2 * BLOCK)


@pytest.mark.parametrize("why", ["head_dim", "capacity", "query_rows", "dtype", "ring", "head_dim_64"])
def test_outside_the_rule_the_plain_body_runs(monkeypatch, why):
    """`_windowed_attention` asks the rule of the layer's own stack: outside it the
    kernel is not called (a ring of 8 + 4 places is no whole number of key blocks);
    inside it, in a full layer of the same cache, it is, at a head of 64 too, and
    gives what the plain body gives."""
    calls = []
    real = kv_decode.attend_rows
    monkeypatch.setattr(kv_decode, "attend_rows", lambda *a, **k: calls.append(1) or real(*a, **k))
    over = {"head_dim": dict(attn_head_dim=96), "head_dim_64": dict(attn_head_dim=64),
            "dtype": dict(dtype=jnp.float16)}.get(why, {})
    cfg = _cfg(**over)
    positions = POSITIONS + 8 if why == "capacity" else POSITIONS
    if why == "query_rows":
        monkeypatch.setattr(kv_decode, "MAX_QUERY_ROWS", 1)
    from galvatron_tpu.models import modeling

    params = modeling.init_model_params(jax.random.key(0), cfg)
    cache = generation.init_kv_cache(cfg, 2, positions, tokens=4)
    x = jax.random.normal(jax.random.key(1), (2, 1, cfg.hidden_size), cfg.dtype)
    offsets = jnp.asarray([3, 20], jnp.int32)

    def attend(windowed):
        layer = cfg.window_layers.index(windowed)
        return generation._windowed_attention(
            x, params["layers"][layer], cfg.layer_view(layer), cache, windowed, 0,
            generation._window_starts(offsets, None, 2), None, offsets, None)[0]

    inside = why in ("ring", "head_dim_64")  # (whose ring of 12 places is outside)
    attend(inside)
    assert not calls
    if inside:  # the same step in a full layer takes the kernel
        got = attend(False)
        assert calls == [1]
        monkeypatch.setattr(kv_decode, "decode_path", lambda *a: "plain")
        _close(got, attend(False), jnp.float32)
        assert calls == [1]


@pytest.mark.parametrize("window,kernels", [(8, 1), (28, 4)], ids=["ring_plain", "ring_kernel"])
def test_a_decode_step_through_the_kernel_is_the_plain_steps(monkeypatch, retraced, window,
                                                            kernels):
    """`forward_with_cache` over a windowed stack whose full layer takes the kernel
    (and, with a ring of 28 + 4 places = two key blocks, its three window layers too),
    a row past the ring's first lap among them, against the same forward with the
    rule answering "plain": the same logits."""
    from galvatron_tpu.models import modeling

    cfg = _cfg(sliding_window_size=window)
    params = modeling.init_model_params(jax.random.key(0), cfg)
    cache = generation.init_kv_cache(cfg, 3, POSITIONS, tokens=4)
    assert cache.wk.shape[3] == window + 4
    row = jax.random.randint(jax.random.key(1), (44,), 0, cfg.vocab_size, jnp.int32).tolist()
    retraced()  # (the key block and the rule are bound when a forward is traced)
    for slot, length in ((1, 44), (2, 8)):  # slot 1 has lapped the ring of 32, slot 2 not
        _, cache = prefill(params, cfg, cache, slot, row[:length], chunk=4)
    toks = jnp.asarray([[0], [5], [7]], jnp.int32)
    offs = jnp.asarray([0, 44, 8], jnp.int32)
    calls = []
    real = kv_decode.attend_rows
    monkeypatch.setattr(kv_decode, "attend_rows", lambda *a, **k: calls.append(k["span"]) or real(*a, **k))
    retraced()
    got, _ = step_forward(params, cfg, cache, toks, offs)
    assert sorted(calls) == [0] + [window] * (kernels - 1)
    monkeypatch.setattr(kv_decode, "decode_path", lambda *a: "plain")
    retraced()
    want, _ = step_forward(params, cfg, cache, toks, offs)
    assert len(calls) == kernels
    _close(got, want, jnp.float32)
