"""The chunk kernel over head-major key/value stacks (``ops/kv_prefill.py``;
interpreted on the CPU) against the plain body ``generation._attend_chunk`` on the
same stacks, the rule that picks between them (`kv_prefill.chunk_path`), and the
counter that says how often the picked body is the kernel (`kv_chunks_kernel`)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.models import generation, modeling
from galvatron_tpu.models.modeling import PRESETS
from galvatron_tpu.ops import kv_prefill
from tests import _stack_harness as harness
from tests._stack_harness import (  # noqa: F401  (`retraced`: a fixture)
    close, prefill, retraced, small_tiles)

# a chunk of 16 rows (a bf16 tile) over slots of four key blocks and a ring of three:
# the window plus a chunk, as the engine sizes it (`generation.ring_positions`)
BLOCK, ROWS, POSITIONS, RING, SPAN, KV = 16, 16, 64, 48, 32, 2
LAYERS, SLOTS, SLOT = 3, 2, 1


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """The kernel's key block, and the plain body's, at the tests' sizes."""
    small_tiles(monkeypatch, kv_prefill, generation, key_block=BLOCK)


@functools.lru_cache(maxsize=None)
def _case(g, d, dtype, places, seed=0):
    """Head-major stacks of ``LAYERS`` x ``SLOTS`` rows of ``places`` and a chunk's
    grouped queries."""
    keys = jax.random.split(jax.random.key(seed), 3)
    shape = (LAYERS, SLOTS, KV, places, d)
    ks = jax.random.normal(keys[0], shape, jnp.float32).astype(dtype)
    vs = jax.random.normal(keys[1], shape, jnp.float32).astype(dtype)
    qg = jax.random.normal(keys[2], (1, ROWS, KV, g, d), jnp.float32).astype(dtype)
    return qg, ks, vs


def _held(offset, places: int, span: int):
    """The absolute position each place holds for the chunk at ``offset``: its own
    index in whole rows, `generation._ring_key_positions` in a ring."""
    at = jnp.arange(places)
    return (generation._ring_key_positions(offset + ROWS - 1, at, places) if span else at[None])


@functools.lru_cache(maxsize=None)
def _programs(layer, span, scale):
    """(the kernel, the plain body) of ``layer`` of a stack, ``offset`` traced as the
    engine's is: one program each for every chunk of a row."""
    def kernel(qg, ks, vs, offset):
        return kv_prefill.attend_chunk(qg, ks, vs, layer, SLOT, offset, scale=scale, span=span)

    def plain(qg, ks, vs, offset):
        places = ks.shape[3]
        block, whole, live = generation.chunk_key_blocks(places, offset + ROWS)
        return generation._attend_chunk(
            qg, ks, vs, layer, SLOT, (offset + jnp.arange(ROWS))[None],
            lambda at: generation._ring_key_positions(offset + ROWS - 1, at, places) if span
            else at[None], jnp.minimum(whole, live), block, span, scale)

    return jax.jit(kernel), jax.jit(plain)


def _close(got, want, dtype):
    assert got.shape == want.shape and got.dtype == dtype
    close(got, want, 1e-5 if dtype == jnp.float32 else 2e-2)


# grouped query heads a key/value head and the head's size: smallthinker's and trinity's
# (whole lane tiles, the stacks read as they are handed), lfm2's (half a lane tile, read
# TRANSPOSED, the keys on the lanes)
HEADS = pytest.mark.parametrize("g,d", [(7, 128), (6, 128), (4, 64)],
                                ids=["g7_d128", "g6_d128", "g4_d64"])
DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])

# (the stack's window, the chunk's offset, the layer): whole slots at the row's start,
# mid-row at a chunk's multiple and not, the slot's last chunk; a ring at its start, with
# its last write inside a key block, its last chunk before it laps, its first chunk of a
# lap (every block live from here on), mid-lap laps on, and a lap's last chunk
CHUNKS = {
    "full_0": (0, 0, 0), "full_mid": (0, 16, 2), "full_unaligned": (0, 21, 0),
    "full_last": (0, POSITIONS - ROWS, 2),
    "ring_0": (SPAN, 0, 0), "ring_unaligned": (SPAN, 8, 2), "ring_before_the_lap": (SPAN, RING - ROWS, 0),
    "ring_lapped": (SPAN, RING, 2), "ring_laps_on": (SPAN, 2 * RING + ROWS, 0),
    "ring_a_laps_last": (SPAN, 4 * RING - ROWS, 2),
}


@pytest.mark.parametrize("chunk", list(CHUNKS))
@HEADS
@DTYPES
def test_the_kernel_is_the_plain_body(dtype, g, d, chunk):
    span, offset, layer = CHUNKS[chunk]
    qg, ks, vs = _case(g, d, dtype, RING if span else POSITIONS)
    assert kv_prefill.chunk_path(ks.shape[3], d, ROWS, dtype) == "kernel"
    kernel, plain = _programs(layer, span, d ** -0.5)
    got = kernel(qg, ks, vs, jnp.int32(offset))
    _close(got, plain(qg, ks, vs, jnp.int32(offset)), dtype)
    if layer:  # another layer's slab gives another answer: the prefetched index is read
        other = _programs(0, span, d ** -0.5)[0](qg, ks, vs, jnp.int32(offset))
        assert not np.allclose(np.asarray(got, np.float32), np.asarray(other, np.float32), atol=1e-2)


def test_the_window_counts():
    """The same ring under a wider window, and under the whole-row rule, reads otherwise
    once the chunk stands past the window."""
    qg, ks, vs = _case(7, 128, jnp.float32, RING)
    got = _programs(1, SPAN, 0.1)[0](qg, ks, vs, jnp.int32(RING - ROWS))
    for other in (SPAN + 8, 0):
        wider = _programs(1, other, 0.1)[0](qg, ks, vs, jnp.int32(RING - ROWS))
        assert not np.allclose(np.asarray(got), np.asarray(wider), atol=1e-3)
    # (inside one window and one lap: the same keys)
    _close(_programs(1, SPAN, 0.1)[0](qg, ks, vs, jnp.int32(ROWS)),
           _programs(1, 0, 0.1)[0](qg, ks, vs, jnp.int32(ROWS)), jnp.float32)


@pytest.mark.parametrize("chunk", ["full_0", "full_unaligned", "full_mid", "ring_0", "ring_unaligned",
                                   "ring_before_the_lap", "ring_lapped", "ring_laps_on"])
@pytest.mark.parametrize("g,d", [(7, 128), (4, 64)], ids=["g7_d128", "g4_d64"])
@DTYPES
def test_what_no_query_of_the_chunk_sees_never_reaches_the_output(dtype, g, d, chunk):
    """NaN, keys and values, in every place past the chunk's end (a ring: every place
    never written or older than the window of the chunk's first query, an earlier lap's
    among them): bit for bit the clean stacks' output (blocks past the last live one are
    not fetched; elsewhere such keys are masked and such values zeroed)."""
    span, offset, layer = CHUNKS[chunk]
    qg, ks, vs = _case(g, d, dtype, RING if span else POSITIONS)
    held = _held(offset, ks.shape[3], span)[0]
    past = (held >= offset + ROWS) | (held < 0) | ((held <= offset - span) & bool(span))
    dirty = [jnp.where(past[None, None, None, :, None], jnp.nan, a) for a in (ks, vs)]
    # what stays clean: the positions from the first query's oldest key to the last write
    oldest = max(offset - span + 1, 0) if span else 0
    assert int((~past).sum()) == offset + ROWS - oldest < ks.shape[3]
    kernel, _ = _programs(layer, span, d ** -0.5)
    got, clean = kernel(qg, *dirty, jnp.int32(offset)), kernel(qg, ks, vs, jnp.int32(offset))
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(clean, np.float32))


def _cfg(**kw):
    """A windowed stack at the smallest sizes the kernel takes: heads of 128, a ring of
    8 + 8 places, one key block."""
    base = dict(vocab_size=96, hidden_size=32, num_layers=4, num_heads=4, num_kv_heads=2,
                attn_head_dim=128, ffn_dim=24, max_seq_len=POSITIONS, sliding_window_size=8,
                moe_experts=4, moe_top_k=2, moe_ffn_dim=24, dtype=jnp.float32)
    base.update(kw)
    return PRESETS["smallthinker-21b-a3b"].replace(**base)


# the rule's cases: (positions, head_dim, rows, dtype, backend) -> path
RULE = {
    "inside": (POSITIONS, 128, 16, jnp.bfloat16, "cpu", "kernel"),
    "inside_f32": (POSITIONS, 256, 8, jnp.float32, "tpu", "kernel"),
    "head_dim_64": (POSITIONS, 64, 16, jnp.bfloat16, "tpu", "kernel"),
    "head_dim_96": (POSITIONS, 96, 16, jnp.bfloat16, "cpu", "plain"),
    "head_dim_32": (POSITIONS, 32, 16, jnp.bfloat16, "tpu", "plain"),
    "capacity": (POSITIONS + 8, 128, 16, jnp.bfloat16, "cpu", "plain"),
    "rows": (32 * POSITIONS, 128, kv_prefill.MAX_CHUNK_ROWS + 16, jnp.bfloat16, "tpu", "plain"),
    "half_a_tile_of_rows": (POSITIONS, 128, 8, jnp.bfloat16, "cpu", "plain"),
    "dtype": (POSITIONS, 128, 16, jnp.float16, "cpu", "plain"),
    "backend": (POSITIONS, 128, 16, jnp.bfloat16, "gpu", "plain"),
}


@pytest.mark.parametrize("case", list(RULE))
def test_the_rule_answers_from_shapes_and_the_backend_and_the_layout_agrees(monkeypatch, case):
    positions, head_dim, rows, dtype, backend, path = RULE[case]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert kv_prefill.chunk_path(positions, head_dim, rows, dtype) == path
    # the same question through a configuration, whose ring (8 + rows, rounded up to
    # whole chunks) is whole key blocks too where the rows are a multiple of 8
    cfg = _cfg(attn_head_dim=head_dim, dtype=dtype)
    ring = generation.ring_positions(cfg, positions, rows)
    assert ring % BLOCK == 0
    layout = generation.chunk_layout(cfg, rows, positions)
    if case == "capacity":  # (the slots are outside the rule, the ring of 32 inside)
        assert layout["chunk_path"] == "mixed"
    else:
        assert layout == {"chunk_path": path, "chunk_key_block": BLOCK if path == "kernel"
                          else modeling.key_block(positions, BLOCK)}
    # a stack whose slots are whole key blocks and whose ring (16 + 8) is not
    if path == "kernel" and rows == 8:
        assert generation.chunk_layout(cfg.replace(sliding_window_size=16), rows, positions)[
            "chunk_path"] == "mixed"


def test_a_stack_without_a_ring_or_a_stack_asks_nothing_of_what_it_lacks():
    lfm2 = PRESETS["lfm2-24b-a2b"].replace(attn_head_dim=64, dtype=jnp.bfloat16)
    assert not lfm2.windowed
    assert generation.chunk_layout(lfm2, 16, POSITIONS)["chunk_path"] == "kernel"
    assert generation.chunk_layout(lfm2, 16, POSITIONS + 8)["chunk_path"] == "plain"
    assert generation.chunk_layout(PRESETS["opt-125m"], 16, POSITIONS) == {}


@pytest.mark.parametrize("why", ["head_dim", "capacity", "rows", "dtype", "ring", "head_dim_64"])
def test_outside_the_rule_the_plain_body_runs(monkeypatch, why):
    """`_windowed_attention` asks the rule of the layer's own stack: outside it the
    kernel is not called (a ring of 16 + 8 places is no whole number of key blocks);
    inside it, in a full layer of the same cache, it is, at a head of 64 too, and
    gives what the plain body gives."""
    calls = []
    real = kv_prefill.attend_chunk
    monkeypatch.setattr(kv_prefill, "attend_chunk", lambda *a, **k: calls.append(1) or real(*a, **k))
    over = {"head_dim": dict(attn_head_dim=96), "head_dim_64": dict(attn_head_dim=64),
            "dtype": dict(dtype=jnp.float16), "ring": dict(sliding_window_size=16)}.get(why, {})
    cfg = _cfg(**over)
    positions = POSITIONS + 8 if why == "capacity" else POSITIONS
    rows = 4 if why == "rows" else 8
    params = modeling.init_model_params(jax.random.key(0), cfg)
    cache = generation.init_kv_cache(cfg, 2, positions, tokens=rows)
    x = jax.random.normal(jax.random.key(1), (1, rows, cfg.hidden_size), cfg.dtype)
    offset, slot = jnp.int32(16), jnp.int32(1)

    def attend(windowed):
        layer = cfg.window_layers.index(windowed)
        return generation._windowed_attention(
            x, params["layers"][layer], cfg.layer_view(layer), cache, windowed, 0,
            generation._window_starts(offset, slot, 1), slot, offset, None)[0]

    # the stack the reason puts outside the rule (the ring of a cache whose slots are no
    # whole key blocks is inside it, and so is every stack at a head of 64)
    outside = {"capacity": False, "head_dim_64": None}.get(why, True)
    if outside is not None:
        attend(outside)
    assert not calls
    if why in ("ring", "head_dim_64"):  # the same chunk in a full layer takes the kernel
        got = attend(False)
        assert calls == [1]
        monkeypatch.setattr(kv_prefill, "chunk_path", lambda *a: "plain")
        _close(got, attend(False), jnp.float32)
        assert calls == [1]


@pytest.mark.parametrize("d", [128, 64])
def test_a_prompt_through_the_kernel_is_the_plain_chunks(monkeypatch, retraced, d):
    """`forward_with_cache` over a windowed stack whose four layers' chunks take the
    kernel, a prompt of 44 tokens in chunks of 8 (the ring of 16 lapped twice, the last
    chunk padded), against the same chunks with the rule answering "plain": the same
    logits at every position, and the same cache (to rounding)."""
    cfg = _cfg(attn_head_dim=d)
    params = modeling.init_model_params(jax.random.key(0), cfg)
    cache = generation.init_kv_cache(cfg, 3, POSITIONS, tokens=8)
    assert cache.wk.shape[3] == 16
    row = jax.random.randint(jax.random.key(1), (44,), 0, cfg.vocab_size, jnp.int32).tolist()
    spans = []
    real = kv_prefill.attend_chunk
    monkeypatch.setattr(kv_prefill, "attend_chunk",
                        lambda *a, **k: spans.append(k["span"]) or real(*a, **k))
    retraced()  # (the key block and the rule are bound when a forward is traced)
    got, held = prefill(params, cfg, cache, 1, row, chunk=8)
    assert sorted(spans) == [0] + [8] * 3  # one trace: every chunk runs the one program
    monkeypatch.setattr(kv_prefill, "chunk_path", lambda *a: "plain")
    retraced()
    want, plain = prefill(params, cfg, cache, 1, row, chunk=8)
    assert len(spans) == 4
    close(got, want, 2e-5)
    for a, b in zip(held[:4], plain[:4]):  # (a later layer's keys are a function of this attention)
        close(a, b, 2e-5)


@pytest.mark.parametrize("path", ["kernel", "plain"])
def test_the_engine_counts_the_chunks_its_chunk_kernel_takes(monkeypatch, path):
    """A prompt of 20 tokens in chunks of 8 (at 0, 8 and 16) through the chunk kernel and
    outside its rule (slots of 64 under the real key block): the greedy tokens are plain
    generation's; `kv_chunks_kernel / prefill_chunks` is 1.0 | 0.0; the request's
    `prefill` span carries the count and the key blocks a full layer's chunk attention
    fetched (1 + 1 + 2 of 16 keys | 3 times all 64)."""
    if path == "plain":
        monkeypatch.undo()
    calls = []
    real = kv_prefill.attend_chunk
    monkeypatch.setattr(kv_prefill, "attend_chunk", lambda *a, **k: calls.append(1) or real(*a, **k))
    # (a vocabulary a path: the engine's compiled chunk is a function of the configuration,
    # and what it binds of this module it binds when it is traced)
    cfg = _cfg(vocab_size=96 + (path == "plain"))
    params, rows = harness.seeded(cfg, batch=1, length=20)
    prompt = np.asarray(rows[0]).tolist()
    engine = harness.engine(cfg, params, num_slots=2, prefill_chunk=8)
    (out,), stats, spans = harness.serve(engine, [prompt], 4, traced=True)
    span, = spans["prefill"]
    assert [out] == harness.generations(params, cfg, [prompt], 4)
    assert len(calls) == (cfg.num_layers if path == "kernel" else 0)
    assert stats["prefill_chunks"] == 3 and stats["chunk_path"] == path
    assert stats["kv_chunks_kernel"] / stats["prefill_chunks"] == (1.0 if path == "kernel" else 0.0)
    assert span["kv_chunks_kernel"] == (3 if path == "kernel" else 0)
    assert span["kv_chunk_key_blocks"] == (4 if path == "kernel" else 3)
    assert not {"latent_chunks_kernel", "latent_chunk_key_blocks"} & (set(stats) | set(span))
