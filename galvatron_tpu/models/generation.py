"""Autoregressive text generation with a static KV cache.

TPU-native counterpart of the reference's text-generation subsystem
(reference: galvatron/site_package/megatron/text_generation/{api.py,
generation.py,sampling.py} and text_generation_server.py): prefill + one
token-per-step decode over a preallocated KV cache, with greedy /
temperature / top-k / top-p sampling.

Design differences from the reference (which loops in Python over
dynamically growing torch tensors): the cache is a static-shape pytree and
the decode loop is a single ``lax.scan`` inside one ``jit`` — XLA sees a
fixed-shape program, so the whole generation runs on-device without host
round-trips per token.

What a cache holds goes by what a LAYER keeps, one rule for every stack:

- whole slots: keys and values of every position of a row (attention);
- a ring: keys and values of the last ``window`` positions (a sliding-window layer);
- a state: a fixed-size array a row, or one a part, no positions (a kind of
  ``mixers.MIXERS`` with ``state``: the gated short convolution's last inputs; the
  Mamba-2 mixer's conv tail and float32 scan state, ``models/ssm.SsmState``);
- or the kind's own position-indexed cache (latent attention: every layer alike, or, of
  a stack with an indexer or window layers, the full layers' latent and index keys in
  whole slots and the window layers' latent in a ring: ``models/mla.LatentCache``).

A stack whose layers all keep whole slots has a `KVCache`; one that mixes whole
slots with rings or states has `SlotStacks`, a stack of arrays for each of the three,
over THOSE layers only (`layer_stacks` maps a layer to its place). A state differs
from positions in four ways, and the cached forwards and the engine hold to each:

1. it is reset by READING: a forward that starts at position 0 reads zeros whatever
   the row holds (positions are simply overwritten before a query can see them);
2. it is written as of the forward's last REAL row (``last``): pad rows of a padded
   prompt chunk must not reach it (a pad position's keys are harmless, masked);
3. it cannot be wound back: no speculation (a rejected draft has advanced it);
4. it cannot run positions twice: a prompt chunk is never slid left, so the slots
   are a whole number of chunks (`serving.Engine`).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from galvatron_tpu.models import mixers, modeling
from galvatron_tpu.models.modeling import ModelConfig, Params
from galvatron_tpu.ops import kv_decode, kv_prefill


class KVCache(NamedTuple):
    """An attention stack's cache: per-layer key/value tensors, each (L, B,
    max_len, kv_heads, head_dim). A stack whose layers' kind keeps a cache of its
    own (``mixers.cache_kind``) has that kind's NamedTuple instead (latent
    attention: ``models/mla.LatentCache``, one array (L, B, max_len, r + dr));
    every array of either is stacked (L, B, max_len, ...). A stack with
    sliding-window layers or with layers that keep a state has `SlotStacks`."""

    k: jax.Array
    v: jax.Array


class SlotStacks(NamedTuple):
    """The cache of a stack whose layers do not all keep whole slots (`stacked`):
    stacks by what a layer keeps, in one cache, each over its own layers only and None
    where the stack has no such layer. The full layers' keys and values whole, ``k`` /
    ``v`` (L_full, B, kv_heads, max_len, head_dim), position p at p; the window layers'
    in a RING, ``wk`` / ``wv`` (L_win, B, kv_heads, R, head_dim), position p at
    ``p mod R`` with ``R = window + the most positions one forward writes a row``
    (`ring_positions`); the state layers' STATE, ``state`` (L_state, B, ...), whose
    trailing shape is the kind's (``init_state`` of its module: the module docstring
    has what a state asks that positions do not; a kind whose row keeps parts of
    different types gives a NamedTuple of such stacks, one a part, which this cache
    carries as the pytree it is). HEAD-major: a key/value head's positions are a matrix
    (positions, head_dim), which the chip's matrix unit takes as it lies; from
    (positions, kv_heads, head_dim) the compiler copied every stack whole, every
    step, into that order (compiled for a described v5e at the cell's size: 7.76
    GiB of temporaries beside 11.6 of weights and cache). Attention masks a ring entry by the ABSOLUTE position it
    holds (`_ring_key_positions`), so an entry from a lap ago or from the slot's
    previous request is never seen; write-then-attend stays: the newest write of a
    forward of s positions overwrites position ``p + s - 1 - R``, which lies before
    the oldest key the forward's first query sees (``p - window + 1``). A chunk's
    write never crosses the ring's end (`write_ring`). A head of 64 values (half a lane
    tile) costs no padding in this order: compiled for a described v5e the chip holds
    (5, 32, 8, 16384, 64) of bf16 in 2.684 GB, its plain size, by laying each head's
    slab with the POSITIONS on the lanes (``{3,4,2,1,0:T(8,128)(2,1)}``: K-transposed,
    (64, 16384)); a head of whole lane tiles (128) it keeps as written
    (``{4,3,2,1,0}``). The logical shape is the same either way, and so are the writers
    and the plain bodies; the decode kernel and the chunk kernel read each where it lies
    (`ops/kv_decode`, `ops/kv_prefill`: a head of 64 through ``swapaxes(stack, 3, 4)``, a
    bitcast on the chip)."""

    k: jax.Array
    v: jax.Array
    wk: Optional[jax.Array] = None
    wv: Optional[jax.Array] = None
    state: Optional[object] = None  # an array, or the kind's NamedTuple of them


def ring_positions(cfg: ModelConfig, max_len: int, tokens: int = 1) -> int:
    """Positions a window layer's ring holds a row: the window plus the most
    positions ONE forward writes a row (``tokens``: a prompt chunk, a verify
    window; 1: plain decode), rounded up to a multiple of ``tokens`` so that a
    chunk that starts at a multiple of them never crosses the ring's end
    (`write_ring`); never more than the row's ``max_len`` (a ring that long is the
    whole row)."""
    tokens = max(1, int(tokens))
    ring = -(-(cfg.sliding_window_size + tokens) // tokens) * tokens
    return min(int(max_len), ring)


STACKS = ("full", "window", "state")


def stacked(cfg: ModelConfig) -> bool:
    """The stack's cache is `SlotStacks`: some layer keeps a ring or a state."""
    return mixers.cache_kind(cfg) is None and (cfg.windowed or bool(mixers.state_kinds(cfg)))


def layer_stacks(cfg: ModelConfig):
    """Of a `stacked` stack, for each layer: (the stack of `SlotStacks` it keeps its
    entries in, ``"full"`` | ``"window"`` | ``"state"``; its index within that stack)."""
    states = mixers.state_kinds(cfg)
    out, counts = [], dict.fromkeys(STACKS, 0)
    for kind, windowed in zip(cfg.kinds, cfg.window_layers):
        stack = "state" if kind in states else "window" if windowed else "full"
        out.append((stack, counts[stack]))
        counts[stack] += 1
    return out


def _state_module(cfg: ModelConfig):
    """The module of the kind whose layers keep a state in ``cfg``'s stack (the
    registry has one such kind; a second in one stack would need a stack each)."""
    kind, = mixers.state_kinds(cfg)
    return mixers.module(kind)


def stack_layers(cfg: ModelConfig) -> dict:
    """How many layers of a `stacked` stack keep each of `STACKS`."""
    counts = dict.fromkeys(STACKS, 0)
    for stack, _ in layer_stacks(cfg):
        counts[stack] += 1
    return counts


def init_kv_cache(cfg: ModelConfig, batch_size: int, max_len: int, tokens: int = 1):
    """The cache ``cfg``'s layers say: K and V for attention, the kind's own otherwise;
    `SlotStacks` where some layers have a window (their ring sized for forwards of up
    to ``tokens`` positions a row: `ring_positions`) or keep a state."""
    for limit in mixers.limits(cfg):
        # every cache of the serving stack (slots, paged pool, generate) starts here
        if limit.what == "kv_cache":
            raise ValueError(limit.sentence())
    kind = mixers.cache_kind(cfg)
    if kind is not None:
        return mixers.module(kind).init_cache(cfg, cfg.num_layers, batch_size, max_len, tokens)
    if stacked(cfg):
        layers = stack_layers(cfg)
        full = (layers["full"], batch_size, cfg.kv_heads, max_len, cfg.head_dim)
        cache = SlotStacks(jnp.zeros(full, cfg.dtype), jnp.zeros(full, cfg.dtype))
        if layers["window"]:
            ring = (layers["window"], batch_size, cfg.kv_heads,
                    ring_positions(cfg, max_len, tokens), cfg.head_dim)
            cache = cache._replace(wk=jnp.zeros(ring, cfg.dtype), wv=jnp.zeros(ring, cfg.dtype))
        if layers["state"]:
            cache = cache._replace(
                state=_state_module(cfg).init_state(cfg, layers["state"], batch_size))
        return cache
    shape = (cfg.num_layers, batch_size, max_len, cfg.kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))


def cache_layout(cfg: ModelConfig, max_len: Optional[int] = None, tokens: int = 1) -> dict:
    """What the cache ``init_kv_cache`` makes costs: ``{"kind": "kv" or the kind's
    word ("latent"), "bytes_per_position": over all layers}`` and, once ``max_len``
    is known (an engine's slots), ``bytes_per_slot``. No one number holds for a
    position of a `stacked` stack, so it has no ``bytes_per_position``: it says
    ``bytes_per_position_per_layer`` and tells its stacks apart (``full_layers`` /
    ``window_layers``, ``window``; with layers that keep a state ``state_layers`` and
    ``state_bytes_per_row``, ONE layer's); with ``max_len`` and ``tokens`` (the engine's
    prompt chunk) also ``ring_positions`` where it has a ring, and its ``bytes_per_slot``
    is the full layers over ``max_len`` plus the window layers over the ring plus the
    state layers' rows."""
    kind = mixers.cache_kind(cfg)
    if kind is not None:
        own = mixers.module(kind).cache_layout(cfg, max_len, tokens)
        if own is not None:  # (stacks of the kind's own, each with its bytes a position)
            return own
        word, per_layer = mixers.MIXERS[kind].cache, mixers.module(kind).cache_bytes_per_position(cfg)
    else:
        word, per_layer = "kv", 2 * cfg.kv_heads * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize
    if stacked(cfg):
        layers = stack_layers(cfg)
        win = layers["window"]
        out = {"kind": word, "bytes_per_position_per_layer": per_layer,
               "full_layers": layers["full"], "window_layers": win,
               "window": cfg.sliding_window_size if win else 0}
        state_bytes = 0
        if layers["state"]:
            module = _state_module(cfg)
            per_row = module.state_bytes_per_row(cfg)
            out.update(state_layers=layers["state"], state_bytes_per_row=per_row)
            if hasattr(module, "state_part_bytes"):  # (a state of several parts)
                out["state_part_bytes"] = module.state_part_bytes(cfg)
            state_bytes = layers["state"] * per_row
        if max_len is not None:
            positions = layers["full"] * max_len
            if win:
                out["ring_positions"] = ring_positions(cfg, max_len, tokens)
                positions += win * out["ring_positions"]
            out["bytes_per_slot"] = per_layer * positions + state_bytes
        return out
    out = {"kind": word, "bytes_per_position": cfg.num_layers * per_layer}
    if max_len is not None:
        out["bytes_per_slot"] = out["bytes_per_position"] * max_len
    return out


def cache_read_positions(cfg: ModelConfig, lengths, rows: int, positions: int, window: int = 1,
                         ring: Optional[int] = None):
    """Positions ONE layer's attention of a decode window (``window`` queries a row)
    fetches by construction from a cache of ``rows`` slots x ``positions``, given the
    positions the windows of the rows in use attend (``lengths``): the kind's own
    answer (host arithmetic, which body its attention takes included); of a
    `stacked` stack ``{"full": ..., "window": ...}``, a layer of each stack (0 for a
    stack it lacks; a state layer reads no position; on the plain
    body every slot's capacity, the ring's ``ring`` positions of a window layer,
    whatever the lengths; through the kernel the rows' lengths rounded up to the key
    block and no more than the slot or ring, `kv_decode.decode_path` asked of each
    stack as `_windowed_attention` asks it); None for plain K and V slots, whose
    decode attention reads every slot's capacity too (ROADMAP A3 ii)."""
    kind = mixers.cache_kind(cfg)
    if kind is None:
        if not stacked(cfg):
            return None

        def read(places):
            path = kv_decode.decode_path(places, cfg.head_dim,
                                         window * (cfg.num_heads // cfg.kv_heads), cfg.dtype)
            return (kv_decode.read_positions(lengths, rows, places) if path == "kernel"
                    else rows * places)

        return {"full": read(positions), "window": read(ring or positions) if cfg.windowed else 0}
    return mixers.module(kind).cache_read_positions(cfg, lengths, rows, positions, window)


def chunk_layout(cfg: ModelConfig, rows: int, positions: int, ring: Optional[int] = None) -> dict:
    """What joins `cache_layout` once an engine knows its prompt chunk (``rows``) and
    its slots (``positions``): which body its chunk attention takes (``chunk_path``)
    and the keys a block of it fetches (``chunk_key_block``), from the shapes. A kind
    with a cache of its own gives its own answer. A `stacked` stack asks
    `kv_prefill.chunk_path` of each stack it has, as `_windowed_attention` does
    (``"kernel"`` | ``"plain"``, ``"mixed"`` where its slots and its ring of ``ring``
    places, `ring_positions` of the chunk if None, disagree); nothing for a plain
    `KVCache`, whose chunk attention is ``modeling.attention_xla``."""
    kind = mixers.cache_kind(cfg)
    if kind is not None:
        return mixers.module(kind).chunk_layout(cfg, rows, positions)
    if not stacked(cfg):
        return {}
    layers = stack_layers(cfg)
    places = ([positions] * bool(layers["full"])
              + [ring or ring_positions(cfg, positions, rows)] * bool(layers["window"]))
    paths = {kv_prefill.chunk_path(n, cfg.head_dim, rows, cfg.dtype) for n in places}
    path = paths.pop() if len(paths) == 1 else "mixed"
    block = kv_prefill.KEY_BLOCK if path == "kernel" else modeling.key_block(positions, KEY_BLOCK)
    return {"chunk_path": path, "chunk_key_block": block}


def _positions(offsets, s: int):
    """Absolute positions of ``s`` new tokens: (s,) for a scalar offset (the
    same for every row), (B, s) for a (B,) one."""
    return jnp.asarray(offsets)[..., None] + jnp.arange(s)


def _rope_at(cfg: ModelConfig, smax: int, offsets, s: int):
    """(cos, sin) for the ``s`` tokens at ``offsets``: full-length tables
    indexed dynamically, so ``offsets`` can be traced. A scalar offset slices
    (s, hd/2) for every row; a (B,) offset gathers per-row (B, s, hd/2)."""
    if cfg.pos_embed != "rope":
        return None
    cos_all, sin_all = modeling.rope_tables(cfg, smax)
    if jnp.ndim(offsets) == 0:
        return (jax.lax.dynamic_slice_in_dim(cos_all, offsets, s, axis=0),
                jax.lax.dynamic_slice_in_dim(sin_all, offsets, s, axis=0))
    pos = _positions(offsets, s)
    return cos_all[pos], sin_all[pos]


def _alibi_bias(cfg: ModelConfig, smax: int, offsets, s: int):
    """ALiBi over absolute positions: (1, nh, s, Smax) for a scalar offset,
    (B, nh, s, Smax) per row for a (B,) one; None for any other embedding."""
    if cfg.pos_embed != "alibi":
        return None
    slopes = jnp.asarray(modeling.alibi_slopes(cfg.num_heads))
    rel = jnp.arange(smax) - _positions(offsets, s)[..., None]  # ([B,] s, Smax)
    bias = (slopes[:, None, None] * rel[..., None, :, :]).astype(jnp.float32)
    return bias.reshape((-1,) + bias.shape[-3:])


def _window_starts(offsets, slot, b: int):
    """Where the new tokens land in the cache, as (row, position) pairs: one
    pair for a scalar offset (rows [row, row + B) all at that position),
    one a row for a (B,) offset."""
    if jnp.ndim(offsets) == 0:
        return [(0 if slot is None else slot, offsets)]
    return [(r, offsets[r]) for r in range(b)]


def write_layer(stacked, layer: int, new, starts, axis: int = 2):
    """Write layer ``layer``'s new entries ``new`` (B, s, ...) (keys or values
    (B, s, kvh, hd); a latent (B, s, r + dr)) into the stacked cache (L, Bc, Smax,
    ...) at ``starts`` (``_window_starts``), in place. ``axis``: where the stacked
    array has its positions (``new`` has them one axis earlier): 2, or 3 in a
    head-major stack (L, Bc, kvh, Smax, hd) with ``new`` (B, kvh, s, hd).

    Only ``lax.dynamic_update_slice`` on the stacked array itself at a static
    layer index keeps a donated cache where it is: slicing the layer's slab
    out and re-stacking copies the slab both ways (and into another layout),
    a vmapped update becomes a pass over the whole slab, and
    ``stacked.at[layer, rows, pos].set`` copies the WHOLE cache (compiled for
    a v5e at opt-1.3b widths, 8 slots x 2048: 5.4 GiB of temporaries, and 6.0
    with the scatter, against 0.2). So rows with their own offsets take one
    update each: ``B`` is static and small."""
    new = new.astype(stacked.dtype)[None]  # (1, B, s, ...)
    if len(starts) == 1:
        (row, pos), = starts
        return jax.lax.dynamic_update_slice(stacked, new, _at(stacked, layer, row, pos, axis))
    for row, pos in starts:
        stacked = jax.lax.dynamic_update_slice(
            stacked, jax.lax.slice_in_dim(new, row, row + 1, axis=1),
            _at(stacked, layer, row, pos, axis))
    return stacked


def _at(stacked, layer, row, pos, axis: int):
    """The start indices of (layer, row) at position ``pos`` along ``axis``."""
    at = [layer, row] + [0] * (stacked.ndim - 2)
    at[axis] = pos
    return tuple(at)


def read_layer(stacked, layer: int, slot):
    """Layer ``layer``'s entries for attention: every row of the cache, or the
    one row ``slot`` (traced) as a batch of one."""
    if slot is None:
        return stacked[layer]
    return jax.lax.dynamic_slice(
        stacked, (layer, slot) + (0,) * (stacked.ndim - 2), (1, 1) + stacked.shape[2:])[0]


# The cached forwards carry the training forward's scope names (PERF.md
# section 3: ``embed``; ``layer_<i>`` > ``attn`` > ``qkv_proj``, ``attn_core``,
# ``out_proj``; ``mlp`` and ``norm`` through ``modeling``; ``head``) and one of
# their own, ``cache_write``, so a device trace of a serving step reads by the
# same names. Scopes are metadata: no operation is added.


@jax.named_scope("embed")
def _embed_at(params: Params, tokens, cfg: ModelConfig, offsets):
    """Token embeddings of ``tokens`` (B, s), plus the learned positions at
    ``offsets`` where the model has them."""
    x = params["embed"]["tok"].astype(cfg.dtype)[tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    if cfg.pos_embed == "learned":
        x = x + params["embed"]["pos"].astype(cfg.dtype)[_positions(offsets, tokens.shape[1])]
    return x


@jax.named_scope("qkv_proj")
def _project_qkv_at(x, p, cfg: ModelConfig, cos_sin):
    """A layer's pre-norm, q/k/v projection and RoPE at the new positions."""
    q, k, v = modeling.project_qkv_heads(modeling.norm(x, p["attn_norm"], cfg), p["attn"], cfg)
    if cos_sin is not None:
        q = modeling.apply_rope(q, *cos_sin)
        k = modeling.apply_rope(k, *cos_sin)
    return q, k, v


@jax.named_scope("qkv_proj")
def _project_gate_at(x, p, cfg: ModelConfig):
    """A layer's output gate from the same normed input (``modeling.project_gate``);
    None where the block has none."""
    if not cfg.attn_gate:
        return None
    return modeling.project_gate(modeling.norm(x, p["attn_norm"], cfg), p["attn"], cfg)


# -- a stack whose layers keep whole slots, a ring or a state: `SlotStacks` ----------

#: keys a step of a prompt chunk's attention takes in a windowed stack
KEY_BLOCK = 1024


def chunk_key_blocks(positions: int, end):
    """Of a row of ``positions`` places that a prompt chunk ending at position ``end``
    (traced, or a host number) attends a block of keys at a time: (the block, the
    row's blocks, the blocks up to the chunk's end). The chunk reads the SMALLER
    count: a full row up to the chunk's end; a ring the same until it has lapped
    (``end`` > R), because the places at or past ``end`` then still hold what an
    earlier request left, `_ring_key_positions` reads them as negative positions and
    a block of masked scores adds exact zeros to the running sum."""
    block = modeling.key_block(positions, KEY_BLOCK)
    return block, positions // block, (end + block - 1) // block


def write_ring(stacked, layer: int, new, starts, aligned: bool, axis: int = 3):
    """`write_layer` into a head-major ring (L, Bc, kvh, R, hd): position p of
    ``new`` (B, kvh, s, hd) lands at ``p mod R``, by ``dynamic_update_slice`` on the
    stacked array alone (`write_layer`'s rule); ``axis`` 2: a ring without heads (L, Bc,
    R, width), ``new`` (B, s, width): a latent's. An update cannot wrap (XLA clamps
    its start, and the entries then lie where the mask reads other positions), so:

    - ``aligned`` (a SCALAR offset: a prompt chunk, ``generate``'s prefill) is one
      update of s positions at ``offset mod R``, and the CALLER keeps it from crossing
      the ring's end: ``R`` is a multiple of the forwards' positions
      (`ring_positions`) and a chunk starts at a multiple of them, as the engine's do;
    - an offset a row with s > 1 (a verify window, anywhere; ONE row too: an engine
      of one slot) is one update a position.

    (A crossing chunk as two read-merge-write updates of s entries was tried: the
    chip's compiler then re-laid the WHOLE ring stack to the new entries' order and
    back, 4 copies of 1.9 GiB a chunk at the cell's size, compiled for a described
    v5e; plain updates leave the stack where it is.)"""
    ring, s = stacked.shape[axis], new.shape[axis - 1]
    if s > ring:
        raise ValueError(f"a forward of {s} positions does not fit a ring of {ring}")
    if s == 1 or aligned:
        return write_layer(stacked, layer, new, [(row, pos % ring) for row, pos in starts],
                           axis=axis)
    for i in range(s):
        stacked = write_layer(stacked, layer, jax.lax.slice_in_dim(new, i, i + 1, axis=axis - 1),
                              [(row, (pos + i) % ring) for row, pos in starts], axis=axis)
    return stacked


def _ring_key_positions(last, slots, ring: int):
    """The absolute position ring place ``slots`` (K,) holds for a row whose newest
    write is position ``last`` (B | 1,): the largest p <= last with p mod R = place
    -> (B | 1, K). Negative: the row has not come that far, nothing to see there."""
    last = jnp.reshape(jnp.asarray(last, jnp.int32), (-1, 1))
    return last - jnp.remainder(last - slots[None].astype(jnp.int32), ring)


def _masked_scores(qg, k, q_pos, k_pos, window: int, scale):
    """Grouped queries ``qg`` (B, s, kv, g, d) against keys ``k`` (B, kv, K, d) ->
    float32 scores (B, kv, g, s, K), scaled, and masked by ABSOLUTE positions:
    query at ``q_pos`` (B | 1, s) sees the key at ``k_pos`` (B | 1, K) where
    ``0 <= k_pos <= q_pos`` and, with a window, ``k_pos > q_pos - window``. No
    key/value head is repeated (``decode_attention``'s grouping, kv-major)."""
    scores = jnp.einsum("bqkgh,bksh->bkgqs", qg, k, preferred_element_type=jnp.float32) * scale
    kp, qp = k_pos[:, None, :], q_pos[:, :, None]
    allowed = (kp <= qp) & (kp >= 0)
    if window:
        allowed = allowed & (kp > qp - window)
    return jnp.where(allowed[:, None, None], scores, modeling.MASKED_SCORE)


def _attend_rows(qg, k, v, q_pos, k_pos, window: int, scale):
    """Rows' windows against their whole rows of the cache at once (a decode step,
    a verify window, ``generate``): -> (B, s, kv, g, d). The plain body, outside
    `kv_decode.decode_path`'s rule, and the kernel's reference."""
    probs = jax.nn.softmax(_masked_scores(qg, k, q_pos, k_pos, window, scale), axis=-1)
    return jnp.einsum("bkgqs,bksh->bqkgh", probs.astype(qg.dtype), v)


def _attend_chunk(qg, ks, vs, layer: int, slot, q_pos, key_positions, blocks, block: int,
                  window: int, scale):
    """One request's prompt chunk against row ``slot`` of the stacked cache, ``block``
    keys at a time with a running softmax, so that no more than (heads, s, block)
    float32 scores live at once (28 x 1,024 x 16,384 x 4 B = 1.9 GB a full layer
    otherwise). ``key_positions(places)``: the absolute positions the places hold;
    ``blocks`` may be traced (a full layer stops at the chunk's end). The plain body,
    outside `kv_prefill.chunk_path`'s rule, and the kernel's reference."""
    b, s, kv, g, d = qg.shape
    shape = (1, 1, kv, block, d)

    def scored(j):
        k = jax.lax.dynamic_slice(ks, (layer, slot, 0, j * block, 0), shape)[0]
        v = jax.lax.dynamic_slice(vs, (layer, slot, 0, j * block, 0), shape)[0]
        scores = _masked_scores(qg, k, q_pos, key_positions(j * block + jnp.arange(block)),
                                window, scale)
        return scores, lambda e: jnp.einsum(
            "bkgqs,bksh->bkgqh", e.astype(qg.dtype), v, preferred_element_type=jnp.float32)

    o = modeling.running_softmax(blocks, scored, (b, kv, g, s), d)
    return jnp.transpose(o, (0, 3, 1, 2, 4)).astype(qg.dtype)


def _windowed_attention(x, p, cfg: ModelConfig, cache: SlotStacks, windowed: bool, index: int,
                        starts, slot, offsets, cos_sin):
    """An attention layer of a `stacked` stack over `SlotStacks` -> (y, cache):
    ``windowed`` says which stack the layer's keys and values live in (the ring, or
    whole rows), ``index`` where in it; ``cfg`` is the layer's view. Both forms fetch
    a key block only if the row holds a position in it: a prompt chunk (``slot``)
    takes the blocks up to its end (`chunk_key_blocks`), through the kernel `kv_chunk`
    where `kv_prefill.chunk_path` says so of the layer's stack, else in a loop
    (`_attend_chunk`); rows' windows go
    through the kernel `kv_decode`, bounded a row by the row's length, where
    `kv_decode.decode_path` says so of the layer's stack, else over every slot's
    capacity (`_attend_rows`). A layer with an output gate (``cfg.attn_gate``)
    projects it beside q, k and v and applies it between the core and the output
    projection, in both stacks and both forms. Scopes: ``window`` | ``full`` >
    ``qkv_proj``, ``cache_write``, ``attn_core``, ``gate``, ``out_proj``."""
    b, s = x.shape[:2]
    kv, g, d = cfg.kv_heads, cfg.num_heads // cfg.kv_heads, cfg.head_dim
    scale = cfg.attention_multiplier if cfg.attention_multiplier is not None else d ** -0.5
    ks, vs = (cache.wk, cache.wv) if windowed else (cache.k, cache.v)
    positions = ks.shape[3]
    with jax.named_scope("window" if windowed else "full"):
        q, k, v = _project_qkv_at(x, p, cfg, cos_sin if cfg.pos_embed == "rope" else None)
        gate = _project_gate_at(x, p, cfg)
        with jax.named_scope("cache_write"):
            write = (partial(write_ring, aligned=jnp.ndim(offsets) == 0) if windowed
                     else partial(write_layer, axis=3))
            k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)  # (B, kvh, s, hd): head-major
            ks, vs = write(ks, index, k, starts), write(vs, index, v, starts)
        with jax.named_scope("attn_core"):
            qg = q.reshape(b, s, kv, g, d)
            q_pos = jnp.reshape(_positions(offsets, s), (-1, s))
            last = jnp.reshape(jnp.asarray(offsets), (-1,)) + s - 1
            if windowed:
                def key_positions(places):
                    return _ring_key_positions(last, places, positions)
            else:
                def key_positions(places):
                    return places[None]
            if slot is not None and kv_prefill.chunk_path(positions, d, s, ks.dtype) == "kernel":
                # the same key blocks as the plain body's, their scores kept on the chip
                o = kv_prefill.attend_chunk(qg, ks, vs, index, slot, offsets, scale=scale,
                                            span=cfg.attn_window)
            elif slot is not None:
                block, whole, live = chunk_key_blocks(positions, offsets + s)
                o = _attend_chunk(qg, ks, vs, index, slot, q_pos, key_positions,
                                  jnp.minimum(whole, live), block, cfg.attn_window, scale)
            elif kv_decode.decode_path(positions, d, s * g, ks.dtype) == "kernel":
                # a row is read up to its length, a ring up to that until it has lapped
                first = jnp.broadcast_to(jnp.reshape(jnp.asarray(offsets, jnp.int32), (-1,)), (b,))
                o = kv_decode.attend_rows(qg, ks, vs, index, first, scale=scale,
                                          span=cfg.attn_window)
            else:
                o = _attend_rows(qg, read_layer(ks, index, None), read_layer(vs, index, None),
                                 q_pos, key_positions(jnp.arange(positions)), cfg.attn_window,
                                 scale)
        o = modeling.gate_output(o.reshape(b, s, kv * g, d), gate)
        with jax.named_scope("out_proj"):
            y = modeling.attn_output(o, p["attn"], cfg, x.dtype)
    cache = cache._replace(wk=ks, wv=vs) if windowed else cache._replace(k=ks, v=vs)
    return y, cache


@jax.named_scope("head")
def _head(x, params: Params, cfg: ModelConfig):
    return modeling.lm_head(modeling.norm(x, params["final_norm"], cfg), params, cfg)


def _mlp_at(x, p, cfg: ModelConfig, moe_stats: Optional[list], router_x=None):
    """A layer's pre-norm and MLP; a dropless expert layer's router statistics go
    into ``moe_stats`` where the caller keeps them; ``router_x``: what such a
    layer's router reads where that is not the block's own normed input."""
    normed = modeling.norm(x, p["mlp_norm"], cfg)
    if not (cfg.moe_dropless and "router" in p["mlp"]):
        return modeling.mlp_block(normed, p["mlp"], cfg, train=False)
    from galvatron_tpu.models import moe

    with jax.named_scope("mlp"):
        # (a cached forward is never differentiated: `moe_topk_block`)
        y, stats = moe.moe_topk_block(normed, p["mlp"], cfg, router_x=router_x,
                                      forward_only=True)
    if moe_stats is not None:
        moe_stats.append(stats)
    return y


def forward_with_cache(params: Params, tokens, cfg: ModelConfig, cache, offsets, slot=None,
                       moe_stats: Optional[list] = None, last=None):
    """Run ``tokens`` (B, s) through the model at absolute positions
    ``offsets``, writing the new keys and values (a latent-attention stack: the
    new latents) into the cache and attending over it. Returns (logits,
    new_cache). ``moe_stats``: a list that takes the router's statistics of every
    dropless expert layer (``moe.router_stats``). ``offsets`` may be traced:

    - a scalar: every row at the same position (``generate``'s lockstep scan);
      with ``slot`` (a traced scalar) ``tokens`` is (1, s) and lands in row
      ``slot`` of a cache of many rows: one prefill chunk of one request;
    - a (B,) vector: row ``b`` at ``offsets[b]``, the forward the
      continuous-batching engine runs once per decode iteration over all slots
      (and over its (B, 1+k) verify window). Rows are independent requests at
      arbitrary depths; a row holding no request carries (0, 0): its write
      lands at position 0 of its own row and is overwritten by the next
      prefill before any query can attend it, since causal masking keeps
      positions past a row's own offset invisible.

    The stacked cache (every array (L, B, Smax, ...)) is carried whole through
    the layers and written in place (``write_layer``), write-then-attend; a
    window that would cross the row's end is CLAMPED back by the update, so
    callers keep ``offset + s <= Smax``. A `stacked` stack (sliding-window layers,
    layers that keep a state) carries `SlotStacks` and maps each layer to its stack
    (`layer_stacks`); ``s`` is then at most what a ring was sized for
    (``init_kv_cache``'s ``tokens``). ``last`` (traced; None: ``s - 1``) is the
    forward's last REAL row, as of which a state layer's state is written: a prompt
    chunk padded at its tail hands over where its tokens end; a state layer takes no
    window of ``s`` > 1 at per-row offsets (speculation: `mixers.limits`)."""
    s = tokens.shape[1]
    last = s - 1 if last is None else last
    smax = cache[0].shape[3 if isinstance(cache, SlotStacks) else 2]
    cos_sin = _rope_at(cfg, smax, offsets, s)
    bias = _alibi_bias(cfg, smax, offsets, s)
    x = _embed_at(params, tokens, cfg, offsets)
    starts = _window_starts(offsets, slot, tokens.shape[0])
    kind = mixers.cache_kind(cfg)
    by_stack = stacked(cfg)
    if by_stack or (kind is not None and cfg.windowed):
        stacks = layer_stacks(cfg)  # (a kind's own stacks too: a layer's place in its own)
    elif kind is None:
        ks, vs = cache
    tables = {cfg.rope_theta: cos_sin}  # (a window layer of another rotary base: its own)
    for i, p in enumerate(params["layers"]):
        with jax.named_scope(f"layer_{i}"):
            # (the router of such a layer reads what its attention block reads)
            router_x = (modeling.norm(x, p["attn_norm"], cfg)
                        if cfg.moe_router_input == "attn" and cfg.moe_dropless else None)
            with jax.named_scope("attn"):
                if by_stack and stacks[i][0] == "state":
                    # (built from the training layer's pieces: the kind's own gates,
                    # conv and projections, the same residual)
                    y, state = mixers.module(cfg.kinds[i]).cached_block(
                        modeling.norm(x, p["attn_norm"], cfg), p[cfg.kinds[i]], cfg,
                        cache.state, stacks[i][1], slot, offsets, last)
                    cache = cache._replace(state=state)
                    x = modeling.residual_add(
                        x, modeling.post_norm(y, p, "post_attn_norm", cfg), cfg)
                elif by_stack:
                    y, cache = _windowed_attention(
                        x, p, cfg.layer_view(i), cache, stacks[i][0] == "window",
                        stacks[i][1], starts, slot, offsets, cos_sin)
                    x = modeling.residual_add(
                        x, modeling.post_norm(y, p, "post_attn_norm", cfg), cfg)
                elif kind is not None:
                    # (under the layer's view; a window layer of other sizes: its own
                    # rotary table and its place in the ring's stack)
                    view = cfg.layer_view(i)
                    if view.rope_theta not in tables:
                        tables[view.rope_theta] = _rope_at(view, smax, offsets, s)
                    y, cache = mixers.module(kind).cached_block(
                        modeling.norm(x, p["attn_norm"], cfg), p[kind], view, cache,
                        stacks[i][1] if cfg.windowed else i, starts, slot, offsets,
                        tables[view.rope_theta])
                    x = modeling.residual_add(
                        x, modeling.post_norm(y, p, "post_attn_norm", cfg), cfg)
                else:
                    q, k, v = _project_qkv_at(x, p, cfg, cos_sin)
                    gate = _project_gate_at(x, p, cfg)
                    with jax.named_scope("cache_write"):
                        ks = write_layer(ks, i, k, starts)
                        vs = write_layer(vs, i, v, starts)
                    with jax.named_scope("attn_core"):
                        o = modeling.attention_xla(
                            q, read_layer(ks, i, slot), read_layer(vs, i, slot), cfg,
                            bias=bias, q_offset=offsets)
                    o = modeling.gate_output(o, gate)
                    with jax.named_scope("out_proj"):
                        y = modeling.attn_output(o, p["attn"], cfg, x.dtype)
                    x = modeling.residual_add(
                        x, modeling.post_norm(y, p, "post_attn_norm", cfg), cfg)
            if "mlp" in p:  # (a layer of its mixer alone has none: ``mlp_layout``)
                x = modeling.residual_add(x, modeling.post_norm(
                    _mlp_at(x, p, cfg, moe_stats, router_x), p, "post_mlp_norm", cfg), cfg)
    return _head(x, params, cfg), (cache if kind is not None or by_stack else KVCache(ks, vs))


# ---------------------------------------------------------------------------
# Paged forward: K/V live in a shared block pool, addressed via block tables
# (serving/paged_kv.py owns the pool and the host-side allocator)
# ---------------------------------------------------------------------------


def _layer_with_cache_paged(x, p, cfg: ModelConfig, pool_k, pool_v, tables,
                            offsets, cos_sin, bias):
    """One decoder layer over a paged pool: ``pool_k``/``pool_v`` are
    (num_blocks, block_size, kvh, hd), ``tables`` is (B, max_blocks) int32
    and row ``b``'s logical position ``p`` lives at
    ``(tables[b, p // bs], p % bs)``. Returns (x, pool_k, pool_v)."""
    from galvatron_tpu.ops import flash_attention

    b, s, h = x.shape
    bs = pool_k.shape[1]
    smax = tables.shape[1] * bs
    with jax.named_scope("attn"):
        q, k, v = _project_qkv_at(x, p, cfg, cos_sin)
        gate = _project_gate_at(x, p, cfg)
        with jax.named_scope("cache_write"):
            # scatter the new k/v through the table (duplicate targets only
            # arise on the null block, whose contents are never attended)
            pos = offsets[:, None] + jnp.arange(s)[None]  # (B, s)
            blk = jnp.take_along_axis(tables, pos // bs, axis=1)  # (B, s)
            sub = pos % bs
            pool_k = pool_k.at[blk, sub].set(k.astype(pool_k.dtype))
            pool_v = pool_v.at[blk, sub].set(v.astype(pool_v.dtype))
        with jax.named_scope("attn_core"):
            if s == 1 and bias is None and cfg.causal:
                # decode step: paged attention reads pages through the table (XLA
                # gather fallback is bit-identical to the slot engine's decode core)
                o = flash_attention.paged_decode_attention(q, pool_k, pool_v, tables, offsets)
            else:
                # prefill chunk (or bias'd attention): materialize the row's context
                # contiguously and reuse the slot attention core unchanged
                k_ctx = pool_k[tables].reshape(b, smax, *pool_k.shape[2:])
                v_ctx = pool_v[tables].reshape(b, smax, *pool_v.shape[2:])
                o = modeling.attention_xla(q, k_ctx, v_ctx, cfg, bias=bias, q_offset=offsets)
        o = modeling.gate_output(o, gate)
        with jax.named_scope("out_proj"):
            y = modeling.attn_output(o, p["attn"], cfg, x.dtype)
        x = modeling.residual_add(x, modeling.post_norm(y, p, "post_attn_norm", cfg), cfg)
    y = modeling.mlp_block(modeling.norm(x, p["mlp_norm"], cfg), p["mlp"], cfg, train=False)
    return (modeling.residual_add(x, modeling.post_norm(y, p, "post_mlp_norm", cfg), cfg),
            pool_k, pool_v)


def forward_with_cache_paged(params: Params, tokens, cfg: ModelConfig,
                             pool: KVCache, tables, offsets):
    """Run ``tokens`` (B, s) through the model with PER-ROW positions
    ``offsets`` (B,), reading/writing K/V through ``tables`` (B, max_blocks)
    into the shared block ``pool`` (L, num_blocks, block_size, kvh, hd).
    Returns (logits, new_pool). ``tables`` and ``offsets`` may be traced —
    both are fixed-shape operands, so the compiled program is reused across
    every allocation pattern the host-side allocator produces.

    Numerics match :func:`forward_with_cache` at per-row offsets bit-for-bit when
    ``block_size * max_blocks`` equals the slot cache's max_seq_len: per-row
    rope tables, scatter-then-attend ordering and the decode attention core
    are all shared, only the K/V addressing differs (the paged/slot parity
    tests pin this)."""
    b, s = tokens.shape
    smax = tables.shape[1] * pool.k.shape[2]
    cos_sin = _rope_at(cfg, smax, offsets, s)
    bias = _alibi_bias(cfg, smax, offsets, s)
    x = _embed_at(params, tokens, cfg, offsets)
    new_k, new_v = [], []
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope(f"layer_{i}"):
            x, ki, vi = _layer_with_cache_paged(
                x, lp, cfg, pool.k[i], pool.v[i], tables, offsets, cos_sin, bias
            )
        new_k.append(ki)
        new_v.append(vi)
    return _head(x, params, cfg), KVCache(jnp.stack(new_k), jnp.stack(new_v))


# ---------------------------------------------------------------------------
# Sampling (reference: megatron/text_generation/sampling.py modify_logits_for_
# top_k_filtering / top_p_filtering + sample)
# ---------------------------------------------------------------------------


def sample_logits(key, logits, temperature=1.0, top_k: int = 0, top_p=0.0,
                  use_top_p: Optional[bool] = None):
    """logits: (B, V) → token ids (B,). temperature 0 (or <0) → greedy.

    ``temperature`` and ``top_p`` may be traced values — under jit, varying
    them does NOT recompile. ``top_k`` must be static (lax.top_k needs a
    concrete k), as must ``use_top_p``, the gate that includes the nucleus
    sort in the program (defaults from ``top_p`` when that is concrete)."""
    if use_top_p is None:
        use_top_p = (not isinstance(top_p, (int, float))) or top_p > 0
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1)
    t = jnp.asarray(temperature, jnp.float32)
    scaled = logits / jnp.where(t > 0, t, 1.0)
    if top_k > 0:
        kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    if use_top_p:
        p = jnp.asarray(top_p, jnp.float32)
        sorted_logits = jnp.sort(scaled, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob >= top_p (always >= 1 tok)
        cutoff_mask = cum - probs < p
        threshold = jnp.min(jnp.where(cutoff_mask, sorted_logits, jnp.inf), axis=-1, keepdims=True)
        scaled = jnp.where((p > 0) & (scaled < threshold), -jnp.inf, scaled)
    sampled = jax.random.categorical(key, scaled, axis=-1)
    return jnp.where(t <= 0, greedy, sampled)


def host_probs(logits, temperature: float, top_k: int, top_p: float):
    """Host-side (numpy, float64) mirror of :func:`sample_logits`'s
    processed distribution over ONE position: temperature scaling, top-k
    filter, nucleus cutoff (smallest prefix with cumulative prob >= top_p,
    always >= 1 token) → normalized probabilities (V,).

    Shared by the serving engine's per-slot sampler and the speculative
    verifier's acceptance test — both must score tokens under the SAME
    distribution the sampler draws from, or rejection sampling stops being
    exact. Greedy (temperature <= 0) returns a one-hot at the argmax.
    """
    logits = np.asarray(logits, np.float64)
    p = np.zeros_like(logits)
    if temperature <= 0:
        p[np.argmax(logits)] = 1.0
        return p
    scaled = logits / temperature
    if top_k > 0:
        kth = np.sort(scaled)[-min(top_k, len(scaled))]
        scaled = np.where(scaled < kth, -np.inf, scaled)
    if top_p > 0:
        sorted_logits = np.sort(scaled)[::-1]
        shifted = sorted_logits - sorted_logits[0]
        probs = np.exp(shifted) / np.exp(shifted).sum()
        cum = np.cumsum(probs)
        keep = cum - probs < top_p
        threshold = sorted_logits[keep].min()
        scaled = np.where(scaled < threshold, -np.inf, scaled)
    shifted = scaled - scaled.max()
    p = np.exp(shifted)
    return p / p.sum()


# The serving engine's draw: :func:`host_probs`'s distribution for MANY rows at
# once on the device, every parameter a per-row array, so one compiled program
# serves any mix of greedy, top-k and nucleus requests. No sort: both cuts are
# "the largest threshold t with weight(row >= t) >= target" (a count for top-k,
# probability mass for the nucleus), a quantity that falls monotonically with
# t, found by 32 bisection steps over the float32 order, one masked sum over
# the rows each.


def _order_keys(x):
    """float32 (no NaN) -> uint32 with the same order; equal floats give equal
    keys (``+ 0.0`` folds -0.0 into +0.0)."""
    bits = jax.lax.bitcast_convert_type(x + 0.0, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000))


def _largest_threshold(keys, weight, target, wanted):
    """Per row the largest uint32 ``t`` with ``sum(weight[keys >= t]) >=
    target`` (the sum falls as ``t`` rises; it holds at ``t`` = 0), built bit
    by bit from the top; 0 (keep everything) for a row not ``wanted``, and no
    pass at all when no row wants one."""
    top = jnp.uint32(0x80000000)

    def step(i, t):
        cand = t | (top >> i.astype(jnp.uint32))
        got = jnp.sum(jnp.where(keys >= cand[:, None], weight, 0), axis=-1)
        return jnp.where(got >= target, cand, t)

    steps = jnp.where(jnp.any(wanted), 32, 0)
    t = jax.lax.fori_loop(0, steps, step, jnp.zeros(keys.shape[:1], jnp.uint32))
    return jnp.where(wanted, t, jnp.uint32(0))


def kept_rows(logits, temperature, top_k, top_p):
    """(scaled, keep) for rows ``logits`` (B, V) under per-row ``temperature``,
    ``top_k``, ``top_p`` (B,): ``scaled`` the float32 logits over the
    temperature (over 1 for a greedy row), ``keep`` the support of
    :func:`host_probs`: the ``top_k`` largest (ties with the k-th kept), then
    of those every token whose strictly larger tokens hold less than ``top_p``
    of the mass (so at least one, ties at the cut kept). ``top_k`` <= 0 and
    ``top_p`` outside (0, 1) switch a cut off, row by row."""
    t = temperature.astype(jnp.float32)[:, None]
    scaled = logits.astype(jnp.float32) / jnp.where(t > 0, t, 1.0)
    keys = _order_keys(scaled)
    k = jnp.minimum(top_k, logits.shape[-1]).astype(jnp.int32)
    cut = _largest_threshold(keys, jnp.int32(1), k, top_k > 0)
    mass = jnp.where(keys >= cut[:, None],
                     jnp.exp(scaled - jnp.max(scaled, axis=-1, keepdims=True)), 0.0)
    p = top_p.astype(jnp.float32)
    nucleus = _largest_threshold(keys, mass, p * jnp.sum(mass, axis=-1), (p > 0) & (p < 1))
    return scaled, keys >= jnp.maximum(cut, nucleus)[:, None]


@jax.named_scope("sample")
def draw_rows(logits, temperature, top_k, top_p, seed, rid, index):
    """One token a row of ``logits`` (B, V) -> (B,) int32, each row under its
    own ``temperature`` / ``top_k`` / ``top_p`` (B,): the argmax for
    ``temperature`` <= 0, else a draw from :func:`host_probs`'s distribution
    (:func:`kept_rows`, Gumbel-max over the kept tokens). The randomness is made
    here from integers: ``seed`` (2,) uint32 words, and per row ``rid`` and
    ``index`` (the request's id and which of its tokens this is), so a row's
    token depends on (seed, rid, index) and its logits alone, not on its
    neighbours or its place in the batch."""
    scaled, keep = kept_rows(logits, temperature, top_k, top_p)
    base = jax.random.wrap_key_data(seed.astype(jnp.uint32), impl="threefry2x32")
    keys = jax.vmap(lambda r, i: jax.random.fold_in(jax.random.fold_in(base, r), i))(rid, index)
    noise = jax.vmap(lambda key: jax.random.gumbel(key, logits.shape[-1:], jnp.float32))(keys)
    drawn = jnp.argmax(jnp.where(keep, scaled + noise, -jnp.inf), axis=-1)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temperature > 0, drawn, greedy).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Generation loop
# ---------------------------------------------------------------------------


@partial(
    jax.jit,
    static_argnames=(
        "cfg",
        "max_new_tokens",
        "min_prompt_len",
        "top_k",
        "use_top_p",
        "eos_id",
        "pad_id",
    ),
)
def generate(
    params: Params,
    prompt: jax.Array,  # (B, P) int32, right-padded with pad_id
    prompt_lengths: jax.Array,  # (B,) true lengths
    cfg: ModelConfig,
    key: jax.Array,
    max_new_tokens: int = 32,
    min_prompt_len: Optional[int] = None,  # static int(prompt_lengths.min())
    temperature=0.0,  # traced: varying it does not recompile
    top_k: int = 0,
    top_p=0.0,  # traced; use_top_p gates the nucleus sort into the program
    use_top_p: bool = False,
    eos_id: int = -1,
    pad_id: int = 0,
) -> jax.Array:
    """Prefill + lockstep scan decode (the reference's scheme: right-padded
    prompts, generation starts at min(context_length), prompt tokens override
    sampled ones until each row's own prompt is exhausted — megatron/
    text_generation/generation.py generate_tokens_probs_and_return_on_first_
    stage). Returns (B, P + max_new_tokens); positions past a row's eos are
    ``pad_id``."""
    if not cfg.causal or cfg.objective != "clm" or cfg.enc_layers > 0:
        raise ValueError(
            "generation requires a decoder-only causal LM (encoder families "
            "train with objective='mlm'; enc-dec decode is not implemented)"
        )
    b, p_len = prompt.shape
    if min_prompt_len is None:
        min_prompt_len = p_len
    max_len = p_len + max_new_tokens
    cache = init_kv_cache(cfg, b, max_len, tokens=min_prompt_len)

    # prefill positions [0, min_prompt_len); all rows have real tokens there
    logits, cache = forward_with_cache(
        params, prompt[:, :min_prompt_len], cfg, cache, 0
    )
    last = logits[:, -1]  # (B, V) — logits at position min_prompt_len-1

    out = jnp.concatenate(
        [prompt, jnp.full((b, max_new_tokens), pad_id, jnp.int32)], axis=1
    )

    def step(carry, i):
        cache, last, key, done, out = carry
        key, sub = jax.random.split(key)
        sampled = sample_logits(
            sub, last, temperature, top_k, top_p, use_top_p=use_top_p
        ).astype(jnp.int32)
        in_prompt = i < prompt_lengths  # (B,) teacher-force rows still in prompt
        tok = jnp.where(in_prompt, out[:, i], jnp.where(done, pad_id, sampled))
        done = done | (~in_prompt & (tok == eos_id))
        out = jax.lax.dynamic_update_slice(out, tok[:, None], (0, i))

        def do_fwd(cache):  # predict position i+1
            logits, cache = forward_with_cache(params, tok[:, None], cfg, cache, i)
            return logits[:, 0], cache

        def skip_fwd(cache):  # last step: nothing left to predict
            return last, cache

        last2, cache = jax.lax.cond(i < max_len - 1, do_fwd, skip_fwd, cache)
        return (cache, last2, key, done, out), None

    done = jnp.zeros((b,), bool)
    steps = jnp.arange(min_prompt_len, max_len)
    carry = (cache, last, key, done, out)
    (cache, _, _, _, out), _ = jax.lax.scan(step, carry, steps)
    return out


def generate_np(params, cfg: ModelConfig, prompts, length_bucket: int = 64, **kw):
    """Host-side convenience: list of variable-length token lists → padded
    arrays → ``generate`` → list of token lists (stopping at eos).

    Prompt length is padded UP and min_prompt_len rounded DOWN to multiples of
    ``length_bucket`` so repeat calls with naturally varying prompt lengths
    hit the jit cache instead of recompiling per length."""
    lengths = np.asarray([len(p) for p in prompts], np.int32)
    if int(lengths.min()) < 1:
        raise ValueError("empty prompt")
    max_new = kw.get("max_new_tokens", 32)
    p_raw = int(lengths.max())
    if p_raw + max_new > cfg.max_seq_len:
        raise ValueError(
            f"prompt ({p_raw}) + max_new_tokens ({max_new}) exceeds "
            f"max_seq_len {cfg.max_seq_len}"
        )
    # pad up to the bucket when the seq-len window allows it
    p_len = min(-(-p_raw // length_bucket) * length_bucket,
                max(p_raw, cfg.max_seq_len - max_new))
    pad_id = kw.get("pad_id", 0)
    batch = np.full((len(prompts), p_len), pad_id, np.int32)
    for i, p in enumerate(prompts):
        batch[i, : len(p)] = p
    key = kw.pop("key", jax.random.key(0))
    tp = kw.get("top_p", 0.0)
    kw.setdefault("use_top_p", not isinstance(tp, (int, float)) or tp > 0)
    min_len = max(1, int(lengths.min()) // length_bucket * length_bucket)
    out = generate(
        params,
        jnp.asarray(batch),
        jnp.asarray(lengths),
        cfg,
        key,
        min_prompt_len=min_len,
        **kw,
    )
    out = np.asarray(out)
    eos_id = kw.get("eos_id", -1)
    res = []
    for i, row in enumerate(out):
        toks = row[: lengths[i]].tolist()
        for t in row[lengths[i] : lengths[i] + kw.get("max_new_tokens", 32)]:
            if t == eos_id:
                break
            toks.append(int(t))
        res.append(toks)
    return res
