"""A prompt chunk's attention over a stack of HEAD-major key/value slots, as one
Pallas kernel whose scores never leave the chip.

    o[i, h] = softmax_j seen from offset+i (q[i, h] . k[layer, slot, h // g, j] * scale)
              v[layer, slot, h // g, j]

the mathematics of ``generation._attend_chunk``: one request's queries at absolute
positions ``offset + [0, s)`` against row ``slot`` of a stack of a
`generation.SlotStacks` ``(layers, rows, kv_heads, positions, head_dim)``: the full
layers', place j holding position j, or (``span`` > 0) the window layers' RING, place
j holding the newest position p <= the chunk's last with ``p mod R = j``. XLA's body
writes the float32 scores of a key block (28 heads x 1,024 x 1,024 = 117 MB) to HBM
and passes over them several times; here the scores, the running maximum, sum and
accumulator live in VMEM, and what the kernel reads is the queries and the keys and
values where they lie. It joins the two modules beside it:

- from ``ops/mla_prefill.py``: a grid over (query head, key block), ALL of the chunk's
  rows against one key block a step; ``layer``, ``slot`` and ``offset`` prefetched
  scalars, so the layers of a program share one traced body a stack
  (`pallas_common.traced_once`) and every chunk of every request the one compiled
  program; a key block past the chunk's last live one is not fetched (its index map
  names the last live block again) and not computed; a block every query sees whole
  takes the body without a mask. Walking the queries in sub-blocks to skip the pairs
  past the diagonal was measured there and lost (PERF.md section 6, PR 53).
- from ``ops/kv_decode.py``: the stacks handed WHOLE and read where the chip keeps them,
  which follows from ``head_dim``: whole lane tiles (128) as written, a block (Tk, d);
  a head in `kv_decode.TRANSPOSED_HEADS` (64) through ``swapaxes(stack, 3, 4)``, a
  bitcast of how the chip lays such a stack, a block (d, Tk), the second product
  contracting the lanes of both operands. The block of query head h is key/value head
  ``h // g``'s: it is fetched once a grouped head (43 MB a smallthinker layer) and no
  key/value head is repeated in HBM. A ring's place holds an ABSOLUTE position by that
  module's ``held`` rule from two scalars; a query sees ``q - span < k <= q``; a ring
  is read up to the chunk's end until it has lapped and whole from then on
  (`generation.chunk_key_blocks`' counts); the values no query of the chunk sees are
  zeroed (what a free place holds is never counted: a 0 probability times a NaN is a
  NaN). A block with no key a query sees counts 1 a key against the running maximum's
  start; the first key the query does see (its own, at the latest) shrinks that to an
  exact 0 (`modeling.running_softmax`'s rule).

Precision is the plain body's: operands in the compute type, float32 accumulation of
both products, float32 scores, maximum, sum and accumulator, the exponentials cast to
the compute type for the second product.

The ``pl.pallas_call`` name ``kv_chunk`` is what a device trace of the PREFILL program
shows under ``full`` | ``window`` > ``attn_core`` (PERF.md section 3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.ops import kv_decode, pallas_common

F32 = jnp.float32
_LANES = 128
#: keys a grid step fetches and attends
KEY_BLOCK = 1024
#: rows a chunk may hold (what `mla_prefill.MAX_CHUNK_ROWS` was measured at): the
#: float32 scores (s, KEY_BLOCK) are 4 MiB of VMEM at 1,024, the exponentials 2
MAX_CHUNK_ROWS = 1024


def chunk_path(positions: int, head_dim: int, rows: int, dtype) -> str:
    """``"kernel"`` or ``"plain"`` for a prompt chunk of ``rows`` queries over
    head-major slots (or a ring) of ``positions`` keys of ``head_dim`` values, from the
    shapes and the backend alone: no flag, no environment variable, no model's name.
    `generation._windowed_attention` and `generation.chunk_layout` both ask here, of
    each stack. The kernel takes a TPU, or the CPU (interpreted:
    `pallas_common.use_interpret`); bf16 or float32; a capacity of whole key blocks;
    at most ``MAX_CHUNK_ROWS`` rows in whole sublane tiles of the compute type; a
    ``head_dim`` of whole lane tiles, which the chip keeps as it is written, or one of
    `kv_decode.TRANSPOSED_HEADS` (64), which it keeps with the positions on the lanes
    and the kernel reads transposed. Everything else keeps the plain body."""
    if jax.default_backend() not in ("tpu", "cpu"):
        return "plain"
    if jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return "plain"
    sublanes = 32 // jnp.dtype(dtype).itemsize  # rows of a packed tile
    inside = ((head_dim % _LANES == 0 or head_dim in kv_decode.TRANSPOSED_HEADS)
              and positions % KEY_BLOCK == 0 and rows <= MAX_CHUNK_ROWS and rows % sublanes == 0)
    return "kernel" if inside else "plain"


def _kernel(layer_ref, slot_ref, offset_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, span: int, ring: int, keys: int):
    """``keys``: the axis of a K / V block its keys lie along: 0, a block (Tk, d) of
    head-major stacks; 1, a block (d, Tk) of their transposes."""
    del layer_ref, slot_ref  # (the index maps')
    j = pl.program_id(1)
    offset = offset_ref[0]  # the first query's position
    rows, block_k = q_ref.shape[0], k_ref.shape[keys]
    start = j * block_k
    end = offset + rows  # the positions the chunk attends
    if span:
        # a ring's place holds a position of the chunk's newest lap up to the place of
        # its last write, of the lap before past it (negative: never written)
        lap = (end - 1) // ring * ring
        newest = end - 1 - lap

        def held(place):
            return place + jnp.where(place <= newest, lap, lap - ring)

        # the block is one run of positions unless the last write lies inside it; every
        # query sees it whole if the run lies in the window of the last query and at or
        # before the first
        run = held(start)
        whole = (jnp.logical_or(start + block_k - 1 <= newest, start > newest)
                 & (run >= 0) & (run + block_k <= offset + 1) & (run > end - 1 - span))
    else:
        # (block 0 holds position 0, which every query sees: the maximum is real from
        # the first block on)
        whole = start + block_k <= offset + 1

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, pallas_common.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(masked: bool):
        # (s, d) against (Tk, d) x 2, or transposed (d, Tk) x 2
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        scores = jax.lax.dot_general(q, k, (((1,), (1 - keys,)), ((), ())),
                                     preferred_element_type=F32) * scale
        if masked:
            k_pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            q_pos = offset + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            v_pos = start + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k) if keys else (block_k, 1), keys)
            if span:
                k_pos, v_pos = held(k_pos), held(v_pos)
                seen = (k_pos <= q_pos) & (k_pos > q_pos - span) & (k_pos >= 0)
                kept = (v_pos > offset - span) & (v_pos >= 0)
            else:
                seen, kept = k_pos <= q_pos, v_pos < end
            scores = jnp.where(seen, scores, pallas_common.NEG_INF)
            v = jnp.where(kept, v, jnp.zeros_like(v))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        shrink = jnp.exp(m_prev - m_new)
        e = jnp.exp(scores - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * shrink + jnp.sum(e, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * shrink + jax.lax.dot_general(
            e.astype(v.dtype), v, (((1,), (keys,)), ((), ())), preferred_element_type=F32)

    pl.when(whole)(functools.partial(accumulate, False))
    pl.when(jnp.logical_and(jnp.logical_not(whole), start < end))(
        functools.partial(accumulate, True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _attend(layer, slot, offset, q, ks, vs, *, scale: float, block_k: int, heads: int,
            span: int, interpret: bool):
    """The chunk's queries ``q`` against row ``slot`` of layer ``layer`` of ``ks`` /
    ``vs`` (layers, slots, kv, positions, d); ``heads`` query heads, ``heads // kv`` a
    key/value head. A head of whole lane tiles: ``q`` and the result (s, heads x d), a
    head's values side by side, the stacks read as they are handed. Any other head:
    ``q`` and the result head-major (heads, s, d) (a block's last axis is then the
    array's), the stacks read TRANSPOSED, a block (d, Tk) of ``swapaxes(ks, 3, 4)``,
    which is how the chip keeps such a stack (`ops/kv_decode`'s docstring)."""
    kv, positions, d = ks.shape[2:]
    s, transposed = q.shape[-2], q.ndim == 3
    blocks, group = positions // block_k, heads // kv

    def live_block(h, j, layer_ref, slot_ref, offset_ref):
        last = jnp.minimum((offset_ref[0] + s - 1) // block_k, blocks - 1)
        at = layer_ref[0], slot_ref[0], h // group
        live = jnp.minimum(j, last)
        return at + ((0, live) if transposed else (live, 0))

    if transposed:
        ks, vs = jnp.swapaxes(ks, 3, 4), jnp.swapaxes(vs, 3, 4)
        block = (None, None, None, d, block_k)
        rows = pl.BlockSpec((None, s, d), lambda h, j, *_: (h, 0, 0))
    else:
        block = (None, None, None, block_k, d)
        rows = pl.BlockSpec((s, d), lambda h, j, *_: (0, h))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(heads, blocks),
        in_specs=[rows, pl.BlockSpec(block, live_block), pl.BlockSpec(block, live_block)],
        out_specs=rows,
        scratch_shapes=[pltpu.VMEM((s, 1), F32), pltpu.VMEM((s, 1), F32),
                        pltpu.VMEM((s, d), F32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, span=span, ring=positions,
                          keys=1 if transposed else 0),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pallas_common.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kv_chunk",
    )(layer, slot, offset, q, ks, vs)


def attend_chunk(qg, ks, vs, layer: int, slot, offset, *, scale: float, span: int = 0):
    """The attention of the chunk whose first query stands at ``offset`` of row ``slot``:
    grouped queries ``qg`` (1, s, kv, g, d) against that row of layer ``layer`` of the
    head-major stacks ``ks`` / ``vs`` (layers, slots, kv, positions, d) -> (1, s, kv, g,
    d) in ``qg``'s type. ``slot`` and ``offset`` may be traced. ``span`` > 0: the stacks
    are rings and a query sees the last ``span`` positions. ``KEY_BLOCK`` divides the
    positions."""
    _, s, kv, g, d = qg.shape
    one = lambda v: jnp.reshape(jnp.asarray(v, jnp.int32), (1,))  # noqa: E731
    flat = d % _LANES == 0
    # (a head of 64 is half a lane tile: its queries and result go head-major, one
    # pass each over 4 MB at the published widths)
    q = qg.reshape(s, kv * g * d) if flat else jnp.swapaxes(qg.reshape(s, kv * g, d), 0, 1)
    out = pallas_common.traced_once(
        _attend, one(layer), one(slot), one(offset), q, ks, vs, scale=float(scale),
        block_k=KEY_BLOCK, heads=kv * g, span=int(span), interpret=pallas_common.use_interpret())
    return (out if flat else jnp.swapaxes(out, 0, 1)).reshape(qg.shape)
