"""A decode window's attention over a stack of HEAD-major key/value slots, as one
Pallas kernel bounded a row by the row's own length.

    o[b, h, i] = softmax_j<=pos(b, i) (q[b, h, i] . k[layer, b, h, j] * scale) v[layer, b, h, j]

``k`` / ``v`` are a stack of a `generation.SlotStacks` ``(layers, rows, kv_heads,
positions, head_dim)``: the full layers', place j holding position j, or (``span`` >
0) the window layers' RING, place j holding the newest position p <= the row's last
write with ``p mod R = j``. Either is handed WHOLE: the index map names ``layer`` (a
prefetched scalar, so the layers of a step share one traced body a stack,
`pallas_common.traced_once`), the row and a block of ``KEY_BLOCK`` keys of ALL
its key/value heads, and nothing copies a layer's slab out. The kernel reads a stack
where the chip keeps it, and which layout that is follows from ``head_dim`` (the
shape's, not the program's; compiled for a described v5e, bf16 and float32 alike):

- whole lane tiles (128, 256): head-major as it is written, row-major
  (``bf16[4,32,4,16384,128]{4,3,2,1,0}``; PERF.md section 6, PR 54). A block is
  (kv_heads, KEY_BLOCK, head_dim), the head's values on the lanes.
- anything else (64; 32, 96 and 192 the same): the POSITIONS on the lanes, the head's
  values on the sublanes, unpadded (``bf16[5,32,8,16384,64]{3,4,2,1,0:T(8,128)(2,1)}``:
  each head's slab lies as K-transposed (64, 16384)). The kernel takes
  ``swapaxes(stack, 3, 4)``, which is a bitcast of that, and a block is (kv_heads,
  head_dim, KEY_BLOCK): the same algorithm with the keys on the lanes, the scores
  contracting the sublanes of K, the second product the lanes of the exponentials and
  of V, both masks an iota over the lanes.

`decode_path` lets in the heads that ``tests/test_topology_aot.py`` compiled and saw
arrive as a bitcast (whole lane tiles, and `TRANSPOSED_HEADS`), and the same tests hold
the compiled step to no copy of a slab.

A grid over (row, key block). The rows' first query positions are prefetched; a
row's window attends ``first + s`` positions. A block past the row's last live
block is not fetched (its index map names the last live block again, so no DMA is
issued) and not computed; a block whose every key lies at or before the row's first
query takes the body without a mask; the last live block masks the keys past each
query's position and zeroes the VALUES past the window (what a free place holds is
never counted: a 0 probability times a NaN is a NaN). The ``g`` grouped query heads
of a key/value head (x the window's ``s`` queries) are the rows of its score
matrix, no key/value head repeated; the softmax runs over the blocks with a float32
maximum, sum and accumulator, the exponentials cast to the compute type for the
second product (the plain body's precision, ``generation._attend_rows``).

A ring is read the same way up to the row's last write until the row has lapped it
and whole from then on; every block of it takes the masked body, which reads the
ABSOLUTE position a place holds (``generation._ring_key_positions``'s rule, from two
scalars a row: no division in the vector unit) and lets a query see the keys of its
window alone; a place that no query of the row's window sees (never written, from a
lap ago, or an earlier request's) has its values zeroed. A block with no key a query
sees counts 1 a key against the running maximum's start; the first key the query
does see (its own, at the latest) shrinks that to an exact 0
(`modeling.running_softmax`'s rule).

The ``pl.pallas_call`` name ``kv_decode`` is what a device trace shows under
``full`` | ``window`` > ``attn_core`` (PERF.md section 3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.ops import pallas_common

F32 = jnp.float32
_LANES = 128
#: the sublanes a bf16 tile holds: the query rows are padded to whole tiles
_ROW_TILE = 16
#: keys a grid step fetches and attends, of every key/value head of the row
KEY_BLOCK = 1024
#: heads of no whole lane tile that the kernel takes, reading the stack transposed:
#: the widths ``tests/test_topology_aot.py`` compiled for a described v5e and saw the
#: stack handed over as a bitcast. The compiler kept 32, 96 and 192 the same way in a
#: probe, but a width the rule guesses wrong costs a copy of the stack a layer a step
#: (not a wrong answer), so the rule is as narrow as what a test holds
TRANSPOSED_HEADS = (64,)
#: query rows (s x g) a key/value head's window may hold: the float32 scores
#: (kv_heads, rows, KEY_BLOCK) are 2 MiB of VMEM at 4 heads x 128 rows, 4 MiB at 8
MAX_QUERY_ROWS = 128


def decode_path(positions: int, head_dim: int, query_rows: int, dtype) -> str:
    """``"kernel"`` or ``"plain"`` for a decode window over head-major slots of
    ``positions`` keys of ``head_dim`` values with ``query_rows`` = s x g queries a
    key/value head, from the shapes and the backend alone: no flag, no environment
    variable, no model's name. `generation._windowed_attention` and
    `generation.cache_read_positions` both ask here. The kernel takes a TPU, or the
    CPU (interpreted: `pallas_common.use_interpret`, the one switch of this
    repo's kernels); a ``head_dim`` of whole lane tiles, which the chip keeps as it is
    written, or one of `TRANSPOSED_HEADS` (64), which it keeps with the positions on
    the lanes and the kernel reads transposed (the module's docstring: either way the
    stacks lie as the kernel reads them); a capacity of whole key blocks; at most
    ``MAX_QUERY_ROWS`` query rows; bf16 or float32. Everything else keeps the plain
    body."""
    if jax.default_backend() not in ("tpu", "cpu"):
        return "plain"
    if jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return "plain"
    inside = ((head_dim % _LANES == 0 or head_dim in TRANSPOSED_HEADS)
              and positions % KEY_BLOCK == 0 and query_rows <= MAX_QUERY_ROWS)
    return "kernel" if inside else "plain"


def read_positions(lengths, rows: int, positions: int) -> int:
    """Positions the kernel fetches of ONE layer's ``rows`` slots of ``positions``
    places (a full layer's capacity, a window layer's ring), given the positions the
    windows of the rows in use attend (``lengths``): each rounded up to the key block
    and no more than the slot (a ring that has lapped is read whole); a row out of
    use attends position 0 of its free slot, one block (host arithmetic)."""
    blocks = sum(min(-(-int(n) // KEY_BLOCK), positions // KEY_BLOCK) for n in lengths)
    return (blocks + rows - len(lengths)) * KEY_BLOCK


def _kernel(layer_ref, first_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, block_k: int, group: int, window: int, span: int, ring: int, keys: int):
    """``keys``: the axis of a K / V block (kv, ., .) its keys lie along: 1, a block
    (kv, Tk, d) of head-major stacks; 2, a block (kv, d, Tk) of their transposes."""
    del layer_ref  # (the index map's)
    row, j = pl.program_id(0), pl.program_id(1)
    first = first_ref[row]  # the first query's position
    length = first + window  # the positions the row's window attends
    start = j * block_k
    if span:
        # a ring's place holds a position of the row's newest lap up to the place of
        # its last write, of the lap before past it (negative: never written)
        lap = (length - 1) // ring * ring
        newest = length - 1 - lap

        def held(place):
            return place + jnp.where(place <= newest, lap, lap - ring)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, pallas_common.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(masked: bool):
        # (kv, s x g, d) against (kv, Tk, d) x 2, or transposed (kv, d, Tk) x 2
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        scores = jax.lax.dot_general(q, k, (((2,), (3 - keys,)), ((0,), (0,))),
                                     preferred_element_type=F32) * scale
        if masked:
            k_pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_k), 2)
            # (rows past s x g pad the tile: they attend as the window's last query)
            q_pos = first + jnp.minimum(
                jax.lax.broadcasted_iota(jnp.int32, (1, q.shape[1], 1), 1) // group, window - 1)
            v_pos = start + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k, 1) if keys == 1 else (1, 1, block_k), keys)
            if span:
                k_pos, v_pos = held(k_pos), held(v_pos)
                seen = (k_pos <= q_pos) & (k_pos > q_pos - span) & (k_pos >= 0)
                kept = (v_pos > first - span) & (v_pos >= 0)
            else:
                seen, kept = k_pos <= q_pos, v_pos < length
            scores = jnp.where(seen, scores, pallas_common.NEG_INF)
            v = jnp.where(kept, v, jnp.zeros_like(v))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        shrink = jnp.exp(m_prev - m_new)
        e = jnp.exp(scores - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * shrink + jnp.sum(e, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * shrink + jax.lax.dot_general(
            e.astype(v.dtype), v, (((2,), (keys,)), ((0,), (0,))), preferred_element_type=F32)

    if span:
        pl.when(start < length)(functools.partial(accumulate, True))
    else:
        # (block 0 holds position 0, which every query sees: the maximum is real from
        # the first block on)
        whole = start + block_k <= first + 1
        pl.when(whole)(functools.partial(accumulate, False))
        pl.when(jnp.logical_and(jnp.logical_not(whole), start < length))(
            functools.partial(accumulate, True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _attend(layer, first, q, ks, vs, *, scale: float, block_k: int, group: int, window: int,
            span: int, interpret: bool):
    """``q`` (B, kv, rows, d), a window's queries query-major (row i x g + h is query
    i of grouped head h; rows past ``window x group`` pad the tile), against ``ks``
    / ``vs`` (layers, slots, kv, positions, d); -> (B, kv, rows, d). A head of whole
    lane tiles is read as it is handed; any other TRANSPOSED, a block (kv, d, Tk) of
    ``swapaxes(ks, 3, 4)``, which is how the chip keeps such a stack (the module's
    docstring): the transpose is a bitcast there, and the same algorithm runs with
    the keys on the lanes."""
    b, kv, rows, d = q.shape
    positions = ks.shape[3]
    blocks = positions // block_k
    transposed = d % _LANES != 0

    def live_block(row, j, layer_ref, first_ref):
        last = jnp.minimum((first_ref[row] + window - 1) // block_k, blocks - 1)
        layer, live = layer_ref[0], jnp.minimum(j, last)
        return (layer, row, 0, 0, live) if transposed else (layer, row, 0, live, 0)

    if transposed:
        ks, vs = jnp.swapaxes(ks, 3, 4), jnp.swapaxes(vs, 3, 4)
    block = (None, None, kv, d, block_k) if transposed else (None, None, kv, block_k, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, blocks),
        in_specs=[
            pl.BlockSpec((None, kv, rows, d), lambda row, j, *_: (row, 0, 0, 0)),
            pl.BlockSpec(block, live_block),
            pl.BlockSpec(block, live_block),
        ],
        out_specs=pl.BlockSpec((None, kv, rows, d), lambda row, j, *_: (row, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((kv, rows, 1), F32), pltpu.VMEM((kv, rows, 1), F32),
                        pltpu.VMEM((kv, rows, d), F32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=block_k, group=group, window=window,
                          span=span, ring=positions, keys=2 if transposed else 1),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pallas_common.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kv_decode",
    )(layer, first, q, ks, vs)


def attend_rows(qg, ks, vs, layer: int, first, *, scale: float, span: int = 0):
    """The attention of the windows whose first queries stand at ``first`` (B,):
    grouped queries ``qg`` (B, s, kv, g, d) against rows [0, B) of layer ``layer`` of
    the head-major stacks ``ks`` / ``vs`` (layers, slots >= B, kv, positions, d) ->
    (B, s, kv, g, d) in ``qg``'s type. ``span`` > 0: the stacks are rings and a query
    sees the last ``span`` positions. ``KEY_BLOCK`` divides the positions."""
    b, s, kv, g, d = qg.shape
    rows = -(-s * g // _ROW_TILE) * _ROW_TILE
    q = jnp.swapaxes(qg, 1, 2).reshape(b, kv, s * g, d)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, rows - s * g), (0, 0)))
    out = pallas_common.traced_once(
        _attend, jnp.full((1,), layer, jnp.int32), first.astype(jnp.int32), q, ks, vs,
        scale=float(scale), block_k=KEY_BLOCK, group=g, window=s, span=int(span),
        interpret=pallas_common.use_interpret())
    return jnp.swapaxes(out[:, :, :s * g].reshape(b, kv, s, g, d), 1, 2)
