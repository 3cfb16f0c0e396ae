"""The permutations and the SwiGLU of a held share of the experts
(``cfg.moe_share``), bounded by the rows the share holds.

A rank that holds E/R of the experts its router scores gets ~1/R of the
(token, expert) pairs.  `models/moe.held_layout` keeps the sorted row buffer at
its static worst case (every pair held) and knows at run time how little of it
is in use: ``num_tiles`` leading row tiles, ``tile_rows[i]`` leading rows of tile
``i``, and a pair is held iff ``pair_row < num_tiles * tile``.  The kernels here
do work in proportion to that and never to the buffer:

- ``moe_held_rows``: buffer row ``r`` = source row ``row_token[r]`` (times a
  weight of the row), for the used tiles only; padding rows inside a used tile
  are written zero, tiles past ``num_tiles`` are never written;
- ``moe_held_pairs``: token ``t`` = sum over its held pairs ``j`` of
  ``weights[t, j] * buffer[pair_row[t, j]]`` in float32 in ``j`` order (or, with
  ``other``, the k dot products ``<buffer[pair_row[t, j]], other[t]>``); a pair
  that is not held fetches nothing and adds an exact zero by a mask;
- ``moe_held_swiglu`` / ``moe_held_swiglu_bwd``: ``silu(gate) * up`` (or, ``act``
  "relu", ``relu(gate) * up``: the kernels keep their names) over the used tiles
  of the fused ``[gate | up]`` buffer.

**Rows past ``num_tiles`` of every buffer on this path are UNDEFINED, not zero**:
every reader masks by index (`ops/grouped_matmul.py` skips them the same way).

A gathered row is one DMA.  Mosaic slices a tiled HBM array only at whole
(8, 128) tiles of its last two dimensions, so a row that is to be fetched alone
lives as a *slab*: ``(rows, h / 128, 128)`` 32-bit words, 4 KB contiguous at
h 2048.  float32 rows are their own words; two bf16 values share a word (columns
``c * 256 + l`` low and ``c * 256 + 128 + l`` high), because a bf16 array packs
two ROWS into a word and half a word cannot be addressed.  `to_slab` builds a
slab from a (T, h) array in one pass (the sources here are token-major:
16,384 rows); the grouped GEMMs write theirs from the accumulator
(`grouped_matmul._gmm(slab_out=True)`), so no pass over a row buffer converts
anything.

The ``pl.pallas_call`` names are a contract (PERF.md §3), prefix ``moe_``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.ops import pallas_common
from galvatron_tpu.ops.grouped_matmul import used_tile

LANES = 128
_HIGH = 0xFFFF0000


def per_word(dtype) -> int:
    """Values of ``dtype`` a 32-bit slab word carries."""
    return 2 if jnp.dtype(dtype) == jnp.bfloat16 else 1


def held_path(hidden: int, width: int, dtype, gated: bool = True) -> str:
    """``"bounded"`` or ``"worst_case"`` for a held share of these sizes, from the
    shapes alone: no flag, no environment variable.  `models/moe._topk_local` and
    `models/moe.held_path_counts` both ask here.  The bounded kernels take bf16 or
    float32 rows that are whole slab chunks (``hidden`` a multiple of 256, of 128
    in float32) and an expert width of whole lane tiles; everything else keeps the
    plain path over the whole buffer.  So do UN-GATED experts (``gated`` False,
    ``act_fn`` "relu2"): the bounded body is built on the fused ``[gate | up]`` buffer
    and `swiglu`; and nemotron_h's sizes are outside it twice over anyway (hidden
    2688 = 21 x 128, an odd number of the lane tiles that `to_slab` packs in pairs;
    width 1856 = 14.5 x 128).  ``worst_case`` names the buffer XLA's passes walk
    (dispatch, the activation, combine: every row of it), not the weights fetched: a
    cached forward's layout gives an expert without a row no tile on this path either
    (`models/moe.forward_gemm`), so its two grouped GEMMs fetch the touched experts
    alone, 23 of 32 in a decode step of 64 rows (PERF.md section 5); a differentiated
    forward keeps a tile for every held expert and fetches them all."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.bfloat16, jnp.float32) or not gated:
        return "worst_case"
    inside = hidden % (LANES * per_word(dtype)) == 0 and width % LANES == 0
    return "bounded" if inside else "worst_case"


def slab_dtype(dtype):
    return jnp.uint32 if per_word(dtype) == 2 else jnp.dtype(dtype)


def to_slab(x: jax.Array) -> jax.Array:
    """(N, h) -> its slab (N, chunks, 128), one XLA pass."""
    n, h = x.shape
    if per_word(x.dtype) == 1:
        return x.reshape(n, h // LANES, LANES)
    bits = jax.lax.bitcast_convert_type(
        x.reshape(n, h // (2 * LANES), 2, LANES), jnp.uint16).astype(jnp.uint32)
    return bits[:, :, 0] | (bits[:, :, 1] << jnp.uint32(16))


def from_slab(slab: jax.Array, dtype) -> jax.Array:
    """`to_slab`'s inverse, for tests and seams (no kernel path needs it)."""
    n = slab.shape[0]
    if per_word(dtype) == 1:
        return slab.reshape(n, -1)
    halves = jnp.stack([slab & jnp.uint32(0xFFFF), slab >> jnp.uint32(16)], axis=2)
    return jax.lax.bitcast_convert_type(halves.astype(jnp.uint16), jnp.bfloat16).reshape(n, -1)


def words_to_f32(words):
    """A (r, 128) block of slab words -> its float32 column blocks, in column order."""
    if words.dtype != jnp.uint32:
        return [words.astype(jnp.float32)]
    return [jax.lax.bitcast_convert_type(words << jnp.uint32(16), jnp.float32),
            jax.lax.bitcast_convert_type(words & jnp.uint32(_HIGH), jnp.float32)]


def f32_to_words(pieces, dtype):
    """`words_to_f32`'s inverse: float32 column blocks -> slab words of ``dtype`` rows."""
    if per_word(dtype) == 1:
        return pieces[0].astype(dtype)
    low, high = (jax.lax.bitcast_convert_type(
        p.astype(jnp.bfloat16).astype(jnp.float32), jnp.uint32) for p in pieces)
    return (low >> jnp.uint32(16)) | (high & jnp.uint32(_HIGH))


def _wait_rows(n, src_ref, dst_ref, sem):
    """Wait for ``n`` row copies on ``sem`` (each wait takes one row's bytes)."""
    def body(_, carry):
        pltpu.make_async_copy(src_ref.at[0], dst_ref, sem).wait()
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def gather_rows(src, row_token, tile_rows, num_tiles, *, dtype, tile: int, scale=None):
    """``out[r] = src[row_token[r]]`` (times ``scale[r]``, float32) for the rows of
    the used tiles: ``src`` a slab of T rows, ``row_token`` (M,), ``tile_rows``
    (M / tile,) the leading rows of a tile that hold a pair, ``num_tiles`` (1,).
    -> (M, h) ``dtype``; padding rows of a used tile zero, later tiles undefined."""
    args = (src, row_token, tile_rows, num_tiles) + (() if scale is None else (scale,))
    return pallas_common.traced_once(_gather_rows, *args, dtype=dtype, tile=tile)


def _gather_rows(src, row_token, tile_rows, num_tiles, scale=None, *, dtype, tile):
    _, chunks, _ = src.shape
    per = per_word(dtype)
    h = chunks * per * LANES
    m = row_token.shape[0]
    tiles = m // tile

    def kernel(tok_ref, rows_ref, count_ref, src_ref, *rest):
        scale_ref = rest[0] if scale is not None else None
        out_ref, buf, sem = rest[-3:]
        i = pl.program_id(0)

        @pl.when(i < count_ref[0])
        def _():
            n = rows_ref[i]

            def start(r, carry):
                pltpu.make_async_copy(src_ref.at[tok_ref[i * tile + r]], buf.at[r], sem).start()
                return carry

            jax.lax.fori_loop(0, n, start, 0)
            _wait_rows(n, src_ref, buf.at[0], sem)
            keep = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < n
            if scale_ref is not None:
                # the tile's weights arrive along the lanes; a row needs its own
                # down the sublanes: the diagonal of their broadcast
                diag = (jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
                        == jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1))
                col = jnp.sum(jnp.where(diag, scale_ref[...], 0.0), axis=1, keepdims=True)
            for c in range(chunks):
                for part, piece in enumerate(words_to_f32(buf[:, c, :])):
                    piece = jnp.where(keep, piece, 0.0)
                    if scale_ref is not None:
                        piece = piece * col
                    at = (c * per + part) * LANES
                    out_ref[:, at:at + LANES] = piece.astype(out_ref.dtype)

    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    args = [src]
    if scale is not None:
        in_specs.append(pl.BlockSpec((None, 1, tile), lambda i, t, r, c: (used_tile(i, c), 0, 0)))
        args.append(scale.astype(jnp.float32).reshape(tiles, 1, tile))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, h), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tile, h), lambda i, t, r, c: (used_tile(i, c), 0)),
            scratch_shapes=[pltpu.VMEM((tile, chunks, LANES), src.dtype),
                            pltpu.SemaphoreType.DMA(())],
        ),
        compiler_params=pallas_common.compiler_params(dimension_semantics=("arbitrary",)),
        interpret=pallas_common.use_interpret(),
        name="moe_held_rows",
    )(row_token, tile_rows, num_tiles, *args)


def _token_tile(tokens: int) -> int:
    for t in (128, 64, 32, 16, 8):
        if tokens % t == 0:
            return t
    return tokens


def _places(pair_row, num_tiles, tile):
    """(T, j, place) bool: pair j of a token is its ``place``-th HELD pair, and the
    (T,) counts of them."""
    k = pair_row.shape[1]
    held = pair_row < num_tiles[0] * tile
    rank = jnp.cumsum(held, axis=1, dtype=jnp.int32) - 1
    first = held[:, :, None] & (rank[:, :, None] == jnp.arange(k, dtype=jnp.int32))
    return first, jnp.sum(held, axis=1, dtype=jnp.int32)


def pairs_index(pair_row, num_tiles, *, tile: int):
    """What `gather_pairs` walks, from ``pair_row`` (T, k): ``rows`` (T * k,) int32, a
    token's HELD pairs' rows first (in ``j`` order), and ``counts`` (T,) int32 of them.
    The kernel's scalar loop then runs over the tokens and over the pairs that are
    held (one a DMA), not over all T * k pairs (27 ns each on the chip, 4.4 ms a
    pass at 163,840: PERF.md §6, PR 49)."""
    first, counts = _places(pair_row, num_tiles, tile)
    rows = jnp.sum(jnp.where(first, pair_row[:, :, None], 0), axis=1, dtype=jnp.int32)
    return rows.reshape(-1), counts


def gather_pairs(src, pair_row, num_tiles, weights, index, *, dtype, tile: int, other=None):
    """See `_gather_pairs`."""
    args = (src, pair_row, num_tiles, weights, *index) + (() if other is None else (other,))
    return pallas_common.traced_once(_gather_pairs, *args, dtype=dtype, tile=tile)


def _gather_pairs(src, pair_row, num_tiles, weights, rows, counts, other=None, *, dtype, tile):
    """Over the held pairs of each token (``pair_row[t, j] < num_tiles * tile``),
    ``src`` a slab of M rows, ``pair_row`` (T, k) int32, ``weights`` (T, k) float32,
    ``rows`` and ``counts`` `pairs_index` of the same ``pair_row``:

    - ``other`` None -> (T, h) ``dtype``: ``sum_j weights[t, j] * src[pair_row[t, j]]``,
      float32, in ``j`` order;
    - ``other`` (T, h) -> (T, k) float32: ``<src[pair_row[t, j]], other[t]>``.

    A pair that is not held starts no copy and adds an exact zero. A token's held
    pairs lie first in VMEM as they do in ``rows`` (place i = its i-th held pair),
    so the vector pass too stops at the token tile's largest count."""
    _, chunks, _ = src.shape
    per = per_word(dtype)
    h = chunks * per * LANES
    tokens, k = pair_row.shape
    tt = _token_tile(tokens)
    first, _ = _places(pair_row, num_tiles, tile)
    # a place's weight: its pair's (the sum has one term)
    placed = jnp.sum(jnp.where(first, weights.astype(jnp.float32)[:, :, None], 0.0), axis=1)

    def kernel(rows_ref, counts_ref, src_ref, count_ref, w_ref, *rest):
        other_ref = rest[0] if other is not None else None
        out_ref, buf, wide, cols, sem = rest[-5:]
        base = pl.program_id(0) * tt

        def start(t, carry):
            n, most = carry
            mine = counts_ref[base + t]

            def one(place, c):
                pltpu.make_async_copy(src_ref.at[rows_ref[(base + t) * k + place]],
                                      buf.at[place, t], sem).start()
                return c

            jax.lax.fori_loop(0, mine, one, 0)
            return n + mine, jnp.maximum(most, mine)

        n, most = jax.lax.fori_loop(0, tt, start, (jnp.int32(0), jnp.int32(0)))
        _wait_rows(n, src_ref, buf.at[0, 0], sem)
        # a token's count and each place's weight across the lanes, a place a slab
        # of `wide`: the loops below index places and chunks at run time
        mine = jnp.broadcast_to(count_ref[:, 0:1], (tt, LANES))
        if other_ref is None:
            for i in range(k):
                wide[i] = jnp.broadcast_to(w_ref[:, i:i + 1], (tt, LANES))

            def chunk(c, carry):
                def place(i, acc):
                    return [a + jnp.where(mine > i, piece, 0.0) * wide[i]
                            for a, piece in zip(acc, words_to_f32(buf[i, :, c, :]))]

                acc = jax.lax.fori_loop(
                    0, most, place, [jnp.zeros((tt, LANES), jnp.float32) for _ in range(per)])
                for part in range(per):
                    cols[c * per + part] = acc[part]
                return carry

            jax.lax.fori_loop(0, chunks, chunk, 0)
            for q in range(chunks * per):
                out_ref[:, q * LANES:(q + 1) * LANES] = cols[q].astype(out_ref.dtype)
        else:
            for q in range(chunks * per):
                cols[q] = other_ref[:, q * LANES:(q + 1) * LANES].astype(jnp.float32)

            def place(i, carry):
                def chunk(c, acc):
                    for part, piece in enumerate(words_to_f32(buf[i, :, c, :])):
                        acc = acc + jnp.where(mine > i, piece, 0.0) * cols[c * per + part]
                    return acc

                wide[i] = jax.lax.fori_loop(0, chunks, chunk, jnp.zeros((tt, LANES), jnp.float32))
                return carry

            jax.lax.fori_loop(0, most, place, 0)
            for i in range(k):  # places past the tile's largest count were never written
                dot = jnp.sum(wide[i], axis=1, keepdims=True)
                out_ref[:, i:i + 1] = jnp.where(mine[:, 0:1] > i, dot, 0.0)

    per_token = lambda i, *_: (i, 0)  # noqa: E731
    in_specs = [pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec((tt, k), per_token),
                pl.BlockSpec((tt, k), per_token)]
    args = [src, jnp.broadcast_to(counts[:, None], (tokens, k)), placed]
    if other is None:
        out_shape, out_block = jax.ShapeDtypeStruct((tokens, h), dtype), (tt, h)
    else:
        in_specs.append(pl.BlockSpec((tt, h), per_token))
        args.append(other)
        out_shape, out_block = jax.ShapeDtypeStruct((tokens, k), jnp.float32), (tt, k)
    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tokens // tt,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(out_block, per_token),
            scratch_shapes=[pltpu.VMEM((k, tt, chunks, LANES), src.dtype),
                            pltpu.VMEM((k, tt, LANES), jnp.float32),  # a place a slab
                            pltpu.VMEM((chunks * per, tt, LANES), jnp.float32),  # column blocks
                            pltpu.SemaphoreType.DMA(())],
        ),
        compiler_params=pallas_common.compiler_params(dimension_semantics=("arbitrary",)),
        interpret=pallas_common.use_interpret(),
        name="moe_held_pairs",
    )(rows, counts, *args)
    if other is None:
        return out
    # a place's dot product back to its pair
    return jnp.sum(jnp.where(first, out[:, None, :], 0.0), axis=2)


def swiglu(gate_up, num_tiles, *, tile: int, act: str = "silu"):
    """``act(gate) * up`` of the fused (M, 2f) ``[gate | up]`` buffer -> (M, f),
    over the used tiles; later tiles undefined. ``act`` "silu" (SwiGLU) or "relu"
    (ReGLU), fixed at trace time."""
    return pallas_common.traced_once(_swiglu, gate_up, num_tiles, tile=tile, act=act)


def _swiglu(gate_up, num_tiles, *, tile, act="silu"):
    m, f2 = gate_up.shape
    f = f2 // 2

    def kernel(count_ref, gu_ref, out_ref):
        @pl.when(pl.program_id(0) < count_ref[0])
        def _():
            gate = gu_ref[:, :f].astype(jnp.float32)
            up = gu_ref[:, f:].astype(jnp.float32)
            if act == "relu":
                out_ref[...] = (jnp.maximum(gate, 0.0) * up).astype(out_ref.dtype)
            else:
                out_ref[...] = (gate * jax.nn.sigmoid(gate) * up).astype(out_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, f), gate_up.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // tile,),
            in_specs=[pl.BlockSpec((tile, f2), lambda i, c: (used_tile(i, c), 0))],
            out_specs=pl.BlockSpec((tile, f), lambda i, c: (used_tile(i, c), 0)),
        ),
        compiler_params=pallas_common.compiler_params(dimension_semantics=("arbitrary",)),
        interpret=pallas_common.use_interpret(),
        name="moe_held_swiglu",
    )(num_tiles, gate_up)


def swiglu_bwd(gate_up, grad, num_tiles, *, tile: int, act: str = "silu"):
    """`swiglu`'s backward: (M, 2f) ``[d gate | d up]`` from the saved buffer and
    the (M, f) gradient of its output, over the used tiles."""
    return pallas_common.traced_once(_swiglu_bwd, gate_up, grad, num_tiles, tile=tile, act=act)


def _swiglu_bwd(gate_up, grad, num_tiles, *, tile, act="silu"):
    m, f2 = gate_up.shape
    f = f2 // 2

    def kernel(count_ref, gu_ref, grad_ref, out_ref):
        @pl.when(pl.program_id(0) < count_ref[0])
        def _():
            gate = gu_ref[:, :f].astype(jnp.float32)
            up = gu_ref[:, f:].astype(jnp.float32)
            g = grad_ref[...].astype(jnp.float32)
            if act == "relu":
                on = gate > 0.0
                out_ref[:, :f] = jnp.where(on, g * up, 0.0).astype(out_ref.dtype)
                out_ref[:, f:] = jnp.where(on, g * gate, 0.0).astype(out_ref.dtype)
                return
            sig = jax.nn.sigmoid(gate)
            out_ref[:, :f] = (g * up * sig * (1.0 + gate * (1.0 - sig))).astype(out_ref.dtype)
            out_ref[:, f:] = (g * gate * sig).astype(out_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, f2), gate_up.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // tile,),
            in_specs=[pl.BlockSpec((tile, f2), lambda i, c: (used_tile(i, c), 0)),
                      pl.BlockSpec((tile, f), lambda i, c: (used_tile(i, c), 0))],
            out_specs=pl.BlockSpec((tile, f2), lambda i, c: (used_tile(i, c), 0)),
        ),
        compiler_params=pallas_common.compiler_params(dimension_semantics=("arbitrary",)),
        interpret=pallas_common.use_interpret(),
        name="moe_held_swiglu_bwd",
    )(num_tiles, gate_up, grad)
