"""Grouped matrix multiplication for the dropless top-k MoE path: rows sorted by
expert, one weight matrix an expert.

The layout is *tile-aligned*: the caller (``models/moe.py``) places each
expert's rows at a multiple of ``tile_m`` (`row_tile`: chosen from the rows an
expert can expect, 16 for a decode step, ``TILE_M`` for a training step) and
pads the group to whole tiles with zero rows, so that every row tile belongs
to exactly one expert.  The
kernels are then plain tiled GEMMs whose weight block is chosen by a
scalar-prefetched ``tile_group`` array: no row masks, no tile visited twice
(a layout whose groups start anywhere re-visits one tile at every group
boundary: 63 more at this PR's cell).  What the padding costs is at
most ``tile_m`` rows a group, ``tile_m / 2`` on average.

- ``moe_gmm``: ``out[rows of g] = lhs[rows of g] @ rhs[g]`` (and, with
  ``transpose_rhs``, ``@ rhs[g].T``: the gradient of the left operand);
- ``moe_tgmm``: ``out[g] = lhs[rows of g].T @ grad[rows of g]``, the
  gradient of the weights; every group owns at least one tile (the layout's
  default, ``empty_tiles``: a forward that is never differentiated asks
  `models/moe.sorted_layout` for none, pays no fetch of an empty group's
  weights and runs `held_matmul` or `forward_matmul`, which carry no VJP), so
  every output block is written.

Row tiles past ``num_tiles`` (the static row count is an upper bound) are
skipped: ``moe_gmm`` writes zeros there, ``moe_tgmm`` leaves them out.  For a
held share of the experts (`held_matmul`; `models/moe.held_experts`) the
contract is weaker on purpose: a skipped tile writes NOTHING, the rows past
``num_tiles`` are undefined, and every reader masks them by index
(`ops/moe_held.py`): zero-filling 150,000 rows that hold no pair was the cost.  The
contraction is not tiled: at the widths this serves (hidden 2048 to 5120, expert
widths 512 to 3072) a whole ``(tile_m, K)`` by ``(K, tile_n)`` product fits the
scoped VMEM, and a weight block is then fetched once a group and not once a
row tile.  The output's columns are cut into blocks of `_tile`: the grid is
(column blocks, row tiles), a row tile is read again for every column block and
a grid step costs its ~0.3 us whatever it does, so no width ends at blocks of one
lane tile.

``grouped_matmul`` ties the three together with a custom VJP.  The
``pl.pallas_call`` names are a contract (PERF.md §3): the benchmark's trace
reduction finds the kernels by the prefix ``moe_gmm`` / ``moe_tgmm``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.ops import pallas_common
from galvatron_tpu.ops.pallas_common import traced_once

#: the most rows a tile: the alignment of a group's first row in the sorted layout
#: where an expert gets hundreds of rows or more (`row_tile`).
#: 256 over 128 / 512 / 1024 on the chip (PERF.md §6, PR 28): smaller tiles pad
#: less (147,456 rows for 131,072 pairs over 64 experts, 512 gives 163,840),
#: at 128 the kernels' rate begins to fall
TILE_M = 256


def row_tile(tokens: int, top_k: int, experts_scored: int, dtype) -> int:
    """Rows a tile of the expert-sorted layout for a forward of these static shapes:
    the largest power of two that the mean rows an expert gets, ``tokens * top_k /
    experts_scored``, still fill, from the dtype's sublane packing (16 rows of a
    16-bit type, 8 of float32: the smallest row block the kernels take) up to
    ``TILE_M``.  Every expert owns at least one whole tile, and the MXU multiplies
    and the kernels move whole tiles: a decode step's 2 or 3 rows an expert in tiles
    of 256 are 99% padding, so the tile follows the rows; an expert that draws more
    than a tile's rows gets consecutive tiles and its weights are still fetched
    once.  Tile = the mean (not twice or four times it) is the chip's reading at the
    four serving shapes (`experiments/ab_moe_held.py --serve`; PERF.md §6, PR 57);
    a training step's hundreds of rows an expert keep ``TILE_M``."""
    tile = 8 if jnp.dtype(dtype).itemsize >= 4 else 16
    while 2 * tile <= TILE_M and 2 * tile * experts_scored <= tokens * top_k:
        tile *= 2
    return tile


def _tile(n: int, want: int) -> int:
    """The column block of a width ``n``: the largest power-of-two multiple of 128 above
    one lane tile that divides ``n``, at most ``want``; a width that none divides is one
    whole block (always legal).  So is a width of 128 x an odd number (2688 = 21 x 128),
    which until PR 69 ended at ONE lane tile: 21 column blocks, each weight block K
    strided rows of 256 B, the row tiles re-read once a column block and a grid step
    paid 21 times a row tile.  The chip reads one block of 2688 ahead of 3 of 896 and 7 of
    384 at both served shapes (PERF.md section 6, PR 69)."""
    t = want
    while t > 128:
        if n % t == 0:
            return t
        t //= 2
    return n


def used_tile(i, count):
    """The row tile a grid step ``i`` names in its block maps, of ``count`` (1,) used ones:
    its own, or, skipped (``i >= count``; the body is under ``pl.when``), the last used
    tile's, so that nothing is fetched or written for it.  ``count`` 0 (a forward-only
    held share no token chose, `models/moe.sorted_layout`) names tile 0: the block before
    the array is no block."""
    return jnp.maximum(jnp.minimum(i, count[0] - 1), 0)


def _gmm(lhs, rhs, tile_group, num_tiles, *, transpose_rhs: bool, tile_m: int, tile_n: int,
         bounded: bool = False, slab_out: bool = False):
    """``bounded`` (a held share, `models/moe.held_experts`; fixed at trace time):
    a skipped tile writes nothing, so rows past ``num_tiles`` come out UNDEFINED and
    a skipped grid step costs a step, not a block of zeros.  ``slab_out`` (bounded
    only): the output is the slab ``(M, N / 128, 128)`` of `ops/moe_held.py`, whose
    rows a later kernel fetches one DMA each; the whole width is one block."""
    from galvatron_tpu.ops import moe_held

    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = n if slab_out else _tile(n, tile_n)
    dims = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
    per, lanes = moe_held.per_word(lhs.dtype), moe_held.LANES
    chunks = n // (per * lanes)  # of a slab row

    def kernel(group_ref, count_ref, lhs_ref, rhs_ref, out_ref):
        del group_ref

        @pl.when(pl.program_id(1) < count_ref[0])
        def _():
            acc = jax.lax.dot_general(
                lhs_ref[...], rhs_ref[...], dims, preferred_element_type=jnp.float32)
            if not slab_out:
                out_ref[...] = acc.astype(out_ref.dtype)
                return
            for c in range(chunks):
                at = c * per * lanes
                out_ref[:, c, :] = moe_held.f32_to_words(
                    [acc[:, at + p * lanes:at + (p + 1) * lanes] for p in range(per)], lhs.dtype)

        if not bounded:
            @pl.when(pl.program_id(1) >= count_ref[0])
            def _():
                out_ref[...] = jnp.zeros_like(out_ref)

    rhs_block = (None, tn, k) if transpose_rhs else (None, k, tn)
    if slab_out:
        out_shape = jax.ShapeDtypeStruct((m, chunks, lanes), moe_held.slab_dtype(lhs.dtype))
        out_spec = pl.BlockSpec((tile_m, chunks, lanes), lambda j, i, g, c: (used_tile(i, c), 0, 0))
    else:
        out_shape = jax.ShapeDtypeStruct((m, n), lhs.dtype)
        out_spec = pl.BlockSpec((tile_m, tn), (lambda j, i, g, c: (used_tile(i, c), j))
                                if bounded else (lambda j, i, g, c: (i, j)))
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, m // tile_m),
            in_specs=[
                pl.BlockSpec((tile_m, k), lambda j, i, g, c: (used_tile(i, c), 0)),
                pl.BlockSpec(rhs_block, (lambda j, i, g, c: (g[used_tile(i, c)], j, 0))
                             if transpose_rhs else (lambda j, i, g, c: (g[used_tile(i, c)], 0, j))),
            ],
            out_specs=out_spec,
        ),
        compiler_params=pallas_common.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=pallas_common.use_interpret(),
        name="moe_gmm_dlhs" if transpose_rhs else "moe_gmm",
    )(tile_group, num_tiles, lhs, rhs)


def _tgmm(lhs, grad, tile_group, num_tiles, num_groups: int, *, tile_m: int, tile_n: int,
          out_dtype):
    m, k = lhs.shape
    n = grad.shape[1]
    tk, tn = _tile(k, tile_n), _tile(n, tile_n)
    tiles = m // tile_m

    def kernel(group_ref, count_ref, lhs_ref, grad_ref, out_ref, acc_ref):
        i = pl.program_id(2)
        here = group_ref[i]
        first = jnp.logical_or(i == 0, group_ref[jnp.maximum(i - 1, 0)] != here)
        last = jnp.logical_or(i == tiles - 1, group_ref[jnp.minimum(i + 1, tiles - 1)] != here)

        @pl.when(first)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(i < count_ref[0])
        def _():
            acc_ref[...] += jax.lax.dot_general(
                lhs_ref[...], grad_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((num_groups, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, n // tn, tiles),
            in_specs=[
                pl.BlockSpec((tile_m, tk), lambda a, b, i, g, c: (used_tile(i, c), a)),
                pl.BlockSpec((tile_m, tn), lambda a, b, i, g, c: (used_tile(i, c), b)),
            ],
            out_specs=pl.BlockSpec((None, tk, tn), lambda a, b, i, g, c: (g[i], a, b)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pallas_common.compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_common.use_interpret(),
        name="moe_tgmm",
    )(tile_group, num_tiles, lhs, grad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def grouped_matmul(lhs, rhs, tile_group, num_tiles, tile_m: int = TILE_M, tile_n: int = 1024):
    """``lhs`` (M, K) by ``rhs`` (G, K, N) -> (M, N), row tile ``i`` (``tile_m``
    rows) against ``rhs[tile_group[i]]``.  ``tile_group`` (M / tile_m,) int32 is
    non-decreasing and names every group at least once; ``num_tiles`` (1,) int32
    counts the leading tiles that hold rows, the rest come out zero.  Rows of a
    tile beyond its group's size must be zero in ``lhs`` (they are multiplied
    like any other)."""
    return _gmm(lhs, rhs, tile_group, num_tiles, transpose_rhs=False,
                tile_m=tile_m, tile_n=tile_n)


def _fwd(lhs, rhs, tile_group, num_tiles, tile_m, tile_n):
    out = grouped_matmul(lhs, rhs, tile_group, num_tiles, tile_m, tile_n)
    return out, (lhs, rhs, tile_group, num_tiles)


def _bwd(tile_m, tile_n, res, grad):
    lhs, rhs, tile_group, num_tiles = res
    dlhs = _gmm(grad, rhs, tile_group, num_tiles, transpose_rhs=True,
                tile_m=tile_m, tile_n=tile_n)
    drhs = _tgmm(lhs, grad, tile_group, num_tiles, rhs.shape[0], tile_m=tile_m,
                 tile_n=tile_n, out_dtype=rhs.dtype)
    return dlhs, drhs, None, None


grouped_matmul.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def grouped_matmul_t(lhs, rhs_t, tile_group, num_tiles, tile_m: int = TILE_M, tile_n: int = 1024):
    """`grouped_matmul` against weights stored OUT-major: ``lhs`` (M, K) by ``rhs_t``
    (G, N, K) -> (M, N), row tile ``i`` against ``rhs_t[tile_group[i]].T`` (the kernel
    ``moe_gmm_dlhs``, which contracts the last dimension of both). For a weight whose
    output width is no whole number of 128-lane tiles (nemotron_h's experts: 2688 ->
    1856): stored (G, K, N) the chip lays such a stack K-minor, to spare the padding of
    N, and re-lays ALL of it in front of every Mosaic call (compiled for a described
    v5e: a 330 MB copy a layer and step); stored (G, N, K) the minor dimension is whole
    tiles and the kernel reads the stack where it lies."""
    return _gmm(lhs, rhs_t, tile_group, num_tiles, transpose_rhs=True,
                tile_m=tile_m, tile_n=tile_n)


def _fwd_t(lhs, rhs_t, tile_group, num_tiles, tile_m, tile_n):
    out = grouped_matmul_t(lhs, rhs_t, tile_group, num_tiles, tile_m, tile_n)
    return out, (lhs, rhs_t, tile_group, num_tiles)


def _bwd_t(tile_m, tile_n, res, grad):
    lhs, rhs_t, tile_group, num_tiles = res
    dlhs = _gmm(grad, rhs_t, tile_group, num_tiles, transpose_rhs=False,
                tile_m=tile_m, tile_n=tile_n)
    drhs_t = _tgmm(grad, lhs, tile_group, num_tiles, rhs_t.shape[0], tile_m=tile_m,
                   tile_n=tile_n, out_dtype=rhs_t.dtype)
    return dlhs, drhs_t, None, None


grouped_matmul_t.defvjp(_fwd_t, _bwd_t)


def held_matmul(lhs, rhs, tile_group, num_tiles, *, tile_m: int = TILE_M,
                transpose_rhs: bool = False, slab_out: bool = False):
    """`grouped_matmul`'s product (or, ``transpose_rhs``, its left gradient) for a
    held share of the experts: tiles past ``num_tiles`` are not written, their rows
    UNDEFINED; ``slab_out``: see `_gmm`.  No VJP of its own: `models/moe.held_experts`
    writes the backward out."""
    return traced_once(_gmm, lhs, rhs, tile_group, num_tiles, transpose_rhs=transpose_rhs,
                       tile_m=tile_m, tile_n=1024, bounded=True, slab_out=slab_out)


def forward_matmul(lhs, rhs, tile_group, num_tiles, *, tile_m: int = TILE_M,
                   transpose_rhs: bool = False):
    """`grouped_matmul`'s product (``transpose_rhs``: `grouped_matmul_t`'s, against
    out-major weights) for a forward that is never differentiated: the same kernel,
    zeros past ``num_tiles``, and NO VJP.  Such a forward runs over a layout without
    empty tiles (`models/moe.sorted_layout`), where `moe_tgmm` would leave the weight
    gradient of a group that owns no tile unwritten: a gradient must raise (Pallas has
    no JVP of a scalar-prefetch call) and never be read out of those blocks."""
    return traced_once(_gmm, lhs, rhs, tile_group, num_tiles, transpose_rhs=transpose_rhs,
                       tile_m=tile_m, tile_n=1024)


def weight_grad(lhs, grad, tile_group, num_tiles, num_groups: int, *, tile_m: int = TILE_M,
                out_dtype):
    """``out[g] = lhs[rows of g].T @ grad[rows of g]`` (`moe_tgmm`), which reads the
    tiles before ``num_tiles`` alone."""
    return traced_once(_tgmm, lhs, grad, tile_group, num_tiles, num_groups=num_groups,
                       tile_m=tile_m, tile_n=1024, out_dtype=out_dtype)
