"""A prompt chunk's latent attention (MLA, NON-absorbed form) over the stacked
latent slot cache, as one Pallas kernel whose scores never leave the chip.

    [k_nope | v][j, h] = c~[layer, slot, j] W_kvb,h          (the expansion, a key block at a time)
    o[i, h] = softmax_j<=offset+i ((q_nope[i, h] . k_nope[j, h] + q_rope[i, h] . k_r[j]) * scale) v[j, h]

the mathematics of ``models/mla.attend_chunk``: queries at absolute positions
``offset + [0, s)`` of row ``slot`` against that row's positions ``[0, offset + s)``.
XLA's body writes the float32 scores of a key block (64 heads x 1,024 x 1,024 =
268 MB) to HBM and passes over them six times; here the scores, the running
maximum, sum and accumulator and the expanded keys and values of a block live in
VMEM, and what the kernel reads is the latent (1.2 MB a key block) and the queries.

The cache is read IN PLACE in the layout the chip keeps it in, as
``ops/mla_decode.py`` reads it: handed ``swapaxes(stacked, 2, 3)`` (a bitcast for a
width that is no multiple of 128), blocks (r + dr, Tk). So the expansion runs in
exactly that orientation: ``W_kvb,h^T (dn + dv, r) x block[:r] (r, Tk)`` gives
``k_nope^T`` and ``v^T``, the shared rotary key ``k_r^T`` is ``block[r:]``, the scores
are ``[q_nope | q_rope] (Tq, dn + dr) x [k_nope^T ; k_r^T]`` and the context
``e (Tq, Tk) x v^T^T``. No K or V of a cached position ever reaches HBM.

A grid over (head, key block); ``layer``, ``slot`` and ``offset`` are prefetched
scalars, so the layers of a program share one traced body
(`pallas_common.traced_once`) and every chunk of every request the one compiled
program. ``offset`` is any value (the engine slides a prompt's last window left
at the slot's end). A key block past the chunk's last live one is not fetched (its
index map names the last live block again) and not computed; a block whose every
key lies at or before the chunk's first query takes the body without a mask; the
blocks that cross the diagonal build the mask and zero the VALUES past the chunk's
end (what a free position holds is never counted: a 0 probability times a NaN is
a NaN). A step attends ALL the chunk's queries against the block: walking them in
sub-blocks to skip the pairs past the diagonal was measured and lost (at 256 x 256
pairs the kernel takes 2.4x the time, at 512 x 512 1.4x: the (Tq, 1) maximum and
sum and the accumulator's rescaling are paid a pair; PERF.md section 6, PR 53).

Precision is the plain body's: operands in the compute type, float32
accumulation of every product, the expansion's ``k_nope`` / ``v`` rounded to the
compute type, float32 scores, maximum, sum and accumulator, the exponentials cast
to the compute type for the second product.

The ``pl.pallas_call`` name ``mla_chunk`` is what a device trace shows under
``attn_core`` > ``expand`` (the kernel IS the expansion; PERF.md section 3).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.ops import pallas_common

F32 = jnp.float32
_LANES = 128
#: keys a grid step fetches, expands and attends
KEY_BLOCK = 1024
#: rows a chunk may hold (what was measured): the float32 scores (s, KEY_BLOCK) are
#: 4 MiB of VMEM at 1,024, the exponentials 2, the queries, accumulator, maximum and
#: sum 2 more
MAX_CHUNK_ROWS = 1024


def chunk_path(positions: int, width: int, rows: int, dims: Tuple[int, ...], dtype) -> str:
    """``"kernel"`` or ``"plain"`` for a prompt chunk of ``rows`` queries over a slot
    of ``positions`` keys of ``width`` = r + dr values, ``dims`` = (heads, dn, dr,
    dv, r), from the shapes and the backend alone: no flag, no environment variable,
    no model's name. `models/mla.attend_chunk` and `models/mla.chunk_layout`
    both ask here. The kernel takes a TPU, or the CPU (interpreted:
    `pallas_common.use_interpret`); bf16 or float32; a capacity of whole key
    blocks; at most ``MAX_CHUNK_ROWS`` rows; and, compiled, shapes the chip tiles
    (dn, dv and r whole lane tiles; dr and the rows whole sublane tiles of the
    compute type) with a latent width that is NOT a whole lane tile (the chip then
    keeps the positions on the lanes, the layout the kernel reads in place:
    ``ops/mla_decode.py``). Everything else keeps the plain body."""
    _, dn, dr, dv, r = dims
    if jax.default_backend() not in ("tpu", "cpu"):
        return "plain"
    if jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return "plain"
    sublanes = 32 // jnp.dtype(dtype).itemsize  # rows of a packed tile
    laid_out = pallas_common.use_interpret() or (
        dn % _LANES == 0 and dv % _LANES == 0 and r % _LANES == 0
        and dr % sublanes == 0 and rows % sublanes == 0 and width % _LANES != 0)
    inside = positions % KEY_BLOCK == 0 and rows <= MAX_CHUNK_ROWS and laid_out
    return "kernel" if inside else "plain"


def _init(j, m_ref, l_ref, acc_ref):
    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, pallas_common.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)


def _attend_block(q_ref, k_t, v_t, m_ref, l_ref, acc_ref, offset, start, *, scale: float,
                  masked: bool):
    """The running softmax's update for the chunk's queries ``q_ref`` (s, dn + dr) at
    positions ``offset + [0, s)`` against a block of keys ``k_t`` (dn + dr, Tk) at
    positions ``start + [0, Tk)`` with values ``v_t`` (dv, Tk)."""
    rows, block_k = q_ref.shape[0], k_t.shape[1]
    scores = jnp.dot(q_ref[...], k_t, preferred_element_type=F32) * scale
    if masked:
        k_pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        q_pos = offset + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        scores = jnp.where(k_pos <= q_pos, scores, pallas_common.NEG_INF)
        v_t = jnp.where(k_pos < offset + rows, v_t, jnp.zeros_like(v_t))
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    shrink = jnp.exp(m_prev - m_new)
    e = jnp.exp(scores - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * shrink + jnp.sum(e, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * shrink + jax.lax.dot_general(
        e.astype(v_t.dtype), v_t, (((1,), (1,)), ((), ())), preferred_element_type=F32)


def _finalize(j, o_ref, l_ref, acc_ref):
    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _kernel(layer_ref, slot_ref, offset_ref, q_ref, w_ref, kt_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale: float, rank: int, nope: int):
    del layer_ref, slot_ref  # (the index maps')
    j = pl.program_id(1)
    offset = offset_ref[0]  # the first query's position
    rows, block_k = q_ref.shape[0], kt_ref.shape[1]
    start = j * block_k
    _init(j, m_ref, l_ref, acc_ref)

    def accumulate(masked: bool):
        latent_t = kt_ref[...]  # (r + dr, Tk)
        # the expansion, in the orientation the cache block has: (dn + dv, r) x (r, Tk)
        kv_t = jnp.dot(w_ref[...], latent_t[:rank], preferred_element_type=F32).astype(latent_t.dtype)
        # [k_nope^T ; k_r^T]: the rotary key is the one every head shares
        k_t = jnp.concatenate([kv_t[:nope], latent_t[rank:]], axis=0)
        _attend_block(q_ref, k_t, kv_t[nope:], m_ref, l_ref, acc_ref, offset, start,
                      scale=scale, masked=masked)

    # (block 0 holds position 0, which every query sees: the maximum is real from
    # the first block on)
    whole = start + block_k <= offset + 1
    pl.when(whole)(functools.partial(accumulate, False))
    pl.when(jnp.logical_and(jnp.logical_not(whole), start < offset + rows))(
        functools.partial(accumulate, True))
    _finalize(j, o_ref, l_ref, acc_ref)


def _attend(layer, slot, offset, q, w_t, stacked_t, *, scale: float, block_k: int, rank: int,
            nope: int, interpret: bool):
    """``q`` (n, s, dn + dr) head-major and ``w_t`` (n, dn + dv, r) against row
    ``slot`` of layer ``layer`` of ``stacked_t`` (layers, rows, r + dr, positions);
    -> (s, n x dv), a head's values side by side."""
    n, s, qk = q.shape
    dv = w_t.shape[1] - nope
    width, positions = stacked_t.shape[2:]
    blocks = positions // block_k

    def live_block(h, j, layer_ref, slot_ref, offset_ref):
        last = jnp.minimum((offset_ref[0] + s - 1) // block_k, blocks - 1)
        return layer_ref[0], slot_ref[0], 0, jnp.minimum(j, last)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n, blocks),
        in_specs=[
            pl.BlockSpec((None, s, qk), lambda h, j, *_: (h, 0, 0)),
            pl.BlockSpec((None, nope + dv, rank), lambda h, j, *_: (h, 0, 0)),
            pl.BlockSpec((None, None, width, block_k), live_block),
        ],
        out_specs=pl.BlockSpec((s, dv), lambda h, j, *_: (0, h)),
        scratch_shapes=[pltpu.VMEM((s, 1), F32), pltpu.VMEM((s, 1), F32),
                        pltpu.VMEM((s, dv), F32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, rank=rank, nope=nope),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, n * dv), q.dtype),
        compiler_params=pallas_common.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_chunk",
    )(layer, slot, offset, q, w_t, stacked_t)


def latent_chunk_attention(q_nope, q_rope, stacked, layer: int, slot, offset, wkvb, *,
                           dims: Tuple[int, ...], scale: float):
    """The chunk form for the chunk at ``offset`` of row ``slot`` of layer ``layer`` of
    ``stacked`` (layers, rows, positions, r + dr): ``q_nope`` (1, s, n, dn) and the
    rotated ``q_rope`` (1, s, n, dr) against the row's positions [0, offset + s),
    expanded through ``wkvb`` (r, n, dn + dv) -> (1, s, n, dv) in the queries' type.
    ``slot`` and ``offset`` may be traced; ``dims`` = (heads, dn, dr, dv, r);
    ``KEY_BLOCK`` divides the positions."""
    n, dn, _, dv, r = dims
    s = q_nope.shape[1]
    one = lambda v: jnp.reshape(jnp.asarray(v, jnp.int32), (1,))  # noqa: E731
    # head-major queries [nope | rope] and a head's expansion transposed: one pass
    # each over 25 MB and 17 MB at the published widths, where the kernel's blocks
    # want them
    q = jnp.transpose(jnp.concatenate([q_nope, q_rope], axis=-1)[0], (1, 0, 2))
    w_t = jnp.transpose(wkvb.astype(q.dtype), (1, 2, 0))
    out = pallas_common.traced_once(
        _attend, one(layer), one(slot), one(offset), q, w_t, jnp.swapaxes(stacked, 2, 3),
        scale=float(scale), block_k=KEY_BLOCK, rank=r, nope=dn,
        interpret=pallas_common.use_interpret())
    return out.reshape(1, s, n, dv)
