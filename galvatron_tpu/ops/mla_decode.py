"""A decode window's latent attention (MLA, absorbed form) over the stacked latent
slot cache, as one Pallas kernel bounded a row by the row's own length.

    ctx[b, i] = softmax_j<=pos(b, i) (q_cat[b, i] . latent[layer, b, j] * scale) latent[layer, b, j, :r]

``q_cat`` is ``[q_nope W_kvb,k^T | q_rope]`` (B, s, n, r + dr) as
``models/mla.py`` builds it, ``latent`` the cache ``(layers, rows, positions,
r + dr)`` WHOLE: the kernel's index map names ``layer`` (a prefetched scalar, so
the five layers of a step share one traced body, `pallas_common.traced_once`),
the row and a block of ``KEY_BLOCK`` keys, and nothing copies a layer's slab out
(XLA would, handed ``stacked[layer]``: 604 MB a layer at 32 x 16,384 x 576).

In place means in the layout the chip keeps: for a width that is no multiple of
128 (576) its compiler lays a slot out with the POSITIONS on the lanes and the
r + dr values on the sublanes (``{2,3,1,0:T(8,128)(2,1)}``; probed for a described
v5e: 576, 320 and 192 wide so, 512 and 640 row-major; PERF.md section 6, PR 52). So
the kernel is handed ``swapaxes(stacked, 2, 3)``, which is a bitcast of that, and
its blocks are (r + dr, Tk): scores = q (s x n, r + dr) x block, context =
e (s x n, Tk) x block[:r]^T. Handed the cache position-major the compiler copies all
of it in and out every step; `decode_path` keeps a row-major width on the plain
body for that reason, and ``tests/test_topology_aot.py`` holds the compiled step to
no copy of a slab.

A grid over (row, key block). The rows' first query positions are prefetched;
a row's window attends ``offset + s`` positions. A block past the row's last
live block is not fetched (its index map names the last live block again, so no
DMA is issued) and not computed; a block whose every key lies at or before the
row's first query takes the body without a mask; the last live block masks the
keys past each query's position and zeroes the VALUES past the window (what a
free position holds is never counted: a 0 probability times a NaN is a NaN).
All n heads of a row share the one latent block; the softmax runs over the blocks
with a float32 maximum, sum and accumulator, the exponentials cast to the compute
type for the second product (the plain body's precision,
``models/mla._plain_context``).

With a SELECTION (``latent_attention(selected=)``, PR 65: a learned sparse attention's
keys, ``models/mla.select_mask``, one query a row) a fifth operand (rows, 1, positions)
int32 rides the latent's block map: every live block takes the masked body with the
selection in place of ``k <= q``, and a row is still read up to its own length only.

A RING (``latent_attention(span=)``, PR 71: a sliding layer's latent, position p at
place ``p mod R``, `models/mla.attend_ring`) is read along the ARC its window holds and
nowhere else: a row whose queries stand at ``first .. first + s - 1`` sees positions
``(first - span, first + s - 1]``, one run of at most ``span + s - 1`` places that may wrap
the ring's end. The grid is (row, `pallas_common.ring_steps`), a STATIC count; step j names
block ``(b0 + j) mod n`` from the prefetched ``first`` (`pallas_common.ring_arc`), and a
step past the row's last needed block names that block again (no DMA, no body). Every
fetched block takes the masked body, which reads the ABSOLUTE position a place holds from
two scalars a row (``lap``, ``newest``: `generation._ring_key_positions`'s rule, no division
in the vector unit) and lets a query see ``k_pos <= q_pos``, ``k_pos > q_pos - span``,
``k_pos >= 0``; the values of a place no query of the window sees (never written, a lap
ago, the slot's previous request) are zeroed. A block with no key a query sees counts 1 a
key against the running maximum's start; the first key the query does see shrinks that to
an exact 0 (`modeling.running_softmax`'s rule). The key block is the shape's
(`ring_block`), not ``KEY_BLOCK``: the smaller it is the closer the fetched places follow
the arc, and the more grid steps a row pays.

The ``pl.pallas_call`` name ``mla_decode`` is what a device trace shows under
``attn_core`` (PERF.md section 3).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.ops import pallas_common

F32 = jnp.float32
_LANES = 128
#: keys a grid step fetches and attends
KEY_BLOCK = 1024
#: query rows (s x n) a row's window may hold: the float32 scores (rows, KEY_BLOCK)
#: and accumulator (rows, r) are 2 + 1 MiB of VMEM at 512
MAX_QUERY_ROWS = 512
#: key blocks a RING may be read in (`ring_block` picks one by the ring's shape)
RING_BLOCKS = (128, 256, 512)
#: what a grid step costs beside its block's places, in ring PLACES: the chip's A/B of the
#: three blocks at the dots3 cell's ring (`experiments/ab_mla_decode.py --ring`, PERF.md
#: section 6, PR 71: 4.21 / 3.51 / 3.74 us a row of 1,088-wide bf16 latent at 5 x 128 / 3 x
#: 256 / 2 x 512, twice to the digit) fits 3.0 ns a place and 0.54 us a step
RING_STEP_PLACES = 180


def decode_path(positions: int, width: int, query_rows: int, rank: int, dtype,
                span: int = 0) -> str:
    """``"kernel"`` or ``"plain"`` for a decode window over slots of ``positions``
    keys of ``width`` = r + dr values with ``query_rows`` = s x n queries a row, from
    the shapes and the backend alone: no flag, no environment variable, no model's
    name. `models/mla.attend_window`, `models/mla.attend_ring` (``span`` > 0: the slots
    are rings of ``positions`` places under a window of ``span``) and
    `models/mla.cache_read_positions` all ask here. The kernel takes a TPU, or the CPU
    (interpreted: `pallas_common.use_interpret`, the one switch of this repo's
    kernels); a capacity of whole key blocks (a ring: one of `ring_block`'s); at most
    ``MAX_QUERY_ROWS`` query rows; bf16 or float32; and, compiled, a rank of whole lane
    tiles and a width that is NOT one (the chip then keeps the positions on the lanes,
    the layout the kernel reads in place: the module's docstring; 576 and, PR 71, 1,088
    compiled for a described v5e). Everything else keeps the plain body."""
    if jax.default_backend() not in ("tpu", "cpu"):
        return "plain"
    if jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return "plain"
    laid_out = pallas_common.use_interpret() or (rank % _LANES == 0 and width % _LANES != 0)
    blocked = ring_block(positions, span) > 0 if span else positions % KEY_BLOCK == 0
    inside = blocked and query_rows <= MAX_QUERY_ROWS and laid_out
    return "kernel" if inside else "plain"


def ring_block(places: int, span: int) -> int:
    """The key block a ring of ``places`` places under a window of ``span`` is read in,
    by shape: of `RING_BLOCKS` that divide the ring the one whose walk of a decode
    step's arc (`pallas_common.ring_steps` blocks a row) costs least, a step counted as
    its block's places plus ``RING_STEP_PLACES``; 0: no block divides the ring (a ring
    capped at a short ``max_len``), the plain body's."""
    cost = {block: pallas_common.ring_steps(1, span, places, block) * (block + RING_STEP_PLACES)
            for block in RING_BLOCKS if places % block == 0}
    return min(cost, key=cost.get, default=0)


def ring_read_positions(lengths, rows: int, places: int, span: int, window: int = 1) -> int:
    """Places the kernel fetches of ONE ring layer's ``rows`` rings of ``places`` by
    construction, given the positions the windows of the rows in use attend
    (``lengths``): the blocks each row's arc touches (its length rounded up to the block
    until it has passed the window, `pallas_common.ring_steps` blocks once the arc is
    whole); a row out of use attends position 0 of its free ring, one block (host
    arithmetic)."""
    block = ring_block(places, span)
    blocks = sum(pallas_common.ring_arc(int(n) - window, window, span, places, block)[1]
                 for n in lengths)
    return (blocks + rows - len(lengths)) * block


def _kernel(layer_ref, first_ref, q_ref, kt_ref, *rest, scale: float, block_k: int, heads: int,
            window: int, rank: int, selected: bool, span: int, ring: int):
    """``selected``: a fifth operand, the keys a row's ONE query attends (1, Tk) int32 (a
    learned selection: `models/mla.select_mask`), in place of every key at or before it.
    ``span`` > 0: the slots are rings of ``ring`` places and grid step j is block j of the
    row's ARC (`pallas_common.ring_arc`)."""
    del layer_ref  # (the index map's)
    seen_ref, (o_ref, m_ref, l_ref, acc_ref) = (rest[0], rest[1:]) if selected else (None, rest)
    row, j = pl.program_id(0), pl.program_id(1)
    first = first_ref[row]  # the first query's position
    length = first + window  # the positions the row's window attends
    if span:
        b0, needed = pallas_common.ring_arc(first, window, span, ring, block_k)
        start = (b0 + j) % (ring // block_k) * block_k
        # a ring's place holds a position of the row's newest lap up to the place of
        # its last write, of the lap before past it (negative: never written)
        lap = (length - 1) // ring * ring
        newest = length - 1 - lap
    else:
        start = j * block_k

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, pallas_common.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(masked: bool):
        q, kt = q_ref[...], kt_ref[...]  # (s x n, r + dr), (r + dr, Tk)
        scores = jnp.dot(q, kt, preferred_element_type=F32) * scale
        values = kt[:rank]
        if masked:
            k_pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            q_pos = first + jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], 1), 0) // heads
            if span:
                k_pos = k_pos + jnp.where(k_pos <= newest, lap, lap - ring)
                seen = (k_pos <= q_pos) & (k_pos > q_pos - span) & (k_pos >= 0)
            else:
                seen = k_pos <= q_pos if seen_ref is None else seen_ref[...] != 0
            scores = jnp.where(seen, scores, pallas_common.NEG_INF)
            kept = (k_pos > first - span) & (k_pos >= 0) if span else k_pos < length
            values = jnp.where(kept, values, jnp.zeros_like(values))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        shrink = jnp.exp(m_prev - m_new)
        e = jnp.exp(scores - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * shrink + jnp.sum(e, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * shrink + jax.lax.dot_general(
            e.astype(values.dtype), values, (((1,), (1,)), ((), ())),
            preferred_element_type=F32)

    # (block 0 holds position 0, which every query sees: the maximum is real from
    # the first block on; under a selection a block may hold no key the query attends:
    # its keys count 1 each against NEG_INF until the first attended key shrinks that to
    # an exact 0, as `modeling.running_softmax` has it, and no block is taken whole)
    if span:
        pl.when(j < needed)(functools.partial(accumulate, True))
    else:
        whole = start < 0 if selected else start + block_k <= first + 1
        pl.when(whole)(functools.partial(accumulate, False))
        pl.when(jnp.logical_and(jnp.logical_not(whole), start < length))(
            functools.partial(accumulate, True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _attend(layer, first, q, stacked_t, *seen, scale: float, block_k: int, heads: int,
            rank: int, interpret: bool, span: int = 0):
    """``q`` (B, s x n, r + dr), rows of a window query-major, against ``stacked_t``
    (layers, rows, r + dr, positions); -> (B, s x n, r). ``seen`` (none, or one (B, 1,
    positions) int32): the keys a row's one query attends (`_kernel`). ``span`` > 0: the
    rows are rings, walked along the window's arc."""
    b, rows, width = q.shape
    positions = stacked_t.shape[3]
    blocks = positions // block_k
    window = rows // heads

    if span:
        def live_block(row, j, layer_ref, first_ref):
            b0, needed = pallas_common.ring_arc(first_ref[row], window, span, positions, block_k)
            return layer_ref[0], row, 0, (b0 + jnp.minimum(j, needed - 1)) % blocks

        steps = pallas_common.ring_steps(window, span, positions, block_k)
    else:
        def live_block(row, j, layer_ref, first_ref):
            last = jnp.minimum((first_ref[row] + window - 1) // block_k, blocks - 1)
            return layer_ref[0], row, 0, jnp.minimum(j, last)

        steps = blocks

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, steps),
        in_specs=[
            pl.BlockSpec((None, rows, width), lambda row, j, *_: (row, 0, 0)),
            pl.BlockSpec((None, None, width, block_k), live_block),
        ] + [pl.BlockSpec((None, 1, block_k),
                          lambda row, j, *refs: (row, 0, live_block(row, j, *refs)[3]))
             for _ in seen],
        out_specs=pl.BlockSpec((None, rows, rank), lambda row, j, *_: (row, 0, 0)),
        scratch_shapes=[pltpu.VMEM((rows, 1), F32), pltpu.VMEM((rows, 1), F32),
                        pltpu.VMEM((rows, rank), F32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=block_k, heads=heads, window=window,
                          rank=rank, selected=bool(seen), span=span, ring=positions),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, rank), q.dtype),
        compiler_params=pallas_common.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_decode",
    )(layer, first, q, stacked_t, *seen)


def latent_attention(q_cat, stacked, layer: int, first, *, rank: int, scale: float,
                     block_k: Optional[int] = None, selected=None, span: int = 0):
    """The context over the latent of the windows whose first queries stand at
    ``first`` (B,): ``q_cat`` (B, s, n, r + dr) against rows [0, B) of layer
    ``layer`` of ``stacked`` (layers, rows >= B, positions, r + dr) -> (B, s, n, r)
    in ``q_cat``'s type. ``block_k`` (None: ``KEY_BLOCK``) divides the positions.
    ``selected`` (B, 1, positions) bool, windows of ONE query: the keys each attends, in
    place of every key at or before it; a row is still read up to its own length only.
    ``span`` > 0: ``stacked`` is a stack of RINGS (position p at place p mod positions)
    and a query sees its last ``span`` positions; ``block_k`` is `ring_block`'s."""
    b, s, n, width = q_cat.shape
    seen = () if selected is None else (selected.astype(jnp.int32),)
    if seen and s != 1:
        raise ValueError(f"a selection is one query's; the window has {s}")
    if seen and span:
        raise ValueError("a selection is a full layer's; a ring has its window")
    out = pallas_common.traced_once(
        _attend, jnp.full((1,), layer, jnp.int32), first.astype(jnp.int32),
        q_cat.reshape(b, s * n, width), jnp.swapaxes(stacked, 2, 3), *seen, scale=float(scale),
        block_k=block_k or KEY_BLOCK, heads=n, rank=rank, interpret=pallas_common.use_interpret(),
        span=int(span))
    return out.reshape(b, s, n, rank)
