"""Decomposed collective-matmul for the TP projection seams.

GSPMD partitions a sequence-parallel column-parallel projection as
``all-gather(x over seq) → matmul`` and its row-parallel dual as
``matmul → reduce-scatter(y over seq)`` — both with the collective
*blocking* the GEMM. This module implements the decomposition of
"Overlap Communication with Dependent Computation via Decomposition in
Large Deep Learning Models" (Wang et al., ASPLOS'23): the operand (or the
partial-sum accumulator) circulates the TP ring one chunk per step via
``ppermute`` while the GEMM runs on the chunk already in hand, so the
per-hop transfer hides behind a 1/T-sized matmul instead of serializing
in front of a full one.

Two entry points, einsum-parameterized so one implementation serves the
qkv / MLP-up / attention-out / MLP-down seams (parallel/placement.py's
proj_up / proj_down dispatch here when the layer strategy sets ``tp_overlap``):

- :func:`allgather_einsum` — all-gather⊗matmul. ``x`` arrives logically
  seq-sharded over the TP axes (the sp layer boundary layout); each
  device GEMMs the piece of a seq chunk it holds against its local weight
  shard and passes the piece on, writing each result at the originating
  chunk's seq offset. Output: full seq, weight-shard dim TP-sharded.
- :func:`einsum_reducescatter` — matmul⊗reduce-scatter. Each device GEMMs
  one seq chunk per step and adds it (the GEMM's fp32 result, rounded once
  per hop) into an accumulator that travels the ring; after T steps device
  i holds the fully-summed chunk i (the sp seq-sharded output layout).
  ``scatter_output=False`` (no sp) appends tiled all-gathers to reconstruct
  the replicated output — the gather half of the all-reduce still blocks.

The ring, as a chip wants it (four v5e chips, PERF.md §6, PR 29):

- **order** (:func:`ring_order`): the ring visits the TP group's devices so
  that every hop is one ICI link, from the devices' ``coords`` (the row-major
  flattening of two binary mesh axes over a 2x2 makes two of four hops
  diagonals). Without coordinates (the CPU simulation) or without such a
  cycle it is the flattened index order.
- **two-way**: with more than two devices each chunk (or accumulator) is
  split in halves along the sequence that travel in opposite directions, so
  both of a device's incoming links carry half a chunk per hop.
- **assembly**: a result is assembled from whole leading slices only, which
  the compiler writes in place (a piece put at a sequence offset of a
  ``[b, S, n]`` result is a strided copy of its own). So a seam whose output
  dims are in a plain GEMM's order (the MLP's two) rings over the *sequence*
  into piece-major buffers; a seam whose all-gather side puts out head-major
  dims (``bcnsd`` of the stacked qkv projection, ``bnsd`` of the output
  projection's cotangent), where the sequence is not the leading dim, is a
  ring in its other direction and pipelines that side over the *batch*,
  which does lead: one tiled all-gather and one GEMM a piece of the
  micro-batch's rows, piece b+1's gather in flight under piece b's GEMM
  (:func:`_allgather_matmul`). With one row, or pieces too short, it gathers
  whole in front of one GEMM (:func:`batch_pieces`).
- **shape test** (:func:`ring_pays`): a seam takes the ring only where a
  piece's GEMM is long enough to cover a good part of its hop, which its
  shapes tell (:func:`hop_cover`); elsewhere, and wherever the chunking does
  not divide, it is the plain ``jnp.einsum`` (GSPMD collectives). The search
  prices a ``tp_overlap`` layer from the same functions
  (:func:`exposed_share`, :func:`batch_exposed_share`).
- **backward**: each entry point is a ``custom_vjp`` whose backward is the
  other ring (the transpose of AG⊗matmul is matmul⊗RS and vice versa) plus
  one whole weight-gradient GEMM, accumulated in fp32, on the gathered
  operand that the all-gather ring keeps on its way: no per-step accumulation
  of ``dw``, no scatter-add of slices that autodiff's transpose would emit.
- **one trace per distinct seam**: forward and backward of either kind are
  four ``jax.jit`` programs that the ``custom_vjp`` rules only bind.

Scopes ``allgather_einsum`` / ``einsum_reducescatter`` wrap the hops (and the
gathers of a batch-wise side) only: the piece GEMMs stay under the caller's scope
(``qkv_proj`` / ``out_proj`` / ``mlp``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# the shape test, shared with the search's pricing (no jax below this line
# until the rings)
# ---------------------------------------------------------------------------

#: MXU FLOPs a chip does in the time one ICI link direction moves one byte:
#: v5e 197 TFLOP/s bf16 over ~45 GB/s a link and direction (two incoming links
#: of the 2x2 gave 88 GB/s, PERF.md §5). v4 (275 / 50) and v5p (459 / 100) are
#: within 25% of it.
MXU_FLOPS_PER_LINK_BYTE = 4400.0
#: a seam takes the ring from this hop cover up: the narrowest seam timed on
#: four v5e chips (opt-1.3b's attention output projection, local contraction
#: 512, cover 0.233) still won alone, 0.966 against 1.085 ms forward + backward
#: (PERF.md §6, PR 29); below it nothing is measured, and the plain einsum stays
RING_MIN_COVER = 0.2
#: rows of one piece below which its GEMM no longer fills the MXU
RING_MIN_PIECE_ROWS = 256


def ring_ways(tp: int) -> int:
    """Directions the ring sends in: both once there are more than two
    devices (with two, both directions are the same link)."""
    return 2 if tp > 2 else 1


def hop_cover(tp: int, local_width: int, itemsize: int) -> float:
    """Time of one piece's GEMM over the time of the hop it has to cover, at
    the MXU's peak and a link's rate. The rows cancel: per row a hop moves
    ``other_width * itemsize / ways`` bytes and the GEMM does ``2 *
    other_width * local_width`` FLOPs, where ``local_width`` is the seam's
    device-local width that does NOT travel (all-gather side: the local
    output columns; reduce-scatter side: the local contraction)."""
    return 2.0 * local_width * ring_ways(tp) / itemsize / MXU_FLOPS_PER_LINK_BYTE


def ring_pays(tp: int, chunk_rows: int, local_width: int, itemsize: int) -> bool:
    """Whether a seam of these shapes takes the ring. ``chunk_rows``: rows
    (batch x sequence, device-local) of one sequence chunk."""
    if tp <= 1:
        return False
    if chunk_rows // ring_ways(tp) < RING_MIN_PIECE_ROWS:
        return False
    return hop_cover(tp, local_width, itemsize) >= RING_MIN_COVER


def exposed_share(tp: int, chunk_rows: int, local_width: int, itemsize: int,
                  backward_gemms: int = 1) -> float:
    """Share of a seam's collective time that stays exposed: all of it on the
    plain path, what the piece GEMMs do not cover on the ring.
    ``backward_gemms``: GEMMs that run on each piece in hand (the backward of
    a reduce-scatter seam computes dx on the piece while dw waits for all)."""
    if not ring_pays(tp, chunk_rows, local_width, itemsize):
        return 1.0
    return max(0.0, 1.0 - backward_gemms * hop_cover(tp, local_width, itemsize))


#: pieces the batch-wise pipeline cuts a micro-batch into where it can: the
#: seam table (experiments/tp_overlap_seams.py, PERF.md §6, PR 32) is timed at
#: 2 and at 4 on a device-local batch of 4
BATCH_PIECES = (4, 2)


def gather_cover(tp: int, local_width: int, itemsize: int) -> float:
    """Time of a row's GEMM over the time of that row's whole all-gather over
    the sequence (every device receives ``(tp - 1) / tp`` of the row, over the
    links :func:`ring_ways` counts): :func:`hop_cover` scaled from a hop to a
    gather."""
    return hop_cover(tp, local_width, itemsize) * tp / (tp - 1)


def batch_pieces(tp: int, local_batch: int, rows_per_sample: int, local_width: int,
                 itemsize: int) -> int:
    """Pieces along the batch that a head-major all-gather side (qkv forward,
    out_proj backward) gathers and multiplies one after the other, the next
    piece's gather under this piece's GEMM; 1 is the whole gather in front of
    one GEMM. ``local_batch``: samples of the micro-batch a device holds;
    ``rows_per_sample``: the gathered sequence; ``local_width``: the GEMM's
    device-local output columns. The largest of :data:`BATCH_PIECES` that
    divides the batch and leaves a piece's GEMM :data:`RING_MIN_PIECE_ROWS`
    rows, where the seam is wide enough to take the ring at all."""
    if tp <= 1 or hop_cover(tp, local_width, itemsize) < RING_MIN_COVER:
        return 1
    for p in BATCH_PIECES:
        if local_batch % p == 0 and local_batch // p * rows_per_sample >= RING_MIN_PIECE_ROWS:
            return p
    return 1


def batch_exposed_share(tp: int, local_batch: int, rows_per_sample: int, local_width: int,
                        itemsize: int) -> float:
    """Share of a head-major all-gather side's collective time that stays
    exposed: all of it gathered whole, else the first piece's gather and what
    a piece's GEMM leaves of the next piece's."""
    p = batch_pieces(tp, local_batch, rows_per_sample, local_width, itemsize)
    if p == 1:
        return 1.0
    uncovered = max(0.0, 1.0 - gather_cover(tp, local_width, itemsize))
    return (1.0 + (p - 1) * uncovered) / p


def ring_order(coords: Sequence[Sequence[Optional[Tuple[int, ...]]]]) -> Tuple[int, ...]:
    """Cyclic order of a TP group's ring positions in which every hop joins
    two devices one ICI link apart. ``coords[g][i]``: physical coordinates of
    the device at flattened TP index ``i`` of group ``g`` (one ``ppermute``
    serves every group, so a hop must be a link in each). Falls back to
    ``0..T-1`` where a device has no coordinates or no such cycle exists."""
    T = len(coords[0])
    identity = tuple(range(T))
    if T <= 2 or any(c is None for g in coords for c in g):
        return identity

    def linked(a: int, b: int) -> bool:
        return all(sum(abs(p - q) for p, q in zip(g[a], g[b])) <= 1 for g in coords)

    def extend(path):
        if len(path) == T:
            return path if linked(path[-1], path[0]) else None
        for nxt in range(T):
            if nxt not in path and linked(path[-1], nxt):
                found = extend(path + [nxt])
                if found:
                    return found
        return None

    return tuple(extend([0]) or identity)


# ---------------------------------------------------------------------------
# the rings
# ---------------------------------------------------------------------------


def tp_group_size(mesh, tp_axes: Sequence[str]) -> int:
    """Flattened TP ring size T — the product of the tp mesh-axis extents."""
    t = 1
    for a in tp_axes or ():
        t *= mesh.shape[a]
    return int(t)


def _parse(subscripts: str) -> Tuple[str, str, str]:
    ins, out = subscripts.replace(" ", "").split("->")
    x_sub, w_sub = ins.split(",")
    return x_sub, w_sub, out


def _axis_entry(axes: Tuple[str, ...]):
    """PartitionSpec entry for a (possibly multi-) mesh-axis group."""
    return axes if len(axes) > 1 else axes[0]


@functools.lru_cache(maxsize=None)
def mesh_ring_order(mesh, tp: Tuple[str, ...]) -> Tuple[int, ...]:
    """:func:`ring_order` of ``mesh``'s TP groups over the axes ``tp`` (the
    flattened index is ``jax.lax.axis_index(tp)``: row-major, first axis most
    significant)."""
    names = list(mesh.axis_names)
    rest = [a for a in names if a not in tp]
    devs = np.transpose(mesh.devices, [names.index(a) for a in rest + list(tp)])
    groups = devs.reshape(-1, tp_group_size(mesh, tp))

    def where(d):
        c = getattr(d, "coords", None)
        return None if c is None else tuple(c) + (getattr(d, "core_on_chip", 0),)

    return ring_order([[where(d) for d in g] for g in groups])


@dataclasses.dataclass(frozen=True)
class _Seam:
    """What one ring is traced from, hashable: the static argument of the
    jitted seam."""

    kind: str  # "ag": all-gather⊗matmul; "rs": matmul⊗reduce-scatter
    subscripts: str
    mesh: Any
    dp: Tuple[str, ...]
    tp: Tuple[str, ...]
    w_shard_dim: int
    seq: str
    order: Tuple[int, ...]
    scatter_output: bool = True
    # applied to x before the GEMM of a reduce-scatter seam, inside its
    # programs: the backward recomputes it from x, so that the seam keeps the
    # pre-activation (which the activation's own backward needs anyway) and
    # not a second full-width copy of what it multiplied
    activation: Optional[Callable] = None

    @property
    def subs(self) -> Tuple[str, str, str]:
        return _parse(self.subscripts)


def _widths(sub: Tuple[str, str, str], x_shape, w_shape) -> Dict[str, int]:
    x_sub, w_sub, _ = sub
    dims = dict(zip(x_sub, x_shape))
    dims.update(zip(w_sub, w_shape))
    return dims


def _seam(kind: str, subscripts: str, x, w, mesh, dp_axes, tp_axes, w_shard_dim: int,
          seq: str, scatter_output: bool = True,
          activation: Optional[Callable] = None) -> Optional[_Seam]:
    """The ring's description for these operands, or None where the seam stays
    the plain einsum: no ring to form, a dim the chunking does not divide, or
    shapes on which the ring does not pay (:func:`ring_pays`)."""
    x_sub, w_sub, out_sub = sub = _parse(subscripts)
    tp, dp = tuple(tp_axes or ()), tuple(dp_axes or ())
    T = tp_group_size(mesh, tp)
    if T <= 1 or mesh.devices.size <= 1:
        return None
    dims = _widths(sub, x.shape, w.shape)
    D = tp_group_size(mesh, dp)
    shard_letter = w_sub[w_shard_dim]
    if dims[seq] % T or dims[shard_letter] % T or (dp and dims[x_sub[0]] % D):
        return None
    rows = int(np.prod([dims[c] for c in x_sub if c in out_sub and c not in w_sub]))
    w_size = int(np.prod(w.shape))
    if kind == "ag":  # local output columns: w's letters that reach the output
        other = int(np.prod([dims[c] for c in w_sub if c not in out_sub]))
    else:  # local contraction: w's letters that do not
        other = int(np.prod([dims[c] for c in w_sub if c in out_sub]))
    if not ring_pays(T, rows // D // T, w_size // other // T, jnp.dtype(x.dtype).itemsize):
        return None
    return _Seam(kind, subscripts, mesh, dp, tp, w_shard_dim, seq, mesh_ring_order(mesh, tp),
                 scatter_output, activation)


def _lanes(order: Tuple[int, ...], s_local: int):
    """The ring's lanes as ``(seq offset in the chunk, rows, direction)``:
    two halves that travel in opposite directions, or the whole chunk one way
    (two devices, or a chunk of odd length)."""
    if ring_ways(len(order)) == 2 and s_local % 2 == 0:
        return ((0, s_local // 2, 1), (s_local // 2, s_local // 2, -1))
    return ((0, s_local, 1),)


def _perm(order: Tuple[int, ...], direction: int):
    T = len(order)
    return [(order[p], order[(p + direction) % T]) for p in range(T)]


def _chunk_at(order: Tuple[int, ...], tp, shift: int):
    """Flattened index of the device ``shift`` ring positions behind this one
    (whose sequence chunk that is)."""
    T = len(order)
    pos = {dev: p for p, dev in enumerate(order)}
    table = [order[(pos[i] - shift) % T] for i in range(T)]
    return jnp.asarray(table, jnp.int32)[jax.lax.axis_index(tp)]


#: the piece axis of a piece-major operand in an einsum (no seam uses capitals)
_PIECE = "P"


def _natural(sub: Tuple[str, str, str]) -> str:
    """The output dims in the order a plain GEMM gives them: x's, then w's."""
    x_sub, w_sub, out_sub = sub
    return "".join(c for c in x_sub + w_sub if c in out_sub)


def _batch_split(sub: Tuple[str, str, str], x_l, w_l, T: int, seq_x: int):
    """``x_l`` in the pieces along its leading batch dim that a head-major
    all-gather side pipelines over (:func:`batch_pieces`); whole where the
    batch does not lead both ``x`` and the output."""
    x_sub, w_sub, out_sub = sub
    if seq_x == 0 or x_sub[0] != out_sub[0] or x_sub[0] in w_sub:
        return [x_l]
    dims = _widths(sub, x_l.shape, w_l.shape)
    rows = int(np.prod([dims[c] for c in x_sub[1:] if c in out_sub])) * T
    width = int(np.prod([dims[c] for c in w_sub if c in out_sub]))
    p = batch_pieces(T, x_l.shape[0], rows, width, jnp.dtype(x_l.dtype).itemsize)
    return [x_l] if p == 1 else jnp.split(x_l, p, axis=0)


def _allgather_matmul(x_l, w_l, *, subscripts: str, tp, order, seq, scope):
    """Device-local all-gather⊗matmul: ``(einsum(subscripts, gathered x, w_l),
    gathered x)``; the gathered operand is the weight gradient's.

    On the ring both are assembled piece-major, ``[T * lanes, ...piece]``
    indexed by where the piece belongs in the sequence (the gathered operand
    stays so: ``P`` leads its subscripts): an update of whole leading slices
    by a GEMM's result is what the compiler writes in place, where an update
    at an offset along the sequence of ``[b, S, n]`` is a strided copy of its
    own (37 us a piece of 4 MB on a v5e, PERF.md §6). The result moves to the
    sequence's place once, at the end.

    That holds only where the output's dims are in a plain GEMM's order. A
    result the GEMM has to transpose (the head-major ``bcnsd`` of the stacked
    qkv projection, ``bnsd`` of the output projection's cotangent) is copied
    and then placed by a slow update, 200 us a piece of the sequence. Such a
    seam's other direction is a ring (the reduce-scatter ring reads pieces
    and writes none); this direction goes piece by piece along the batch,
    which leads both operand and result: each piece is gathered whole over
    the sequence and multiplied by itself, so piece b+1's gather runs under
    piece b's GEMM, and result and gathered operand are joined from whole
    leading slices (on four v5e chips 0.22 ms of the qkv projection's 1.47
    and 0.05 of the output projection's 0.96, PERF.md §6, PR 32). The same
    GEMM on the same rows; one piece (:func:`batch_pieces`) is the whole
    gather in front of one GEMM, as GSPMD would do it."""
    x_sub, w_sub, out_sub = sub = _parse(subscripts)
    T = len(order)
    seq_x = x_sub.index(seq)
    if _natural(sub) != out_sub:
        outs, fulls = [], []
        for piece in _batch_split(sub, x_l, w_l, T, seq_x):
            with jax.named_scope(scope):
                fulls.append(jax.lax.all_gather(piece, tp, axis=seq_x, tiled=True))
            outs.append(jnp.einsum(subscripts, fulls[-1], w_l))
        if len(outs) == 1:
            return outs[0], fulls[0]
        return jnp.concatenate(outs, axis=0), jnp.concatenate(fulls, axis=0)
    lanes = _lanes(order, x_l.shape[seq_x])
    pieces = [jax.lax.slice_in_dim(x_l, o, o + n, axis=seq_x) for o, n, _ in lanes]
    dims = _widths(sub, pieces[0].shape, w_l.shape)
    slots = T * len(lanes)
    out = jnp.zeros([slots] + [dims[c] for c in out_sub], x_l.dtype)
    full = jnp.zeros([slots] + [dims[c] for c in x_sub], x_l.dtype)
    for t in range(T):
        if t < T - 1:
            with jax.named_scope(scope):
                onward = [jax.lax.ppermute(p, tp, _perm(order, d))
                          for p, (_, _, d) in zip(pieces, lanes)]
        for lane, (p, (_, _, d)) in enumerate(zip(pieces, lanes)):
            # the piece in hand left the device t hops against the lane's direction
            slot = _chunk_at(order, tp, d * t) * len(lanes) + lane
            full = jax.lax.dynamic_update_index_in_dim(full, p, slot, axis=0)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.einsum(subscripts, p, w_l), slot, axis=0)
        if t < T - 1:
            pieces = onward
    at = out_sub.index(seq)
    out = jnp.moveaxis(out, 0, at)
    return out.reshape(out.shape[:at] + (-1,) + out.shape[at + 2:]), full


def _ring_matmul_reducescatter(x_l, w_l, *, subscripts: str, tp, order, seq, scope):
    """Device-local matmul⊗reduce-scatter: this device's sequence chunk of
    ``einsum(subscripts, x, w)`` summed over the ring's partial products."""
    x_sub, _, out_sub = _parse(subscripts)
    T = len(order)
    seq_x, seq_out = x_sub.index(seq), out_sub.index(seq)
    s_local = x_l.shape[seq_x] // T
    done = []
    for o, n, d in _lanes(order, s_local):
        def partial(t):
            # the accumulator in hand at step t comes to rest T - 1 - t hops on
            at = _chunk_at(order, tp, d * (1 + t)) * s_local + o
            x_c = jax.lax.dynamic_slice_in_dim(x_l, at, n, axis=seq_x)
            return jnp.einsum(subscripts, x_c, w_l, preferred_element_type=jnp.float32)

        acc = partial(0).astype(x_l.dtype)
        for t in range(1, T):
            with jax.named_scope(scope):
                acc = jax.lax.ppermute(acc, tp, _perm(order, d))
            acc = (partial(t) + acc.astype(jnp.float32)).astype(x_l.dtype)
        done.append(acc)
    return done[0] if len(done) == 1 else jnp.concatenate(done, axis=seq_out)


def _weight_grad(seam: _Seam, x_sub: str, x, out_sub: str, g, dtype):
    """``dw`` from the seam's operand and its cotangent, one of them possibly
    piece-major (``P`` leads its subscripts): the other's sequence then splits
    into (piece, rows of a piece), a free reshape. Summed over the data-parallel
    devices (each saw its own rows) before it is rounded to the weight's dtype."""
    seq, w_sub = seam.seq, seam.subs[1]
    if x_sub[0] == _PIECE:
        g, out_sub = _split_seq(g, out_sub, seq, x.shape[0]), out_sub.replace(seq, _PIECE + seq)
    elif out_sub[0] == _PIECE:
        x, x_sub = _split_seq(x, x_sub, seq, g.shape[0]), x_sub.replace(seq, _PIECE + seq)
    dw = jnp.einsum(f"{x_sub},{out_sub}->{w_sub}", x, g, preferred_element_type=jnp.float32)
    if seam.dp:
        dw = jax.lax.psum(dw, seam.dp)
    return dw.astype(dtype)


def _gathered_sub(sub: str, gathered) -> str:
    """Subscripts of what ``_allgather_matmul`` gathered: piece-major off the
    ring (one dim more), ``sub`` itself where it gathered whole."""
    return sub if gathered.ndim == len(sub) else _PIECE + sub


def _split_seq(a, sub: str, seq: str, slots: int):
    """``a`` with its sequence dim as (pieces, rows of a piece)."""
    at = sub.index(seq)
    return a.reshape(a.shape[:at] + (slots, -1) + a.shape[at + 1:])


# Each seam is four programs, traced and lowered once per distinct seam however
# many layers call it: forward and backward of either kind, each ``jax.jit`` of
# one ``shard_map``, tied by a ``custom_vjp`` whose rules only bind them. A
# backward left to autodiff is a new jaxpr at every call site (and again in
# every rematerialised region), each lowered by itself: 124 more shard_maps in
# the four-chip step, +75% on its lowering (PERF.md §6).


def _specs(seam: _Seam, w_ndim: int):
    """PartitionSpecs of (x, w, y, the all-gather side's gathered operand)."""
    from jax.sharding import PartitionSpec as P

    x_sub, w_sub, out_sub = seam.subs
    tp = _axis_entry(seam.tp)
    batch = {x_sub[0]: _axis_entry(seam.dp)} if seam.dp else {}
    letter = w_sub[seam.w_shard_dim]

    def spec(sub, entries):
        return P(*[{**batch, **entries}.get(c) for c in sub])

    w_spec = P(*[tp if i == seam.w_shard_dim else None for i in range(w_ndim)])
    if seam.kind == "ag":
        x_spec, y_spec = spec(x_sub, {seam.seq: tp}), spec(out_sub, {letter: tp})
        gathered, ring = x_sub, _natural(seam.subs) == out_sub
    else:
        x_spec = spec(x_sub, {letter: tp})
        y_spec = spec(out_sub, {seam.seq: tp} if seam.scatter_output else {})
        gathered, ring = out_sub, _natural((out_sub, w_sub, x_sub)) == x_sub
    # piece-major off the ring (every device its own pieces: the tp axes on
    # the piece dim), whole and the same on every tp device otherwise
    full_spec = P(tp, *spec(gathered, {})) if ring else spec(gathered, {})
    return x_spec, w_spec, y_spec, full_spec


def _program(seam: _Seam, local_fn, in_specs, out_specs, *args):
    from galvatron_tpu.parallel.mesh import ambient_or, manual_axis_names

    am = ambient_or(seam.mesh)
    return jax.shard_map(
        local_fn, mesh=am, in_specs=in_specs, out_specs=out_specs,
        axis_names=manual_axis_names(am), check_vma=False,
    )(*args)


def _ring_args(seam: _Seam):
    return dict(tp=seam.tp, order=seam.order, seq=seam.seq)


def _dual(seam: _Seam) -> str:
    """Subscripts of dx: the seam's cotangent against its weight."""
    x_sub, w_sub, out_sub = seam.subs
    return f"{out_sub},{w_sub}->{x_sub}"


@functools.partial(jax.jit, static_argnames=("seam",))
def _allgather_forward(x, w, *, seam: _Seam):
    """(y, gathered x): the weight gradient's operand is the residual."""
    x_spec, w_spec, y_spec, full_spec = _specs(seam, w.ndim)
    return _program(
        seam,
        lambda x_l, w_l: _allgather_matmul(
            x_l, w_l.astype(x_l.dtype), subscripts=seam.subscripts, scope="allgather_einsum",
            **_ring_args(seam)),
        (x_spec, w_spec), (y_spec, full_spec), x, w)


@functools.partial(jax.jit, static_argnames=("seam",))
def _allgather_backward(full, w, g, *, seam: _Seam):
    """dx through the reduce-scatter ring, dw one GEMM on what the forward gathered."""
    x_sub, w_sub, out_sub = seam.subs
    x_spec, w_spec, y_spec, full_spec = _specs(seam, w.ndim)

    def local_fn(full_l, w_l, g_l):
        dx = _ring_matmul_reducescatter(
            g_l, w_l.astype(g_l.dtype), subscripts=_dual(seam), scope="einsum_reducescatter",
            **_ring_args(seam))
        return dx, _weight_grad(seam, _gathered_sub(x_sub, full_l), full_l, out_sub, g_l,
                                w_l.dtype)

    return _program(seam, local_fn, (full_spec, w_spec, y_spec), (x_spec, w_spec), full, w, g)


@functools.partial(jax.jit, static_argnames=("seam",))
def _reducescatter_forward(x, w, *, seam: _Seam):
    x_spec, w_spec, y_spec, _ = _specs(seam, w.ndim)
    seq_out = seam.subs[2].index(seam.seq)

    def local_fn(x_l, w_l):
        if seam.activation is not None:
            x_l = seam.activation(x_l)
        acc = _ring_matmul_reducescatter(
            x_l, w_l.astype(x_l.dtype), subscripts=seam.subscripts, scope="einsum_reducescatter",
            **_ring_args(seam))
        if not seam.scatter_output:
            # back along the ring's flattened index: minor (fastest-varying)
            # axis first, so each tiled gather concatenates contiguous blocks
            for a in reversed(seam.tp):
                acc = jax.lax.all_gather(acc, a, axis=seq_out, tiled=True)
        return acc

    return _program(seam, local_fn, (x_spec, w_spec), y_spec, x, w)


@functools.partial(jax.jit, static_argnames=("seam",))
def _reducescatter_backward(x, w, g, *, seam: _Seam):
    """The all-gather ring of the cotangent gives dx piece by piece and,
    gathered, dw's operand."""
    x_sub, w_sub, out_sub = seam.subs
    x_spec, w_spec, y_spec, _ = _specs(seam, w.ndim)
    seq_out = out_sub.index(seam.seq)

    def local_fn(x_l, w_l, g_l):
        if not seam.scatter_output:  # the whole cotangent on every device: its own chunk
            rows = g_l.shape[seq_out] // len(seam.order)
            g_l = jax.lax.dynamic_slice_in_dim(
                g_l, jax.lax.axis_index(seam.tp) * rows, rows, axis=seq_out)
        undo = None
        if seam.activation is not None:
            x_l, undo = jax.vjp(seam.activation, x_l)
        dx, g_full = _allgather_matmul(
            g_l, w_l.astype(g_l.dtype), subscripts=_dual(seam), scope="allgather_einsum",
            **_ring_args(seam))
        dw = _weight_grad(seam, x_sub, x_l, _gathered_sub(out_sub, g_full), g_full, w_l.dtype)
        return (dx if undo is None else undo(dx)[0]), dw

    return _program(seam, local_fn, (x_spec, w_spec, y_spec), (x_spec, w_spec), x, w, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _allgather_seam(x, w, seam: _Seam):
    return _allgather_forward(x, w, seam=seam)[0]


def _allgather_seam_fwd(x, w, seam: _Seam):
    y, full = _allgather_forward(x, w, seam=seam)
    return y, (full, w)


def _allgather_seam_bwd(seam: _Seam, res, g):
    return _allgather_backward(*res, g, seam=seam)


_allgather_seam.defvjp(_allgather_seam_fwd, _allgather_seam_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _reducescatter_seam(x, w, seam: _Seam):
    return _reducescatter_forward(x, w, seam=seam)


def _reducescatter_seam_fwd(x, w, seam: _Seam):
    return _reducescatter_forward(x, w, seam=seam), (x, w)


def _reducescatter_seam_bwd(seam: _Seam, res, g):
    return _reducescatter_backward(*res, g, seam=seam)


_reducescatter_seam.defvjp(_reducescatter_seam_fwd, _reducescatter_seam_bwd)


def allgather_einsum(
    subscripts: str,
    x,
    w,
    *,
    mesh,
    dp_axes: Sequence[str],
    tp_axes: Sequence[str],
    w_shard_dim: int,
    seq: str = "s",
):
    """``einsum(subscripts, x, w)`` with the seq all-gather of ``x`` pipelined
    behind the GEMM chunks. ``x``'s first dim is the dp-sharded batch, its
    ``seq`` dim is logically sharded over ``tp_axes``; ``w`` is TP-sharded at
    ``w_shard_dim`` (the column-parallel output dim). Global shapes in, global
    shapes out — only the layout differs from the plain einsum. ``w`` may be
    in another dtype than ``x`` (the stored parameter): it is cast to ``x``'s
    inside the seam's programs, forward and backward, and no cast copy is kept."""
    seam = _seam("ag", subscripts, x, w, mesh, dp_axes, tp_axes, w_shard_dim, seq)
    if seam is None:
        return jnp.einsum(subscripts, x, w.astype(x.dtype))
    return _allgather_seam(x, w, seam)


def einsum_reducescatter(
    subscripts: str,
    x,
    w,
    *,
    mesh,
    dp_axes: Sequence[str],
    tp_axes: Sequence[str],
    w_shard_dim: int,
    scatter_output: bool = True,
    seq: str = "s",
    activation: Optional[Callable] = None,
):
    """``einsum(subscripts, activation(x), w)`` with the trailing TP reduction pipelined
    behind the GEMM chunks. ``w`` is TP-sharded at ``w_shard_dim`` (the
    row-parallel *contracted* dim, whose letter also indexes ``x``'s
    TP-sharded dim), so each device's einsum yields a partial sum. The
    accumulator ring reduces it seq-chunk by seq-chunk: ``scatter_output=True``
    returns the sp layout (out seq-sharded over tp); ``False`` appends tiled
    all-gathers for a replicated output — the full all-reduce's gather half.
    ``activation`` (elementwise, a module-level function: it keys the jitted
    programs) is applied to ``x`` first; see ``_Seam.activation``."""
    seam = _seam("rs", subscripts, x, w, mesh, dp_axes, tp_axes, w_shard_dim, seq,
                 scatter_output, activation)
    if seam is None:
        return jnp.einsum(
            subscripts, x if activation is None else activation(x), w.astype(x.dtype))
    return _reducescatter_seam(x, w, seam)
