"""The gated delta rule of a Gated DeltaNet layer (Yang, Kautz & Hatamizadeh
2024, "Gated Delta Networks"; the Qwen3-Next linear-attention layers), chunked.

The recurrence, per value head (state ``S`` in R^{Dk x Dv}, ``S_0 = 0``; one
scalar log-decay ``g_t <= 0`` and one write strength ``beta_t`` a head)::

    S <- exp(g_t) S;   r_t = v_t - S^T k_t;   S <- S + k_t (beta_t r_t)^T;   o_t = S^T q_t

is computed in chunks of ``chunk`` positions (the published code's 64). Inside
a chunk, with ``G`` the running sum of ``g`` and ``D_ij = exp(G_i - G_j)``
(``i >= j``), the writes of the chunk solve one unit lower-triangular system::

    (I + strict_lower(diag(beta) K K^T * D)) [U | W] = [beta V | beta K exp(G)]

(``U``: what each position writes if the state entering the chunk were zero;
``W``: what of the entering state each position sees), so that with the
entering state ``S``::

    V' = U - W S
    O  = (Q exp(G)) S + lower(Q K^T * D) V'
    S <- exp(G_last) S + (K exp(G_last - G))^T V'

Two bodies behind `scan_path`, which chooses from shapes and backend alone and
which `models/gdn.block` asks. `gated_delta_chunked` is plain ``jax.numpy`` over
q and k already normalised (a ``lax.scan`` carries the states across the chunks,
everything inside a chunk is batched over the chunks, the system goes through
``jax.scipy.linalg.solve_triangular``, autodiff gives the backward).
`gated_delta_fused` is a ``jax.custom_vjp`` over the Pallas kernels ``gdn_fwd`` /
``gdn_bwd`` (further down: the state in VMEM across a sequential grid axis, the
system inverted by matrix products, a backward of its own that keeps its three
inputs and the chunks' entering states); it takes the conv's output as it lies,
``[q | k | v]`` a position, and the kernels normalise q and k themselves (since
PR 73). The two share a contract at the mixer's level (`block`'s output and
gradients), not an entry.

Precision, both bodies: the log-decays, their running sums, ``beta``, the
system, its inverse (or solve) and its application, and the carried state (and
its gradient) are float32 always, float32 operands at full precision; the
operands of the other products are the compute dtype (``q``'s) with float32
accumulation.

Each key head serves ``Hv / Hk`` consecutive value heads (value head ``j``
reads key head ``j // (Hv / Hk)``); q and k are never repeated in memory.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.ops import pallas_common

F32 = jnp.float32


def _mm(spec, a, b, dtype):
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype), preferred_element_type=F32)


def gated_delta_chunked(q, k, v, g, beta, chunk: int = 64):
    """``q``, ``k`` (B, S, Hk, Dk), already normalised and scaled; ``v``
    (B, S, Hv, Dv); ``g`` (log-decay, <= 0) and ``beta`` (B, S, Hv) ->
    ``o`` (B, S, Hv, Dv) in ``v``'s dtype. A sequence that is not whole chunks
    is padded at its end (causal: nothing earlier moves)."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r = hv // hk
    dtype = q.dtype
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
    n = (s + pad) // chunk
    # (B, Hk, [R,] N, C, D): chunks beside the heads, positions inside a chunk last
    qc = q.reshape(b, n, chunk, hk, dk).transpose(0, 3, 1, 2, 4)
    kc = k.reshape(b, n, chunk, hk, dk).transpose(0, 3, 1, 2, 4)
    vc = v.reshape(b, n, chunk, hk, r, dv).transpose(0, 3, 4, 1, 2, 5)
    gc = jnp.cumsum(g.astype(F32).reshape(b, n, chunk, hk, r).transpose(0, 3, 4, 1, 2), axis=-1)
    bc = beta.astype(F32).reshape(b, n, chunk, hk, r).transpose(0, 3, 4, 1, 2)

    i = jnp.arange(chunk)
    lower = i[:, None] >= i[None, :]
    # D_ij = exp(G_i - G_j) on and below the diagonal, 0 above (masked before the exp)
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :], -jnp.inf))
    kk = _mm("bhncd,bhned->bhnce", kc, kc, dtype)  # (B, Hk, N, C, C)
    qk = _mm("bhncd,bhned->bhnce", qc, kc, dtype)
    strict = (i[:, None] > i[None, :]).astype(F32)
    system = strict * bc[..., :, None] * kk[:, :, None] * decay + jnp.eye(chunk, dtype=F32)
    k_seen = kc[:, :, None].astype(F32) * (bc * jnp.exp(gc))[..., None]  # (B, Hk, R, N, C, Dk)
    rhs = jnp.concatenate([vc.astype(F32) * bc[..., None], k_seen], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(system, rhs, lower=True, unit_diagonal=True)
    # the products' operands in the compute dtype before the scan keeps them (and
    # what autodiff saves of them) at that width
    u, w = sol[..., :dv], sol[..., dv:].astype(dtype)
    intra = (qk[:, :, None] * decay).astype(dtype)  # (B, Hk, R, N, C, C), diagonal included
    q_in = (qc[:, :, None].astype(F32) * jnp.exp(gc)[..., None]).astype(dtype)
    g_last = gc[..., -1]  # (B, Hk, R, N)
    k_out = (kc[:, :, None].astype(F32) * jnp.exp(g_last[..., None] - gc)[..., None]).astype(dtype)

    def step(state, xs):  # state (B, Hk, R, Dk, Dv) float32
        u_n, w_n, intra_n, q_n, k_n, last_n = xs
        v_new = u_n - _mm("bhrcd,bhrde->bhrce", w_n, state, dtype)
        o_n = (_mm("bhrcd,bhrde->bhrce", q_n, state, dtype)
               + _mm("bhrce,bhred->bhrcd", intra_n, v_new, dtype))
        state = (state * jnp.exp(last_n)[..., None, None]
                 + _mm("bhrcd,bhrce->bhrde", k_n, v_new, dtype))
        return state, o_n

    chunks_first = lambda t: jnp.moveaxis(t, 3, 0)  # noqa: E731
    _, o = jax.lax.scan(
        step, jnp.zeros((b, hk, r, dk, dv), F32),
        tuple(chunks_first(t) for t in (u, w, intra, q_in, k_out, g_last)))
    # (N, B, Hk, R, C, Dv) -> (B, S, Hv, Dv)
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(b, n * chunk, hv, dv)
    return o[:, :s].astype(v.dtype)


# -- the fused kernels -----------------------------------------------------------
#
# One grid step is `_SLABS` slabs of one key head, a slab `_SLAB` = 128 positions =
# two chunks of 64: grid (batch, key head, step), the step axis sequential
# ("arbitrary"), the states of the key head's ``Hv / Hk`` value heads, float32
# (Dk, Dv) each, in VMEM scratch from one step to the next. q, k and v are three
# blocks of ONE array, the conv's output (B, S, 2 Hk Dk + Hv Dv) where it lies: q a
# (positions, Dk) block at lane block ``j``, k at ``Hk + j``, v a (positions, R Dv)
# block at ``2 Hk Dk / (R Dv) + j``; dq, dk and dv leave token-major the same way,
# and one concatenation makes them the conv's cotangent. The kernels normalise: a
# block is (positions, Dk) of ONE head, so the L2 norm over a head is a reduction
# along the lanes of what the kernel already holds (`_normalised`), and its VJP runs
# on the float32 accumulators of dq and dk before their one cast. (The norms were
# XLA's until PR 73, in float32 over head-major copies: on a token-major (S, Hk x
# Dk) array XLA can only reduce over a head after re-tiling to (S, Hk, Dk), a
# physical copy, and head-major blocks with XLA's norms need the transposition:
# ~32 ms a step in the cell either way, PERF.md §6, PR 48 and PR 73. Only both
# together leave XLA nothing to copy.) o and its gradient STAY head-major, (B, Hv,
# S, Dv), their transpositions XLA's under the caller's scope: the gated norm that
# follows reduces over Dv a head and lost 13 ms when o arrived in the tiling it
# reduces in, and a token-major o is what made the unnamed copies of PR 48's first
# try. The per-position scalars come both ways, made outside from (B, S, Hv) float32
# (2 MB at the cell's sizes): a position a sublane (the running sums G and beta, a
# value head picked by a lane mask) and a position a lane (G again, (B, Hk, R, S)).
#
# Every (C, C) block of a value head lives PACKED: the two chunks of the slab side
# by side on the 128 lanes, (64, 128) = [chunk a | chunk b], so an elementwise op
# fills whole registers. ``x @ blockdiag(z_a, z_b)`` multiplies both chunks' blocks
# in one (64, 128) x (128, 128) product (`_pdot`); a 128 x 128 Gram product of the
# slab's rows gives both chunks' K K^T at once on its diagonal blocks (`_halves`).
#
# The system: ``A`` is strictly lower, so ``(I + A)^-1`` is built by products
# alone, from 2 x 2 diagonal blocks (``I - A`` there) up: two inverted diagonal
# blocks ``T11``, ``T22`` of size s/2 merge into one of size s as
# ``[[T11, 0], [-T22 A21 T11, T22]]`` = ``T - T (A on the off-diagonal blocks) T``,
# five times (s = 4 .. 64): block forward substitution, each step exact given its
# halves, no power of ``A`` ever formed. Float32 operands at full precision
# (`_dot`), two products a level, each waiting for the one before it.
#
# The order things are written in is the order they run in, nearly: Mosaic's
# scheduler overlaps what stands close. So every chain of dependent products (a
# head's inverse, a head's walk over its chunks) is written side by side with the
# same chain of the step's other heads and slabs, stage by stage, and what does not
# wait for the state is computed before the walk (`_heads_setup`, ``ready``): one
# chain's elementwise work then runs under another's products. (On the chip, a
# layer's forward at the cell's sizes: 15.2 ms one chain after the other, 7.3 ms
# side by side; PERF.md §6, PR 48.)
#
# The backward walks the steps from the last to the first with the gradient of the
# state in VMEM, rebuilds the normalised q and k, ``A``, ``T``, ``U``, ``W`` of its
# slabs from the inputs and ``V'`` from the states the forward rule kept (one
# entering a chunk: (B, S/64, Hv, Dk, Dv) float32, the only residual besides the
# inputs), and takes the inverse's gradient as ``dA = -strict(dXu U^T + dXw W^T)``
# with ``[dXu | dXw] = T^T [dU | dW]``: products, no solve. ``dg`` leaves the kernel
# summed back over each chunk (the running sum's transpose), a position a lane.

L2_EPS = 1e-6  # under the root of q's and k's norms (the published code's; `models/gdn` reads it here)
_CHUNK = 64  # the chunk the kernels are written for (the published code's)
_SLAB = 2 * _CHUNK
_SLABS = 2  # slabs a grid step: their chains side by side (one: 9.8 ms a forward at the
# cell's sizes, two: 7.3, four: 7.3 and three times the compile; PERF.md §6, PR 48)
_NEG = -1e30  # exp() of it is 0: the mask of the decay blocks
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(a, b, dims):
    """Float32 accumulation; bf16 operands one pass, float32 operands at full
    precision (``HIGHEST``: Mosaic's six bf16 passes, the MXU's default for float32
    operands being fewer; ``experiments/ab_gdn.py`` holds it on the chip)."""
    precision = jax.lax.Precision.HIGHEST if a.dtype == F32 else None
    return jax.lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=F32,
                               precision=precision)


def _geometry():
    """Of a packed (64, 128) block: its row, its column inside its own chunk,
    whether a lane belongs to the slab's first chunk."""
    row = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, _SLAB), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, _SLAB), 1)
    return row, lane & (_CHUNK - 1), lane < _CHUNK


def _halves(x, first):
    """The diagonal blocks of a (128, 128) product over the slab's rows, packed."""
    return jnp.where(first, x[:_CHUNK], x[_CHUNK:])


def _spread(col, first):
    """(128, 1), a position a sublane -> packed: each chunk's own 64 over its lanes."""
    return jnp.where(first, col[:_CHUNK], col[_CHUNK:])


def _bd(x, first):
    """Packed -> (128, 128) block diagonal."""
    return jnp.concatenate([jnp.where(first, x, 0.0), jnp.where(first, 0.0, x)], axis=0)


def _pdot(x, z, first):
    """Packed ``[x_a z_a | x_b z_b]`` of packed float32 operands."""
    return _dot(x, _bd(z, first), _NN)


def _lower_rows(x, half):
    """The rows of the lower half of every block of ``2 * half`` rows, one after
    the other (``half`` whole sublane tiles: nothing moves)."""
    return jnp.concatenate([x[b + half:b + 2 * half] for b in range(0, _CHUNK, 2 * half)], axis=0)


def _with_lower_rows(x, low, half):
    """``x`` with those rows replaced by ``low``'s."""
    parts = []
    for n, b in enumerate(range(0, _CHUNK, 2 * half)):
        parts += [x[b:b + half], low[n * half:(n + 1) * half]]
    return jnp.concatenate(parts, axis=0)


def _inverses(blocks, row, col, first):
    """Packed ``(I + A)^-1`` of each packed strictly lower ``A`` of ``blocks`` (see
    above). The chains are independent and each product waits for the one before
    it: written level by level across the blocks, so that one chain's elementwise
    work runs under another's products. A merge changes only the lower half of
    each block's rows: from halves of 8 rows up only those rows go through the
    MXU (what bounds these products is popping their results)."""
    ts = [(row == col).astype(F32) - jnp.where((row >> 1) == (col >> 1), a, 0.0) for a in blocks]
    bits = 2
    while (1 << bits) <= _CHUNK:
        half = 1 << (bits - 1)
        off = ((row >> bits) == (col >> bits)) & ((row >> (bits - 1)) != (col >> (bits - 1)))
        if half < 8:
            xs = [_pdot(jnp.where(off, a, 0.0), t, first) for a, t in zip(blocks, ts)]
            ts = [t - _pdot(t, x, first) for t, x in zip(ts, xs)]
        else:
            zeros = jnp.zeros((_CHUNK, _SLAB), F32)
            xs = [_pdot(_lower_rows(jnp.where(off, a, 0.0), half), t, first)
                  for a, t in zip(blocks, ts)]
            lows = [_lower_rows(t, half) for t in ts]
            ts = [_with_lower_rows(t, low - _pdot(low, _with_lower_rows(zeros, x, half), first), half)
                  for t, low, x in zip(ts, lows, xs)]
        bits += 1
    return ts


def _last_row(col_block, width):
    """The last of a chunk's (64, 1) column over ``width`` lanes, (1, width) (a
    (1, 1) slice is not spread both ways by Mosaic: summed out of a broadcast)."""
    at_end = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, width), 0) == _CHUNK - 1
    return jnp.sum(jnp.where(at_end, jnp.broadcast_to(col_block, (_CHUNK, width)), 0.0),
                   axis=0, keepdims=True)


def _slab_rows(sl):
    return slice(sl * _SLAB, (sl + 1) * _SLAB)


def _chunk_rows(c):
    return slice(c * _CHUNK, (c + 1) * _CHUNK)


def _padded(x, c):
    """A chunk's (64, n) rows where the slab has them, zeros in the other chunk's."""
    zeros = jnp.zeros_like(x)
    return jnp.concatenate([x, zeros] if c == 0 else [zeros, x], axis=0)


class _Slab(NamedTuple):
    """q and k of a slab (128 positions of a key head), normalised, in the compute
    dtype and widened again, and their two Gram products, packed."""
    q: Any
    k: Any
    q32: Any
    k32: Any
    kk: Any
    qk: Any


class _Head(NamedTuple):
    """What is chunk parallel of a value head in a slab: the columns G and beta
    (128, 1), the packed decay block, ``A``, ``T``, v in float32, ``beta e^G K``
    and ``[U | W]`` (128, Dv + Dk)."""
    gcol: Any
    bcol: Any
    decay: Any
    a: Any
    t: Any
    v32: Any
    k_seen: Any
    uw: Any


def _normalised(ref, sl):
    """A slab's rows of q or k as the conv wrote them -> float32 of unit length
    over the head's lanes (`models/gdn._l2norm`'s arithmetic), and the factor that
    made them so, (128, 1)."""
    x = ref[0, _slab_rows(sl)].astype(F32)
    factor = jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + L2_EPS)
    return x * factor, factor


def _slab_setup(sl, q_ref, k_ref, first):
    # rounded to the compute dtype where `models/gdn.block` rounds them for the plain
    # body: the rule's products see the same operands
    dtype = q_ref.dtype
    q = (_normalised(q_ref, sl)[0] * q_ref.shape[2] ** -0.5).astype(dtype)
    k = _normalised(k_ref, sl)[0].astype(dtype)
    return _Slab(q, k, q.astype(F32), k.astype(F32),
                 _halves(_dot(k, k, _NT), first), _halves(_dot(q, k, _NT), first))


def _norm_vjp(ref, sl, d, scale):
    """The float32 gradient ``d`` of a slab's normalised (and scaled) rows -> that
    of the rows as they came: ``scale r (d - n <d, n>)``."""
    n, factor = _normalised(ref, sl)
    return scale * factor * (d - n * jnp.sum(d * n, axis=1, keepdims=True))


def _heads_setup(r, slab, v_ref, gc_ref, bc_ref, gr_ref, geometry):
    """The `_Head` of every slab ``sl`` of the step and value head ``i`` of its key
    head, by ``(sl, i)``: what both kernels build chunk parallel."""
    row, col, first = geometry
    dv = v_ref.shape[2] // r
    heads = jax.lax.broadcasted_iota(jnp.int32, (_SLAB, gc_ref.shape[2]), 1)
    keys = [(sl, i) for sl in range(_SLABS) for i in range(r)]
    built = {}
    for sl, i in keys:
        rows = _slab_rows(sl)
        mine = heads == pl.program_id(1) * r + i
        gcol = jnp.sum(jnp.where(mine, gc_ref[0, rows], 0.0), axis=1, keepdims=True)  # (128, 1)
        bcol = jnp.sum(jnp.where(mine, bc_ref[0, rows], 0.0), axis=1, keepdims=True)
        grow = gr_ref[0, 0, i:i + 1, rows]  # (1, 128)
        decay = jnp.exp(jnp.where(row >= col, _spread(gcol, first) - grow, _NEG))
        a = jnp.where(row > col, _spread(bcol, first) * slab[sl].kk * decay, 0.0)
        built[sl, i] = (gcol, bcol, decay, a)
    inverses = _inverses([built[key][3] for key in keys], row, col, first)
    for (sl, i), t in zip(keys, inverses):
        gcol, bcol, decay, a = built[sl, i]
        v32 = v_ref[0, _slab_rows(sl), i * dv:(i + 1) * dv].astype(F32)
        k_seen = slab[sl].k32 * (bcol * jnp.exp(gcol))
        # both chunks' T on one load of [beta V | beta e^G K]: chunk a's rows, then b's
        uw = _dot(_bd(t, first), jnp.concatenate([v32 * bcol, k_seen], axis=1), _NN)
        built[sl, i] = _Head(gcol, bcol, decay, a, t, v32, k_seen, uw)
    return built


def _fwd_kernel(q_ref, k_ref, v_ref, gc_ref, bc_ref, gr_ref, o_ref, *rest, r, keep_states):
    state = rest[-1]  # (R, Dk, Dv) float32
    dtype = q_ref.dtype
    dv, dk = v_ref.shape[2] // r, q_ref.shape[2]

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        state[...] = jnp.zeros_like(state)

    geometry = _geometry()
    first = geometry[2]
    # chunk parallel first (every slab and head of the step), then the chunks in
    # their order, the value heads' chains side by side (the scheduler keeps close
    # to the order written: one head's products run under the other's updates)
    slab = [_slab_setup(sl, q_ref, k_ref, first) for sl in range(_SLABS)]
    head = _heads_setup(r, slab, v_ref, gc_ref, bc_ref, gr_ref, geometry)
    ready = {}
    for sl in range(_SLABS):
        q32, k32 = slab[sl].q32, slab[sl].k32
        for i in range(r):
            uw = head[sl, i].uw
            intra = (slab[sl].qk * head[sl, i].decay).astype(dtype)  # packed, the diagonal included
            for c in range(2):
                at = _chunk_rows(c)
                g_c = head[sl, i].gcol[at]
                # [W ; Q e^G]: both read the entering state, one load of it
                reads = jnp.concatenate([uw[at, dv:], q32[at] * jnp.exp(g_c)], axis=0).astype(dtype)
                k_out = (k32[at] * jnp.exp(_last_row(g_c, dk) - g_c)).astype(dtype)
                ready[sl, c, i] = (uw[at, :dv], reads, intra, k_out, jnp.exp(_last_row(g_c, dv)))
    states = [state[i] for i in range(r)]
    for sl in range(_SLABS):
        for c in range(2):
            for i in range(r):
                u, reads, intra, k_out, kept = ready[sl, c, i]
                entering = states[i]
                if keep_states:
                    rest[0][0, 2 * sl + c, i] = entering
                seen = _dot(reads, entering.astype(dtype), _NN)  # [W S ; (Q e^G) S]
                v_new = (u - seen[:_CHUNK]).astype(dtype)
                o = seen[_CHUNK:] + _dot(intra, _padded(v_new, c), _NN)
                start = sl * _SLAB + c * _CHUNK
                o_ref[0, i, start:start + _CHUNK] = o.astype(o_ref.dtype)
                states[i] = entering * kept + _dot(k_out, v_new, _TN)
    for i in range(r):
        state[i] = states[i]


def _suffix_sums(v):
    """(8, 128) float32 -> the sum from each lane to the end of its chunk (64
    lanes): log2(64) shifted adds, the running sum's transpose."""
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1) & (_CHUNK - 1)
    n, k = v.shape[1], 1
    while k < _CHUNK:
        v = v + jnp.where(lane < _CHUNK - k, pltpu.roll(v, n - k, 1), 0.0)
        k *= 2
    return v


def _bwd_kernel(q_ref, k_ref, v_ref, gc_ref, bc_ref, gr_ref, do_ref, st_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate, *, r):
    dtype = q_ref.dtype
    dv, dk = v_ref.shape[2] // r, q_ref.shape[2]

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        dstate[...] = jnp.zeros_like(dstate)

    geometry = _geometry()
    row, col, first = geometry
    lane = jax.lax.broadcasted_iota(jnp.int32, (_SLAB, _SLAB), 1)
    slab = [_slab_setup(sl, q_ref, k_ref, first) for sl in range(_SLABS)]
    head = _heads_setup(r, slab, v_ref, gc_ref, bc_ref, gr_ref, geometry)
    dq = [jnp.zeros((_SLAB, dk), F32) for _ in range(_SLABS)]
    dkey = [jnp.zeros((_SLAB, dk), F32) for _ in range(_SLABS)]
    leaving = [dstate[i] for i in range(r)]
    value_heads = range(r)

    def row_sums(x):  # of a packed block, a chunk's rows where the slab has them
        return jnp.concatenate(
            [jnp.sum(jnp.where(first, x, 0.0), axis=1, keepdims=True),
             jnp.sum(jnp.where(first, 0.0, x), axis=1, keepdims=True)], axis=0)

    # (every stage below runs over the value heads side by side, as the forward's)
    for sl in reversed(range(_SLABS)):
        rows = _slab_rows(sl)
        q, k, q32, k32, kk, qk = slab[sl]
        # what does not wait for the state's gradient, chunk parallel
        do, intra32, intra, w, sd, v_new, d_intra, entering = [[None] * r for _ in range(8)]
        for i in value_heads:
            decay, uw = head[sl, i].decay, head[sl, i].uw
            do[i] = do_ref[0, i, rows]
            intra32[i] = qk * decay
            intra[i] = intra32[i].astype(dtype)
            w[i] = uw[:, dv:].astype(dtype)
            entering[i] = [st_ref[0, 2 * sl + c, i] for c in range(2)]
            sd[i] = [e.astype(dtype) for e in entering[i]]
            v_new[i] = jnp.concatenate(
                [uw[_chunk_rows(c), :dv] - _dot(w[i][_chunk_rows(c)], sd[i][c], _NN)
                 for c in range(2)], axis=0).astype(dtype)
            d_intra[i] = jnp.where(row >= col, _halves(_dot(do[i], v_new[i], _NT), first), 0.0)
        ready = {}
        for c in (1, 0):
            at = _chunk_rows(c)
            for i in value_heads:
                g_c = head[sl, i].gcol[at]
                grown = jnp.exp(_last_row(g_c, dk) - g_c)  # e^(G_last - G) over Dk lanes
                q_in32 = q32[at] * jnp.exp(g_c)
                dq_in = _dot(do[i][at], sd[i][c], _NT)
                mine = first if c == 0 else ~first
                through = _dot(jnp.where(mine, intra[i], 0), do[i][at], _TN)[at]
                ready[c, i] = (g_c, jnp.exp(_last_row(g_c, dv)), grown, k32[at] * grown, q_in32,
                               dq_in, through)
                dq[sl] = dq[sl] + _padded(dq_in * jnp.exp(g_c), c)

        # the sequential part, the later chunk first
        du, dw, g_cols, d_last = [[[None, None] for _ in value_heads] for _ in range(4)]
        for c in (1, 0):
            at = _chunk_rows(c)
            for i in value_heads:
                g_c, kept, grown, k_out32, q_in32, dq_in, through = ready[c, i]
                ld = leaving[i].astype(dtype)
                du[i][c] = through + _dot(k_out32.astype(dtype), ld, _NN)
                dud = du[i][c].astype(dtype)
                dk_out = _dot(v_new[i][at], ld, _NT)
                dw[i][c] = -_dot(dud, sd[i][c], _NT)
                carried = jnp.sum(jnp.sum(leaving[i] * entering[i][c], axis=0, keepdims=True)
                                  * kept, axis=1, keepdims=True)  # (1, 1)
                sent = jnp.sum(dk_out * k_out32, axis=1, keepdims=True)  # (64, 1)
                d_last[i][c] = carried + jnp.sum(sent, axis=0, keepdims=True)
                g_cols[i][c] = jnp.sum(dq_in * q_in32, axis=1, keepdims=True) - sent
                dkey[sl] = dkey[sl] + _padded(dk_out * grown, c)
                leaving[i] = (leaving[i] * kept + _dot(q_in32.astype(dtype), do[i][at], _TN)
                              - _dot(w[i][at], dud, _TN))

        # back through the application of T and through its inverse, chunk parallel
        dx = [_dot(_bd(head[sl, i].t, first),
                   jnp.concatenate([jnp.concatenate(du[i], axis=0),
                                    jnp.concatenate(dw[i], axis=0)], axis=1), _TN)
              for i in value_heads]  # T^T [dU | dW], (128, Dv + Dk)
        da = [-jnp.where(row > col, _halves(_dot(dx[i], head[sl, i].uw, _NT), first), 0.0)
              for i in value_heads]
        for i in value_heads:
            gcol, bcol, decay, a, _, v32, k_seen, _ = head[sl, i]
            dxu, dxw = dx[i][:, :dv], dx[i][:, dv:]
            dkk = (da[i] * _spread(bcol, first) * decay).astype(dtype)
            dqk = (d_intra[i] * decay).astype(dtype)
            # d decay x decay: + to its row's G, - to its column's
            e = da[i] * a + d_intra[i] * intra32[i]
            f = da[i] * kk * decay  # d beta of a row
            for c in range(2):
                at = _chunk_rows(c)
                mine = first if c == 0 else ~first
                dkk_c, dqk_c = jnp.where(mine, dkk, 0), jnp.where(mine, dqk, 0)
                dq[sl] = dq[sl] + _padded(_dot(dqk_c, k, _NN), c)
                dkey[sl] = (dkey[sl] + _padded(_dot(dkk_c, k, _NN), c) + _dot(dkk_c, k[at], _TN)
                            + _dot(dqk_c, q[at], _TN))
            dkey[sl] = dkey[sl] + dxw * (bcol * jnp.exp(gcol))
            dv_ref[0, rows, i * dv:(i + 1) * dv] = (dxu * bcol).astype(dv_ref.dtype)
            dg_col = (jnp.sum(dxw * k_seen, axis=1, keepdims=True)
                      + jnp.concatenate(g_cols[i], axis=0) + row_sums(e))
            db_col = (jnp.sum(dxu * v32, axis=1, keepdims=True)
                      + jnp.sum(dxw * (k32 * jnp.exp(gcol)), axis=1, keepdims=True) + row_sums(f))
            # a position a sublane -> a position a lane, through one aligned transpose
            across = (jnp.where(lane == 0, dg_col, 0.0) + jnp.where(lane == 1, db_col, 0.0)).T
            dg_row = (across[0:1] - jnp.sum(e, axis=0, keepdims=True)
                      + jnp.where(lane[:1] == _CHUNK - 1, d_last[i][0], 0.0)
                      + jnp.where(lane[:1] == _SLAB - 1, d_last[i][1], 0.0))
            dg_ref[0, 0, i:i + 1, rows] = _suffix_sums(jnp.broadcast_to(dg_row, (8, _SLAB)))[:1]
            db_ref[0, 0, i:i + 1, rows] = across[1:2]
    for i in value_heads:
        dstate[i] = leaving[i]
    # through the norms in float32, then the one cast
    for sl in range(_SLABS):
        dq_ref[0, _slab_rows(sl)] = _norm_vjp(q_ref, sl, dq[sl], dk ** -0.5).astype(dq_ref.dtype)
        dk_ref[0, _slab_rows(sl)] = _norm_vjp(k_ref, sl, dkey[sl], 1.0).astype(dk_ref.dtype)


def _specs(bsz, sp, hk, r, dk, dv, rev):
    """Grid and block specs shared by the two kernels; ``rev`` walks the steps
    from the last to the first. ``joined``: q, k and v of a key head as blocks of
    the conv's output (B, S, 2 Hk Dk + Hv Dv); ``own``: the same blocks of arrays as
    wide as their own part of it (dq, dk: Hk Dk; dv: Hv Dv)."""
    block = _SLABS * _SLAB
    ns = sp // block
    sl = (lambda c: ns - 1 - c) if rev else (lambda c: c)
    at = lambda width, first: pl.BlockSpec(  # noqa: E731
        (1, block, width), lambda b, j, c: (b, sl(c), first + j))
    v_first = 2 * hk * dk // (r * dv)
    joined = at(dk, 0), at(dk, hk), at(r * dv, v_first)
    own = at(dk, 0), at(dk, 0), at(r * dv, 0)
    heads = pl.BlockSpec((1, r, block, dv), lambda b, j, c: (b, j, sl(c), 0))  # o, do: (B, Hv, S, Dv)
    cols = pl.BlockSpec((1, block, hk * r), lambda b, j, c: (b, sl(c), 0))
    rows = pl.BlockSpec((1, 1, r, block), lambda b, j, c: (b, j, 0, sl(c)))
    states = pl.BlockSpec((1, 2 * _SLABS, r, dk, dv), lambda b, j, c: (b, sl(c), j, 0, 0))
    return (bsz, hk, ns), joined, own, heads, cols, rows, states


def _sizes(qkv, gr, dk):
    bsz, sp, width = qkv.shape
    hk, r = gr.shape[1:3]
    return bsz, sp, hk, r, dk, (width - 2 * hk * dk) // (hk * r)


def _params():
    return pallas_common.compiler_params(dimension_semantics=("parallel", "parallel", "arbitrary"))


# (jitted and inlined: a program traces each kernel once, however many layers call
# it, and every call site still lowers under its own scope names)
@functools.partial(jax.jit, static_argnames=("dk", "keep_states"), inline=True)
def _fwd_call(qkv, gc, bc, gr, dk, keep_states):
    bsz, sp, hk, r, dk, dv = _sizes(qkv, gr, dk)
    grid, joined, _, heads, cols, rows, states = _specs(bsz, sp, hk, r, dk, dv, rev=False)
    out_shape, out_specs = [jax.ShapeDtypeStruct((bsz, hk * r, sp, dv), qkv.dtype)], [heads]
    if keep_states:
        out_shape.append(jax.ShapeDtypeStruct((bsz, sp // _CHUNK, hk * r, dk, dv), F32))
        out_specs.append(states)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, r=r, keep_states=keep_states),
        grid=grid, in_specs=[*joined, cols, cols, rows],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((r, dk, dv), F32)], compiler_params=_params(),
        interpret=pallas_common.use_interpret(), name="gdn_fwd",
    )(qkv, qkv, qkv, gc, bc, gr)


@functools.partial(jax.jit, static_argnames="dk", inline=True)
def _bwd_call(qkv, gc, bc, gr, do, states, dk):
    bsz, sp, hk, r, dk, dv = _sizes(qkv, gr, dk)
    grid, joined, own, heads, cols, rows, st = _specs(bsz, sp, hk, r, dk, dv, rev=True)
    wide = lambda width: jax.ShapeDtypeStruct((bsz, sp, width), qkv.dtype)  # noqa: E731
    like = jax.ShapeDtypeStruct(gr.shape, gr.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, r=r),
        grid=grid, in_specs=[*joined, cols, cols, rows, heads, st],
        out_specs=[*own, rows, rows],
        out_shape=[wide(hk * dk), wide(hk * dk), wide(hk * r * dv), like, like],
        scratch_shapes=[pltpu.VMEM((r, dk, dv), F32)], compiler_params=_params(),
        interpret=pallas_common.use_interpret(), name="gdn_bwd",
    )(qkv, qkv, qkv, gc, bc, gr, do, states)


def _prepared(g, beta, hk):
    """The running sums of the log-decays from each chunk's start, a position a
    sublane and a position a lane; ``beta`` beside the first."""
    bsz, sp, hv = g.shape
    gc = jnp.cumsum(g.reshape(bsz, sp // _CHUNK, _CHUNK, hv), axis=2).reshape(bsz, sp, hv)
    return gc, beta, gc.transpose(0, 2, 1).reshape(bsz, hk, hv // hk, sp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _delta_core(qkv, g, beta, hk, dk):
    """qkv (B, S, 2 Hk Dk + Hv Dv), g and beta (B, S, Hv) float32, S in whole
    steps -> o (B, Hv, S, Dv)."""
    return _fwd_call(qkv, *_prepared(g, beta, hk), dk=dk, keep_states=False)[0]


def _core_fwd(qkv, g, beta, hk, dk):
    o, states = _fwd_call(qkv, *_prepared(g, beta, hk), dk=dk, keep_states=True)
    return o, (qkv, g, beta, states)


def _core_bwd(hk, dk, res, do):
    qkv, g, beta, states = res
    dq, dkey, dv, dg, db = _bwd_call(qkv, *_prepared(g, beta, hk), do, states, dk=dk)
    bsz, sp, hv = g.shape
    return (jnp.concatenate([dq, dkey, dv], axis=-1),
            *(t.reshape(bsz, hv, sp).transpose(0, 2, 1) for t in (dg, db)))


_delta_core.defvjp(_core_fwd, _core_bwd)


def gated_delta_fused(qkv, g, beta, hk: int, dk: int, chunk: int = _CHUNK):
    """The rule through the kernels (`_delta_core`), for sizes inside `scan_path`'s
    envelope: ``qkv`` (B, S, 2 Hk Dk + Hv Dv), `ops/ssd`'s conv + SiLU output
    untouched (``[q | k | v]`` a position, q and k NOT normalised: the kernels do
    it), ``g`` (log-decay, <= 0) and ``beta`` (B, S, Hv) -> ``o`` (B, S, Hv, Dv) in
    ``qkv``'s dtype, which is the compute dtype. Outside the kernels only the
    padding to whole steps, o's transposition (see above), the running sums and
    the transpose of the scalars, and in the backward one concatenation."""
    if chunk != _CHUNK:
        raise ValueError(f"the fused delta rule is written for chunks of {_CHUNK}, not {chunk}")
    s = qkv.shape[1]
    pad = -s % (_SLABS * _SLAB)
    if pad:
        qkv, g, beta = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (qkv, g, beta))
    o = _delta_core(qkv, g.astype(F32), beta.astype(F32), hk, dk)
    return o.transpose(0, 2, 1, 3)[:, :s]


def _fused_vmem_mb(r: int, dk: int, dv: int, hv: int, itemsize: int) -> float:
    """What a backward step holds in VMEM, reckoned from the shapes: the blocks of
    q, k, dq, dk, of v, do, dv, of the scalars and of the entering states of the
    step's chunks (two buffers each), the carried gradient, the float32
    temporaries of a value head of a slab (a dozen packed blocks, a dozen
    slab-sized products), and of a slab q and k normalised and their gradients."""
    step = _SLABS * _SLAB
    lanes = -(-hv // 128) * 128
    blocks = 2 * (4 * step * dk * itemsize + 3 * step * r * dv * itemsize
                  + 2 * step * lanes * 4 + 3 * 8 * step * 4 + 2 * _SLABS * r * dk * dv * 4)
    scratch = r * dk * dv * 4
    temps = (_SLABS * r * (12 * _CHUNK * _SLAB * 4 + 12 * _SLAB * (dk + dv) * 4)
             + _SLABS * 6 * _SLAB * dk * 4 + 2 * _SLAB * _SLAB * 4)
    return (blocks + scratch + temps) / 2**20


def scan_path(hk: int, hv: int, dk: int, dv: int, chunk: int, dtype) -> str:
    """``"fused"`` or ``"plain"`` for a gated delta rule of these sizes, from the
    shapes and the backend alone: no flag, no environment variable, no model's
    name. `models/gdn.block` and `models/gdn.path_counts` both ask here. The
    fused kernels take, and everything else takes the plain body:

    - a chip (`pallas_common.use_interpret`'s rule, the one switch of this
      repo's kernels: on the CPU they run interpreted, which only the tests that
      call `gated_delta_fused` themselves, or turn `block` to it, want);
    - chunks of 64, the size the kernels are written for (two side by side fill
      the 128 lanes);
    - ``dk`` and ``dv`` multiples of 128: a head's q, k and v are whole lane tiles
      of the token-major array, and every product's operands whole MXU tiles;
    - ``hv`` a multiple of ``hk``: a grid step is a key head and its value heads,
      and q and k together whole blocks of a key head's v (``2 Hk Dk`` a multiple
      of ``R Dv``: v's blocks are counted from the array's first lane);
    - bf16 or float32 compute;
    - a VMEM charge (`_fused_vmem_mb`, 11.3 MB at the published sizes) inside the
      budget `flash_attention._seq_envelope` reckons with.
    """
    dtype = jnp.dtype(dtype)
    if (pallas_common.use_interpret() or hk < 1 or hv % hk
            or dtype not in (jnp.bfloat16, jnp.float32)):
        return "plain"
    inside = (chunk == _CHUNK and dk % 128 == 0 and dv % 128 == 0
              and 2 * hk * dk % (hv // hk * dv) == 0
              and 1.1 * _fused_vmem_mb(hv // hk, dk, dv, hv, dtype.itemsize)
              <= pallas_common.VMEM_LIMIT_MB)
    return "fused" if inside else "plain"
