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
qkv / MLP-up / attention-out / MLP-down seams (modeling._proj_up /
_proj_down dispatch here when the layer strategy sets ``tp_overlap``):

- :func:`allgather_einsum` — all-gather⊗matmul. ``x`` arrives logically
  seq-sharded over the TP axes (the sp layer boundary layout); each
  device GEMMs the seq chunk it holds against its local weight shard and
  rotates the chunk to its ring neighbor, writing each result at the
  originating chunk's seq offset. Output: full seq, weight-shard dim
  TP-sharded — bit-compatible with GSPMD's gather→matmul.
- :func:`einsum_reducescatter` — matmul⊗reduce-scatter. Each device
  GEMMs one seq chunk per step and adds it into an accumulator that
  rotates the ring; after T steps device i holds the fully-summed chunk
  i (the sp seq-sharded output layout). ``scatter_output=False`` (no sp)
  appends tiled all-gathers to reconstruct the replicated output — the
  gather half of the all-reduce still blocks, but the reduce half is
  pipelined.

Both fall back to a plain ``jnp.einsum`` (GSPMD collectives) whenever the
decomposition cannot apply: single device, T == 1, or a seq / shard dim
the ring chunking does not divide. The ring index over multiple binary
mesh axes is ``jax.lax.axis_index(tuple(tp_axes))`` — row-major, first
axis most significant — and the ``ppermute`` permutation is expressed in
that same flattened index space, so tp_consec=True and False layouts
share one code path. Autodiff needs no custom VJP: shard_map transposes
``ppermute`` to the reverse rotation and ``dynamic_update_slice`` to the
matching slice, which is exactly the dual ring (the transpose of
AG⊗matmul is RS⊗matmul — the parity tests check gradients through both).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp



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


def _batch_indivisible(x, mesh, dp: Tuple[str, ...]) -> bool:
    """shard_map needs every sharded dim to divide exactly — bail to the
    plain einsum when the (leading) batch dim does not."""
    return bool(dp) and x.shape[0] % tp_group_size(mesh, dp) != 0


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
    shapes out — only the layout differs from the plain einsum."""
    from galvatron_tpu.parallel.mesh import ambient_or, manual_axis_names
    from jax.sharding import PartitionSpec as P

    x_sub, w_sub, out_sub = _parse(subscripts)
    tp = tuple(tp_axes or ())
    dp = tuple(dp_axes or ())
    T = tp_group_size(mesh, tp)
    seq_x = x_sub.index(seq)
    shard_letter = w_sub[w_shard_dim]
    if (
        T <= 1
        or mesh.devices.size <= 1
        or x.shape[seq_x] % T != 0
        or w.shape[w_shard_dim] % T != 0
        or _batch_indivisible(x, mesh, dp)
    ):
        return jnp.einsum(subscripts, x, w)
    seq_out = out_sub.index(seq)
    shard_out = out_sub.index(shard_letter)
    batch_letter = x_sub[0]

    def spec(sub: str, entries: dict) -> P:
        return P(*[entries.get(c) for c in sub])

    x_entries = {seq: _axis_entry(tp)}
    out_entries = {shard_letter: _axis_entry(tp)}
    if dp:
        x_entries[batch_letter] = _axis_entry(dp)
        out_entries[batch_letter] = _axis_entry(dp)
    w_spec = P(*[_axis_entry(tp) if i == w_shard_dim else None for i in range(w.ndim)])
    s_local = x.shape[seq_x] // T
    perm = [(j, (j + 1) % T) for j in range(T)]

    def local_fn(x_l, w_l):
        idx = jax.lax.axis_index(tp)
        out_shape = [0] * len(out_sub)
        chunk_shape = dict(zip(x_sub, x_l.shape))
        chunk_shape.update(
            {c: d for c, d in zip(w_sub, w_l.shape) if c not in x_sub}
        )
        for i, c in enumerate(out_sub):
            out_shape[i] = chunk_shape[c] if c != seq else x.shape[seq_x]
        out = jnp.zeros(out_shape, dtype=jnp.result_type(x_l.dtype, w_l.dtype))
        chunk = x_l
        for t in range(T):
            # chunk in hand originated at ring position (idx - t); GEMM it
            # while (on hardware, under the latency-hiding scheduler) the
            # next hop's ppermute is in flight
            src = (idx - t) % T
            y_c = jnp.einsum(subscripts, chunk, w_l)
            out = jax.lax.dynamic_update_slice_in_dim(
                out, y_c.astype(out.dtype), src * s_local, axis=seq_out
            )
            if t < T - 1:
                chunk = jax.lax.ppermute(chunk, tp, perm)
        return out

    am = ambient_or(mesh)
    with jax.named_scope("allgather_einsum"):
        return jax.shard_map(
            local_fn,
            mesh=am,
            in_specs=(spec(x_sub, x_entries), w_spec),
            out_specs=spec(out_sub, out_entries),
            axis_names=manual_axis_names(am),
            check_vma=False,
        )(x, w)


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
):
    """``einsum(subscripts, x, w)`` with the trailing TP reduction pipelined
    behind the GEMM chunks. ``w`` is TP-sharded at ``w_shard_dim`` (the
    row-parallel *contracted* dim, whose letter also indexes ``x``'s
    TP-sharded dim), so each device's einsum yields a partial sum. The
    accumulator ring reduces it seq-chunk by seq-chunk: ``scatter_output=True``
    returns the sp layout (out seq-sharded over tp); ``False`` appends tiled
    all-gathers (minor axis first, matching the row-major ring index) for a
    replicated output — the full all-reduce's gather half."""
    from galvatron_tpu.parallel.mesh import ambient_or, manual_axis_names
    from jax.sharding import PartitionSpec as P

    x_sub, w_sub, out_sub = _parse(subscripts)
    tp = tuple(tp_axes or ())
    dp = tuple(dp_axes or ())
    T = tp_group_size(mesh, tp)
    shard_letter = w_sub[w_shard_dim]
    seq_x = x_sub.index(seq)
    x_shard_dim = x_sub.index(shard_letter)
    if (
        T <= 1
        or mesh.devices.size <= 1
        or x.shape[seq_x] % T != 0
        or x.shape[x_shard_dim] % T != 0
        or _batch_indivisible(x, mesh, dp)
    ):
        return jnp.einsum(subscripts, x, w)
    seq_out = out_sub.index(seq)
    batch_letter = x_sub[0]

    def spec(sub: str, entries: dict) -> P:
        return P(*[entries.get(c) for c in sub])

    x_entries = {shard_letter: _axis_entry(tp)}
    out_entries = {}
    if scatter_output:
        out_entries[seq] = _axis_entry(tp)
    if dp:
        x_entries[batch_letter] = _axis_entry(dp)
        out_entries[batch_letter] = _axis_entry(dp)
    w_spec = P(*[_axis_entry(tp) if i == w_shard_dim else None for i in range(w.ndim)])
    s_global = x.shape[seq_x]
    s_local = s_global // T
    perm = [(j, (j + 1) % T) for j in range(T)]

    def local_fn(x_l, w_l):
        idx = jax.lax.axis_index(tp)

        def partial_chunk(c):
            x_c = jax.lax.dynamic_slice_in_dim(x_l, c * s_local, s_local, axis=seq_x)
            return jnp.einsum(subscripts, x_c, w_l)

        # the accumulator that rests on device i visits i+1, ..., i+T = i;
        # at step t device i therefore contributes its partial for chunk
        # (i - 1 - t) mod T, overlapping the GEMM with the incoming hop
        acc = partial_chunk((idx - 1) % T)
        for t in range(1, T):
            acc = jax.lax.ppermute(acc, tp, perm)
            acc = acc + partial_chunk((idx - 1 - t) % T)
        if not scatter_output:
            # minor (fastest-varying) axis first: each tiled gather then
            # concatenates ring-contiguous seq blocks in index order
            for a in reversed(tp):
                acc = jax.lax.all_gather(acc, a, axis=seq_out, tiled=True)
        return acc

    am = ambient_or(mesh)
    with jax.named_scope("einsum_reducescatter"):
        return jax.shard_map(
            local_fn,
            mesh=am,
            in_specs=(spec(x_sub, x_entries), w_spec),
            out_specs=spec(out_sub, out_entries),
            axis_names=manual_axis_names(am),
            check_vma=False,
        )(x, w)
