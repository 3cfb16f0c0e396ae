"""Device-mesh construction and per-layer axis assignment.

The reference materializes one NCCL process group per (tp_size, consecutive)
combination plus dual DP groups and redistribution groups between layers
(galvatron/core/comm_groups.py:58-254). On TPU we instead build ONE
``jax.sharding.Mesh`` whose non-pipeline extent is factored into **binary
axes**: world W, pipeline degree P gives mesh shape ``(P, 2, 2, ..., 2)`` with
axis names ``("pp", "x0", "x1", ..., "x{m-1}")`` where ``m = log2(W / P)``.

A layer strategy then maps to a *subset* of the binary axes:

- TP degree ``2^k`` with ``tp_consec=True`` takes the **minor** k axes
  (``x{m-k}..x{m-1}``) — adjacent device ids, the reference's "consecutive"
  rank layout which lands on the fastest ICI links; ``tp_consec=False`` takes
  the **major** k axes — strided ranks (reference: gen_tp_group_dist,
  galvatron/core/comm_groups.py:58-89).
- The complementary axes are the DP axes (dual construction, reference:
  gen_dp_group_dist, comm_groups.py:91-122).
- Context parallelism (ring attention) takes the minor axes of the DP block.

Because ``PartitionSpec`` entries accept *tuples* of axis names, a per-layer
choice of TP/DP axes is just a per-layer ``NamedSharding`` — XLA inserts the
activation resharding collectives between layers with different TP that the
reference hand-codes in galvatron/core/redistribute.py.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy


def _log2(n: int) -> int:
    k = int(round(math.log2(n)))
    if 2**k != n:
        raise ValueError(f"{n} is not a power of two")
    return k


@dataclass(frozen=True)
class MeshAxes:
    """Axis-name bookkeeping for the factored mesh."""

    pp: str
    data_axes: Tuple[str, ...]  # binary axes, major → minor

    @property
    def all_axes(self) -> Tuple[str, ...]:
        return (self.pp,) + self.data_axes

    def tp_axes(self, tp: int, consec: bool = True) -> Tuple[str, ...]:
        """Axes carrying tensor parallelism for a layer with degree ``tp``."""
        k = _log2(tp)
        if k > len(self.data_axes):
            raise ValueError(f"tp={tp} exceeds mesh data extent 2^{len(self.data_axes)}")
        if k == 0:
            return ()
        return self.data_axes[-k:] if consec else self.data_axes[:k]

    def dp_axes(self, tp: int, consec: bool = True, cp: int = 1) -> Tuple[str, ...]:
        """Axes carrying (sharded-)data parallelism: the complement of TP∪CP."""
        used = set(self.tp_axes(tp, consec)) | set(self.cp_axes(tp, consec, cp))
        return tuple(a for a in self.data_axes if a not in used)

    def cp_axes(self, tp: int, consec: bool = True, cp: int = 1) -> Tuple[str, ...]:
        """Context-parallel (ring attention) axes: minor axes of the non-TP block."""
        if cp == 1:
            return ()
        k = _log2(cp)
        rest = [a for a in self.data_axes if a not in set(self.tp_axes(tp, consec))]
        if k > len(rest):
            raise ValueError(f"cp={cp} exceeds remaining mesh extent")
        return tuple(rest[-k:])

    def ep_axes(self, tp: int, consec: bool = True, ep: int = 1) -> Tuple[str, ...]:
        """Expert-parallel axes for MoE layers: same minor-axes-of-the-non-TP-
        block selection as cp (EP subdivides data parallelism, reference:
        parallel_state.py:450-478); a strategy never uses both (strategy.py)."""
        return self.cp_axes(tp, consec, ep)


def build_mesh(
    pp: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
    axis_prefix: str = "x",
    num_slices: Optional[int] = None,
) -> Tuple[Mesh, MeshAxes]:
    """Build the factored mesh over all (or given) devices.

    Device order follows ``jax.devices()`` — on real TPU slices jax returns
    devices in torus-major order so minor mesh axes correspond to
    ICI-adjacent chips, matching the 'consecutive ranks = intra-node NVLink'
    empirical layout the reference profiles (SURVEY §5, hardware_configs).

    Multislice (DCN-connected slices; the reference's 2-node×8-GPU IB
    topology class): devices are ordered slice-major so the OUTERMOST mesh
    dims span slices — pipeline stages (which tolerate low-bandwidth p2p)
    and the major/'strided' data axes cross the DCN boundary, while
    minor/'consecutive' axes stay on ICI; the hardware profiler then
    measures DCN bandwidth for exactly the axis combinations that pay it.
    ``num_slices`` defaults to the distinct ``slice_index`` values on the
    devices (1 on single-slice systems and the CPU sim).
    """
    if devices is None:
        devices = jax.devices()
    world = len(devices)
    if world % pp != 0:
        raise ValueError(f"pp={pp} must divide world size {world}")
    if num_slices:
        # explicit request: invalid values are hard errors
        if not _is_pow2_int(num_slices):
            raise ValueError(f"num_slices must be a power of two, got {num_slices}")
        if world % num_slices:
            raise ValueError(
                f"{num_slices} slices must evenly divide the {world} devices"
            )
        devices = sorted(devices, key=_slice_key)
    else:
        # inference: reorder only when the detected slice structure is a
        # clean binary factor — otherwise keep jax's device order (device
        # subsets or exotic topologies must not break single-slice callers)
        n = len({_slice_key(d)[0] for d in devices})
        if n > 1 and _is_pow2_int(n) and world % n == 0:
            devices = sorted(devices, key=_slice_key)
    m = _log2(world // pp)
    shape = (pp,) + (2,) * m
    dev_array = np.asarray(devices).reshape(shape)
    names = ("pp",) + tuple(f"{axis_prefix}{i}" for i in range(m))
    mesh = Mesh(dev_array, names)
    return mesh, MeshAxes(pp="pp", data_axes=names[1:])


def _is_pow2_int(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


def _slice_key(d) -> Tuple[int, int]:
    """Slice-major device ordering key (slice_index absent → one slice)."""
    return (getattr(d, "slice_index", 0), d.id)


def data_parallel_degree(axes: MeshAxes, s: LayerStrategy) -> int:
    return 2 ** len(axes.dp_axes(s.tp, s.tp_consec, s.cp))


def batch_spec(axes: MeshAxes, s: LayerStrategy) -> P:
    """Sharding for a (batch, seq, ...) activation entering a layer.

    Batch over DP axes always; sequence over TP axes when Megatron-SP is on
    (reference: mappings_group scatter/gather, SURVEY §2.3 'SP'), and over CP
    axes when ring attention is on.
    """
    dp = axes.dp_axes(s.tp, s.tp_consec, s.cp)
    seq_axes: Tuple[str, ...] = ()
    if s.sp:
        seq_axes += axes.tp_axes(s.tp, s.tp_consec)
    if s.cp > 1:
        seq_axes += axes.cp_axes(s.tp, s.tp_consec, s.cp)
    return P(dp or None, seq_axes or None)


def moe_token_axes(axes: MeshAxes, s: LayerStrategy) -> Tuple[str, ...]:
    """Axes sharding the flattened (B·S) token dim for MoE dispatch: the
    batch axes plus (under SP/CP) the sequence axes — the row-major
    (B, S, H) → (B·S, H) merge keeps the product sharding."""
    bs = batch_spec(axes, s)

    def flat(e) -> Tuple[str, ...]:
        if e is None:
            return ()
        return (e,) if isinstance(e, str) else tuple(e)

    return flat(bs[0]) + flat(bs[1])


def global_batch_spec(axes: MeshAxes) -> P:
    """Sharding for the raw token batch: all data axes (dataloader layout)."""
    return P(axes.data_axes or None, None)


# Curated XLA latency-hiding flag sets (--xla_overlap). 'auto' turns on the
# latency-hiding scheduler — the pass that moves collective-permute/all-gather
# starts above independent compute so the decomposed collective-matmul rings
# (ops/collective_matmul.py) and the per-layer ZeRO gradient buckets
# (sharding.overlap_grad_sync) actually overlap instead of merely being
# reorderable. 'aggressive' additionally fuses collectives into async pairs
# across multiple scheduling steps — higher compile time, occasionally better
# steady-state. Recorded verbatim in the run manifest and every BENCH metric
# line so a BENCH_r* delta is attributable to code, not scheduler drift.
XLA_OVERLAP_FLAG_SETS = {
    "off": (),
    "auto": (
        "--xla_tpu_enable_latency_hiding_scheduler=true",
        "--xla_tpu_enable_async_collective_fusion=true",
    ),
    "aggressive": (
        "--xla_tpu_enable_latency_hiding_scheduler=true",
        "--xla_tpu_enable_async_collective_fusion=true",
        "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
        "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
        "--xla_tpu_overlap_compute_collective_tc=true",
    ),
}


def _tpu_backend_expected() -> bool:
    """True when this process will initialize a TPU backend — decided WITHOUT
    touching jax (the flags must land in XLA_FLAGS before first backend use).
    An explicit JAX_PLATFORMS pin is authoritative; otherwise presence of
    libtpu decides. CPU/GPU backends must never see --xla_tpu_* flags: XLA
    rejects unknown flags at backend init and the process dies."""
    plat = os.environ.get("JAX_PLATFORMS", "") or os.environ.get("JAX_PLATFORM_NAME", "")
    if plat:
        return "tpu" in plat.lower()
    try:
        import importlib.util

        return importlib.util.find_spec("libtpu") is not None
    except Exception:  # noqa: BLE001 — any probe failure means "not a TPU"
        return False


def apply_xla_overlap(mode: str) -> List[str]:
    """Append the ``--xla_overlap`` mode's curated flag set to ``XLA_FLAGS``
    (idempotent — re-applying or overlapping a user-supplied flag never
    duplicates a token). Returns the flags in effect for this mode, or ``[]``
    when nothing was applied ('off', or a non-TPU backend). Must run before
    the first jax backend touch; the trainer calls it from ``train()`` and
    records mode + returned flags in the run manifest."""
    if mode not in XLA_OVERLAP_FLAG_SETS:
        raise ValueError(
            f"xla_overlap must be one of {sorted(XLA_OVERLAP_FLAG_SETS)}, got {mode!r}"
        )
    flags = XLA_OVERLAP_FLAG_SETS[mode]
    if not flags or not _tpu_backend_expected():
        return []
    toks = os.environ.get("XLA_FLAGS", "").split()
    for f in flags:
        if f not in toks:
            toks.append(f)
    os.environ["XLA_FLAGS"] = " ".join(toks)
    return list(flags)


def ambient_or(mesh):
    """Mesh to hand a nested ``shard_map``: inside a manual region (the pp>1
    pipeline runs stages under a manual-'pp' shard_map) a nested shard_map
    must be given the ambient AbstractMesh — whose manual axes are marked
    Manual — not the original concrete mesh, or tracing fails with an
    axis-type mismatch. Load-bearing for every cp impl (ring/a2a) at pp>1."""
    am = jax.sharding.get_abstract_mesh()
    types = getattr(am, "axis_types", None) or ()
    if any(t == jax.sharding.AxisType.Manual for t in types):
        return am
    return mesh


def manual_axis_names(am) -> set:
    """Every ambient-mesh axis not already Manual — the axis_names set a
    nested shard_map wrapping a Mosaic kernel must manualize. Any axis left
    auto — including a size-1 'pp' axis at pp=1 or the dp axes carrying the
    batch — keeps the body under GSPMD, which cannot partition Mosaic custom
    calls on a real multi-chip TPU ("Mosaic kernels cannot be automatically
    partitioned"; caught by tests/test_topology_aot.py — CPU interpret-mode
    kernels never surface it). Axes already Manual (the pp engines' 'pp')
    must not be re-bound."""
    types = getattr(am, "axis_types", None) or ()
    manual = {
        n for n, t in zip(am.axis_names, types)
        if t != jax.sharding.AxisType.Manual
    }
    return manual or set(am.axis_names)
