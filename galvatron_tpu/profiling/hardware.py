"""Hardware profiler: ICI/DCN collective bandwidth + overlap coefficient.

The nccl-tests replacement (reference: galvatron/core/profiler.py:404-532
shells out to all_reduce_perf/sendrecv_perf and parses 'Avg bus bandwidth';
profile_overlap.py:14-160 measures the compute/comm overlap slowdown with
CUDA streams). Here each measurement is a jitted collective over a subset of
mesh axes, timed with forced host synchronization:

- allreduce bus bandwidth per (group size, consec-vs-strided axis layout) —
  consec = minor mesh axes (ICI-adjacent), strided = major axes, the layout
  dimension the search engine prices (hardware_configs/allreduce_bandwidth_*);
- p2p bandwidth per pipeline degree via ppermute along the pp axis;
- overlap coefficient: slowdown of a matmul+allreduce program vs
  max(matmul, allreduce) alone.

Writes the ProfiledHardware JSON schema consumed by the search engine.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Sequence

import jax

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from galvatron_tpu.parallel.mesh import MeshAxes, build_mesh
from galvatron_tpu.search.cost_model import ProfiledHardware


def _default_chain() -> int:
    """Measurement window length: on accelerators, chain dependent in-jit
    applications and sync once per window — per-call host syncs would fold
    the host round-trip into every sample (it dwarfs a single collective on
    remote-dispatch setups and pads small-message bandwidths everywhere).
    On the CPU simulation the numbers are synthetic anyway and the scanned
    program compiles much slower, so stay with per-call timing."""
    return 1 if jax.default_backend() == "cpu" else 8


def _time_fn(fn, *args, iters: int = 5, chain: Optional[int] = None) -> float:
    """Median wall time (s) per application of ``fn`` (shape-preserving —
    every profiled collective here is), timed in windows of ``chain``
    dependent applications (see _default_chain)."""
    chain = chain or _default_chain()
    single = len(args) == 1
    if chain == 1:
        run = fn if getattr(fn, "lower", None) else jax.jit(fn)
    else:

        @jax.jit
        def run(*a):
            def body(c, _):
                o = fn(*c)
                return ((o,) if single else tuple(o)), None

            c, _ = jax.lax.scan(body, tuple(a), None, length=chain)
            return c

    out = run(*args)  # compile + warm
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = run(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / chain)
    return float(np.median(times))


def profile_allreduce(
    mesh: Mesh,
    axes: MeshAxes,
    msg_mb: float = 64.0,
    dtype=jnp.bfloat16,
) -> Dict[str, float]:
    """Bus bandwidth (GB/s) for every (group size, consec) the mesh supports."""
    out: Dict[str, float] = {}
    m = len(axes.data_axes)
    nbytes = np.dtype(dtype).itemsize
    n_elem = int(msg_mb * 1e6 / nbytes)
    x = jnp.ones((n_elem,), dtype)
    for k in range(1, m + 1):
        size = 2**k
        for consec in (True, False):
            if k == m and not consec:
                continue  # full-extent group has one layout
            group = axes.tp_axes(size, consec)

            @jax.jit
            def ar(x, group=group):
                y = jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, P(axes.data_axes))
                )
                return jax.shard_map(
                    lambda v: jax.lax.psum(v, group),
                    mesh=mesh,
                    in_specs=P(axes.data_axes),
                    out_specs=P(axes.data_axes),
                    axis_names=set(axes.data_axes) | {axes.pp},
                    check_vma=False,
                )(y)

            t = _time_fn(ar, x)
            bus_gb = 2.0 * (size - 1) / size * (n_elem * nbytes / size) / t / 1e9
            out[f"{size}_{int(consec)}"] = round(bus_gb * size, 3)
    return out


def profile_p2p(
    world: int, msg_mb: float = 64.0, dtype=jnp.bfloat16, num_slices: int = 1
) -> Dict[int, float]:
    """ppermute bandwidth (GB/s) per pipeline degree (reference p2p profile:
    core/profiler.py:429-441). With ``num_slices``>1 the mesh is built
    slice-major exactly as the runtime's (mesh.build_mesh), so the pp ring
    crosses the DCN boundary and the measured bandwidth IS the DCN number
    the search will price pp>1 with."""
    out: Dict[int, float] = {}
    nbytes = np.dtype(dtype).itemsize
    pp = 2
    while pp <= world:
        mesh, axes = build_mesh(pp=pp, num_slices=num_slices if num_slices > 1 else None)
        n_per = int(msg_mb * 1e6 / nbytes)  # message size per stage boundary
        x = jnp.ones((pp, n_per), dtype)
        perm = [(i, (i + 1) % pp) for i in range(pp)]

        @jax.jit
        def send(x, mesh=mesh, perm=perm):
            return jax.shard_map(
                lambda v: jax.lax.ppermute(v, "pp", perm),
                mesh=mesh,
                in_specs=P("pp"),
                out_specs=P("pp"),
                axis_names={"pp"},
                check_vma=False,
            )(x)

        t = _time_fn(send, x)
        out[pp] = round((n_per * nbytes) / t / 1e9, 3)
        pp *= 2
    return out


def profile_overlap_coe(mesh: Mesh, axes: MeshAxes, size_mb: float = 64.0) -> float:
    """Compute/communication overlap slowdown (reference:
    profile_hardware/profile_overlap.py — gemm + allreduce on parallel CUDA
    streams; here: one XLA program containing both, which XLA overlaps)."""
    n = 2048
    a = jnp.ones((n, n), jnp.bfloat16)
    nbytes = int(size_mb * 1e6 / 2)
    x = jnp.ones((nbytes,), jnp.bfloat16)
    group = axes.data_axes

    def mm(a):
        for _ in range(8):
            a = a @ a * 0.01
        return a

    sm = lambda f: jax.shard_map(
        f, mesh=mesh, in_specs=P(axes.data_axes), out_specs=P(axes.data_axes),
        axis_names=set(axes.data_axes) | {axes.pp}, check_vma=False,
    )
    ar = lambda v: jax.lax.psum(v, group)
    t_mm = _time_fn(jax.jit(mm), a)
    t_ar = _time_fn(jax.jit(sm(ar)), x)
    t_both = _time_fn(jax.jit(lambda a, x: (mm(a), sm(ar)(x))), a, x)
    coe = t_both / max(t_mm, t_ar)
    return round(max(1.0, float(coe)), 4)


def dcn_crossing_keys(world: int, num_slices: int) -> list:
    """Which "size_consec" allreduce keys cross the slice/DCN boundary under
    the runtime's slice-major mesh ordering (mesh.build_mesh): the top
    log2(num_slices) data axes span slices, so every STRIDED (major-axis)
    group crosses, and a CONSECUTIVE group crosses once it outgrows one
    slice's extent. (The pp axis is outermost, so with num_slices>1 every
    p2p degree crosses too.)"""
    if num_slices <= 1 or world <= 1:
        return []
    m = int(np.log2(world))
    s = int(np.log2(num_slices))
    out = []
    for k in range(1, m + 1):
        if k < m:
            out.append(f"{2 ** k}_0")  # strided: always on the major axes
        if k > m - s:
            out.append(f"{2 ** k}_1")  # consecutive group wider than a slice
    return out


def profile_hardware(
    msg_mb: float = 64.0, out_path: Optional[str] = None,
    num_slices: Optional[int] = None,
) -> ProfiledHardware:
    """Full sweep (reference entry: profile_hardware/profile_hardware.py).

    Pods/multislice recipe (docs/HARDWARE_PROFILING.md): run this once on
    the target topology (``profile-hardware --num_slices N`` on a DCN-
    connected deployment; N is auto-detected from device slice indices when
    omitted). The profiler builds the SAME slice-major mesh the runtime
    uses, so the (size, consec) groups it times are exactly the axis
    combinations the search prices — strided/major groups and the pp ring
    ride the DCN and their measured entries carry the DCN bandwidth, keyed
    identically. ``dcn_keys`` records which entries crossed the boundary."""
    mesh, axes = build_mesh(pp=1, num_slices=num_slices)
    world = mesh.devices.size
    eff_slices = num_slices or len(
        {getattr(d, "slice_index", 0) for d in np.asarray(mesh.devices).ravel()}
    )
    # mirror build_mesh's inference guard: it only slice-major-orders clean
    # binary factors, so anything else must be treated as one slice here too
    # (a 3-slice detection would otherwise crash the p2p mesh build and
    # mislabel dcn_keys)
    if eff_slices < 1 or eff_slices & (eff_slices - 1) or world % eff_slices:
        eff_slices = 1
    hw = ProfiledHardware(
        allreduce_bw=profile_allreduce(mesh, axes, msg_mb),
        p2p_bw=profile_p2p(world, msg_mb, num_slices=eff_slices) if world > 1 else {},
        overlap_coe=profile_overlap_coe(mesh, axes, msg_mb) if world > 1 else 1.1,
        dcn_keys=dcn_crossing_keys(world, eff_slices),
    )
    if out_path:
        from galvatron_tpu.utils.config_utils import save_profiled_hardware

        save_profiled_hardware(hw, out_path)
    return hw
