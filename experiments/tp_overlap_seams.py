"""The four tensor-parallel projection seams alone, ring against GSPMD, on four chips.

Times forward + backward of each seam of an opt-1.3b layer under the four-chip cell's
plan (tp 4 + sp, a micro-batch of 4 x 2048 tokens, h 2048, ffn 8192, 32 heads of 64,
bf16) as GSPMD partitions the plain einsum (all-gather, GEMM / GEMM, reduce-scatter) and
as ``ops/collective_matmul.py`` decomposes it, in the arms

    gspmd       the plain einsum under the seam's sp shardings
    ring        two-way, ring order from the devices' coordinates (what the program runs)
    one_way     whole chunks one way, coordinate order
    flat_order  two-way, the flattened-index order (0 -> 1 -> 2 -> 3 -> 0)
    whole       ``ring`` with the head-major all-gather sides (qkv forward, out_proj
                backward) gathered whole in front of one GEMM (PR 29's program)
    batch2/4    ``ring`` with those sides pipelined over the batch in 2 / 4 pieces

The ring's shape test is switched off here so that every seam runs on the ring: its
threshold (``RING_MIN_COVER``) is set from this table (PERF.md §6, PR 29); ``ring`` asks
``batch_pieces`` as the program does, and what that returns is read from the three last
arms (PERF.md §6, PR 32), which only the two head-major seams run. ``--profile ARMS`` also
traces those arms and writes device 0's operations, by kind and as one pass's timeline,
to ``chiprun_out/seam_profile_<seam>_<arm>.txt``: how the ring's copies were found.

    chiprun --chips 4 -- python experiments/tp_overlap_seams.py
    JAX_PLATFORMS=cpu python experiments/tp_overlap_seams.py --aot [--dump DIR]

``--aot`` compiles every arm for a described v5e 2x2 instead (no chip, no times) and
prints what the compiler made of it: collectives and fusions by kind.
"""

import argparse
import collections
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from galvatron_tpu.ops import collective_matmul as cm  # noqa: E402
from galvatron_tpu.parallel.mesh import build_mesh  # noqa: E402

B, S, H, F, N, D = 4, 2048, 2048, 8192, 32, 64
#: seam -> (entry point, subscripts, x shape, w shape, w_shard_dim)
SEAMS = {
    "qkv_proj": ("ag", "bsh,hcnd->bcnsd", (B, S, H), (H, 3, N, D), 2),
    "out_proj": ("rs", "bnsd,nde->bse", (B, N, S, D), (N, D, H), 0),
    "mlp_up": ("ag", "bsh,hf->bsf", (B, S, H), (H, F), 1),
    "mlp_down": ("rs", "bsf,fh->bsh", (B, S, F), (F, H), 0),
}
ARMS = ("gspmd", "ring", "one_way", "flat_order", "whole", "batch2", "batch4")
#: arms that differ from ``ring`` only where an all-gather side is head-major
BATCH_ARMS = {"whole": 1, "batch2": 2, "batch4": 4}
HEAD_MAJOR = ("qkv_proj", "out_proj")
REPEATS = 8  # seam applications inside one timed call (a scan over stacked weights)


def seam_specs(kind, subscripts, w_ndim, w_shard_dim, tp):
    """(x, w, y) PartitionSpecs of a seam under sp: what the layer hands it."""
    x_sub, w_sub, out_sub = cm._parse(subscripts)
    letter = w_sub[w_shard_dim]
    w_spec = P(*[tp if i == w_shard_dim else None for i in range(w_ndim)])
    if kind == "ag":
        return (P(*[tp if c == "s" else None for c in x_sub]), w_spec,
                P(*[tp if c == letter else None for c in out_sub]))
    return (P(*[tp if c == letter else None for c in x_sub]), w_spec,
            P(*[tp if c == "s" else None for c in out_sub]))


def build(arm, name, mesh, axes):
    """jitted ``(x, ws, g) -> (dx, dws)``: REPEATS forward + backward passes of
    the seam, one per stacked weight."""
    kind, subscripts, x_shape, w_shape, w_shard_dim = SEAMS[name]
    tp_axes = axes.tp_axes(4, True)
    tp = tp_axes if len(tp_axes) > 1 else tp_axes[0]
    x_spec, w_spec, y_spec = seam_specs(kind, subscripts, len(w_shape), w_shard_dim, tp)
    entry = cm.allgather_einsum if kind == "ag" else cm.einsum_reducescatter

    def seam(x, w):
        if arm == "gspmd":
            y = jnp.einsum(subscripts, x, w)
        else:
            y = entry(subscripts, x, w, mesh=mesh, dp_axes=(), tp_axes=tp_axes,
                      w_shard_dim=w_shard_dim)
        return jax.lax.with_sharding_constraint(y, NamedSharding(mesh, y_spec))

    def step(x, ws, g):
        def body(x_in, w):
            # the next pass's input hangs on this pass's dx, and its cotangent on
            # this pass's y: nothing of a pass is dead, no two passes overlap
            y, vjp = jax.vjp(seam, x_in, w)
            dx, dw = vjp(g + (y * 1e-3).astype(g.dtype))
            return x + (dx * 1e-3).astype(x.dtype), dw
        dx, dws = jax.lax.scan(body, x, ws)
        return dx, dws

    sh = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    ws_spec = P(None, *w_spec)
    y_shape = jax.eval_shape(lambda x, w: jnp.einsum(subscripts, x, w),
                             jax.ShapeDtypeStruct(x_shape, jnp.bfloat16),
                             jax.ShapeDtypeStruct(w_shape, jnp.bfloat16)).shape
    avals = (jax.ShapeDtypeStruct(x_shape, jnp.bfloat16, sharding=sh(x_spec)),
             jax.ShapeDtypeStruct((REPEATS,) + w_shape, jnp.bfloat16, sharding=sh(ws_spec)),
             jax.ShapeDtypeStruct(y_shape, jnp.bfloat16, sharding=sh(y_spec)))
    fn = jax.jit(step, in_shardings=tuple(a.sharding for a in avals),
                 out_shardings=(sh(x_spec), sh(ws_spec)))
    return fn, avals


class arm_settings:
    """The ring as the arm wants it, for the trace of one seam (the jitted
    seams key their cache on the ring order; ``ring_ways`` is read at trace
    time, so the caches are dropped between arms)."""

    def __init__(self, arm):
        self.arm = arm

    def __enter__(self):
        self.saved = (cm.ring_pays, cm.ring_ways, cm.mesh_ring_order, cm.batch_pieces)
        cm.ring_pays = lambda tp, *a, **k: tp > 1
        if self.arm in BATCH_ARMS:
            cm.batch_pieces = lambda *a, **k: BATCH_ARMS[self.arm]
        if self.arm == "one_way":
            cm.ring_ways = lambda tp: 1
        if self.arm == "flat_order":
            cm.mesh_ring_order = lambda mesh, tp: tuple(range(cm.tp_group_size(mesh, tp)))
        jax.clear_caches()

    def __exit__(self, *exc):
        cm.ring_pays, cm.ring_ways, cm.mesh_ring_order, cm.batch_pieces = self.saved
        jax.clear_caches()


def hlo_summary(text):
    kinds = collections.Counter()
    for m in re.finditer(r" (all-gather|all-reduce|reduce-scatter|collective-permute|"
                         r"all-to-all)(?:-start)?\(", text):
        kinds[m.group(1)] += 1
    for m in re.finditer(r" fusion\(.*?kind=(k\w+)", text):
        kinds["fusion:" + m.group(1)] += 1
    kinds["convolution"] = len(re.findall(r" convolution\(", text))
    kinds["dynamic-update-slice"] = len(re.findall(r" dynamic-update-slice\(", text))
    return dict(sorted(kinds.items()))


def profile(compiled, args, tag):
    """Device 0's operations of two traced calls, by kind and as the timeline
    of the last of the REPEATS passes, written under ``chiprun_out/``."""
    import shutil
    import tempfile

    from benchmark.lib import xplane

    tdir = tempfile.mkdtemp(prefix="seam_profile_")
    with jax.profiler.trace(tdir):
        for _ in range(2):
            jax.block_until_ready(compiled(*args))
    ops = xplane.leaf_ops(xplane.first_device(xplane.load(xplane.find_trace(tdir))))
    shutil.rmtree(tdir, ignore_errors=True)
    lines = [f"{tag}: {len(ops)} operations on device 0, busy "
             f"{xplane.busy_ns(ops) / 2 / REPEATS / 1e6:.4f} ms a pass, collectives in flight / "
             "exposed %.4f / %.4f ms a pass" % tuple(
                 v / 2 / REPEATS / 1e6 for v in xplane.collective_ns(ops))]
    sums = collections.defaultdict(lambda: [0, 0.0])
    for o in ops:
        key = f"{o.category} {xplane.base_name(o.name)} {o.shape}"[:110]
        sums[key][0] += 1
        sums[key][1] += o.end - o.start
    lines += [f"  {v[1] / 2 / REPEATS / 1e3:9.1f} us a pass  x{v[0] // 2 // REPEATS or 1:3d}  {k}"
              for k, v in sorted(sums.items(), key=lambda kv: -kv[1][1])]
    last = ops[-len(ops) // (2 * REPEATS):]
    t0 = last[0].start
    lines.append("timeline of the last pass (us from its first operation, duration, operation):")
    lines += [f"  {(o.start - t0) / 1e3:9.1f} {(o.end - o.start) / 1e3:8.1f}  "
              f"{o.category} {o.name} {o.shape}"[:150] for o in last]
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/seam_profile_{tag}.txt", "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines[:14]), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--dump", default=None)
    ap.add_argument("--seams", default=",".join(SEAMS))
    ap.add_argument("--arms", default=",".join(ARMS))
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--profile", default="", help="arms to trace as well, e.g. gspmd,ring")
    ns = ap.parse_args()
    if ns.aot:
        from jax.experimental import topologies

        devices = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    else:
        devices = jax.devices()
        if devices[0].platform != "tpu" or len(devices) != 4:
            raise SystemExit(f"needs four TPU chips, found {devices}")
    mesh, axes = build_mesh(pp=1, devices=list(devices))
    print("devices:", [(d.id, getattr(d, "coords", None)) for d in mesh.devices.flat])
    print("ring order:", cm.mesh_ring_order(mesh, axes.tp_axes(4, True)))
    rows = []
    for name in ns.seams.split(","):
        kind, subscripts, x_shape, w_shape, w_shard_dim = SEAMS[name]
        local = (int(np.prod(w_shape)) // (x_shape[-1] if kind == "ag" else H)) // 4
        row = {"seam": name, "kind": kind, "hop_cover": round(cm.hop_cover(4, local, 2), 3)}
        for arm in ns.arms.split(","):
            if arm in BATCH_ARMS and name not in HEAD_MAJOR:
                continue
            with arm_settings(arm):
                fn, avals = build(arm, name, mesh, axes)
                t0 = time.time()
                compiled = fn.lower(*avals).compile()
                compile_s = time.time() - t0
                if ns.aot:
                    text = compiled.as_text()
                    ma = compiled.memory_analysis()
                    print(name, arm, f"compile {compile_s:.1f} s, temp "
                          f"{ma.temp_size_in_bytes / 2**20:.0f} MiB", json.dumps(hlo_summary(text)))
                    if ns.dump:
                        os.makedirs(ns.dump, exist_ok=True)
                        with open(os.path.join(ns.dump, f"{name}.{arm}.hlo.txt"), "w") as f:
                            f.write(text)
                    continue
                key = jax.random.key(0)
                args = [jax.jit(lambda k, a=a: jax.random.normal(k, a.shape, a.dtype) * 0.05,
                                out_shardings=a.sharding)(jax.random.fold_in(key, i))
                        for i, a in enumerate(avals)]
                out = compiled(*args)
                jax.block_until_ready(out)
                if arm != "gspmd":  # the ring against the plain einsum, on the chip
                    ref = row["_ref"]
                    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                              / (float(jnp.max(jnp.abs(b.astype(jnp.float32)))) + 1e-9)
                              for a, b in zip(out, ref))
                    row[arm + "_rel_err"] = round(err, 5)
                else:
                    row["_ref"] = out
                times = []
                for _ in range(ns.iters):
                    t0 = time.perf_counter()
                    jax.block_until_ready(compiled(*args))
                    times.append((time.perf_counter() - t0) / REPEATS * 1e3)
                row[arm + "_ms"] = round(float(np.median(times)), 4)
                print(name, arm, f"compile {compile_s:.1f} s, fwd+bwd "
                      f"{row[arm + '_ms']:.4f} ms a seam (min {min(times):.4f})", flush=True)
                if arm in ns.profile.split(","):
                    profile(compiled, args, f"{name}_{arm}")
        row.pop("_ref", None)
        rows.append(row)
    if not ns.aot:
        print(json.dumps(rows))
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/tp_overlap_seams.json", "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
