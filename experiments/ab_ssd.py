"""The Mamba-2 scan alone on the chip: the plain ``jax.numpy`` body against the
fused kernels (`ops/ssd.py`: ``ssd_fwd`` / ``ssd_bwd``), at the shape of
`granite-4.0-h-micro_s8192` (B 1, S 8192, H 64, P 64, G 1, N 128, chunks of
256, bf16).

    chiprun --chips 1 -- python experiments/ab_ssd.py [--seams scan,mixer]

Two seams, each forward, forward + backward, and forward + backward under
``jax.checkpoint`` (the cell's ``--global_checkpoint 1``: forward, replayed
forward, backward), ms a layer and the compiler's peak of temporaries:

- ``scan``: `ssd_scan` on its five operands;
- ``mixer``: `models/ssm.block`, the whole layer, so that what the
  compiler inserts around the scan (the re-tiling copies of x) is in the time.

The fused result is held to the plain one on the same inputs (largest
difference over the largest magnitude, y and every gradient). After the
timings, five traced calls of each case's remat program give the device time
of its largest operations by name (the kernels are ``ssd_fwd``, ``ssd_bwd``,
``ssd_decay``, ``ssd_decay_bwd``). One JSON line a measurement, the table at
the end; no CPU fallback.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.lib import xplane  # noqa: E402
from galvatron_tpu.models import ssm  # noqa: E402
from galvatron_tpu.models.modeling import PRESETS  # noqa: E402
from galvatron_tpu.ops import ssd  # noqa: E402

SEQ = 8192


def timed(fn, *args, iters=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def device_ops(fn, args, calls=5, top=6):
    """[(category:name shape, ms a call)] of the largest device operations."""
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        ops = xplane.first_device(xplane.load(xplane.find_trace(d))) or []
    sums = {}
    for o in xplane.leaf_ops(ops):
        key = f"{o.category}:{xplane.base_name(o.name)} {o.shape}"
        sums[key] = sums.get(key, 0.0) + (o.end - o.start) / 1e6 / calls
    return sorted(sums.items(), key=lambda kv: -kv[1])[:top]


def measure(name, fn, args, cot, top=6):
    """fn(*args) -> y; times y, its gradients, and the gradients under remat."""
    loss = lambda *t: jnp.sum(fn(*t).astype(jnp.float32) * cot)  # noqa: E731
    argnums = tuple(range(len(args)))
    fwd = jax.jit(lambda *t: fn(*t))  # a new function a variant: jit caches by identity
    grad = jax.jit(jax.grad(loss, argnums=argnums))
    remat = jax.jit(jax.grad(jax.checkpoint(loss), argnums=argnums))
    out = {"case": name, "fwd_ms": timed(fwd, *args), "fwd_bwd_ms": timed(grad, *args),
           "remat_fwd_bwd_ms": timed(remat, *args)}
    for key, f in (("fwd", fwd), ("fwd_bwd", grad), ("remat_fwd_bwd", remat)):
        mem = f.lower(*args).compile().memory_analysis()
        out[key + "_temp_mb"] = mem.temp_size_in_bytes / 2**20
    out["remat_device_ops_ms"] = device_ops(remat, args, top=top)
    return out, fwd(*args), grad(*args)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seams", default="scan,mixer")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("ab_ssd: needs a TPU")
    cfg = PRESETS["granite-4.0-h-micro"].replace(max_seq_len=SEQ, dtype=jnp.bfloat16)
    h, p, g, n, chunk = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_chunk
    ks = jax.random.split(jax.random.key(0), 8)
    scan_args = (
        jax.random.normal(ks[0], (1, SEQ, h, p), jnp.bfloat16),
        jax.nn.softplus(jax.random.normal(ks[1], (1, SEQ, h)) - 2.0),
        -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5),
        jax.random.normal(ks[3], (1, SEQ, g, n), jnp.bfloat16),
        jax.random.normal(ks[4], (1, SEQ, g, n), jnp.bfloat16))
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim == 2 else a,
                          ssm.init_params(ks[5], cfg))
    hidden = jax.random.normal(ks[6], (1, SEQ, cfg.hidden_size), jnp.bfloat16)
    scans = {"plain": ssd.ssd_scan_plain, "fused": ssd.ssd_scan_fused}

    def mixer(body):
        def run(x_, p_):  # `ssm.block` with its scan bound to one body while it is traced
            with mock.patch.object(ssm, "ssd_scan", scans[body]):
                return ssm.block(x_, p_, cfg)
        return run

    assert ssd.scan_path(h, p, g, n, chunk, jnp.bfloat16) == "fused"
    seams = {
        "scan": (lambda body: lambda *t: scans[body](*t, chunk), scan_args,
                 jax.random.normal(ks[7], (1, SEQ, h, p))),
        "mixer": (mixer, (hidden, params), jax.random.normal(ks[7], (1, SEQ, cfg.hidden_size))),
    }
    rows = []
    for seam in args.seams.split(","):
        make, fargs, cot = seams[seam]
        want = None
        for body in scans:
            row, y, grads = measure(f"{seam}/{body}", make(body), fargs, cot)
            got = [y] + jax.tree.leaves(grads)
            if want is None:
                want = got
            else:
                row["y_rel_diff"] = rel(got[0], want[0])
                row["grad_rel_diff_max"] = max(rel(a, b) for a, b in zip(got[1:], want[1:]))
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ab_ssd.json", "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows}, f, indent=1)
    keys = ("fwd_ms", "fwd_bwd_ms", "remat_fwd_bwd_ms", "remat_fwd_bwd_temp_mb", "y_rel_diff",
            "grad_rel_diff_max")
    print("| case | " + " | ".join(keys) + " |")
    for row in rows:
        print(f"| {row['case']} | " + " | ".join(
            f"{row[k]:.4g}" if k in row else "" for k in keys) + " |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
