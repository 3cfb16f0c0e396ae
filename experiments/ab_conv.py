"""The Mamba-2 causal conv + SiLU alone on the chip: today's plain path
(`ops/ssd.causal_conv1d` + ``jax.nn.silu``, autodiff's backward), the same
forward with a backward of its own and no kernel (``plain_vjp``: the
pre-activation recomputed, one anti-causal conv of ``dpre``, one multi-output
reduction for ``dw`` and ``db``), and the kernels (`ops/ssd.conv_silu_fused`:
``ssm_conv_fwd`` / ``ssm_conv_bwd``), at the shape of
`granite-4.0-h-micro_s8192` (B 1, S 8192, 4352 channels, K 4, bf16) and at
batch 2 in float32.

    chiprun --chips 1 -- python experiments/ab_conv.py [--seams op,blocks,mixer]

- ``op``: forward and forward + backward (x, w, b) of each body stand-alone, ms
  a call and GB/s of the bytes that must move (forward: x read, y written;
  backward: the cotangent and x read, dx written) beside `lib/peaks.json`'s
  819 GB/s; each body's result and gradients held to the plain one's.
- ``blocks``: the kernels on the mixer's largest window (4096 channels at
  column 4096 of in_proj's (1, 8192, 8512) output) over strips, sequence and
  channel blocks, and with the sigmoid's division approximate.
- ``mixer``: `models/ssm.block`, the whole layer, forward, forward +
  backward and under ``jax.checkpoint`` (the cell's ``--global_checkpoint 1``),
  with the conv plain, as one fused window sliced in three behind it
  (``fused_sliced``: the plain boundary), and as three windows read in place
  (``fused``: what `ssm.conv_split` ships); the largest device operations of
  each remat program by name.

One JSON line a measurement, the tables at the end; no CPU fallback. Read with
the rows (PERF.md §6, PR 40): every forward + backward row holds the loss's own
pass over y and the cotangent (~0.26 ms at the bf16 shape), and every ``blocks``
row a copy of the (1, 8192, 8512) argument (0.42 ms: a jit argument of 66.5 lane
tiles is laid out again for the kernel; in the mixer in_proj's fusion writes
the layout the kernel reads, and the copy is not there), which the seam's last
row shows beside the kernel's own 0.23 ms.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from experiments.ab_ssd import device_ops, measure, rel, timed  # noqa: E402
from galvatron_tpu.models import ssm  # noqa: E402
from galvatron_tpu.models.modeling import PRESETS  # noqa: E402
from galvatron_tpu.ops import ssd  # noqa: E402

SEQ, PEAK_GBS = 8192, 819.0
F32 = jnp.float32


def plain(x, w, b):
    return jax.nn.silu(ssd.causal_conv1d(x, w, b))


@jax.custom_vjp
def plain_vjp(x, w, b):
    return plain(x, w, b)


def _plain_vjp_bwd(res, g):
    x, w, b = res
    k, s = w.shape[0], x.shape[1]
    w32 = w.astype(F32)
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    moved = [xp[:, j:j + s].astype(F32) for j in range(k)]  # x_{t-K+1+j}
    pre = b.astype(F32) + sum(moved[j] * w32[j] for j in range(k))
    sig = jax.nn.sigmoid(pre)
    dpre = g.astype(F32) * (sig * (1.0 + pre * (1.0 - sig)))
    dp = jnp.pad(dpre, ((0, 0), (0, k - 1), (0, 0)))
    dx = sum(dp[:, k - 1 - j:k - 1 - j + s] * w32[j] for j in range(k))
    dw = jnp.stack([jnp.sum(dpre * moved[j], axis=(0, 1)) for j in range(k)])
    return dx.astype(x.dtype), dw.astype(w.dtype), jnp.sum(dpre, axis=(0, 1)).astype(b.dtype)


plain_vjp.defvjp(lambda x, w, b: (plain(x, w, b), (x, w, b)), _plain_vjp_bwd)

BODIES = {"plain": plain, "plain_vjp": plain_vjp, "kernel": ssd.conv_silu_fused}


def time_op(name, fn, args, cot, passes=(2, 5), argnums=(0, 1, 2)):
    """fn(*args) -> y; ms and GB/s of y and of y + its gradients."""
    window = cot.size * args[0].dtype.itemsize  # one pass over the op's own channels
    fwd = jax.jit(lambda *t: fn(*t))

    def loss(*t):  # y comes back too, or a linear loss lets the compiler drop the forward;
        y = fn(*t[:-1])  # the cotangent an argument: as a constant it is in the executable
        return jnp.sum(y.astype(F32) * t[-1]), y

    grad = jax.jit(lambda *t: jax.grad(loss, argnums=argnums, has_aux=True)(*t)[::-1])
    row = {"case": name, "fwd_ms": timed(fwd, *args), "fwd_bwd_ms": timed(grad, *args, cot)}
    for key, n in zip(("fwd", "fwd_bwd"), passes):
        row[key + "_gbs"] = n * window / row[key + "_ms"] / 1e6
        row[key + "_of_peak"] = row[key + "_gbs"] / PEAK_GBS
    y, grads = grad(*args, cot)
    return row, [y] + list(grads)


def seam_op(rows):
    for bsz, dtype in ((1, jnp.bfloat16), (2, F32)):
        ks = jax.random.split(jax.random.key(bsz), 4)
        args = (jax.random.normal(ks[0], (bsz, SEQ, 4352), dtype),
                jax.random.uniform(ks[1], (4, 4352), F32, -0.5, 0.5),
                0.1 * jax.random.normal(ks[2], (4352,), F32))
        cot = jax.random.normal(ks[3], (bsz, SEQ, 4352))
        want = None
        for body, fn in BODIES.items():
            row, got = time_op(f"op/{body}/b{bsz}_{jnp.dtype(dtype).name}", fn, args, cot)
            if want is None:
                want = got
            else:
                row["y_rel_diff"] = rel(got[0], want[0])
                row["grad_rel_diff_max"] = max(rel(a, b) for a, b in zip(got[1:], want[1:]))
            rows.append(row)
            print(json.dumps(row), flush=True)


def seam_blocks(rows):
    ks = jax.random.split(jax.random.key(3), 4)
    args = (jax.random.normal(ks[0], (1, SEQ, 8512), jnp.bfloat16),
            jax.random.uniform(ks[1], (4, 4096), F32, -0.5, 0.5),
            0.1 * jax.random.normal(ks[2], (4096,), F32))
    cot = jax.random.normal(ks[3], (1, SEQ, 4096))
    approx = lambda v: pl.reciprocal(1.0 + jnp.exp(-v), approx=True)  # noqa: E731
    cases = [(32, 1024, 512, False), (16, 1024, 512, False), (64, 1024, 512, False),
             (32, 512, 512, False), (32, 2048, 512, False), (32, 1024, 256, False),
             (64, 2048, 256, False), (16, 2048, 256, False), (32, 1024, 512, True)]
    for strip, block_s, block_c, fast in cases:
        with mock.patch.multiple(ssd, _CONV_STRIP=strip, _CONV_BLOCK_S=block_s,
                                 _CONV_BLOCK_C=block_c), \
                mock.patch.object(jax.nn, "sigmoid", approx if fast else jax.nn.sigmoid):
            # gradients of w and b only: the kernel writes dx all the same, and the
            # pad that sets it into the wide array (in the model XLA fuses it into
            # in_proj's backward) stays out of the time
            fn = lambda x, w, b: ssd.conv_silu_fused(x, w, b, 4096)  # noqa: E731
            try:
                row, _ = time_op(f"blocks/strip{strip}_s{block_s}_c{block_c}"
                                 + ("_approx" if fast else ""), fn, args, cot, argnums=(1, 2))
            except Exception as e:  # a block Mosaic refuses is a row of the table too
                row = {"case": f"blocks/strip{strip}_s{block_s}_c{block_c}", "error": str(e)[:200]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    # what one forward call is on the device: the kernel, and whatever a jit argument
    # of 8512 columns costs on its way to it (in the mixer in_proj's fusion writes it)
    fwd = jax.jit(lambda x, w, b: ssd.conv_silu_fused(x, w, b, 4096))
    row = {"case": "blocks/fwd_device_ops_ms", "ops": device_ops(fwd, args, top=4)}
    rows.append(row)
    print(json.dumps(row), flush=True)


def fused_sliced(zxbcdt, w, b, cfg, place=None):
    """The plain boundary: one fused window of all conv channels, sliced behind."""
    windows = ssm.conv_windows(cfg)
    xbc = ssd.conv_silu_fused(zxbcdt, w, b, windows[0])
    starts = (0, windows[0], windows[0] + windows[1])
    return tuple(xbc[..., a:a + n] for a, n in zip(starts, windows))


def seam_mixer(rows):
    cfg = PRESETS["granite-4.0-h-micro"].replace(max_seq_len=SEQ, dtype=jnp.bfloat16)
    ks = jax.random.split(jax.random.key(0), 3)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim == 2 else a,
                          ssm.init_params(ks[0], cfg))
    hidden = jax.random.normal(ks[1], (1, SEQ, cfg.hidden_size), jnp.bfloat16)
    cot = jax.random.normal(ks[2], (1, SEQ, cfg.hidden_size))
    assert ssd.conv_path(ssm.conv_windows(cfg), cfg.ssm_conv, cfg.dtype) == "fused"
    patches = {"plain": mock.patch.object(ssm, "conv_path", lambda *a: "plain"),
               "fused_sliced": mock.patch.object(ssm, "conv_split", fused_sliced),
               "fused": mock.patch.object(ssm, "conv_path", ssd.conv_path)}
    want = None
    for body, patch in patches.items():
        def run(x_, p_, patch=patch):  # `ssm.block` with its conv bound while it is traced
            with patch:
                return ssm.block(x_, p_, cfg)

        row, y, grads = measure(f"mixer/{body}", run, (hidden, params), cot, top=14)
        got = [y] + jax.tree.leaves(grads)
        if want is None:
            want = got
        else:
            row["y_rel_diff"] = rel(got[0], want[0])
            row["grad_rel_diff_max"] = max(rel(a, b) for a, b in zip(got[1:], want[1:]))
        rows.append(row)
        print(json.dumps(row), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seams", default="op,blocks,mixer")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("ab_conv: needs a TPU")
    rows = []
    for seam in args.seams.split(","):
        {"op": seam_op, "blocks": seam_blocks, "mixer": seam_mixer}[seam](rows)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ab_conv.json", "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows}, f, indent=1)
    keys = ("fwd_ms", "fwd_bwd_ms", "remat_fwd_bwd_ms", "fwd_gbs", "fwd_bwd_gbs", "fwd_of_peak",
            "fwd_bwd_of_peak", "y_rel_diff", "grad_rel_diff_max")
    print("| case | " + " | ".join(keys) + " |")
    for row in rows:
        print(f"| {row['case']} | " + " | ".join(
            f"{row[k]:.4g}" if k in row else "" for k in keys) + " |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
