"""What `models/gdn.block` runs under ``gdn/scan`` from the conv's output on, alone
and inside its mixer on the chip, at the sizes of `qwen3-next-80b-a3b_s4096` (batch
4, 4096 tokens, 16 key / 32 value heads of 128, chunks of 64, bf16): the plain body
(`models/gdn._l2norm` over slices of ``qkv``, then
`ops/gated_delta.gated_delta_chunked`: a triangular solve, a ``lax.scan`` over the
chunks, autodiff's backward) against the kernels (`gated_delta_fused`: ``gdn_fwd`` /
``gdn_bwd`` reading q, k and v out of ``qkv`` and normalising q and k themselves),
and with ``--parent DIR`` against the body another checkout of this repo has there
(PR 73's parent: XLA's slices, float32 norms and transpositions in front of kernels
that took q and k normalised and head-major).

    chiprun --chips 1 -- python experiments/ab_gdn.py [--seams scan,mixer] [--parent _parent]

- ``scan``: forward and forward + backward of each body, ms a call (``qkv``, ``g``,
  ``beta`` in, ``o`` and the three gradients out), the largest device operations of
  the fused bodies' backward programs by name, and the largest absolute difference
  and the largest difference over the largest magnitude of ``o`` and of the five
  gradients (dq, dk, dv: the three parts of ``qkv``'s; dg; dbeta) of each body
  against a float32 run of the plain body on the same inputs (where float32
  precision of the inverse on the MXU shows: interpret mode on the CPU cannot), and
  in float32 compute body against body.
- ``mixer``: `models/gdn.block`, the whole layer, forward, forward + backward and
  under ``jax.checkpoint`` (the cell's full-layer recomputation) with its rule plain
  (its own checkpoint included, as the model runs it) and fused; the largest device
  operations of each remat program by name. (The parent's mixer: this script of the
  parent's checkout, in the same call.)

One JSON line a measurement, the tables at the end; no CPU fallback.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from experiments.ab_ssd import device_ops, measure, rel, timed  # noqa: E402
from galvatron_tpu.models import gdn  # noqa: E402
from galvatron_tpu.models.modeling import PRESETS  # noqa: E402
from galvatron_tpu.ops import gated_delta as gd  # noqa: E402

BATCH, SEQ = 4, 4096
F32 = jnp.float32
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


def split(qkv, cfg):
    """`models/gdn.block`'s plain branch in front of its rule: q and k normalised
    in float32 and rounded, v, a head an axis."""
    hk, hv, dk, dv = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    lead, key_dim, dtype = qkv.shape[:2], hk * dk, qkv.dtype
    q = (gdn._l2norm(qkv[..., :key_dim].reshape(*lead, hk, dk)) * dk ** -0.5).astype(dtype)
    k = gdn._l2norm(qkv[..., key_dim:2 * key_dim].reshape(*lead, hk, dk)).astype(dtype)
    return q, k, qkv[..., 2 * key_dim:].reshape(*lead, hv, dv)


def bodies(cfg, parent):
    """name -> fn(qkv, g, beta) -> o (B, S, Hv, Dv)."""
    out = {
        "plain": lambda qkv, g, beta: gd.gated_delta_chunked(*split(qkv, cfg), g, beta, cfg.gdn_chunk),
        "fused": lambda qkv, g, beta: gd.gated_delta_fused(
            qkv, g, beta, cfg.gdn_key_heads, cfg.gdn_key_dim, cfg.gdn_chunk),
    }
    if parent:
        spec = importlib.util.spec_from_file_location(
            "parent_gated_delta", os.path.join(parent, "galvatron_tpu", "ops", "gated_delta.py"))
        theirs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(theirs)
        out["parent"] = lambda qkv, g, beta: theirs.gated_delta_fused(
            *split(qkv, cfg), g, beta, cfg.gdn_chunk)
    return out


def scan_inputs(cfg, dtype):
    hk, hv, dk, dv = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    ks = jax.random.split(jax.random.key(0), 6)
    q = jax.random.normal(ks[0], (BATCH, SEQ, hk * dk))
    # keys that share a direction: the chunk's system is far from the identity
    k = jax.random.normal(ks[1], (BATCH, SEQ, hk * dk)) + 0.3
    v = jax.nn.silu(jax.random.normal(ks[2], (BATCH, SEQ, hv * dv)))
    g = -0.1 * jax.nn.softplus(jax.random.normal(ks[3], (BATCH, SEQ, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (BATCH, SEQ, hv)))
    cot = jax.random.normal(ks[5], (BATCH, SEQ, hv, dv)).astype(dtype)
    return (jnp.concatenate([q, k, v], axis=-1).astype(dtype), g, beta), cot


def run_scan(fn, args, cot, cfg, time_it=True, top=0):
    fwd = jax.jit(fn)

    def loss(*t):  # o comes back too; the cotangent an argument, not a constant
        o = fn(*t[:-1])
        return jnp.sum(o.astype(F32) * t[-1].astype(F32)), o

    grad = jax.jit(lambda *t: jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(*t)[::-1])
    row = {"fwd_ms": timed(fwd, *args), "fwd_bwd_ms": timed(grad, *args, cot)} if time_it else {}
    if top:
        row["fwd_bwd_device_ops_ms"] = device_ops(grad, (*args, cot), top=top)
    o, (dqkv, dg, dbeta) = grad(*args, cot)
    key_dim = cfg.gdn_key_heads * cfg.gdn_key_dim
    parts = (o, dqkv[..., :key_dim], dqkv[..., key_dim:2 * key_dim], dqkv[..., 2 * key_dim:],
             dg, dbeta)
    return row, [np.asarray(t, np.float64) for t in parts]


def differences(got, want):
    return {name: {"abs": float(np.abs(a - b).max()), "rel": rel(a, b)}
            for name, a, b in zip(NAMES, got, want)}


def seam_scan(rows, cfg, parent):
    args, cot = scan_inputs(cfg, jnp.bfloat16)
    wide = (args[0].astype(F32), *args[1:])
    fns = bodies(cfg, parent)
    with jax.default_matmul_precision("highest"):  # every product of it, not the solve alone
        _, exact = run_scan(fns["plain"], wide, cot.astype(F32), cfg, time_it=False)
    results = {}
    for body, fn in fns.items():
        row, results[body] = run_scan(fn, args, cot, cfg, top=0 if body == "plain" else 12)
        row = {"case": f"scan/{body}/bf16", **row, "against_float32_plain":
               differences(results[body], exact)}
        if body != "plain":
            row["against_plain"] = differences(results[body], results["plain"])
        rows.append(row)
        print(json.dumps(row), flush=True)
    # float32 compute, body against body: the inverse and its application alone differ
    for body in [b for b in fns if b != "plain"]:
        row, got = run_scan(fns[body], wide, cot.astype(F32), cfg)
        row = {"case": f"scan/{body}/float32", **row,
               "against_float32_plain": differences(got, exact)}
        rows.append(row)
        print(json.dumps(row), flush=True)


def seam_mixer(rows, cfg):
    ks = jax.random.split(jax.random.key(1), 3)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim == 2 else a,
                          gdn.init_params(ks[0], cfg))
    hidden = jax.random.normal(ks[1], (BATCH, SEQ, cfg.hidden_size), jnp.bfloat16)
    cot = jax.random.normal(ks[2], (BATCH, SEQ, cfg.hidden_size))
    want = None
    for body in ("plain", "fused"):
        def run(x_, p_, body=body):  # `block` with its rule bound to one body while traced
            with mock.patch.object(gdn, "scan_path", lambda *a: body):
                return gdn.block(x_, p_, cfg)

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
    ap.add_argument("--seams", default="scan,mixer")
    ap.add_argument("--parent", default="", help="another checkout of this repo, its fused "
                    "body (of five pre-normalised operands) beside this one's under `scan`")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("ab_gdn: needs a TPU")
    cfg = PRESETS["qwen3-next-80b-a3b"].replace(max_seq_len=SEQ, dtype=jnp.bfloat16)
    assert gd.scan_path(cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
                        cfg.gdn_value_dim, cfg.gdn_chunk, cfg.dtype) == "fused"
    rows = []
    seams = {"scan": lambda: seam_scan(rows, cfg, args.parent),
             "mixer": lambda: seam_mixer(rows, cfg)}
    for seam in args.seams.split(","):
        seams[seam]()
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ab_gdn.json", "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows}, f, indent=1)
    keys = ("fwd_ms", "fwd_bwd_ms", "remat_fwd_bwd_ms", "y_rel_diff", "grad_rel_diff_max")
    print("| case | " + " | ".join(keys) + " |")
    for row in rows:
        print(f"| {row['case']} | " + " | ".join(
            f"{row[k]:.4g}" if k in row else "" for k in keys) + " |")
    print("| case against the float32 plain body | " + " | ".join(NAMES) + " |")
    for row in rows:
        if "against_float32_plain" in row:
            print(f"| {row['case']} | " + " | ".join(
                f"{row['against_float32_plain'][n]['rel']:.3g}" for n in NAMES) + " |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
