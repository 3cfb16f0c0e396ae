"""The gated delta rule alone and inside its mixer on the chip, the plain chunked
body (`ops/gated_delta.gated_delta_chunked`: a triangular solve, a ``lax.scan``
over the chunks, autodiff's backward) against the kernels
(`gated_delta_fused`: ``gdn_fwd`` / ``gdn_bwd``), at the sizes of
`qwen3-next-80b-a3b_s4096` (batch 4, 4096 tokens, 16 key / 32 value heads of 128,
chunks of 64, bf16).

    chiprun --chips 1 -- python experiments/ab_gdn.py [--seams rule,mixer]

- ``rule``: forward and forward + backward (all five gradients) of each body, ms a
  call, and the largest absolute and relative difference of ``o`` and of each
  gradient between the two bodies and of each against a float32 run of the plain
  body on the same inputs (where float32 precision of the inverse on the MXU shows:
  interpret mode on the CPU cannot), and in float32 compute body against body.
- ``mixer``: `models/gdn.block`, the whole layer, forward, forward + backward and
  under ``jax.checkpoint`` (the cell's full-layer recomputation) with the rule plain
  (its own checkpoint included, as the model runs it) and fused; the largest device
  operations of each remat program by name.

One JSON line a measurement, the tables at the end; no CPU fallback.
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
import numpy as np  # noqa: E402

from experiments.ab_ssd import measure, rel, timed  # noqa: E402
from galvatron_tpu.models import gdn  # noqa: E402
from galvatron_tpu.models.modeling import PRESETS  # noqa: E402
from galvatron_tpu.ops import gated_delta as gd  # noqa: E402

BATCH, SEQ = 4, 4096
F32 = jnp.float32
BODIES = {"plain": gd.gated_delta_chunked, "fused": gd.gated_delta_fused}
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


def rule_inputs(cfg, dtype):
    hk, hv, dk, dv = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    ks = jax.random.split(jax.random.key(0), 6)
    l2 = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = (l2(jax.random.normal(ks[0], (BATCH, SEQ, hk, dk))) * dk ** -0.5).astype(dtype)
    # keys that share a direction: the chunk's system is far from the identity
    k = l2(jax.random.normal(ks[1], (BATCH, SEQ, hk, dk)) + 0.3).astype(dtype)
    v = jax.nn.silu(jax.random.normal(ks[2], (BATCH, SEQ, hv, dv))).astype(dtype)
    g = -0.1 * jax.nn.softplus(jax.random.normal(ks[3], (BATCH, SEQ, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (BATCH, SEQ, hv)))
    cot = jax.random.normal(ks[5], (BATCH, SEQ, hv, dv)).astype(dtype)
    return (q, k, v, g, beta), cot


def run_rule(fn, args, cot, chunk, time_it=True):
    fwd = jax.jit(lambda *t: fn(*t, chunk))

    def loss(*t):  # o comes back too; the cotangent an argument, not a constant
        o = fn(*t[:-1], chunk)
        return jnp.sum(o.astype(F32) * t[-1].astype(F32)), o

    grad = jax.jit(lambda *t: jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*t)[::-1])
    row = {"fwd_ms": timed(fwd, *args), "fwd_bwd_ms": timed(grad, *args, cot)} if time_it else {}
    o, grads = grad(*args, cot)
    return row, [np.asarray(t, np.float64) for t in (o, *grads)]


def differences(got, want):
    return {name: {"abs": float(np.abs(a - b).max()), "rel": rel(a, b)}
            for name, a, b in zip(NAMES, got, want)}


def seam_rule(rows, cfg):
    args, cot = rule_inputs(cfg, jnp.bfloat16)
    wide = tuple(t.astype(F32) for t in args)
    with jax.default_matmul_precision("highest"):  # every product of it, not the solve alone
        _, exact = run_rule(gd.gated_delta_chunked, wide, cot.astype(F32), cfg.gdn_chunk,
                            time_it=False)
    results = {}
    for body, fn in BODIES.items():
        row, results[body] = run_rule(fn, args, cot, cfg.gdn_chunk)
        row = {"case": f"rule/{body}/bf16", **row, "against_float32_plain":
               differences(results[body], exact)}
        if body == "fused":
            row["against_plain"] = differences(results["fused"], results["plain"])
        rows.append(row)
        print(json.dumps(row), flush=True)
    # float32 compute, body against body: the inverse and its application alone differ
    row, got = run_rule(gd.gated_delta_fused, wide, cot.astype(F32), cfg.gdn_chunk)
    row = {"case": "rule/fused/float32", **row, "against_float32_plain": differences(got, exact)}
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
    ap.add_argument("--seams", default="rule,mixer")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("ab_gdn: needs a TPU")
    cfg = PRESETS["qwen3-next-80b-a3b"].replace(max_seq_len=SEQ, dtype=jnp.bfloat16)
    assert gd.scan_path(cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
                        cfg.gdn_value_dim, cfg.gdn_chunk, cfg.dtype) == "fused"
    rows = []
    for seam in args.seams.split(","):
        {"rule": seam_rule, "mixer": seam_mixer}[seam](rows, cfg)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ab_gdn.json", "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows}, f, indent=1)
    keys = ("fwd_ms", "fwd_bwd_ms", "remat_fwd_bwd_ms", "y_rel_diff", "grad_rel_diff_max")
    print("| case | " + " | ".join(keys) + " |")
    for row in rows:
        print(f"| {row['case']} | " + " | ".join(
            f"{row[k]:.4g}" if k in row else "" for k in keys) + " |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
