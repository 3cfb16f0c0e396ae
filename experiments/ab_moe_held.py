"""A held share of the experts on the chip: one layer's dispatch + experts + combine
at the shape of `qwen3-next-80b-a3b_s4096` (16,384 tokens x top-10 over 512 experts
of width 512, 32 of them held, hidden 2048, bf16, tiles of 256 rows: a buffer of
172,288 rows), the plain path over the whole buffer (`_dispatch`, three
`grouped_gemm`, `_combine`) against the bounded one (`moe.held_experts`), both
behind the same `held_layout`.

    chiprun --chips 1 -- python experiments/ab_moe_held.py [--shares 0.0625,0.25,0.98]

For each share of the pairs that falls on the held experts (1/16 is the cell's when
the load is even, 1/4 a rank of four, 0.98 a buffer that is nearly full: the end of
the range where the bounded path has nothing to skip): forward and forward +
backward (x, the combine weights and the three expert weights) of each body, ms a
call, the layout alone (both bodies pay it), `moe_held_rows_share`, the largest
relative difference of the output and of each gradient between the bodies, whether
everything is finite, and the largest device operations of the bounded backward.

One JSON line a measurement, the table at the end; no CPU fallback.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from experiments.ab_ssd import device_ops, rel, timed  # noqa: E402
from galvatron_tpu.models import moe  # noqa: E402

TOKENS, TOP_K, EXPERTS, HELD, HIDDEN, WIDTH, TILE = 16384, 10, 512, 32, 2048, 512, 256
NAMES = ("y", "dx", "dweights", "dw1", "dw3", "dw2")


def inputs(share: float, dtype=jnp.bfloat16):
    """Activations, combine weights and expert choices of which ``share`` name a held
    expert (uneven among the held ones, as a router's are), the held experts'
    weights, and a cotangent."""
    ks = jax.random.split(jax.random.key(int(share * 1e4)), 9)
    x = jax.random.normal(ks[0], (TOKENS, HIDDEN), dtype)
    weights = jax.nn.softmax(jax.random.normal(ks[1], (TOKENS, TOP_K)), axis=-1)
    skew = jax.nn.softmax(jax.random.normal(ks[2], (HELD,)))
    inside = jax.random.choice(ks[3], HELD, (TOKENS, TOP_K), p=skew)
    outside = jax.random.randint(ks[4], (TOKENS, TOP_K), HELD, EXPERTS)
    idx = jnp.where(jax.random.uniform(ks[5], (TOKENS, TOP_K)) < share, inside, outside)
    w1, w3 = (jax.random.normal(k, (HELD, HIDDEN, WIDTH), dtype) * HIDDEN ** -0.5
              for k in ks[6:8])
    w2 = jax.random.normal(ks[8], (HELD, WIDTH, HIDDEN), dtype) * WIDTH ** -0.5
    cot = jax.random.normal(jax.random.fold_in(ks[0], 1), (TOKENS, HIDDEN), jnp.float32)
    return (x, weights, w1, w3, w2), idx.astype(jnp.int32), cot


def layout_of(idx):
    return moe.held_layout(idx, HELD, TILE, 0)


def plain(x, weights, w1, w3, w2, idx):
    lay = layout_of(idx)
    rows = moe._dispatch(x, lay.row_pair // TOP_K, lay.row_valid, lay.pair_row)
    gate, up = moe.grouped_gemm(rows, w1, lay, TILE), moe.grouped_gemm(rows, w3, lay, TILE)
    out = moe.grouped_gemm(jax.nn.silu(gate) * up, w2, lay, TILE)
    return moe._combine(out, weights, lay.pair_row, lay.row_pair, lay.row_valid)


def bounded(x, weights, w1, w3, w2, idx):
    lay = layout_of(idx)
    return moe.held_experts(x, weights, jnp.concatenate([w1, w3], axis=-1), w2, lay.pair_row,
                            lay.row_pair, lay.row_valid, lay.tile_group, lay.num_tiles, TILE)


BODIES = {"plain": plain, "bounded": bounded}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shares", default="0.0625,0.25,0.98")
    ap.add_argument("--ops", type=int, default=8, help="device operations listed a case")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("ab_moe_held: needs a TPU")
    rows = []
    for share in (float(s) for s in args.shares.split(",")):
        operands, idx, cot = inputs(share)
        # (idx and the cotangent are operands: closed over they would be 140 MB of
        # constants in each executable)
        operands += (idx, cot)
        lay = jax.jit(layout_of)(idx)
        rows_share = float(lay.num_tiles[0]) * TILE / lay.row_valid.shape[0]
        layout_ms = timed(jax.jit(layout_of), idx)
        got = {}
        for name, body in BODIES.items():
            fwd = jax.jit(lambda *t, body=body: body(*t[:-1]))
            grad = jax.jit(jax.grad(
                lambda *t, body=body: jnp.sum(body(*t[:-1]).astype(jnp.float32) * t[-1]),
                argnums=tuple(range(5))))
            got[name] = (fwd(*operands),) + grad(*operands)
            row = {"share": share, "body": name, "moe_held_rows_share": rows_share,
                   "layout_ms": layout_ms, "fwd_ms": timed(fwd, *operands),
                   "fwd_bwd_ms": timed(grad, *operands),
                   "finite": all(bool(jnp.isfinite(t.astype(jnp.float32)).all())
                                 for t in got[name])}
            if name == "bounded":
                row["rel_to_plain"] = {n: rel(b, p) for n, b, p in
                                       zip(NAMES, got["bounded"], got["plain"])}
                row["fwd_bwd_device_ops_ms"] = device_ops(grad, operands, top=args.ops)
            print(json.dumps(row), flush=True)
            rows.append(row)
    print("| share of pairs held | rows share | body | layout ms | fwd ms | fwd + bwd ms |")
    print("| --- | --- | --- | --- | --- | --- |")
    for r in rows:
        print(f"| {r['share']} | {r['moe_held_rows_share']:.4f} | {r['body']} | "
              f"{r['layout_ms']:.2f} | {r['fwd_ms']:.2f} | {r['fwd_bwd_ms']:.2f} |")
    worst = max(max(r["rel_to_plain"].values()) for r in rows if "rel_to_plain" in r)
    ok = all(r["finite"] for r in rows) and worst < 0.05
    print(json.dumps({"ok": ok, "worst_rel_to_plain": worst,
                      "device": str(np.asarray(jax.devices())[0])}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
