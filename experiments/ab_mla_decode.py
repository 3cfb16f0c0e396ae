"""A decode step's latent attention on the chip: the middle of the absorbed form
(scores, softmax, probabilities x latent) at the shape of
`sarvam-105b_serve_long_above_knee` (32 slots x 16,384 positions x 576 of a 5-layer
stacked cache, 64 heads, rank 512, bf16), the plain body (`mla._plain_context`
over a layer's whole slab) against the kernel (`ops/mla_decode.latent_attention`,
bounded a row by the row's length).

    chiprun --chips 1 -- python experiments/ab_mla_decode.py [--blocks 512,1024,2048]

Cases: rows whose lengths are drawn as the cell's traffic draws them (a prompt of
the mix plus a uniform share of its answer: mean fill ~0.31 of the capacity, what a
recorded window holds), and every row at a fill of 0.05 / 0.31 / 1.0. For each: ms
a layer of each body (the kernel at each key block of ``--blocks``), the GB/s the
live positions' latent makes of that time (819 is the chip's), the share of the
fetched positions that were live, the largest relative difference to the plain
body, and the kernel's device operations. ``--window 4`` times a verify window.

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
from galvatron_tpu.models import mla  # noqa: E402
from galvatron_tpu.models.modeling import PRESETS  # noqa: E402
from galvatron_tpu.ops import mla_decode  # noqa: E402

LAYERS, ROWS, POSITIONS, LAYER = 5, 32, 16384, 2
CFG = PRESETS["sarvam-105b"].replace(dtype=jnp.bfloat16)
HEADS, _, ROPE, _, RANK = mla.dims(CFG)
WIDTH = RANK + ROPE


def drawn_lengths(seed: int = 0):
    """Lengths of 32 requests in flight, as `serve_long_open_above_knee` draws them."""
    rng = np.random.default_rng(seed)
    prompt = np.clip(np.exp(rng.normal(np.log(4096), 0.6, ROWS)), 1024, 12288)
    answer = np.clip(np.exp(rng.normal(np.log(256), 0.6, ROWS)), 64, 768)
    return np.minimum(prompt + rng.uniform(0, 1, ROWS) * answer, POSITIONS).astype(np.int32)


def plain(q_cat, stacked, first):
    q_pos = first[:, None] + jnp.arange(q_cat.shape[1])[None]
    return mla._plain_context(q_cat, stacked[LAYER], q_pos, CFG)


def kernel(block_k):
    def body(q_cat, stacked, first):
        return mla_decode.latent_attention(q_cat, stacked, LAYER, first, rank=RANK,
                                           scale=mla.softmax_scale(CFG), block_k=block_k)
    return body


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="512,1024,2048")
    ap.add_argument("--window", type=int, default=1, help="queries a row (a verify window: 4)")
    ap.add_argument("--ops", type=int, default=4, help="device operations listed a case")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("ab_mla_decode: needs a TPU")
    blocks = [int(b) for b in args.blocks.split(",")]
    ks = jax.random.split(jax.random.key(0), 2)
    # (unit-variance latent and queries scaled so that the scores are a few units wide)
    stacked = jax.random.normal(ks[0], (LAYERS, ROWS, POSITIONS, WIDTH), jnp.bfloat16)
    q_cat = (jax.random.normal(ks[1], (ROWS, args.window, HEADS, WIDTH), jnp.float32)
             * 0.5).astype(jnp.bfloat16)
    cases = [("drawn", drawn_lengths())] + [
        (f"fill_{fill}", np.full((ROWS,), max(int(fill * POSITIONS), args.window), np.int32))
        for fill in (0.05, 0.31, 1.0)]
    rows = []
    for name, lengths in cases:
        first = jnp.asarray(lengths - args.window)
        live_bytes = float(lengths.sum()) * WIDTH * 2
        operands = (q_cat, stacked, first)
        want = jax.jit(plain)(*operands)
        bodies = [("plain", 0, plain)] + [(f"kernel_{b}", b, kernel(b)) for b in blocks]
        for body, block, fn in bodies:
            fn = jax.jit(fn)
            got = fn(*operands)
            ms = timed(fn, *operands)
            read = (ROWS * POSITIONS if not block else
                    int(sum(-(-int(n) // block) * block for n in lengths)))
            row = {"case": name, "fill": float(lengths.mean()) / POSITIONS, "body": body,
                   "ms_a_layer": ms, "live_gb_s": live_bytes / ms / 1e6,
                   "live_over_read": float(lengths.sum()) / read,
                   "rel_to_plain": rel(got.astype(jnp.float32), want.astype(jnp.float32)),
                   "finite": bool(jnp.isfinite(got.astype(jnp.float32)).all())}
            if name == "drawn":
                row["device_ops_ms"] = device_ops(fn, operands, top=args.ops)
            print(json.dumps(row), flush=True)
            rows.append(row)
    print("| case | fill | body | ms a layer | live GB/s | live / read | rel to plain |")
    print("| --- | --- | --- | --- | --- | --- | --- |")
    for r in rows:
        print(f"| {r['case']} | {r['fill']:.3f} | {r['body']} | {r['ms_a_layer']:.3f} | "
              f"{r['live_gb_s']:.0f} | {r['live_over_read']:.3f} | {r['rel_to_plain']:.4f} |")
    worst = max(r["rel_to_plain"] for r in rows)
    ok = all(r["finite"] for r in rows) and worst < 0.05
    print(json.dumps({"ok": ok, "worst_rel_to_plain": worst,
                      "device": str(np.asarray(jax.devices())[0])}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
