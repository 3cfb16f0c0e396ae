"""A prompt chunk's attention over head-major K/V stacks on the chip: the plain body
(`generation._attend_chunk`: XLA's loop over key blocks, float32 scores through HBM)
against the kernel `kv_chunk` (`ops/kv_prefill.attend_chunk`) at the shapes of the three
serving cells whose stacks are `generation.SlotStacks` (a chunk of 1,024 queries against
one of 32 slots, bf16):

- `smallthinker-21b-a3b_serve_long_above_knee`: 4 key/value heads of 128 under 7 grouped
  query heads; 4 full layers of 16,384 positions, 12 rings of 4,096 + 1,024;
- `lfm2-24b-a2b_serve_long_above_knee`: 8 heads of 64 under 4 (the stacks read
  transposed); 5 full layers of 16,384;
- `trinity-large-preview_serve_agent_above_knee`: 8 heads of 128 under 6; 1 full layer
  of 16,384, a ring of 4,096 + 1,024.

    chiprun --chips 1 -- python experiments/ab_kv_chunk.py [--ops 6]

Cases: a full layer's chunk that ends at 1,024, 4,096 and 12,288 keys (1, 4 and 12 live
key blocks); a ring's first chunk, its last before it laps (all 5 blocks, 4 of them this
lap's), and a chunk of a lapped ring (5 blocks, 3 of them seen whole). For each: ms a
layer of each body, the TFLOP/s that the key blocks the chunk attends make of that time
by PERF.md section 6's count (PR 66: every query head x 1,024 rows x the live blocks'
keys x head_dim x 4, no discount for the pairs a mask hides) and its share of the chip's
197, the largest relative difference to the plain body, and the largest device
operations of both bodies in one case a model.

One JSON line a measurement, the table at the end; no CPU fallback (``--tiny`` is the
CPU rehearsal at small shapes, interpreted: its times mean nothing).
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
from galvatron_tpu.models import generation  # noqa: E402
from galvatron_tpu.ops import kv_prefill  # noqa: E402

F32 = jnp.float32
PEAK_TFLOPS = 197.0
SLOT = 5
#: (model, key/value heads, grouped query heads, head_dim, full layers, window layers)
MODELS = [("smallthinker", 4, 7, 128, 4, 12), ("lfm2", 8, 4, 64, 5, 0),
          ("trinity", 8, 6, 128, 1, 4)]


def chunk_flops(heads: int, d: int, s: int, keys: int) -> float:
    """Both products of every query head's ``s`` rows against ``keys`` keys (the live
    key blocks', whole): what the MXU is handed, pairs under the mask included."""
    return 4.0 * heads * s * keys * d


def bodies(layer: int, s: int, span: int, scale: float):
    """(plain, kernel): the chunk at a traced ``offset`` of row ``SLOT``."""
    def plain(qg, ks, vs, offset):
        places = ks.shape[3]
        block, whole, live = generation.chunk_key_blocks(places, offset + s)
        return generation._attend_chunk(
            qg, ks, vs, layer, jnp.int32(SLOT), (offset + jnp.arange(s))[None],
            (lambda at: generation._ring_key_positions(offset + s - 1, at, places)) if span
            else (lambda at: at[None]), jnp.minimum(whole, live), block, span, scale)

    def kernel(qg, ks, vs, offset):
        return kv_prefill.attend_chunk(qg, ks, vs, layer, jnp.int32(SLOT), offset, scale=scale,
                                       span=span)

    return {"plain": jax.jit(plain), "kernel": jax.jit(kernel)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", type=int, default=6, help="device operations listed a body")
    ap.add_argument("--tiny", action="store_true", help="small shapes, any backend (a rehearsal)")
    args = ap.parse_args(argv)
    tiny = args.tiny
    if not tiny and jax.devices()[0].platform != "tpu":
        raise SystemExit("ab_kv_chunk: needs a TPU")
    shrink = 64 if tiny else 1
    if tiny:  # (the kernel's key block and the plain body's, at the rehearsal's sizes)
        kv_prefill.KEY_BLOCK = generation.KEY_BLOCK = 1024 // shrink
    rows, s, positions, window = (3 if tiny else 32), 1024 // shrink, 16384 // shrink, 4096 // shrink
    ring = window + s
    out_rows = []
    for model, kv, g, d, full_layers, window_layers in MODELS:
        scale = d ** -0.5
        keys = jax.random.split(jax.random.key(kv * g), 5)
        qg = jax.random.normal(keys[0], (1, s, kv, g, d), jnp.bfloat16)
        stacks = {0: [jax.random.normal(k, (min(full_layers, 2), rows, kv, positions, d), jnp.bfloat16)
                      for k in keys[1:3]]}
        cases = [("full", 0, f"ends_{n * s}", (n - 1) * s, n) for n in (1, 4, 12)]
        if window_layers:
            stacks[window] = [jax.random.normal(k, (2, rows, kv, ring, d), jnp.bfloat16)
                              for k in keys[3:5]]
            cases += [("ring", window, "first", 0, 1), ("ring", window, "before_the_lap", window, 5),
                      ("ring", window, "lapped", 2 * ring + s, 5)]
        for stack, span, name, offset, blocks in cases:
            ks, vs = stacks[span]
            fns = bodies(ks.shape[0] - 1, s, span, scale)
            operands = (qg, ks, vs, jnp.int32(offset))
            flops = chunk_flops(kv * g, d, s, blocks * (1024 // shrink))
            want = fns["plain"](*operands)
            for body, fn in fns.items():
                got = fn(*operands)
                ms = timed(fn, *operands, iters=3 if tiny else 20)
                row = {"model": model, "stack": stack, "case": name, "offset": offset,
                       "key_blocks": blocks, "body": body, "ms_a_layer": ms,
                       "tflops": flops / ms / 1e9,
                       "share_of_peak": flops / ms / 1e9 / PEAK_TFLOPS,
                       "rel_to_plain": rel(got.astype(F32), want.astype(F32)),
                       "finite": bool(jnp.isfinite(got.astype(F32)).all())}
                if not tiny and name in ("ends_4096", "lapped"):
                    row["device_ops_ms"] = device_ops(fn, operands, top=args.ops)
                print(json.dumps(row), flush=True)
                out_rows.append(row)
    print("| model | stack | case | key blocks | body | ms a layer | TFLOP/s | of 197 | rel to plain |")
    print("| --- | --- | --- | --- | --- | --- | --- | --- | --- |")
    for row in out_rows:
        print(f"| {row['model']} | {row['stack']} | {row['case']} | {row['key_blocks']} | {row['body']} | "
              f"{row['ms_a_layer']:.3f} | {row['tflops']:.1f} | {row['share_of_peak']:.1%} | "
              f"{row['rel_to_plain']:.4f} |")
    worst = max(row["rel_to_plain"] for row in out_rows)
    ok = all(row["finite"] for row in out_rows) and worst < 0.05
    print(json.dumps({"ok": ok, "worst_rel_to_plain": worst,
                      "device": str(np.asarray(jax.devices())[0])}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
