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

``--ring`` (PR 71): the sliding layers' latent RING instead, at the shape of
`dots3-note-prev_serve_reason_above_knee` (3 layers x 32 rows x 2,048 places x 1,088, 64
heads, rank 1,024, a window of 513, bf16): the plain body (`mla._masked_context` over every
place of every row's ring, what `mla.attend_ring` runs outside the kernel's envelope)
against the kernel walking each row's ARC at each key block of ``--blocks`` (128,256,512
there), in the DEVICE's ms a layer (the profile's operations). Cases: rows at the lengths the cell's traffic holds (all past the window, most
lapped), every row short of the window (300), past it and short of a lap (1,500), lapped
(5,000 ..), and half the rows out of use. Then (``--step``) the cell's whole decode step,
`serving.engine._decode_step` at the cell's configuration on seeded weights, with the
ring on the plain body and at each key block: `mla_decode.ring_block`'s rule
(``RING_STEP_PLACES``) is fixed from these two tables (PERF.md section 6, PR 71).
``--tiny`` rehearses the ring's layer table at small widths on any backend.

    chiprun --chips 1 -- python experiments/ab_mla_decode.py --ring --step

One JSON line a measurement, the table at the end; no CPU fallback.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from experiments.ab_ssd import device_ops, rel, timed  # noqa: E402
from galvatron_tpu.models import generation, mla  # noqa: E402
from galvatron_tpu.models.modeling import PRESETS  # noqa: E402
from galvatron_tpu.ops import mla_decode, pallas_common  # noqa: E402

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


# -- the ring (--ring) ---------------------------------------------------------------------

RING_CELL = "dots3-note-prev_serve_reason_above_knee"
RING_LAYERS, RING_SLOT, RING_CHUNK = 3, 20480, 1024


def ring_lengths(seed: int = 0):
    """Lengths of 32 requests in flight, as `serve_reason_open_above_knee` draws them: a
    prompt of the mix plus a uniform share of its answer (3.8k-10.9k at their ends)."""
    rng = np.random.default_rng(seed)
    prompt = np.clip(np.exp(rng.normal(np.log(3072), 0.4, ROWS)), 768, 8192)
    answer = np.clip(np.exp(rng.normal(np.log(4096), 0.3, ROWS)), 1536, 10240)
    return np.minimum(prompt + rng.uniform(0, 1, ROWS) * answer, RING_SLOT - 1).astype(np.int32)


def ring_layer_table(args) -> list:
    """The layer alone: plain body against the kernel at each key block, by case."""
    cfg = PRESETS["dots3-note-prev"].replace(dtype=jnp.bfloat16)
    view = next(v for v in map(cfg.layer_view, range(cfg.num_layers)) if v.attn_window)
    if args.tiny:
        view = view.replace(num_heads=4, mla_kv_rank=128, mla_rope_dim=16)
    heads, _, rope, _, rank = mla.dims(view)
    span, width = view.attn_window, rank + rope
    places = generation.ring_positions(cfg, RING_SLOT, RING_CHUNK)
    rows = 4 if args.tiny else ROWS
    s, scale = args.window, mla.softmax_scale(view)
    ks = jax.random.split(jax.random.key(0), 2)
    ring = jax.random.normal(ks[0], (RING_LAYERS, rows, places, width), jnp.bfloat16)
    q_cat = (jax.random.normal(ks[1], (rows, s, heads, width), jnp.float32) * 0.5).astype(jnp.bfloat16)

    def plain(q_cat, ring, first):
        held = generation._ring_key_positions(first + s - 1, jnp.arange(places), places)
        visible = mla._in_window(first[:, None] + jnp.arange(s)[None], held, span)
        return mla._masked_context(q_cat, ring[1], visible, view)

    def kernel(block):
        return lambda q_cat, ring, first: mla_decode.latent_attention(
            q_cat, ring, 1, first, rank=rank, scale=scale, span=span, block_k=block)

    drawn = ring_lengths()[:rows]
    cases = [("drawn", drawn), ("short_of_the_window", np.full((rows,), 300, np.int32)),
             ("short_of_a_lap", np.full((rows,), 1500, np.int32)),
             ("lapped", (5000 + 37 * np.arange(rows)).astype(np.int32)),
             ("half_out_of_use", np.where(np.arange(rows) % 2, drawn, s).astype(np.int32))]
    out = []
    for name, lengths in cases:
        first = jnp.asarray(lengths - s)
        live = float(np.minimum(lengths, span + s - 1).sum())
        operands = (q_cat, ring, first)
        want = jax.jit(plain)(*operands)
        for body, block, fn in [("plain", 0, plain)] + [
                (f"kernel_{b}", b, kernel(b)) for b in args.block_list]:
            fn = jax.jit(fn)
            got = fn(*operands)
            read = rows * places if not block else block * sum(
                int(pallas_common.ring_arc(int(n) - s, s, span, places, block)[1]) for n in lengths)
            # the DEVICE's ms (a host loop over a call this short times its own dispatch,
            # ~0.2 ms here whatever the body); ``--tiny``, off the chip: the host's
            ops = [] if args.tiny else device_ops(fn, operands, calls=20, top=1000)
            ms = sum(t for _, t in ops) if ops else timed(fn, *operands, iters=3)
            row = {"case": name, "body": body, "ms_a_layer": ms,
                   "steps_a_row": pallas_common.ring_steps(s, span, places, block) if block else 0,
                   "read_gb_s": read * width * 2 / ms / 1e6, "read_over_live": read / live,
                   "rel_to_plain": rel(got.astype(jnp.float32), want.astype(jnp.float32)),
                   "finite": bool(jnp.isfinite(got.astype(jnp.float32)).all())}
            if name == "drawn":
                row["device_ops_ms"] = ops[:args.ops]
            print(json.dumps(row), flush=True)
            out.append(row)
    return out


def ring_step_table(args) -> list:
    """The cell's decode step on seeded weights, the ring on the plain body and at each key
    block (`mla_decode.RING_BLOCKS` held to one; the programs traced anew a body)."""
    from benchmark.lib import harness
    from galvatron_tpu.aot import registry
    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args
    from galvatron_tpu.serving import engine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    _, config, traffic = harness.load_cell(root, RING_CELL)
    ns = initialize_galvatron("serve", [*config["program_flags"], *traffic["serve_flags"]])
    cfg = model_config_from_args(ns)
    ctx = registry.ProgramContext(cfg=cfg, num_slots=ns.num_slots,
                                  prefill_chunk=ns.prefill_chunk, max_seq_len=cfg.max_seq_len)
    spec, = [sp for sp in registry.enumerate_programs(ctx, include=("serving",))
             if sp.name == "serving_decode"]
    params_abs, _, cache_abs, tokens_abs, _ = spec.args
    keys = iter(jax.random.split(jax.random.key(1), 4096))

    def seeded(x):  # (small weights, a unit latent: what the times need, not a model's rows)
        if jnp.issubdtype(x.dtype, jnp.floating):
            return jax.random.normal(next(keys), x.shape, x.dtype) * jnp.asarray(0.02, x.dtype)
        return jnp.zeros(x.shape, x.dtype)

    params = jax.tree.map(seeded, params_abs)
    tokens = jax.random.randint(jax.random.key(2), tokens_abs.shape, 0, cfg.vocab_size, jnp.int32)
    offsets = jnp.asarray(ring_lengths() - 1)
    out = []
    for body, blocks in [("plain", ())] + [(f"kernel_{b}", (b,)) for b in args.block_list]:
        mla_decode.RING_BLOCKS = blocks
        engine._decode_step.clear_cache()
        cache = jax.tree.map(seeded, cache_abs)
        logits, cache, _ = engine._decode_step(params, cfg, cache, tokens, offsets)
        jax.block_until_ready(logits)
        t0 = time.perf_counter()
        for _ in range(args.step_iters):
            logits, cache, _ = engine._decode_step(params, cfg, cache, tokens, offsets)
        jax.block_until_ready(logits)
        row = {"step_body": body, "ms_a_step": (time.perf_counter() - t0) / args.step_iters * 1e3,
               "finite": bool(jnp.isfinite(logits.astype(jnp.float32)).all())}
        print(json.dumps(row), flush=True)
        out.append(row)
        del cache
    return out


def ring_main(args) -> int:
    rows = ring_layer_table(args)
    steps = ring_step_table(args) if args.step else []
    print("| case | body | steps a row | ms a layer | read GB/s | read / live | rel to plain |")
    print("| --- | --- | --- | --- | --- | --- | --- |")
    for r in rows:
        print(f"| {r['case']} | {r['body']} | {r['steps_a_row']} | {r['ms_a_layer']:.4f} | "
              f"{r['read_gb_s']:.0f} | {r['read_over_live']:.2f} | {r['rel_to_plain']:.4f} |")
    for r in steps:
        print(f"| the decode step | {r['step_body']} | | {r['ms_a_step']:.3f} | | | |")
    worst = max(r["rel_to_plain"] for r in rows)
    ok = all(r["finite"] for r in rows + steps) and worst < 0.05
    print(json.dumps({"ok": ok, "worst_rel_to_plain": worst,
                      "device": str(np.asarray(jax.devices())[0])}))
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default=None, help="key blocks (512,1024,2048; --ring: 128,256,512)")
    ap.add_argument("--window", type=int, default=1, help="queries a row (a verify window: 4)")
    ap.add_argument("--ops", type=int, default=4, help="device operations listed a case")
    ap.add_argument("--ring", action="store_true", help="the dots3 cell's latent ring (PR 71)")
    ap.add_argument("--step", action="store_true", help="--ring: the cell's decode step too")
    ap.add_argument("--step_iters", type=int, default=100)
    ap.add_argument("--tiny", action="store_true", help="--ring at small widths, any backend")
    args = ap.parse_args(argv)
    if not (args.ring and args.tiny) and jax.devices()[0].platform != "tpu":
        raise SystemExit("ab_mla_decode: needs a TPU")
    args.block_list = [int(b) for b in (
        args.blocks or ("128,256,512" if args.ring else "512,1024,2048")).split(",")]
    if args.ring:
        return ring_main(args)
    blocks = args.block_list
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
