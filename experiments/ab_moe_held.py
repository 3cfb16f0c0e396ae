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
call, the layout alone (both bodies pay it), counted as `moe.held_layout` runs it
(PR 67) against the sort-based reference called directly, `moe._layout_by_sort`, at the
cell's 33 groups and at 129, 256 and 512 (no cell's): host ms a call, the device's ms a
call and its largest operations, the compiler's temporaries, and whether the six arrays
are the same bits,
`moe_held_rows_share`, the largest
relative difference of the output and of each gradient between the bodies, whether
everything is finite, and the largest device operations of the bounded backward.

One JSON line a measurement, the table at the end; no CPU fallback.

    chiprun --chips 1 -- python experiments/ab_moe_held.py --serve [--tiles 16,32,64,128,256]

``--serve``: the table `ops/grouped_matmul.row_tile`'s constant is read from. The
bounded forward alone (layout + `moe.held_experts`, the ``(w1, w3)`` pair held in
bf16 as `cli serve --param_dtype bf16` holds it) at the serving shapes, a decode
step's 32 tokens and a prompt chunk's 1,024 of `sarvam-105b_serve_long_above_knee`
(top-8 over 128 scored, 32 held, 4096 x 2048), of
`smallthinker-21b-a3b_serve_long_above_knee` (top-6 over 64, 16 held, 2560 x 768,
ReGLU) and of `trinity-large-preview_serve_agent_above_knee` (top-4 over 256, 32
held, 3072 x 3072), at each row tile: ms a call, the GB/s that makes of the held
weights' bytes (every held expert's three matrices once), the buffer's rows in use,
the largest difference from the tile-256 output, the largest device operations; and
beside each, the forward-only layout (PR 62: `held_layout(empty_tiles=False)` under
`moe.held_forward`, what a cached forward runs): its ms, the held experts that got
a row (whose weights alone it fetches), the rows its tiles cover, and whether its
output is the default layout's to the bit; and, once a shape at the tile the layers
choose themselves (`ops/grouped_matmul.row_tile`), the layout alone, counted and sorted,
as above. ``--skew`` sets the routers' loads (0
even, 1 a few favourites); ``--tiny`` rehearses the mode at small widths on any
backend.

``--serve --plain`` (PR 69): the expert layer of
`nemotron-3-nano-30b-a3b_serve_chat_above_knee` alone, on the PLAIN held path
(un-gated ``down(relu(up x)^2)``, top-6 over 128 scored, 32 held, 2688 x 1856, ``w1``
out-major; a decode step's 64 tokens and a prompt chunk's 1,024; skew 0.5 touches 23-27
of 32 at 64 rows, as the cell's seeded routers do), by the two axes of the grid its
grouped GEMMs walk: the layout with and without empty tiles (the parent's | (a)) and
the down projection's column block, 128 (the parent's, 21 blocks) | 384 | 896 | 2688 (one
block: (b)): ms a call of the layer, the device's ms of `moe_gmm` (down) and
`moe_gmm_dlhs` (up) in it and of all its operations, those times 11 layers, the grid
steps, the experts that own a tile, and whether the output is the parent's to the bit.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from experiments.ab_ssd import device_ops, rel, timed  # noqa: E402
from galvatron_tpu.models import moe  # noqa: E402
from galvatron_tpu.ops.grouped_matmul import row_tile  # noqa: E402

TOKENS, TOP_K, EXPERTS, HELD, HIDDEN, WIDTH, TILE = 16384, 10, 512, 32, 2048, 512, 256
WIDE_HELD = (128, 255, 511)  # groups - 1 of the wide layouts timed beside the cell's
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


def layout_alone(idx, held, tile, empty_tiles=True, tiny=False, top=6):
    """The layout of ``idx`` alone, counted (`moe.held_layout` as the library runs it)
    and sorted (the same over `moe._layout_by_sort`, the reference the library keeps for
    this and for its tests, called directly). A row a body: host ms a call, the device's
    ms a call and its largest operations, the compiler's temporaries."""
    def by_sort(i):  # (trace-time: `held_layout` looks `sorted_layout` up by name)
        with mock.patch.object(moe, "sorted_layout", moe._layout_by_sort):
            return moe.held_layout(i, held, tile, 0, empty_tiles)

    bodies = {"counted": jax.jit(lambda i: moe.held_layout(i, held, tile, 0, empty_tiles)),
              "sorted": jax.jit(by_sort)}
    want = bodies["sorted"](idx)
    rows = []
    for name, fn in bodies.items():
        ops = [] if tiny else device_ops(fn, (idx,), top=1000)
        rows.append({"layout_body": name, "layout_ms": timed(fn, idx, iters=3 if tiny else 30),
                     "layout_device_ms": sum(ms for _, ms in ops), "layout_device_ops": len(ops),
                     "temp_mb": fn.lower(idx).compile().memory_analysis().temp_size_in_bytes / 1e6,
                     "same_bits": all(bool(jnp.array_equal(g, w)) for g, w in zip(fn(idx), want)),
                     "layout_device_ops_ms": ops[:top]})
    return rows


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

#: the expert layers of three held-share serving cells (scored, held, top-k, hidden, width, gate)
SERVE_SHAPES = {
    "sarvam-105b": (128, 32, 8, 4096, 2048, "silu"),
    "smallthinker-21b-a3b": (64, 16, 6, 2560, 768, "relu"),
    "trinity-large-preview": (256, 32, 4, 3072, 3072, "silu"),
}
TINY_SHAPES = {"tiny": (16, 4, 2, 256, 128, "silu")}


def serve_inputs(tokens, experts, held, top_k, hidden, width, skew=1.0, seed=0,
                 dtype=jnp.bfloat16):
    """A forward's activations, a router's choices over ALL the scored experts
    (distinct a token; ``skew`` times a standard normal on every expert's logit: 0 an
    even load, 1 a trained router's few favourites) with their renormalised weights,
    and the held experts' three matrices."""
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (tokens, hidden), dtype)
    scores = (jax.random.normal(ks[1], (tokens, experts))
              + skew * jax.random.normal(ks[2], (experts,)))
    weights, idx = jax.lax.top_k(jax.nn.softmax(scores, axis=-1), top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    w1, w3 = (jax.random.normal(k, (held, hidden, width), dtype) * hidden ** -0.5
              for k in ks[3:5])
    w2 = jax.random.normal(ks[5], (held, width, hidden), dtype) * width ** -0.5
    return x, weights, w1, w3, w2, idx.astype(jnp.int32)


def serve_forward(x, weights, w1, w3, w2, idx, *, held, tile, act, empty_tiles=True):
    """``empty_tiles`` False: what `moe._topk_local` runs for a cached forward."""
    lay = moe.held_layout(idx, held, tile, 0, empty_tiles=empty_tiles)
    run = moe.held_experts if empty_tiles else moe.held_forward
    return run(x, weights, (w1, w3), w2, lay.pair_row, lay.row_pair, lay.row_valid,
               lay.tile_group, lay.num_tiles, tile, act)


#: the un-gated expert layer on the plain held path (scored, held, top-k, hidden, width)
PLAIN_SHAPES = {"nemotron-3-nano-30b-a3b": (128, 32, 6, 2688, 1856)}
TINY_PLAIN_SHAPES = {"tiny": (16, 4, 2, 384, 160)}
PLAIN_LAYERS = 11  # the cell's expert layers


def plain_forward(x, weights, w1_t, w2, idx, *, held, tile, empty_tiles, block):
    """The un-gated plain held path as `moe._topk_local` runs it, with the column block of
    a width it divides as a parameter (trace-time: `_gmm` looks `_tile` up by name): 128
    is what `_tile` gave 2688 before PR 69."""
    from galvatron_tpu.models.modeling import relu2
    from galvatron_tpu.ops import grouped_matmul

    lay = moe.held_layout(idx, held, tile, 0, empty_tiles=empty_tiles)
    rows = moe._dispatch(x, lay.row_pair // weights.shape[1], lay.row_valid, lay.pair_row)
    with mock.patch.object(grouped_matmul, "_tile", lambda n, want: n if n % block else block):
        up = moe.grouped_gemm(rows, w1_t, lay, tile, out_major=True)
        out = moe.grouped_gemm(relu2(up), w2, lay, tile)
    return moe._combine(out, weights, lay.pair_row, lay.row_pair, lay.row_valid)


def serve_plain(args) -> int:
    tiny = args.tiny
    rows = []
    for model, (experts, held, top_k, hidden, width) in (
            TINY_PLAIN_SHAPES if tiny else PLAIN_SHAPES).items():
        blocks = (128, 384) if tiny else (128, 384, 896, hidden)
        for tokens in ((8, 64) if tiny else (64, 1024)):
            for seed in (int(s_) for s_ in args.seeds.split(",")):
                x, weights, w1, _, w2, idx = serve_inputs(
                    tokens, experts, held, top_k, hidden, width, float(args.skew.split(",")[0]),
                    seed)
                operands = (x, weights, jnp.swapaxes(w1, 1, 2), w2, idx)
                tile = row_tile(tokens, top_k, experts, x.dtype)
                want = None
                for block in blocks:
                    for empty_tiles in (True, False):
                        fn = jax.jit(functools.partial(plain_forward, held=held, tile=tile,
                                                       empty_tiles=empty_tiles, block=block))
                        y = jax.block_until_ready(fn(*operands))
                        want = y if want is None else want
                        lay = jax.jit(functools.partial(
                            moe.held_layout, held=held, tile=tile, first_held=0,
                            empty_tiles=empty_tiles))(idx)
                        ops = [] if tiny else device_ops(fn, operands, top=1000)
                        kernel = lambda name: sum(  # noqa: E731  (keys: "category:name shape")
                            ms for key, ms in ops if f":{name} " in key)
                        row = {"model": model, "tokens": tokens, "seed": seed, "tile": tile,
                               "empty_tiles": empty_tiles, "column_block": block,
                               "grid_steps_down": hidden // block * (lay.row_valid.shape[0] // tile),
                               "tiles_in_use": int(lay.num_tiles[0]),
                               "tiles": int(lay.row_valid.shape[0]) // tile,
                               "experts_with_a_tile": len(set(
                                   np.asarray(lay.tile_group)[:int(lay.num_tiles[0])].tolist())),
                               "experts_touched": int(jnp.sum(lay.sizes > 0)),
                               "held_pairs": int(jnp.sum(lay.sizes)),
                               "layer_ms": timed(fn, *operands, iters=3 if tiny else 50),
                               "down_ms": kernel("moe_gmm"), "up_ms": kernel("moe_gmm_dlhs"),
                               "device_ms": sum(ms for _, ms in ops),
                               "same_bits": bool(jnp.array_equal(y, want)),
                               "rel_to_parent": rel(y.astype(jnp.float32),
                                                    want.astype(jnp.float32)),
                               "finite": bool(jnp.isfinite(y.astype(jnp.float32)).all())}
                        print(json.dumps(row), flush=True)
                        rows.append(row)
    print("| model | tokens | seed | empty tiles | column block | grid steps (down) | tiles in use "
          "| experts with a tile / touched | layer ms | device ms | up ms | down ms | "
          f"experts' kernels x {PLAIN_LAYERS} | device x {PLAIN_LAYERS} | same bits |")
    print("| --- | --- | --- | --- | --- | --- | --- | --- | --- | --- | --- | --- | --- | --- | --- |")
    for r in rows:
        print(f"| {r['model']} | {r['tokens']} | {r['seed']} | {r['empty_tiles']} | "
              f"{r['column_block']} | {r['grid_steps_down']} | {r['tiles_in_use']} / {r['tiles']} | "
              f"{r['experts_with_a_tile']} / {r['experts_touched']} | {r['layer_ms']:.3f} | "
              f"{r['device_ms']:.3f} | {r['up_ms']:.3f} | {r['down_ms']:.3f} | "
              f"{PLAIN_LAYERS * (r['up_ms'] + r['down_ms']):.2f} | "
              f"{PLAIN_LAYERS * r['device_ms']:.2f} | {r['same_bits']} |")
    worst = max(r["rel_to_parent"] for r in rows)
    ok = all(r["finite"] for r in rows) and worst < 0.02 and all(
        r["same_bits"] for r in rows if r["column_block"] == 128)
    print(json.dumps({"ok": ok, "worst_rel_to_parent": worst,
                      "device": str(np.asarray(jax.devices())[0])}))
    return 0 if ok else 1


def print_layouts(layouts):
    print("| model | tokens | pairs x groups | tile | empty tiles | body | host ms | device ms | "
          "device operations | temporaries MB | same bits |")
    print("| --- | --- | --- | --- | --- | --- | --- | --- | --- | --- | --- |")
    for r in layouts:
        print(f"| {r['model']} | {r['tokens']} | {r['pairs']} x {r['groups']} | {r['tile']} | "
              f"{r['empty_tiles']} | {r['layout_body']} | {r['layout_ms']:.3f} | "
              f"{r['layout_device_ms']:.4f} | {r['layout_device_ops']} | {r['temp_mb']:.1f} | "
              f"{r['same_bits']} |")


def serve(args) -> int:
    tiny = args.tiny
    shapes = TINY_SHAPES if tiny else SERVE_SHAPES
    tiles = [int(t) for t in args.tiles.split(",")]
    rows, layouts = [], []
    for model, (experts, held, top_k, hidden, width, act) in shapes.items():
        weight_bytes = 3 * held * hidden * width * 2
        for tokens, skew in ((t, float(k)) for t in ((8, 64) if tiny else (32, 1024))
                             for k in args.skew.split(",")):
            operands = serve_inputs(tokens, experts, held, top_k, hidden, width, skew)
            mean_rows = tokens * top_k / experts
            own_tile = row_tile(tokens, top_k, experts, operands[0].dtype)
            for empty_tiles in (True, False):  # (False: what a cached forward asks for)
                for r in layout_alone(operands[-1], held, own_tile, empty_tiles, tiny):
                    r = {"model": model, "tokens": tokens, "skew": skew, "tile": own_tile,
                         "pairs": tokens * top_k, "groups": held + 1,
                         "empty_tiles": empty_tiles, **r}
                    print(json.dumps(r), flush=True)
                    layouts.append(r)
            want = None
            for tile in sorted(tiles, reverse=True):  # 256 first: the one the others are held to
                fwd, only = (jax.jit(functools.partial(serve_forward, held=held, tile=tile,
                                                       act=act, empty_tiles=e))
                             for e in (True, False))
                iters = 3 if tiny else 30
                y = jax.block_until_ready(fwd(*operands))
                ms = timed(fwd, *operands, iters=iters)
                y_only = jax.block_until_ready(only(*operands))
                only_ms = timed(only, *operands, iters=iters)
                ops, only_ops = ((device_ops(f, operands, top=args.ops) for f in (fwd, only))
                                 if args.ops and not tiny else ([], []))
                lay, lay_only = (jax.jit(functools.partial(
                    moe.held_layout, held=held, tile=tile, first_held=0, empty_tiles=e))(
                        operands[-1]) for e in (True, False))
                want = y if want is None else want
                row = {"model": model, "tokens": tokens, "skew": skew,
                       "mean_rows_an_expert": mean_rows, "tile": tile, "fwd_ms": ms,
                       "weights_gb_per_s": weight_bytes / ms / 1e6,
                       "buffer_rows": int(lay.row_valid.shape[0]),
                       "rows_in_use": int(lay.num_tiles[0]) * tile,
                       "held_pairs": int(jnp.sum(lay.sizes)),
                       "experts_touched": int(jnp.sum(lay.sizes > 0)), "experts_held": held,
                       "fwd_only_ms": only_ms,
                       "fwd_only_rows_in_use": int(lay_only.num_tiles[0]) * tile,
                       "fwd_only_is_the_default_to_the_bit": bool(jnp.array_equal(y_only, y)),
                       "fwd_only_device_ops_ms": only_ops,
                       "rel_to_256": rel(y.astype(jnp.float32), want.astype(jnp.float32)),
                       "finite": bool(jnp.isfinite(y.astype(jnp.float32)).all()),
                       "device_ops_ms": ops}
                print(json.dumps(row), flush=True)
                rows.append(row)
    print("| model | tokens | skew | mean rows an expert | tile | rows in use / buffer | "
          "held pairs | fwd ms | GB/s of the weights | touched / held | forward-only rows | "
          "forward-only ms | same bits |")
    print("| --- | --- | --- | --- | --- | --- | --- | --- | --- | --- | --- | --- | --- |")
    for r in rows:
        print(f"| {r['model']} | {r['tokens']} | {r['skew']:g} | {r['mean_rows_an_expert']:g} | "
              f"{r['tile']} | {r['rows_in_use']} / {r['buffer_rows']} | {r['held_pairs']} | "
              f"{r['fwd_ms']:.3f} | {r['weights_gb_per_s']:.0f} | "
              f"{r['experts_touched']} / {r['experts_held']} | {r['fwd_only_rows_in_use']} | "
              f"{r['fwd_only_ms']:.3f} | {r['fwd_only_is_the_default_to_the_bit']} |")
    print_layouts(layouts)
    worst = max(r["rel_to_256"] for r in rows)
    ok = (all(r["finite"] and r["fwd_only_is_the_default_to_the_bit"] for r in rows)
          and all(r["same_bits"] for r in layouts) and worst < 0.02)
    print(json.dumps({"ok": ok, "worst_rel_to_256": worst,
                      "device": str(np.asarray(jax.devices())[0])}))
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shares", default="0.0625,0.25,0.98")
    ap.add_argument("--ops", type=int, default=8, help="device operations listed a case")
    ap.add_argument("--serve", action="store_true", help="the forward at the serving shapes, by tile")
    ap.add_argument("--tiles", default="16,32,64,128,256")
    ap.add_argument("--skew", default="1", help="--serve: the routers' skews (0: an even load)")
    ap.add_argument("--tiny", action="store_true", help="--serve at small widths, any backend")
    ap.add_argument("--plain", action="store_true",
                    help="--serve: the un-gated plain held path by layout and column block")
    ap.add_argument("--seeds", default="0,3", help="--plain: the seeds of the routers' choices")
    args = ap.parse_args(argv)
    if not (args.serve and args.tiny) and jax.devices()[0].platform != "tpu":
        raise SystemExit("ab_moe_held: needs a TPU")
    if args.serve:
        return serve_plain(args) if args.plain else serve(args)
    rows, layouts = [], []
    shares = [float(s) for s in args.shares.split(",")]
    for share in shares:
        operands, idx, cot = inputs(share)
        # (the cell's 32 held + the dropped, then wider: a device that held 128, 256 or all
        # 512 of the experts; no cell does, and the layout is counted there all the same)
        for held in (HELD,) + (WIDE_HELD if share == shares[0] else ()):
            for r in layout_alone(idx, held, TILE, top=args.ops):
                r = {"model": "qwen3-next-80b-a3b", "tokens": TOKENS, "share": share,
                     "tile": TILE, "pairs": TOKENS * TOP_K, "groups": held + 1,
                     "empty_tiles": True, **r}
                print(json.dumps(r), flush=True)
                layouts.append(r)
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
    print_layouts(layouts)
    worst = max(max(r["rel_to_plain"].values()) for r in rows if "rel_to_plain" in r)
    ok = all(r["finite"] for r in rows) and all(r["same_bits"] for r in layouts) and worst < 0.05
    print(json.dumps({"ok": ok, "worst_rel_to_plain": worst,
                      "device": str(np.asarray(jax.devices())[0])}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
