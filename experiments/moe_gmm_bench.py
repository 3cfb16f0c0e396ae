"""Grouped GEMM for the dropless top-k MoE path, alone on the chip: which
implementation `models/moe.py` should call at the OLMoE cell's shape.

    chiprun --chips 1 -- python experiments/moe_gmm_bench.py

Shape: 4 x 4096 tokens, top-8 of 64 experts = 131072 (token, expert) pairs,
hidden 2048, expert width 1024 (`olmoe-1b-7b_s4096`), bf16 operands, the
routing of a seeded softmax top-8 router (plain, and with router columns of
log-normal length, sd 0.5, so that load is uneven).  Candidates, all over the tile-aligned sorted
row buffer of ``moe.sorted_layout``: ``jax.lax.ragged_dot``, this repo's
``ops/grouped_matmul.py`` and jax's ``megablox`` ``gmm`` / ``tgmm`` pair, the
kernels over a few tilings.  Timed: the expert FFN (gate, up, SwiGLU, down)
forward + backward through ``jax.value_and_grad``, and the whole MoE block
(router, top-k, sort, gather, FFN, combine) forward + backward, which is what
a train step runs.  One JSON line a measurement; no CPU fallback.
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

from galvatron_tpu.models import moe  # noqa: E402
from galvatron_tpu.models.modeling import ModelConfig  # noqa: E402

BATCH, SEQ, EXPERTS, HIDDEN, WIDTH, TOPK = 4, 4096, 64, 2048, 1024, 8


def timed(fn, *args, iters=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def group_rows(layout, tile):
    """Rows of each group in the sorted buffer, padding included."""
    return (jnp.maximum(-(-layout.sizes // tile), 1) * tile).astype(jnp.int32)


def ragged_gemm(lhs, rhs, layout, tile):
    return jax.lax.ragged_dot(lhs, rhs, group_rows(layout, tile))


def megablox_gemm(tiling):
    from jax.experimental.pallas.ops.tpu.megablox import ops as mb

    def gemm(lhs, rhs, layout, tile):
        return mb.gmm(lhs, rhs, group_rows(layout, tile), lhs.dtype, tiling)

    return gemm


def candidates():
    """name -> (row tile of the layout, grouped GEMM)."""
    out = {"ragged_dot_t8": (8, ragged_gemm), "ragged_dot_t256": (256, ragged_gemm)}
    for tile, tn in ((512, 1024), (512, 512), (256, 1024), (1024, 1024), (128, 1024)):
        def gemm(l, r, lay, t, tn=tn):
            from galvatron_tpu.ops.grouped_matmul import grouped_matmul

            return grouped_matmul(l, r, lay.tile_group, lay.num_tiles, t, tn)

        out[f"pallas_m{tile}_n{tn}"] = (tile, gemm)
    for tiling in ((512, 1024, 1024), (512, 2048, 1024), (256, 1024, 1024), (512, 512, 512)):
        out["megablox_%dx%dx%d" % tiling] = (tiling[0], megablox_gemm(tiling))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated name prefixes to run")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("moe_gmm_bench: needs a TPU")
    cfg = ModelConfig(hidden_size=HIDDEN, num_heads=16, ffn_dim=WIDTH, moe_experts=EXPERTS,
                      moe_router="softmax_topk", moe_top_k=TOPK)
    ks = jax.random.split(jax.random.key(0), 4)
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), moe.init_moe_params(ks[0], cfg))
    x = jax.random.normal(ks[1], (BATCH, SEQ, HIDDEN), jnp.bfloat16)
    cot = jax.random.normal(ks[2], (BATCH, SEQ, HIDDEN), jnp.bfloat16)
    tokens = BATCH * SEQ
    ffn_flops = 3 * 3 * 2.0 * tokens * TOPK * HIDDEN * WIDTH  # 9 GEMMs, forward + backward
    for bias_sd in (0.0, 0.5):
        # columns of uneven norm: an expert with a longer column wins more tokens
        router = {"w": jax.random.normal(ks[3], (HIDDEN, EXPERTS)) * 0.02
                  * jnp.exp(bias_sd * jax.random.normal(ks[0], (1, EXPERTS)))}
        pr = dict(p, router=router)
        ref = None
        for name, (tile, gemm) in candidates().items():
            if args.only and not any(name.startswith(o) for o in args.only.split(",")):
                continue
            try:
                moe.grouped_gemm = gemm  # the block calls it by this name
                block = jax.jit(lambda x_, p_, tile=tile: moe.moe_topk_block(x_, p_, cfg, tile=tile))
                y, stats = block(x, pr)
                load = float(moe.load_max_over_mean([stats], EXPERTS, TOPK))
                step = jax.jit(jax.value_and_grad(
                    lambda x_, p_, tile=tile: jnp.sum(
                        moe.moe_topk_block(x_, p_, cfg, tile=tile)[0].astype(jnp.float32) * cot),
                    argnums=(0, 1)))
                ms_block = timed(step, x, pr)

                # the expert FFN alone, on the rows the block built
                _, idx = jax.lax.top_k(jax.nn.softmax(
                    x.reshape(tokens, HIDDEN).astype(jnp.float32) @ router["w"], -1), TOPK)
                lay = jax.jit(lambda i, tile=tile: moe.sorted_layout(i, EXPERTS, tile))(idx)
                rows = jnp.where(lay.row_valid[:, None],
                                 x.reshape(tokens, HIDDEN)[lay.row_pair // TOPK], 0)
                cot_rows = jnp.where(lay.row_valid[:, None],
                                     cot.reshape(tokens, HIDDEN)[lay.row_pair // TOPK], 0)

                def ffn(rows_, w1, w3, w2, lay_, tile=tile, gemm=gemm):
                    g = gemm(rows_, w1, lay_, tile)
                    u = gemm(rows_, w3, lay_, tile)
                    return gemm(jax.nn.silu(g) * u, w2, lay_, tile)

                ffn_step = jax.jit(jax.value_and_grad(
                    lambda r_, a, b, c, l_: jnp.sum(
                        ffn(r_, a, b, c, l_).astype(jnp.float32) * cot_rows), argnums=(0, 1, 2, 3)))
                ms_ffn = timed(ffn_step, rows, pr["w1"], pr["w3"], pr["w2"], lay)
                ms_fwd = timed(jax.jit(ffn), rows, pr["w1"], pr["w3"], pr["w2"], lay)
                y = np.asarray(y.astype(jnp.float32))
                ref = y if ref is None else ref
                print(json.dumps({
                    "impl": name, "bias_sd": bias_sd, "load_max_over_mean": load,
                    "rows": int(rows.shape[0]), "tiles_used": int(lay.num_tiles[0]),
                    "ffn_fwd_ms": ms_fwd, "ffn_fwd_bwd_ms": ms_ffn,
                    "ffn_fwd_bwd_tflops": ffn_flops / ms_ffn / 1e9,
                    "block_fwd_bwd_ms": ms_block,
                    "rel_to_first": float(np.abs(y - ref).max() / np.abs(ref).max())}),
                    flush=True)
            except Exception as e:  # a tiling the chip refuses is a result, not a crash
                print(json.dumps({"impl": name, "bias_sd": bias_sd,
                                  "error": repr(e)[:400]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
