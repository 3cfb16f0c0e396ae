"""A/B the combined flash backward's block configs and seq envelope on-chip.

Paired layer-diff of a FULL fwd+bwd+sgd train step at (l1, l2) = (2, 4);
each variant monkeypatches the flash module's backward block constants / seq
gate and re-jits. Motivated by the VMEM finding (experiments/vmem_probe.py):
the chip runs kernels with >=120 MB resident, so the (256, 512) blocks and
the s*d <= 2048*128 combined-backward gate — both chosen against Mosaic's
16 MB default — are no longer forced.

Timing discipline: the window is ONE dispatch (a lax.scan
whose params carry chains the iterations), synced by a scalar D2H fetch.

Usage:
  python experiments/ab_flash_bwd.py --seq 2048 --variants cur,b512,b512x1024,grid
  python experiments/ab_flash_bwd.py --seq 4096 --variants grid,cur,b512x1024
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from galvatron_tpu.models import modeling
from galvatron_tpu.ops import flash_attention as fa

# name -> (bq_sub, bk, max_seq_x_dim); "grid" forces the pre-round-4 grid
# kernels by zeroing the combined-backward gate
VARIANTS = {
    "cur": (256, 512, 4096 * 128, 4096 * 128),
    "b512": (512, 512, 4096 * 128, 4096 * 128),
    "b512x1024": (512, 1024, 4096 * 128, 4096 * 128),
    "b1024": (1024, 1024, 4096 * 128, 4096 * 128),
    "grid": (256, 512, 0, 4096 * 128),
    # extend BOTH the blocked-forward and combined-backward envelopes to 8k
    "ext8k": (256, 512, 8192 * 128, 8192 * 128),
    "gridall": (256, 512, 0, 0),
}


_SHARED = {}


def shared_params(bsz, seq, l_max):
    """One param set + token batch per (bsz, seq), shared by every window
    (smaller windows slice the layer list) so holding many compiled variants
    does not multiply resident HBM."""
    key = (bsz, seq)
    if key not in _SHARED:
        cfg = modeling.ModelConfig(
            vocab_size=32000, hidden_size=4096, num_layers=l_max,
            num_heads=32, ffn_dim=11008, max_seq_len=seq,
            dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, attn_impl="flash",
        )
        _SHARED[key] = (
            cfg,
            modeling.init_model_params(jax.random.key(0), cfg),
            jnp.zeros((bsz, seq), jnp.int32),
        )
    return _SHARED[key]


def make_window(num_layers, bsz, seq, iters=4):
    cfg_full, params_full, tokens = shared_params(bsz, seq, 4)
    cfg = cfg_full.replace(num_layers=num_layers)
    params0 = dict(params_full, layers=params_full["layers"][:num_layers])

    def loss_fn(params, tokens):
        x = modeling.embed(tokens, params, cfg)
        cos_sin = modeling.rope_tables(cfg, seq)
        for lp in params["layers"]:
            x = modeling.decoder_layer(x, lp, cfg, cos_sin, None)
        return jnp.sum(x.astype(jnp.float32))

    @jax.jit
    def window(params, tokens):
        def body(carry, _):
            params, acc = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
            # the sgd update chains iterations through the carry, so no grad
            # GEMM can be DCE'd (every update feeds the next iteration's
            # loss; the last one is materialized as a window output)
            new_params = jax.tree.map(
                lambda p, g: p - (1e-9 * g).astype(p.dtype), params, grads
            )
            return (new_params, acc + loss), None

        carry, _ = jax.lax.scan(
            body, (params, jnp.zeros((), jnp.float32)), None, length=iters
        )
        return carry

    _, acc = window(params0, tokens)
    _ = float(acc)  # compile + sync

    def run():
        t0 = time.perf_counter()
        _, acc = window(params0, tokens)
        _ = float(acc)
        return (time.perf_counter() - t0) / iters * 1000

    return run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variants", default="cur,b512,b512x1024,grid")
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--bsz", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()
    names = args.variants.split(",")
    l1, l2 = 2, 4

    wins = {}
    for nm in names:
        bq_sub, bk, max_sxd, fwd_sxd = VARIANTS[nm]
        fa._BWD_BQ_SUB, fa._BWD_BK, fa._BWD_MAX_SEQ_X_DIM = bq_sub, bk, max_sxd
        fa._BLOCKED_MAX_SEQ_X_DIM = fwd_sxd
        print(f"compiling {nm} (bq_sub={bq_sub}, bk={bk}, gate={max_sxd})...",
              flush=True)
        # make_window compiles eagerly, inside this variant's constants
        wins[nm] = (
            make_window(l1, args.bsz, args.seq),
            make_window(l2, args.bsz, args.seq),
        )

    results = {nm: [] for nm in names}
    for r in range(args.rounds):
        for nm in names:
            w1, w2 = wins[nm]
            diff = (w2() - w1()) / (l2 - l1) / args.bsz
            results[nm].append(diff)
            print(f"round {r} {nm}: {diff:.4f} ms/layer/sample fwd+bwd",
                  flush=True)
    print("---")
    for nm in names:
        print(f"{nm}: median {np.median(results[nm]):.4f}  "
              f"all={['%.4f' % x for x in results[nm]]}")


if __name__ == "__main__":
    main()
