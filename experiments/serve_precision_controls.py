#!/usr/bin/env python3
"""What a serving cell's ``correct`` reads when one part of the program is computed
below the precision its configuration states, or a row's state is left unreset (PR 51,
54, 58).

    python experiments/serve_precision_controls.py --workload sarvam-105b_serve_long_above_knee \
        --seeds 2147488001,2147488002 [--seconds 30] [--modes sound,router_bf16,cache_e4m3,kv_e4m3,int8]

One process; for every seed and mode one run of the cell through the benchmark's
own ``run_serve_cell`` at the cell's own load, the fault planted underneath it:

- ``sound``: the cell as it is;
- ``router_bf16``: the float32 router's GEMM and sigmoid computed in bfloat16
  (``models/moe.router_scores``);
- ``cache_e4m3``: every latent cache entry ``[c~ | k_r]`` rounded to float8 e4m3's
  3 mantissa bits before it is written (``models/mla.project``): a cache kept
  below bfloat16;
- ``kv_e4m3``: every key and value rounded the same way before it is written to a
  K/V slot cache (``models/generation._project_qkv_at``; a windowed stack's two
  stacks alike): PR 54's cell;
- ``state_e4m3``: a layer's per-row state (the gated short convolution's last inputs)
  rounded the same way before it is written (``models/shortconv.stored``): a state kept
  below bfloat16; PR 58's cell;
- ``state_stale``: a forward that starts a request at position 0 reads the state its
  slot holds (the previous request's, or what idle decode steps left) in place of zeros
  (``models/shortconv.fresh``): a state not reset at admission;
- ``scan_bf16``: a Mamba-2 layer's scan state kept in bfloat16 where the configuration
  states float32 (``models/ssm.state_shapes``: the stack's type, so every step's and every
  chunk's state is rounded as it is written); PR 68's cell;
- ``scan_stale``: a forward that starts a request at position 0 reads the conv tail and the
  scan state its slot holds in place of zeros (``models/ssm.cached_block`` told that every
  forward starts past position 0);
- ``weights_e4m3``: every matrix the ENGINE serves with rounded to float8 e4m3's 3 mantissa
  bits (the nearest float type below the bfloat16 the configuration states), the reference
  on the weights as they were drawn: ``lib/serve.make_weights`` hands the engine the rounded
  tree, and the reference's ``published_weights`` drops it and draws the seed's tree again
  (two trees of 9.5 GB do not fit a chip); PR 70's cell;
- ``residual_one``: ``models/modeling.residual_add`` told that ``residual_multiplier`` is 1
  (a branch joins the stream as it is: what every cached forward but a state layer's did
  before PR 70); ``logits_one``: ``models/modeling.lm_head`` told that ``logits_scaling`` is
  1; both need a stack with Granite's multipliers (PR 70's cell);
- ``int8``: the engine's own per-channel int8 weights (``--serve_quant int8``), as
  ``benchmark/control.py`` reads them.

Prints what ``correct`` compared a run and, at the end, each mode's readings beside
the limit in the traffic file (PERF.md section 6).  No run of the benchmark plants
any of these.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

INT8 = ("--serve_quant", "int8", "--quant_drift_max", "1e9")


@contextlib.contextmanager
def planted(mode: str):
    """The program with ``mode``'s fault under it; every compiled program is
    dropped on the way in and out (a jitted step traced before would keep its own)."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference, serve
    from galvatron_tpu.models import generation, mla, modeling, moe, shortconv, ssm

    real_scores, real_project = moe.router_scores, mla.project
    real_qkv = generation._project_qkv_at
    real_stored, real_fresh = shortconv.stored, shortconv.fresh
    real_shapes, real_cached = ssm.state_shapes, ssm.cached_block
    real_add, real_head = modeling.residual_add, modeling.lm_head
    real_make, real_load = serve.make_weights, reference.load

    def e4m3(t):
        bits = jax.lax.bitcast_convert_type(t.astype(jnp.bfloat16), jnp.uint16)
        # bfloat16 keeps 7 mantissa bits, e4m3 keeps 3: round the low 4 away (half up)
        bits = (bits + jnp.uint16(8)) & jnp.uint16(0xFFF0)
        return jax.lax.bitcast_convert_type(bits, jnp.bfloat16).astype(t.dtype)

    def qkv_e4m3(x, p, cfg, cos_sin):
        q, k, v = real_qkv(x, p, cfg, cos_sin)
        return q, e4m3(k), e4m3(v)

    def scores_bf16(xt, router, cfg):
        x, w = xt.astype(jnp.bfloat16), router["w"].astype(jnp.bfloat16)
        return jax.nn.sigmoid(x @ w).astype(jnp.float32)

    def shapes_bf16(cfg):
        shapes = real_shapes(cfg)
        return dict(shapes, scan=(shapes["scan"][0], jnp.dtype(jnp.bfloat16)))

    def project_e4m3(x, p, cfg, cos_sin):
        q_nope, q_rope, new = real_project(x, p, cfg, cos_sin)
        return q_nope, q_rope, e4m3(new)

    drawn = {}  # weights_e4m3: what the engine's tree was drawn from

    def make_rounded(cfg, seed):
        drawn["args"] = (cfg, seed)
        return jax.jit(lambda tree: jax.tree.map(lambda a: e4m3(a) if a.ndim >= 2 else a, tree),
                       donate_argnums=0)(real_make(cfg, seed))

    def load_unrounded(root, model_type):
        arch = real_load(root, model_type)

        def published_weights(params, config):
            for leaf in jax.tree.leaves(params):  # the rounded tree: the engine is gone
                leaf.delete()
            return arch.published_weights(real_make(*drawn["args"]), config)

        return types.SimpleNamespace(**{**vars(arch), "published_weights": published_weights})

    jax.clear_caches()
    if mode == "weights_e4m3":
        serve.make_weights, reference.load = make_rounded, load_unrounded
    elif mode == "residual_one":
        modeling.residual_add = lambda x, y, cfg: x + y
    elif mode == "logits_one":
        modeling.lm_head = lambda x, params, cfg: real_head(x, params, cfg.replace(logits_scaling=1.0))
    elif mode == "router_bf16":
        moe.router_scores = scores_bf16
    elif mode == "cache_e4m3":
        mla.project = project_e4m3
    elif mode == "kv_e4m3":
        generation._project_qkv_at = qkv_e4m3
    elif mode == "state_e4m3":
        shortconv.stored = lambda new, dtype: e4m3(new).astype(dtype)
    elif mode == "state_stale":
        shortconv.fresh = lambda prev, offsets: prev
    elif mode == "scan_bf16":
        ssm.state_shapes = shapes_bf16
    elif mode == "scan_stale":
        ssm.cached_block = lambda x, p, cfg, state, layer, slot, offsets, last: real_cached(
            x, p, cfg, state, layer, slot, jnp.maximum(offsets, 1), last)
    try:
        yield
    finally:
        moe.router_scores, mla.project = real_scores, real_project
        generation._project_qkv_at = real_qkv
        shortconv.stored, shortconv.fresh = real_stored, real_fresh
        ssm.state_shapes, ssm.cached_block = real_shapes, real_cached
        modeling.residual_add, modeling.lm_head = real_add, real_head
        serve.make_weights, reference.load = real_make, real_load
        jax.clear_caches()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--modes", default="sound,router_bf16,cache_e4m3,int8")
    args = ap.parse_args(argv)

    import jax

    from benchmark.lib import harness, serve

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("serve_precision_controls: needs a TPU; the limits are set from chip "
                         "readings")
    read = {mode: [] for mode in args.modes.split(",")}
    for seed in (int(x) for x in args.seeds.split(",")):
        for mode in read:
            out_dir = tempfile.mkdtemp(prefix="galvatron_controls_")
            try:
                with planted(mode):
                    res = serve.run_serve_cell(
                        ROOT, args.workload, seed=seed, seconds=args.seconds, trace=False,
                        out_dir=out_dir, t_start=time.time(),
                        overrides=INT8 if mode == "int8" else ())
            finally:
                shutil.rmtree(out_dir, ignore_errors=True)
            read[mode].append(res["compared"]["logits_kl"])
            print("CONTROL " + json.dumps({
                "mode": mode, "seed": seed, "correct": res["correct"], "failed": res["failed"],
                "tokens_per_s": res["metrics"].get("serve_tokens_per_s_per_chip", {}).get("value"),
                **res["compared"]}), flush=True)
    limit = harness.load_cell(ROOT, args.workload)[2]["correct"]["logits_kl_max"]
    print("READINGS " + json.dumps({"workload": args.workload, "logits_kl_max": limit, **read}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
