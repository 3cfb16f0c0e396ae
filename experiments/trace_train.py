"""Trace one full train step (fwd + bwd) of the 7B-shape model and print the
per-op device-time breakdown.

The headline bench is forward-only; this is the tool that exposes what the
BACKWARD pays (flash bwd kernels, layout copies around them, GEMM grads).
Parses the device trace (vm.trace.json.gz) and sums durations per op name,
mapping fusions to model code via args.long_name/source.

Usage: python experiments/trace_train.py [--layers 4] [--steps 3] [--top 45]
"""

from __future__ import annotations

import argparse
import collections
import functools
import glob
import gzip
import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from galvatron_tpu.models import modeling


def build_step(num_layers, bsz=8, seq=2048):
    cfg = modeling.ModelConfig(
        vocab_size=32000, hidden_size=4096, num_layers=num_layers,
        num_heads=32, ffn_dim=11008, max_seq_len=seq,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, attn_impl="flash",
    )
    params = modeling.init_model_params(jax.random.key(0), cfg)
    tokens = jnp.zeros((bsz, seq), jnp.int32)

    def loss_fn(params, tokens):
        x = modeling.embed(tokens, params, cfg)
        cos_sin = modeling.rope_tables(cfg, seq)
        for lp in params["layers"]:
            x = modeling.decoder_layer(x, lp, cfg, cos_sin, None)
        return jnp.sum(x.astype(jnp.float32))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(params, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        # RETURN the sgd-updated params: outputs must be materialized, so no
        # grad GEMM can be DCE'd or algebraically collapsed (a bare
        # sum(grads) consumption gets rewritten by XLA into scalar reduce
        # fusions that elide the weight-grad GEMMs entirely)
        new_params = jax.tree.map(lambda p, g: p - (1e-9 * g).astype(p.dtype), params, grads)
        return loss, new_params

    return step, params, tokens


def collect_trace(step, params, tokens, steps):
    tdir = tempfile.mkdtemp(prefix="trace_train_")
    loss, params = step(params, tokens)  # compile
    _ = float(loss)
    with jax.profiler.trace(tdir):
        for _ in range(steps):
            loss, params = step(params, tokens)
        _ = float(loss)
    return tdir


def parse_trace(tdir, steps, top, per_layer_divisor):
    paths = glob.glob(os.path.join(tdir, "**", "*.trace.json.gz"), recursive=True)
    assert paths, f"no trace files under {tdir}"
    durs = collections.defaultdict(float)   # name -> us (all steps)
    longname = {}
    for p in paths:
        with gzip.open(p, "rt") as f:
            data = json.load(f)
        for ev in data.get("traceEvents", []):
            if ev.get("ph") != "X" or "dur" not in ev:
                continue
            # device (TensorCore) lanes only: host lanes have pid names like
            # python; the device op events carry run_id/long_name args
            args = ev.get("args") or {}
            name = ev.get("name", "")
            if "long_name" not in args and "tf_op" not in args and not name.startswith(
                ("fusion", "copy", "custom-call", "convolution", "dot", "transpose",
                 "dynamic-slice", "dynamic-update-slice", "reduce", "broadcast",
                 "bitcast", "concatenate", "scatter", "all-reduce", "slice",
                 "iota", "select", "convert", "pad", "reshape", "rsqrt", "add",
                 "multiply", "subtract", "divide", "exponential", "tanh", "log")
            ):
                continue
            durs[name] += ev["dur"]
            ln = args.get("long_name") or args.get("source") or ""
            if ln and name not in longname:
                longname[name] = ln[:160]
    total = sum(durs.values())
    print(f"total device op time: {total / 1000 / steps:.3f} ms/step "
          f"({total / 1000 / steps / per_layer_divisor:.3f} ms/layer-batch)")
    print(f"{'ms/layer-batch':>14}  op")
    for name, us in sorted(durs.items(), key=lambda kv: -kv[1])[:top]:
        ms_lb = us / 1000 / steps / per_layer_divisor
        print(f"{ms_lb:14.3f}  {name}   {longname.get(name, '')}")
    return durs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--top", type=int, default=45)
    args = ap.parse_args()
    step, params, tokens = build_step(args.layers)
    tdir = collect_trace(step, params, tokens, args.steps)
    print(f"trace dir: {tdir}")
    parse_trace(tdir, args.steps, args.top, args.layers)


if __name__ == "__main__":
    main()
