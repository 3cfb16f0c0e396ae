"""Activation-memory sweep: the mlp_recompute policy vs the TPU compiler.

Measures, against the device-less v5e:2x4 topology (the round-5 channel,
search/memory_fidelity.py), per-device state/temp MB for the fidelity cells
at BOTH the 7B-representative and the small shape, with mlp_recompute in
{off, policy} — the numbers behind:

  - the act_mb sp/tp coefficient refit (search/cost_model.py),
  - the buffer-accounting pins in tests/test_topology_aot.py,
  - the max feasible per-device batch under the v5e budget.

Prints one JSON line per measurement; run from the repo root:
  JAX_PLATFORMS=cpu python experiments/act_memory_sweep.py [--quick]
"""

import json
import sys
import time

sys.path.insert(0, ".")

import jax.numpy as jnp

from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.search.memory_fidelity import measured_train_mb

# attn_impl: 'flash' is the production path, but the round-5 audit showed
# the gate/norm/CE buffer inflation is attention-impl independent ("Same
# inflation with attn_impl='xla'"), and Mosaic AOT lowering SIGILLs on some
# sandboxed hosts — default to the xla channel, override with --flash.
ATTN = "flash" if "--flash" in sys.argv else "xla"
BIG = ModelConfig(vocab_size=8192, hidden_size=2048, num_layers=4, num_heads=16,
                  max_seq_len=2048, dtype=jnp.bfloat16, attn_impl=ATTN)
SMALL = ModelConfig(vocab_size=512, hidden_size=512, num_layers=4, num_heads=4,
                    max_seq_len=512, dtype=jnp.bfloat16, attn_impl=ATTN)


def hp(s, n=4, **kw):
    kw.setdefault("vocab_tp", s.tp)
    kw.setdefault("mixed_precision", "bf16")
    return HybridParallelConfig(layer_strategies=[s] * n, **kw)


def cells():
    # small shape first: cheap compiles give the off/policy delta signal
    # before the big-shape cells land
    yield "small", "tp2 zero3 sp", SMALL, hp(
        LayerStrategy(tp=2, dp_type="zero3", sp=True)), 16
    yield "big", "tp1 ddp", BIG, hp(LayerStrategy(tp=1)), 16
    yield "big", "tp2 ddp", BIG, hp(LayerStrategy(tp=2)), 16
    yield "big", "tp2 sp", BIG, hp(LayerStrategy(tp=2, sp=True)), 16
    yield "big", "tp2 zero3 sp", BIG, hp(LayerStrategy(tp=2, dp_type="zero3", sp=True)), 16
    yield "big", "tp1 ckpt", BIG, hp(LayerStrategy(tp=1, ckpt="full")), 16
    yield "big", "pp2 gpipe ch2", BIG, hp(
        LayerStrategy(tp=1), pp=2, chunks=2, pipeline_type="gpipe"), 16
    yield "big", "pp2 1f1b ch4", BIG, hp(
        LayerStrategy(tp=1), pp=2, chunks=4, pipeline_type="pipedream_flush"), 16
    yield "small", "tp1 ddp", SMALL, hp(LayerStrategy(tp=1)), 16
    yield "small", "tp2 sp", SMALL, hp(LayerStrategy(tp=2, sp=True)), 16
    yield "small", "pp2 1f1b ch4", SMALL, hp(
        LayerStrategy(tp=1), pp=2, chunks=4, pipeline_type="pipedream_flush"), 16
    yield "small", "pp4 1f1b ch4", SMALL, hp(
        LayerStrategy(tp=1), pp=4, chunks=4, pipeline_type="pipedream_flush"), 16


def measure(cfg, h, bsz):
    t0 = time.time()
    m = measured_train_mb(cfg, h, bsz)
    if m is None:
        return None
    m["compile_s"] = round(time.time() - t0, 1)
    return m


def main():
    quick = "--quick" in sys.argv
    for shape, label, cfg, h, bsz in cells():
        if quick and shape == "small":
            continue
        for mode in ("off", "policy"):
            c = cfg.replace(mlp_recompute=mode)
            # the strategy's mode wins inside build_runtime — set BOTH
            h.mlp_recompute = mode
            m = measure(c, h, bsz)
            if m is None:
                print(json.dumps({"error": "topology unavailable"}), flush=True)
                return
            print(json.dumps({
                "shape": shape, "cell": label, "mode": mode, "bsz": bsz,
                "state_mb": round(m["state_mb"], 1),
                "temp_mb": round(m["temp_mb"], 1),
                "total_mb": round(m["total_mb"], 1),
                "compile_s": m["compile_s"],
            }), flush=True)

    # max feasible per-device batch at the 7B-representative shape under the
    # v5e 16 GB HBM budget, tp2+zero3+sp cell
    budget_mb = 16384.0 * 0.92  # leave the runtime's own overhead headroom
    for mode in ("off", "policy"):
        feasible = 0
        # +8 global (= +2 per device) steps: doubling cannot resolve a
        # ~10-15% memory win at the feasibility boundary
        bsz = 16
        while bsz <= 512:
            c = BIG.replace(mlp_recompute=mode)
            h2 = hp(LayerStrategy(tp=2, dp_type="zero3", sp=True))
            h2.mlp_recompute = mode
            m = measure(c, h2, bsz)
            if m is None:
                return
            fits = m["total_mb"] <= budget_mb
            print(json.dumps({
                "probe": "max_feasible", "mode": mode, "global_bsz": bsz,
                "per_device_bsz": bsz / 4, "total_mb": round(m["total_mb"], 1),
                "fits": fits, "compile_s": m["compile_s"],
            }), flush=True)
            if not fits:
                break
            feasible = bsz
            bsz += 8
        print(json.dumps({
            "probe": "max_feasible_result", "mode": mode,
            "global_bsz": feasible, "per_device_bsz": feasible / 4,
        }), flush=True)


if __name__ == "__main__":
    main()
