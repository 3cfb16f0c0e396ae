#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

One process, no children, no network.  Run from the root of the repo (or of
a plain copy of it):

    python chip_smoke.py            # one TPU chip
    python chip_smoke.py --chips 4  # one four-chip host: the multi-chip plans ONLY

One chip (what the driver runs), each phase printing its own lines:

- *device*: anything but a TPU is an error — there is no CPU fallback;
- *kernel parity*: the Pallas flash-attention entries the model really calls
  (stacked-qkv for MHA, head-major for GQA), forward and ``jax.grad``,
  against the fp32 einsum reference at llama-7b attention shapes;
- *train*: ``initialize_galvatron`` + ``core.trainer.train`` — the path
  ``python -m galvatron_tpu.cli train`` takes — at llama-7b's published
  widths with the depth cut to what one 16 GB chip holds.

``--chips 4`` runs three plans at llama-7b widths on the same seed and
batches: plain ZeRO-3 data parallelism with XLA attention (the reference),
then a 1F1B pipeline (pp=2 x tp=2) and a layer-heterogeneous plan, both with
the flash kernels, and holds their per-step losses to the reference's.

Any failed check exits non-zero.  On success the LAST line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

The phases are functions of their sizes; ``main`` alone fixes the real sizes
and the device check, so tests/test_chip_smoke.py drives the same phases at
a tiny size on the virtual CPU mesh.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import statistics
import sys
import time
from typing import Any, Dict, List, Sequence

#: per-step loss agreement between two plans that compute in bf16: one bf16
#: ulp of the loss (the same bound tests/test_hybrid_runtime.py::
#: test_loss_parity holds its bf16 case to)
BF16_LOSS_TOL = {"rtol": 2.0 ** -8, "atol": 0.0}
#: kernel vs fp32 reference, max|err| / max|ref| (bf16 outputs: 2^-8 per rounding)
KERNEL_FWD_TOL = 2e-2
KERNEL_GRAD_TOL = 4e-2
HBM_V5E_GIB = 15.75  # what the TPU compiler reports as usable on a 16 GB v5e


def say(msg: str) -> None:
    print(msg, flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED — {msg}")


# ---------------------------------------------------------------------------
# phase: kernel parity
# ---------------------------------------------------------------------------


def phase_kernel_parity(*, batch: int, seq: int, heads: int, kv_heads: int,
                        head_dim: int, seed: int = 0) -> List[Dict[str, Any]]:
    """Flash kernels vs ``attention_xla`` in fp32, forward and grad, through
    the two entries ``modeling._attn_block_headmajor`` dispatches to: the
    stacked (b, 3, n, s, d) one for MHA and the head-major one with grouped
    K/V for GQA.  Returns one report per case; raises on a bound."""
    import jax
    import jax.numpy as jnp

    from galvatron_tpu.models import modeling
    from galvatron_tpu.ops import flash_attention as fa

    cfg = modeling.ModelConfig(
        num_heads=heads, hidden_size=heads * head_dim, causal=True,
        dtype=jnp.float32,
    )
    rope = modeling.rope_tables(cfg, seq)
    # the wrappers give way to an einsum when a shape does not tile: the
    # smoke is about the kernels, so its shapes must take the kernel path
    require(fa.flash_tileable(seq), f"seq {seq} does not tile the flash kernels")
    require(fa.flash_qkv_supported(seq, head_dim, True),
            f"s={seq} d={head_dim} is outside the stacked-qkv kernel's envelope")

    def to_bsnd(x):
        return jnp.transpose(x.astype(jnp.float32), (0, 2, 1, 3))

    def ref_loss(q, k, v, w):
        # one sample at a time (see below), head-major bf16 in, fp32 math
        with jax.default_matmul_precision("highest"):
            o = modeling.attention_xla(
                modeling.apply_rope(to_bsnd(q), *rope),
                modeling.apply_rope(to_bsnd(k), *rope),
                to_bsnd(v), cfg,
            )
        o = jnp.transpose(o, (0, 2, 1, 3))
        return jnp.sum(o * w), o

    ref_vg = jax.jit(jax.value_and_grad(ref_loss, argnums=(0, 1, 2), has_aux=True))

    def reference(q, k, v, w):
        # per sample: the fp32 (n, s, s) scores of a whole batch at the real
        # size would crowd the chip the kernels are being checked on
        outs, grads = [], []
        for i in range(q.shape[0]):
            (_, o), g = ref_vg(q[i:i + 1], k[i:i + 1], v[i:i + 1], w[i:i + 1])
            outs.append(o)
            grads.append(g)
        cat = lambda xs: jnp.concatenate(xs, axis=0)  # noqa: E731
        return cat(outs), tuple(cat([g[j] for g in grads]) for j in range(3))

    def err(got, want):
        got = jnp.asarray(got, jnp.float32)
        want = jnp.asarray(want, jnp.float32)
        abs_err = float(jnp.max(jnp.abs(got - want)))
        return abs_err, abs_err / max(float(jnp.max(jnp.abs(want))), 1e-30)

    keys = jax.random.split(jax.random.key(seed), 5)
    shape_q = (batch, heads, seq, head_dim)
    w = jax.random.normal(keys[3], shape_q, jnp.float32)
    reports = []

    def run_case(name, kv, kernel_loss, kernel_args, qkv_of, grads_of):
        fn = jax.jit(jax.value_and_grad(kernel_loss, has_aux=True))
        compiled = fn.lower(*kernel_args).compile()
        calls = compiled.as_text().count("tpu_custom_call")
        (_, out), g = compiled(*kernel_args)
        ref_out, ref_g = reference(*qkv_of(*kernel_args), w)
        require(bool(jnp.all(jnp.isfinite(out.astype(jnp.float32)))),
                f"{name}: non-finite kernel output")
        fwd_abs, fwd_rel = err(out, ref_out)
        rep = {"case": name, "kernel_calls": calls, "fwd_abs": fwd_abs,
               "fwd_rel": fwd_rel, "grad_rel": {}}
        for gname, got, want in zip("qkv", grads_of(g), ref_g):
            rep["grad_rel"]["d" + gname] = err(got, want)[1]
        say(f"kernel parity [{name}] b{batch} s{seq} h{heads} kv{kv} "
            f"d{head_dim} bf16+rope: fwd max_abs={fwd_abs:.3e} rel={fwd_rel:.3e} "
            f"(bound {KERNEL_FWD_TOL:g}); grad rel "
            + " ".join(f"{k}={v:.3e}" for k, v in rep["grad_rel"].items())
            + f" (bound {KERNEL_GRAD_TOL:g}); tpu_custom_call x{calls}")
        require(fwd_rel <= KERNEL_FWD_TOL, f"{name}: forward error {fwd_rel:.3e}")
        for gname, rel in rep["grad_rel"].items():
            require(rel <= KERNEL_GRAD_TOL, f"{name}: {gname} error {rel:.3e}")
        reports.append(rep)

    # MHA, stacked projection output — what llama-7b (qkv_blocked) dispatches
    qkv = jax.random.normal(keys[0], (batch, 3, heads, seq, head_dim), jnp.bfloat16)

    def loss_qkv(qkv_, w_):
        o = fa.flash_attention_qkv(qkv_, rope=rope)
        return jnp.sum(o.astype(jnp.float32) * w_), o

    run_case(
        "flash_attention_qkv mha", heads, loss_qkv, (qkv, w),
        lambda qkv_, _w: (qkv_[:, 0], qkv_[:, 1], qkv_[:, 2]),
        lambda g: (g[:, 0], g[:, 1], g[:, 2]),
    )

    # GQA, head-major q with grouped K/V served natively by the kernels
    q = jax.random.normal(keys[1], shape_q, jnp.bfloat16)
    kv = jax.random.normal(keys[2], (2, batch, kv_heads, seq, head_dim), jnp.bfloat16)

    def loss_hm(qkv_, w_):
        q_, k_, v_ = qkv_
        o = fa.flash_attention_hm(q_, k_, v_, causal=True, rope=rope)
        return jnp.sum(o.astype(jnp.float32) * w_), o

    run_case(
        "flash_attention_hm gqa", kv_heads, loss_hm, ((q, kv[0], kv[1]), w),
        lambda qkv_, _w: qkv_,
        lambda g: g,
    )
    return reports


# ---------------------------------------------------------------------------
# phase: train through the normal entry
# ---------------------------------------------------------------------------


def compiled_step_text(rt, global_batch: int, seq_len: int) -> str:
    """Optimized-HLO text of ``rt.train_step`` at the run's shapes.  Compiled
    with the persistent cache off: an executable read back from the cache is
    not guaranteed to carry its text."""
    import jax
    import jax.numpy as jnp

    from galvatron_tpu.aot.cache import persistent_cache_off
    from galvatron_tpu.core.checkpoint import abstract_state_of

    batch = jax.ShapeDtypeStruct(
        (global_batch, seq_len + 1), jnp.int32, sharding=rt.batch_sharding
    )
    with persistent_cache_off():
        return rt.train_step.lower(abstract_state_of(rt), batch).compile().as_text()


def phase_train(*, model_size: str, num_layers: int, seq_len: int,
                global_batch: int, iters: int, seed: int = 0,
                overrides: Sequence[str] = ()) -> Dict[str, Any]:
    """A few steps on synthetic tokens through ``initialize_galvatron`` +
    ``trainer.train`` — exactly what ``cli train`` runs.  ``overrides`` are
    extra train flags (the tests shrink the widths with them)."""
    import jax

    from galvatron_tpu.core.arguments import initialize_galvatron
    from galvatron_tpu.core.trainer import train

    argv = [
        "--model_size", model_size, "--num_layers", str(num_layers),
        "--seq_length", str(seq_len),
        "--global_train_batch_size", str(global_batch),
        "--mixed_precision", "bf16", "--attn_impl", "auto",
        "--train_iters", str(iters), "--check_loss", "1", "--seed", str(seed),
        *overrides,
    ]
    say("train: python -m galvatron_tpu.cli train " + " ".join(argv))
    ns = initialize_galvatron("train", argv)
    out = train(ns)
    rt, losses = out["runtime"], out["losses"]
    cfg = rt.cfg
    require(len(losses) == iters, f"expected {iters} losses, got {len(losses)}")
    require(all(math.isfinite(x) for x in losses), f"non-finite loss in {losses}")
    ln_v = math.log(cfg.vocab_size)
    require(abs(losses[0] - ln_v) <= 1.0,
            f"first loss {losses[0]:.4f} is not within 1.0 of ln({cfg.vocab_size}) = {ln_v:.2f}")
    text = compiled_step_text(rt, global_batch, seq_len)
    dev = rt.mesh.devices.flat[0]
    n_dev = int(rt.mesh.devices.size)
    stats = dev.memory_stats() or {}
    iter_ms = out["iter_ms"]
    rep = {
        "losses": losses, "iter_ms": iter_ms, "attn_impl": cfg.attn_impl,
        "kernel_calls": text.count("tpu_custom_call"),
        "peak_bytes": stats.get("peak_bytes_in_use"),
        "widths": (cfg.hidden_size, cfg.num_heads, cfg.ffn_dim, cfg.vocab_size),
    }
    say(f"train: model {model_size} h={cfg.hidden_size} heads={cfg.num_heads} "
        f"ffn={cfg.ffn_dim} vocab={cfg.vocab_size} seq={seq_len} "
        f"num_layers={cfg.num_layers} global_batch={global_batch} "
        f"attn_impl={cfg.attn_impl}")
    say("train: losses " + " ".join(f"{x:.4f}" for x in losses)
        + f" (ln vocab = {ln_v:.2f})")
    gib = lambda k: f"{stats[k] / 2**30:.2f} GiB" if stats.get(k) else "not reported"  # noqa: E731
    # on the v5e runtime peak_bytes_in_use counts live arrays (the train
    # state); the step program's temp shows under peak_bytes_reserved
    say(f"train: step program holds tpu_custom_call x{rep['kernel_calls']}; "
        f"peak device memory: peak_bytes_in_use {gib('peak_bytes_in_use')}, "
        f"peak_bytes_reserved {gib('peak_bytes_reserved')}")
    say(f"train: smoke output (not a benchmark): step {iter_ms:.1f} ms, "
        f"{global_batch * seq_len / (iter_ms / 1e3):.0f} tokens/s "
        f"(mean of {iters - 1} steps after the first, host clock around "
        f"block_until_ready) on {dev.platform} {dev.device_kind} x{n_dev}")
    return rep


# ---------------------------------------------------------------------------
# phase: the multi-chip plans (--chips 4)
# ---------------------------------------------------------------------------


def multichip_plans(num_layers: int):
    """name -> (HybridParallelConfig, attn_impl), reference plan first."""
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy

    half = num_layers // 2
    return {
        "zero3_dp4_xla": (
            HybridParallelConfig.uniform(
                num_layers, tp=1, dp_type="zero3", vocab_tp=1,
                embed_dp_type="zero3", mixed_precision="bf16",
            ),
            "xla",
        ),
        "pp2_tp2_sp_1f1b_flash": (
            HybridParallelConfig(
                pp=2, layer_strategies=[LayerStrategy(tp=2, sp=True)] * num_layers,
                chunks=2, pipeline_type="pipedream_flush", vocab_tp=2,
                mixed_precision="bf16",
            ),
            "flash",
        ),
        "hetero_tp2sp_tp1_zero3_flash": (
            HybridParallelConfig(
                pp=1,
                layer_strategies=[LayerStrategy(tp=2, dp_type="zero3", sp=True)] * half
                + [LayerStrategy(tp=1, dp_type="zero3")] * (num_layers - half),
                vocab_tp=2, mixed_precision="bf16",
            ),
            "flash",
        ),
    }


def phase_multichip(devices, *, cfg, global_batch: int, seq_len: int,
                    steps: int, seed: int = 0) -> Dict[str, Dict[str, Any]]:
    """Train ``steps`` steps under each plan of :func:`multichip_plans` on
    ``devices`` (same init key, same batches), one runtime at a time, and
    hold the pipeline and the layer-heterogeneous plan to the plain one.
    "Same seed" means the same weights: the first plan initialises from the
    key and the others load a host copy of exactly those parameters."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from galvatron_tpu.core.checkpoint import abstract_state_of
    from galvatron_tpu.core.dataloader import build_dataloader
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.parallel.hybrid import build_runtime
    from galvatron_tpu.parallel.mesh import build_mesh

    devices = list(devices)
    require(len({d.id for d in devices}) == len(devices) == 4,
            f"the multi-chip phase wants 4 distinct devices, got {devices}")
    loader = build_dataloader(cfg, global_batch, seq_len, seed=seed)
    batches = [np.asarray(next(loader)) for _ in range(steps)]
    reports: Dict[str, Dict[str, Any]] = {}
    flat = None  # host copy of the reference plan's initial weights
    for name, (hp, impl) in multichip_plans(cfg.num_layers).items():
        mesh, axes = build_mesh(pp=hp.pp, devices=devices)
        require({d.id for d in mesh.devices.flat} == {d.id for d in devices},
                f"{name}: mesh does not cover the 4 devices")
        rt = build_runtime(
            cfg.replace(attn_impl=impl), hp, mesh=mesh, axes=axes,
            adam=AdamConfig(lr=1e-4, grad_clip=1.0),
            global_batch_size=global_batch, seq_len=seq_len,
        )
        batch_abs = jax.ShapeDtypeStruct(
            (global_batch, seq_len + 1), jnp.int32, sharding=rt.batch_sharding
        )
        t0 = time.perf_counter()
        # the executable compiled here is the one the steps below run
        step = rt.train_step.lower(abstract_state_of(rt), batch_abs).compile()
        compile_s = time.perf_counter() - t0
        text = step.as_text()
        if flat is None:
            # the reference plan (pp=1: its params ARE the flat tree) draws
            # the weights; the pipeline engine stacks and seeds its own init
            # differently, so every later plan starts from this host copy
            state = rt.init_state(jax.random.key(seed))
            flat = jax.tree.map(np.asarray, state["params"])
        else:
            state = rt.init_state_from(flat)
        losses, step_ms = [], []
        for b in batches:
            t0 = time.perf_counter()
            state, loss = step(state, rt.shard_batch(b))
            losses.append(float(jax.block_until_ready(loss)))
            step_ms.append((time.perf_counter() - t0) * 1e3)
        require(all(math.isfinite(x) for x in losses), f"{name}: non-finite loss {losses}")
        param_bytes = {d.id: 0 for d in devices}
        for leaf in jax.tree.leaves(state["params"]):
            for sh in leaf.addressable_shards:
                param_bytes[sh.device.id] += sh.data.nbytes
        require(all(v > 0 for v in param_bytes.values()),
                f"{name}: a device holds no parameter shard: {param_bytes}")
        stats = [d.memory_stats() or {} for d in devices]
        in_use = [st.get("bytes_in_use") for st in stats]
        rep = {
            "losses": losses, "param_bytes": param_bytes, "bytes_in_use": in_use,
            "ops": {op: text.count(op) for op in (
                "tpu_custom_call", "collective-permute", "all-gather",
                "reduce-scatter", "all-reduce")},
        }
        reports[name] = rep
        say(f"plan {name}: mesh {dict(mesh.shape)} compile {compile_s:.1f} s; "
            f"losses " + " ".join(f"{x:.4f}" for x in losses))
        say(f"plan {name}: program holds "
            + ", ".join(f"{k} x{v}" for k, v in rep["ops"].items()))
        say(f"plan {name}: per-device param MiB "
            + " ".join(f"{v / 2**20:.0f}" for v in param_bytes.values())
            + "; bytes_in_use GiB "
            + " ".join("n/a" if v is None else f"{v / 2**30:.2f}" for v in in_use))
        say(f"plan {name}: memory_stats[0] {json.dumps(stats[0], sort_keys=True)}")
        say(f"plan {name}: smoke output (not a benchmark): median step "
            f"{statistics.median(step_ms[1:] or step_ms):.1f} ms over "
            f"{max(1, len(step_ms) - 1)} steps after the first, on "
            f"{devices[0].platform} {devices[0].device_kind} x{len(devices)}")
        # free this runtime's state before the next plan is built
        del state, step, rt
        gc.collect()

    names = list(reports)
    ref = reports[names[0]]["losses"]
    for name in names[1:]:
        got = reports[name]["losses"]
        diff = max(abs(a - b) for a, b in zip(got, ref))
        say(f"plan {name}: max |loss - {names[0]}| = {diff:.3e} "
            f"(tolerance rtol {BF16_LOSS_TOL['rtol']:g})")
        require(bool(np.allclose(got, ref, **BF16_LOSS_TOL)),
                f"{name}: losses {got} disagree with {names[0]} {ref}")
    pipe, hetero = reports[names[1]]["ops"], reports[names[2]]["ops"]
    require(pipe["collective-permute"] > 0,
            f"{names[1]}: no collective-permute in the compiled step")
    require(hetero["all-gather"] + hetero["reduce-scatter"] > 0,
            f"{names[2]}: no all-gather/reduce-scatter in the compiled step")
    return reports


# ---------------------------------------------------------------------------
# main: the real sizes and the device check
# ---------------------------------------------------------------------------


def tpu_devices(want: int):
    import jax

    devs = jax.devices()
    d0 = devs[0]
    say(f"device: platform={d0.platform} kind={d0.device_kind} count={len(devs)} "
        f"jax={jax.__version__}")
    require(d0.platform == "tpu",
            f"needs a TPU, JAX reports platform {d0.platform!r}; there is no CPU fallback")
    require(len(devs) >= want, f"needs {want} chips, JAX reports {len(devs)}")
    return devs[:want]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run the multi-chip plans (and only them)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from galvatron_tpu.aot.cache import enable_persistent_cache, resolve_compile_cache_dir
    from galvatron_tpu.models.modeling import PRESETS

    devices = tpu_devices(args.chips)
    say(f"compile cache: {enable_persistent_cache(resolve_compile_cache_dir())}")
    width = PRESETS["llama-7b"]
    if args.chips == 4:
        layers = 4
        say(f"cut: llama-7b published widths (h={width.hidden_size} heads={width.num_heads} "
            f"ffn={width.ffn_dim} vocab={width.vocab_size}), num_layers cut "
            f"{width.num_layers} -> {layers}; global batch 8, seq 2048")
        reports = phase_multichip(
            devices, cfg=width.replace(num_layers=layers, max_seq_len=2048),
            global_batch=8, seq_len=2048, steps=4, seed=args.seed,
        )
        for name, rep in reports.items():
            if "flash" in name:
                require(rep["ops"]["tpu_custom_call"] > 0,
                        f"{name}: the compiled step holds no Pallas kernel")
            in_use = rep["bytes_in_use"]
            require(all(in_use), f"{name}: memory_stats() not reported: {in_use}")
            require(max(in_use) <= 4 * min(in_use),
                    f"{name}: device memory is not spread over the chips: {in_use}")
    else:
        layers = 2
        say(f"cut: llama-7b published widths (h={width.hidden_size} heads={width.num_heads} "
            f"ffn={width.ffn_dim} vocab={width.vocab_size}), num_layers cut "
            f"{width.num_layers} -> {layers} (one 16 GB chip, fp32 params + Adam); "
            "global batch 4, seq 2048")
        for rep in phase_kernel_parity(batch=4, seq=2048, heads=width.num_heads,
                                       kv_heads=8, head_dim=width.head_dim,
                                       seed=args.seed):
            require(rep["kernel_calls"] > 0,
                    f"{rep['case']}: the program holds no tpu_custom_call")
        rep = phase_train(model_size="llama-7b", num_layers=layers, seq_len=2048,
                          global_batch=4, iters=6, seed=args.seed)
        require(rep["attn_impl"] == "flash",
                f"--attn_impl auto resolved to {rep['attn_impl']!r} on a TPU")
        require(rep["kernel_calls"] > 0, "the train step that ran holds no Pallas kernel")
        require(rep["widths"] == (4096, 32, 11008, 32000), f"widths moved: {rep['widths']}")
        require(bool(rep["peak_bytes"]) and rep["peak_bytes"] < HBM_V5E_GIB * 2**30,
                f"peak device memory {rep['peak_bytes']}")
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
