"""Benchmark: LLaMA-7B-shape per-layer times + memory-constrained batch.

Prints one JSON line per metric; the HEADLINE (forward) metric is printed
LAST so single-line consumers keep parsing the same number:

  llama7b_shape_fwdbwd_ms_per_layer_per_sample_bf16 — fwd+bwd train-step
    time per layer per sample (guards the flash combined-backward's -9.3%
    train-step win, which the forward-only headline cannot see);
  llama7b_rep_max_feasible_per_device_batch_tp2zero3sp (--memory) — the
    largest per-device batch whose tp2+zero3+sp train step fits the v5e
    16 GB HBM budget at the 7B-representative shape, from the real TPU
    compiler's buffer assignment (topology AOT, no chips needed), plus
    tokens/s at that batch derived from the measured fwd+bwd number —
    the memory→batch→throughput metric the mlp_recompute policy moves;
  llama7b_shape_fwd_ms_per_layer_per_sample_bf16 — the headline.

The reference ships no absolute end-to-end numbers (BASELINE.md); its
concrete per-layer artifact is 4.64 ms forward per layer per sample for the
LLaMA-7B shape (h=4096, 32 heads, seq 2048) in bf16 on one A100 (reference:
models/llama_hf/configs/computation_profiling_bf16_hidden4096_head32_
seqlen2048.json:4). We measure the same quantity on one TPU chip with the
Pallas flash-attention path, by the same layer-count difference method the
reference profiler uses. vs_baseline = reference_ms / measured_ms (>1 ⇒
faster per layer than the reference's A100 measurement). The fwd+bwd
baseline uses the reference's bwd = 2x fwd convention
(galvatron/core/cost_model.py:190-191): 3 x 4.64 ms.

Flags: --memory runs the (slow, topology-AOT) feasible-batch probe;
--recovery runs the host-loss recovery drill (kill-host chaos scenario under
the elastic supervisor) and emits recovery_mttr_ms + recovery_steps_lost;
--smoke shrinks shapes so CI can assert the metric lines exist on CPU.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REF_MS_PER_LAYER_PER_SAMPLE = 4.64
REF_FWDBWD_MS_PER_LAYER_PER_SAMPLE = 3.0 * REF_MS_PER_LAYER_PER_SAMPLE


def make_window(cfg, bsz, seq, iters=6, train=False):
    """One-dispatch timing window of ``iters`` chained forwards (or fwd+bwd
    when ``train``).

    The whole window runs as ONE dispatch (a ``lax.scan`` whose carry makes
    every iteration data-dependent on the last — XLA cannot fold or reorder
    it), so a busy host cannot starve the device between iterations: per-iter
    Python dispatch is exactly the contention artifact that inflated
    driver-captured numbers by ~0.4 ms/layer/sample.
    Returns a zero-arg callable: one timed window in ms/iteration."""
    from galvatron_tpu.models import modeling

    params = modeling.init_model_params(jax.random.key(0), cfg)
    tokens = jnp.zeros((bsz, seq), jnp.int32)

    def fwd(params, tokens, c):
        x = modeling.embed(tokens, params, cfg)
        # the tiny carry-dependent bias chains iterations without touching
        # the math at bf16 precision
        x = x + c.astype(x.dtype)
        cos_sin = modeling.rope_tables(cfg, seq)
        for lp in params["layers"]:
            x = modeling.decoder_layer(x, lp, cfg, cos_sin, None)
        return jnp.sum(x.astype(jnp.float32))

    if train:
        # fwd+bwd through the same layer stack: grad wrt params makes every
        # layer's backward run (dw + dx), the train-step shape minus the
        # optimizer (which the layer-count difference cancels anyway)
        def step(params, tokens, c):
            loss, grads = jax.value_and_grad(fwd)(params, tokens, c)
            acc = sum(
                jnp.sum(g.astype(jnp.float32)) for g in jax.tree.leaves(grads)
            )
            return loss + acc * 1e-30

        body_fn = step
    else:
        body_fn = fwd

    @jax.jit
    def window(params, tokens):
        def body(c, _):
            out = body_fn(params, tokens, c * 1e-30)
            return out * 1e-30, None

        c, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), None, length=iters)
        return c

    _ = float(window(params, tokens))  # compile + sync

    def run():
        t0 = time.perf_counter()
        _ = float(window(params, tokens))
        return (time.perf_counter() - t0) / iters * 1000.0

    return run


def layer_diff_ms(base, bsz, seq, l1, l2, rounds=5, train=False):
    """Median per-layer per-sample ms by the paired layer-count difference.

    PAIRED rounds: each round times an adjacent (L1, L2) window pair, so
    chip-state drift over the run cannot bias the layer difference (the
    chip drifts on minutes-to-hours scales; an unpaired all-L1-then-all-L2
    ordering folds that drift straight into t2 - t1). MEDIAN over the
    per-round differences is robust to both drift (the pairing) and
    asymmetric contention spikes (a positive spike on the small window
    SHRINKS that round's diff, so a min would seek corrupted rounds)."""
    w1 = make_window(base.replace(num_layers=l1), bsz, seq, train=train)
    w2 = make_window(base.replace(num_layers=l2), bsz, seq, train=train)
    diffs = []
    for _ in range(rounds):
        t1 = w1()
        t2 = w2()
        diffs.append((t2 - t1) / (l2 - l1) / bsz)
    return float(np.median(diffs))


# environment provenance stamped into EVERY metric line: overlap numbers are
# meaningless without knowing which XLA flags / jax / chip produced them, and
# the driver archives bench output long after the run env is gone. Populated
# once in main() (after any XLA_FLAGS mutation the run performs).
_ENV: dict = {}


def _env_provenance() -> dict:
    import os

    try:
        kind = jax.devices()[0].device_kind
    except Exception:
        kind = "unknown"
    return {
        "jax_version": jax.__version__,
        "device_kind": kind,
        "num_devices": jax.device_count(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }


def emit(metric, value, unit, **extra):
    print(json.dumps(
        {"metric": metric, "value": value, "unit": unit, **_ENV, **extra}
    ))


def memory_metrics(smoke: bool):
    """Memory-constrained feasible batch at the 7B-representative shape
    (h=2048/L4/s2048/v8192 — the fidelity shape whose tp2+zero3+sp cell the
    activation-memory work targets), measured against the REAL TPU
    compiler's buffer assignment via the device-less v5e:2x4 topology.
    Emits the max per-device batch under the 16 GB HBM budget and tokens/s
    at that batch (derived from a fwd+bwd layer-diff measured at THIS rep
    shape — the memory win converts to throughput linearly in batch).
    Uses the xla attention channel: the buffer accounting is attention-impl
    independent (BASELINE.md round 6) and Mosaic AOT lowering SIGILLs —
    uncatchably — on some sandboxed hosts, which would cost the headline.
    Skips (with a skipped marker) where topology AOT is unavailable."""
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
    from galvatron_tpu.models.modeling import ModelConfig
    from galvatron_tpu.search.memory_fidelity import measured_train_mb

    seq = 256 if smoke else 2048
    rep = ModelConfig(
        vocab_size=8192, hidden_size=2048, num_layers=4, num_heads=16,
        max_seq_len=seq, dtype=jnp.bfloat16, attn_impl="xla",
    )
    hp = HybridParallelConfig(
        layer_strategies=[LayerStrategy(tp=2, dp_type="zero3", sp=True)] * 4,
        vocab_tp=2, mixed_precision="bf16",
    )
    budget_mb = 16384.0 * 0.92  # v5e HBM minus runtime headroom
    dp = 4  # world 8 / tp 2
    feasible = 0
    # step the global batch by 8 (= +2 per device): power-of-two doubling is
    # too coarse to resolve a ~10-15% memory win at the feasibility boundary
    bsz = 16
    while bsz <= (32 if smoke else 512):
        m = measured_train_mb(rep, hp, bsz, seq=seq)
        if m is None:
            emit(
                "llama7b_rep_max_feasible_per_device_batch_tp2zero3sp",
                0, "samples", skipped="topology AOT unavailable",
            )
            return
        if m["total_mb"] > budget_mb:
            break
        feasible = bsz
        bsz += 8
    emit(
        "llama7b_rep_max_feasible_per_device_batch_tp2zero3sp",
        feasible // dp, "samples",
        global_bsz=feasible, budget_mb=budget_mb,
    )
    if feasible:
        # fwd+bwd per-layer time measured at THE REP SHAPE itself (h=2048 —
        # the 7B-shape headline number is ~4x heavier per layer and must not
        # be reused here); cheap at this width
        rep_fwdbwd = layer_diff_ms(
            rep.replace(attn_impl="flash" if jax.default_backend() != "cpu" else "xla"),
            min(4, feasible // dp), seq, 2, 6,
            rounds=2 if smoke else 3, train=True,
        )
        # per-device step ms at the feasible batch (layers per device = 4 /
        # 1 stage; tp=2 halves per-device layer work — stated as derived
        # from a tp=1 measurement, not a direct tp2 measurement)
        step_ms = rep_fwdbwd * rep.num_layers / 2.0 * (feasible / dp)
        tokens_per_s = (feasible / dp) * seq / (step_ms / 1000.0)
        emit(
            "llama7b_rep_tokens_per_s_at_max_feasible_batch",
            round(tokens_per_s, 1), "tokens/s",
            derived_from="rep-shape fwdbwd layer-diff x max feasible batch",
        )


def loader_metrics(smoke: bool):
    """Input-path throughput: tokens/s through the FULL production data
    pipeline (sharded corpora → weighted mixture → first-fit packing →
    prefetch thread → device_put), measured loader-only so input-side
    regressions are attributable separately from model compute. Two synthetic
    short-document corpora are built in a temp dir (the mixed-short-document
    shape packing exists for); the emitted value is NON-PAD tokens/s with the
    realized packing efficiency attached."""
    import os
    import tempfile

    import jax.numpy as jnp

    from galvatron_tpu.data import build_data_pipeline, write_sharded_dataset

    seq = 128 if smoke else 1024
    bsz = 8 if smoke else 32
    n_batches = 10 if smoke else 50
    d = tempfile.mkdtemp(prefix="galvatron_bench_data_")
    rng = np.random.RandomState(0)
    for name, n_docs in (("web", 600), ("books", 400)):
        write_sharded_dataset(
            os.path.join(d, name),
            [list(rng.randint(1, 30000, rng.randint(24, seq))) for _ in range(n_docs)],
            32000,
        )
    mixture = f"{os.path.join(d, 'web')}=0.7,{os.path.join(d, 'books')}=0.3"

    class _Cfg:
        image_size = 0
        objective = "clm"
        enc_layers = 0
        vocab_size = 32000

    pipe = build_data_pipeline(
        _Cfg, bsz, seq, seed=1234, mixture=mixture, pack=True,
        prefetch_depth=2, put_fn=jnp.asarray,
    )
    try:
        next(pipe)  # warm the prefetch thread before the timed window
        t0 = time.perf_counter()
        nonpad = raw = 0
        for _ in range(n_batches):
            batch = next(pipe)
            batch.block_until_ready()
            nonpad += pipe.last_meta["nonpad_tokens"]
            raw += pipe.last_meta["raw_tokens"]
        dt = time.perf_counter() - t0
    finally:
        pipe.close()
    emit(
        "data_pipeline_loader_tokens_per_s",
        round(nonpad / dt, 1), "tokens/s",
        # AGGREGATE fill over the window, not the last batch's — a single
        # unlucky tail batch must not flake the CI threshold
        packing_efficiency=round(nonpad / raw, 4) if raw else 0.0,
        batch_size=bsz, seq_len=seq, prefetch_depth=2,
    )


def compile_metrics(smoke: bool):
    """Cold-start trajectory (galvatron_tpu/aot): cold vs warm compile_ms
    for the default train_step and the serving decode step, measured through
    the real AOT warmup path against a fresh persistent compile cache. The
    cold number is what a trainer start / serving cold-start pays today; the
    warm number is what the same start pays after `cli warmup` (or any prior
    run) populated the cache — the delta is the win BENCH_r09 starts
    tracking. Tiny shapes: compile time scales with program structure, and
    the cold/warm RATIO is the signal, not absolute ms."""
    import shutil
    import tempfile

    import jax.numpy as jnp

    from galvatron_tpu.aot import warmup as aot_warmup
    from galvatron_tpu.aot.cache import ArtifactStore, enable_persistent_cache
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import ModelConfig

    # the section needs a throwaway cache dir for a true cold measurement;
    # hand the process-wide cache back exactly as found afterwards (an
    # operator's JAX_COMPILATION_CACHE_DIR must serve the later sections)
    prev_dir = getattr(jax.config, "jax_compilation_cache_dir", None)
    prev_entry = getattr(jax.config, "jax_persistent_cache_min_entry_size_bytes", None)
    prev_time = getattr(jax.config, "jax_persistent_cache_min_compile_time_secs", None)
    d = tempfile.mkdtemp(prefix="galvatron_bench_aot_")
    try:
        store = ArtifactStore(enable_persistent_cache(d))
        cfg = ModelConfig(
            vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
            ffn_dim=512, max_seq_len=64 if smoke else 128, dtype=jnp.bfloat16,
            attn_impl="xla",  # compile-time metric: kernel-impl independent
        )
        hp = HybridParallelConfig.uniform(cfg.num_layers)
        include = ("train_step", "serving_decode")

        def sweep():
            return {
                r["program"]: r
                for r in aot_warmup.warmup_plan(
                    cfg, hp, global_bsz=4, store=store, include=include,
                    verbose=False,
                )
            }

        cold, warm = sweep(), sweep()
    finally:
        try:
            jax.config.update("jax_compilation_cache_dir", prev_dir)
            if prev_entry is not None:
                jax.config.update(
                    "jax_persistent_cache_min_entry_size_bytes", int(prev_entry)
                )
            if prev_time is not None:
                jax.config.update(
                    "jax_persistent_cache_min_compile_time_secs", float(prev_time)
                )
            from jax._src import compilation_cache as _cc

            _cc.reset_cache()
        except Exception:
            pass
        shutil.rmtree(d, ignore_errors=True)
    for prog in include:
        c, w = cold.get(prog), warm.get(prog)
        if not c or c["status"] != "compiled":
            emit(f"compile_time_{prog}_ms", 0, "ms",
                 skipped=(c or {}).get("error", "not built"))
            continue
        extra = {}
        if w and w["status"] == "compiled":
            extra = {
                "warm_ms": w["compile_ms"],
                "warm_speedup": round(c["compile_ms"] / max(w["compile_ms"], 1e-3), 2),
                "warm_cache_hit": bool(w.get("cache_hit")),
            }
        emit(f"compile_time_{prog}_ms", c["compile_ms"], "ms", **extra)


def _overlap_step_ms(cfg, hp, bsz, seq, iters):
    """Median-free short window over a real build_runtime train step —
    the on/off arms share shape and data, so constant overheads cancel in
    the delta. Returns (ms/step, last loss)."""
    from galvatron_tpu.parallel.hybrid import build_runtime

    rt = build_runtime(cfg, hp, global_batch_size=bsz, seq_len=seq)
    state = rt.init_state(jax.random.key(0))
    batch = rt.shard_batch(
        np.random.RandomState(0)
        .randint(1, cfg.vocab_size, (bsz, seq + 1))
        .astype(np.int32)
    )
    state, loss = rt.train_step(state, batch)  # compile + warm
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = rt.train_step(state, batch)
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) / iters * 1000.0, float(loss)


def _overlap_pair(cfg, hp_off, hp_on, metric, bsz, seq, iters, **tags):
    """Time the paired off/on arms and emit one metric line: value = the
    overlap-ON step time, extras carry the off arm and the delta."""
    off_ms, off_loss = _overlap_step_ms(cfg, hp_off, bsz, seq, iters)
    on_ms, on_loss = _overlap_step_ms(cfg, hp_on, bsz, seq, iters)
    extra = dict(tags)
    extra.update(
        off_ms=round(off_ms, 4),
        delta_ms=round(off_ms - on_ms, 4),
        speedup=round(off_ms / on_ms, 4) if on_ms > 0 else 0.0,
        # the decomposition must not change the math: both arms see the
        # same data, so their losses agree to dtype tolerance
        loss_abs_diff=round(abs(off_loss - on_loss), 6),
    )
    emit(metric, round(on_ms, 4), "ms", **extra)
    return {"on_ms": on_ms, "off_ms": off_ms, **extra}


def tp_overlap_metrics(smoke: bool):
    """Collective-matmul on/off (DESIGN.md "Overlap"): the same uniform
    tp+sp train step with the ops/collective_matmul decomposition on vs
    off. On single-device hosts (CI CPU) both arms take the plain-einsum
    fallback and the delta reads ~0 — the line still emits."""
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import ModelConfig

    world = jax.device_count()
    tp = world if world & (world - 1) == 0 else 1
    seq = 128 if smoke else 2048
    bsz = max(2, world) if smoke else max(8, world)
    cfg = ModelConfig(
        vocab_size=512 if smoke else 32000,
        hidden_size=256 if smoke else 4096,
        num_layers=2, num_heads=4 if smoke else 32,
        ffn_dim=1024 if smoke else 11008, max_seq_len=seq,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, attn_impl="xla",
    )
    mk = lambda ov: HybridParallelConfig.uniform(
        cfg.num_layers, tp=tp, sp=(tp > 1), tp_overlap=ov,
    )
    return _overlap_pair(
        cfg, mk(False), mk(True), "overlap_collective_matmul_train_step_ms",
        bsz, seq, iters=3 if smoke else 10, tp=tp,
    )


def grad_overlap_metrics(smoke: bool):
    """Async ZeRO gradient overlap on/off: uniform zero2 train step with
    per-layer backward reduce-scatter pinning (sharding.overlap_grad_sync)
    on vs off. Single-device arms are both no-ops (delta ~0, line emits)."""
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import ModelConfig

    world = jax.device_count()
    seq = 128 if smoke else 2048
    bsz = max(2, world) if smoke else max(8, world)
    cfg = ModelConfig(
        vocab_size=512 if smoke else 32000,
        hidden_size=256 if smoke else 4096,
        num_layers=2, num_heads=4 if smoke else 32,
        ffn_dim=1024 if smoke else 11008, max_seq_len=seq,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, attn_impl="xla",
    )
    mk = lambda ov: HybridParallelConfig.uniform(
        cfg.num_layers, dp_type="zero2", grad_overlap=ov,
    )
    _overlap_pair(
        cfg, mk(False), mk(True), "overlap_grad_sync_train_step_ms",
        bsz, seq, iters=3 if smoke else 10, world=world,
    )


def recovery_metrics(smoke: bool):
    """Host-loss recovery drill (--recovery): the kill-host chaos scenario
    end-to-end under the elastic supervisor — the disk save is blocked by an
    injected storage outage so the step-2 state lives ONLY in a peer store's
    RAM, then SIGKILL mid-step 3 — and the two numbers the preemption work
    is judged by, read from the supervisor's own accounting:

      recovery_mttr_ms — child death → first post-restore step committed
        (restart + peer restore + recompile), the cost the free-restart path
        keeps flat;
      recovery_steps_lost — fault step minus the replica's resume step; the
        replication invariant is steps_lost < save_interval, which a
        disk-only cadence cannot give when the disk is down.

    Tiny fixed shape regardless of --smoke: the metric is a recovery-path
    drill, not a throughput measurement — model size only moves the
    recompile slice of MTTR."""
    import os
    import shutil
    import subprocess
    import tempfile

    fault_step, save_interval = 3, 2
    d = tempfile.mkdtemp(prefix="galvatron_bench_recovery_")
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        GALVATRON_FAULTS=f"storage_outage=1,kill_host_mid_step={fault_step}",
        GALVATRON_FAULTS_WORLD="2",
    )
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(d, "jax_cache"))
    try:
        subprocess.run(
            [sys.executable, "-m", "galvatron_tpu.cli", "run-elastic",
             "--model_size", "llama-0.3b", "--num_layers", "2",
             "--hidden_size", "32", "--num_heads", "2", "--ffn_dim", "64",
             "--vocab_size", "128", "--seq_length", "16",
             "--global_train_batch_size", "8", "--mixed_precision", "fp32",
             "--train_iters", "4", "--save", os.path.join(d, "ckpt"),
             "--save_interval", str(save_interval),
             "--max_restarts", "3", "--restart_backoff_s", "0.1",
             "--step_timeout_s", "30", "--replan_search_space", "dp+tp",
             "--peer_replicate", "3"],
            env=env, check=True, capture_output=True, text=True, timeout=360,
        )
        with open(os.path.join(d, "ckpt", "elastic_events.jsonl")) as f:
            evs = [json.loads(line) for line in f]
        ro = next(e for e in evs if e["event"] == "recovery_observed")
        assert ro["source"] == "peer", ro
        emit(
            "recovery_mttr_ms", round(float(ro["mttr_ms"]), 1), "ms",
            source=ro["source"], fault="storage_outage+kill_host_mid_step",
            save_interval=save_interval,
        )
        emit(
            "recovery_steps_lost", fault_step - int(ro["step"]), "steps",
            fault_step=fault_step, resume_step=int(ro["step"]),
            save_interval=save_interval,
        )
    finally:
        shutil.rmtree(d, ignore_errors=True)


def main():
    from galvatron_tpu.models.modeling import ModelConfig

    smoke = "--smoke" in sys.argv
    _ENV.update(_env_provenance())
    bsz, seq = (2, 128) if smoke else (8, 2048)
    base = ModelConfig(
        vocab_size=512 if smoke else 32000,
        hidden_size=256 if smoke else 4096,
        num_layers=2,
        num_heads=4 if smoke else 32,
        ffn_dim=1024 if smoke else 11008,
        max_seq_len=seq,
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
        attn_impl="flash" if jax.default_backend() != "cpu" else "xla",
    )
    l1, l2 = 2, 6
    rounds = 2 if smoke else 5

    # cold-vs-warm compile FIRST (failure-isolated like every non-headline
    # section): BENCH_r09 starts the cold-start trajectory, and running it
    # before any other section means its cold numbers see a truly cold cache
    try:
        compile_metrics(smoke)
    except Exception as e:
        emit("compile_time_train_step_ms", 0, "ms",
             skipped=f"{type(e).__name__}: {e}"[:200])

    # loader-only input-path throughput (failure-isolated like every
    # non-headline section): BENCH_r08 starts the input-path trajectory
    try:
        loader_metrics(smoke)
    except Exception as e:
        emit(
            "data_pipeline_loader_tokens_per_s",
            0, "tokens/s", skipped=f"{type(e).__name__}: {e}"[:200],
        )

    # the fwd+bwd and memory sections must never cost the headline: any
    # failure here is reported as a skipped metric and the run continues
    fwdbwd = 0.0
    try:
        fwdbwd = layer_diff_ms(base, bsz, seq, l1, l2, rounds=rounds, train=True)
        emit(
            "llama7b_shape_fwdbwd_ms_per_layer_per_sample_bf16",
            round(fwdbwd, 4), "ms",
            vs_baseline=round(REF_FWDBWD_MS_PER_LAYER_PER_SAMPLE / fwdbwd, 4),
        )
    except Exception as e:
        emit(
            "llama7b_shape_fwdbwd_ms_per_layer_per_sample_bf16",
            0, "ms", skipped=f"{type(e).__name__}: {e}"[:200],
        )

    # overlap push (DESIGN.md "Overlap"): paired on/off deltas for the
    # collective-matmul decomposition and the async ZeRO grad reduce-scatter.
    # Failure-isolated PER SECTION — a tp_overlap regression must not cost
    # the grad-overlap line, and neither may cost the headline.
    tp_pair = None
    try:
        tp_pair = tp_overlap_metrics(smoke)
    except Exception as e:
        emit("overlap_collective_matmul_train_step_ms", 0, "ms",
             skipped=f"{type(e).__name__}: {e}"[:200])
    try:
        grad_overlap_metrics(smoke)
    except Exception as e:
        emit("overlap_grad_sync_train_step_ms", 0, "ms",
             skipped=f"{type(e).__name__}: {e}"[:200])

    if "--memory" in sys.argv:
        try:
            memory_metrics(smoke)
        except Exception as e:
            emit(
                "llama7b_rep_max_feasible_per_device_batch_tp2zero3sp",
                0, "samples", skipped=f"{type(e).__name__}: {e}"[:200],
            )

    # host-loss recovery drill (--recovery): failure-isolated like every
    # other non-headline section — a broken supervisor must not cost the
    # perf headline, it must show up as a skipped recovery metric
    if "--recovery" in sys.argv:
        try:
            recovery_metrics(smoke)
        except Exception as e:
            emit("recovery_mttr_ms", 0, "ms",
                 skipped=f"{type(e).__name__}: {e}"[:200])
            emit("recovery_steps_lost", -1, "steps",
                 skipped=f"{type(e).__name__}: {e}"[:200])

    fwd = layer_diff_ms(base, bsz, seq, l1, l2, rounds=rounds, train=False)

    # per-phase breakdown + utilization (obs/stepstats.py): the perf
    # trajectory starts with attribution — where a layer's time goes (fwd vs
    # bwd) and how far from the chip's peak it sits — not just a throughput
    # scalar. MFU uses model FLOPs; on hosts with no known peak (CPU) the
    # mfu fields are omitted rather than invented. Failure-isolated like the
    # other non-headline sections.
    try:
        from galvatron_tpu.obs import stepstats as ss

        flops_fwd = ss.layer_fwd_flops_per_token(base, seq) * seq  # /layer/sample
        peak = ss.peak_flops_per_device()
        extra = {"fwd_ms": round(fwd, 4), "fwdbwd_ms": round(fwdbwd, 4),
                 "flops_fwd_per_layer_per_sample": flops_fwd}
        if fwd > 0:
            extra["achieved_fwd_tflops"] = round(flops_fwd / (fwd / 1e3) / 1e12, 3)
            if peak:
                extra["mfu_fwd"] = round(flops_fwd / (fwd / 1e3) / peak, 4)
        if fwdbwd > 0 and fwd > 0:
            extra["bwd_ms"] = round(fwdbwd - fwd, 4)
            extra["bwd_over_fwd"] = round((fwdbwd - fwd) / fwd, 3)
            extra["achieved_fwdbwd_tflops"] = round(
                3.0 * flops_fwd / (fwdbwd / 1e3) / 1e12, 3
            )
            if peak:
                extra["mfu_fwdbwd"] = round(3.0 * flops_fwd / (fwdbwd / 1e3) / peak, 4)
        if peak:
            extra["peak_tflops_per_device"] = round(peak / 1e12, 1)
        emit("llama7b_shape_phase_breakdown", round(fwd, 4), "ms", **extra)
    except Exception as e:
        emit(
            "llama7b_shape_phase_breakdown",
            0, "ms", skipped=f"{type(e).__name__}: {e}"[:200],
        )

    # headline LAST: single-line consumers (the driver) parse the tail line.
    # The headline went stale once overlap work started landing: the recorded
    # number kept describing the flag-OFF arm while the shipped configuration
    # drifted. The emit now states its arm explicitly, and the moment the
    # overlap flags become shipped defaults (LayerStrategy().tp_overlap /
    # HybridParallelConfig().grad_overlap flipping True) the value is
    # RE-DERIVED from the measured overlap-on arm of this same run — the
    # tp_overlap pair, because collective-matmul is the only overlap that
    # touches the forward this metric times (grad overlap is backward-only)
    # — instead of silently repeating the flag-off measurement.
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy

    overlap_shipped = bool(
        LayerStrategy().tp_overlap or HybridParallelConfig().grad_overlap
    )
    headline = fwd
    extra = {"headline_arm": "overlap-off (shipped default)"}
    if overlap_shipped and tp_pair and tp_pair["off_ms"] > 0:
        ratio = tp_pair["on_ms"] / tp_pair["off_ms"]
        headline = fwd * min(1.0, ratio)
        extra = {
            "headline_arm": "overlap-on (shipped default)",
            "rederived_from": "overlap_collective_matmul_train_step_ms "
                              "on/off ratio, this run",
            "overlap_on_off_ratio": round(ratio, 4),
            "flag_off_ms": round(fwd, 4),
        }
    emit(
        "llama7b_shape_fwd_ms_per_layer_per_sample_bf16",
        round(headline, 4), "ms",
        vs_baseline=round(REF_MS_PER_LAYER_PER_SAMPLE / headline, 4),
        **extra,
    )


if __name__ == "__main__":
    main()
