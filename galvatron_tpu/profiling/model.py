"""Model profiler: per-layer compute time and memory.

Counterpart of the reference's launcher-based profiler (reference:
galvatron/core/profiler.py:194-401 — launches train_dist.py across
{layernum_min,max} x tp x ckpt via os.system, then differences the results).
Here no process launches are needed: the layernum-difference method runs two
jitted training programs in-process, and memory comes from XLA's compile-time
memory analysis instead of allocator snapshots:

  per-layer fwd ms  = (iter(L2) - iter(L1)) / (L2 - L1) / bsz / 3
  per-layer act MB  = (temp_bytes(L2) - temp_bytes(L1)) / (L2 - L1) / bsz

(the /3 removes the bwd≈2x fwd share from a full training step; the reference
separates fwd via profile hooks, core/profiler.py:133-171).

Parameter sizes are computed analytically from the model config.
"""

from __future__ import annotations

import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from galvatron_tpu.core.optim import AdamConfig
from galvatron_tpu.core.strategy import HybridParallelConfig
from galvatron_tpu.models import modeling
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.search.cost_model import ProfiledLayerType, ProfiledModelCosts

# Single source of truth for analytic parameter counts (MoE-aware: the
# expert-stack branch matters — a dense count here once made
# moe_expert_param_fraction exceed 1 and turned dense_mb negative in the
# cost model).
from galvatron_tpu.search import theoretical
from galvatron_tpu.search.theoretical import layer_param_count, other_param_count


def measure_strategy_ms(
    cfg: ModelConfig,
    hp,
    bsz: int,
    seq: Optional[int] = None,
    iters: int = 4,
    devices=None,
) -> float:
    """Measured wall time per training iteration of ``hp`` through the hybrid
    runtime's own train_step (windowed: one sync to open, one to close). The
    reference profiles through its real trainer the same way (train_dist.py
    --profile, core/profiler.py:194-240); a separate plain-model loop was
    ~10% slower than what training actually runs (no buffer donation,
    different loss plumbing), which skewed predicted-vs-measured fidelity."""
    from galvatron_tpu.parallel.hybrid import build_runtime
    from galvatron_tpu.parallel.mesh import build_mesh

    mesh, axes = build_mesh(pp=hp.pp, devices=devices)
    if cfg.objective == "cls":
        rt = build_runtime(
            cfg, hp, mesh=mesh, axes=axes, adam=AdamConfig(lr=1e-4),
            global_batch_size=bsz,
        )
        batch = jnp.zeros((bsz, cfg.sample_len + 1), jnp.int32)
    else:
        rt = build_runtime(
            cfg, hp, mesh=mesh, axes=axes, adam=AdamConfig(lr=1e-4),
            global_batch_size=bsz, seq_len=seq,
        )
        # match build_runtime's own seq resolution (seq_len or cfg.sample_len
        # — enc-dec samples are enc_seq + max_seq_len tokens)
        batch = jnp.zeros((bsz, (seq or cfg.sample_len) + 1), jnp.int32)
    batch = rt.shard_batch(batch)
    state = rt.init_state(jax.random.key(0))
    state, loss = rt.train_step(state, batch)  # compile
    _ = float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = rt.train_step(state, batch)
    _ = float(loss)  # host sync
    return (time.perf_counter() - t0) / iters * 1000.0


def _iter_time_ms(cfg: ModelConfig, bsz: int, seq: int, iters: int = 4) -> float:
    """Single-device trivial-strategy iteration time — the per-layer profile
    basis (tp=1, ddp, chunks=1 on ONE device)."""
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy

    hp = HybridParallelConfig(
        pp=1,
        layer_strategies=[LayerStrategy()] * cfg.total_layers,  # enc + dec
        chunks=1,
        vocab_tp=1,
        mixed_precision=_mp_of(cfg),
    )
    return measure_strategy_ms(cfg, hp, bsz, seq, iters, devices=jax.devices()[:1])


def _mp_of(cfg: ModelConfig) -> str:
    return {jnp.bfloat16: "bf16", jnp.float16: "fp16"}.get(cfg.dtype, "fp32")


def profile_vocab_costs(
    cfg: ModelConfig,
    bsz: int,
    vocab_tps: Optional[Sequence[int]] = None,
    seq: Optional[int] = None,
    iters: int = 4,
) -> Tuple[dict, dict, str]:
    """MEASURED embed+head+loss cost per vocab_tp as (slope ms/sample,
    const ms/iteration, precision): a ZERO-LAYER model on exactly vocab_tp
    devices (dp=1) runs precisely the computation the cost model's "other"
    terms price — embedding gather, head GEMM, (vocab-parallel) cross-
    entropy with its per-token scalar reductions, and the optimizer update
    on those params — with the runtime's real shardings. Two batch sizes
    (bsz, 2·bsz) separate the batch-linear share from the batch-independent
    one (the Adam update on V·h params dominates a zero-layer step at small
    batch, so a single-point linear scaling would grossly over-price large
    per-device batches). dp=1 keeps the dp-extent comm OUT of the
    measurement; other_time_cost adds it analytically for the search
    topology. Skips vocab_tp degrees the host cannot supply (>1 on a single
    chip) — those fall back to the analytic terms."""
    seq = seq or cfg.max_seq_len
    mp = _mp_of(cfg)
    if cfg.enc_layers > 0 or cfg.objective == "cls":
        return {}, {}, mp  # enc-dec / cls 'other' paths keep the analytic model
    if vocab_tps is None:
        # every power of two this host can supply — the search consumes the
        # fit only when ALL degrees its sweep can select are covered
        # (SearchEngine._vocab_use_measured), so a capped default would
        # silently disable measured pricing on larger hosts
        n = len(jax.devices())
        vocab_tps = [2 ** k for k in range(int(np.log2(n)) + 1)]
    cfg0 = cfg.replace(num_layers=0)
    slope, const = {}, {}
    for vt in vocab_tps:
        if vt > len(jax.devices()) or cfg.vocab_size % vt:
            continue
        hp = HybridParallelConfig(
            pp=1, layer_strategies=[], chunks=1, vocab_tp=vt, mixed_precision=mp
        )
        try:
            t1 = measure_strategy_ms(cfg0, hp, bsz, seq, iters, devices=jax.devices()[:vt])
            t2 = measure_strategy_ms(
                cfg0, hp, 2 * bsz, seq, iters, devices=jax.devices()[:vt]
            )
        except Exception:
            continue  # leave this degree to the analytic fallback
        m = max(0.0, (t2 - t1) / bsz)  # ms per sample-per-device
        slope[int(vt)] = float(m)
        const[int(vt)] = float(max(0.0, t1 - m * bsz))
    return slope, const, mp


def _temp_bytes(cfg: ModelConfig, bsz: int, seq: int) -> Optional[int]:
    """XLA-reported temporary (activation) bytes for a jitted loss+grad."""

    def f(params, batch):
        return jax.value_and_grad(lambda p: modeling.lm_loss(p, batch, cfg))(params)

    params = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    batch = jax.ShapeDtypeStruct((bsz, seq + 1), jnp.int32)
    try:
        compiled = jax.jit(f).lower(params, batch).compile()
        ma = compiled.memory_analysis()
        if ma is None:
            return None
        return int(ma.temp_size_in_bytes)
    except Exception:
        return None


def _temp_bytes_tp(cfg: ModelConfig, bsz: int, seq: int, tp: int) -> Optional[int]:
    """Per-device XLA temp bytes of the ACTUAL tp-sharded train step,
    compiled (not run) on ``tp`` local devices — the measured counterpart of
    the reference's per-tp memory profiling sweep (core/profiler.py:194-240
    launches real runs across tp degrees). Needs >= tp devices (a pod host);
    single-chip hosts fall back to the analytic ~1/tp curve."""
    if tp > len(jax.devices()):
        return None
    try:
        from galvatron_tpu.core.checkpoint import abstract_state_of
        from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
        from galvatron_tpu.parallel.hybrid import build_runtime
        from galvatron_tpu.parallel.mesh import build_mesh

        mesh, axes = build_mesh(pp=1, devices=jax.devices()[:tp])
        hp = HybridParallelConfig(
            pp=1,
            layer_strategies=[LayerStrategy(tp=tp)] * cfg.num_layers,
            chunks=1, vocab_tp=tp, mixed_precision=_mp_of(cfg),
        )
        rt = build_runtime(
            cfg, hp, mesh=mesh, axes=axes, adam=AdamConfig(lr=1e-4),
            global_batch_size=bsz, seq_len=seq,
        )
        abstract = abstract_state_of(rt)
        batch = jax.ShapeDtypeStruct(
            (bsz, seq + 1), jnp.int32, sharding=rt.batch_sharding
        )
        ma = rt.train_step.lower(abstract, batch).compile().memory_analysis()
        if ma is None:
            return None
        return int(ma.temp_size_in_bytes)
    except Exception:
        return None


def _act_fallback_mb(cfg: ModelConfig, S: int) -> float:
    """Analytic activation fallback (bf16): residuals + attn + mlp
    intermediates per layer per sample."""
    return S * cfg.hidden_size * (10 + 4 * cfg.ffn / cfg.hidden_size) * 2 / 1e6


def _maybe_save(costs: ProfiledModelCosts, out_prefix: Optional[str]) -> None:
    if out_prefix:
        from galvatron_tpu.utils.config_utils import save_profiled_model

        save_profiled_model(
            costs, f"{out_prefix}_computation.json", f"{out_prefix}_memory.json"
        )


# adaptive-layernum cap: profiling AT the target layer count removes the
# extrapolation bias of the (2,4) basis — the marginal per-layer iteration
# cost is NOT constant in L (measured h=2048/bsz 8, one process:
# 37.7 ms/layer at 2→4, 35.9 at 4→8, 48.1 at 8→12 as the model approaches
# HBM pressure) — but compile+measure time grows with L, so the upper point
# is capped; beyond it the difference method extrapolates as before.
_PROFILE_MAX_LAYERS = 12


def _default_layernums(total_layers: int) -> Tuple[int, int]:
    l2 = max(2, min(total_layers, _PROFILE_MAX_LAYERS))
    return max(1, l2 // 2), l2


def profile_model(
    cfg: ModelConfig,
    bsz: int = 8,
    seq: Optional[int] = None,
    layernums: Optional[Tuple[int, int]] = None,
    measure_time: bool = True,
    out_prefix: Optional[str] = None,
) -> ProfiledModelCosts:
    """Difference-method profile (reference: process_profiled_data,
    core/profiler.py:243-401). Writes reference-schema JSONs if out_prefix.

    ``layernums=None`` picks (total_layers//2, total_layers) capped at
    ``_PROFILE_MAX_LAYERS`` so models that fit are profiled at their real
    depth; an OOM at the adaptively-chosen sizes falls back to halved layer
    counts (explicitly-passed layernums are never silently overridden).
    Enc-dec profiles keep the fixed (2, 4) three-point basis of
    ``_profile_encdec_model`` — the adaptive depth scaling does not apply
    there yet."""
    if cfg.enc_layers > 0:
        if seq is not None:
            raise ValueError(
                "seq does not apply to enc-dec profiles (two sequence "
                "lengths); set cfg.enc_seq / cfg.max_seq_len instead"
            )
        return _profile_encdec_model(
            cfg, bsz, layernums or (2, 4), measure_time, out_prefix
        )
    if cfg.swin_depths:
        if seq is not None or layernums is not None:
            raise ValueError(
                "seq/layernums do not apply to swin profiles (the pyramid "
                "fixes per-section resolutions; the sweep varies section "
                "depths)"
            )
        return _profile_swin_model(cfg, bsz, measure_time, out_prefix)
    seq = seq or cfg.max_seq_len
    adaptive = layernums is None
    l1, l2 = layernums or _default_layernums(cfg.total_layers)

    if measure_time:
        t_cache: dict = {}

        def t_of(ln: int) -> float:
            if ln not in t_cache:
                t_cache[ln] = _iter_time_ms(cfg.replace(num_layers=ln), bsz, seq)
            return t_cache[ln]

        while True:
            try:
                t1, t2 = t_of(l1), t_of(l2)
                break
            except Exception as e:
                # only the ADAPTIVE basis falls back, and only on memory
                # exhaustion — explicit layernums and deterministic errors
                # surface to the caller
                oom = any(
                    m in str(e)
                    for m in ("RESOURCE_EXHAUSTED", "Ran out of memory", "OOM")
                )
                if not adaptive or not oom or l2 <= 2:
                    raise
                l2 = max(2, l2 // 2)
                l1 = max(1, l2 // 2)
        fwd_ms = max(1e-4, (t2 - t1) / (l2 - l1) / bsz / 3.0)
        other_ms = max(0.0, (t1 - fwd_ms * 3.0 * bsz * l1) / bsz / 3.0)
    else:
        fwd_ms, other_ms = 1.0, 0.1

    # MoE: MEASURE the expert-time fraction (the ep-shardable share of the
    # switch layer's time) by a two-point fit of the marginal layer time
    # over the expert FFN width — t(f) = a + b*f, expert share = b*f/(a+b*f);
    # the intercept a is the routing/sinkhorn/dispatch overhead that does
    # NOT shard by ep (the param-fraction proxy overstated the ep win by
    # pricing it as shardable). Measured on-chip (BASELINE.md round 5).
    moe_tfrac = None
    if measure_time and cfg.moe_experts > 0 and not cfg.moe_dropless:
        try:
            f1 = cfg.ffn
            f2 = max(256, (f1 // 4 + 255) // 256 * 256)
            if f2 < f1:
                cfg_small = cfg.replace(ffn_dim=f2)
                ts1 = _iter_time_ms(cfg_small.replace(num_layers=l1), bsz, seq)
                ts2 = _iter_time_ms(cfg_small.replace(num_layers=l2), bsz, seq)
                fwd_small = max(1e-4, (ts2 - ts1) / (l2 - l1) / bsz / 3.0)
                b_slope = (fwd_ms - fwd_small) / (f1 - f2)
                # a degenerate fit (non-positive slope: noise or a too-small
                # model) must fall back to the param proxy, not price EP as
                # zero benefit
                if b_slope > 0:
                    moe_tfrac = float(min(b_slope * f1 / fwd_ms, 0.99))
        except Exception:
            moe_tfrac = None  # leave the param-fraction proxy in place
    cfg1, cfg2 = cfg.replace(num_layers=l1), cfg.replace(num_layers=l2)

    b1, b2 = _temp_bytes(cfg1, bsz, seq), _temp_bytes(cfg2, bsz, seq)
    if b1 is not None and b2 is not None and b2 > b1:
        act_mb = (b2 - b1) / (l2 - l1) / bsz / 1e6
    else:
        act_mb = _act_fallback_mb(cfg, seq)
    # per-tp curve: measured (compiled tp-sharded step) where the host has
    # enough devices, ~1/tp analytic otherwise (reference sweeps real runs
    # across tp degrees, core/profiler.py:194-240)
    act_curve = {1: float(act_mb)}
    for t in (2, 4, 8):
        if cfg.hidden_size % t or cfg.num_heads % t or bsz % t:
            act_curve[t] = float(act_mb / t)
            continue
        bt1 = _temp_bytes_tp(cfg1, bsz, seq, t)
        bt2 = _temp_bytes_tp(cfg2, bsz, seq, t)
        if bt1 is not None and bt2 is not None and bt2 > bt1:
            act_curve[t] = (bt2 - bt1) / (l2 - l1) / bsz / 1e6
        else:
            act_curve[t] = float(act_mb / t)

    boundary_mb = seq * cfg.hidden_size * 2 / 1e6  # one bf16 (S, H) tensor
    p_layer = layer_param_count(cfg)
    p_mb = p_layer * 4 / 1e6
    # MoE: expert-stack param fraction + dispatch/combine a2a volume — the
    # analytic structural facts the measured profile cannot see (search/
    # theoretical.py uses the same derivation)
    moe_frac, moe_a2a = 0.0, 0.0
    if cfg.moe_experts > 0:
        moe_frac = theoretical.moe_expert_params(cfg) / p_layer
        moe_a2a = 2.0 * seq * cfg.hidden_size * 2 / 1e6  # bf16, each way
    costs = ProfiledModelCosts(
        layer_types={
            0: ProfiledLayerType(
                fwd_ms_per_sample=float(fwd_ms),
                parameter_mb=float(p_mb),
                activation_mb_per_sample=act_curve,
                boundary_activation_mb_per_sample=float(boundary_mb),
                moe_expert_param_fraction=float(moe_frac),
                moe_a2a_mb_per_sample=float(moe_a2a),
                moe_expert_time_fraction=0.0 if cfg.moe_dropless else moe_tfrac,
                moe_untp_time_fraction=theoretical.moe_untp_time_fraction(cfg, seq),
            )
        },
        other_param_mb=float(other_param_count(cfg) * 4 / 1e6),
        other_act_mb_per_sample=float(seq * cfg.vocab_size * 4 / 1e6),  # logits fp32
        other_fwd_ms_per_sample=float(other_ms),
        hidden_size=cfg.hidden_size,
    )
    # vocab measurement costs ~2 jitted builds per feasible vocab_tp — worth
    # it on real hardware, but on the CPU simulation the numbers are
    # synthetic (like the hardware profiler's) and the compiles are slow, so
    # it defaults off there; call profile_vocab_costs directly to force
    if measure_time and jax.default_backend() != "cpu":
        vslope, vconst, vmp = profile_vocab_costs(cfg, bsz, seq=seq)
        costs.measured_vocab_slope_ms = vslope
        costs.measured_vocab_const_ms = vconst
        costs.measured_vocab_mp = vmp
    _maybe_save(costs, out_prefix)
    return costs


def _profile_swin_model(
    cfg: ModelConfig,
    bsz: int,
    measure_time: bool,
    out_prefix: Optional[str],
) -> ProfiledModelCosts:
    """Swin difference profile: one layer type PER SECTION from a (K+1)-point
    sweep — a base pyramid of one PAIR (two layers) per section, then +1
    pair in section k holding the others fixed (the reference's
    multi-layer-type layernum launch matrix, core/profiler.py:194-240, for
    its legacy swin branch; pairs because Swin alternates plain/shifted
    windows per position parity, models/modeling.py::swin_layer)."""
    from galvatron_tpu.models.modeling import swin_geometry, vision_layer_cfg

    K = len(cfg.swin_depths)

    def with_depths(d):
        return cfg.replace(num_layers=sum(d), swin_depths=tuple(d))

    cfg_base = with_depths((2,) * K)
    var_cfgs = [
        with_depths(tuple(4 if j == k else 2 for j in range(K))) for k in range(K)
    ]
    if measure_time:
        t_base = _iter_time_ms(cfg_base, bsz, None)
        t_var = [_iter_time_ms(c, bsz, None) for c in var_cfgs]
        sec_ms = [max(1e-4, (t - t_base) / 2.0 / bsz / 3.0) for t in t_var]
        other_ms = max(0.0, (t_base - sum(sec_ms) * 2.0 * 3.0 * bsz) / bsz / 3.0)
    else:
        sec_ms = [1.0] * K
        other_ms = 0.1

    S = cfg.sample_len
    b_base = _temp_bytes(cfg_base, bsz, S)
    b_var = [_temp_bytes(c, bsz, S) for c in var_cfgs]
    base_idx = np.cumsum([0] + list(cfg.swin_depths[:-1]))

    sec_lts = []
    for k in range(K):
        h, w, c_k, _ = swin_geometry(cfg, k)
        S_k = h * w
        lcfg = vision_layer_cfg(cfg, int(base_idx[k]))
        if b_base is not None and b_var[k] is not None and b_var[k] > b_base:
            act_mb = (b_var[k] - b_base) / 2.0 / bsz / 1e6
        else:
            act_mb = _act_fallback_mb(lcfg, S_k)
        curve = {t: float(act_mb / t) for t in (1, 2, 4, 8) if c_k % t == 0}
        sec_lts.append(
            ProfiledLayerType(
                fwd_ms_per_sample=float(sec_ms[k]),
                parameter_mb=float(layer_param_count(lcfg) * 4 / 1e6),
                activation_mb_per_sample=curve,
                boundary_activation_mb_per_sample=float(S_k * c_k * 2 / 1e6),
            )
        )
    layer_types = {}
    i = 0
    for k, d in enumerate(cfg.swin_depths):
        for _ in range(d):
            layer_types[i] = sec_lts[k]
            i += 1
    costs = ProfiledModelCosts(
        layer_types=layer_types,
        other_param_mb=float(other_param_count(cfg) * 4 / 1e6),
        # patch-embedding output dominates "other" activations (cls logits
        # are tiny) — same structural term the analytic path uses
        other_act_mb_per_sample=float(cfg.n_patches * cfg.hidden_size * 2 / 1e6),
        other_fwd_ms_per_sample=float(other_ms),
        hidden_size=cfg.hidden_size,
    )
    _maybe_save(costs, out_prefix)
    return costs


def _profile_encdec_model(
    cfg: ModelConfig,
    bsz: int,
    layernums: Tuple[int, int],
    measure_time: bool,
    out_prefix: Optional[str],
) -> ProfiledModelCosts:
    """Enc-dec difference profile: TWO layer types from a three-point sweep —
    vary the decoder count at fixed encoder count, then the encoder count at
    fixed decoder count (the reference's multi-layer-type layernum lists,
    core/profiler.py:194-240 launch matrix)."""
    l1, l2 = layernums
    S_e, S_d = cfg.enc_seq, cfg.max_seq_len
    c11 = cfg.replace(num_layers=l1, enc_layers=l1)
    c12 = cfg.replace(num_layers=l2, enc_layers=l1)
    c21 = cfg.replace(num_layers=l1, enc_layers=l2)

    if measure_time:
        t11 = _iter_time_ms(c11, bsz, None)
        t12 = _iter_time_ms(c12, bsz, None)
        t21 = _iter_time_ms(c21, bsz, None)
        dec_ms = max(1e-4, (t12 - t11) / (l2 - l1) / bsz / 3.0)
        enc_ms = max(1e-4, (t21 - t11) / (l2 - l1) / bsz / 3.0)
        other_ms = max(
            0.0, (t11 - (enc_ms + dec_ms) * 3.0 * bsz * l1) / bsz / 3.0
        )
    else:
        enc_ms, dec_ms, other_ms = 1.0, 1.5, 0.1

    S = cfg.sample_len
    b11, b12, b21 = (
        _temp_bytes(c11, bsz, S), _temp_bytes(c12, bsz, S), _temp_bytes(c21, bsz, S)
    )

    def act_of(b_hi, b_lo, S_type):
        if b_hi is not None and b_lo is not None and b_hi > b_lo:
            return (b_hi - b_lo) / (l2 - l1) / bsz / 1e6
        return _act_fallback_mb(cfg, S_type)

    enc_act = act_of(b21, b11, S_e)
    dec_act = act_of(b12, b11, S_d)

    def make_lt(fwd, act_mb, S_type, cross):
        p_mb = layer_param_count(cfg, cross=cross) * 4 / 1e6
        curve = {
            t: float(act_mb / t)
            for t in (1, 2, 4, 8)
            if cfg.hidden_size % t == 0
        }
        return ProfiledLayerType(
            fwd_ms_per_sample=float(fwd),
            parameter_mb=float(p_mb),
            activation_mb_per_sample=curve,
            boundary_activation_mb_per_sample=float(S_type * cfg.hidden_size * 2 / 1e6),
        )

    enc_lt = make_lt(enc_ms, enc_act, S_e, cross=False)
    dec_lt = make_lt(dec_ms, dec_act, S_d, cross=True)
    layer_types = {i: enc_lt for i in range(cfg.enc_layers)}
    layer_types.update({cfg.enc_layers + i: dec_lt for i in range(cfg.num_layers)})
    costs = ProfiledModelCosts(
        layer_types=layer_types,
        other_param_mb=float(other_param_count(cfg) * 4 / 1e6),
        other_act_mb_per_sample=float(S_d * cfg.vocab_size * 4 / 1e6),
        other_fwd_ms_per_sample=float(other_ms),
        hidden_size=cfg.hidden_size,
    )
    _maybe_save(costs, out_prefix)
    return costs
