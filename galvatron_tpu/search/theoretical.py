"""Analytic (no-profiling) parameter/memory estimates from a model config.

Counterpart of the vendored Megatron ``theoretical_memory_usage.py``
(reference: site_package/megatron/theoretical_memory_usage.py — unused by the
reference's own trainer, SURVEY §2.6), re-derived for this runtime:

- exact parameter counts from ModelConfig (GQA, SwiGLU/GeLU, tied embeddings);
- model-state memory per chip under a LayerStrategy (fp32 master + 2 Adam
  moments + optional bf16 working cast; ZeRO-2 shards moments, ZeRO-3 all);
- activation estimates per layer per sample for the three attention paths
  (flash never materializes the (S, S) score matrix; xla does).

Useful to seed the search before any profiling has run, and as the
cross-check for the profiler's measured numbers (``check_cost_model``).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict

from galvatron_tpu.core.strategy import LayerStrategy
from galvatron_tpu.models import mixers
from galvatron_tpu.models.modeling import ModelConfig

_BYTES = {"fp32": 4, "bf16": 2, "fp16": 2}


def moe_expert_params(cfg: ModelConfig) -> int:
    """Parameters in the expert stack (shardable by ep): E MLPs, w1/w2
    (+ w3 for swiglu) — matches moe.init_moe_params."""
    mats = 3 if cfg.act_fn == "swiglu" else 2
    if cfg.moe_dropless:  # the experts held here, at the routed experts' own width
        return cfg.moe_held * mats * cfg.hidden_size * cfg.expert_ffn
    return cfg.moe_experts * mats * cfg.hidden_size * cfg.ffn


def layer_active_param_count(cfg: ModelConfig, kind: str = "attention") -> int:
    """Weights ONE token is multiplied by in a layer — what its time scales
    with. A dropless top-k MoE layer holds E experts (layer_param_count, its
    memory) and runs ``moe_top_k`` of them a token, plus the router; every
    other layer runs all it holds."""
    p = layer_param_count(cfg, kind=kind)
    if cfg.moe_dropless:
        p -= (cfg.moe_held - _held_top_k(cfg)) * 3 * cfg.hidden_size * cfg.expert_ffn
    return p


def _held_top_k(cfg: ModelConfig) -> float:
    """Routed experts a token runs HERE: ``moe_top_k``, or its even share
    ``k * held / E`` where this copy holds a share of the experts."""
    return cfg.moe_top_k * cfg.moe_held / cfg.moe_experts


def moe_untp_time_fraction(cfg: ModelConfig, seq_len: int) -> float:
    """Share of a dropless top-k MoE layer's forward FLOPs in its routed MLP
    (router + k experts a token): what tensor parallelism does not divide
    (cost_model.ProfiledLayerType.moe_untp_time_fraction). 0 for other layers."""
    if not cfg.moe_dropless:
        return 0.0
    h = cfg.hidden_size
    routed = 2.0 * (h * cfg.moe_experts + _held_top_k(cfg) * 3 * h * cfg.expert_ffn)
    total = 2.0 * layer_active_param_count(cfg) + 4.0 * cfg.num_heads * cfg.head_dim * seq_len
    return routed / total


def layer_param_count(cfg: ModelConfig, cross: bool = False, kind: str = "attention",
                      mlp: bool = True) -> int:
    """Exact per-layer parameter count (matches init_layer_params).
    ``cross``: enc-dec decoder layers carry a cross-attention block
    (wq + wkv + wo + cross_norm). ``kind``: a kind of ``mixers.MIXERS`` (a
    hybrid stack's layer) counts that kind's mixer in place of attention. ``mlp``
    False: the layer is its mixer alone (``ModelConfig.mlp_layout``): no MLP, one
    norm. (Times and activations price such a layer as one WITH an MLP still.)"""
    h, hd = cfg.hidden_size, cfg.head_dim
    q_out, kv_out = cfg.num_heads * hd, cfg.kv_heads * hd
    attn = h * q_out + 2 * h * kv_out + q_out * h
    if cfg.attn_gate:  # the output gate's projection
        attn += h * q_out
    if kind in mixers.MIXERS:
        attn = mixers.module(kind).param_count(cfg)
    if cross:
        attn += h * q_out + 2 * h * kv_out + q_out * h
        attn += h if cfg.norm_type == "rms" else 2 * h  # cross_norm
    has_mlp = mlp
    if cfg.moe_experts > 0:
        # router (+ its selection bias) + per-expert MLPs (+ the shared expert and its
        # gate); a leading dense layer (moe_dense_layers) is priced as an expert layer
        mlp = h * cfg.moe_experts + moe_expert_params(cfg)
        if cfg.moe_router == "sigmoid_topk":
            mlp += cfg.moe_experts
        if cfg.moe_shared_ffn_dim:
            mats = 3 if cfg.act_fn == "swiglu" else 2
            mlp += mats * h * cfg.moe_shared_ffn_dim + (h if cfg.moe_shared_gate else 0)
    elif cfg.act_fn == "swiglu":
        mlp = 3 * h * cfg.ffn
    else:
        mlp = 2 * h * cfg.ffn
    norms = (4 if cfg.post_norms else 2) * (h if cfg.norm_type == "rms" else 2 * h)
    if not has_mlp:
        mlp, norms = 0, norms // 2
    if cfg.qk_norm and kind not in mixers.MIXERS:
        norms += 2 * hd if cfg.qk_norm_per_head else q_out + kv_out
    bias = 0
    if cfg.use_bias:  # qkv slots + wo (+ dense-MLP biases; MoE MLPs carry none)
        bias = 3 * q_out + h
        if cfg.moe_experts == 0:
            bias += (2 * cfg.ffn if cfg.act_fn == "swiglu" else cfg.ffn) + h
    return attn + mlp + norms + bias


def other_param_count(cfg: ModelConfig) -> int:
    """Embedding + final norm + output head (+ Swin patch merges)."""
    if cfg.image_size:
        from galvatron_tpu.models.modeling import swin_geometry

        patch_dim = cfg.patch_size * cfg.patch_size * cfg.num_channels
        n = patch_dim * cfg.hidden_size + cfg.n_patches * cfg.hidden_size
        c_last = cfg.hidden_size << max(0, len(cfg.swin_depths) - 1)
        n += c_last * cfg.num_classes
        n += c_last if cfg.norm_type == "rms" else 2 * c_last
        for s in range(len(cfg.swin_depths) - 1):
            _, _, c, _ = swin_geometry(cfg, s)
            n += 4 * c * 2 * c + (4 * c if cfg.norm_type == "rms" else 8 * c)
        return n
    n = cfg.vocab_size * cfg.hidden_size  # token embedding
    if cfg.pos_embed == "learned":
        n += cfg.max_seq_len * cfg.hidden_size
    n += cfg.hidden_size if cfg.norm_type == "rms" else 2 * cfg.hidden_size
    if not cfg.tie_word_embeddings:
        n += cfg.hidden_size * cfg.vocab_size
    return n


def total_param_count(cfg: ModelConfig) -> int:
    if cfg.swin_depths:
        from galvatron_tpu.models.modeling import vision_layer_cfg

        layers = sum(
            layer_param_count(vision_layer_cfg(cfg, i)) for i in range(cfg.num_layers)
        )
        return layers + other_param_count(cfg)
    # (a layer under its own view: a latent stack's window layers have sizes of their own)
    return sum(layer_param_count(cfg.layer_view(i), kind=k, mlp=cfg.mlp_layers[i])
               for i, k in enumerate(cfg.kinds)) + other_param_count(cfg)


def layer_states_mb(
    cfg: ModelConfig, s: LayerStrategy, world: int, pp: int = 1,
    mixed_precision: str = "bf16",
) -> float:
    """Per-chip model-state MB for one layer under strategy ``s`` — the
    analytic form of layer_memory_cost's states term."""
    dp = world // (pp * s.tp * s.cp)
    p_mb = layer_param_count(cfg) * 4 / 1e6 / s.tp  # fp32 MB after TP
    cast = 0.5 * p_mb if mixed_precision in ("bf16", "fp16") else 0.0
    if s.dp_type == "zero3":
        return 4.0 * p_mb / dp + cast
    if s.dp_type == "zero2":
        return 2.0 * p_mb + 2.0 * p_mb / dp + cast
    return 4.0 * p_mb + cast


def layer_activation_mb_per_sample(
    cfg: ModelConfig, s: LayerStrategy, seq_len: int = 0,
    mixed_precision: str = "bf16", kind: str = "attention",
) -> float:
    """Analytic activation MB per layer per sample, no remat.

    Derivation (per token, compute dtype bytes b): residual h, two norm
    outputs 2h, qkv (1 + 2·kv/n)·h·(n·hd/h), attention context h, mlp inputs
    h + {3 ffn (swiglu: w1 out, w3 out, product) | 2 ffn (gelu)}. The xla
    attention path additionally saves the (n_heads, S, S) probs in fp32;
    flash saves only the (S, 1) LSE. TP divides the sharded intermediates;
    SP additionally shards the replicated residual/norm tensors.

    Under ``cfg.mlp_recompute`` ('gate'/'policy', the default) the MLP
    saves ONLY the gate/up projection output — the activation product is
    recomputed in the backward (modeling.mlp_residual) — so the mlp term
    drops by one ffn-wide save (swiglu 3→2, gelu/relu 2→1 ffn).
    """
    S = seq_len or cfg.max_seq_len
    h, n, kvn, hd = cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.head_dim
    b = _BYTES[mixed_precision]
    tp = s.tp
    # replicated (residual stream + norm inputs): sharded only under SP
    repl = 3 * h * b / (tp if s.sp else 1)
    # TP-sharded intermediates
    qkv = (n + 2 * kvn) * hd * b / tp
    ctx = n * hd * b / tp
    recompute = getattr(cfg, "mlp_recompute", "policy") in ("gate", "policy")
    if cfg.moe_dropless:
        # every one of a token's k pairs keeps its row in and out (2h) and
        # its gate, up and product (3f) for the backward; the experts run
        # whole on every device (moe.moe_topk_block), so tp divides nothing
        mlp = cfg.moe_top_k * (2 * h + 3 * cfg.expert_ffn) * b + 3 * cfg.moe_shared_ffn_dim * b
    elif cfg.moe_experts > 0:
        mlp = 3 * cfg.ffn * b / tp  # per routed token (capacity ~1); the
        # recompute policy excludes MoE layers (modeling.mlp_residual)
    elif cfg.act_fn == "swiglu":
        mlp = (2 if recompute else 3) * cfg.ffn * b / tp
    else:
        mlp = (1 if recompute else 2) * cfg.ffn * b / tp
    if kind in mixers.MIXERS:
        # the kind's mixer in place of qkv + context: what it keeps a token
        mixer = mixers.module(kind).saved_bytes_per_token(cfg, b)
        return (repl + mixer + mlp) * S / 1e6
    if cfg.attn_gate:
        ctx *= 2  # the gate beside the context
    per_token = repl + qkv + ctx + mlp
    total = per_token * S
    if cfg.attn_impl == "xla":
        total += 4.0 * (n / tp) * S * S  # fp32 probs
    else:
        total += 4.0 * (n / tp) * S  # flash LSE
    return total / 1e6 / max(1, s.cp)


def analytic_model_costs(
    cfg: ModelConfig, seq_len: int = 0, peak_tflops: float = 100.0, mfu: float = 0.4,
    mixed_precision: str = "bf16",
):
    """ProfiledModelCosts from pure analysis — lets the search run before any
    profiling exists (the reference cannot: it always requires profiled JSON,
    search_engine.py:92-121). fwd time from the 2·P·T FLOP estimate at an
    assumed MFU; activation table from layer_activation_mb_per_sample."""
    # what price_plan's ``basis`` says of these costs: the one compute rate
    # every fwd_ms here is a FLOP count over
    basis = {"costs": "analytic", "peak_tflops": peak_tflops, "efficiency": mfu,
             "compute_tflops": peak_tflops * mfu}
    return dataclasses.replace(
        _analytic_model_costs(cfg, seq_len, peak_tflops, mfu, mixed_precision), basis=basis)


def _analytic_model_costs(cfg, seq_len, peak_tflops, mfu, mixed_precision):
    from galvatron_tpu.search.cost_model import ProfiledLayerType, ProfiledModelCosts

    if cfg.image_size:
        return _analytic_vision_costs(cfg, peak_tflops, mfu, mixed_precision)
    if cfg.enc_layers > 0:
        return _analytic_encdec_costs(cfg, peak_tflops, mfu, mixed_precision)
    if mixers.has_mixer_layers(cfg):
        return _analytic_hybrid_costs(cfg, seq_len, peak_tflops, mfu, mixed_precision)
    S = seq_len or cfg.max_seq_len
    b = _BYTES[mixed_precision]
    p_layer = layer_param_count(cfg)
    # fwd multiply-accumulate per sample: the weights a token meets (a top-k
    # MoE layer: k experts and the router, not all E it holds in memory)
    flops = 2.0 * layer_active_param_count(cfg) * S
    if cfg.attn_impl == "xla" or cfg.attn_impl == "flash":
        flops += 2.0 * 2.0 * cfg.num_heads * cfg.head_dim * S * S  # qk^T + pv
    fwd_ms = flops / (peak_tflops * 1e12 * mfu) * 1e3
    act = {
        tp: layer_activation_mb_per_sample(
            cfg, LayerStrategy(tp=tp), S, mixed_precision
        )
        for tp in (1, 2, 4, 8)
        if cfg.hidden_size % tp == 0
    }
    other_p = other_param_count(cfg)
    # logits dominate "other" activation
    other_act = S * cfg.vocab_size * b / 1e6
    other_flops = 2.0 * cfg.hidden_size * cfg.vocab_size * S
    # MoE: expert-stack fraction of the layer (shardable by ep) and the token
    # dispatch+combine all-to-all volume — one (S, h) activation each way
    frac = 0.0
    a2a = 0.0
    if cfg.moe_experts > 0:
        frac = moe_expert_params(cfg) / p_layer
        a2a = 2.0 * S * cfg.hidden_size * b / 1e6
    return ProfiledModelCosts(
        layer_types={
            0: ProfiledLayerType(
                fwd_ms_per_sample=fwd_ms,
                parameter_mb=p_layer * 4 / 1e6,
                activation_mb_per_sample=act,
                boundary_activation_mb_per_sample=S * cfg.hidden_size * b / 1e6,
                moe_expert_param_fraction=frac,
                moe_a2a_mb_per_sample=a2a,
                # a dropless layer has no ep to shard its time by (the search
                # leaves ep out for it); its routed share is what tp leaves whole
                moe_expert_time_fraction=0.0 if cfg.moe_dropless else None,
                moe_untp_time_fraction=moe_untp_time_fraction(cfg, S),
            )
        },
        other_param_mb=other_p * 4 / 1e6,
        other_act_mb_per_sample=other_act,
        other_fwd_ms_per_sample=other_flops / (peak_tflops * 1e12 * mfu) * 1e3,
    )


def with_tp_seams(costs, cfg: ModelConfig):
    """``costs`` with the model's projection seams on every layer type that
    carries none: the shapes ``cost_model.tp_overlap_exposed`` prices
    ``s.tp_overlap`` from (a profile carries times and sizes, not the
    projections' widths).  ONE rule for the search and for whoever prices a
    plan outside it."""
    from galvatron_tpu.models.modeling import projection_seams

    seq = int(cfg.max_seq_len)
    seams = tuple((k, w, seq, blk) for _, k, w, blk in projection_seams(cfg, seq))
    return dataclasses.replace(costs, layer_types={
        i: lt if lt.tp_seams else dataclasses.replace(lt, tp_seams=seams)
        for i, lt in costs.layer_types.items()
    })


def price_model_plan(cfg: ModelConfig, hp, world: int, global_bsz: int):
    """``price.price_plan`` of ``hp`` from the basis ``cli search
    --analytic_costs 1`` searches on: this module's costs for the model at its
    own sequence length and ``ProfiledHardware``'s defaults.  How the trainer
    prices a plan that no search priced."""
    from galvatron_tpu.search.cost_model import ProfiledHardware
    from galvatron_tpu.search.price import price_plan

    return price_plan(
        with_tp_seams(analytic_model_costs(cfg), cfg), ProfiledHardware(), hp,
        world, global_bsz, hp.mixed_precision, section_pipeline=bool(cfg.swin_depths),
    )


def _analytic_hybrid_costs(
    cfg: ModelConfig, seq_len: int, peak_tflops: float, mfu: float, mixed_precision: str
):
    """A hybrid stack: one layer type a KIND, keyed by layer index, so that the
    multi-layer-type search prices the published interleaving (pp = 1; the
    search leaves pp > 1 and tp > 1 out for such a model). A recurrent kind's
    FLOPs grow linearly with the sequence (its weights and what its module's
    ``fwd_flops_per_token`` counts); the attention layer's quadratically."""
    from galvatron_tpu.search.cost_model import ProfiledLayerType, ProfiledModelCosts

    S = seq_len or cfg.max_seq_len
    b = _BYTES[mixed_precision]
    rate = peak_tflops * 1e12 * mfu

    def make_lt(kind: str) -> ProfiledLayerType:
        flops = 2.0 * layer_active_param_count(cfg, kind) * S
        if kind in mixers.MIXERS:
            flops += mixers.module(kind).fwd_flops_per_token(cfg) * S
        else:
            flops += 4.0 * cfg.num_heads * cfg.head_dim * S * S
        return ProfiledLayerType(
            fwd_ms_per_sample=flops / rate * 1e3,
            parameter_mb=layer_param_count(cfg, kind=kind) * 4 / 1e6,
            activation_mb_per_sample={
                tp: layer_activation_mb_per_sample(
                    cfg, LayerStrategy(tp=tp), S, mixed_precision, kind=kind)
                for tp in (1, 2, 4, 8) if cfg.hidden_size % tp == 0
            },
            boundary_activation_mb_per_sample=S * cfg.hidden_size * b / 1e6,
        )

    by_kind = {kind: make_lt(kind) for kind in set(cfg.kinds)}
    return ProfiledModelCosts(
        layer_types={i: by_kind[kind] for i, kind in enumerate(cfg.kinds)},
        other_param_mb=other_param_count(cfg) * 4 / 1e6,
        other_act_mb_per_sample=S * cfg.vocab_size * b / 1e6,
        other_fwd_ms_per_sample=2.0 * cfg.hidden_size * cfg.vocab_size * S / rate * 1e3,
    )


def _analytic_encdec_costs(
    cfg: ModelConfig, peak_tflops: float, mfu: float, mixed_precision: str
):
    """Enc-dec variant: TWO layer types (encoder at enc_seq; decoder with
    cross-attention at max_seq_len) so the multi-layer-type search — incl.
    the pp>1 enc-dec pipeline path — gets per-type costs."""
    from galvatron_tpu.search.cost_model import ProfiledLayerType, ProfiledModelCosts

    b = _BYTES[mixed_precision]
    S_e, S_d = cfg.enc_seq, cfg.max_seq_len
    rate = peak_tflops * 1e12 * mfu

    def make_lt(S, cross):
        p = layer_param_count(cfg, cross=cross)
        flops = 2.0 * p * S
        flops += 4.0 * cfg.num_heads * cfg.head_dim * S * S  # self attn
        if cross:
            flops += 4.0 * cfg.num_heads * cfg.head_dim * S * S_e  # cross attn
            # the cross K/V projection runs over the ENCODER tokens (S_e),
            # not the decoder length the 2pS term assumed
            cross_kv = 2 * cfg.hidden_size * cfg.kv_heads * cfg.head_dim
            flops += 2.0 * cross_kv * (S_e - S)
        act = {
            tp: layer_activation_mb_per_sample(
                cfg, LayerStrategy(tp=tp), S, mixed_precision
            )
            # cross-attention roughly replays the attention activations
            * (1.5 if cross else 1.0)
            for tp in (1, 2, 4, 8)
            if cfg.hidden_size % tp == 0
        }
        frac = moe_expert_params(cfg) / p if cfg.moe_experts > 0 else 0.0
        a2a = 2.0 * S * cfg.hidden_size * b / 1e6 if cfg.moe_experts > 0 else 0.0
        return ProfiledLayerType(
            fwd_ms_per_sample=flops / rate * 1e3,
            parameter_mb=p * 4 / 1e6,
            activation_mb_per_sample=act,
            boundary_activation_mb_per_sample=S * cfg.hidden_size * b / 1e6,
            moe_expert_param_fraction=frac,
            moe_a2a_mb_per_sample=a2a,
        )

    enc_lt = make_lt(S_e, cross=False)
    dec_lt = make_lt(S_d, cross=True)
    layer_types = {i: enc_lt for i in range(cfg.enc_layers)}
    layer_types.update(
        {cfg.enc_layers + i: dec_lt for i in range(cfg.num_layers)}
    )
    other_p = other_param_count(cfg)
    other_flops = 2.0 * cfg.hidden_size * cfg.vocab_size * S_d
    return ProfiledModelCosts(
        layer_types=layer_types,
        other_param_mb=other_p * 4 / 1e6,
        other_act_mb_per_sample=S_d * cfg.vocab_size * b / 1e6,
        other_fwd_ms_per_sample=other_flops / rate * 1e3,
    )


def _analytic_vision_costs(
    cfg: ModelConfig, peak_tflops: float, mfu: float, mixed_precision: str
):
    """Vision variant of analytic_model_costs: ViT = one uniform layer type at
    seq = n_patches; Swin = one layer type per layer (the stage pyramid makes
    widths/resolutions layer-dependent — the multi-layer-type DP case,
    reference: _build_dp_and_run_multi_layer_type,
    galvatron/core/dynamic_programming.py:304-455)."""
    from galvatron_tpu.models.modeling import swin_geometry, swin_stage_of, vision_layer_cfg
    from galvatron_tpu.search.cost_model import ProfiledLayerType, ProfiledModelCosts

    b = _BYTES[mixed_precision]

    def layer_type_for(i: int) -> ProfiledLayerType:
        lcfg = vision_layer_cfg(cfg, i)
        if cfg.swin_depths:
            from galvatron_tpu.models.modeling import swin_window_for

            stage, _ = swin_stage_of(cfg, i)
            h_side, w_side, _, heads = swin_geometry(cfg, stage)
            S = h_side * w_side
            win = swin_window_for(cfg, stage)
            ctx = win * win  # each token attends its window
        else:
            S = cfg.n_patches
            heads, ctx = cfg.num_heads, cfg.n_patches
        p_layer = layer_param_count(lcfg)
        flops = 2.0 * p_layer * S + 2.0 * 2.0 * heads * lcfg.head_dim * S * ctx
        fwd_ms = flops / (peak_tflops * 1e12 * mfu) * 1e3
        act = {}
        for tp in (1, 2, 4, 8):
            if lcfg.hidden_size % tp:
                continue
            base = layer_activation_mb_per_sample(
                lcfg.replace(attn_impl="flash"), LayerStrategy(tp=tp), S, mixed_precision
            )
            # replace the flash-LSE term with the windowed fp32 probs
            act[tp] = base + 4.0 * (heads / tp) * S * (ctx - 1) / 1e6
        return ProfiledLayerType(
            fwd_ms_per_sample=fwd_ms,
            parameter_mb=p_layer * 4 / 1e6,
            activation_mb_per_sample=act,
            boundary_activation_mb_per_sample=S * lcfg.hidden_size * b / 1e6,
        )

    if cfg.swin_depths:
        layer_types = {i: layer_type_for(i) for i in range(cfg.num_layers)}
    else:
        layer_types = {0: layer_type_for(0)}
    other_p = other_param_count(cfg)
    patch_dim = cfg.patch_size * cfg.patch_size * cfg.num_channels
    other_flops = 2.0 * patch_dim * cfg.hidden_size * cfg.n_patches
    c_last = cfg.hidden_size << max(0, len(cfg.swin_depths) - 1)
    other_flops += 2.0 * c_last * cfg.num_classes
    # patch embedding output dominates "other" activation
    other_act = cfg.n_patches * cfg.hidden_size * b / 1e6
    return ProfiledModelCosts(
        layer_types=layer_types,
        other_param_mb=other_p * 4 / 1e6,
        other_act_mb_per_sample=other_act,
        other_fwd_ms_per_sample=other_flops / (peak_tflops * 1e12 * mfu) * 1e3,
    )


@dataclass
class TheoreticalReport:
    total_params: int
    per_layer_params: int
    other_params: int
    layer_states_mb: float
    layer_act_mb_per_sample: float
    model_states_total_mb: float

    def lines(self) -> str:
        return (
            f"params: total {self.total_params/1e9:.3f}B "
            f"(layer {self.per_layer_params/1e6:.1f}M x N + other {self.other_params/1e6:.1f}M)\n"
            f"per-chip layer states: {self.layer_states_mb:.1f} MB | "
            f"layer activation/sample: {self.layer_act_mb_per_sample:.2f} MB | "
            f"all-layer states: {self.model_states_total_mb:.0f} MB"
        )


def report(
    cfg: ModelConfig, s: LayerStrategy, world: int, pp: int = 1,
    seq_len: int = 0, mixed_precision: str = "bf16",
) -> TheoreticalReport:
    lsm = layer_states_mb(cfg, s, world, pp, mixed_precision)
    return TheoreticalReport(
        total_params=total_param_count(cfg),
        per_layer_params=layer_param_count(cfg),
        other_params=other_param_count(cfg),
        layer_states_mb=lsm,
        layer_act_mb_per_sample=layer_activation_mb_per_sample(
            cfg, s, seq_len, mixed_precision
        ),
        model_states_total_mb=lsm * (cfg.num_layers // pp),
    )
