"""Encoder-decoder (T5-class) pipeline: 2·pp virtual stages over the pp ring.

The reference pipelines enc-dec models by flattening encoder + decoder into
one PipeSequential and placing arbitrary layer ranges per stage
(galvatron/core/hybrid_parallel_model.py:81-153, pipeline.py:75-77), passing
the encoder output along as an extra p2p tensor. The SPMD stage stacking here
needs homogeneous layer pytrees per stack — encoder layers (self-attn + MLP)
and decoder layers (+ cross-attn) differ — so the TPU-native rendering runs
TWO COUPLED SUB-PIPELINES over the pp ring: device ``s`` holds encoder
virtual stage ``s`` and decoder virtual stage ``pp+s``, each a homogeneous
stack, and every clocked tick runs BOTH its encoder section (chunk ``t-s``)
and its decoder section (chunk ``t-pp-s``). There is no stage-diverging
control flow — GSPMD's resharding collectives span stages, so a per-stage
``lax.cond`` deadlocks (verified on the CPU sim) — and no steady-state
waste: each device does useful encoder AND decoder work every tick, so
total time ≈ (chunks + 2·pp - 1) ticks × (enc_vstage + dec_vstage), matching
the ideal interleaved schedule up to a slightly longer fill.

Ring wiring per tick:
- encoder sends ride a WRAPPED ring (device pp-1 → 0): the wrap delivers
  chunk ``t-pp``'s finished encoder output to device 0 exactly when that
  chunk's decoder starts there; device 0 applies enc_final_norm
  (token-local, SPMD-safe) to form ``ctx``;
- decoder ``(y, ctx)`` rides the plain chain (s → s+1), so every decoder
  virtual stage cross-attends against the same normed encoder output.

Backward is autodiff through the clocked scan (GPipe ordering). Encoder and
decoder sequence lengths are independent (separate carries, no padding).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from galvatron_tpu.core.optim import (
    AdamConfig,
    adamw_update,
    apply_update_with_scaler,
    init_opt_state,
)
from galvatron_tpu.core.schedules import (
    LossScalerConfig,
    init_scaler_state,
    scaled_value_and_grad,
)
from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models import modeling
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.parallel import placement
from galvatron_tpu.parallel.mesh import MeshAxes
from galvatron_tpu.parallel.pipeline import cpu_sim_compiler_options
from galvatron_tpu.parallel.sharding import constrain, param_spec, sharding_tree


class EncDecLayout:
    """Per-sub-stack stage layout: ragged encoder/decoder layer counts are
    realized by PADDED stacking exactly like the decoder-only pipeline
    (pipeline.stage_layout): each sub-stack carries max(division) positions,
    stages with fewer real layers get zero-filled padding slots whose compute
    is masked out in the section functions.

    ``hp.pp_division`` of length 2*pp is read as [enc division ‖ dec
    division]; anything else (including the auto-filled single-stack default
    from HybridParallelConfig.__post_init__, which sums E+D) falls back to a
    per-stack balanced division."""

    def __init__(self, cfg: ModelConfig, hp: HybridParallelConfig):
        from galvatron_tpu.core.strategy import balanced_division

        E, D, pp = cfg.enc_layers, cfg.num_layers, hp.pp
        if E < 1 or D < 1:
            raise ValueError(
                f"enc-dec pipeline needs at least one encoder and one decoder "
                f"layer (got {E} enc / {D} dec)"
            )
        # a sub-stack SMALLER than pp is fine: balanced_division yields zero
        # entries for the tail stages, whose padded positions are fully
        # masked (identity sections that just forward the ring traffic) —
        # the reference places arbitrary layer ranges per stage the same way
        # (galvatron/core/pipeline/pipeline.py:75-77)
        div = hp.pp_division
        if div is not None and len(div) == pp:
            # HybridParallelConfig.__post_init__ auto-fills a length-pp
            # balanced division over E+D, which is meaningless for the
            # two-stack layout and ignored. Anything ELSE of length pp is
            # provably user-provided — reject it instead of silently
            # training under a different layout than the config states.
            if div != balanced_division(E + D, pp):
                raise ValueError(
                    f"enc-dec models take a 2*pp pp_division "
                    f"([enc ‖ dec] stage splits), got the single-stack "
                    f"division {div}"
                )
            div = None
        if div is not None and len(div) == 2 * pp and sum(div) == E + D:
            self.div_e, self.div_d = list(div[:pp]), list(div[pp:])
            if sum(self.div_e) != E or sum(self.div_d) != D or min(
                self.div_e + self.div_d
            ) < 0:
                raise ValueError(
                    f"enc-dec pp_division {div} must split as enc({E}) ‖ "
                    f"dec({D}) with non-negative per-stage counts"
                )
        else:
            self.div_e = balanced_division(E, pp)
            self.div_d = balanced_division(D, pp)
        self.off_e = list(np.cumsum([0] + self.div_e[:-1]))
        self.off_d = list(np.cumsum([0] + self.div_d[:-1]))
        self.lpe, self.lpd = max(self.div_e), max(self.div_d)
        self.pp = pp
        from galvatron_tpu.parallel.pipeline import position_strategies

        self.enc_pos = position_strategies(
            hp.layer_strategies[:E], self.div_e, self.off_e, "encoder"
        )
        self.dec_pos = position_strategies(
            hp.layer_strategies[E:], self.div_d, self.off_d, "decoder"
        )


def validate_encdec_pipeline(
    cfg: ModelConfig, hp: HybridParallelConfig
) -> EncDecLayout:
    """Schedule constraints + the per-sub-stack stage layout."""
    if hp.vpp > 1:
        raise ValueError("enc-dec pipeline does not compose with vpp>1")
    if hp.pipeline_type not in ("gpipe", "pipedream_flush"):
        raise ValueError(
            f"unknown pipeline_type {hp.pipeline_type!r} for the enc-dec "
            "pipeline (gpipe | pipedream_flush)"
        )
    return EncDecLayout(cfg, hp)


def _pad_stack(items, div, off, lps, pp, zeros):
    """Per-position (pp, ...) stacks from a flat per-layer list; zero padding
    where a stage has fewer real layers than the stack height."""
    return [
        jax.tree.map(
            lambda *ls: jnp.stack(ls),
            *[items[off[s] + q] if div[s] > q else zeros for s in range(pp)],
        )
        for q in range(lps)
    ]


def init_encdec_pipeline_params(key, cfg: ModelConfig, hp: HybridParallelConfig):
    """embed / norms / head replicated over pp; ``enc_stages[q]`` and
    ``dec_stages[q]`` are (pp, ...) stacks — device s's slice is its virtual
    stage's q-th layer (zero-filled padding where the division is ragged)."""
    lay = validate_encdec_pipeline(cfg, hp)
    pp = hp.pp
    ks = jax.random.split(key, 6)
    base: Dict[str, Any] = {
        "embed": {
            "tok": jax.random.normal(
                ks[0], (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype
            )
            * 0.02
        },
        "enc_final_norm": {"scale": jnp.ones((cfg.hidden_size,), cfg.param_dtype)},
        "final_norm": {"scale": jnp.ones((cfg.hidden_size,), cfg.param_dtype)},
    }
    if cfg.pos_embed == "learned":
        pos_len = max(cfg.max_seq_len, cfg.enc_seq)
        base["embed"]["pos"] = (
            jax.random.normal(ks[1], (pos_len, cfg.hidden_size), cfg.param_dtype) * 0.02
        )
    if cfg.norm_type == "layernorm":
        base["enc_final_norm"]["bias"] = jnp.zeros((cfg.hidden_size,), cfg.param_dtype)
        base["final_norm"]["bias"] = jnp.zeros((cfg.hidden_size,), cfg.param_dtype)
    if not cfg.tie_word_embeddings:
        base["head"] = {
            "w": modeling._dense_init(ks[2], cfg.hidden_size, cfg.vocab_size, cfg.param_dtype)
        }
    enc_keys = jax.random.split(ks[3], cfg.enc_layers)
    dec_keys = jax.random.split(ks[4], cfg.num_layers)
    enc_layers = [modeling.init_layer_params(k, cfg) for k in enc_keys]
    dec_layers = [modeling.init_layer_params(k, cfg, cross=True) for k in dec_keys]
    base["enc_stages"] = _pad_stack(
        enc_layers, lay.div_e, lay.off_e, lay.lpe, pp,
        jax.tree.map(jnp.zeros_like, enc_layers[0]),
    )
    base["dec_stages"] = _pad_stack(
        dec_layers, lay.div_d, lay.off_d, lay.lpd, pp,
        jax.tree.map(jnp.zeros_like, dec_layers[0]),
    )
    return base


def restack_flat_encdec(flat_params, cfg: ModelConfig, hp: HybridParallelConfig):
    """Flat ``enc_layers``/``layers`` lists → the enc/dec virtual-stage
    stacks (portable-checkpoint layout); zero padding on ragged divisions."""
    lay = validate_encdec_pipeline(cfg, hp)
    params = {
        k: v for k, v in flat_params.items() if k not in ("enc_layers", "layers")
    }
    enc = flat_params["enc_layers"]
    dec = flat_params["layers"]
    params["enc_stages"] = _pad_stack(
        enc, lay.div_e, lay.off_e, lay.lpe, hp.pp,
        jax.tree.map(jnp.zeros_like, enc[0]),
    )
    params["dec_stages"] = _pad_stack(
        dec, lay.div_d, lay.off_d, lay.lpd, hp.pp,
        jax.tree.map(jnp.zeros_like, dec[0]),
    )
    return params


def flatten_encdec(params, cfg: ModelConfig, hp: HybridParallelConfig):
    """Inverse of restack_flat_encdec (padded slots dropped)."""
    lay = validate_encdec_pipeline(cfg, hp)
    flat = {
        k: v for k, v in params.items() if k not in ("enc_stages", "dec_stages")
    }

    def unstack(stacks, div, off, total):
        out = [None] * total
        for s in range(hp.pp):
            for q in range(div[s]):
                out[off[s] + q] = jax.tree.map(lambda a, s_=s: a[s_], stacks[q])
        return out

    flat["enc_layers"] = unstack(params["enc_stages"], lay.div_e, lay.off_e, cfg.enc_layers)
    flat["layers"] = unstack(params["dec_stages"], lay.div_d, lay.off_d, cfg.num_layers)
    return flat


def encdec_param_specs(
    params_shape, cfg: ModelConfig, hp: HybridParallelConfig, axes: MeshAxes,
    *, for_opt_state: bool = False,
):
    lay = validate_encdec_pipeline(cfg, hp)
    enc_pos, dec_pos = lay.enc_pos, lay.dec_pos
    embed_strategy = LayerStrategy(
        tp=hp.vocab_tp, tp_consec=True, dp_type=hp.embed_dp_type, sp=hp.vocab_sp
    )
    is_leaf = lambda x: hasattr(x, "shape")
    model_annots = {
        "embed": {"tok": ("tp", "fsdp")},
        "enc_final_norm": {"scale": ("fsdp",)},
        "final_norm": {"scale": ("fsdp",)},
    }
    if cfg.pos_embed == "learned":
        model_annots["embed"]["pos"] = ("fsdp", None)
    if cfg.norm_type == "layernorm":
        model_annots["enc_final_norm"]["bias"] = ("fsdp",)
        model_annots["final_norm"]["bias"] = ("fsdp",)
    if not cfg.tie_word_embeddings:
        model_annots["head"] = {"w": ("fsdp", "tp")}

    def stack_specs(shapes, annots, pos_strategies):
        return [
            jax.tree.map(
                lambda leaf, a: P(
                    "pp",
                    *param_spec(
                        leaf.shape[1:], a, axes, pos_strategies[q],
                        for_opt_state=for_opt_state,
                    ),
                ),
                shapes[q],
                annots,
                is_leaf=is_leaf,
            )
            for q in range(len(shapes))
        ]

    specs: Dict[str, Any] = {}
    for key in params_shape:
        if key == "enc_stages":
            specs[key] = stack_specs(
                params_shape[key], modeling.layer_annotations(cfg), enc_pos
            )
        elif key == "dec_stages":
            specs[key] = stack_specs(
                params_shape[key], modeling.layer_annotations(cfg, cross=True), dec_pos
            )
        else:
            specs[key] = jax.tree.map(
                lambda leaf, a: param_spec(
                    leaf.shape, a, axes, embed_strategy, for_opt_state=for_opt_state
                ),
                params_shape[key],
                model_annots[key],
                is_leaf=is_leaf,
            )
    return specs


def _make_section_fns(cfg: ModelConfig, hp: HybridParallelConfig, mesh, axes):
    """(enc_section, dec_section): run one virtual stage's layers with
    per-position sharding constraints + remat. Ragged divisions mask padding
    positions to identity (runs inside the manual-'pp' shard_map, so the
    stage index comes from lax.axis_index)."""
    lay = validate_encdec_pipeline(cfg, hp)
    enc_pos, dec_pos = lay.enc_pos, lay.dec_pos
    uneven_e = len(set(lay.div_e)) > 1
    uneven_d = len(set(lay.div_d)) > 1

    # the same per-layer rules as the pp=1 hook (hybrid._make_layer_hook)
    enc_placed = [placement.place_layer(cfg, s, mesh, axes) for s in enc_pos]
    dec_placed = [placement.place_layer(cfg, s, mesh, axes) for s in dec_pos]
    cos_e = modeling.rope_tables(cfg, cfg.enc_seq) if cfg.pos_embed == "rope" else None

    def enc_section(stage_params, x):
        n_active = (
            jnp.asarray(lay.div_e)[jax.lax.axis_index("pp")] if uneven_e else None
        )
        for q, s in enumerate(enc_pos):
            x = constrain(x, mesh, placement.activation_spec(axes, s))
            lcfg, place = enc_placed[q]
            run = lambda x_, lp_, lcfg=lcfg, place=place: modeling.encoder_layer(
                x_, lp_, lcfg, cos_e, remat_attn=(s.ckpt == "selective"), place=place
            )
            if s.ckpt == "full":
                run = jax.checkpoint(run)
            out = run(x, stage_params[q])
            x = out if n_active is None else jnp.where(q < n_active, out, x)
        return x

    def dec_section(stage_params, x, ctx):
        cos_d = (
            modeling.rope_tables(cfg, x.shape[1]) if cfg.pos_embed == "rope" else None
        )
        n_active = (
            jnp.asarray(lay.div_d)[jax.lax.axis_index("pp")] if uneven_d else None
        )
        for q, s in enumerate(dec_pos):
            x = constrain(x, mesh, placement.activation_spec(axes, s))
            lcfg, place = dec_placed[q]
            run = lambda x_, lp_, lcfg=lcfg, place=place: modeling.decoder_layer(
                x_, lp_, lcfg, cos_d, None,
                remat_attn=(s.ckpt == "selective"), enc_out=ctx, place=place,
            )
            if s.ckpt == "full":
                run = jax.checkpoint(run)
            out = run(x, stage_params[q])
            x = out if n_active is None else jnp.where(q < n_active, out, x)
        return x

    return enc_section, dec_section


def build_encdec_pipeline_runtime(
    cfg: ModelConfig,
    hp: HybridParallelConfig,
    mesh: Mesh,
    axes: MeshAxes,
    adam: AdamConfig,
    global_batch_size: int,
    seq_len: int,
):
    from galvatron_tpu.parallel.hybrid import HybridParallelRuntime

    pp, chunks = hp.pp, max(1, hp.chunks)
    if global_batch_size % chunks:
        raise ValueError(f"global batch {global_batch_size} not divisible by chunks {chunks}")
    mb = global_batch_size // chunks
    validate_encdec_pipeline(cfg, hp)
    enc_section, dec_section = _make_section_fns(cfg, hp, mesh, axes)

    S_e = cfg.enc_seq
    S_d = cfg.sample_len - cfg.enc_seq  # decoder input length (dec[:, :-1])
    # two coupled sub-pipelines advancing in lockstep each tick: every device
    # runs its ENCODER section on chunk t-s and its DECODER section on chunk
    # t-pp-s. The encoder send rides a wrapped ring (device pp-1's finished
    # encoder output reaches device 0 exactly when that chunk's decoder
    # starts there); decoder (y, ctx) rides the plain chain. Every device
    # does real work on both sections every steady-state tick — no stage-
    # diverging control flow (GSPMD resharding collectives span stages, so a
    # per-stage lax.cond deadlocks; verified on the CPU sim), no 2x waste.
    ring_wrap = [(i, (i + 1) % pp) for i in range(pp)]
    chain = [(i, i + 1) for i in range(pp - 1)]
    # last useful write: chunk chunks-1's decoder at device pp-1, tick
    # (chunks-1) + pp + (pp-1) = chunks + 2pp - 2 -> T = chunks + 2pp - 1
    T = chunks + 2 * pp - 1
    full_spec = P(("pp",) + axes.data_axes, None, None)

    def pipeline(enc_stages, dec_stages, enc_norm, enc_mbs, dec_mbs):
        """Manual-'pp' shard_map body. enc_mbs (chunks, mb, S_e, H) and
        dec_mbs (chunks, mb, S_d, H) are replicated; returns (1, chunks, mb,
        S_d, H) — real decoder outputs in the pp-1 slice."""
        enc_stages = jax.tree.map(lambda a: jnp.squeeze(a, 0), enc_stages)
        dec_stages = jax.tree.map(lambda a: jnp.squeeze(a, 0), dec_stages)
        s = jax.lax.axis_index("pp")
        h = cfg.hidden_size
        carry0 = {
            "enc": jnp.zeros((mb, S_e, h), enc_mbs.dtype),
            "dec": jnp.zeros((mb, S_d, h), enc_mbs.dtype),
            "ctx": jnp.zeros((mb, S_e, h), enc_mbs.dtype),
            "ys": jnp.zeros((chunks + 1, mb, S_d, h), enc_mbs.dtype),
        }

        def tick(carry, t):
            recv_e = jax.lax.ppermute(carry["enc"], "pp", ring_wrap)
            recv_d = jax.lax.ppermute(carry["dec"], "pp", chain)
            recv_ctx = jax.lax.ppermute(carry["ctx"], "pp", chain)

            m_e = jnp.clip(t - s, 0, chunks - 1)
            m_d_raw = t - pp - s
            m_d = jnp.clip(m_d_raw, 0, chunks - 1)
            enc_emb = jax.lax.dynamic_index_in_dim(enc_mbs, m_e, keepdims=False)
            dec_emb = jax.lax.dynamic_index_in_dim(dec_mbs, m_d, keepdims=False)

            # encoder sub-pipeline
            x_in = jnp.where(s == 0, enc_emb, recv_e)
            enc_out = enc_section(enc_stages, x_in)

            # decoder sub-pipeline: device 0 enters the chunk whose encoder
            # output just wrapped around (recv_e is chunk t-pp's enc_out
            # there); enc_final_norm is token-local — SPMD-safe
            y_in = jnp.where(s == 0, dec_emb, recv_d)
            ctx_in = jnp.where(
                s == 0, modeling.norm(recv_e, enc_norm, cfg), recv_ctx
            )
            y_out = dec_section(dec_stages, y_in, ctx_in)

            # device pp-1 holds the finished decoder outputs (gpipe-style)
            valid = (m_d_raw >= 0) & (m_d_raw < chunks)
            slot = jnp.where(valid, m_d, chunks)
            ys = jax.lax.dynamic_update_index_in_dim(carry["ys"], y_out, slot, 0)
            return {"enc": enc_out, "dec": y_out, "ctx": ctx_in, "ys": ys}, None

        carry, _ = jax.lax.scan(tick, carry0, jnp.arange(T))
        return carry["ys"][None, :chunks]

    pipe_sm = jax.shard_map(
        pipeline,
        mesh=mesh,
        in_specs=(P("pp"), P("pp"), P(), P(), P()),
        out_specs=P("pp"),
        axis_names={"pp"},
        check_vma=False,
    )

    def loss_fn(params, batch):
        enc_tokens = batch[:, :S_e]
        dec = batch[:, S_e:]
        dec_tokens, labels = dec[:, :-1], dec[:, 1:]
        xe = modeling.embed(enc_tokens, params, cfg)
        xd = modeling.embed(dec_tokens, params, cfg)
        xe = constrain(xe, mesh, full_spec)
        xd = constrain(xd, mesh, full_spec)
        enc_mbs = xe.reshape(chunks, mb, S_e, cfg.hidden_size)
        dec_mbs = xd.reshape(chunks, mb, S_d, cfg.hidden_size)
        ys = pipe_sm(
            params["enc_stages"], params["dec_stages"], params["enc_final_norm"],
            enc_mbs, dec_mbs,
        )  # (pp, chunks, mb, S_d, H); real outputs in the pp-1 slice
        y = ys[-1].reshape(global_batch_size, S_d, cfg.hidden_size)
        y = constrain(y, mesh, full_spec)
        y = modeling.norm(y, params["final_norm"], cfg)
        logits = modeling.lm_head(y, params, cfg)
        ssum, n = modeling.cross_entropy_sum(logits, labels, remat=modeling.ce_remat(cfg))
        return ssum / jnp.maximum(n, 1)

    fp16 = hp.mixed_precision == "fp16"
    scaler_cfg = LossScalerConfig()

    def gpipe_train_step(state, batch):
        if fp16:
            loss, grads = scaled_value_and_grad(loss_fn, state["scaler"]["scale"])(
                state["params"], batch
            )
            return apply_update_with_scaler(state, loss, grads, adam, scaler_cfg)
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch)
        new_params, new_opt = adamw_update(state["params"], grads, state["opt"], adam)
        return {"params": new_params, "opt": new_opt, "step": state["step"] + 1}, loss

    # ------------------------------------------------------------------
    # 1F1B (pipedream_flush) ordering: hand-written backward over the coupled
    # sub-pipelines. The coupled pipeline is an interleaved virtual pipeline
    # of depth 2*pp (enc virtual stage s and dec virtual stage pp+s live on
    # device s), so the backward mirrors pipeline_1f1b: the dec backward wave
    # starts at the last device in the SAME tick as that chunk's dec forward,
    # rides the down-chain accumulating the cross-attention context cotangent,
    # wraps at device 0 to seed the enc backward wave. Backward recomputes
    # each section from stashed inputs (ring buffers bounded by the schedule
    # depth, independent of chunks — the 1F1B property the gpipe-ordered
    # autodiff backward lacks). enc_final_norm is folded INTO the dec section
    # here (ctx rides the chain pre-norm), so its vjp and parameter grads fall
    # out of the per-stage dec vjp with no separate norm bookkeeping.
    #
    #   enc fwd: m = t - s            dec fwd: m = t - pp - s
    #   dec bwd: m = t - (3pp-2) + s  enc bwd: m = t - (4pp-2) + s
    #   T = chunks + 4pp - 2;  stashes: enc min(chunks, 4pp-1),
    #   dec/ctx min(chunks, 2pp-1)   (+1 sacrificial slot each)
    # ------------------------------------------------------------------
    from galvatron_tpu.parallel.pipeline_1f1b import _head_loss

    head_keys = ("final_norm", "embed") if cfg.tie_word_embeddings else ("final_norm", "head")
    n_se = min(chunks, 4 * pp - 1)
    n_sd = min(chunks, 2 * pp - 1)
    T_1f1b = chunks + 4 * pp - 2
    n_static = mb * S_d  # loss-carrying positions per micro-batch
    chain_down = [(i + 1, i) for i in range(pp - 1)]
    ring_wrap_down = [(i, (i - 1) % pp) for i in range(pp)]

    def dec_sec_norm(dec_stages_, enc_norm_, y, pre_ctx):
        return dec_section(dec_stages_, y, modeling.norm(pre_ctx, enc_norm_, cfg))

    def pipeline_body_1f1b(enc_stages, dec_stages, enc_norm, head_sub,
                           enc_mbs, dec_mbs, labels_mbs, scale):
        enc_stages = jax.tree.map(lambda a: jnp.squeeze(a, 0), enc_stages)
        dec_stages = jax.tree.map(lambda a: jnp.squeeze(a, 0), dec_stages)
        s = jax.lax.axis_index("pp")
        is_last = s == pp - 1
        is_first = s == 0
        h = cfg.hidden_size
        dt = enc_mbs.dtype
        ea = (mb, S_e, h)
        da = (mb, S_d, h)
        f32 = lambda tree: jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), tree)

        carry0 = {
            "fe": jnp.zeros(ea, dt),       # enc fwd send (wrapped up-ring)
            "fd": jnp.zeros(da, dt),       # dec fwd send (up chain)
            "fctx": jnp.zeros(ea, dt),     # pre-norm ctx send (up chain)
            "bdy": jnp.zeros(da, dt),      # dec bwd dy send (down chain)
            "bdctx": jnp.zeros(ea, jnp.float32),  # accumulated dctx (down chain)
            "be": jnp.zeros(ea, jnp.float32),     # enc bwd seed (wrapped down-ring)
            "bey": jnp.zeros(ea, dt),      # enc bwd dy send (down chain)
            "stash_e": jnp.zeros((n_se + 1,) + ea, dt),
            "stash_d": jnp.zeros((n_sd + 1,) + da, dt),
            "stash_ctx": jnp.zeros((n_sd + 1,) + ea, dt),
            "dw_e": f32(enc_stages),
            "dw_d": f32(dec_stages),
            "dnorm": f32(enc_norm),
            "dhead": f32(head_sub),
            "dxe": jnp.zeros((chunks + 1,) + ea, jnp.float32),
            "dxd": jnp.zeros((chunks + 1,) + da, jnp.float32),
            "loss_sum": jnp.zeros((), jnp.float32),
            "tok": jnp.zeros((), jnp.float32),
        }

        def tick(carry, t):
            re = jax.lax.ppermute(carry["fe"], "pp", ring_wrap)
            rd = jax.lax.ppermute(carry["fd"], "pp", chain)
            rctx = jax.lax.ppermute(carry["fctx"], "pp", chain)
            rdy_d = jax.lax.ppermute(carry["bdy"], "pp", chain_down)
            rdctx = jax.lax.ppermute(carry["bdctx"], "pp", chain_down)
            rbe = jax.lax.ppermute(carry["be"], "pp", ring_wrap_down)
            rdy_e = jax.lax.ppermute(carry["bey"], "pp", chain_down)

            # ---- encoder forward
            m_ef = t - s
            ef_valid = (m_ef >= 0) & (m_ef < chunks)
            mef_c = jnp.clip(m_ef, 0, chunks - 1)
            x_in_e = jnp.where(
                is_first, jax.lax.dynamic_index_in_dim(enc_mbs, mef_c, keepdims=False), re
            )
            out_e = enc_section(enc_stages, x_in_e)
            e_slot = jnp.where(ef_valid, jnp.mod(mef_c, n_se), n_se)
            stash_e = jax.lax.dynamic_update_index_in_dim(
                carry["stash_e"], x_in_e, e_slot, 0
            )

            # ---- decoder forward (ctx rides the chain PRE-norm; device 0's
            # ctx is the wrapped enc output of the same chunk)
            m_df = t - pp - s
            df_valid = (m_df >= 0) & (m_df < chunks)
            mdf_c = jnp.clip(m_df, 0, chunks - 1)
            y_in = jnp.where(
                is_first, jax.lax.dynamic_index_in_dim(dec_mbs, mdf_c, keepdims=False), rd
            )
            ctx_in = jnp.where(is_first, re, rctx)
            out_d = dec_sec_norm(dec_stages, enc_norm, y_in, ctx_in)
            d_slot = jnp.where(df_valid, jnp.mod(mdf_c, n_sd), n_sd)
            stash_d = jax.lax.dynamic_update_index_in_dim(carry["stash_d"], y_in, d_slot, 0)
            stash_ctx = jax.lax.dynamic_update_index_in_dim(
                carry["stash_ctx"], ctx_in, d_slot, 0
            )

            # ---- decoder backward (recompute from stash; head loss on the
            # recomputed output at the last device, 1F1B same-tick fwd/bwd)
            m_db = t - (3 * pp - 2) + s
            db_valid = (m_db >= 0) & (m_db < chunks)
            mdb_c = jnp.clip(m_db, 0, chunks - 1)
            y_saved = jax.lax.dynamic_index_in_dim(
                stash_d, jnp.mod(mdb_c, n_sd), keepdims=False
            )
            ctx_saved = jax.lax.dynamic_index_in_dim(
                stash_ctx, jnp.mod(mdb_c, n_sd), keepdims=False
            )
            out_rec, d_vjp = jax.vjp(dec_sec_norm, dec_stages, enc_norm, y_saved, ctx_saved)
            labels = jax.lax.dynamic_index_in_dim(labels_mbs, mdb_c, keepdims=False)
            nll, head_vjp, cnt = jax.vjp(
                lambda hs, y: _head_loss(hs, y, labels, cfg), head_sub, out_rec,
                has_aux=True,
            )
            head_mask = (is_last & db_valid).astype(jnp.float32)
            dhead_mb, dy_head = head_vjp(head_mask * scale / n_static)
            dy_in = jnp.where(is_last, dy_head, rdy_d)
            dy_in = jnp.where(db_valid, dy_in, jnp.zeros_like(dy_in))
            dw_d_mb, dnorm_mb, dy_out, dctx_out = d_vjp(dy_in.astype(dt))
            dctx_acc = dctx_out.astype(jnp.float32) + jnp.where(
                is_last, jnp.zeros_like(rdctx), rdctx
            )
            dxd = jax.lax.dynamic_update_index_in_dim(
                carry["dxd"], dy_out.astype(jnp.float32),
                jnp.where(db_valid & is_first, mdb_c, chunks), 0,
            )

            # ---- encoder backward (seeded by device 0's accumulated dctx,
            # wrapped to the last device one tick later)
            m_eb = t - (4 * pp - 2) + s
            eb_valid = (m_eb >= 0) & (m_eb < chunks)
            meb_c = jnp.clip(m_eb, 0, chunks - 1)
            xe_saved = jax.lax.dynamic_index_in_dim(
                stash_e, jnp.mod(meb_c, n_se), keepdims=False
            )
            _, e_vjp = jax.vjp(enc_section, enc_stages, xe_saved)
            dye_in = jnp.where(is_last, rbe.astype(dt), rdy_e)
            dye_in = jnp.where(eb_valid, dye_in, jnp.zeros_like(dye_in))
            dw_e_mb, dxe_out = e_vjp(dye_in)
            dxe = jax.lax.dynamic_update_index_in_dim(
                carry["dxe"], dxe_out.astype(jnp.float32),
                jnp.where(eb_valid & is_first, meb_c, chunks), 0,
            )

            new_carry = {
                "fe": out_e,
                "fd": out_d,
                "fctx": ctx_in,
                "bdy": dy_out.astype(dt),
                "bdctx": dctx_acc,
                "be": dctx_acc,  # meaningful only from device 0 via the wrap
                "bey": dxe_out.astype(dt),
                "stash_e": stash_e,
                "stash_d": stash_d,
                "stash_ctx": stash_ctx,
                "dw_e": jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), carry["dw_e"], dw_e_mb
                ),
                "dw_d": jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), carry["dw_d"], dw_d_mb
                ),
                "dnorm": jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), carry["dnorm"], dnorm_mb
                ),
                "dhead": jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), carry["dhead"], dhead_mb
                ),
                "dxe": dxe,
                "dxd": dxd,
                "loss_sum": carry["loss_sum"] + nll * head_mask,
                "tok": carry["tok"] + cnt * head_mask,
            }
            return new_carry, None

        carry, _ = jax.lax.scan(tick, carry0, jnp.arange(T_1f1b))
        stack = lambda tree: jax.tree.map(lambda a: a[None], tree)
        return (
            carry["loss_sum"][None],
            carry["tok"][None],
            stack(carry["dw_e"]),
            stack(carry["dw_d"]),
            stack(carry["dnorm"]),
            stack(carry["dhead"]),
            carry["dxe"][None, :chunks],
            carry["dxd"][None, :chunks],
        )

    body_1f1b_sm = jax.shard_map(
        pipeline_body_1f1b,
        mesh=mesh,
        in_specs=(P("pp"), P("pp"), P(), P(), P(), P(), P(), P()),
        out_specs=tuple([P("pp")] * 8),
        axis_names={"pp"},
        check_vma=False,
    )

    def train_step_1f1b(state, batch):
        params = state["params"]
        scale = state["scaler"]["scale"] if fp16 else jnp.ones((), jnp.float32)
        enc_tokens = batch[:, :S_e]
        dec = batch[:, S_e:]
        dec_tokens, labels = dec[:, :-1], dec[:, 1:]
        head_sub = {k: params[k] for k in head_keys}

        def embed_fn(embed_params):
            pe = {"embed": embed_params}
            xe = modeling.embed(enc_tokens, pe, cfg)
            xd = modeling.embed(dec_tokens, pe, cfg)
            return constrain(xe, mesh, full_spec), constrain(xd, mesh, full_spec)

        (xe, xd), embed_vjp = jax.vjp(embed_fn, params["embed"])
        enc_mbs = xe.reshape(chunks, mb, S_e, cfg.hidden_size)
        dec_mbs = xd.reshape(chunks, mb, S_d, cfg.hidden_size)
        labels_mbs = labels.reshape(chunks, mb, S_d)

        (loss_s, tok_s, dw_e_s, dw_d_s, dnorm_s, dhead_s, dxe_s, dxd_s) = body_1f1b_sm(
            params["enc_stages"], params["dec_stages"], params["enc_final_norm"],
            head_sub, enc_mbs, dec_mbs, labels_mbs, scale,
        )
        loss_sum = loss_s[-1]
        tok = jnp.maximum(tok_s[-1], 1.0)
        d_head = jax.tree.map(lambda a: a[-1], dhead_s)
        # enc_final_norm grads accumulate on EVERY device (each dec sub-stage
        # back-propagates through the folded norm) — sum the pp stack
        d_norm = jax.tree.map(lambda a: a.sum(axis=0), dnorm_s)
        dxe_full = dxe_s[0].reshape(global_batch_size, S_e, cfg.hidden_size)
        dxd_full = dxd_s[0].reshape(global_batch_size, S_d, cfg.hidden_size)
        (d_embed,) = embed_vjp((dxe_full.astype(xe.dtype), dxd_full.astype(xd.dtype)))

        grads: Dict[str, Any] = {
            "enc_stages": dw_e_s,
            "dec_stages": dw_d_s,
            "embed": d_embed,
            "enc_final_norm": d_norm,
        }
        for k in head_keys:
            if k == "embed":
                grads["embed"] = jax.tree.map(
                    lambda a, b: a.astype(jnp.float32) + b, grads["embed"], d_head["embed"]
                )
            else:
                grads[k] = d_head[k]
        gdenom = tok * scale / n_static
        grads = {k: jax.tree.map(lambda g: g / gdenom, v) for k, v in grads.items()}
        loss = loss_sum / tok

        if fp16:
            return apply_update_with_scaler(state, loss, grads, adam, scaler_cfg)
        new_params, new_opt = adamw_update(params, grads, state["opt"], adam)
        return {"params": new_params, "opt": new_opt, "step": state["step"] + 1}, loss

    train_step = (
        train_step_1f1b if hp.pipeline_type == "pipedream_flush" else gpipe_train_step
    )

    def init_state(key):
        params = init_encdec_pipeline_params(key, cfg, hp)
        state = {"params": params, "opt": init_opt_state(params), "step": jnp.zeros((), jnp.int32)}
        if fp16:
            state["scaler"] = init_scaler_state(scaler_cfg)
        return state

    def state_from(flat_params):
        params = restack_flat_encdec(flat_params, cfg, hp)
        state = {"params": params, "opt": init_opt_state(params), "step": jnp.zeros((), jnp.int32)}
        if fp16:
            state["scaler"] = init_scaler_state(scaler_cfg)
        return state

    state_shape = jax.eval_shape(init_state, jax.random.key(0))
    specs = {
        "params": encdec_param_specs(state_shape["params"], cfg, hp, axes),
        "opt": {
            "mu": encdec_param_specs(state_shape["params"], cfg, hp, axes, for_opt_state=True),
            "nu": encdec_param_specs(state_shape["params"], cfg, hp, axes, for_opt_state=True),
            "count": P(),
        },
        "step": P(),
    }
    if "scaler" in state_shape:
        specs["scaler"] = jax.tree.map(lambda _: P(), state_shape["scaler"])
    shardings = sharding_tree(mesh, specs)
    batch_sharding = NamedSharding(mesh, P(("pp",) + axes.data_axes, None))
    copts = cpu_sim_compiler_options(mesh)
    jit_train = jax.jit(
        train_step,
        in_shardings=(shardings, batch_sharding),
        out_shardings=(shardings, NamedSharding(mesh, P())),
        donate_argnums=(0,),
        compiler_options=copts,
    )
    jit_eval = jax.jit(
        lambda state, batch: loss_fn(state["params"], batch),
        in_shardings=(shardings, batch_sharding),
        out_shardings=NamedSharding(mesh, P()),
        compiler_options=copts,
    )
    jit_init = jax.jit(init_state, out_shardings=shardings)
    jit_state_from = jax.jit(state_from, out_shardings=shardings)
    return HybridParallelRuntime(
        cfg=cfg, hp=hp, mesh=mesh, axes=axes, adam=adam,
        train_step=jit_train, eval_loss=jit_eval, init_state=jit_init,
        state_shardings=shardings, batch_sharding=batch_sharding,
        init_state_from=jit_state_from,
        flatten_params=lambda sp: flatten_encdec(sp, cfg, hp),
        restack_params=lambda fp: restack_flat_encdec(fp, cfg, hp),
    )
