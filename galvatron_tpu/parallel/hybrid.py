"""Hybrid-parallel runtime: build and execute a layer-heterogeneous strategy.

The TPU-native equivalent of the reference's 7-step model construction
(construct_hybrid_parallel_model_api, galvatron/core/hybrid_parallel_model.py:81-153):

  reference step                         → here
  [0] gen_comm_groups                    → build_mesh (one Mesh, binary axes)
  [1] construct_tensor_parallel_model    → per-layer param specs ('tp' dims)
  [2] construct_sequential_model         → the model is already functional
  [3] wrap relocation modules            → with_sharding_constraint per layer
  [4] PipelineParallel stage placement   → galvatron_tpu.parallel.pipeline
  [5] per-layer FSDP wrapping            → 'fsdp' dims in param/opt specs
  [6] per-layer checkpoint wrapping      → jax.checkpoint per layer

``HybridParallelRuntime`` owns the jitted ``train_step`` (the
GalvatronModel.forward_backward equivalent, reference:
galvatron/core/hybrid_parallel_model.py:15-35), dispatching between the
no-pipeline GSPMD path (pp=1, with optional micro-batch gradient
accumulation) and the shard_map pipeline schedules (pp>1).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
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
from galvatron_tpu.models import mixers, modeling
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.parallel import placement
from galvatron_tpu.parallel.mesh import MeshAxes, build_mesh, global_batch_spec
from galvatron_tpu.parallel.sharding import (
    constrain,
    cp_shard_axes,
    overlap_grad_sync,
    param_spec,
    sharding_tree,
)


#: what a dropless top-k MoE model's train state carries of its last step
#: (``state["moe_stats"]``), logged in the trainer's ``train_iter`` record
MOE_STATS = ("moe_aux_loss", "moe_load_max_over_mean")
#: two more where the model holds a share of its experts (``cfg.moe_share``): the
#: pairs a token puts on the held experts, and the share of the worst-case row
#: buffer whose tiles are in use (the work left where the held path is bounded)
MOE_HELD_STATS = ("moe_held_pairs_per_token", "moe_held_rows_share")


def moe_stat_names(cfg) -> tuple:
    return MOE_STATS + (MOE_HELD_STATS if cfg.moe_holds_share else ())


def model_param_specs(
    params_shape: Any, cfg: ModelConfig, hp: HybridParallelConfig, axes: MeshAxes,
    *, for_opt_state: bool = False,
) -> Any:
    """Spec tree for the whole model: per-layer strategies for the decoder
    layers, vocab_tp/embed_dp for embedding+head+final norm (reference:
    hp_config_whole_model, galvatron/core/hybrid_parallel_config.py:141-179)."""
    annots = modeling.model_annotations(cfg)
    embed_strategy = LayerStrategy(
        tp=hp.vocab_tp, tp_consec=True, dp_type=hp.embed_dp_type, sp=hp.vocab_sp
    )
    ps = lambda leaf, a, s: param_spec(leaf.shape, a, axes, s, for_opt_state=for_opt_state)
    specs: Dict[str, Any] = {}
    is_leaf = lambda x: hasattr(x, "shape")
    E = cfg.enc_layers  # strategy indices: encoder stack first, then decoder
    for key in params_shape:
        if key == "enc_layers":
            specs["enc_layers"] = [
                jax.tree.map(
                    functools.partial(ps, s=hp.layer_strategies[i]),
                    params_shape["enc_layers"][i],
                    annots["enc_layers"][i],
                    is_leaf=is_leaf,
                )
                for i in range(len(params_shape["enc_layers"]))
            ]
        elif key == "layers":
            specs["layers"] = [
                jax.tree.map(
                    functools.partial(ps, s=hp.layer_strategies[E + i]),
                    params_shape["layers"][i],
                    annots["layers"][i],
                    is_leaf=is_leaf,
                )
                for i in range(len(params_shape["layers"]))
            ]
        else:
            specs[key] = jax.tree.map(
                functools.partial(ps, s=embed_strategy),
                params_shape[key],
                annots[key],
                is_leaf=is_leaf,
            )
    return specs


def state_specs(state_shape, cfg, hp, axes):
    """Specs for the full train state {params, opt{mu,nu,count}, step}."""
    pspec = model_param_specs(state_shape["params"], cfg, hp, axes)
    ospec = model_param_specs(state_shape["params"], cfg, hp, axes, for_opt_state=True)
    specs = {
        "params": pspec,
        "opt": {"mu": ospec, "nu": ospec, "count": P()},
        "step": P(),
    }
    for key in ("scaler", "moe_stats"):
        # replicated scalars: the fp16 dynamic loss scale; the last step's
        # auxiliary loss and expert load of a dropless top-k MoE model
        if key in state_shape:
            specs[key] = jax.tree.map(lambda _: P(), state_shape[key])
    return specs


@dataclass
class HybridParallelRuntime:
    """Executable hybrid-parallel model (GalvatronModel equivalent)."""

    cfg: ModelConfig
    hp: HybridParallelConfig
    mesh: Mesh
    axes: MeshAxes
    adam: AdamConfig
    train_step: Callable  # (state, batch) -> (state, loss)
    eval_loss: Callable  # (state, batch) -> loss
    init_state: Callable  # (key) -> state
    state_shardings: Any
    batch_sharding: Any = None  # NamedSharding of the token batch
    # (flat model param tree) -> fresh state carrying those weights — the
    # pretrained-weight entry point (e.g. models/convert.py HF import). The
    # pipeline runtime restacks transformer layers per stage first.
    init_state_from: Callable = None
    # portable-checkpoint layout transforms (None = params are already flat):
    # flatten_params: engine layout -> flat {layers: [...]} tree;
    # restack_params: the inverse. Checkpoints are always SAVED flat so
    # resume works across pipeline degrees/schedules (core/checkpoint.py).
    flatten_params: Callable = None
    restack_params: Callable = None
    # {"ring": n, "plain": m, "batchwise": k}: projection seams of the plan's
    # tp_overlap layers that take the collective-matmul ring / stay the plain
    # einsum, and the ring seams whose head-major all-gather side pipelines
    # over the batch (placement.tp_overlap_seam_counts); the trainer puts it
    # in the run's fingerprint and on the build_runtime span
    tp_overlap_seams: Any = None

    def shard_batch(self, batch_np):
        """Global on-device batch from a (host-replicated) numpy batch.

        Single-process: a device_put. Multi-host (TPU pods over DCN): every
        process runs the same deterministic loader, and
        ``jax.make_array_from_callback`` materializes only the rows this
        process's addressable devices own — the distributed data path the
        reference gets from DistributedSampler + NCCL
        (utils/training_utils.py:14-23)."""
        import numpy as _np

        batch_np = _np.asarray(batch_np)
        if self.batch_sharding is None or jax.process_count() == 1:
            if self.batch_sharding is None:
                return jnp.asarray(batch_np)
            return jax.device_put(batch_np, self.batch_sharding)
        return jax.make_array_from_callback(
            batch_np.shape, self.batch_sharding, lambda idx: batch_np[idx]
        )


def _make_layer_hook(cfg: ModelConfig, hp: HybridParallelConfig, mesh: Mesh, axes: MeshAxes):
    """Per-layer execution hook: sharding-constraint boundary (redistribution)
    + optional remat (checkpoint_wrapper) + ring-attention dispatch."""

    # async ZeRO gradient overlap (sharding.overlap_grad_sync): the hook pins
    # each zero2/zero3 layer's param cotangents to their reduce-scattered
    # sharding, so the per-layer gradient buckets issue during backward
    grad_annots = modeling.model_annotations(cfg) if hp.grad_overlap else None
    # (a decoder layer is placed under its own view of the model: its window, its rope)
    placed = [placement.place_layer(
        cfg.layer_view(i - cfg.enc_layers) if i >= cfg.enc_layers else cfg, s, mesh, axes)
        for i, s in enumerate(hp.layer_strategies)]

    def hook(i: int, x, lp, enc_out=None, seg_ids=None):
        s = hp.layer_strategies[i]
        with jax.named_scope("redistribute"):
            x = constrain(x, mesh, placement.activation_spec(axes, s))
        layer_cfg, place = placed[i]
        if layer_cfg.pos_embed == "rope":
            # packed rows: per-segment position reset → per-row gathered tables
            cos_sin = (
                modeling.packed_rope_tables(
                    layer_cfg, modeling.positions_from_segments(seg_ids)
                )
                if seg_ids is not None
                else modeling.rope_tables(layer_cfg, x.shape[1])
            )
        else:
            cos_sin = None
        alibi = (
            jnp.asarray(modeling.alibi_slopes(layer_cfg.num_heads))
            if layer_cfg.pos_embed == "alibi"
            else None
        )
        is_encoder = cfg.enc_layers > 0 and i < cfg.enc_layers
        if grad_annots is not None and s.dp_type in ("zero2", "zero3"):
            la = (
                grad_annots["enc_layers"][i]
                if is_encoder
                else grad_annots["layers"][i - cfg.enc_layers]
            )
            lp = overlap_grad_sync(lp, la, mesh, axes, s)

        def run(x_, lp_):
            if cfg.swin_depths:
                return modeling.swin_layer(
                    x_, lp_, cfg, i, remat_attn=(s.ckpt == "selective")
                )
            if is_encoder:
                return modeling.encoder_layer(
                    x_, lp_, layer_cfg, cos_sin, remat_attn=(s.ckpt == "selective"),
                    place=place,
                )
            if s.cp > 1:
                cp_axes = axes.cp_axes(s.tp, s.tp_consec, s.cp)
                cp_kw = cp_shard_axes(s, axes)
                if s.cp_impl == "a2a":
                    from galvatron_tpu.parallel.ulysses import ulysses_decoder_layer

                    return ulysses_decoder_layer(
                        x_, lp_, layer_cfg, mesh, cp_axes, cos_sin, place=place, **cp_kw
                    )
                from galvatron_tpu.parallel.ring import ring_decoder_layer

                return ring_decoder_layer(
                    x_, lp_, layer_cfg, mesh, cp_axes, cos_sin, place=place, **cp_kw
                )
            return modeling.decoder_layer(
                x_, lp_, layer_cfg, cos_sin, alibi,
                remat_attn=(s.ckpt == "selective"), enc_out=enc_out,
                seg_ids=seg_ids, place=place,
            )

        if (
            (place.tp_overlap or cfg.layer_kinds) and not cfg.swin_depths and not is_encoder
            and enc_out is None and seg_ids is None
        ):
            # layers of one plan entry (and, in a hybrid stack, of one kind)
            # are one program: see _decoder_layer_once
            return _decoder_layer_once(
                x, lp, cos_sin, alibi, cfg=layer_cfg, place=place, ckpt=s.ckpt)
        if s.ckpt == "full":
            run = jax.checkpoint(run)
        return run(x, lp)

    return hook


@functools.partial(jax.jit, static_argnames=("cfg", "place", "ckpt"))
def _decoder_layer_once(x, lp, cos_sin, alibi, *, cfg: ModelConfig, place, ckpt):
    """A tp_overlap decoder layer, or a layer of a hybrid stack, through
    ``jax.jit``: layers with the same configuration, placement and parameter
    tree (all 24 of the four-chip cell; the nine state-space layers and the
    one attention layer of a granite period: two programs for ten layers) are
    traced, differentiated and lowered once, not once each — the rings'
    unrolled steps and the chunked scan are the longest Python a layer has,
    and set-up time is gated. The caller's ``layer_<i>`` scope stays around
    the call."""

    def run(x_, lp_):
        return modeling.decoder_layer(
            x_, lp_, cfg, cos_sin, alibi, remat_attn=(ckpt == "selective"), place=place)

    if ckpt == "full":
        run = jax.checkpoint(run)
    return run(x, lp)


def build_runtime(
    cfg: ModelConfig,
    hp: HybridParallelConfig,
    mesh: Optional[Mesh] = None,
    axes: Optional[MeshAxes] = None,
    adam: AdamConfig = AdamConfig(),
    global_batch_size: int = 8,
    seq_len: Optional[int] = None,
) -> HybridParallelRuntime:
    """Construct the jitted train/eval step for (model config, hybrid strategy).

    pp=1 → pure-GSPMD path with optional micro-batch grad accumulation
    (the no_pipeline_forward_backward equivalent, reference:
    galvatron/core/pipeline/pipeline.py:173-235); pp>1 → shard_map pipeline
    (galvatron_tpu.parallel.pipeline).
    """
    if mesh is None:
        mesh, axes = build_mesh(pp=hp.pp)
    assert axes is not None
    if hp.num_layers != cfg.total_layers:
        raise ValueError(
            f"strategy has {hp.num_layers} layer entries but model has "
            f"{cfg.total_layers} (encoder + decoder) layers"
        )
    hp.validate(mesh.devices.size)
    if not cfg.causal and any(s.cp > 1 for s in hp.layer_strategies):
        raise ValueError(
            "context parallelism (cp>1) is causal-only (ring/Ulysses kernels "
            "assume a causal mask); encoder models must use tp/sp instead"
        )
    if cfg.enc_layers > 0:
        if any(s.cp > 1 for s in hp.layer_strategies):
            raise ValueError("context parallelism is not supported for enc-dec models")
    if cfg.pack_sequences:
        # packed sequences (galvatron_tpu.data): supported on the GSPMD path
        # and the gpipe/1F1B stage-stacked pipelines. Everything the segment
        # mask cannot reach is refused loudly rather than silently attending
        # across documents.
        if cfg.objective != "clm" or cfg.enc_layers or cfg.image_size:
            raise ValueError(
                "pack_sequences requires a decoder-only CLM model "
                "(enc-dec / vision / mlm rows carry no segment layout)"
            )
        if cfg.attn_impl != "xla":
            raise ValueError(
                "pack_sequences requires attn_impl='xla': the flash/ring "
                "Pallas kernels carry no segment mask, and running them would "
                "silently attend across packed documents"
            )
        if any(s.cp > 1 for s in hp.layer_strategies):
            raise ValueError(
                "pack_sequences is incompatible with context parallelism "
                "(ring/Ulysses assume a plain causal mask)"
            )
        if hp.pp > 1 and hp.vpp > 1:
            raise ValueError(
                "pack_sequences is not threaded through the interleaved "
                "(vpp>1) schedule; use vpp=1 pipelines"
            )
    # what the model's layers do not implement (a recurrent kind's tp / cp /
    # packing, pp over interleaved kinds, the dropless expert path's ep / pp / cp /
    # fp16: models/mixers.limits) is refused here, by name: nothing is silently
    # mis-sharded, nothing falls back to another path
    for limit in mixers.limits(cfg):
        at = limit.broken_by(cfg, hp)
        if at:
            raise ValueError(limit.sentence(at))
    if cfg.attention_multiplier is not None and any(s.cp > 1 for s in hp.layer_strategies):
        raise ValueError(
            "context parallelism (cp>1) is not implemented with attention_multiplier: "
            "the ring/Ulysses layers scale by 1/sqrt(head_dim); use cp=1"
        )
    if (cfg.attn_gate or cfg.post_norms) and any(s.cp > 1 for s in hp.layer_strategies):
        raise ValueError(
            "context parallelism (cp>1) is not implemented with attn_gate or post_norms: the "
            "ring/Ulysses layers project and add their output themselves, without the gate "
            "and without the norms after a block; use cp=1"
        )
    seq_len = seq_len or cfg.sample_len

    # the strategy's activation-recompute mode rides the model config so
    # every execution path (GSPMD hook, all pipeline engines, the head/loss
    # seams) sees the same policy
    if cfg.mlp_recompute != hp.mlp_recompute:
        cfg = cfg.replace(mlp_recompute=hp.mlp_recompute)
    if cfg.dtype != jnp.float32 and hp.mixed_precision == "fp32":
        cfg = cfg.replace(dtype=jnp.float32)
    if hp.mixed_precision == "bf16" and cfg.dtype == jnp.float32:
        cfg = cfg.replace(dtype=jnp.bfloat16)
    # fp16 parity path (reference: --mixed_precision fp16, core/arguments.py:
    # 104-106 + megatron grad_scaler): fp16 compute, fp32 master params,
    # dynamic loss scaling with skip-on-overflow. bf16 is the TPU-native
    # choice; fp16 exists so reference configs port unchanged.
    fp16 = hp.mixed_precision == "fp16"
    if fp16:
        cfg = cfg.replace(dtype=jnp.float16)
        scaler_cfg = LossScalerConfig()

    seams = placement.tp_overlap_seam_counts(cfg, hp, mesh, axes, global_batch_size, seq_len)
    if hp.pp > 1:
        if cfg.swin_depths:
            from galvatron_tpu.parallel.pipeline_swin import (
                build_swin_pipeline_runtime,
            )

            rt = build_swin_pipeline_runtime(
                cfg, hp, mesh, axes, adam, global_batch_size, seq_len
            )
        elif cfg.enc_layers > 0:
            from galvatron_tpu.parallel.pipeline_encdec import (
                build_encdec_pipeline_runtime,
            )

            rt = build_encdec_pipeline_runtime(
                cfg, hp, mesh, axes, adam, global_batch_size, seq_len
            )
        else:
            from galvatron_tpu.parallel.pipeline import build_pipeline_runtime

            rt = build_pipeline_runtime(cfg, hp, mesh, axes, adam, global_batch_size, seq_len)
        rt.tp_overlap_seams = seams
        return rt

    hook = _make_layer_hook(cfg, hp, mesh, axes)

    def loss_fn(params, tokens_batch):
        return modeling.lm_loss(params, tokens_batch, cfg, layer_hook=hook)

    chunks = max(1, hp.chunks)
    if global_batch_size % chunks != 0:
        raise ValueError(
            f"global batch {global_batch_size} not divisible by chunks {chunks}"
        )

    def moe_grads_fn(params, batch):
        """(loss, grads, aux) of a dropless top-k MoE model: ``loss`` is the
        cross entropy alone (what is logged and checked); the gradient is that
        of cross entropy + moe_aux_coef * L_aux, the auxiliary loss weighted
        by each micro-batch's share of the loss tokens; ``aux`` holds the
        step's ``moe_aux_loss`` (same weighting) and ``moe_load_max_over_mean``
        (the fullest micro-batch's)."""
        if chunks == 1:
            def mean_objective(params):
                s, n, aux = modeling.moe_loss_sum(params, batch, cfg, layer_hook=hook)
                ce = s / jnp.maximum(n, 1)
                return ce + cfg.moe_aux_coef * aux["moe_aux_loss"], (ce, aux)

            (_, (loss, aux)), grads = jax.value_and_grad(mean_objective, has_aux=True)(params)
            return loss, grads, aux
        # micro-batches: sums, as grads_fn accumulates them
        b = batch.shape[0]
        mbs = batch.reshape(chunks, b // chunks, *batch.shape[1:])

        def sum_objective(params, mb):
            s, n, aux = modeling.moe_loss_sum(params, mb, cfg, layer_hook=hook)
            n = n.astype(jnp.float32)
            return s + cfg.moe_aux_coef * aux["moe_aux_loss"] * n, (s, n, aux)

        held = cfg.moe_holds_share

        def body(acc, mb):
            (_, (s, n, aux)), g = jax.value_and_grad(sum_objective, has_aux=True)(params, mb)
            acc_s, acc_n, acc_aux, acc_load, acc_g = acc[:5]
            out = (acc_s + s, acc_n + n, acc_aux + aux["moe_aux_loss"] * n,
                   jnp.maximum(acc_load, aux["moe_load_max_over_mean"]),
                   jax.tree.map(jnp.add, acc_g, g))
            if held:  # weighted like the auxiliary loss
                out += tuple(a + aux[name] * n for a, name in zip(acc[5:], MOE_HELD_STATS))
            return out, None

        zero = (jnp.zeros((), jnp.float32),) * 4 + (
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),)
        if held:
            zero += (jnp.zeros((), jnp.float32),) * len(MOE_HELD_STATS)
        with jax.named_scope("grad_accum"):
            (tot_s, tot_n, tot_aux, load, tot_g, *tot_held), _ = jax.lax.scan(body, zero, mbs)
        denom = jnp.maximum(tot_n, 1.0)
        aux = {"moe_aux_loss": tot_aux / denom, "moe_load_max_over_mean": load}
        if held:
            aux.update({name: tot / denom for name, tot in zip(MOE_HELD_STATS, tot_held)})
        return tot_s / denom, jax.tree.map(lambda g: g / denom, tot_g), aux

    def grads_fn(params, batch, scale=None):
        """(loss, grads); with ``scale`` (fp16) the backward runs on
        ``loss * scale`` and grads are returned unscaled in fp32."""
        if chunks == 1:
            if scale is None:
                return jax.value_and_grad(loss_fn)(params, batch)
            return scaled_value_and_grad(loss_fn, scale)(params, batch)
        # micro-batch gradient accumulation via scan (chunk_batch equivalent,
        # reference: galvatron/core/pipeline/utils.py:9-36). Accumulates
        # (nll_sum, token_count) so the result equals the unchunked global
        # token-mean even with uneven ignore_index masks per chunk.
        b = batch.shape[0]
        assert b % chunks == 0, f"global batch {b} not divisible by chunks {chunks}"
        mbs = batch.reshape(chunks, b // chunks, *batch.shape[1:])

        def sum_fn(params, mb):
            s, n = modeling.lm_loss_sum(params, mb, cfg, layer_hook=hook)
            return s, n

        # fp16: seed on the mean-equivalent loss (sum / static token count) so
        # cotangent magnitudes match the unchunked mean-loss path — a raw
        # sum-loss seed multiplies O(1) per-token cotangents by the full scale
        # and overflows fp16 immediately at the 2^16 initial scale
        n_static = (b // chunks) * modeling.loss_tokens_per_sample(cfg, batch.shape[1] - 1)

        def body(acc, mb):
            if scale is None:
                (s, n), g = jax.value_and_grad(sum_fn, has_aux=True)(params, mb)
            else:

                def scaled(p, mb_):
                    s_, n_ = sum_fn(p, mb_)
                    return s_ * (scale / n_static), (s_, n_)

                (_, (s, n)), g = jax.value_and_grad(scaled, has_aux=True)(params, mb)
            acc_s, acc_n, acc_g = acc
            return (acc_s + s, acc_n + n, jax.tree.map(jnp.add, acc_g, g)), None

        zero = (
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.int32),
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        )
        with jax.named_scope("grad_accum"):
            (tot_s, tot_n, tot_g), _ = jax.lax.scan(body, zero, mbs)
        denom = jnp.maximum(tot_n, 1).astype(jnp.float32)
        gdenom = denom if scale is None else denom * scale / n_static
        return tot_s / denom, jax.tree.map(lambda g: g / gdenom, tot_g)

    def train_step(state, batch):
        if fp16:
            loss, grads = grads_fn(state["params"], batch, state["scaler"]["scale"])
            return apply_update_with_scaler(state, loss, grads, adam, scaler_cfg)
        if cfg.moe_dropless:
            # the step's auxiliary loss and expert load ride the state, as the
            # fp16 scaler does: (state, loss) stays the step's whole signature
            # and the trainer reads them after the sync on the loss it makes anyway
            loss, grads, moe_stats = moe_grads_fn(state["params"], batch)
        else:
            loss, grads = grads_fn(state["params"], batch)
        new_params, new_opt = adamw_update(state["params"], grads, state["opt"], adam)
        new_state = {"params": new_params, "opt": new_opt, "step": state["step"] + 1}
        if cfg.moe_dropless:
            new_state["moe_stats"] = moe_stats
        return new_state, loss

    def state_from(params):
        state = {
            "params": params,
            "opt": init_opt_state(params),
            "step": jnp.zeros((), jnp.int32),
        }
        if fp16:
            state["scaler"] = init_scaler_state(scaler_cfg)
        if cfg.moe_dropless:
            state["moe_stats"] = {k: jnp.zeros((), jnp.float32) for k in moe_stat_names(cfg)}
        return state

    def init_state(key):
        return state_from(modeling.init_model_params(key, cfg))

    # shardings
    state_shape = jax.eval_shape(init_state, jax.random.key(0))
    specs = state_specs(state_shape, cfg, hp, axes)
    shardings = sharding_tree(mesh, specs)
    batch_sharding = NamedSharding(mesh, global_batch_spec(axes))

    jit_train = jax.jit(
        train_step,
        in_shardings=(shardings, batch_sharding),
        out_shardings=(shardings, NamedSharding(mesh, P())),
        donate_argnums=(0,),
    )
    jit_eval = jax.jit(
        lambda state, batch: loss_fn(state["params"], batch),
        in_shardings=(shardings, batch_sharding),
        out_shardings=NamedSharding(mesh, P()),
    )
    jit_init = jax.jit(init_state, out_shardings=shardings)
    jit_state_from = jax.jit(state_from, out_shardings=shardings)

    return HybridParallelRuntime(
        cfg=cfg, hp=hp, mesh=mesh, axes=axes, adam=adam,
        train_step=jit_train, eval_loss=jit_eval, init_state=jit_init,
        state_shardings=shardings, batch_sharding=batch_sharding,
        init_state_from=jit_state_from, tp_overlap_seams=seams,
    )


# --- AOT program registration (galvatron_tpu/aot): the trainer family -------
# One family covers EVERY engine build_runtime can dispatch to (GSPMD hybrid,
# gpipe/1F1B/interleaved shard_map pipelines, enc-dec, swin): they all expose
# the same jitted (state, batch) train_step / eval_loss seam, so the set of
# programs a plan needs is enumerable here with no data and no compile.


def _trainer_programs(ctx):
    import jax.numpy as _jnp

    from galvatron_tpu.aot.registry import ProgramSpec
    from galvatron_tpu.core.checkpoint import abstract_state_of

    rt = ctx.runtime
    if rt is None:
        rt = build_runtime(
            ctx.cfg, ctx.hp, mesh=ctx.mesh, axes=ctx.axes,
            adam=ctx.adam if ctx.adam is not None else AdamConfig(),
            global_batch_size=ctx.global_bsz, seq_len=ctx.seq_len,
        )
    state_abs = abstract_state_of(rt)
    seq = ctx.seq_len or rt.cfg.sample_len
    # the loader row contract lives in modeling.batch_row_width (packed rows
    # are 2·(S+1), not S+1) — same aval the fidelity harness lowers against
    # (search/memory_fidelity.measured_train_mb); a wrong width here would
    # warm a program the run never dispatches and wrongly drop the
    # watchdog's first-step compile grace
    batch_abs = jax.ShapeDtypeStruct(
        (ctx.global_bsz, modeling.batch_row_width(rt.cfg, seq)),
        _jnp.int32,
        sharding=rt.batch_sharding,
    )
    engine = "pipeline" if rt.hp.pp > 1 else "gspmd"
    # optimizer hyperparameters are CONSTANTS inside the compiled step — a
    # different lr/schedule is a different program, so they join the key;
    # exec_cfg is the runtime's EXECUTED config (build_runtime rewrites
    # dtype/mlp_recompute from the plan), the one both the trainer consult
    # and the elastic prewarm must key on to agree
    key_extra = {"adam": repr(rt.adam), "engine": engine}
    specs = [
        ProgramSpec(
            "train_step", rt.train_step, (state_abs, batch_abs),
            meta={"donate": (0,), "engine": engine, "key_extra": key_extra,
                  "exec_cfg": rt.cfg},
        ),
        ProgramSpec(
            "eval_loss", rt.eval_loss, (state_abs, batch_abs),
            meta={"engine": engine, "key_extra": {"engine": engine},
                  "exec_cfg": rt.cfg},
        ),
    ]
    if hasattr(rt.init_state, "lower"):  # some pipeline engines init host-side
        key_abs = jax.eval_shape(lambda: jax.random.key(0))
        specs.append(
            ProgramSpec("init_state", rt.init_state, (key_abs,),
                        meta={"engine": engine, "exec_cfg": rt.cfg,
                              "key_extra": {"engine": engine}})
        )
    return specs


def _register_aot_programs():
    from galvatron_tpu.aot.registry import register_program

    register_program(
        "trainer", _trainer_programs, needs_plan=True,
        programs=("train_step", "eval_loss", "init_state"),
    )


_register_aot_programs()
