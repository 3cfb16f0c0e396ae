"""1F1B (pipedream-flush) pipeline schedule with a hand-written backward.

The reference's pipedream_flush interleaves warmup forwards, steady-state
1F1B, and cooldown backwards to bound live activations at O(pp) micro-batches
per stage instead of GPipe's O(chunks) (reference:
galvatron/core/pipeline/pipeline.py:237-480; combined send/recv ops
:1076-1177; FSDP hook re-registration :392-404 — unnecessary here since JAX
grads are pure values).

SPMD formulation: one clocked scan over T = chunks + 2(pp-1) ticks inside a
manual-'pp' shard_map. On tick t, stage s:

  forward of micro-batch  m_f = t - s                (if 0 <= m_f < chunks)
  backward of micro-batch m_b = t - 2(pp-1) + s      (if 0 <= m_b < chunks)

so the last stage runs fwd(m) and bwd(m) in the same tick (loss is computed
in-pipeline), and stage s holds at most 2(pp-1-s)+1 in-flight micro-batches.
Backward recomputes the stage forward from a stashed input ring buffer of
min(chunks, 2(pp-1)+1) slots via jax.vjp — 1F1B-with-recompute, the natural
XLA-static-shape rendering of the schedule.

Forward activations ride ppermute s→s+1; cotangents ride ppermute s→s-1 —
both deterministic, replacing the deadlock-avoidance machinery of the NCCL
engine (reference pipeline.py:373-378,966-968).
"""

from __future__ import annotations

from typing import Any, Dict

import jax

import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from galvatron_tpu.core.optim import (
    AdamConfig,
    adamw_update,
    apply_update_with_scaler,
    init_opt_state,
)
from galvatron_tpu.core.schedules import LossScalerConfig, init_scaler_state
from galvatron_tpu.core.strategy import HybridParallelConfig
from galvatron_tpu.models import modeling
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.parallel.mesh import MeshAxes
from galvatron_tpu.parallel.pipeline import cpu_sim_compiler_options
from galvatron_tpu.parallel.sharding import constrain, sharding_tree


def _head_loss(head_sub, y, labels, cfg: ModelConfig):
    """Final norm + output head + summed loss for one micro-batch; returns
    (nll_sum, aux=count). Dispatches per objective (LM / masked-LM labels are
    prepared by modeling.split_batch; 'cls' pools and classifies)."""
    y = modeling.norm(y, head_sub["final_norm"], cfg)
    if cfg.objective == "cls":
        s, n = modeling.cross_entropy_sum(
            modeling.cls_head(y, head_sub, cfg), labels, remat=modeling.ce_remat(cfg)
        )
        return s, n.astype(jnp.float32)
    if cfg.tie_word_embeddings:
        w = head_sub["embed"]["tok"].astype(y.dtype).T
    else:
        w = head_sub["head"]["w"].astype(y.dtype)
    logits = y @ w
    s, n = modeling.cross_entropy_sum(logits, labels, remat=modeling.ce_remat(cfg))
    return s, n.astype(jnp.float32)


def make_1f1b_train_step(
    cfg: ModelConfig,
    hp: HybridParallelConfig,
    mesh: Mesh,
    axes: MeshAxes,
    adam: AdamConfig,
    global_batch_size: int,
    seq_len: int,
    stage_fn,
):
    from galvatron_tpu.parallel.hybrid import HybridParallelRuntime
    from galvatron_tpu.parallel.pipeline import (
        flatten_stacked_layers,
        init_pipeline_params,
        pipeline_param_specs,
        restack_flat_layers,
    )

    pp, chunks = hp.pp, max(1, hp.chunks)
    if global_batch_size % chunks != 0:
        raise ValueError(f"global batch {global_batch_size} not divisible by chunks {chunks}")
    mb = global_batch_size // chunks
    n_stash = min(chunks, 2 * (pp - 1) + 1)
    # loss-carrying positions per micro-batch (fp16-safe cotangent seeding)
    n_static = (global_batch_size // chunks) * modeling.loss_tokens_per_sample(cfg, seq_len)
    T = chunks + 2 * (pp - 1)
    up_perm = [(i, i + 1) for i in range(pp - 1)]
    down_perm = [(i + 1, i) for i in range(pp - 1)]
    head_keys = ("final_norm", "embed") if cfg.tie_word_embeddings else ("final_norm", "head")
    full_spec = P(("pp",) + axes.data_axes, None, None)

    packed = cfg.pack_sequences

    def pipeline_body(stage_params, head_sub, x_mbs, labels_mbs, scale, seg_mbs=None):
        """Runs under shard_map(manual={'pp'}). Returns per-stage-stacked
        (loss_sum, tok_count, d_stages, d_head, dx_embed). ``scale`` seeds the
        backward cotangent (fp16 loss scaling; 1.0 otherwise) so in-flight
        fp16 cotangents stay in range — all weight grads come back scaled.

        ``seg_mbs`` ((chunks, mb, S), packed sequences): segment ids per
        micro-batch, replicated over pp — the schedule's index arithmetic
        names the micro-batch each stage computes (fwd ``t − s``, bwd
        ``t − 2(pp−1) + s``), so forward AND the recompute-backward index the
        replicated array directly; no seg stash ring is needed."""
        # strip the size-1 local stage dim from the pp-stacked params
        stage_params = jax.tree.map(lambda a: jnp.squeeze(a, 0), stage_params)
        stage = jax.lax.axis_index("pp")
        is_last = stage == pp - 1
        is_first = stage == 0
        act = x_mbs.shape[1:]  # (mb, S, H)
        f32 = lambda tree: jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), tree)

        # SPMD discipline: every stage executes the SAME ops every tick —
        # collectives inside stage/head compute (TP psums, loss reductions,
        # ZeRO gathers) would deadlock under stage-varying lax.cond, so
        # validity is expressed by masking and by routing invalid writes to a
        # sacrificial extra slot (index n_stash / chunks) of each buffer.
        carry0 = {
            "fwd_send": jnp.zeros(act, x_mbs.dtype),
            "bwd_send": jnp.zeros(act, x_mbs.dtype),
            "stash": jnp.zeros((n_stash + 1,) + act, x_mbs.dtype),
            "dw": f32(stage_params),
            "dhead": f32(head_sub),
            "dx_embed": jnp.zeros((chunks + 1,) + act, jnp.float32),
            "loss_sum": jnp.zeros((), jnp.float32),
            "tok": jnp.zeros((), jnp.float32),
        }

        def tick(carry, t):
            prev_up = jax.lax.ppermute(carry["fwd_send"], "pp", up_perm)
            prev_dn = jax.lax.ppermute(carry["bwd_send"], "pp", down_perm)

            m_f = t - stage
            fwd_valid = (m_f >= 0) & (m_f < chunks)
            m_b = t - 2 * (pp - 1) + stage
            bwd_valid = (m_b >= 0) & (m_b < chunks)
            mf_c = jnp.clip(m_f, 0, chunks - 1)
            mb_c = jnp.clip(m_b, 0, chunks - 1)

            x_in = jnp.where(
                is_first, jax.lax.dynamic_index_in_dim(x_mbs, mf_c, keepdims=False), prev_up
            )

            # forward (unconditional; invalid ticks compute on garbage which
            # never reaches a valid consumer — see schedule proof in module doc)
            if seg_mbs is not None:
                seg_f = jax.lax.dynamic_index_in_dim(seg_mbs, mf_c, keepdims=False)
                out = stage_fn(stage_params, x_in, seg_f)
            else:
                out = stage_fn(stage_params, x_in)
            fwd_slot = jnp.where(fwd_valid, jnp.mod(mf_c, n_stash), n_stash)
            stash = jax.lax.dynamic_update_index_in_dim(carry["stash"], x_in, fwd_slot, 0)

            # head + loss cotangent (real only on the last stage's fwd ticks)
            labels = jax.lax.dynamic_index_in_dim(labels_mbs, mf_c, keepdims=False)
            nll, head_vjp, cnt = jax.vjp(
                lambda hs, y: _head_loss(hs, y, labels, cfg), head_sub, out, has_aux=True
            )
            head_mask = (is_last & fwd_valid).astype(jnp.float32)
            # seed normalized by the static micro-batch token count so the
            # scaled cotangents have mean-loss magnitude (a raw sum-loss seed
            # overflows fp16 at the initial 2^16 scale)
            dhead_mb, dy_head = head_vjp(head_mask * scale / n_static)

            # backward: recompute stage forward from the stashed input. Reads
            # the *updated* stash: the last stage backwards a micro-batch in
            # the same tick as its forward; for valid (fwd, bwd) pairs the
            # slots never collide (their index gap 2(pp-1-s) is < n_stash).
            x_saved = jax.lax.dynamic_index_in_dim(
                stash, jnp.mod(mb_c, n_stash), keepdims=False
            )
            dy_in = jnp.where(is_last, dy_head, prev_dn)
            dy_in = jnp.where(bwd_valid, dy_in, jnp.zeros_like(dy_in))
            if seg_mbs is not None:
                # the backward recompute must see the BACKWARD micro-batch's
                # segment ids (m_b ≠ m_f on interior ticks); closed over so
                # the vjp differentiates (params, x) only
                seg_b = jax.lax.dynamic_index_in_dim(seg_mbs, mb_c, keepdims=False)
                _, f_vjp = jax.vjp(
                    lambda p_, x_: stage_fn(p_, x_, seg_b), stage_params, x_saved
                )
            else:
                _, f_vjp = jax.vjp(stage_fn, stage_params, x_saved)
            dw_mb, dx = f_vjp(dy_in.astype(x_mbs.dtype))

            emb_slot = jnp.where(bwd_valid & is_first, mb_c, chunks)
            dx_embed = jax.lax.dynamic_update_index_in_dim(
                carry["dx_embed"], dx.astype(jnp.float32), emb_slot, 0
            )

            new_carry = {
                "fwd_send": out,
                "bwd_send": dx,
                "stash": stash,
                "dw": jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), carry["dw"], dw_mb
                ),
                "dhead": jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), carry["dhead"], dhead_mb
                ),
                "dx_embed": dx_embed,
                "loss_sum": carry["loss_sum"] + nll * head_mask,
                "tok": carry["tok"] + cnt * head_mask,
            }
            return new_carry, None

        carry, _ = jax.lax.scan(tick, carry0, jnp.arange(T))
        stack = lambda tree: jax.tree.map(lambda a: a[None], tree)
        return (
            carry["loss_sum"][None],
            carry["tok"][None],
            stack(carry["dw"]),
            stack(carry["dhead"]),
            carry["dx_embed"][None, :chunks],
        )

    body_sm = jax.shard_map(
        pipeline_body,
        mesh=mesh,
        in_specs=(P("pp"), P(), P(), P(), P(), P()) if packed
        else (P("pp"), P(), P(), P(), P()),
        out_specs=(P("pp"), P("pp"), P("pp"), P("pp"), P("pp")),
        axis_names={"pp"},
        check_vma=False,
    )

    def eval_body(stage_params, head_sub, x_mbs, labels_mbs, seg_mbs=None):
        """Forward-only clocked schedule (chunks + pp - 1 ticks): no vjp, no
        stash ring, no gradient accumulators — eval at ~1/3 of train cost."""
        stage_params = jax.tree.map(lambda a: jnp.squeeze(a, 0), stage_params)
        stage = jax.lax.axis_index("pp")
        is_last = stage == pp - 1
        is_first = stage == 0
        act = x_mbs.shape[1:]
        carry0 = {
            "fwd_send": jnp.zeros(act, x_mbs.dtype),
            "loss_sum": jnp.zeros((), jnp.float32),
            "tok": jnp.zeros((), jnp.float32),
        }

        def tick(carry, t):
            prev_up = jax.lax.ppermute(carry["fwd_send"], "pp", up_perm)
            m_f = t - stage
            fwd_valid = (m_f >= 0) & (m_f < chunks)
            mf_c = jnp.clip(m_f, 0, chunks - 1)
            x_in = jnp.where(
                is_first, jax.lax.dynamic_index_in_dim(x_mbs, mf_c, keepdims=False), prev_up
            )
            if seg_mbs is not None:
                seg_f = jax.lax.dynamic_index_in_dim(seg_mbs, mf_c, keepdims=False)
                out = stage_fn(stage_params, x_in, seg_f)
            else:
                out = stage_fn(stage_params, x_in)
            labels = jax.lax.dynamic_index_in_dim(labels_mbs, mf_c, keepdims=False)
            nll, cnt = _head_loss(head_sub, out, labels, cfg)
            head_mask = (is_last & fwd_valid).astype(jnp.float32)
            return {
                "fwd_send": out,
                "loss_sum": carry["loss_sum"] + nll * head_mask,
                "tok": carry["tok"] + cnt * head_mask,
            }, None

        carry, _ = jax.lax.scan(tick, carry0, jnp.arange(chunks + pp - 1))
        return carry["loss_sum"][None], carry["tok"][None]

    eval_sm = jax.shard_map(
        eval_body,
        mesh=mesh,
        in_specs=(P("pp"), P(), P(), P(), P()) if packed else (P("pp"), P(), P(), P()),
        out_specs=(P("pp"), P("pp")),
        axis_names={"pp"},
        check_vma=False,
    )

    fp16 = hp.mixed_precision == "fp16"
    scaler_cfg = LossScalerConfig()

    def train_step(state, batch):
        params = state["params"]
        scale = state["scaler"]["scale"] if fp16 else jnp.ones((), jnp.float32)
        inputs, labels = modeling.split_batch(batch, cfg)
        head_sub = {k: params[k] for k in head_keys}
        if packed:
            tokens, seg, pos_ids = modeling.split_packed_inputs(inputs)
        else:
            tokens, seg, pos_ids = inputs, None, None

        # embedding forward (outside the pipelined section), with vjp capture
        def embed_fn(embed_params):
            if packed:
                x = modeling.embed(tokens, {"embed": embed_params}, cfg, pos_ids=pos_ids)
            else:
                x = modeling.embed_any(tokens, {"embed": embed_params}, cfg)
            return constrain(x, mesh, full_spec)

        x, embed_vjp = jax.vjp(embed_fn, params["embed"])
        x_mbs = x.reshape(chunks, mb, *x.shape[1:])
        labels_mbs = labels.reshape(chunks, mb, *labels.shape[1:])
        extra = (seg.reshape(chunks, mb, seg.shape[1]),) if packed else ()

        loss_s, tok_s, d_stages, d_head_s, dx_embed_s = body_sm(
            params["stages"], head_sub, x_mbs, labels_mbs, scale, *extra
        )
        loss_sum = loss_s[-1]
        tok = jnp.maximum(tok_s[-1], 1.0)
        d_head = jax.tree.map(lambda a: a[-1], d_head_s)
        dx_embed = dx_embed_s[0].reshape(global_batch_size, *x.shape[1:])
        (d_embed,) = embed_vjp(dx_embed.astype(x.dtype))

        # assemble the full gradient tree (mean over tokens)
        grads: Dict[str, Any] = {"stages": d_stages, "embed": d_embed}
        for k in head_keys:
            if k == "embed":  # tied head: add the in-pipeline contribution
                grads["embed"] = jax.tree.map(
                    lambda a, b: a.astype(jnp.float32) + b, grads["embed"], d_head["embed"]
                )
            else:
                grads[k] = d_head[k]
        gdenom = tok * scale / n_static  # unscale the seeded backward + token-mean
        grads = {k: jax.tree.map(lambda g: g / gdenom, v) for k, v in grads.items()}
        loss = loss_sum / tok

        if fp16:
            return apply_update_with_scaler(state, loss, grads, adam, scaler_cfg)
        new_params, new_opt = adamw_update(params, grads, state["opt"], adam)
        return {"params": new_params, "opt": new_opt, "step": state["step"] + 1}, loss

    def eval_loss(state, batch):
        params = state["params"]
        inputs, labels = modeling.split_batch(batch, cfg)
        head_sub = {k: params[k] for k in head_keys}
        if packed:
            tokens, seg, pos_ids = modeling.split_packed_inputs(inputs)
            x = modeling.embed(tokens, params, cfg, pos_ids=pos_ids)
            extra = (seg.reshape(chunks, mb, seg.shape[1]),)
        else:
            x = modeling.embed_any(inputs, params, cfg)
            extra = ()
        x = constrain(x, mesh, full_spec)
        loss_s, tok_s = eval_sm(
            params["stages"],
            head_sub,
            x.reshape(chunks, mb, *x.shape[1:]),
            labels.reshape(chunks, mb, *labels.shape[1:]),
            *extra,
        )
        return loss_s[-1] / jnp.maximum(tok_s[-1], 1.0)

    def init_state(key):
        params = init_pipeline_params(key, cfg, hp)
        state = {"params": params, "opt": init_opt_state(params), "step": jnp.zeros((), jnp.int32)}
        if fp16:
            state["scaler"] = init_scaler_state(scaler_cfg)
        return state

    def state_from(flat_params):
        params = restack_flat_layers(flat_params, cfg, hp)
        state = {"params": params, "opt": init_opt_state(params), "step": jnp.zeros((), jnp.int32)}
        if fp16:
            state["scaler"] = init_scaler_state(scaler_cfg)
        return state

    state_shape = jax.eval_shape(init_state, jax.random.key(0))
    specs = {
        "params": pipeline_param_specs(state_shape["params"], cfg, hp, axes),
        "opt": {
            "mu": pipeline_param_specs(state_shape["params"], cfg, hp, axes, for_opt_state=True),
            "nu": pipeline_param_specs(state_shape["params"], cfg, hp, axes, for_opt_state=True),
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
        eval_loss,
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
        flatten_params=lambda sp: flatten_stacked_layers(sp, cfg, hp),
        restack_params=lambda fp: restack_flat_layers(fp, cfg, hp),
    )
