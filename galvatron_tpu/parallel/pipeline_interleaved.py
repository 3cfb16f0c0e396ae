"""Interleaved (virtual-pipeline-stage) schedule over the pp ring.

TPU-native rendering of the interleaved schedule the reference carries only
in its vendored Megatron (core/pipeline_parallel/schedules.py:367,
``--num-layers-per-virtual-pipeline-stage``) and never wires into Galvatron's
own engine (SURVEY §2.3 'PP' row). Here it is first-class: the model is cut
into ``vpp * pp`` *virtual stages*; device ``s`` holds virtual stages
``{s, s+pp, ..., s+(vpp-1)·pp}``, so each micro-batch travels the device ring
``vpp`` times. Ticks are one virtual stage long (1/vpp of a physical stage),
shrinking the pipeline-fill bubble from ``(pp-1)·T/pp`` to ``(pp-1)·T/(pp·vpp)``
— the same bubble/vpp factor as Megatron's interleaved 1F1B.

Schedule (all static arithmetic, one ``lax.scan``): micro-batches flow in
groups of ``pp`` (hence ``chunks % pp == 0``, the reference's own interleaved
constraint). At tick ``t`` device ``s`` computes virtual chunk ``j`` of
micro-batch ``m`` where, with ``n = t - s``::

    r = n mod pp;  q = n div pp;  j = q mod vpp;  g = q div vpp;  m = g·pp + r

This is a bijection (r, j, g) ↔ n, so every device is busy every tick of
``[s, s + vpp·chunks)`` — the only idle ticks are the ``pp-1``-tick ramp.
Sends ride one ring ``ppermute`` (the pp-1 → 0 edge carries the
chunk-boundary handoff); finished micro-batches surface on device 0's receive
port at ``j == 0`` ticks. Backward = autodiff reversing the scan (GPipe
ordering); activation footprint is that of the forward scan, reduced per
layer by the usual remat strategies.
"""

from __future__ import annotations

from typing import Any, List

import jax

import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models import modeling
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.parallel.mesh import MeshAxes
from galvatron_tpu.parallel.sharding import param_spec


def validate_interleaved_strategies(cfg: ModelConfig, hp: HybridParallelConfig) -> int:
    """Check the stacking constraint; returns layers per *virtual* stage.

    All virtual stages share one (pp, vpp)-stacked param array per position,
    hence one sharding: layer strategies must repeat with period
    ``num_layers / (pp*vpp)`` across the whole model."""
    L, pp, vpp = cfg.num_layers, hp.pp, hp.vpp
    if L % (pp * vpp) != 0:
        raise ValueError(f"pp*vpp={pp * vpp} must divide the layer count {L}")
    lpvs = L // (pp * vpp)
    for q in range(lpvs):
        base = hp.layer_strategies[q]
        for k in range(1, pp * vpp):
            other = hp.layer_strategies[k * lpvs + q]
            if other != base:
                raise ValueError(
                    f"interleaved schedule: layers at virtual-stage position {q} "
                    f"must share one strategy across all {pp * vpp} virtual "
                    f"stages (virtual stage 0 has {base}, {k} has {other})"
                )
    return lpvs


def init_interleaved_params(key, cfg: ModelConfig, hp: HybridParallelConfig):
    """Param tree: embed/final_norm/head as in the plain pipeline;
    ``vstages[q]`` = position-q layer params stacked (pp, vpp, ...) — entry
    [s, j] belongs to layer ``(s + j·pp)·lpvs + q``."""
    from galvatron_tpu.parallel.pipeline import base_model_params

    lpvs = validate_interleaved_strategies(cfg, hp)
    pp, vpp = hp.pp, hp.vpp
    ks = jax.random.split(key, 4)
    base = base_model_params(ks, cfg)
    layer_keys = jax.random.split(ks[3], cfg.num_layers)
    vstages = []
    for q in range(lpvs):
        keys_q = jnp.stack(
            [
                jnp.stack([layer_keys[(s + j * pp) * lpvs + q] for j in range(vpp)])
                for s in range(pp)
            ]
        )  # (pp, vpp, key)
        vstages.append(
            jax.vmap(jax.vmap(lambda k: modeling.init_layer_params(k, cfg)))(keys_q)
        )
    base["vstages"] = vstages
    return base


def restack_flat_vstages(flat_params, cfg: ModelConfig, hp: HybridParallelConfig):
    """Flat ``layers`` list → the ``vstages[q]`` (pp, vpp) stacks; entry
    [s, j] is layer (s + j·pp)·lpvs + q (shared by the gpipe-ordered and
    1F1B interleaved engines)."""
    pp, vpp = hp.pp, hp.vpp
    lpvs = cfg.num_layers // (pp * vpp)
    layers = flat_params["layers"]
    params = {k: v for k, v in flat_params.items() if k != "layers"}
    params["vstages"] = [
        jax.tree.map(
            lambda *per_s: jnp.stack(per_s),
            *[
                jax.tree.map(
                    lambda *per_j: jnp.stack(per_j),
                    *[layers[(s + j * pp) * lpvs + q] for j in range(vpp)],
                )
                for s in range(pp)
            ],
        )
        for q in range(lpvs)
    ]
    return params


def flatten_vstages(params, cfg: ModelConfig, hp: HybridParallelConfig):
    """Inverse of restack_flat_vstages (portable-checkpoint layout)."""
    pp, vpp = hp.pp, hp.vpp
    lpvs = cfg.num_layers // (pp * vpp)
    flat = {k: v for k, v in params.items() if k != "vstages"}
    layers = [None] * cfg.num_layers
    for q in range(lpvs):
        for s in range(pp):
            for j in range(vpp):
                layers[(s + j * pp) * lpvs + q] = jax.tree.map(
                    lambda a, s_=s, j_=j: a[s_, j_], params["vstages"][q]
                )
    flat["layers"] = layers
    return flat


def interleaved_param_specs(
    params_shape, cfg: ModelConfig, hp: HybridParallelConfig, axes: MeshAxes,
    *, for_opt_state: bool = False,
):
    """vstages[q] leaves get P('pp', None, *strategy_q_spec) — the vpp dim is
    replicated-by-stacking (each [s, j] slice is a distinct layer's params);
    embed/head/norm identical to the plain pipeline."""
    from galvatron_tpu.parallel.pipeline import pipeline_param_specs

    lpvs = cfg.num_layers // (hp.pp * hp.vpp)
    annots = modeling.layer_annotations(cfg)
    is_leaf = lambda x: hasattr(x, "shape")
    # embed/head/norm: reuse the plain-pipeline spec builder on a shape tree
    # without the layer stacks
    other_shape = {k: v for k, v in params_shape.items() if k != "vstages"}
    specs = pipeline_param_specs(other_shape, cfg, hp, axes, for_opt_state=for_opt_state)
    specs["vstages"] = []
    for q in range(lpvs):
        s_q = hp.layer_strategies[q]
        specs["vstages"].append(
            jax.tree.map(
                lambda leaf, a: P(
                    "pp", None,
                    *param_spec(leaf.shape[2:], a, axes, s_q, for_opt_state=for_opt_state),
                ),
                params_shape["vstages"][q],
                annots,
                is_leaf=is_leaf,
            )
        )
    return specs


def interleaved_pipeline(block_fn, pp: int, vpp: int, chunks: int, mesh: Mesh):
    """Returns f(vstage_params_local, x_mbs) -> ys for a manual-'pp' shard_map.
    ``ys`` is (1, chunks, mb, S, H) locally; globally stacked over pp with the
    real outputs in the pp=0 slice (finished micro-batches surface at device
    0's receive port)."""

    ring = [(i, (i + 1) % pp) for i in range(pp)]
    n_total = vpp * chunks
    T = n_total + pp

    def run(vstage_params, x_mbs):
        # strip the size-1 local 'pp' stacking dim → leaves (vpp, ...)
        vstage_params = jax.tree.map(lambda a: jnp.squeeze(a, 0), vstage_params)
        s = jax.lax.axis_index("pp")
        mb_shape = x_mbs.shape[1:]
        send0 = jnp.zeros(mb_shape, x_mbs.dtype)
        # chunks real slots + one sacrificial slot for invalid-tick writes
        ys0 = jnp.zeros((chunks + 1,) + mb_shape, x_mbs.dtype)

        def tick(carry, t):
            send, ys = carry
            recv = jax.lax.ppermute(send, "pp", ring)
            n = t - s
            nc = jnp.maximum(n, 0)  # decomposition below needs n >= 0
            r = jnp.mod(nc, pp)
            q2 = nc // pp
            j = jnp.mod(q2, vpp)
            g = q2 // vpp
            m = g * pp + r
            first_in = jax.lax.dynamic_index_in_dim(
                x_mbs, jnp.clip(m, 0, chunks - 1), keepdims=False
            )
            x_in = jnp.where((s == 0) & (j == 0), first_in, recv)
            params_j = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, j, 0, keepdims=False),
                vstage_params,
            )
            out = block_fn(params_j, x_in)
            # capture: on device 0 a j==0 tick's incoming value is the finished
            # output of micro-batch m - pp (sent by device pp-1, virtual chunk
            # vpp-1, one tick earlier)
            m_out = m - pp
            cap = (s == 0) & (j == 0) & (m_out >= 0) & (m_out < chunks) & (n >= 0)
            slot = jnp.where(cap, jnp.clip(m_out, 0, chunks - 1), chunks)
            ys = jax.lax.dynamic_update_index_in_dim(ys, recv, slot, 0)
            return (out, ys), None

        (send, ys), _ = jax.lax.scan(tick, (send0, ys0), jnp.arange(T))
        return ys[None, :chunks]

    return run


# ---------------------------------------------------------------------------
# Interleaved 1F1B (bounded-activation virtual stages)
# ---------------------------------------------------------------------------


def make_interleaved_1f1b_train_step(
    cfg: ModelConfig,
    hp: HybridParallelConfig,
    mesh: Mesh,
    axes: MeshAxes,
    adam,
    global_batch_size: int,
    seq_len: int,
    block_fn,
):
    """Interleaved schedule with a hand-written 1F1B-style backward: live
    activations are bounded by the schedule depth (O(pp·vpp) micro-batch
    stashes per device), independent of ``chunks`` — the property the
    reference's vendored interleaved 1F1B provides (megatron
    core/pipeline_parallel/schedules.py:367) and its gpipe-ordered interleaved
    cousin here (``interleaved_pipeline``) lacks.

    Schedule (uniform SPMD clocked scan; all ticks run one masked forward AND
    one masked backward virtual-stage pass):

      forward  (device s, tick t):  n = t - s;            r = n mod pp;
                q = n div pp; j = q mod vpp; g = q div vpp; m = g·pp + r
      backward (device s, tick t):  n' = t - vpp·pp - (pp-1-s); with the same
                decomposition of n', j' = vpp-1 - (q' mod vpp), m' = g'·pp+r'

    i.e. the backward wave mirrors the forward wave (reversed device and
    virtual-stage order) at lag vpp·pp. Forward activations ride the wrapped
    up-ring; cotangents ride the wrapped down-ring, and each arrives exactly
    one tick before its consumer (the lag telescopes: t_b(m,j,s+1) =
    t_b(m,j,s) - 1 and t_b(m,j+1,0) = t_b(m,j,pp-1) - 1). Backward recomputes
    the virtual-stage forward from a stashed input ring buffer of
    min(chunks, 3·pp+1) slots per virtual stage (in-flight micro-batches per
    virtual stage span < 3 pp-groups at the vpp·pp lag).
    """
    from galvatron_tpu.core.optim import (
        adamw_update,
        apply_update_with_scaler,
        init_opt_state,
    )
    from galvatron_tpu.core.schedules import LossScalerConfig, init_scaler_state
    from galvatron_tpu.parallel.hybrid import HybridParallelRuntime
    from galvatron_tpu.parallel.pipeline import cpu_sim_compiler_options
    from galvatron_tpu.parallel.pipeline_1f1b import _head_loss
    from galvatron_tpu.parallel.sharding import constrain, sharding_tree
    from jax.sharding import NamedSharding

    pp, vpp, chunks = hp.pp, hp.vpp, max(1, hp.chunks)
    if global_batch_size % chunks:
        raise ValueError(f"global batch {global_batch_size} not divisible by chunks {chunks}")
    mb = global_batch_size // chunks
    n_stash = min(chunks, 3 * pp + 1)
    n_static = mb * modeling.loss_tokens_per_sample(cfg, seq_len)
    T = vpp * chunks + vpp * pp + pp - 1
    up_ring = [(i, (i + 1) % pp) for i in range(pp)]
    down_ring = [(i, (i - 1) % pp) for i in range(pp)]
    head_keys = ("final_norm", "embed") if cfg.tie_word_embeddings else ("final_norm", "head")
    full_spec = P(("pp",) + axes.data_axes, None, None)

    def pipeline_body(vstage_params, head_sub, x_mbs, labels_mbs, scale):
        """shard_map(manual={'pp'}) body → per-stage-stacked (loss_sum, tok,
        d_vstages, d_head, dx_embed)."""
        vstage_params = jax.tree.map(lambda a: jnp.squeeze(a, 0), vstage_params)
        s = jax.lax.axis_index("pp")
        is_last = s == pp - 1
        is_first = s == 0
        act = x_mbs.shape[1:]  # (mb, S, H)
        f32 = lambda tree: jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), tree)

        carry0 = {
            "fwd_send": jnp.zeros(act, x_mbs.dtype),
            "bwd_send": jnp.zeros(act, x_mbs.dtype),
            # per-virtual-stage input stash (+1 sacrificial slot)
            "stash": jnp.zeros((vpp, n_stash + 1) + act, x_mbs.dtype),
            "dw": f32(vstage_params),
            "dhead": f32(head_sub),
            "dx_embed": jnp.zeros((chunks + 1,) + act, jnp.float32),
            "loss_sum": jnp.zeros((), jnp.float32),
            "tok": jnp.zeros((), jnp.float32),
        }

        def decompose(n):
            nc = jnp.maximum(n, 0)
            r = jnp.mod(nc, pp)
            q = nc // pp
            return r, jnp.mod(q, vpp), q // vpp

        def tick(carry, t):
            recv_up = jax.lax.ppermute(carry["fwd_send"], "pp", up_ring)
            recv_dn = jax.lax.ppermute(carry["bwd_send"], "pp", down_ring)

            # ---- forward virtual-stage pass
            n_f = t - s
            r_f, j_f, g_f = decompose(n_f)
            m_f = jnp.clip(g_f * pp + r_f, 0, chunks - 1)
            fwd_valid = (n_f >= 0) & (n_f < vpp * chunks)
            first_in = jax.lax.dynamic_index_in_dim(x_mbs, m_f, keepdims=False)
            x_in = jnp.where(is_first & (j_f == 0), first_in, recv_up)
            params_jf = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, j_f, 0, keepdims=False),
                vstage_params,
            )
            out = block_fn(params_jf, x_in)
            fwd_slot = jnp.where(fwd_valid, jnp.mod(m_f, n_stash), n_stash)
            stash = carry["stash"].at[j_f, fwd_slot].set(x_in)

            # ---- backward virtual-stage pass (mirrored wave at lag vpp*pp)
            n_b = t - vpp * pp - (pp - 1 - s)
            r_b, jj, g_b = decompose(n_b)
            j_b = vpp - 1 - jj
            m_b = jnp.clip(g_b * pp + r_b, 0, chunks - 1)
            bwd_valid = (n_b >= 0) & (n_b < vpp * chunks)
            x_saved = stash[j_b, jnp.mod(m_b, n_stash)]
            params_jb = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, j_b, 0, keepdims=False),
                vstage_params,
            )
            out_rec, f_vjp = jax.vjp(block_fn, params_jb, x_saved)

            # head loss on the recomputed output of the LAST virtual stage
            labels = jax.lax.dynamic_index_in_dim(labels_mbs, m_b, keepdims=False)
            nll, head_vjp, cnt = jax.vjp(
                lambda hs, y: _head_loss(hs, y, labels, cfg), head_sub, out_rec,
                has_aux=True,
            )
            head_mask = (is_last & bwd_valid & (j_b == vpp - 1)).astype(jnp.float32)
            dhead_mb, dy_head = head_vjp(head_mask * scale / n_static)

            dy_in = jnp.where(is_last & (j_b == vpp - 1), dy_head, recv_dn)
            dy_in = jnp.where(bwd_valid, dy_in, jnp.zeros_like(dy_in))
            dw_mb, dx = f_vjp(dy_in.astype(x_mbs.dtype))

            emb_slot = jnp.where(bwd_valid & is_first & (j_b == 0), m_b, chunks)
            dx_embed = jax.lax.dynamic_update_index_in_dim(
                carry["dx_embed"], dx.astype(jnp.float32), emb_slot, 0
            )
            dw = jax.tree.map(
                lambda A, g: A.at[j_b].add(g.astype(jnp.float32)), carry["dw"], dw_mb
            )

            new_carry = {
                "fwd_send": out,
                "bwd_send": dx,
                "stash": stash,
                "dw": dw,
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
        in_specs=(P("pp"), P(), P(), P(), P()),
        out_specs=(P("pp"), P("pp"), P("pp"), P("pp"), P("pp")),
        axis_names={"pp"},
        check_vma=False,
    )

    def eval_body(vstage_params, head_sub, x_mbs, labels_mbs):
        """Forward-only interleaved wave (vpp*chunks + pp - 1 ticks): the
        head loss rides the forward output of the last virtual stage; no
        vjp/stash/grad machinery — eval at ~1/3 of train cost."""
        vstage_params = jax.tree.map(lambda a: jnp.squeeze(a, 0), vstage_params)
        s = jax.lax.axis_index("pp")
        is_last = s == pp - 1
        is_first = s == 0
        act = x_mbs.shape[1:]
        carry0 = {
            "fwd_send": jnp.zeros(act, x_mbs.dtype),
            "loss_sum": jnp.zeros((), jnp.float32),
            "tok": jnp.zeros((), jnp.float32),
        }

        def decompose(n):
            nc = jnp.maximum(n, 0)
            r = jnp.mod(nc, pp)
            q = nc // pp
            return r, jnp.mod(q, vpp), q // vpp

        def tick(carry, t):
            recv_up = jax.lax.ppermute(carry["fwd_send"], "pp", up_ring)
            n_f = t - s
            r_f, j_f, g_f = decompose(n_f)
            m_f = jnp.clip(g_f * pp + r_f, 0, chunks - 1)
            fwd_valid = (n_f >= 0) & (n_f < vpp * chunks)
            first_in = jax.lax.dynamic_index_in_dim(x_mbs, m_f, keepdims=False)
            x_in = jnp.where(is_first & (j_f == 0), first_in, recv_up)
            params_jf = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, j_f, 0, keepdims=False),
                vstage_params,
            )
            out = block_fn(params_jf, x_in)
            labels = jax.lax.dynamic_index_in_dim(labels_mbs, m_f, keepdims=False)
            nll, cnt = _head_loss(head_sub, out, labels, cfg)
            head_mask = (is_last & fwd_valid & (j_f == vpp - 1)).astype(jnp.float32)
            return {
                "fwd_send": out,
                "loss_sum": carry["loss_sum"] + nll * head_mask,
                "tok": carry["tok"] + cnt * head_mask,
            }, None

        carry, _ = jax.lax.scan(tick, carry0, jnp.arange(vpp * chunks + pp - 1))
        return carry["loss_sum"][None], carry["tok"][None]

    eval_sm = jax.shard_map(
        eval_body,
        mesh=mesh,
        in_specs=(P("pp"), P(), P(), P()),
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

        def embed_fn(embed_params):
            x = modeling.embed_any(inputs, {"embed": embed_params}, cfg)
            return constrain(x, mesh, full_spec)

        x, embed_vjp = jax.vjp(embed_fn, params["embed"])
        x_mbs = x.reshape(chunks, mb, *x.shape[1:])
        labels_mbs = labels.reshape(chunks, mb, *labels.shape[1:])
        loss_s, tok_s, d_vstages, d_head_s, dx_embed_s = body_sm(
            params["vstages"], head_sub, x_mbs, labels_mbs, scale
        )
        loss_sum = loss_s[-1]
        tok = jnp.maximum(tok_s[-1], 1.0)
        d_head = jax.tree.map(lambda a: a[-1], d_head_s)
        dx_embed = dx_embed_s[0].reshape(global_batch_size, *x.shape[1:])
        (d_embed,) = embed_vjp(dx_embed.astype(x.dtype))

        grads = {"vstages": d_vstages, "embed": d_embed}
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

    def eval_loss(state, batch):
        params = state["params"]
        inputs, labels = modeling.split_batch(batch, cfg)
        head_sub = {k: params[k] for k in head_keys}
        x = constrain(modeling.embed_any(inputs, params, cfg), mesh, full_spec)
        loss_s, tok_s = eval_sm(
            params["vstages"], head_sub,
            x.reshape(chunks, mb, *x.shape[1:]),
            labels.reshape(chunks, mb, *labels.shape[1:]),
        )
        return loss_s[-1] / jnp.maximum(tok_s[-1], 1.0)

    def init_state(key):
        params = init_interleaved_params(key, cfg, hp)
        state = {"params": params, "opt": init_opt_state(params), "step": jnp.zeros((), jnp.int32)}
        if fp16:
            state["scaler"] = init_scaler_state(scaler_cfg)
        return state

    def state_from(flat_params):
        params = restack_flat_vstages(flat_params, cfg, hp)
        state = {"params": params, "opt": init_opt_state(params), "step": jnp.zeros((), jnp.int32)}
        if fp16:
            state["scaler"] = init_scaler_state(scaler_cfg)
        return state

    state_shape = jax.eval_shape(init_state, jax.random.key(0))
    specs = {
        "params": interleaved_param_specs(state_shape["params"], cfg, hp, axes),
        "opt": {
            "mu": interleaved_param_specs(state_shape["params"], cfg, hp, axes, for_opt_state=True),
            "nu": interleaved_param_specs(state_shape["params"], cfg, hp, axes, for_opt_state=True),
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
        flatten_params=lambda sp: flatten_vstages(sp, cfg, hp),
        restack_params=lambda fp: restack_flat_vstages(fp, cfg, hp),
    )
