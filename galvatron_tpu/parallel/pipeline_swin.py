"""Swin (hierarchical vision) pipeline: K coupled sections over the pp ring.

The reference pipelines its legacy swin branch by arbitrary per-stage layer
ranges (galvatron/core/hybrid_parallel_model.py:81-153); SPMD stage stacking
needs homogeneous pytrees per stack, and a Swin pyramid's stages have
DIFFERENT widths/resolutions, so this engine generalizes the enc-dec
coupled-sub-pipeline design (parallel/pipeline_encdec.py) from two sections
to K = len(swin_depths): device ``s`` holds a sub-stack of every section, and
every clocked tick runs section ``k`` on chunk ``t - k·pp - s`` — no
stage-diverging control flow (per-stage lax.cond around in-layer collectives
deadlocks under GSPMD), no steady-state waste.

Ring wiring: each section's output rides a WRAPPED ring (device pp-1 → 0);
within a section the wrap-free edges are the plain chain, and the wrap edge
delivers section k's finished output to device 0 exactly when that chunk
enters section k+1 there — device 0 applies the patch-merge projection
(replicated, token-local) to form the next section's input. The last useful
write is chunk chunks-1 at section K-1 on device pp-1 → T = chunks + K·pp - 1
ticks. Backward under ``pipeline_type='gpipe'`` is autodiff through the
clocked scan; ``'pipedream_flush'`` runs the hand-written coupled 1F1B below
(the enc-dec two-section 1F1B of pipeline_encdec.py generalized to K
sections), whose stash rings are bounded by the schedule depth instead of
growing with chunks.

Stacking unit = layer PAIR (plain + shifted window): Swin alternates the
window shift by position parity within a stage, so single-layer stacking
would give devices at different offsets different static shift programs —
pairs keep every stack position the same trace. Sections whose pair count is
smaller than pp leave zero-pair stages (masked to identity), so any
swin_depths pipeline at any pp >= 2 with even depths.
"""

from __future__ import annotations

from typing import Any, Dict, List

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
from galvatron_tpu.parallel.mesh import MeshAxes, batch_spec
from galvatron_tpu.parallel.pipeline import cpu_sim_compiler_options
from galvatron_tpu.parallel.sharding import constrain, param_spec, sharding_tree


def _spread_pairs(pairs: int, pp: int) -> List[int]:
    """Pairs over stages, zeros allowed (a section narrower than the ring
    leaves idle stages for that section); remainder placed by the same stage
    order as strategy.balanced_division so every section's maximum lands on
    the same stage."""
    base, rem = divmod(pairs, pp)
    div = [base] * pp
    order = sorted(range(pp), key=lambda s: (abs(s - (pp - 1) / 2), -s))
    for i in range(rem):
        div[order[i]] += 1
    return div


class SwinLayout:
    """Per-section pair-stack layout + per-pair-position strategies."""

    def __init__(self, cfg: ModelConfig, hp: HybridParallelConfig):
        depths = cfg.swin_depths
        pp = hp.pp
        if any(d % 2 for d in depths):
            raise ValueError(
                f"swin pipeline stacks layer PAIRS (plain+shifted) — depths "
                f"{depths} must all be even"
            )
        if hp.vpp > 1:
            raise ValueError("swin pipeline does not compose with vpp>1")
        if hp.pipeline_type not in ("gpipe", "pipedream_flush"):
            raise ValueError(
                "swin pipeline implements the coupled-sections schedule in "
                f"gpipe and pipedream_flush (1F1B) orderings (got "
                f"{hp.pipeline_type!r})"
            )
        # the layout derives its per-section divisions from swin_depths; a
        # user-provided pp_division that differs from the auto-filled
        # balanced default is rejected instead of silently ignored (the
        # enc-dec layout applies the same guard)
        from galvatron_tpu.core.strategy import balanced_division

        if hp.pp_division is not None and hp.pp_division != balanced_division(
            sum(depths), pp
        ):
            raise ValueError(
                f"swin pipeline derives stage divisions from swin_depths "
                f"{tuple(depths)} per section; a custom pp_division "
                f"({hp.pp_division}) is not honored"
            )
        self.K = len(depths)
        self.pp = pp
        self.base = list(np.cumsum([0] + [d for d in depths[:-1]]))  # layer idx base
        self.div = [_spread_pairs(d // 2, pp) for d in depths]
        self.off = [list(np.cumsum([0] + dv[:-1])) for dv in self.div]
        self.lpk = [max(dv) for dv in self.div]
        # strategy per (section, pair position): both pair layers and every
        # stage holding the position must agree (stacked arrays, one sharding)
        self.pos: List[List[LayerStrategy]] = []
        for k in range(self.K):
            sec: List[LayerStrategy] = []
            for q in range(self.lpk[k]):
                idxs = [
                    self.base[k] + 2 * (self.off[k][s] + q) + half
                    for s in range(pp)
                    if self.div[k][s] > q
                    for half in (0, 1)
                ]
                ss = {hp.layer_strategies[i] for i in idxs}
                if len(ss) > 1:
                    raise ValueError(
                        f"swin section {k} pair position {q}: the pair's "
                        f"layers must share one strategy across stages "
                        f"(got {sorted(map(str, ss))})"
                    )
                sec.append(next(iter(ss)))
            self.pos.append(sec)


def validate_swin_pipeline(cfg: ModelConfig, hp: HybridParallelConfig) -> SwinLayout:
    return SwinLayout(cfg, hp)


def _pair_tree(layers: List, i0: int):
    return {"a": layers[i0], "b": layers[i0 + 1]}


def init_swin_pipeline_params(key, cfg: ModelConfig, hp: HybridParallelConfig):
    """Base (embed/final_norm/head) + merges replicated over pp;
    ``sections[k][q]`` = (pp, ...) stacks of PAIR params (zero padding on
    stages with fewer pairs)."""
    lay = validate_swin_pipeline(cfg, hp)
    flat = modeling.init_model_params(key, cfg)
    return restack_flat_swin(flat, cfg, hp, _lay=lay)


def restack_flat_swin(flat_params, cfg: ModelConfig, hp: HybridParallelConfig, _lay=None):
    lay = _lay or validate_swin_pipeline(cfg, hp)
    params = {k: v for k, v in flat_params.items() if k != "layers"}
    layers = flat_params["layers"]
    sections = []
    for k in range(lay.K):
        zeros = jax.tree.map(
            jnp.zeros_like, _pair_tree(layers, lay.base[k])
        )
        stacks = []
        for q in range(lay.lpk[k]):
            stacks.append(
                jax.tree.map(
                    lambda *ls: jnp.stack(ls),
                    *[
                        _pair_tree(layers, lay.base[k] + 2 * (lay.off[k][s] + q))
                        if lay.div[k][s] > q
                        else zeros
                        for s in range(lay.pp)
                    ],
                )
            )
        sections.append(stacks)
    params["sections"] = sections
    return params


def flatten_swin(params, cfg: ModelConfig, hp: HybridParallelConfig):
    """Inverse of restack_flat_swin (padding dropped) — the portable flat
    ``layers`` checkpoint layout."""
    lay = validate_swin_pipeline(cfg, hp)
    flat = {k: v for k, v in params.items() if k != "sections"}
    layers: List[Any] = [None] * cfg.num_layers
    for k in range(lay.K):
        for s in range(lay.pp):
            for q in range(lay.div[k][s]):
                pair = jax.tree.map(lambda a, s_=s: a[s_], params["sections"][k][q])
                i0 = lay.base[k] + 2 * (lay.off[k][s] + q)
                layers[i0] = pair["a"]
                layers[i0 + 1] = pair["b"]
    flat["layers"] = layers
    return flat


def swin_param_specs(
    params_shape, cfg: ModelConfig, hp: HybridParallelConfig, axes: MeshAxes,
    *, for_opt_state: bool = False,
):
    lay = validate_swin_pipeline(cfg, hp)
    embed_strategy = LayerStrategy(
        tp=hp.vocab_tp, tp_consec=True, dp_type=hp.embed_dp_type, sp=hp.vocab_sp
    )
    is_leaf = lambda x: hasattr(x, "shape")
    base_annots = modeling.vision_annotations(cfg)
    specs: Dict[str, Any] = {}
    for key in params_shape:
        if key == "sections":
            specs["sections"] = []
            for k in range(lay.K):
                lcfg = modeling.vision_layer_cfg(cfg, lay.base[k])
                pair_annots = {
                    "a": modeling.layer_annotations(lcfg),
                    "b": modeling.layer_annotations(lcfg),
                }
                specs["sections"].append(
                    [
                        jax.tree.map(
                            lambda leaf, a, q=q, k=k: P(
                                "pp",
                                *param_spec(
                                    leaf.shape[1:], a, axes, lay.pos[k][q],
                                    for_opt_state=for_opt_state,
                                ),
                            ),
                            params_shape["sections"][k][q],
                            pair_annots,
                            is_leaf=is_leaf,
                        )
                        for q in range(lay.lpk[k])
                    ]
                )
        else:
            specs[key] = jax.tree.map(
                lambda leaf, a: param_spec(
                    leaf.shape, a, axes, embed_strategy, for_opt_state=for_opt_state
                ),
                params_shape[key],
                base_annots[key],
                is_leaf=is_leaf,
            )
    return specs


def build_swin_pipeline_runtime(
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
    lay = validate_swin_pipeline(cfg, hp)
    K = lay.K

    # per-section geometry + a representative pair of global layer indices
    # (every pair in a section is the same static program: stage geometry +
    # shift parity depend only on the section and the half)
    geom = [modeling.swin_geometry(cfg, k) for k in range(K)]  # (h, w, c, heads)
    sec_len = [g[0] * g[1] for g in geom]
    sec_c = [g[2] for g in geom]

    def act_spec(s: LayerStrategy) -> P:
        bs = batch_spec(axes, s)
        return P(bs[0], bs[1], None)

    def section_fn(k):
        i0 = lay.base[k]
        uneven = len(set(lay.div[k])) > 1 or min(lay.div[k]) == 0

        def run_section(stacks, x):
            n_active = (
                jnp.asarray(lay.div[k])[jax.lax.axis_index("pp")] if uneven else None
            )
            for q, s in enumerate(lay.pos[k]):
                x = constrain(x, mesh, act_spec(s))
                # full-layer remat subsumes the gate-save policy
                lcfg = (
                    cfg.replace(mlp_recompute="off")
                    if s.ckpt == "full" and cfg.mlp_recompute != "off"
                    else cfg
                )

                def pair(x_, pp_, lcfg=lcfg):
                    y = modeling.swin_layer(
                        x_, pp_["a"], lcfg, i0, remat_attn=(s.ckpt == "selective")
                    )
                    return modeling.swin_layer(
                        y, pp_["b"], lcfg, i0 + 1, remat_attn=(s.ckpt == "selective")
                    )

                if s.ckpt == "full":
                    pair = jax.checkpoint(pair)
                out = pair(x, stacks[q])
                x = out if n_active is None else jnp.where(q < n_active, out, x)
            return x

        return run_section

    section_fns = [section_fn(k) for k in range(K)]
    ring_wrap = [(i, (i + 1) % pp) for i in range(pp)]
    T = chunks + K * pp - 1
    full_spec = P(("pp",) + axes.data_axes, None, None)

    def pipeline(sections, merges, emb_mbs):
        """Manual-'pp' shard_map body → (1, chunks, mb, L_last, c_last)
        (real outputs in the pp-1 slice)."""
        sections = jax.tree.map(lambda a: jnp.squeeze(a, 0), sections)
        s = jax.lax.axis_index("pp")
        carry0 = {
            f"sec{k}": jnp.zeros((mb, sec_len[k], sec_c[k]), emb_mbs.dtype)
            for k in range(K)
        }
        carry0["ys"] = jnp.zeros(
            (chunks + 1, mb, sec_len[K - 1], sec_c[K - 1]), emb_mbs.dtype
        )

        def tick(carry, t):
            recv = [
                jax.lax.ppermute(carry[f"sec{k}"], "pp", ring_wrap) for k in range(K)
            ]
            new_carry = dict(carry)
            for k in range(K):
                m_k = jnp.clip(t - k * pp - s, 0, chunks - 1)
                if k == 0:
                    first_in = jax.lax.dynamic_index_in_dim(emb_mbs, m_k, keepdims=False)
                else:
                    # device 0 enters the chunk whose previous section just
                    # wrapped; patch-merge is replicated + token-local
                    first_in = modeling.patch_merge(recv[k - 1], merges[k - 1], cfg, k - 1)
                x_in = jnp.where(s == 0, first_in, recv[k])
                new_carry[f"sec{k}"] = section_fns[k](sections[k], x_in)
            m_last_raw = t - (K - 1) * pp - s
            valid = (m_last_raw >= 0) & (m_last_raw < chunks)
            slot = jnp.where(valid, jnp.clip(m_last_raw, 0, chunks - 1), chunks)
            new_carry["ys"] = jax.lax.dynamic_update_index_in_dim(
                carry["ys"], new_carry[f"sec{K - 1}"], slot, 0
            )
            return new_carry, None

        carry, _ = jax.lax.scan(tick, carry0, jnp.arange(T))
        return carry["ys"][None, :chunks]

    pipe_sm = jax.shard_map(
        pipeline,
        mesh=mesh,
        in_specs=(P("pp"), P(), P()),
        out_specs=P("pp"),
        axis_names={"pp"},
        check_vma=False,
    )

    def loss_fn(params, batch):
        pixels, labels = modeling.split_batch(batch, cfg)
        x = modeling.vision_embed(pixels, params, cfg)
        x = constrain(x, mesh, full_spec)
        emb_mbs = x.reshape(chunks, mb, sec_len[0], sec_c[0])
        ys = pipe_sm(params["sections"], params["merges"], emb_mbs)
        y = ys[-1].reshape(global_batch_size, sec_len[K - 1], sec_c[K - 1])
        y = constrain(y, mesh, full_spec)
        y = modeling.norm(y, params["final_norm"], cfg)
        ssum, n = modeling.cross_entropy_sum(
            modeling.cls_head(y, params, cfg), labels, remat=modeling.ce_remat(cfg)
        )
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
    # 1F1B (pipedream_flush) ordering: the enc-dec coupled 1F1B
    # (pipeline_encdec.py) generalized to K sections. The coupled pipeline is
    # an interleaved virtual pipeline of depth K*pp (section k's virtual
    # stage s lives on device s), so the backward mirrors pipeline_1f1b: the
    # section-(K-1) backward wave starts at the last device in the SAME tick
    # as that chunk's final forward, each wave rides the down-chain, and at
    # device 0 the wave wraps (down-ring) to seed the previous section's
    # backward at device pp-1 one tick later. Backward recomputes each
    # section from stashed inputs — ring buffers bounded by the schedule
    # depth, independent of chunks (the 1F1B property the gpipe-ordered
    # autodiff backward lacks).
    #
    # Patch-merge placement flips versus the gpipe body: the SENDER merges
    # (every device computes section k then its merge; device 0 consumes the
    # wrapped, already-merged output) so every device's section-k input — and
    # therefore the one stash ring per section — has the uniform section-k
    # shape. The cotangent seed of the composed (section, merge) vjp is the
    # pair (dy_section, dy_merged): the down-chain recv fills the first on
    # s < pp-1, the down-ring wrap recv (device 0's section-(k+1) input
    # cotangent) fills the second on the last device; vjp linearity zeroes
    # the unused half. Numerically identical to merge-on-consumer (ppermute
    # is exact).
    #
    #   sec k fwd: m = t - k*pp - s
    #   sec k bwd: m = t - ((2K-k)*pp - 2) + s
    #   T = chunks + 2K*pp - 2;  stash[k]: min(chunks, 2*(K-k)*pp - 1)
    # ------------------------------------------------------------------
    from galvatron_tpu.parallel.pipeline_1f1b import _head_loss

    n_s = [min(chunks, 2 * (K - k) * pp - 1) for k in range(K)]
    off = [(2 * K - k) * pp - 2 for k in range(K)]
    T_1f1b = chunks + 2 * K * pp - 2
    n_static = mb  # loss-carrying positions per micro-batch (cls: one/sample)
    ring_wrap_down = [(i, (i - 1) % pp) for i in range(pp)]

    def sec_merge_fn(k):
        if k == K - 1:
            return section_fns[k]

        def f(stacks_k, merge_k, x):
            out = section_fns[k](stacks_k, x)
            return out, modeling.patch_merge(out, merge_k, cfg, k)

        return f

    sec_fns_1f1b = [sec_merge_fn(k) for k in range(K)]

    def pipeline_body_1f1b(sections, merges, head_sub, emb_mbs, labels_mbs, scale):
        sections = jax.tree.map(lambda a: jnp.squeeze(a, 0), sections)
        s = jax.lax.axis_index("pp")
        is_last = s == pp - 1
        is_first = s == 0
        dt = emb_mbs.dtype
        shp = [(mb, sec_len[k], sec_c[k]) for k in range(K)]
        f32 = lambda tree: jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), tree)

        carry0 = {"loss_sum": jnp.zeros((), jnp.float32), "tok": jnp.zeros((), jnp.float32)}
        for k in range(K):
            carry0[f"f{k}"] = jnp.zeros(shp[k], dt)         # fwd send (wrapped ring)
            carry0[f"b{k}"] = jnp.zeros(shp[k], dt)         # bwd dx send (down ring)
            carry0[f"stash{k}"] = jnp.zeros((n_s[k] + 1,) + shp[k], dt)
            carry0[f"dw{k}"] = f32(sections[k])
            if k < K - 1:
                carry0[f"fm{k}"] = jnp.zeros(shp[k + 1], dt)  # merged send (wrap)
                carry0[f"dm{k}"] = f32(merges[k])
        carry0["dhead"] = f32(head_sub)
        carry0["dxe"] = jnp.zeros((chunks + 1,) + shp[0], jnp.float32)

        def tick(carry, t):
            rf = [jax.lax.ppermute(carry[f"f{k}"], "pp", ring_wrap) for k in range(K)]
            rfm = [
                jax.lax.ppermute(carry[f"fm{k}"], "pp", ring_wrap) for k in range(K - 1)
            ]
            rb = [
                jax.lax.ppermute(carry[f"b{k}"], "pp", ring_wrap_down) for k in range(K)
            ]
            new_carry = dict(carry)

            # ---- forwards (stash the section input, send out + merged out)
            for k in range(K):
                m_f = t - k * pp - s
                f_valid = (m_f >= 0) & (m_f < chunks)
                mf_c = jnp.clip(m_f, 0, chunks - 1)
                if k == 0:
                    first_in = jax.lax.dynamic_index_in_dim(emb_mbs, mf_c, keepdims=False)
                else:
                    first_in = rfm[k - 1]
                x_in = jnp.where(is_first, first_in, rf[k])
                slot = jnp.where(f_valid, jnp.mod(mf_c, n_s[k]), n_s[k])
                new_carry[f"stash{k}"] = jax.lax.dynamic_update_index_in_dim(
                    carry[f"stash{k}"], x_in, slot, 0
                )
                if k < K - 1:
                    out, mout = sec_fns_1f1b[k](sections[k], merges[k], x_in)
                    new_carry[f"fm{k}"] = mout
                else:
                    out = sec_fns_1f1b[k](sections[k], x_in)
                new_carry[f"f{k}"] = out

            # ---- backwards (recompute from the updated stash; the last
            # device backwards section K-1 of a chunk in the same tick as
            # its forward — for valid pairs the ring slots never collide)
            for k in range(K - 1, -1, -1):
                m_b = t - off[k] + s
                b_valid = (m_b >= 0) & (m_b < chunks)
                mb_c = jnp.clip(m_b, 0, chunks - 1)
                x_saved = jax.lax.dynamic_index_in_dim(
                    new_carry[f"stash{k}"], jnp.mod(mb_c, n_s[k]), keepdims=False
                )
                if k == K - 1:
                    out_rec, sec_vjp = jax.vjp(sec_fns_1f1b[k], sections[k], x_saved)
                    labels = jax.lax.dynamic_index_in_dim(
                        labels_mbs, mb_c, keepdims=False
                    )
                    nll, head_vjp, cnt = jax.vjp(
                        lambda hs, y: _head_loss(hs, y, labels, cfg),
                        head_sub, out_rec, has_aux=True,
                    )
                    head_mask = (is_last & b_valid).astype(jnp.float32)
                    dhead_mb, dy_head = head_vjp(head_mask * scale / n_static)
                    dy = jnp.where(is_last, dy_head, rb[k])
                    dy = jnp.where(b_valid, dy, jnp.zeros_like(dy))
                    dw_mb, dx = sec_vjp(dy.astype(dt))
                    new_carry["dhead"] = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), carry["dhead"], dhead_mb
                    )
                    new_carry["loss_sum"] = carry["loss_sum"] + nll * head_mask
                    new_carry["tok"] = carry["tok"] + cnt * head_mask
                else:
                    _, sec_vjp = jax.vjp(
                        sec_fns_1f1b[k], sections[k], merges[k], x_saved
                    )
                    dy_sec = jnp.where(
                        b_valid & jnp.logical_not(is_last), rb[k],
                        jnp.zeros_like(rb[k]),
                    )
                    dy_mout = jnp.where(
                        b_valid & is_last, rb[k + 1], jnp.zeros_like(rb[k + 1])
                    )
                    dw_mb, dmerge_mb, dx = sec_vjp((dy_sec.astype(dt), dy_mout.astype(dt)))
                    new_carry[f"dm{k}"] = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), carry[f"dm{k}"], dmerge_mb
                    )
                new_carry[f"dw{k}"] = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), carry[f"dw{k}"], dw_mb
                )
                new_carry[f"b{k}"] = dx.astype(dt)
                if k == 0:
                    new_carry["dxe"] = jax.lax.dynamic_update_index_in_dim(
                        carry["dxe"], dx.astype(jnp.float32),
                        jnp.where(b_valid & is_first, mb_c, chunks), 0,
                    )
            return new_carry, None

        carry, _ = jax.lax.scan(tick, carry0, jnp.arange(T_1f1b))
        stack = lambda tree: jax.tree.map(lambda a: a[None], tree)
        return (
            carry["loss_sum"][None],
            carry["tok"][None],
            stack([carry[f"dw{k}"] for k in range(K)]),
            stack([carry[f"dm{k}"] for k in range(K - 1)]),
            stack(carry["dhead"]),
            carry["dxe"][None, :chunks],
        )

    body_1f1b_sm = jax.shard_map(
        pipeline_body_1f1b,
        mesh=mesh,
        in_specs=(P("pp"), P(), P(), P(), P(), P()),
        out_specs=tuple([P("pp")] * 6),
        axis_names={"pp"},
        check_vma=False,
    )

    def train_step_1f1b(state, batch):
        params = state["params"]
        scale = state["scaler"]["scale"] if fp16 else jnp.ones((), jnp.float32)
        pixels, labels = modeling.split_batch(batch, cfg)
        head_sub = {"final_norm": params["final_norm"], "head": params["head"]}

        def embed_fn(embed_params):
            x = modeling.vision_embed(pixels, {"embed": embed_params}, cfg)
            return constrain(x, mesh, full_spec)

        x, embed_vjp = jax.vjp(embed_fn, params["embed"])
        emb_mbs = x.reshape(chunks, mb, sec_len[0], sec_c[0])
        labels_mbs = labels.reshape(chunks, mb)

        loss_s, tok_s, dw_s, dmerge_s, dhead_s, dxe_s = body_1f1b_sm(
            params["sections"], params["merges"], head_sub, emb_mbs, labels_mbs, scale
        )
        loss_sum = loss_s[-1]
        tok = jnp.maximum(tok_s[-1], 1.0)
        d_head = jax.tree.map(lambda a: a[-1], dhead_s)
        # merge grads are nonzero only where the wrap cotangent lands (the
        # last device) — sum the pp stack, like enc_final_norm in enc-dec
        d_merge = jax.tree.map(lambda a: a.sum(axis=0), dmerge_s)
        dxe_full = dxe_s[0].reshape(global_batch_size, sec_len[0], sec_c[0])
        (d_embed,) = embed_vjp(dxe_full.astype(x.dtype))

        grads: Dict[str, Any] = {
            "sections": dw_s,
            "merges": d_merge,
            "embed": d_embed,
            "final_norm": d_head["final_norm"],
            "head": d_head["head"],
        }
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
        params = init_swin_pipeline_params(key, cfg, hp)
        state = {"params": params, "opt": init_opt_state(params), "step": jnp.zeros((), jnp.int32)}
        if fp16:
            state["scaler"] = init_scaler_state(scaler_cfg)
        return state

    def state_from(flat_params):
        params = restack_flat_swin(flat_params, cfg, hp)
        state = {"params": params, "opt": init_opt_state(params), "step": jnp.zeros((), jnp.int32)}
        if fp16:
            state["scaler"] = init_scaler_state(scaler_cfg)
        return state

    state_shape = jax.eval_shape(init_state, jax.random.key(0))
    specs = {
        "params": swin_param_specs(state_shape["params"], cfg, hp, axes),
        "opt": {
            "mu": swin_param_specs(state_shape["params"], cfg, hp, axes, for_opt_state=True),
            "nu": swin_param_specs(state_shape["params"], cfg, hp, axes, for_opt_state=True),
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
        flatten_params=lambda sp: flatten_swin(sp, cfg, hp),
        restack_params=lambda fp: restack_flat_swin(fp, cfg, hp),
    )
