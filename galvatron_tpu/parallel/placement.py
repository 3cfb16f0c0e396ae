"""One placement per layer: what a layer's strategy does to its computation.

``place_layer`` is the only code that reads a ``LayerStrategy`` to choose the
sharding pins, the ``shard_map`` wrappers and the per-strategy config
overrides of a layer; every engine (the pp=1 hook, the stage-stacked
pipelines, the enc-dec sections) calls it, so the engines cannot diverge.
``LayerPlacement`` carries the result into the model step, which takes it as
a static argument (``models/placement.py`` has the interface and the
single-device ``LOCAL``) and knows nothing about meshes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models.mixers import has_mixer_layers
from galvatron_tpu.models.placement import LOCAL, Placement
from galvatron_tpu.ops import collective_matmul as cm
from galvatron_tpu.ops.quant import QuantTensor
from galvatron_tpu.parallel.mesh import (
    MeshAxes,
    ambient_or,
    batch_spec,
    manual_axis_names,
    moe_token_axes,
)
from galvatron_tpu.parallel.sharding import constrain


def activation_spec(axes: MeshAxes, s: LayerStrategy) -> P:
    """(B, S, H) activation spec at a layer boundary."""
    bs = batch_spec(axes, s)
    return P(bs[0], bs[1], None)


@dataclass(frozen=True)
class LayerPlacement(Placement):
    """A layer on a multi-device mesh: its axes, and which pins and wrappers
    apply (``place_layer`` decides; equal strategies give equal placements)."""

    mesh: Mesh
    dp_axes: Tuple[str, ...]
    tp_axes: Tuple[str, ...]
    act_spec: P  # the layer's (B, S, H) activation
    sp: bool = False
    ep_axes: Tuple[str, ...] = ()
    token_axes: Tuple[str, ...] = ()  # the axes of act_spec, flat: the (B·S) token dim
    qkv_pin: bool = False  # tp > 1
    attn_out_pin: bool = False  # zero3 + tp
    kernel_wrap: bool = False  # flash layers and stacks with state-space layers, cp == 1
    tp_overlap: bool = False  # the plan's tp_overlap, tp > 1, cp == 1
    moe_pin: bool = False  # switch-MoE layers, ep > 1
    token_wrap: bool = False  # dropless top-k MoE layers

    @property
    def kernel_tp(self) -> int:
        return cm.tp_group_size(self.mesh, self.tp_axes) if self.kernel_wrap else 1

    def constrain_qkv(self, qkv):
        """Pin the stacked (b, 3, n, s, d) qkv to (dp, -, tp, -, -). The
        forward pin is a no-op (it matches propagation), but
        with_sharding_constraint's transpose applies the same spec to the
        BACKWARD cotangent — without it GSPMD has been seen sharding the
        combined bwd kernel's dqkv along the size-3 stack axis (padding it
        across tp x dp devices) and paying an involuntary
        replicate-and-repartition."""
        if not self.qkv_pin:
            return qkv
        return constrain(
            qkv, self.mesh, P(self.dp_axes or None, None, self.tp_axes or None, None, None)
        )

    def constrain_attn_out(self, o):
        """Pin the attention context to batch-sharded/head-replicated before
        the output projection. Without it the dWo^T grad dot (output sharded
        fsdp x tp) finds no common axes with the batch-sharded dy and the SPMD
        partitioner falls back to an involuntary full rematerialization
        (world-wide replicate) of dy — XLA b/433785288. The pin trades that
        for a tp-wide gather of o in forward."""
        if not self.attn_out_pin:
            return o
        return constrain(o, self.mesh, P(self.dp_axes or None, *([None] * (o.ndim - 1))))

    def shard_kernel(self, fn, arg_dims, out_dims):
        """Wrap a Mosaic kernel entry in a shard_map over the layer's (dp,
        tp) axes: GSPMD cannot partition Mosaic custom calls ("Mosaic kernels
        cannot be automatically partitioned"), so each device must invoke the
        kernel on its local (batch, head) shard. The CPU simulation never
        surfaces this (interpret-mode kernels are plain jnp ops GSPMD can
        partition); a real-TPU topology AOT compile does
        (tests/test_topology_aot.py). Nests inside the pp engines' manual
        region via ambient_or."""
        dp, tp = self.dp_axes, self.tp_axes
        if not self.kernel_wrap or (not dp and not tp):
            return fn

        def spec(dims, ndim):
            entries = [None] * ndim
            b_dim, h_dim = dims
            if b_dim is not None and dp:
                entries[b_dim] = dp if len(dp) > 1 else dp[0]
            if h_dim is not None and tp:
                entries[h_dim] = tp if len(tp) > 1 else tp[0]
            return P(*entries)

        def wrapped(*args):
            in_specs = tuple(spec(d, a.ndim) for d, a in zip(arg_dims, args))
            out_shape = jax.eval_shape(fn, *args)
            am = ambient_or(self.mesh)
            return jax.shard_map(
                fn, mesh=am, in_specs=in_specs,
                out_specs=spec(out_dims, len(out_shape.shape)),
                axis_names=manual_axis_names(am), check_vma=False,
            )(*args)

        return wrapped

    def proj_up(self, subscripts, x, w, w_shard_dim: int):
        """With ``tp_overlap`` and the layer sequence-parallel, ``x`` arrives
        seq-sharded over the tp axes and the GSPMD-inserted blocking seq
        all-gather is replaced by the decomposed all-gather⊗matmul ring
        (ops.collective_matmul). Non-sp layers keep the plain einsum — x is
        already tp-replicated, there is no gather to overlap. The ring
        streams fp weight shards, so a quantized weight is materialized back
        to fp first (serving never places a layer — this is for safety)."""
        if not self.tp_overlap:
            return super().proj_up(subscripts, x, w, w_shard_dim)
        if isinstance(w, QuantTensor):
            w = w.dequantize(x.dtype)
        if not self.sp:
            return jnp.einsum(subscripts, x, w)
        return cm.allgather_einsum(
            subscripts, x, w, mesh=self.mesh, dp_axes=self.dp_axes, tp_axes=self.tp_axes,
            w_shard_dim=w_shard_dim,
        )

    def proj_down(self, subscripts, x, w, w_shard_dim: int, activation=None):
        """With ``tp_overlap`` the trailing TP reduction is pipelined as the
        accumulator-ring reduce-scatter⊗matmul (ops.collective_matmul): sp
        layers keep the seq-scattered output layout; non-sp layers gather it
        back (the reduce half of the all-reduce still overlaps). The seam
        applies ``activation`` to ``x`` itself, keeps ``x`` and recomputes
        the activation in its backward (mlp_block)."""
        if not self.tp_overlap:
            return super().proj_down(subscripts, x, w, w_shard_dim, activation)
        if isinstance(w, QuantTensor):
            w = w.dequantize(x.dtype)
        return cm.einsum_reducescatter(
            subscripts, x, w, mesh=self.mesh, dp_axes=self.dp_axes, tp_axes=self.tp_axes,
            w_shard_dim=w_shard_dim, scatter_output=self.sp, activation=activation,
        )

    def pin_tokens(self, a):
        """Token-side tensors of an ep > 1 switch-MoE block are pinned to the
        token/batch sharding and the per-expert buffers (``pin_experts``) to
        the ep sharding, so the expert all-to-all happens exactly at the
        dispatch/combine einsums — without the pins, sharding propagation let
        the backward pick an SPMD replicate-and-repartition ("involuntary
        full rematerialization") on the dispatch reshape."""
        if not self.moe_pin:
            return a
        return constrain(a, self.mesh, P(self.token_axes, *([None] * (a.ndim - 1))))

    def pin_experts(self, a):
        if not self.moe_pin:
            return a
        return constrain(a, self.mesh, P(self.ep_axes, *([None] * (a.ndim - 1))))

    def route_tokens(self, local_fn):
        """Run the dropless MoE block under a ``shard_map`` over the axes the
        activation is sharded on, every expert's weights whole on every
        device: routing is per token, so each device sorts and computes its
        own tokens and only the statistics cross devices. GSPMD cannot
        partition the Mosaic kernels, and a global sort would gather every
        token. Devices that hold the same tokens (tp without sp) repeat the
        work."""
        if not self.token_wrap:
            return super().route_tokens(local_fn)
        spec, over = self.act_spec, self.token_axes

        def routed(x, p):
            am = ambient_or(self.mesh)
            return jax.shard_map(
                lambda x_, p_: local_fn(x_, p_, over),
                mesh=am, in_specs=(spec, P()), out_specs=(spec, P()),  # P(): every statistic
                axis_names=manual_axis_names(am), check_vma=False,
            )(x, p)

        return routed


def place_layer(cfg, s: LayerStrategy, mesh: Mesh, axes: MeshAxes):
    """``(layer_cfg, placement)`` of a layer of model ``cfg`` under strategy
    ``s``: the run's config with the two per-strategy overrides, and the
    placement the layer functions take. One device gives ``LOCAL``."""
    layer_cfg = cfg
    if s.ckpt == "full" and cfg.mlp_recompute != "off":
        # full-layer remat saves only the layer boundary — a nested
        # gate-save policy inside the remat region is pure overhead
        layer_cfg = layer_cfg.replace(mlp_recompute="off")
    if s.cp > 1 and s.cp_impl == "ring":
        layer_cfg = layer_cfg.replace(attn_impl="ring")
    if mesh.devices.size <= 1:
        return layer_cfg, LOCAL
    # cp > 1 layers: the ring/ulysses paths carry their own shard_maps and
    # own their projection seams
    return layer_cfg, LayerPlacement(
        mesh=mesh,
        dp_axes=axes.dp_axes(s.tp, s.tp_consec, s.cp),
        tp_axes=axes.tp_axes(s.tp, s.tp_consec),
        act_spec=activation_spec(axes, s),
        sp=bool(s.sp),
        ep_axes=axes.ep_axes(s.tp, s.tp_consec, s.ep),
        token_axes=moe_token_axes(axes, s),
        qkv_pin=s.tp > 1,
        attn_out_pin=s.dp_type == "zero3" and s.tp > 1,
        kernel_wrap=(layer_cfg.attn_impl == "flash" or has_mixer_layers(cfg)) and s.cp == 1,
        tp_overlap=bool(s.tp_overlap) and s.tp > 1 and s.cp == 1,
        moe_pin=cfg.moe_experts > 0 and s.ep > 1,
        token_wrap=cfg.moe_dropless,
    )


def tp_overlap_seam_counts(
    cfg, hp: HybridParallelConfig, mesh: Mesh, axes: MeshAxes,
    global_batch_size: int, seq_len: int,
) -> dict:
    """``{"ring": n, "plain": m, "batchwise": k}`` over the plan's
    ``tp_overlap`` layers: how many projection seams take the
    collective-matmul ring and how many stay the plain einsum, by the shape
    test the seams themselves apply to a micro-batch
    (ops.collective_matmul.ring_pays; non-sp layers have no all-gather to
    decompose, so their column-parallel seams are plain), and how many of the
    ring seams pipeline their head-major all-gather side over the batch
    (ops.collective_matmul.batch_pieces; the others of them gather whole)."""
    from galvatron_tpu.models.modeling import projection_seams

    counts = {"ring": 0, "plain": 0, "batchwise": 0}
    itemsize = 4 if hp.mixed_precision == "fp32" else 2
    seams = projection_seams(cfg, seq_len)
    micro = global_batch_size // max(1, hp.chunks)
    for s in hp.layer_strategies:
        place = place_layer(cfg, s, mesh, axes)[1]
        if not place.tp_overlap:
            continue
        dp = cm.tp_group_size(mesh, place.dp_axes)
        rows = micro // dp * (seq_len // s.tp)
        for _, kind, width, blockwise in seams:
            ring = (
                (s.sp or kind == "rs")
                and seq_len % s.tp == 0 and width % s.tp == 0 and micro % dp == 0
                and cm.ring_pays(s.tp, rows, width // s.tp, itemsize)
            )
            counts["ring" if ring else "plain"] += 1
            if ring and not blockwise:
                counts["batchwise"] += cm.batch_pieces(
                    s.tp, micro // dp, seq_len, width // s.tp, itemsize) > 1
    return counts
