"""Per-layer parameter & optimizer-state sharding rules.

Replaces three reference subsystems with ``NamedSharding`` specs:

- Megatron TP layer wrappers (Column/RowParallelLinear with explicit tp_group;
  reference: site_package/megatron/core/tensor_parallel/layers.py:581,828) →
  weight dims annotated ``"tp"`` are sharded over the layer's TP axes;
- per-layer FSDP wrapping {ddp→NO_SHARD, zero2→SHARD_GRAD_OP, zero3→FULL_SHARD}
  (reference: galvatron/core/parallel.py:30-32,174-207) → dims annotated
  ``"fsdp"`` are sharded over the layer's DP axes for zero3 params and for
  zero2/zero3 optimizer state; XLA's GSPMD inserts the same all-gather /
  reduce-scatter pattern FSDP hand-schedules;
- activation redistribution between layers with different TP
  (reference: galvatron/core/redistribute.py) → ``with_sharding_constraint``
  at layer boundaries with each layer's ``batch_spec``.

Parameters are annotated with a *logical axes* tuple, one entry per dim, drawn
from {"tp", "fsdp", None}. ``"tp"`` marks a Megatron-sharded dim (column-
parallel output dim or row-parallel input dim); ``"fsdp"`` marks the dim ZeRO
shards (at most one per param is honored, the first divisible one).
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence, Tuple

import jax

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from galvatron_tpu.core.strategy import LayerStrategy
from galvatron_tpu.parallel.mesh import MeshAxes

Annotation = Tuple[Optional[str], ...]


def param_spec(
    shape: Sequence[int],
    annot: Annotation,
    axes: MeshAxes,
    s: LayerStrategy,
    *,
    for_opt_state: bool = False,
) -> P:
    """PartitionSpec for one parameter (or its Adam moment) under strategy ``s``.

    ZeRO semantics: zero3 shards params AND optimizer state over DP axes;
    zero2 shards only optimizer state (grad reduce-scatter + sharded update +
    param all-gather fall out of GSPMD); ddp shards neither.
    (reference: galvatron/core/parallel.py:30-32, cost-model ratio curves
    galvatron/core/cost_model.py:56-60)
    """
    if len(shape) != len(annot):
        raise ValueError(f"shape {shape} vs annotation {annot} rank mismatch")
    tp_ax = axes.tp_axes(s.tp, s.tp_consec)
    ep_ax = axes.ep_axes(s.tp, s.tp_consec, s.ep) if "ep" in annot else ()
    zero = s.dp_type == "zero3" or (for_opt_state and s.dp_type == "zero2")
    dp_ax = axes.dp_axes(s.tp, s.tp_consec, s.cp) if zero else ()
    # expert params are distinct per EP group: ZeRO shards them only over the
    # data axes *within* an EP group (reference: expert weights live on their
    # EP rank, parallel_state.py:611-621)
    dp_ax = tuple(a for a in dp_ax if a not in set(ep_ax))
    entries: list = []
    fsdp_used = False
    for dim, tag in zip(shape, annot):
        if tag == "tp" and tp_ax and dim % (2 ** len(tp_ax)) == 0:
            entries.append(tp_ax)
        elif tag == "ep" and ep_ax and dim % (2 ** len(ep_ax)) == 0:
            entries.append(ep_ax)
        elif tag == "fsdp" and dp_ax and not fsdp_used and dim % (2 ** len(dp_ax)) == 0:
            entries.append(dp_ax)
            fsdp_used = True
        else:
            entries.append(None)
    return P(*entries)


def spec_tree(
    params: Any,
    annots: Any,
    axes: MeshAxes,
    s: LayerStrategy,
    *,
    for_opt_state: bool = False,
) -> Any:
    """Map ``param_spec`` over a pytree of params and a matching tree of
    annotations (annotation leaves are tuples, so the annotation tree uses the
    param tree's structure with tuple leaves)."""
    return jax.tree.map(
        lambda p, a: param_spec(p.shape, a, axes, s, for_opt_state=for_opt_state),
        params,
        annots,
        is_leaf=lambda x: hasattr(x, "shape"),
    )


def sharding_tree(mesh: Mesh, specs: Any) -> Any:
    return jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs, is_leaf=lambda x: isinstance(x, P)
    )


def constrain(x, mesh: Mesh, spec: P):
    """``with_sharding_constraint`` under an explicit mesh — the activation-
    resharding boundary (replaces reference redistribute.py split/gather
    autograd functions; XLA emits the fused collective the reference's
    `_Fused_split_allgather` hand-writes).

    Inside a (partial-)manual shard_map region the constraint must be built
    on the tracing context's AbstractMesh (whose manual axes are typed
    Manual); the concrete mesh's sharding would be rejected in the
    transpose/grad path."""
    am = jax.sharding.get_abstract_mesh()
    target = am if (am is not None and not am.empty) else mesh
    return jax.lax.with_sharding_constraint(x, NamedSharding(target, spec))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _grad_shard(x, mesh, spec):
    return x


def _grad_shard_fwd(x, mesh, spec):
    return x, None


def _grad_shard_bwd(mesh, spec, _res, g):
    with jax.named_scope("grad_sync"):
        return (constrain(g, mesh, spec),)


_grad_shard.defvjp(_grad_shard_fwd, _grad_shard_bwd)


def overlap_grad_sync(params, annots, mesh: Mesh, axes: MeshAxes, s: LayerStrategy):
    """Async ZeRO gradient overlap: identity on ``params``, but each leaf's
    COTANGENT is pinned to its reduce-scattered (opt-state) sharding at the
    layer's point in the backward graph. Without the pin GSPMD is free to
    defer every zero2/zero3 gradient reduce-scatter to the jit output
    boundary — one trailing blob after the whole backward; with it, each
    layer's bucket is issued as its backward completes and overlaps the next
    layer's dgrad compute (the ZeRO overlap, Rajbhandari et al.). Applied by
    the pp=1 layer hook when HybridParallelConfig.grad_overlap is set."""
    if s.dp_type not in ("zero2", "zero3"):
        return params

    def leaf(p, a):
        spec = param_spec(p.shape, a, axes, s, for_opt_state=True)
        if all(e is None for e in spec):
            return p
        return _grad_shard(p, mesh, spec)

    return jax.tree.map(leaf, params, annots, is_leaf=lambda x: hasattr(x, "shape"))


def cp_shard_axes(s: LayerStrategy, axes: MeshAxes) -> dict:
    """(batch_axes, head_axes) kwargs for the ring/ulysses CP entries — one
    derivation shared by the pp=1 hook and the pipeline engines so they
    cannot diverge (the layer's other placement rules: placement.place_layer)."""
    return dict(
        batch_axes=axes.dp_axes(s.tp, s.tp_consec, s.cp),
        head_axes=axes.tp_axes(s.tp, s.tp_consec),
    )
