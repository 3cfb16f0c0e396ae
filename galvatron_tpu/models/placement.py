"""What a layer's place on the mesh does to its computation: the single-device case.

The model step (``modeling.py``, ``moe.py``) asks a placement object at every
point where a multi-device mesh needs something a single device does not: a
sharding pin, a ``shard_map`` around a Mosaic kernel, a collective-matmul
ring on a projection seam. This module holds the interface and its
single-device instance ``LOCAL``, whose methods are the identity or the plain
einsum; the mesh-backed class is ``parallel/placement.LayerPlacement``, built
per layer by ``parallel/placement.place_layer`` from the layer's strategy.
Nothing here (or anywhere under ``models/``) knows about meshes.

A placement is a static argument of the layer functions: frozen, hashable,
and equal for equal strategies, so a jitted layer is traced once per distinct
placement.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from galvatron_tpu.ops.quant import QuantTensor, qeinsum


def _plain_einsum(subscripts, x, w):
    # int8 weights (serving, ops.quant) dequantize inside the einsum
    if isinstance(w, QuantTensor):
        return qeinsum(subscripts, x, w)
    return jnp.einsum(subscripts, x, w)


@dataclass(frozen=True)
class Placement:
    """One device: every method is the identity or the plain computation."""

    #: the projection seams are collective-matmul rings (mlp_block and
    #: mlp_residual arrange what the MLP saves around that)
    tp_overlap = False

    @property
    def kernel_tp(self) -> int:
        """Ways ``shard_kernel`` splits a kernel's head dim."""
        return 1

    def constrain_qkv(self, qkv):
        """The stacked (b, 3, n, s, d) qkv projection output."""
        return qkv

    def constrain_attn_out(self, o):
        """The attention context, (B, S, n, hd) or (B, n, S, hd)."""
        return o

    def shard_kernel(self, fn, arg_dims, out_dims):
        """A Mosaic kernel entry; ``arg_dims`` / ``out_dims``: per-array
        (batch_dim, head_dim) positions, (None, None) for replicated tables."""
        return fn

    def proj_up(self, subscripts, x, w, w_shard_dim: int):
        """Column-parallel projection einsum (qkv, MLP gate/up)."""
        return _plain_einsum(subscripts, x, w)

    def proj_down(self, subscripts, x, w, w_shard_dim: int, activation=None):
        """Row-parallel projection einsum (wo, MLP down) of ``activation(x)``."""
        return _plain_einsum(subscripts, x if activation is None else activation(x), w)

    def pin_tokens(self, a):
        """A (T, ...) token-major tensor of the switch-MoE block."""
        return a

    def pin_experts(self, a):
        """An (E, ...) expert-major buffer of the switch-MoE block."""
        return a

    def route_tokens(self, local_fn):
        """``local_fn(x, p, over)`` -> ``f(x, p)`` for the dropless MoE block,
        ``over`` being the mesh axes the tokens are split on."""
        return lambda x, p: local_fn(x, p, ())


LOCAL = Placement()
