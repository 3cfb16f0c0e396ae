"""Ulysses-style context parallelism: sequence all-to-all over ICI.

The second long-context capability beyond the reference (SURVEY §2.3: no
CP/ring/Ulysses anywhere in Galvatron) and the alternative to ring attention
(galvatron_tpu.parallel.ring): instead of rotating K/V blocks around a ring,
one ``all_to_all`` re-shards activations from sequence-sharded to
head-sharded, each device runs *full-sequence* attention for its head subset
(on TPU: the Pallas flash kernel), and a second ``all_to_all`` restores
sequence sharding.

Trade-off vs ring (why both exist): Ulysses moves 2×(q+k+v+o)/cp bytes in two
bursty all-to-alls and keeps the attention core un-tiled (best when heads ≥
cp and the MXU-friendly full-length kernel wins); ring moves k+v per step
overlapped with compute and has no head-count constraint (best at extreme
sequence lengths or few heads). The strategy dimension ``cp_impl`` selects
per layer.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax

import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from galvatron_tpu.models import modeling
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.models.placement import LOCAL, Placement
from galvatron_tpu.parallel.mesh import ambient_or, manual_axis_names


def _a2a_attn_local(q, k, v, cfg: ModelConfig, axis_name, cp: int):
    """Runs inside shard_map with ``axis_name`` manual. q local:
    (B, S/cp, n, d) sequence-sharded; k/v may still be at kv_heads — the
    attention core GQA-repeats after the all-to-all, so grouped K/V cross the
    CP axes at 1/group_factor of the repeated volume."""
    # seq-sharded → head-sharded: (B, S/cp, n, d) → (B, S, n/cp, d)
    q = jax.lax.all_to_all(q, axis_name, 2, 1, tiled=True)
    k = jax.lax.all_to_all(k, axis_name, 2, 1, tiled=True)
    v = jax.lax.all_to_all(v, axis_name, 2, 1, tiled=True)
    o = modeling.attention(q, k, v, cfg)  # full-sequence causal core
    # head-sharded → seq-sharded
    return jax.lax.all_to_all(o, axis_name, 1, 2, tiled=True)


def ulysses_attention(
    q, k, v, cfg: ModelConfig, mesh: Mesh, cp_axes: Sequence[str],
    batch_axes: Sequence[str] = (), head_axes: Sequence[str] = (),
):
    """q/k/v: (B, S, n, d) global arrays, sequence sharded over ``cp_axes``;
    n must be divisible by the CP degree (the Ulysses head constraint).
    ``batch_axes``/``head_axes``: the layer's dp/tp axes — the region is
    fully manual (see mesh.manual_axis_names: GSPMD cannot partition the
    Mosaic attention core on a real multi-chip TPU), so the batch/head dims
    must carry their sharding explicitly."""
    cp = int(np.prod([mesh.shape[a] for a in cp_axes]))
    tp = int(np.prod([mesh.shape[a] for a in head_axes])) if head_axes else 1
    # the head dim is tp-sharded inside the manual region, so the a2a splits
    # the tp-LOCAL head count — validate that, not the global one
    if q.shape[2] % tp or (q.shape[2] // tp) % cp:
        raise ValueError(
            f"cp_impl='a2a' needs the tp-local head count "
            f"{q.shape[2]}/tp={tp} divisible by cp={cp} "
            "(use cp_impl='ring' for few-head models)"
        )
    kv = k.shape[2]
    if kv % tp or (kv // tp) % cp:  # grouped K/V can't split over tp×cp — repeat
        k = modeling._repeat_kv(k, q.shape[2] // kv)
        v = modeling._repeat_kv(v, q.shape[2] // kv)
    if cfg.attn_impl == "ring":  # never recurse into the ring dispatch
        cfg = cfg.replace(attn_impl="xla")
    axis = tuple(cp_axes)
    spec = P(tuple(batch_axes) or None, axis, tuple(head_axes) or None, None)
    mesh = ambient_or(mesh)
    fn = jax.shard_map(
        functools.partial(_a2a_attn_local, cfg=cfg, axis_name=axis, cp=cp),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names=manual_axis_names(mesh),
        check_vma=False,
    )
    return fn(q, k, v)


def ulysses_decoder_layer(
    x, p, cfg: ModelConfig, mesh, cp_axes, cos_sin,
    batch_axes: Sequence[str] = (), head_axes: Sequence[str] = (),
    place: Placement = LOCAL,
):
    """Decoder layer with the attention core Ulysses-parallelized (drop-in for
    modeling.decoder_layer when a layer strategy sets cp > 1, cp_impl='a2a').
    Projections and RoPE run at the global level (GSPMD shards them over the
    sequence); only the core crosses the CP axes."""

    def attn(xn):
        b, s, h = xn.shape
        hd = cfg.head_dim
        q, k, v = modeling.project_qkv_heads(xn, p["attn"], cfg)
        if cfg.pos_embed == "rope":
            cos, sin = cos_sin
            q = modeling.apply_rope(q, cos, sin)
            k = modeling.apply_rope(k, cos, sin)
        # K/V stay at kv_heads across the all-to-all (GQA repeat happens in
        # the local attention core) — group_factor× less CP traffic
        o = place.constrain_attn_out(
            ulysses_attention(
                q, k, v, cfg, mesh, cp_axes,
                batch_axes=batch_axes, head_axes=head_axes,
            )
        )
        return modeling.attn_output(o, p["attn"], cfg, xn.dtype)

    x = x + attn(modeling.norm(x, p["attn_norm"], cfg))
    x = x + modeling.mlp_block(
        modeling.norm(x, p["mlp_norm"], cfg), p["mlp"], cfg, place=place
    )
    return x
