"""Ring attention: context parallelism over an ICI ring.

A first-class long-context capability the reference lacks entirely (SURVEY
§2.3: no CP/ring/Ulysses anywhere in Galvatron; its long-context story is
Megatron-SP + FlashAttention + ckpt only). Sequence is sharded over the CP
mesh axes; K/V blocks rotate around the ring via ``lax.ppermute`` while each
device accumulates its queries' attention with online softmax — O(S/cp)
activation memory per device, exact causal attention.

Schedule: step 0 attends to the local (diagonal) K/V block, so the running
max starts finite; later steps mask by global position (blocks entirely in
the future contribute exp(-inf - m) = 0, never NaN).

Two per-hop compute paths:

- **Pallas flash blocks** (``_ring_flash``, the default when the local
  sequence tiles): each hop runs the flash-attention forward kernel on the
  resident K/V block (causal on the diagonal hop, unmasked on past hops,
  skipped on future hops) and folds the block's normalized output into a
  running (max, sum, acc) via its log-sum-exp. The backward is a second ring
  pass over the flash dq/dkv kernels with the GLOBAL lse/delta — the flash
  decomposition makes per-block gradient contributions independent once the
  per-row statistics are fixed; dk/dv accumulators rotate with their K/V
  block and arrive home after cp hops.
- **einsum fallback** (``_ring_attn_local``) for shapes that don't tile.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from galvatron_tpu.models import modeling
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.models.placement import LOCAL, Placement
from galvatron_tpu.parallel.mesh import ambient_or, manual_axis_names
from galvatron_tpu.ops import pallas_common
from galvatron_tpu.ops.flash_attention import _flash_bwd_parts, _flash_fwd
from galvatron_tpu.ops.pallas_common import NEG_INF


def _ring_attn_local(q, k, v, idx_arr, axis_name: str, cp: int, sm_scale: float):
    """Runs inside shard_map with ``axis_name`` manual. q/k/v local:
    (B, S/cp, n, d), sequence sharded in ring order; ``idx_arr`` is this
    shard's slice of arange(cp) (the ring position)."""
    idx = idx_arr[0]
    s_local = q.shape[1]
    perm = [(i, (i + 1) % cp) for i in range(cp)]  # kv block i → device i+1

    q32 = q.astype(jnp.float32)
    rows = idx * s_local + jnp.arange(s_local)  # global q positions

    def accum(carry, k_cur, v_cur, owner):
        m, l, acc = carry
        cols = owner * s_local + jnp.arange(s_local)
        scores = (
            jnp.einsum("bqnh,bknh->bnqk", q32, k_cur.astype(jnp.float32)) * sm_scale
        )
        mask = rows[:, None] >= cols[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1)
        acc_new = alpha[..., None] * acc + jnp.einsum(
            "bnqk,bknh->bnqh", p, v_cur.astype(jnp.float32)
        )
        return m_new, l_new, acc_new

    b, _, n, d = q.shape
    m0 = jnp.full((b, n, s_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n, s_local), jnp.float32)
    acc0 = jnp.zeros((b, n, s_local, d), jnp.float32)
    # hop 0: the local (diagonal) block — no rotation needed; scan steps
    # permute first, then compute, so no hop rotates K/V just to discard it
    carry0 = accum((m0, l0, acc0), k, v, idx)

    def step(carry, step_idx):
        k_cur, v_cur, mla = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        owner = (idx - step_idx) % cp  # whose kv block we now hold
        return (k_cur, v_cur, accum(mla, k_cur, v_cur, owner)), None

    (_, _, (m, l, acc)), _ = jax.lax.scan(step, (k, v, carry0), jnp.arange(1, cp))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # (B, S/cp, n, d)


# ---------------------------------------------------------------------------
# Flash-block ring (Pallas kernels per hop, custom VJP)
# ---------------------------------------------------------------------------


def _ring_block(is_past, q, k_cur, v_cur, sm_scale, block_q, block_k, interpret):
    """(fp32 out, lse) of q against a non-diagonal resident K/V block:
    unmasked when the block is in the past, nothing (lse = -inf) when it is
    in the future. The diagonal (locally causal) hop runs outside the scan."""

    def past(q, k_, v_):
        return _flash_fwd(
            q, k_, v_, None, sm_scale, False, block_q, block_k, interpret,
            out_dtype=jnp.float32,
        )

    def future(q, k_, v_):
        b, h, s, _ = q.shape
        return (
            jnp.zeros(q.shape, jnp.float32),
            jnp.full((b, h, s, 1), NEG_INF, jnp.float32),
        )

    return jax.lax.cond(is_past, past, future, q, k_cur, v_cur)


def _lse_combine(m, l, acc, o_b, lse_b):
    """Fold a block's normalized output into the running (max, sum, acc):
    o_b's unnormalized row sum is exp(lse_b), so blocks combine by lse like
    partial softmaxes."""
    m_new = jnp.maximum(m, lse_b)
    alpha = jnp.exp(m - m_new)
    w_b = jnp.exp(lse_b - m_new)
    return m_new, l * alpha + w_b, acc * alpha + o_b * w_b


def _ring_flash_fwd(q, k, v, idx, axis_name, cp, sm_scale, block_q, block_k, interpret):
    """q/k/v local (B, n, S/cp, d); ``idx`` the ring position scalar.
    Returns (out, global lse).

    Hop 0 (the diagonal, locally causal block) runs before the scan; each
    scan step permutes K/V first and then computes, so no hop rotates K/V
    only to discard the result."""
    perm = [(i, (i + 1) % cp) for i in range(cp)]
    b, h, s, d = q.shape

    o0, lse0 = _flash_fwd(
        q, k, v, None, sm_scale, True, block_q, block_k, interpret,
        out_dtype=jnp.float32,
    )
    m0 = jnp.full((b, h, s, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, s, d), jnp.float32)
    m0, l0, acc0 = _lse_combine(m0, l0, acc0, o0, lse0)

    def step(carry, step_idx):
        k_cur, v_cur, m, l, acc = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        owner = (idx - step_idx) % cp
        o_b, lse_b = _ring_block(
            owner < idx, q, k_cur, v_cur, sm_scale, block_q, block_k, interpret
        )
        m, l, acc = _lse_combine(m, l, acc, o_b, lse_b)
        return (k_cur, v_cur, m, l, acc), None

    (_, _, m, l, acc), _ = jax.lax.scan(
        step, (k, v, m0, l0, acc0), jnp.arange(1, cp)
    )
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _ring_flash(q, k, v, idx, axis_name, cp, sm_scale, block_q, block_k, interpret):
    out, _ = _ring_flash_fwd(q, k, v, idx, axis_name, cp, sm_scale, block_q, block_k, interpret)
    return out


def _ring_flash_fwd_rule(q, k, v, idx, axis_name, cp, sm_scale, block_q, block_k, interpret):
    out, lse = _ring_flash_fwd(q, k, v, idx, axis_name, cp, sm_scale, block_q, block_k, interpret)
    return out, (q, k, v, idx, out, lse)


def _ring_flash_bwd_rule(axis_name, cp, sm_scale, block_q, block_k, interpret, res, do):
    """Second ring pass over the flash dq/dkv kernels with the GLOBAL
    lse/delta. Hop 0 (diagonal) runs before the scan; scan steps permute
    first, then compute. dk/dv accumulators ride the ring with their K/V
    block — cp-1 hops inside the scan plus one final hop lands them home."""
    q, k, v, idx, out, lse = res
    perm = [(i, (i + 1) % cp) for i in range(cp)]
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )

    def block_grads(is_past, k_cur, v_cur):
        def past(k_, v_):
            return _flash_bwd_parts(
                q, k_, v_, do, lse, delta, None, sm_scale, False, block_q, block_k,
                interpret,
            )

        def future(k_, v_):
            return jnp.zeros_like(q), jnp.zeros_like(k_), jnp.zeros_like(v_)

        return jax.lax.cond(is_past, past, future, k_cur, v_cur)

    dq0, dk0, dv0 = _flash_bwd_parts(
        q, k, v, do, lse, delta, None, sm_scale, True, block_q, block_k, interpret
    )

    def step(carry, step_idx):
        k_cur, v_cur, dk_cur, dv_cur, dq = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)
        owner = (idx - step_idx) % cp
        dq_b, dk_b, dv_b = block_grads(owner < idx, k_cur, v_cur)
        dq = dq + dq_b.astype(jnp.float32)
        dk_cur = dk_cur + dk_b.astype(jnp.float32)
        dv_cur = dv_cur + dv_b.astype(jnp.float32)
        return (k_cur, v_cur, dk_cur, dv_cur, dq), None

    (_, _, dk, dv, dq), _ = jax.lax.scan(
        step,
        (k, v, dk0.astype(jnp.float32), dv0.astype(jnp.float32), dq0.astype(jnp.float32)),
        jnp.arange(1, cp),
    )
    dk = jax.lax.ppermute(dk, axis_name, perm)
    dv = jax.lax.ppermute(dv, axis_name, perm)
    didx = np.zeros(idx.shape, dtype=jax.dtypes.float0)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), didx


_ring_flash.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def _ring_flash_local(q, k, v, idx_arr, axis_name: str, cp: int, sm_scale: float, block: int):
    """shard_map body for the flash path. q/k/v local (B, S/cp, n, d)."""
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    out = _ring_flash(
        qt, kt, vt, idx_arr[0], axis_name, cp, sm_scale, block, block,
        pallas_common.use_interpret(),
    )
    return jnp.transpose(out, (0, 2, 1, 3))


def _flash_block_size(s_local: int) -> int:
    """Largest power-of-two tile <= 1024 dividing the local sequence; 0 if the
    shape doesn't tile (callers fall back to the einsum ring)."""
    for block in (1024, 512, 256, 128, 64, 32, 16, 8):
        if s_local % block == 0:
            return block
    return 0


def ring_attention(
    q, k, v, mesh: Mesh, cp_axes: Sequence[str], sm_scale: float | None = None,
    batch_axes: Sequence[str] = (), head_axes: Sequence[str] = (),
):
    """q/k/v: (B, S, n, d) global arrays; sequence ring-sharded over cp_axes.

    Uses the Pallas flash kernels per ring hop when the local sequence
    tiles; otherwise the einsum online-softmax fallback. ``batch_axes``/
    ``head_axes``: the layer's dp/tp axes — the batch and head dims keep
    their sharding through the (fully-manual) region instead of being
    gathered."""
    cp = int(np.prod([mesh.shape[a] for a in cp_axes]))
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    axis = tuple(cp_axes)
    b_ax = tuple(batch_axes) or None
    h_ax = tuple(head_axes) or None
    spec = P(b_ax, axis, h_ax, None)
    mesh = ambient_or(mesh)
    block = _flash_block_size(q.shape[1] // cp)
    if block:
        local = functools.partial(
            _ring_flash_local, axis_name=axis, cp=cp, sm_scale=sm_scale, block=block
        )
    else:
        local = functools.partial(
            _ring_attn_local, axis_name=axis, cp=cp, sm_scale=sm_scale
        )
    # ring position fed as a sharded arange rather than lax.axis_index: when
    # this shard_map nests inside the pipeline's manual-'pp' region, shardy
    # cannot lower axis_index (it would re-bind the parent's manual axes),
    # while plain data sharding over the cp axes works — same linearization
    # as ppermute over the axis tuple
    idx_arr = jnp.arange(cp, dtype=jnp.int32)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec, P(axis)),
        out_specs=spec,
        axis_names=manual_axis_names(mesh),
        check_vma=False,
    )
    return fn(q, k, v, idx_arr)


def ring_decoder_layer(
    x, p, cfg: ModelConfig, mesh, cp_axes, cos_sin,
    batch_axes: Sequence[str] = (), head_axes: Sequence[str] = (),
    place: Placement = LOCAL,
):
    """Decoder layer with the attention core ring-parallelized (drop-in for
    modeling.decoder_layer when a layer strategy sets cp > 1)."""

    def attn(xn):
        b, s, h = xn.shape
        hd = cfg.head_dim
        q, k, v = modeling.project_qkv_heads(xn, p["attn"], cfg)
        if cfg.pos_embed == "rope":
            cos, sin = cos_sin
            q = modeling.apply_rope(q, cos, sin)
            k = modeling.apply_rope(k, cos, sin)
        k = modeling._repeat_kv(k, cfg.num_heads // k.shape[2])
        v = modeling._repeat_kv(v, cfg.num_heads // v.shape[2])
        o = place.constrain_attn_out(
            ring_attention(
                q, k, v, mesh, cp_axes,
                batch_axes=batch_axes, head_axes=head_axes,
            )
        )
        return modeling.attn_output(o, p["attn"], cfg, xn.dtype)

    x = x + attn(modeling.norm(x, p["attn_norm"], cfg))
    x = x + modeling.mlp_block(
        modeling.norm(x, p["mlp_norm"], cfg), p["mlp"], cfg, place=place
    )
    return x
