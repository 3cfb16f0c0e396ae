"""Pallas TPU flash attention (forward + backward kernels, custom VJP).

Replaces the reference's FlashAttention-2 CUDA dependency
(flash_attn_unpadded_func import, reference: galvatron/core/tensor_parallel/
transformer.py:33-39,437-496) with a from-scratch FlashAttention-2-style
online-softmax kernel for the MXU:

- forward: grid (batch, heads, q_blocks, k_blocks), k innermost; running
  (m, l, acc) in VMEM scratch; causal blocks above the diagonal skipped with
  ``pl.when``; emits the per-row log-sum-exp for the backward.
- backward: two kernels — dK/dV (grid over k blocks, q innermost) and dQ
  (grid over q blocks, k innermost) — recomputing probabilities from the
  saved LSE, never materializing the (S, S) score matrix.
- causal attention inside the VMEM envelopes (``_use_blocked``), with fused
  RoPE or with none, takes the blocked family instead: one forward call per
  q row block with the causal structure static, and one combined dq/dk/dv
  backward. The grid family serves non-causal attention, shapes outside the
  envelopes and ring attention's per-hop calls.

Falls back to the einsum path automatically on CPU (interpret mode is used in
tests) and for shapes that don't tile (seq % block != 0).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.ops import pallas_common
from galvatron_tpu.ops.pallas_common import NEG_INF, compiler_params

LOG2E = 1.4426950408889634  # log2(e)
LN2 = 0.6931471805599453  # 1/log2(e)


def _single_buffered(shape, index_map) -> pl.BlockSpec:
    """BlockSpec pinned to single-buffering — a VMEM-budget optimization
    only, numerics identical to Mosaic's default double-buffering."""
    return pl.BlockSpec(
        shape, index_map, pipeline_mode=pl.Buffered(buffer_count=1)
    )


def _rope_rows(x, c, s):
    """Rotate-half RoPE on one (rows, d) block; c/s are (rows, d/2) fp32.
    Returns fp32 (cast back to the MXU dtype at the dot)."""
    xf = x.astype(jnp.float32)
    d2 = xf.shape[-1] // 2
    x1, x2 = xf[:, :d2], xf[:, d2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _rope_rows_t(y, c, s):
    """Transpose (inverse) rotation — maps gradients w.r.t. roped vectors back
    to gradients w.r.t. the raw q/k rows."""
    d2 = y.shape[-1] // 2
    y1, y2 = y[:, :d2], y[:, d2:]
    return jnp.concatenate([y1 * c + y2 * s, y2 * c - y1 * s], axis=-1)


def _rope_io(rope, block_q: int, block_k: int, d: int, qk_order: str):
    """(extra in_specs, extra inputs) for the fused-rope kernels: cos/sin row
    blocks for the q rows then the k rows. ``qk_order`` is 'ij' when the grid
    is (..., q_block, k_block) and 'ji' when it is (..., k_block, q_block)."""
    if rope is None:
        return [], []
    cos, sin = rope
    if qk_order == "ij":
        qrow = pl.BlockSpec((block_q, d // 2), lambda b_, h_, i, j: (i, 0))
        krow = pl.BlockSpec((block_k, d // 2), lambda b_, h_, i, j: (j, 0))
    else:
        qrow = pl.BlockSpec((block_q, d // 2), lambda b_, h_, j, i: (i, 0))
        krow = pl.BlockSpec((block_k, d // 2), lambda b_, h_, j, i: (j, 0))
    return [qrow, qrow, krow, krow], [cos, sin, cos, sin]


def _dispatch_causal(causal, contributes, fully_below, accum):
    """Run ``accum(masked)`` under the right predicate. Causal blocks fully
    below the diagonal skip the mask arithmetic (it is a no-op there — and
    iota/where on every score element is a sizeable share of a VPU-bound
    kernel); diagonal-straddling blocks apply it; non-causal blocks always
    run unmasked. ``fully_below`` implies ``contributes``, so the two
    branches are disjoint and exhaustive over contributing blocks."""
    if not causal:
        accum(False)
        return
    pl.when(fully_below)(lambda: accum(False))
    pl.when(contributes & jnp.logical_not(fully_below))(lambda: accum(True))


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, sm_scale, causal, block_q, block_k, num_k_blocks, rope):
    if rope:
        q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref = refs[:7]
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs[7:]
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: k block j contributes to q block i iff some (row, col) with
    # row >= col overlaps, i.e. (i+1)*block_q - 1 >= j*block_k (block sizes
    # may differ)
    if causal:
        last_j = jnp.minimum(((i + 1) * block_q - 1) // block_k, num_k_blocks - 1)
        contributes = ((i + 1) * block_q - 1) >= j * block_k
        # every row >= every col: min row i*bq, max col (j+1)*bk - 1
        fully_below = (i * block_q) >= ((j + 1) * block_k - 1)
    else:
        last_j = num_k_blocks - 1
        contributes = fully_below = None

    def _accum(masked):
        # keep q/k/v in their storage dtype (bf16): fp32 MXU matmul runs at a
        # fraction of the bf16 rate; accumulation stays fp32 via
        # preferred_element_type, softmax math stays fp32. RoPE (when fused)
        # rotates the VMEM-resident blocks — the roped q/k never round-trip
        # through HBM. The softmax scale folds the exp→exp2 base change into
        # its (single, fp32, post-matmul) multiply: the running max lives in
        # base-2 units and exp2 replaces exp. Scaling q instead would save
        # that multiply but requantizes q to bf16, doubling the output error.
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        if rope:
            q = _rope_rows(q, cq_ref[...], sq_ref[...]).astype(q_ref.dtype)
            k = _rope_rows(k, ck_ref[...], sk_ref[...]).astype(k_ref.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (sm_scale * LOG2E)  # (block_q, block_k), base-2 logits
        if masked:
            rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_old = m_scr[:, :1]  # (block_q, 1), lanes replicated
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m_old - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = alpha * acc_scr[:] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    _dispatch_causal(causal, contributes, fully_below, _accum)

    @pl.when(j == last_j)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # running max is in base-2 units; emit the natural-log LSE the
        # backward (and ring-attention combining) expects
        lse_ref[0, 0] = (
            m_scr[:, :1] * LN2 + jnp.log(jnp.maximum(l, 1e-30))
        ).astype(jnp.float32)


def _flash_fwd(q, k, v, rope, sm_scale, causal, block_q, block_k, interpret,
               out_dtype=None, kv_rep: int = 1):
    """``out_dtype`` overrides the output dtype (ring attention asks for fp32
    so per-hop block outputs are not requantized before the lse recombine).

    ``kv_rep`` > 1: GQA-native serving — k/v carry kv_heads = h/kv_rep and
    their index maps send head h to kv group h // kv_rep, so the group's
    queries share the RESIDENT K/V block (consecutive grid steps with an
    unchanged block index skip the re-fetch) instead of reading a
    materialized group-times-repeated copy from HBM."""
    b, h, s, d = q.shape
    nq, nk = s // block_q, s // block_k
    grid = (b, h, nq, nk)
    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=nk,
        rope=rope is not None,
    )
    rope_specs, rope_inputs = _rope_io(rope, block_q, block_k, d, "ij")
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b_, h_, i, j: (b_, h_ // kv_rep, j, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b_, h_, i, j: (b_, h_ // kv_rep, j, 0)),
    ] + rope_specs
    inputs = [q, k, v] + rope_inputs
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            # trailing unit dim keeps the block 2D-tileable on real TPUs
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), out_dtype or q.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_fwd_grid",
    )(*inputs)
    return out, lse


# ---------------------------------------------------------------------------
# Blocked-causal forward: one pallas call per q row block, statically
# unrolled k loop, value-carried (m, l, acc)
# ---------------------------------------------------------------------------
#
# For causal attention the grid-scan kernel above leaves real time on the
# table (measured on v5e, LLaMA-7B shape with RoPE: ~0.14 ms/layer/sample;
# opt-1.3b's tp-4 shard, no RoPE, d 64: 0.46 -> 0.27 ms a call, PERF.md §6):
# every (i, j) grid step re-ropes q, pays scratch init/finalize bookkeeping,
# and diagonal blocks run an iota+compare+select mask over the full score
# block. Specializing ONE pallas call per q row block makes the causal
# structure static — call i unrolls exactly the j <= i contributing k blocks,
# the diagonal block applies a precomputed additive triangular bias, q is
# roped once, and (m, l, acc) stay SSA values so Mosaic sees the whole
# dependence graph.
#
# ``rope`` is a static flag of the body (``rope is None`` at the caller, a
# Python branch taken while tracing): the two instances share no operand or
# equation the other does not need, and the RoPE instance lowers to the text
# it had before the no-RoPE one existed (tests/test_ops.py pins its equation
# counts). With RoPE the softmax scale (and the exp->exp2 base change) is
# folded into the q-side rope tables at trace time: the fp32 rotation output
# is cast to bf16 regardless, so the scale costs nothing and the score block
# needs no post-matmul multiply. Without RoPE there are no table operands
# and the scale is one fp32 multiply on the score block, as in the grid
# kernels (scaling q instead would requantize it to bf16, see _fwd_kernel).


def _fwd_kernel_blocked(*refs, nkb, block_q, block_k, stacked=False, rope=True,
                        sm_scale=None):
    if rope:
        (q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref, tri_ref,
         o_ref, lse_ref) = refs
    else:
        q_ref, k_ref, v_ref, tri_ref, o_ref, lse_ref = refs
    # ``stacked``: q/k/v are index-mapped blocks of ONE (b, 3, h, s, d)
    # array (one extra leading unit dim) — feeding the projection's stacked
    # output directly removes the q/k/v slice copies XLA otherwise
    # materializes for the custom-call operands (~1.2 ms/layer-batch on the
    # v5e 7B bench, the last structural copy the trace showed)
    lead = (0, 0, 0) if stacked else (0, 0)
    if rope:
        # cq/sq pre-scaled by sm_scale*LOG2E: scores come out in base-2 units
        q = _rope_rows(q_ref[lead], cq_ref[...], sq_ref[...]).astype(q_ref.dtype)
        kf = _rope_rows(k_ref[lead], ck_ref[...], sk_ref[...]).astype(k_ref.dtype)
    else:
        q = q_ref[lead]
        kf = k_ref[lead]
    vf = v_ref[lead]
    m = l = acc = None
    for j in range(nkb):
        kj = kf[j * block_k:(j + 1) * block_k]
        s = jax.lax.dot_general(
            q, kj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if not rope:
            s = s * (sm_scale * LOG2E)  # fp32, post-matmul: base-2 logits
        if j == nkb - 1:  # bq == bk: only the last block straddles the diagonal
            s = s + tri_ref[...].astype(jnp.float32)
        if j == 0:
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp2(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            acc = jax.lax.dot(
                p.astype(vf.dtype), vf[:block_k], preferred_element_type=jnp.float32
            )
        else:
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp2(s - m_new)
            alpha = jnp.exp2(m - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc = alpha * acc + jax.lax.dot(
                p.astype(vf.dtype), vf[j * block_k:(j + 1) * block_k],
                preferred_element_type=jnp.float32,
            )
            m = m_new
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[0, 0] = (m * LN2 + jnp.log(jnp.maximum(l, 1e-30))).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _flash_qkv(qkv, rope, sm_scale, block_q):
    out, _ = _flash_fwd_blocked_qkv(qkv, rope, sm_scale, block_q, pallas_common.use_interpret())
    return out


def _flash_qkv_fwd_rule(qkv, rope, sm_scale, block_q):
    out, lse = _flash_fwd_blocked_qkv(qkv, rope, sm_scale, block_q, pallas_common.use_interpret())
    return out, (qkv, out, lse, rope)


def _flash_qkv_bwd_rule(sm_scale, block_q, res, do):
    qkv, out, lse, rope = res
    s, d = qkv.shape[3], qkv.shape[4]
    if _use_blocked_bwd(s, d, True, block_q, block_q):
        bk, bq_sub = _bwd_blocks(block_q, rope is not None)
        dqkv = _blocked_bwd(rope)(
            None, None, None, do, out, lse, rope, sm_scale, bk, bq_sub,
            pallas_common.use_interpret(), qkv=qkv, do_stacked_out=True,
        )
    else:
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        dq, dk, dv = _flash_bwd(
            (q, k, v, out, lse, rope), do, sm_scale, True, block_q, block_q,
            pallas_common.use_interpret(),
        )
        dqkv = jnp.stack([dq, dk, dv], axis=1)
    drope = None if rope is None else jax.tree.map(jnp.zeros_like, rope)
    return dqkv, drope


_flash_qkv.defvjp(_flash_qkv_fwd_rule, _flash_qkv_bwd_rule)


def flash_attention_qkv(qkv, sm_scale=None, block_q: int = 1024, rope=None):
    """Stacked head-major entry: ``qkv`` is the fused projection's
    (b, 3, h, s, d) output (bias already added), consumed directly by the
    blocked-causal kernels and answered in the backward by one stacked dqkv.
    Causal attention only, with fused RoPE (``rope`` = (cos, sin)) or with
    none (learned / absolute positions) — callers gate on
    flash_qkv_supported."""
    d = qkv.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    return _flash_qkv(qkv, rope, sm_scale, min(block_q, qkv.shape[3]))


def flash_qkv_supported(s: int, d: int, causal: bool, block_q: int = 1024) -> bool:
    """Whether the stacked-qkv blocked path applies (modeling's gate): causal
    attention whose (s, d) lies inside the blocked forward's envelope, with
    RoPE or without."""
    return _use_blocked(s, d, causal, min(block_q, s), min(block_q, s))


# The last q-block call keeps the full k prefix resident in VMEM (k, v, rope
# rows, fp32 rope intermediates scale with s*d) and statically unrolls nq k
# iterations; both must stay bounded. With the raised vmem_limit_bytes
# (`pallas_common.VMEM_LIMIT_MB`: the 16 MB figure was Mosaic's default, not the
# chip's — BASELINE.md, "Round-4 VMEM discovery") the envelope extends to s=8192 at
# d=128, measured −15% on the full train step vs the grid kernels at that
# shape (BASELINE.md's dated table; v5e). Each envelope's threshold is derived from
# its measured scoped-VMEM anchor (charges scale ~linearly in s·d): fwd ~24 MB
# at s=8192·d=128; bwd 21.4 MB at s=4096·d=128 ⇒ ~43 MB at s=8192 — so the
# bwd 8k extension needs a ≥ ~48 MB budget, not the fwd's ≥ 32.
def _seq_envelope(mb_per_sxd, candidates, floor, budget_mb=pallas_common.VMEM_LIMIT_MB):
    """Largest s·d envelope whose estimated scoped charge (with a 1.1×
    safety factor) fits the VMEM budget. The floor is the envelope proven
    under Mosaic's 16 MB default."""
    for sxd in candidates + (floor,):
        if budget_mb >= mb_per_sxd * sxd * 1.1:
            return sxd
    return 0


_FWD_MB_PER_SXD = 24.0 / (8192 * 128)
_BLOCKED_MAX_SEQ_X_DIM = _seq_envelope(_FWD_MB_PER_SXD, (8192 * 128,), 4096 * 128)
_BLOCKED_MAX_UNROLL = 8


def _vmem_lanes(d):
    """Lanes an (s, d) slab occupies in VMEM: the envelopes were measured at
    d 128, and a d-64 slab pads to the same 128-lane tiles."""
    return -(-d // 128) * 128


# Forward row block of the no-RoPE instance. Its time is the softmax's, not
# the MXU's (d 64 and d 128 take the same time), so what counts is how much
# of the square above the diagonal a row block drags in: 62.5% of the square
# at 512 rows against 75% at 1024 (s 2048; measured -24 / -17 / -11% at s
# 1024 / 2048 / 4096, PERF.md §6). The RoPE instance re-ropes the k prefix in
# every call, which eats that (measured +3% at d 64), and keeps block_q.
_NO_ROPE_BQ = 512


def _no_rope_rows(block_q, s):
    if block_q % _NO_ROPE_BQ == 0 and s // _NO_ROPE_BQ <= _BLOCKED_MAX_UNROLL:
        return _NO_ROPE_BQ
    return block_q


def _use_blocked(s, d, causal, block_q, block_k):
    return (
        causal
        and block_q == block_k
        and s % block_q == 0
        and s * _vmem_lanes(d) <= _BLOCKED_MAX_SEQ_X_DIM
        and s // block_q <= _BLOCKED_MAX_UNROLL
    )


def _flash_fwd_blocked(
    q, k, v, rope, sm_scale, block_q, interpret, out_dtype=None, qkv=None,
    kv_rep: int = 1,
):
    """Blocked-causal forward. Either q/k/v (b, h, s, d) separately, or
    ``qkv`` stacked (b, 3, h, s, d) consumed via index-mapped block specs
    (no slice copies). Returns (out, lse). ``kv_rep`` > 1: GQA-native k/v at
    kv_heads = h/kv_rep, index-mapped h -> h // kv_rep (see _flash_fwd).
    ``rope`` None: the no-RoPE instance, without table operands."""
    stacked = qkv is not None
    if stacked:
        b, _, h, s, d = qkv.shape
        dtype = qkv.dtype
        inputs = (qkv, qkv, qkv)
    else:
        b, h, s, d = q.shape
        dtype = q.dtype
        inputs = (q, k, v)
    if rope is None:
        tables = ()
        block_q = _no_rope_rows(block_q, s)
    else:
        lam = sm_scale * LOG2E
        cos, sin = rope
        tables = (cos * lam, sin * lam, cos, sin)
    nq = s // block_q
    r = np.arange(block_q)
    tri = jnp.asarray(
        np.where(r[:, None] >= r[None, :], 0.0, NEG_INF), jnp.bfloat16
    )
    outs, lses = [], []
    for i in range(nq):
        nkb = i + 1
        kl = nkb * block_q
        if stacked:
            qkv_specs = [
                pl.BlockSpec((1, 1, 1, block_q, d), lambda b_, h_, i=i: (b_, 0, h_, i, 0)),
                pl.BlockSpec((1, 1, 1, kl, d), lambda b_, h_: (b_, 1, h_, 0, 0)),
                pl.BlockSpec((1, 1, 1, kl, d), lambda b_, h_: (b_, 2, h_, 0, 0)),
            ]
        else:
            qkv_specs = [
                pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i=i: (b_, h_, i, 0)),
                pl.BlockSpec((1, 1, kl, d), lambda b_, h_: (b_, h_ // kv_rep, 0, 0)),
                pl.BlockSpec((1, 1, kl, d), lambda b_, h_: (b_, h_ // kv_rep, 0, 0)),
            ]
        table_specs = [] if rope is None else [
            pl.BlockSpec((block_q, d // 2), lambda b_, h_, i=i: (i, 0)),
            pl.BlockSpec((block_q, d // 2), lambda b_, h_, i=i: (i, 0)),
            pl.BlockSpec((kl, d // 2), lambda b_, h_: (0, 0)),
            pl.BlockSpec((kl, d // 2), lambda b_, h_: (0, 0)),
        ]
        out_i, lse_i = pl.pallas_call(
            functools.partial(
                _fwd_kernel_blocked, nkb=nkb, block_q=block_q, block_k=block_q,
                stacked=stacked, rope=rope is not None, sm_scale=float(sm_scale),
            ),
            grid=(b, h),
            in_specs=qkv_specs + table_specs + [
                pl.BlockSpec((block_q, block_q), lambda b_, h_: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, d), lambda b_, h_: (b_, h_, 0, 0)),
                pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_: (b_, h_, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, h, block_q, d), out_dtype or dtype),
                jax.ShapeDtypeStruct((b, h, block_q, 1), jnp.float32),
            ],
            compiler_params=compiler_params(
                dimension_semantics=("parallel", "parallel")
            ),
            interpret=interpret,
            name="flash_fwd_qkv" if stacked else "flash_fwd_blocked",
        )(*inputs, *tables, tri)
        outs.append(out_i)
        lses.append(lse_i)
    if nq == 1:
        return outs[0], lses[0]
    return jnp.concatenate(outs, axis=2), jnp.concatenate(lses, axis=2)


# The no-RoPE instance is called through ``jax.jit`` (here and at the combined
# backward): a model's layers hand it the same shapes, so its unrolled bodies
# are traced and lowered for Mosaic once a model, not once a layer (opt-1.3b's
# 24 layers: PERF.md §6). XLA inlines the calls; each keeps its caller's
# scopes. The RoPE instance is called directly, as it always was: its lowered
# text is pinned (PERF.md §6), and a private function around it is a change.
_flash_fwd_blocked_no_rope = jax.jit(
    _flash_fwd_blocked,
    static_argnames=("sm_scale", "block_q", "interpret", "out_dtype", "kv_rep"),
)


def _blocked_fwd(rope):
    return _flash_fwd_blocked if rope is not None else _flash_fwd_blocked_no_rope


def _flash_fwd_blocked_qkv(qkv, rope, sm_scale, block_q, interpret):
    return _blocked_fwd(rope)(
        None, None, None, rope, sm_scale, block_q, interpret, qkv=qkv
    )


# ---------------------------------------------------------------------------
# Blocked-causal COMBINED backward: one pallas call per (batch, head),
# k-block-outer / q-sub-block-inner, dq + dk + dv in one pass
# ---------------------------------------------------------------------------
#
# The grid-style dK/dV + dQ kernels below recompute the score and dp matmuls
# in BOTH kernels (7 dots per block pair) and pay per-(i,j) grid bookkeeping;
# a round-4 train-step trace measured them at
# 13.7 ms/layer-batch plus 3.3 ms for the separate delta pass — 4.7x the
# blocked forward's 3.59 ms for 3.5x the FLOPs. This kernel applies the
# forward's round-3 treatment to the backward: ONE invocation per (b, h)
# with a statically unrolled causal loop (k blocks outer, q sub-blocks
# inner), sharing the recomputed p and dp across dq/dk/dv (5 dots per pair),
# computing delta = sum(do*out) in-kernel from operands it already reads,
# and (on the stacked path) consuming the (b, 3, h, s, d) qkv residual and
# emitting a stacked (b, 3, h, s, d) dqkv via index-mapped block specs so
# the fused-projection backward sees slice-copy-free operands.
#
# Scale folding (mirrors the forward): q is roped through tables pre-scaled
# by sm_scale*LOG2E, so base-2 scores are a plain dot and
#   dk_roped = sm_scale * ds^T @ R(q) = LN2 * ds^T @ q_scaled
#   dq_roped = sm_scale * ds   @ R(k)
# with the counter-rotations using the UNSCALED tables. Without RoPE (the
# body's static ``rope`` flag, as in the forward: no table operands, no
# rotations) the scores take the fp32 multiply after the dot and dk, dq are
# scaled by sm_scale on the way out.


def _bwd_kernel_blocked(*refs, nk, ratio, bq_sub, bk, stacked, sm_scale, rope=True):
    if rope:
        (q_ref, k_ref, v_ref, do_ref, out_ref, lse_ref,
         cos_ref, sin_ref) = refs[:8]
        outs = refs[8:]
    else:
        q_ref, k_ref, v_ref, do_ref, out_ref, lse_ref = refs[:6]
        outs = refs[6:]
    if stacked:
        (dqkv_ref,) = outs
    else:
        dq_ref, dk_ref, dv_ref = outs
    lead = (0, 0, 0) if stacked else (0, 0)
    s_len = q_ref.shape[-2]
    nqs = s_len // bq_sub
    lam = jnp.float32(sm_scale * LOG2E)

    # q sub-blocks roped lazily through scale-folded tables derived from the
    # unscaled ones in-kernel (separate scaled inputs would cost another
    # s x d/2 x 2 fp32 of VMEM; full-s rope would hold s x d fp32
    # intermediates — per-block keeps transients at bq_sub x d). Without
    # RoPE q stays raw and the scale goes onto the fp32 score block.
    q_s = [None] * nqs

    def q_rows(i):
        rows = slice(i * bq_sub, (i + 1) * bq_sub)
        if not rope:
            return q_ref[lead][rows]
        if q_s[i] is None:
            q_s[i] = _rope_rows(
                q_ref[lead][rows], cos_ref[rows] * lam, sin_ref[rows] * lam
            ).astype(q_ref.dtype)
        return q_s[i]

    do = do_ref[0, 0]
    # delta = sum(do*out) per row, computed lazily per q sub-block (a full-s
    # fp32 product would transiently hold s x d fp32)
    delta_c = [None] * nqs

    def delta_rows(i):
        if delta_c[i] is None:
            rows = slice(i * bq_sub, (i + 1) * bq_sub)
            delta_c[i] = jnp.sum(
                do[rows].astype(jnp.float32)
                * out_ref[0, 0][rows].astype(jnp.float32),
                axis=-1, keepdims=True,
            )
        return delta_c[i]

    lse2 = lse_ref[0, 0].astype(jnp.float32) * LOG2E  # base-2

    dq = [None] * nqs
    for j in range(nk):
        k_r = k_ref[lead][j * bk:(j + 1) * bk]
        if rope:
            k_r = _rope_rows(
                k_r, cos_ref[j * bk:(j + 1) * bk], sin_ref[j * bk:(j + 1) * bk],
            ).astype(k_ref.dtype)
        v_j = v_ref[lead][j * bk:(j + 1) * bk]
        dk_acc = dv_acc = None
        for i in range(j * ratio, nqs):
            rows = slice(i * bq_sub, (i + 1) * bq_sub)
            s2 = jax.lax.dot_general(
                q_rows(i), k_r, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if not rope:
                s2 = s2 * lam
            t = i - j * ratio
            if t < ratio:  # diagonal-straddling sub-block: iota mask with
                # the static row offset (cheaper in VMEM than a mask input)
                r_io = t * bq_sub + jax.lax.broadcasted_iota(
                    jnp.int32, (bq_sub, bk), 0
                )
                c_io = jax.lax.broadcasted_iota(jnp.int32, (bq_sub, bk), 1)
                s2 = jnp.where(r_io >= c_io, s2, NEG_INF)
            p = jnp.exp2(s2 - lse2[rows])
            do_i = do[rows]
            pv = jax.lax.dot_general(
                p.astype(do.dtype), do_i, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dv_acc = pv if dv_acc is None else dv_acc + pv
            dp = jax.lax.dot_general(
                do_i, v_j, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = (p * (dp - delta_rows(i))).astype(q_ref.dtype)
            dk_i = jax.lax.dot_general(
                ds, q_rows(i), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dk_acc = dk_i if dk_acc is None else dk_acc + dk_i
            dq_i = jax.lax.dot(ds, k_r, preferred_element_type=jnp.float32)
            dq[i] = dq_i if dq[i] is None else dq[i] + dq_i
        cols = slice(j * bk, (j + 1) * bk)
        if rope:  # dk_acc was taken against q scaled by sm_scale*LOG2E
            dk_out = _rope_rows_t(dk_acc * LN2, cos_ref[cols], sin_ref[cols])
        else:
            dk_out = dk_acc * sm_scale
        if stacked:
            dqkv_ref[0, 1, 0, cols] = dk_out.astype(dqkv_ref.dtype)
            dqkv_ref[0, 2, 0, cols] = dv_acc.astype(dqkv_ref.dtype)
        else:
            dk_ref[0, 0, cols] = dk_out.astype(dk_ref.dtype)
            dv_ref[0, 0, cols] = dv_acc.astype(dv_ref.dtype)
    for i in range(nqs):
        rows = slice(i * bq_sub, (i + 1) * bq_sub)
        # dq was accumulated against R(k) (unscaled tables), or against k
        dq_out = dq[i] * sm_scale
        if rope:
            dq_out = _rope_rows_t(dq_out, cos_ref[rows], sin_ref[rows])
        if stacked:
            dqkv_ref[0, 0, 0, rows] = dq_out.astype(dqkv_ref.dtype)
        else:
            dq_ref[0, 0, rows] = dq_out.astype(dq_ref.dtype)


def _flash_bwd_blocked(
    q, k, v, do, out, lse, rope, sm_scale, bk, bq_sub, interpret, qkv=None, do_stacked_out=False
):
    """Combined blocked-causal backward. Either separate (b, h, s, d) q/k/v
    (returns dq, dk, dv) or stacked ``qkv`` (b, 3, h, s, d) with
    ``do_stacked_out`` (returns dqkv)."""
    stacked = qkv is not None
    if stacked:
        b, _, h, s, d = qkv.shape
        dtype = qkv.dtype
    else:
        b, h, s, d = q.shape
        dtype = q.dtype
    nk = s // bk
    ratio = bk // bq_sub
    tables = () if rope is None else tuple(rope)  # cos, sin
    # single-buffer the big (s, d) slabs: Mosaic's default double-buffering
    # across grid steps costs 2x VMEM on every operand, which blows the 16M
    # scoped limit at the 7B shape (measured 19.3M); per-invocation compute
    # (~4 GFLOP) dwarfs the unoverlapped slab fetch
    if stacked:
        qkv_specs = [
            _single_buffered((1, 1, 1, s, d), lambda b_, h_: (b_, 0, h_, 0, 0)),
            _single_buffered((1, 1, 1, s, d), lambda b_, h_: (b_, 1, h_, 0, 0)),
            _single_buffered((1, 1, 1, s, d), lambda b_, h_: (b_, 2, h_, 0, 0)),
        ]
        qkv_inputs = (qkv, qkv, qkv)
    else:
        spec = _single_buffered((1, 1, s, d), lambda b_, h_: (b_, h_, 0, 0))
        qkv_specs = [spec, spec, spec]
        qkv_inputs = (q, k, v)
    bhsd = _single_buffered((1, 1, s, d), lambda b_, h_: (b_, h_, 0, 0))
    rows = _single_buffered((s, d // 2), lambda b_, h_: (0, 0))
    if do_stacked_out:
        out_specs = [_single_buffered((1, 3, 1, s, d), lambda b_, h_: (b_, 0, h_, 0, 0))]
        out_shape = [jax.ShapeDtypeStruct((b, 3, h, s, d), dtype)]
    else:
        out_specs = [bhsd, bhsd, bhsd]
        out_shape = [jax.ShapeDtypeStruct((b, h, s, d), dtype)] * 3
    res = pl.pallas_call(
        functools.partial(
            _bwd_kernel_blocked, nk=nk, ratio=ratio, bq_sub=bq_sub, bk=bk,
            stacked=stacked, sm_scale=float(sm_scale), rope=rope is not None,
        ),
        grid=(b, h),
        in_specs=qkv_specs + [
            bhsd,  # do
            bhsd,  # out
            # (s, 1) pads to (s, 128) lanes under TPU tiling — 1M fp32, so
            # single-buffer it like the slabs
            _single_buffered((1, 1, s, 1), lambda b_, h_: (b_, h_, 0, 0)),
        ] + [rows] * len(tables),
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
        name="flash_bwd_blocked",
    )(*qkv_inputs, do, out, lse, *tables)
    return res[0] if do_stacked_out else tuple(res)


_flash_bwd_blocked_no_rope = jax.jit(
    _flash_bwd_blocked,
    static_argnames=("sm_scale", "bk", "bq_sub", "interpret", "do_stacked_out"),
)


def _blocked_bwd(rope):
    return _flash_bwd_blocked if rope is not None else _flash_bwd_blocked_no_rope


# VMEM budget for the combined backward: resident operands + the (bq_sub, bk)
# fp32 score/p/dp/ds transients. (256, 512) was originally forced by
# Mosaic's 16 MB default budget; with the raised limit, (512, 512) and
# (512, 1024) are legal but measure FLAT on the full train step at s=2048
# and within noise at s=4096 (BASELINE.md, "Round-4 VMEM discovery") — per-block
# bookkeeping is not what bounds this kernel — so the proven config stays.
_BWD_BQ_SUB = 256
_BWD_BK = 512
# the no-RoPE instance has no (s, d/2) tables resident and no rotations in its
# pairs; (512, 512) measures -7% against (512, 256) at s 1024 ... 4096, d 64 and
# d 128 (PERF.md §6), larger k blocks measure worse
_BWD_BQ_SUB_NO_ROPE = 512
# the combined backward keeps ALL slabs + dq accumulators resident per
# invocation (s=4096/d=128 measures 21.4M scoped), which overflowed Mosaic's
# 16 MB default budget beyond s=2048; under the raised vmem_limit_bytes the
# envelope extends to s=8192, measured −9% (s=4096) / −15% (s=8192, with the
# forward extension) on the full train step vs the grid kernels
# (BASELINE.md's dated table; `git show 384a03e:experiments/ab_flash_bwd.py`, v5e).
# Beyond this (per-shape thresholds derived from the 21.4 MB s=4096 anchor; see
# _seq_envelope) the grid kernels serve.
_BWD_MB_PER_SXD = 21.4 / (4096 * 128)
_BWD_MAX_SEQ_X_DIM = _seq_envelope(
    _BWD_MB_PER_SXD, (8192 * 128, 4096 * 128), 2048 * 128
)


def _bwd_blocks(block_q, rope=True):
    """(bk, bq_sub) the combined backward actually uses for a forward block
    size ``block_q``, for the RoPE instance or the no-RoPE one (whose
    sub-block is the k block or 512, so it tiles whatever the RoPE one does)."""
    bk = min(_BWD_BK, block_q)
    return bk, min(_BWD_BQ_SUB if rope else _BWD_BQ_SUB_NO_ROPE, bk)


def _use_blocked_bwd(s, d, causal, block_q, block_k):
    bk, bq_sub = _bwd_blocks(block_q)
    return (
        _use_blocked(s, d, causal, block_q, block_k)
        and s * _vmem_lanes(d) <= _BWD_MAX_SEQ_X_DIM
        and s % bk == 0
        and bk % bq_sub == 0
    )


# ---------------------------------------------------------------------------
# Backward kernels (grid style — non-causal attention, shapes outside the
# blocked envelopes, ring attention's per-hop calls)
# ---------------------------------------------------------------------------


def _bwd_dkv_kernel(*refs, sm_scale, causal, block_q, block_k, num_q_blocks, rope):
    if rope:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         cq_ref, sq_ref, ck_ref, sk_ref, dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    j = pl.program_id(2)  # k block
    i = pl.program_id(3)  # q block (innermost)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if causal:
        contributes = ((i + 1) * block_q - 1) >= j * block_k
        fully_below = (i * block_q) >= ((j + 1) * block_k - 1)
    else:
        contributes = fully_below = None

    def _accum(masked):
        # bf16 MXU inputs, fp32 accumulate/softmax, base-2 logits with the
        # base change folded into the fp32 post-matmul scale (see _fwd_kernel
        # note). ds omits the sm_scale factor; the dk finalize multiplies it
        # back in once per k block.
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        if rope:
            q = _rope_rows(q, cq_ref[...], sq_ref[...]).astype(q_ref.dtype)
            k = _rope_rows(k, ck_ref[...], sk_ref[...]).astype(k_ref.dtype)
        lse2 = lse_ref[0, 0].astype(jnp.float32) * LOG2E  # (block_q, 1), base-2
        delta = delta_ref[0, 0].astype(jnp.float32)  # (block_q, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (sm_scale * LOG2E)
        if masked:
            rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp2(s - lse2)  # softmax probs
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)  # natural-units dL/ds except the sm_scale factor
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _dispatch_causal(causal, contributes, fully_below, _accum)

    @pl.when(i == num_q_blocks - 1)
    def _finalize():
        dk = dk_scr[:] * sm_scale  # ds omitted sm_scale in the accumulation
        if rope:
            # dk was accumulated w.r.t. the ROPED k — counter-rotate back
            dk = _rope_rows_t(dk, ck_ref[...], sk_ref[...])
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, sm_scale, causal, block_q, block_k, num_k_blocks, rope):
    if rope:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         cq_ref, sq_ref, ck_ref, sk_ref, dq_ref, dq_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr) = refs
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # k block (innermost)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    if causal:
        last_j = jnp.minimum(((i + 1) * block_q - 1) // block_k, num_k_blocks - 1)
        contributes = ((i + 1) * block_q - 1) >= j * block_k
        fully_below = (i * block_q) >= ((j + 1) * block_k - 1)
    else:
        last_j = num_k_blocks - 1
        contributes = fully_below = None

    def _accum(masked):
        # bf16 MXU inputs, fp32 accumulate/softmax, base-2 logits with the
        # base change folded into the fp32 post-matmul scale (see _fwd_kernel
        # note). ds omits the sm_scale factor; the finalize multiplies it
        # back in once per q block.
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        if rope:
            q = _rope_rows(q, cq_ref[...], sq_ref[...]).astype(q_ref.dtype)
            k = _rope_rows(k, ck_ref[...], sk_ref[...]).astype(k_ref.dtype)
        lse2 = lse_ref[0, 0].astype(jnp.float32) * LOG2E  # (block_q, 1), base-2
        delta = delta_ref[0, 0].astype(jnp.float32)  # (block_q, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (sm_scale * LOG2E)
        if masked:
            rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp2(s - lse2)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dq_scr[:] += jax.lax.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        )

    _dispatch_causal(causal, contributes, fully_below, _accum)

    @pl.when(j == last_j)
    def _finalize():
        dq = dq_scr[:] * sm_scale  # ds omitted sm_scale in the accumulation
        if rope:
            # dq was accumulated w.r.t. the ROPED q — counter-rotate back
            dq = _rope_rows_t(dq, cq_ref[...], sq_ref[...])
        dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _flash_bwd(res, do_bhsd, sm_scale, causal, block_q, block_k, interpret):
    q, k, v, out, lse, rope = res
    delta = jnp.sum(
        do_bhsd.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )  # (b, h, s, 1)
    return _flash_bwd_parts(
        q, k, v, do_bhsd, lse, delta, rope, sm_scale, causal, block_q, block_k,
        interpret,
    )


def _flash_bwd_parts(
    q, k, v, do_bhsd, lse, delta, rope, sm_scale, causal, block_q, block_k, interpret
):
    """dq/dk/dv kernels given the (possibly GLOBAL, e.g. ring-combined) LSE
    and delta = sum(do*out) — the flash decomposition makes per-k-block
    gradient contributions independent once those per-row statistics are
    fixed, which is what lets ring attention run these kernels per ring hop."""
    b, h, s, d = q.shape
    nq, nk = s // block_q, s // block_k

    qspec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, j, i: (b_, h_, i, 0))
    kspec = pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, j, i: (b_, h_, j, 0))
    rowspec = pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, j, i: (b_, h_, i, 0))
    rope_specs_ji, rope_inputs = _rope_io(rope, block_q, block_k, d, "ji")
    dkv_in_specs = [qspec, kspec, kspec, qspec, rowspec, rowspec] + rope_specs_ji
    dkv_inputs = [q, k, v, do_bhsd, lse, delta] + rope_inputs
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel,
            sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_q_blocks=nq,
            rope=rope is not None,
        ),
        grid=(b, h, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, j, i: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, j, i: (b_, h_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*dkv_inputs)

    qspec2 = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    kspec2 = pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_, j, 0))
    rowspec2 = pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, i, 0))
    rope_specs_ij, rope_inputs_ij = _rope_io(rope, block_q, block_k, d, "ij")
    dq_in_specs = [qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2] + rope_specs_ij
    dq_inputs = [q, k, v, do_bhsd, lse, delta] + rope_inputs_ij
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel,
            sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_k_blocks=nk,
            rope=rope is not None,
        ),
        grid=(b, h, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*dq_inputs)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API with custom VJP ((B, S, n, d) layout, matching modeling.attention)
# ---------------------------------------------------------------------------


def _fwd_dispatch(q, k, v, rope, sm_scale, causal, block_q, block_k, interpret):
    # GQA-native: k/v may carry kv_heads < heads; the kernels serve each kv
    # group's queries from the resident grouped K/V block (h -> h // rep
    # index maps) instead of a materialized repeated copy
    kv_rep = q.shape[1] // k.shape[1]
    if _use_blocked(q.shape[2], q.shape[3], causal, block_q, block_k):
        return _blocked_fwd(rope)(
            q, k, v, rope, sm_scale, block_q, interpret, kv_rep=kv_rep
        )
    return _flash_fwd(
        q, k, v, rope, sm_scale, causal, block_q, block_k, interpret,
        kv_rep=kv_rep,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, rope, sm_scale, causal, block_q, block_k):
    out, _ = _fwd_dispatch(q, k, v, rope, sm_scale, causal, block_q, block_k,
                           pallas_common.use_interpret())
    return out


def _flash_fwd_rule(q, k, v, rope, sm_scale, causal, block_q, block_k):
    out, lse = _fwd_dispatch(q, k, v, rope, sm_scale, causal, block_q, block_k,
                             pallas_common.use_interpret())
    return out, (q, k, v, out, lse, rope)


def _flash_bwd_rule(sm_scale, causal, block_q, block_k, res, do):
    q, k, v, out, lse, rope = res
    kv_rep = q.shape[1] // k.shape[1]
    if kv_rep > 1:
        # backward serves the repeated layout (the bwd kernels accumulate dk
        # per full head); group gradients are the exact sum over the group
        b, kvh, s, d = k.shape
        k = jnp.broadcast_to(k[:, :, None], (b, kvh, kv_rep, s, d)).reshape(
            b, kvh * kv_rep, s, d
        )
        v = jnp.broadcast_to(v[:, :, None], (b, kvh, kv_rep, s, d)).reshape(
            b, kvh * kv_rep, s, d
        )
        res = (q, k, v, out, lse, rope)
    if _use_blocked_bwd(q.shape[2], q.shape[3], causal, block_q, block_k):
        bk, bq_sub = _bwd_blocks(block_q, rope is not None)
        dq, dk, dv = _blocked_bwd(rope)(
            q, k, v, do, out, lse, rope, sm_scale, bk, bq_sub, pallas_common.use_interpret(),
        )
    else:
        dq, dk, dv = _flash_bwd(res, do, sm_scale, causal, block_q, block_k,
                                pallas_common.use_interpret())
    if kv_rep > 1:
        b, h, s, d = dk.shape
        dk = dk.reshape(b, h // kv_rep, kv_rep, s, d).sum(axis=2)
        dv = dv.reshape(b, h // kv_rep, kv_rep, s, d).sum(axis=2)
    drope = None if rope is None else jax.tree.map(jnp.zeros_like, rope)
    return dq, dk, dv, drope


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention_hm(
    q,
    k,
    v,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 1024,
    block_k: int = 1024,
    rope=None,
):
    """Head-major entry: q/k/v and the result are (batch, heads, seq, head_dim).

    The kernels are head-major internally, so this skips the (B,S,H,D) <->
    (B,H,S,D) boundary transposes entirely. Callers that can produce q/k/v
    head-major (modeling's einsum projection) should use this; measured
    ~0.32 ms/layer/sample on the v5e 7B-shape bench vs the transposing
    wrapper. Untileable shapes fall back through the (B,S,H,D) path.

    GQA-NATIVE: k/v may carry kv_heads < heads (heads % kv_heads == 0) —
    the forward kernels serve each kv group's queries from the resident
    grouped K/V block instead of a materialized repeated copy (group-factor
    less K/V HBM traffic; reference serves GQA natively the same way via
    head-group splitting, galvatron/core/tensor_parallel/transformer.py:
    679-708)."""
    b, h, s, d = q.shape
    if h % k.shape[1]:
        raise ValueError(f"heads {h} not divisible by kv_heads {k.shape[1]}")
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if not flash_tileable(s, block_q) or not flash_tileable(s, block_k):
        rep = h // k.shape[1]
        if rep > 1:  # the (B,S,H,D) fallback expects repeated K/V
            kvh = k.shape[1]
            k = jnp.broadcast_to(k[:, :, None], (b, kvh, rep, s, d)).reshape(b, h, s, d)
            v = jnp.broadcast_to(v[:, :, None], (b, kvh, rep, s, d)).reshape(b, h, s, d)
        out = flash_attention(
            jnp.transpose(q, (0, 2, 1, 3)),
            jnp.transpose(k, (0, 2, 1, 3)),
            jnp.transpose(v, (0, 2, 1, 3)),
            causal=causal, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
            rope=rope,
        )
        return jnp.transpose(out, (0, 2, 1, 3))
    return _flash(q, k, v, rope, sm_scale, causal, block_q, block_k)


def flash_tileable(s: int, block: int = 1024) -> bool:
    """True when a (…, s, …) shape takes the kernel path (no einsum
    fallback). The ONE tileability predicate: both wrappers and modeling's
    head-major gate key on it, so they cannot drift apart."""
    return s % min(block, s) == 0


def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 1024,
    block_k: int = 1024,
    rope=None,
):
    """q, k, v: (batch, seq, heads, head_dim); returns same layout.

    GQA callers repeat kv heads first (modeling._repeat_kv). Tiles of
    (block_q, block_k); shapes that don't tile fall back to the einsum path.

    ``rope``: optional (cos, sin) tables, each (seq, head_dim/2) fp32 — the
    rotate-half rotary embedding is applied to q/k blocks INSIDE the kernels
    (forward and both backward passes, with the transpose rotation mapping
    dq/dk back to raw coordinates). Fusing it removes the HBM round-trip of
    materialized roped q/k that a separate apply_rope costs (~0.27 ms/layer/
    sample on the v5e LLaMA-7B-shape bench).

    Defaults tuned on v5e (b8 x s2048 x h32 x d128) with bf16 MXU inputs:
    1024/1024 is fastest end-to-end; fp32 operands would run the MXU at a
    fraction of the bf16 rate (softmax/accumulation stay fp32).
    """
    b, s, n, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    if s == 1:
        # one query row: the tiled kernels degenerate to block 1 with zero
        # reuse — the dot-product decode path is exact and cheaper. With a
        # single same-length key, causal and full masks coincide.
        if rope is not None:
            from galvatron_tpu.models import modeling

            q = modeling.apply_rope(q, *rope)
            k = modeling.apply_rope(k, *rope)
        return decode_attention(
            q, k, v, q_offset=k.shape[1] - 1, sm_scale=sm_scale
        )
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if not flash_tileable(s, block_q) or not flash_tileable(s, block_k):
        from galvatron_tpu.models import modeling

        if rope is not None:
            q = modeling.apply_rope(q, *rope)
            k = modeling.apply_rope(k, *rope)
        # honor the caller's mask and scale (attention_xla divides by sqrt(d),
        # so pre-scale q to express an arbitrary sm_scale)
        q = q * jnp.asarray(sm_scale * np.sqrt(d), q.dtype)
        cfg = modeling.ModelConfig(num_heads=n, hidden_size=n * d, attn_impl="xla", causal=causal)
        return modeling.attention_xla(q, k, v, cfg)
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    out = _flash(qt, kt, vt, rope, sm_scale, causal, block_q, block_k)
    return jnp.transpose(out, (0, 2, 1, 3))


def decode_attention(q, k, v, q_offset=0, sm_scale=None):
    """Single-query attention for KV-cache decode (q_len == 1).

    q: (B, 1, n, d); k/v: (B, S, kv, d), n % kv == 0. Flash tiling buys
    nothing for one query row — there is no q x k tile reuse, and the
    (block_q, block_k) kernels cannot even launch on q_len 1. The decode
    step is a pure dot-product: two einsums and a masked fp32 softmax.

    GQA-native: kv heads are NOT repeated. The group dim ``g = n // kv``
    rides inside the einsum (q reshaped head-dim (kv, g), kv-major to match
    modeling._repeat_kv's interleave), so the KV cache — the dominant HBM
    traffic of a decode step — is read once instead of materialized g x.

    ``q_offset``: absolute position of the query token, scalar or (B,)
    (continuous batching: each slot at its own depth). Keys at positions
    > offset are masked; cache tails past the write point never leak in.
    """
    b, q_len, n, d = q.shape
    assert q_len == 1, f"decode_attention requires q_len == 1, got {q_len}"
    kv = k.shape[2]
    g = n // kv
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    qg = q[:, 0].reshape(b, kv, g, d)
    scores = jnp.einsum("bkgh,bskh->bkgs", qg, k).astype(jnp.float32)
    scores = scores * sm_scale
    k_pos = jnp.arange(k.shape[1])
    allowed = k_pos[None] <= jnp.reshape(jnp.asarray(q_offset), (-1, 1))
    scores = jnp.where(allowed[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", probs, v)
    return out.reshape(b, 1, n, d)


# ---------------------------------------------------------------------------
# Paged decode: K/V live in a block pool, addressed through block tables
# ---------------------------------------------------------------------------


def _paged_decode_kernel(
    tables_ref,  # scalar-prefetch (B, mb) int32 — logical block j of row b
    off_ref,  # scalar-prefetch (B,) int32 — absolute position of the query
    q_ref,  # (1, 1, g, d) block of (B, kv, g, d)
    k_ref,  # (1, bs, 1, d) page of (N, bs, kv, d), chosen by the index map
    v_ref,
    o_ref,  # (1, 1, g, d)
    m_ref,  # VMEM (g, 1) fp32 running max
    l_ref,  # VMEM (g, 1) fp32 running denominator
    acc_ref,  # VMEM (g, d) fp32 running numerator
    *,
    sm_scale: float,
    block_size: int,
    max_blocks: int,
):
    """One grid step = one (row, kv head, logical block): FlashAttention-style
    online softmax over the row's pages. The page lives wherever the block
    table says — the index map resolves ``tables_ref[b, j]`` at prefetch time,
    so the DMA engine streams exactly the pages this row owns and the gather
    is never materialized in HBM."""
    bi = pl.program_id(0)
    ji = pl.program_id(2)

    @pl.when(ji == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip pages entirely past the query position (their scores would all
    # mask out anyway; the predicate saves the VPU work)
    @pl.when(ji * block_size <= off_ref[bi])
    def _accum():
        qb = q_ref[0, 0].astype(jnp.float32)  # (g, d)
        kb = k_ref[0, :, 0].astype(jnp.float32)  # (bs, d)
        vb = v_ref[0, :, 0].astype(jnp.float32)
        s = jnp.dot(qb, kb.T) * sm_scale  # (g, bs)
        k_pos = ji * block_size + jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
        s = jnp.where(k_pos <= off_ref[bi], s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, vb)

    @pl.when(ji == max_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_decode_attention(
    q, k_pages, v_pages, block_tables, q_offset, sm_scale=None, impl: str = "auto"
):
    """``decode_attention`` over paged K/V: one query token per row, keys and
    values gathered through a block table instead of a contiguous cache row.

    q: (B, 1, n, d); k_pages/v_pages: (num_blocks, block_size, kv, d) — the
    serving block pool for ONE layer; block_tables: (B, max_blocks) int32
    mapping row b's logical block j to a pool block (entries past a row's
    reserved capacity point at the null block and are masked by ``q_offset``);
    q_offset: (B,) absolute query positions.

    ``impl``: 'xla' gathers pages into a contiguous (B, S, kv, d) view and
    delegates to :func:`decode_attention` — bit-identical to the slot
    engine's decode when block_size divides its max_seq_len, which is what
    the paged/slot parity tests pin. 'pallas' runs the online-softmax kernel
    above (per-page DMA via scalar-prefetched tables, no materialized
    gather; interpret mode on CPU). 'auto' picks pallas on TPU, xla
    elsewhere.
    """
    b, q_len, n, d = q.shape
    assert q_len == 1, f"paged_decode_attention requires q_len == 1, got {q_len}"
    num_blocks, block_size, kv, _ = k_pages.shape
    max_blocks = block_tables.shape[1]
    g = n // kv
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(f"impl must be auto|xla|pallas, got {impl!r}")
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    offsets = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32).reshape(-1), (b,))

    if impl == "xla":
        k = k_pages[block_tables].reshape(b, max_blocks * block_size, kv, d)
        v = v_pages[block_tables].reshape(b, max_blocks * block_size, kv, d)
        return decode_attention(q, k, v, q_offset=offsets, sm_scale=sm_scale)

    qg = q[:, 0].reshape(b, kv, g, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kv, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, ji, tables, off: (bi, hi, 0, 0)),
            pl.BlockSpec(
                (1, block_size, 1, d),
                lambda bi, hi, ji, tables, off: (tables[bi, ji], 0, hi, 0),
            ),
            pl.BlockSpec(
                (1, block_size, 1, d),
                lambda bi, hi, ji, tables, off: (tables[bi, ji], 0, hi, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, d), lambda bi, hi, ji, tables, off: (bi, hi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel,
            sm_scale=float(sm_scale),
            block_size=block_size,
            max_blocks=max_blocks,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=pallas_common.use_interpret(),
        name="flash_paged_decode",
    )(block_tables.astype(jnp.int32), offsets, qg, k_pages, v_pages)
    return out.reshape(b, 1, n, d)
