"""A/B flash-attention forward variants in-context (paired layer-diff).

Variants (all forward-only; bench never differentiates):
  base : current galvatron_tpu.ops.flash_attention
  v1b  : same grid, softmax scale folded into the q-side rope tables
  v2c  : per-q-block specialized pallas calls, statically unrolled k loop,
         value-carried (m, l, acc), additive triangular bias on the diagonal
         block, scale folded into rope.

Usage: python experiments/ab_flash.py [--variants base,v1b,v2c] [--rounds 4]
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from galvatron_tpu.ops import flash_attention as fa

NEG_INF = -1e30
LOG2E = 1.4426950408889634
LN2 = 0.6931471805599453


def _rope_rows(x, c, s):
    xf = x.astype(jnp.float32)
    d2 = xf.shape[-1] // 2
    x1, x2 = xf[:, :d2], xf[:, d2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# ---------------------------------------------------------------------------
# v1b: current structure, scale folded into q rope tables
# ---------------------------------------------------------------------------


def _fwd_kernel_v1b(*refs, causal, block_q, block_k, num_k_blocks):
    q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref = refs[:7]
    o_ref, lse_ref, m_scr, l_scr, acc_scr = refs[7:]
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    if causal:
        last_j = jnp.minimum(((i + 1) * block_q - 1) // block_k, num_k_blocks - 1)
        contributes = ((i + 1) * block_q - 1) >= j * block_k
        fully_below = (i * block_q) >= ((j + 1) * block_k - 1)
    else:
        last_j = num_k_blocks - 1
        contributes = fully_below = None

    def _accum(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        # cq/sq pre-scaled by sm_scale*LOG2E: s comes out in base-2 units
        q = _rope_rows(q, cq_ref[...], sq_ref[...]).astype(q_ref.dtype)
        k = _rope_rows(k, ck_ref[...], sk_ref[...]).astype(k_ref.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if masked:
            rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_old = m_scr[:, :1]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m_old - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = alpha * acc_scr[:] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    fa._dispatch_causal(causal, contributes, fully_below, _accum)

    @pl.when(j == last_j)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0, 0] = (
            m_scr[:, :1] * LN2 + jnp.log(jnp.maximum(l, 1e-30))
        ).astype(jnp.float32)


def flash_v1b(q, k, v, causal=True, sm_scale=None, block_q=1024, block_k=1024, rope=None):
    b, s, n, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    assert rope is not None and s % block_q == 0 and s % block_k == 0
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    nq, nk = s // block_q, s // block_k
    lam = sm_scale * LOG2E
    cos, sin = rope
    cqs, sqs = cos * lam, sin * lam
    grid = (b, n, nq, nk)
    qrow = pl.BlockSpec((block_q, d // 2), lambda b_, h_, i, j: (i, 0))
    krow = pl.BlockSpec((block_k, d // 2), lambda b_, h_, i, j: (j, 0))
    out, _lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel_v1b, causal=causal, block_q=block_q, block_k=block_k,
            num_k_blocks=nk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_, j, 0)),
            qrow, qrow, krow, krow,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, n, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=fa._compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
    )(qt, kt, vt, cqs, sqs, cos, sin)
    return jnp.transpose(out, (0, 2, 1, 3))


# ---------------------------------------------------------------------------
# v2c: per-q-block specialized calls, unrolled k loop, value accumulation
# ---------------------------------------------------------------------------


def _fwd_kernel_v2c(*refs, nkb, diag, block_q, block_k, d):
    if diag:
        q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref, tri_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref, o_ref, lse_ref = refs
    q = _rope_rows(q_ref[0, 0], cq_ref[...], sq_ref[...]).astype(q_ref.dtype)
    kf = _rope_rows(k_ref[0, 0], ck_ref[...], sk_ref[...]).astype(k_ref.dtype)
    vf = v_ref[0, 0]
    m = l = acc = None
    for j in range(nkb):
        kj = kf[j * block_k:(j + 1) * block_k]
        s = jax.lax.dot_general(
            q, kj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if diag and j == nkb - 1:
            s = s + tri_ref[...].astype(jnp.float32)
        pv_j = None
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


def flash_v2c(q, k, v, causal=True, sm_scale=None, block_q=1024, block_k=1024, rope=None):
    b, s, n, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    assert rope is not None and causal and block_q == block_k and s % block_q == 0
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    nq = s // block_q
    lam = sm_scale * LOG2E
    cos, sin = rope
    cqs, sqs = cos * lam, sin * lam
    r = np.arange(block_q)
    tri = jnp.asarray(
        np.where(r[:, None] >= r[None, :], 0.0, NEG_INF), jnp.bfloat16
    )
    outs = []
    for i in range(nq):
        nkb = i + 1
        kl = nkb * block_k
        out_i, _lse_i = pl.pallas_call(
            functools.partial(
                _fwd_kernel_v2c, nkb=nkb, diag=True, block_q=block_q,
                block_k=block_k, d=d,
            ),
            grid=(b, n),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i=i: (b_, h_, i, 0)),
                pl.BlockSpec((1, 1, kl, d), lambda b_, h_: (b_, h_, 0, 0)),
                pl.BlockSpec((1, 1, kl, d), lambda b_, h_: (b_, h_, 0, 0)),
                pl.BlockSpec((block_q, d // 2), lambda b_, h_, i=i: (i, 0)),
                pl.BlockSpec((block_q, d // 2), lambda b_, h_, i=i: (i, 0)),
                pl.BlockSpec((kl, d // 2), lambda b_, h_: (0, 0)),
                pl.BlockSpec((kl, d // 2), lambda b_, h_: (0, 0)),
                pl.BlockSpec((block_q, block_k), lambda b_, h_: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, d), lambda b_, h_: (b_, h_, 0, 0)),
                pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_: (b_, h_, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, n, block_q, d), q.dtype),
                jax.ShapeDtypeStruct((b, n, block_q, 1), jnp.float32),
            ],
            compiler_params=fa._compiler_params(
                dimension_semantics=("parallel", "parallel")
            ),
        )(qt, kt, vt, cqs, sqs, cos, sin, tri)
        outs.append(out_i)
    out = jnp.concatenate(outs, axis=2) if nq > 1 else outs[0]
    return jnp.transpose(out, (0, 2, 1, 3))


# ---------------------------------------------------------------------------
# v2d: ONE call, both q blocks unrolled in-kernel (no output concat)
# ---------------------------------------------------------------------------


def _fwd_kernel_v2d(*refs, nq, nk, block_q, block_k, d):
    q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref, tri_ref, o_ref, lse_ref = refs
    qf = _rope_rows(q_ref[0, 0], cq_ref[...], sq_ref[...]).astype(q_ref.dtype)
    kf = _rope_rows(k_ref[0, 0], ck_ref[...], sk_ref[...]).astype(k_ref.dtype)
    vf = v_ref[0, 0]
    for i in range(nq):
        q = qf[i * block_q:(i + 1) * block_q]
        m = l = acc = None
        # causal, bq == bk: exactly blocks j <= i contribute; j == i is diagonal
        for j in range(i + 1):
            kj = kf[j * block_k:(j + 1) * block_k]
            s = jax.lax.dot_general(
                q, kj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            if j == i:
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
        o_ref[0, 0, i * block_q:(i + 1) * block_q] = (
            acc / jnp.maximum(l, 1e-30)
        ).astype(o_ref.dtype)
        lse_ref[0, 0, i * block_q:(i + 1) * block_q] = (
            m * LN2 + jnp.log(jnp.maximum(l, 1e-30))
        ).astype(jnp.float32)


def make_flash_v2d(block=1024):
    def flash_v2d(q, k, v, causal=True, sm_scale=None, block_q=None, block_k=None, rope=None):
        b, s, n, d = q.shape
        bq = bk = block
        if sm_scale is None:
            sm_scale = 1.0 / float(np.sqrt(d))
        assert rope is not None and causal and s % bq == 0
        qt = jnp.transpose(q, (0, 2, 1, 3))
        kt = jnp.transpose(k, (0, 2, 1, 3))
        vt = jnp.transpose(v, (0, 2, 1, 3))
        nq = s // bq
        lam = sm_scale * LOG2E
        cos, sin = rope
        cqs, sqs = cos * lam, sin * lam
        r = np.arange(bq)
        tri = jnp.asarray(np.where(r[:, None] >= r[None, :], 0.0, NEG_INF), jnp.bfloat16)
        full = pl.BlockSpec((1, 1, s, d), lambda b_, h_: (b_, h_, 0, 0))
        rows = pl.BlockSpec((s, d // 2), lambda b_, h_: (0, 0))
        out, _lse = pl.pallas_call(
            functools.partial(_fwd_kernel_v2d, nq=nq, nk=nq, block_q=bq, block_k=bk, d=d),
            grid=(b, n),
            in_specs=[full, full, full, rows, rows, rows, rows,
                      pl.BlockSpec((bq, bk), lambda b_, h_: (0, 0))],
            out_specs=[full, pl.BlockSpec((1, 1, s, 1), lambda b_, h_: (b_, h_, 0, 0))],
            out_shape=[
                jax.ShapeDtypeStruct((b, n, s, d), q.dtype),
                jax.ShapeDtypeStruct((b, n, s, 1), jnp.float32),
            ],
            compiler_params=fa._compiler_params(
                dimension_semantics=("parallel", "parallel")
            ),
        )(qt, kt, vt, cqs, sqs, cos, sin, tri)
        return jnp.transpose(out, (0, 2, 1, 3))

    return flash_v2d


def make_flash_v2c(block):
    return functools.partial(flash_v2c, block_q=block, block_k=block)


# ---------------------------------------------------------------------------
# v2e: per-q-block calls, bq=1024 / bk=512, explicit 2-deep dot pipeline
# (next block's MXU dot issued before current block's VPU softmax);
# v2f: same but ALL dots hoisted up front.
# ---------------------------------------------------------------------------


def _fwd_kernel_v2e(*refs, i, nkb, block_q, block_k, d, hoist_all):
    (q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref,
     tri0_ref, tri1_ref, o_ref, lse_ref) = refs
    q = _rope_rows(q_ref[0, 0], cq_ref[...], sq_ref[...]).astype(q_ref.dtype)
    kf = _rope_rows(k_ref[0, 0], ck_ref[...], sk_ref[...]).astype(k_ref.dtype)
    vf = v_ref[0, 0]
    ratio = block_q // block_k  # k blocks per q block

    def dot_j(j):
        kj = kf[j * block_k:(j + 1) * block_k]
        s = jax.lax.dot_general(
            q, kj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        # rows are i*block_q + r, cols j*block_k + c; the last `ratio` blocks
        # straddle the diagonal with static offsets 0, block_k, ...
        off = j * block_k - i * block_q
        if off >= 0:
            tri = tri0_ref if off == 0 else tri1_ref
            s = s + tri[...].astype(jnp.float32)
        return s

    if hoist_all:
        ss = [dot_j(j) for j in range(nkb)]
    else:
        ss = None
    m = l = acc = None
    s_cur = dot_j(0) if not hoist_all else None
    for j in range(nkb):
        s = ss[j] if hoist_all else s_cur
        if not hoist_all and j + 1 < nkb:
            s_cur = dot_j(j + 1)  # issue next dot before this block's softmax
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


def make_flash_v2e(block_q=1024, block_k=512, hoist_all=False):
    def flash_v2e(q, k, v, causal=True, sm_scale=None, rope=None, **_):
        b, s, n, d = q.shape
        bq, bk = block_q, block_k
        if sm_scale is None:
            sm_scale = 1.0 / float(np.sqrt(d))
        assert rope is not None and causal and s % bq == 0 and bq % bk == 0
        qt = jnp.transpose(q, (0, 2, 1, 3))
        kt = jnp.transpose(k, (0, 2, 1, 3))
        vt = jnp.transpose(v, (0, 2, 1, 3))
        nq = s // bq
        lam = sm_scale * LOG2E
        cos, sin = rope
        cqs, sqs = cos * lam, sin * lam
        r = np.arange(bq)[:, None]
        c = np.arange(bk)[None, :]
        tri0 = jnp.asarray(np.where(r >= c, 0.0, NEG_INF), jnp.bfloat16)
        tri1 = jnp.asarray(np.where(r >= c + bk, 0.0, NEG_INF), jnp.bfloat16)
        outs = []
        for i in range(nq):
            nkb = (i + 1) * (bq // bk)
            kl = nkb * bk
            out_i, _lse_i = pl.pallas_call(
                functools.partial(
                    _fwd_kernel_v2e, i=i, nkb=nkb, block_q=bq, block_k=bk, d=d,
                    hoist_all=hoist_all,
                ),
                grid=(b, n),
                in_specs=[
                    pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i=i: (b_, h_, i, 0)),
                    pl.BlockSpec((1, 1, kl, d), lambda b_, h_: (b_, h_, 0, 0)),
                    pl.BlockSpec((1, 1, kl, d), lambda b_, h_: (b_, h_, 0, 0)),
                    pl.BlockSpec((bq, d // 2), lambda b_, h_, i=i: (i, 0)),
                    pl.BlockSpec((bq, d // 2), lambda b_, h_, i=i: (i, 0)),
                    pl.BlockSpec((kl, d // 2), lambda b_, h_: (0, 0)),
                    pl.BlockSpec((kl, d // 2), lambda b_, h_: (0, 0)),
                    pl.BlockSpec((bq, bk), lambda b_, h_: (0, 0)),
                    pl.BlockSpec((bq, bk), lambda b_, h_: (0, 0)),
                ],
                out_specs=[
                    pl.BlockSpec((1, 1, bq, d), lambda b_, h_: (b_, h_, 0, 0)),
                    pl.BlockSpec((1, 1, bq, 1), lambda b_, h_: (b_, h_, 0, 0)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((b, n, bq, d), q.dtype),
                    jax.ShapeDtypeStruct((b, n, bq, 1), jnp.float32),
                ],
                compiler_params=fa._compiler_params(
                    dimension_semantics=("parallel", "parallel")
                ),
            )(qt, kt, vt, cqs, sqs, cos, sin, tri0, tri1)
            outs.append(out_i)
        out = jnp.concatenate(outs, axis=2) if nq > 1 else outs[0]
        return jnp.transpose(out, (0, 2, 1, 3))

    return flash_v2e


def flash_notr(q, k, v, causal=True, sm_scale=None, rope=None, **_):
    """TIMING-ONLY ablation: transposes replaced by free reshapes (data is
    WRONG — quantifies the structural transpose cost in context)."""
    b, s, n, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    qt = q.reshape(b, n, s, d)
    kt = k.reshape(b, n, s, d)
    vt = v.reshape(b, n, s, d)
    out, _ = fa._flash_fwd_blocked(qt, kt, vt, rope, sm_scale, 1024, False)
    return out.reshape(b, s, n, d)


# ---------------------------------------------------------------------------
# v3: fixed-base softmax — m_r = lam*||q_r||*max_c||k_c|| upper-bounds every
# score (rotate-half rope preserves norms), so exp2 never overflows and the
# online max/alpha machinery disappears; flash math is exact for ANY base.
# ---------------------------------------------------------------------------


def _fwd_kernel_v3(*refs, nkb, block_q, block_k):
    (q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref, tri_ref,
     o_ref, lse_ref) = refs
    qf = _rope_rows(q_ref[0, 0], cq_ref[...], sq_ref[...])  # fp32, scaled by lam
    kf32 = _rope_rows(k_ref[0, 0], ck_ref[...], sk_ref[...])
    q = qf.astype(q_ref.dtype)
    kf = kf32.astype(k_ref.dtype)
    vf = v_ref[0, 0]
    # per-row score bound: s_rc = (lam q_r) . k_c <= ||lam q_r|| * max_c ||k_c||
    qn = jnp.sqrt(jnp.sum(qf * qf, axis=1, keepdims=True))  # (bq, 1)
    kmax = jnp.sqrt(jnp.max(jnp.sum(kf32 * kf32, axis=1, keepdims=True)))
    m = qn * kmax + 1.0  # +1: bf16 rounding headroom; any base >= max is exact
    l = acc = None
    for j in range(nkb):
        kj = kf[j * block_k:(j + 1) * block_k]
        s = jax.lax.dot_general(
            q, kj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if j == nkb - 1:
            s = s + tri_ref[...].astype(jnp.float32)
        p = jnp.exp2(s - m)
        if j == 0:
            l = jnp.sum(p, axis=1, keepdims=True)
            acc = jax.lax.dot(
                p.astype(vf.dtype), vf[:block_k], preferred_element_type=jnp.float32
            )
        else:
            l = l + jnp.sum(p, axis=1, keepdims=True)
            acc = acc + jax.lax.dot(
                p.astype(vf.dtype), vf[j * block_k:(j + 1) * block_k],
                preferred_element_type=jnp.float32,
            )
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[0, 0] = (m * LN2 + jnp.log(jnp.maximum(l, 1e-30))).astype(jnp.float32)


def flash_v3(q, k, v, causal=True, sm_scale=None, rope=None, **_):
    b, s, n, d = q.shape
    bq = bk = 1024
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    nq = s // bq
    lam = sm_scale * LOG2E
    cos, sin = rope
    cqs, sqs = cos * lam, sin * lam
    r = np.arange(bq)
    tri = jnp.asarray(np.where(r[:, None] >= r[None, :], 0.0, NEG_INF), jnp.bfloat16)
    outs = []
    for i in range(nq):
        nkb = i + 1
        kl = nkb * bk
        out_i, _lse_i = pl.pallas_call(
            functools.partial(_fwd_kernel_v3, nkb=nkb, block_q=bq, block_k=bk),
            grid=(b, n),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i=i: (b_, h_, i, 0)),
                pl.BlockSpec((1, 1, kl, d), lambda b_, h_: (b_, h_, 0, 0)),
                pl.BlockSpec((1, 1, kl, d), lambda b_, h_: (b_, h_, 0, 0)),
                pl.BlockSpec((bq, d // 2), lambda b_, h_, i=i: (i, 0)),
                pl.BlockSpec((bq, d // 2), lambda b_, h_, i=i: (i, 0)),
                pl.BlockSpec((kl, d // 2), lambda b_, h_: (0, 0)),
                pl.BlockSpec((kl, d // 2), lambda b_, h_: (0, 0)),
                pl.BlockSpec((bq, bk), lambda b_, h_: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bq, d), lambda b_, h_: (b_, h_, 0, 0)),
                pl.BlockSpec((1, 1, bq, 1), lambda b_, h_: (b_, h_, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, n, bq, d), q.dtype),
                jax.ShapeDtypeStruct((b, n, bq, 1), jnp.float32),
            ],
            compiler_params=fa._compiler_params(
                dimension_semantics=("parallel", "parallel")
            ),
        )(qt, kt, vt, cqs, sqs, cos, sin, tri)
        outs.append(out_i)
    out = jnp.concatenate(outs, axis=2) if nq > 1 else outs[0]
    return jnp.transpose(out, (0, 2, 1, 3))


# ---------------------------------------------------------------------------
# v4: blocked-causal with HB heads per invocation (fewer grid invocations,
# per-head sequential inner loop reusing the score buffer)
# ---------------------------------------------------------------------------


def _fwd_kernel_v4(*refs, nkb, block_q, block_k, hb):
    (q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref, tri_ref,
     o_ref, lse_ref) = refs
    cq, sq = cq_ref[...], sq_ref[...]
    ck, sk = ck_ref[...], sk_ref[...]
    for h in range(hb):
        q = _rope_rows(q_ref[0, h], cq, sq).astype(q_ref.dtype)
        kf = _rope_rows(k_ref[0, h], ck, sk).astype(k_ref.dtype)
        vf = v_ref[0, h]
        m = l = acc = None
        for j in range(nkb):
            kj = kf[j * block_k:(j + 1) * block_k]
            s = jax.lax.dot_general(
                q, kj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            if j == nkb - 1:
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
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0, h] = (m * LN2 + jnp.log(jnp.maximum(l, 1e-30))).astype(jnp.float32)


def make_flash_v4(hb=2, block=1024):
    def flash_v4(q, k, v, causal=True, sm_scale=None, rope=None, **_):
        b, s, n, d = q.shape
        bq = bk = block
        if sm_scale is None:
            sm_scale = 1.0 / float(np.sqrt(d))
        qt = jnp.transpose(q, (0, 2, 1, 3))
        kt = jnp.transpose(k, (0, 2, 1, 3))
        vt = jnp.transpose(v, (0, 2, 1, 3))
        nq = s // bq
        lam = sm_scale * LOG2E
        cos, sin = rope
        cqs, sqs = cos * lam, sin * lam
        r = np.arange(bq)
        tri = jnp.asarray(np.where(r[:, None] >= r[None, :], 0.0, NEG_INF), jnp.bfloat16)
        outs = []
        for i in range(nq):
            nkb = i + 1
            kl = nkb * bk
            out_i, _lse_i = pl.pallas_call(
                functools.partial(_fwd_kernel_v4, nkb=nkb, block_q=bq, block_k=bk, hb=hb),
                grid=(b, n // hb),
                in_specs=[
                    pl.BlockSpec((1, hb, bq, d), lambda b_, h_, i=i: (b_, h_, i, 0)),
                    pl.BlockSpec((1, hb, kl, d), lambda b_, h_: (b_, h_, 0, 0)),
                    pl.BlockSpec((1, hb, kl, d), lambda b_, h_: (b_, h_, 0, 0)),
                    pl.BlockSpec((bq, d // 2), lambda b_, h_, i=i: (i, 0)),
                    pl.BlockSpec((bq, d // 2), lambda b_, h_, i=i: (i, 0)),
                    pl.BlockSpec((kl, d // 2), lambda b_, h_: (0, 0)),
                    pl.BlockSpec((kl, d // 2), lambda b_, h_: (0, 0)),
                    pl.BlockSpec((bq, bk), lambda b_, h_: (0, 0)),
                ],
                out_specs=[
                    pl.BlockSpec((1, hb, bq, d), lambda b_, h_: (b_, h_, 0, 0)),
                    pl.BlockSpec((1, hb, bq, 1), lambda b_, h_: (b_, h_, 0, 0)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((b, n, bq, d), q.dtype),
                    jax.ShapeDtypeStruct((b, n, bq, 1), jnp.float32),
                ],
                compiler_params=fa._compiler_params(
                    dimension_semantics=("parallel", "parallel")
                ),
            )(qt, kt, vt, cqs, sqs, cos, sin, tri)
            outs.append(out_i)
        out = jnp.concatenate(outs, axis=2) if nq > 1 else outs[0]
        return jnp.transpose(out, (0, 2, 1, 3))

    return flash_v4


# Timing-only probes: mutate the blocked kernel's softmax internals to
# localize VPU cost (numerics WRONG — never ship).


def _fwd_kernel_probe(*refs, nkb, block_q, block_k, mode):
    (q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref, tri_ref,
     o_ref, lse_ref) = refs
    q = _rope_rows(q_ref[0, 0], cq_ref[...], sq_ref[...]).astype(q_ref.dtype)
    kf = _rope_rows(k_ref[0, 0], ck_ref[...], sk_ref[...]).astype(k_ref.dtype)
    vf = v_ref[0, 0]
    m = l = acc = None
    for j in range(nkb):
        kj = kf[j * block_k:(j + 1) * block_k]
        s = jax.lax.dot_general(
            q, kj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if j == nkb - 1 and mode != "notri":
            s = s + tri_ref[...].astype(jnp.float32)
        if j == 0:
            m = jnp.max(s, axis=1, keepdims=True)
            p = (s - m) if mode in ("noexp", "nosum") else jnp.exp2(s - m)
            l = m if mode == "nosum" else jnp.sum(p, axis=1, keepdims=True)
            acc = jax.lax.dot(
                p.astype(vf.dtype), vf[:block_k], preferred_element_type=jnp.float32
            )
        else:
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = (s - m_new) if mode in ("noexp", "nosum") else jnp.exp2(s - m_new)
            alpha = jnp.exp2(m - m_new)
            l = m_new if mode == "nosum" else (alpha * l + jnp.sum(p, axis=1, keepdims=True))
            acc = alpha * acc + jax.lax.dot(
                p.astype(vf.dtype), vf[j * block_k:(j + 1) * block_k],
                preferred_element_type=jnp.float32,
            )
            m = m_new
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[0, 0] = (m * LN2 + jnp.log(jnp.maximum(l, 1e-30))).astype(jnp.float32)


def make_flash_probe(mode):
    def flash_probe(q, k, v, causal=True, sm_scale=None, rope=None, **_):
        b, s, n, d = q.shape
        bq = bk = 1024
        if sm_scale is None:
            sm_scale = 1.0 / float(np.sqrt(d))
        qt = jnp.transpose(q, (0, 2, 1, 3))
        kt = jnp.transpose(k, (0, 2, 1, 3))
        vt = jnp.transpose(v, (0, 2, 1, 3))
        nq = s // bq
        lam = sm_scale * LOG2E
        cos, sin = rope
        cqs, sqs = cos * lam, sin * lam
        r = np.arange(bq)
        tri = jnp.asarray(np.where(r[:, None] >= r[None, :], 0.0, NEG_INF), jnp.bfloat16)
        outs = []
        for i in range(nq):
            nkb = i + 1
            kl = nkb * bk
            out_i, _lse_i = pl.pallas_call(
                functools.partial(_fwd_kernel_probe, nkb=nkb, block_q=bq, block_k=bk, mode=mode),
                grid=(b, n),
                in_specs=[
                    pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i=i: (b_, h_, i, 0)),
                    pl.BlockSpec((1, 1, kl, d), lambda b_, h_: (b_, h_, 0, 0)),
                    pl.BlockSpec((1, 1, kl, d), lambda b_, h_: (b_, h_, 0, 0)),
                    pl.BlockSpec((bq, d // 2), lambda b_, h_, i=i: (i, 0)),
                    pl.BlockSpec((bq, d // 2), lambda b_, h_, i=i: (i, 0)),
                    pl.BlockSpec((kl, d // 2), lambda b_, h_: (0, 0)),
                    pl.BlockSpec((kl, d // 2), lambda b_, h_: (0, 0)),
                    pl.BlockSpec((bq, bk), lambda b_, h_: (0, 0)),
                ],
                out_specs=[
                    pl.BlockSpec((1, 1, bq, d), lambda b_, h_: (b_, h_, 0, 0)),
                    pl.BlockSpec((1, 1, bq, 1), lambda b_, h_: (b_, h_, 0, 0)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((b, n, bq, d), q.dtype),
                    jax.ShapeDtypeStruct((b, n, bq, 1), jnp.float32),
                ],
                compiler_params=fa._compiler_params(
                    dimension_semantics=("parallel", "parallel")
                ),
            )(qt, kt, vt, cqs, sqs, cos, sin, tri)
            outs.append(out_i)
        out = jnp.concatenate(outs, axis=2) if nq > 1 else outs[0]
        return jnp.transpose(out, (0, 2, 1, 3))

    return flash_probe


def flash_ident(q, k, v, **_):
    """TIMING-ONLY ablation: attention removed entirely (o := q)."""
    return q


# ---------------------------------------------------------------------------
# v5: ONE pallas call per (b, h) — k-outer / all-q-chains-live structure with
# hand-rolled double-buffered HBM→VMEM DMA of k/v blocks (the emit_pipeline
# idea, but with a statically unrolled k loop so the causal specialization
# stays static). Removes: per-q-block invocation overhead, the output
# concatenate, and (nq(nq+1)/2 - nq) redundant k-block ropes. ``interleave``
# orders both chains' dots before both softmaxes per step to give Mosaic
# adjacent independent MXU/VPU ops.
# ---------------------------------------------------------------------------


def _fwd_kernel_v5(*refs, nq, block, interleave):
    (q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref, tri_ref,
     o_ref, lse_ref, k_buf, v_buf, sems) = refs
    b_idx = pl.program_id(0)
    h_idx = pl.program_id(1)

    def k_dma(j, slot):
        return pltpu.make_async_copy(
            k_ref.at[b_idx, h_idx, pl.ds(j * block, block), :],
            k_buf.at[slot], sems.at[slot, 0],
        )

    def v_dma(j, slot):
        return pltpu.make_async_copy(
            v_ref.at[b_idx, h_idx, pl.ds(j * block, block), :],
            v_buf.at[slot], sems.at[slot, 1],
        )

    k_dma(0, 0).start()
    v_dma(0, 0).start()
    # rope all q chains once (scale folded into cq/sq)
    qs = [
        _rope_rows(
            q_ref[0, 0, i * block:(i + 1) * block],
            cq_ref[i * block:(i + 1) * block],
            sq_ref[i * block:(i + 1) * block],
        ).astype(q_ref.dtype)
        for i in range(nq)
    ]
    m = [None] * nq
    l = [None] * nq
    acc = [None] * nq
    for j in range(nq):
        slot = j % 2
        if j + 1 < nq:
            k_dma(j + 1, (j + 1) % 2).start()
            v_dma(j + 1, (j + 1) % 2).start()
        k_dma(j, slot).wait()
        v_dma(j, slot).wait()
        kj = _rope_rows(
            k_buf[slot],
            ck_ref[j * block:(j + 1) * block],
            sk_ref[j * block:(j + 1) * block],
        ).astype(k_buf.dtype)
        vj = v_buf[slot]
        chains = list(range(j, nq))  # causal: chain i sees k block j iff j <= i

        def score(i):
            s = jax.lax.dot_general(
                qs[i], kj, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if i == j:  # diagonal block
                s = s + tri_ref[...].astype(jnp.float32)
            return s

        def update(i, s):
            if m[i] is None:
                m[i] = jnp.max(s, axis=1, keepdims=True)
                p = jnp.exp2(s - m[i])
                l[i] = jnp.sum(p, axis=1, keepdims=True)
                acc[i] = jax.lax.dot(
                    p.astype(vj.dtype), vj, preferred_element_type=jnp.float32
                )
            else:
                m_new = jnp.maximum(m[i], jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp2(s - m_new)
                alpha = jnp.exp2(m[i] - m_new)
                l[i] = alpha * l[i] + jnp.sum(p, axis=1, keepdims=True)
                acc[i] = alpha * acc[i] + jax.lax.dot(
                    p.astype(vj.dtype), vj, preferred_element_type=jnp.float32
                )
                m[i] = m_new

        if interleave:
            ss = {i: score(i) for i in chains}
            for i in chains:
                update(i, ss[i])
        else:
            for i in chains:
                update(i, score(i))
    for i in range(nq):
        o_ref[0, 0, i * block:(i + 1) * block] = (
            acc[i] / jnp.maximum(l[i], 1e-30)
        ).astype(o_ref.dtype)
        lse_ref[0, 0, i * block:(i + 1) * block] = (
            m[i] * LN2 + jnp.log(jnp.maximum(l[i], 1e-30))
        ).astype(jnp.float32)


def make_flash_v5(block=1024, interleave=False):
    def flash_v5(q, k, v, causal=True, sm_scale=None, rope=None, **_):
        b, s, n, d = q.shape
        if sm_scale is None:
            sm_scale = 1.0 / float(np.sqrt(d))
        assert rope is not None and causal and s % block == 0
        qt = jnp.transpose(q, (0, 2, 1, 3))
        kt = jnp.transpose(k, (0, 2, 1, 3))
        vt = jnp.transpose(v, (0, 2, 1, 3))
        nq = s // block
        lam = sm_scale * LOG2E
        cos, sin = rope
        cqs, sqs = cos * lam, sin * lam
        r = np.arange(block)
        tri = jnp.asarray(np.where(r[:, None] >= r[None, :], 0.0, NEG_INF), jnp.bfloat16)
        rows = pl.BlockSpec((s, d // 2), lambda b_, h_: (0, 0))
        out, _lse = pl.pallas_call(
            functools.partial(_fwd_kernel_v5, nq=nq, block=block, interleave=interleave),
            grid=(b, n),
            in_specs=[
                pl.BlockSpec((1, 1, s, d), lambda b_, h_: (b_, h_, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.ANY),
                pl.BlockSpec(memory_space=pltpu.ANY),
                rows, rows, rows, rows,
                pl.BlockSpec((block, block), lambda b_, h_: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, s, d), lambda b_, h_: (b_, h_, 0, 0)),
                pl.BlockSpec((1, 1, s, 1), lambda b_, h_: (b_, h_, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, n, s, d), q.dtype),
                jax.ShapeDtypeStruct((b, n, s, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, block, d), q.dtype),
                pltpu.VMEM((2, block, d), q.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
            compiler_params=fa._compiler_params(
                dimension_semantics=("parallel", "parallel")
            ),
        )(qt, kt, vt, cqs, sqs, cos, sin, tri)
        return jnp.transpose(out, (0, 2, 1, 3))

    return flash_v5


# NOTE: "base" now means the transposing flash_attention wrapper with
# flash_headmajor=False; the full production path (head-major wiring) is
# the "xlahm"-equivalent in ATTN_VARIANTS / make_window_attnblock.


VARIANTS = {
    "base": fa.flash_attention,
    "notr": flash_notr,
    "v3": flash_v3,
    "v4h2": make_flash_v4(2),
    "ident": flash_ident,
    "pnoexp": make_flash_probe("noexp"),
    "pnosum": make_flash_probe("nosum"),
    "pnotri": make_flash_probe("notri"),
    "v1b": flash_v1b,
    "v2c": flash_v2c,
    "v2c512": make_flash_v2c(512),
    "v2d": make_flash_v2d(1024),
    "v2d512": make_flash_v2d(512),
    "v2e": make_flash_v2e(1024, 512, hoist_all=False),
    "v2f": make_flash_v2e(1024, 512, hoist_all=True),
    "v2e1024": make_flash_v2e(1024, 1024, hoist_all=False),
    "v5": make_flash_v5(1024, interleave=False),
    "v5i": make_flash_v5(1024, interleave=True),
    "v5b512": make_flash_v5(512, interleave=False),
}


def check_numerics(names=None):
    key = jax.random.key(0)
    b, s, n, d = 2, 2048, 4, 128
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, n, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, n, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, n, d), jnp.bfloat16)
    pos = np.arange(s)
    inv = 1.0 / (10000.0 ** (np.arange(0, d, 2) / d))
    fr = np.outer(pos, inv)
    rope = (jnp.asarray(np.cos(fr), jnp.float32), jnp.asarray(np.sin(fr), jnp.float32))
    ref = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, rope=rope))(q, k, v)
    for name, fn in VARIANTS.items():
        if name == "base" or (names is not None and name not in names):
            continue
        got = jax.jit(lambda q, k, v, fn=fn: fn(q, k, v, rope=rope))(q, k, v)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32))))
        print(f"numerics {name}: max abs err vs base = {err:.4f}", flush=True)
        assert err < 0.05, (name, err)


def make_window(variant_fn, num_layers, bsz=8, seq=2048, iters=6):
    import galvatron_tpu.ops.flash_attention as famod
    from galvatron_tpu.models import modeling

    famod_orig = famod.flash_attention
    famod.flash_attention = variant_fn
    try:
        # the head-major production wiring bypasses the flash_attention
        # symbol — disable it (flash_headmajor=False) or every kernel
        # variant (even ident) benches the same path
        cfg = modeling.ModelConfig(
            vocab_size=32000, hidden_size=4096, num_layers=num_layers,
            num_heads=32, ffn_dim=11008, max_seq_len=seq,
            dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, attn_impl="flash",
            flash_headmajor=False,
        )
        params = modeling.init_model_params(jax.random.key(0), cfg)
        tokens = jnp.zeros((bsz, seq), jnp.int32)

        def fwd(params, tokens, c):
            x = modeling.embed(tokens, params, cfg)
            x = x + c.astype(x.dtype)
            cos_sin = modeling.rope_tables(cfg, seq)
            for lp in params["layers"]:
                x = modeling.decoder_layer(x, lp, cfg, cos_sin, None)
            return jnp.sum(x.astype(jnp.float32))

        @jax.jit
        def window(params, tokens):
            def body(c, _):
                out = fwd(params, tokens, c * 1e-30)
                return out * 1e-30, None

            c, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), None, length=iters)
            return c

        _ = float(window(params, tokens))
    finally:
        famod.flash_attention = famod_orig

    def run():
        t0 = time.perf_counter()
        _ = float(window(params, tokens))
        return (time.perf_counter() - t0) / iters * 1000.0

    return run


# ---------------------------------------------------------------------------
# Head-major wiring experiments: replace project->transpose with layouts XLA
# (or pallas) produces directly. Patched at the attn_block level.
# ---------------------------------------------------------------------------

from galvatron_tpu.models import modeling as _mod

_ATTN_BLOCK_ORIG = _mod.attn_block


def _flash_hm(qt, kt, vt, rope, d):
    """Blocked flash on already-head-major (b, h, s, d) inputs; returns
    (b, h, s, d)."""
    sm_scale = 1.0 / float(np.sqrt(d))
    out, _ = fa._flash_fwd_blocked(qt, kt, vt, rope, sm_scale, 1024, False)
    return out


def attn_block_xlahm(x, p, cfg, cos_sin=None, alibi=None, remat_attn=False):
    """qkv via einsum straight to head-major; o-proj via einsum from
    head-major (XLA decides how to realize the layouts)."""
    b, s, h = x.shape
    hd = cfg.head_dim
    n = cfg.num_heads
    w = p["wqkv"].astype(x.dtype).reshape(h, 3, n, hd)
    qkv = jnp.einsum("bsh,hcnd->bcnsd", x, w)  # (b, 3, n, s, d)
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    o = _flash_hm(q, k, v, cos_sin, hd)  # (b, n, s, d)
    wo = p["wo"].astype(x.dtype).reshape(n, hd, h)
    return jnp.einsum("bnsd,nde->bse", o, wo)


def make_window_attnblock(attn_impl_fn, num_layers, bsz=8, seq=2048, iters=6):
    orig = _mod.attn_block
    _mod.attn_block = attn_impl_fn
    try:
        cfg = _mod.ModelConfig(
            vocab_size=32000, hidden_size=4096, num_layers=num_layers,
            num_heads=32, ffn_dim=11008, max_seq_len=seq,
            dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, attn_impl="flash",
        )
        params = _mod.init_model_params(jax.random.key(0), cfg)
        tokens = jnp.zeros((bsz, seq), jnp.int32)

        def fwd(params, tokens, c):
            x = _mod.embed(tokens, params, cfg)
            x = x + c.astype(x.dtype)
            cos_sin = _mod.rope_tables(cfg, seq)
            for lp in params["layers"]:
                x = _mod.decoder_layer(x, lp, cfg, cos_sin, None)
            return jnp.sum(x.astype(jnp.float32))

        @jax.jit
        def window(params, tokens):
            def body(c, _):
                out = fwd(params, tokens, c * 1e-30)
                return out * 1e-30, None

            c, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), None, length=iters)
            return c

        _ = float(window(params, tokens))
    finally:
        _mod.attn_block = orig

    def run():
        t0 = time.perf_counter()
        _ = float(window(params, tokens))
        return (time.perf_counter() - t0) / iters * 1000.0

    return run


def attn_block_qkvstack(x, p, cfg, cos_sin=None, alibi=None, remat_attn=False):
    """Head-major wiring with the STACKED qkv fed straight to the kernels
    (no q/k/v slice copies) — calls the production ops entry. NOTE: since
    this landed as the production default, "hmprod" routes through the same
    path; compare against historical commits, not hmprod."""
    b, s, h = x.shape
    hd = cfg.head_dim
    n = cfg.num_heads
    w = p["wqkv"].astype(x.dtype)
    qkv = jnp.einsum("bsh,hcnd->bcnsd", x, w.reshape(h, 3, n, hd))
    o = fa.flash_attention_qkv(qkv, rope=cos_sin)
    return jnp.einsum("bnsd,nde->bse", o, p["wo"].astype(x.dtype).reshape(n, hd, h))


# "hmprod" is the real production attn_block (head-major gate active) —
# compare kernel variants against it, not against "base"
ATTN_VARIANTS = {
    "xlahm": attn_block_xlahm,
    "hmprod": _ATTN_BLOCK_ORIG,
    "qkvstack": attn_block_qkvstack,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variants", default="base,v1b,v2c")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--skip_numerics", action="store_true")
    args = ap.parse_args()
    names = args.variants.split(",")
    if not args.skip_numerics:
        check_numerics(names)
    l1, l2 = 2, 6
    wins = {}
    for nm in names:
        print(f"compiling {nm}...", flush=True)
        if nm in ATTN_VARIANTS:
            wins[nm] = (
                make_window_attnblock(ATTN_VARIANTS[nm], l1),
                make_window_attnblock(ATTN_VARIANTS[nm], l2),
            )
        else:
            wins[nm] = (make_window(VARIANTS[nm], l1), make_window(VARIANTS[nm], l2))
    results = {nm: [] for nm in names}
    for r in range(args.rounds):
        for nm in names:
            w1, w2 = wins[nm]
            t1 = w1()
            t2 = w2()
            diff = (t2 - t1) / (l2 - l1) / 8
            results[nm].append(diff)
            print(f"round {r} {nm}: {diff:.4f} ms/layer/sample", flush=True)
    print("---")
    for nm in names:
        print(f"{nm}: median {np.median(results[nm]):.4f}  all={['%.4f' % x for x in results[nm]]}")


def make_attn_qkvstack_block(block):
    def attn(x, p, cfg, cos_sin=None, alibi=None, remat_attn=False):
        b, s, h = x.shape
        hd = cfg.head_dim
        n = cfg.num_heads
        w = p["wqkv"].astype(x.dtype)
        qkv = jnp.einsum("bsh,hcnd->bcnsd", x, w.reshape(h, 3, n, hd))
        o = fa.flash_attention_qkv(qkv, rope=cos_sin, block_q=block)
        return jnp.einsum("bnsd,nde->bse", o, p["wo"].astype(x.dtype).reshape(n, hd, h))

    return attn


ATTN_VARIANTS["qkvstack512"] = make_attn_qkvstack_block(512)
ATTN_VARIANTS["qkvstack2048"] = make_attn_qkvstack_block(2048)


if __name__ == "__main__":
    main()
