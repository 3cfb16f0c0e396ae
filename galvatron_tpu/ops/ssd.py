"""State-space duality (SSD) scan of a Mamba-2 layer, chunked, and the causal
depthwise convolution in front of it.

The recurrence, per head (``H`` in R^{P x N}; one scalar decay a head)::

    H_t = exp(dt_t * A) * H_{t-1} + dt_t * x_t B_t^T        y_t = H_t C_t

is computed in chunks of ``chunk`` positions (Dao & Gu 2024, "Transformers are
SSMs", listing 1): inside a chunk the output is a decay-masked ``C B^T``
product (an attention-like (L, L) block a head), one state a chunk summarises
what the chunk adds, and the states are carried across chunks by a scan over
the chunk axis. Written in plain ``jax.numpy`` so that autodiff gives the
backward; XLA fuses the mask, the exponentials and the casts around the four
batched GEMMs.

Precision: the log-decays, their cumulative sums, ``dt`` and the carried
states are float32 always (a decay in bf16 loses the recurrence after a few
hundred steps); the GEMM operands are the compute dtype with float32
accumulation. B and C are shared by the heads of a group (``G`` groups).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def causal_conv1d(x, w, b):
    """Depthwise causal convolution over the sequence: ``x`` (B, S, C),
    ``w`` (K, C) with tap ``K-1`` on the current position (the published
    conv1d weight (C, 1, K), transposed), ``b`` (C,) ->
    ``y_t = sum_j w[j] * x_{t-K+1+j} + b``; positions before the sequence read
    zero. Accumulates in float32, returns ``x``'s dtype."""
    k, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    w32 = w.astype(F32)
    y = b.astype(F32)
    for j in range(k):
        y = y + xp[:, j:j + s].astype(F32) * w32[j]
    return y.astype(x.dtype)


def ssd_scan(x, dt, a, b_mat, c_mat, chunk: int):
    """Chunked SSD: ``x`` (B, S, H, P) in the compute dtype, ``dt`` (B, S, H)
    float32 and positive, ``a`` (H,) float32 and negative, ``b_mat`` / ``c_mat``
    (B, S, G, N) -> ``y`` (B, S, H, P) in ``x``'s dtype, without the ``D x``
    skip. A sequence that ``chunk`` does not divide is padded at its end
    (``dt`` 0 there: no decay, no input; causal, so nothing earlier moves).

    Everything between the two transposes is head-major, (B, G, R, chunks, L,
    ...) with H = G x R: the score blocks differ by head, so the batched GEMMs
    want the heads in front; written token-major the compiler inserted its
    own copies of every x-sized operand (twice the bytes written, `scan` 110 ->
    89 ms a step in the cell; PERF.md §6, PR 33). What it still inserts: one
    re-tiling copy of x a pass, nameless in a trace (PERF.md §7)."""
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2:]
    r = h // g
    pad = -s % chunk
    if pad:
        x, dt, b_mat, c_mat = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b_mat, c_mat))
    nc, dtype = (s + pad) // chunk, x.dtype
    xh = x.transpose(0, 2, 1, 3).reshape(bsz, g, r, nc, chunk, p)
    dth = dt.astype(F32).transpose(0, 2, 1).reshape(bsz, g, r, nc, chunk)
    bc = b_mat.astype(dtype).transpose(0, 2, 1, 3).reshape(bsz, g, nc, chunk, n)
    cc = c_mat.astype(dtype).transpose(0, 2, 1, 3).reshape(bsz, g, nc, chunk, n)
    # log-decays summed from each chunk's start: (B, G, R, nc, L)
    cum = jnp.cumsum(dth * a.astype(F32).reshape(g, r)[None, :, :, None, None], axis=-1)
    x32 = xh.astype(F32) * dth[..., None]  # dt_t x_t

    # 1. inside a chunk: y_l += sum_{s<=l} exp(cum_l - cum_s) (C_l . B_s) dt_s x_s
    cb = jnp.einsum("bgcln,bgcsn->bgcls", cc, bc, preferred_element_type=F32)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    scores = (cb[:, :, None] * decay).astype(dtype)
    y = jnp.einsum("bgrcls,bgrcsp->bgrclp", scores, x32.astype(dtype),
                   preferred_element_type=F32)

    # 2. what each chunk adds to the state by its end: (nc, B, G, R, P, N)
    xw = (x32 * jnp.exp(cum[..., -1:] - cum)[..., None]).astype(dtype)
    added = jnp.einsum("bgcsn,bgrcsp->cbgrpn", bc, xw, preferred_element_type=F32)

    # 3. the carry: the state entering each chunk, float32, one step a chunk
    def carry(state, inp):
        dec, add = inp
        return state * dec[..., None, None] + add, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros((bsz, g, r, p, n), F32),
        (jnp.moveaxis(jnp.exp(cum[..., -1]), -1, 0), added))

    # 4. the entering state read out at every position of its chunk
    y_off = jnp.einsum("bgcln,cbgrpn->bgrclp", cc, entering.astype(dtype),
                       preferred_element_type=F32)
    y = (y + y_off * jnp.exp(cum)[..., None]).astype(dtype)
    return y.reshape(bsz, h, nc * chunk, p).transpose(0, 2, 1, 3)[:, :s]
