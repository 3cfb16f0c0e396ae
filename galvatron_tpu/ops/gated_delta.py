"""The gated delta rule of a Gated DeltaNet layer (Yang, Kautz & Hatamizadeh
2024, "Gated Delta Networks"; the Qwen3-Next linear-attention layers), chunked.

The recurrence, per value head (state ``S`` in R^{Dk x Dv}, ``S_0 = 0``; one
scalar log-decay ``g_t <= 0`` and one write strength ``beta_t`` a head)::

    S <- exp(g_t) S;   r_t = v_t - S^T k_t;   S <- S + k_t (beta_t r_t)^T;   o_t = S^T q_t

is computed in chunks of ``chunk`` positions (the published code's 64). Inside
a chunk, with ``G`` the running sum of ``g`` and ``D_ij = exp(G_i - G_j)``
(``i >= j``), the writes of the chunk solve one unit lower-triangular system::

    (I + strict_lower(diag(beta) K K^T * D)) [U | W] = [beta V | beta K exp(G)]

(``U``: what each position writes if the state entering the chunk were zero;
``W``: what of the entering state each position sees), so that with the
entering state ``S``::

    V' = U - W S
    O  = (Q exp(G)) S + lower(Q K^T * D) V'
    S <- exp(G_last) S + (K exp(G_last - G))^T V'

The states are carried across chunks by a ``lax.scan``; everything inside a
chunk is batched over the chunks. Plain ``jax.numpy`` (autodiff gives the
backward; the triangular solve is ``jax.scipy.linalg.solve_triangular``, whose
derivative is another solve): a fused kernel is a later optimisation (PERF.md
§7).

Precision: the log-decays, their running sums, ``beta``, the system and its
solve, and the carried state are float32 always; the operands of the other
products are the compute dtype (``q``'s) with float32 accumulation.

Each key head serves ``Hv / Hk`` consecutive value heads (value head ``j``
reads key head ``j // (Hv / Hk)``); q and k are never repeated in memory.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _mm(spec, a, b, dtype):
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype), preferred_element_type=F32)


def gated_delta_chunked(q, k, v, g, beta, chunk: int = 64):
    """``q``, ``k`` (B, S, Hk, Dk), already normalised and scaled; ``v``
    (B, S, Hv, Dv); ``g`` (log-decay, <= 0) and ``beta`` (B, S, Hv) ->
    ``o`` (B, S, Hv, Dv) in ``v``'s dtype. A sequence that is not whole chunks
    is padded at its end (causal: nothing earlier moves)."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r = hv // hk
    dtype = q.dtype
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
    n = (s + pad) // chunk
    # (B, Hk, [R,] N, C, D): chunks beside the heads, positions inside a chunk last
    qc = q.reshape(b, n, chunk, hk, dk).transpose(0, 3, 1, 2, 4)
    kc = k.reshape(b, n, chunk, hk, dk).transpose(0, 3, 1, 2, 4)
    vc = v.reshape(b, n, chunk, hk, r, dv).transpose(0, 3, 4, 1, 2, 5)
    gc = jnp.cumsum(g.astype(F32).reshape(b, n, chunk, hk, r).transpose(0, 3, 4, 1, 2), axis=-1)
    bc = beta.astype(F32).reshape(b, n, chunk, hk, r).transpose(0, 3, 4, 1, 2)

    i = jnp.arange(chunk)
    lower = i[:, None] >= i[None, :]
    # D_ij = exp(G_i - G_j) on and below the diagonal, 0 above (masked before the exp)
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :], -jnp.inf))
    kk = _mm("bhncd,bhned->bhnce", kc, kc, dtype)  # (B, Hk, N, C, C)
    qk = _mm("bhncd,bhned->bhnce", qc, kc, dtype)
    strict = (i[:, None] > i[None, :]).astype(F32)
    system = strict * bc[..., :, None] * kk[:, :, None] * decay + jnp.eye(chunk, dtype=F32)
    k_seen = kc[:, :, None].astype(F32) * (bc * jnp.exp(gc))[..., None]  # (B, Hk, R, N, C, Dk)
    rhs = jnp.concatenate([vc.astype(F32) * bc[..., None], k_seen], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(system, rhs, lower=True, unit_diagonal=True)
    # the products' operands in the compute dtype before the scan keeps them (and
    # what autodiff saves of them) at that width
    u, w = sol[..., :dv], sol[..., dv:].astype(dtype)
    intra = (qk[:, :, None] * decay).astype(dtype)  # (B, Hk, R, N, C, C), diagonal included
    q_in = (qc[:, :, None].astype(F32) * jnp.exp(gc)[..., None]).astype(dtype)
    g_last = gc[..., -1]  # (B, Hk, R, N)
    k_out = (kc[:, :, None].astype(F32) * jnp.exp(g_last[..., None] - gc)[..., None]).astype(dtype)

    def step(state, xs):  # state (B, Hk, R, Dk, Dv) float32
        u_n, w_n, intra_n, q_n, k_n, last_n = xs
        v_new = u_n - _mm("bhrcd,bhrde->bhrce", w_n, state, dtype)
        o_n = (_mm("bhrcd,bhrde->bhrce", q_n, state, dtype)
               + _mm("bhrce,bhred->bhrcd", intra_n, v_new, dtype))
        state = (state * jnp.exp(last_n)[..., None, None]
                 + _mm("bhrcd,bhrce->bhrde", k_n, v_new, dtype))
        return state, o_n

    chunks_first = lambda t: jnp.moveaxis(t, 3, 0)  # noqa: E731
    _, o = jax.lax.scan(
        step, jnp.zeros((b, hk, r, dk, dv), F32),
        tuple(chunks_first(t) for t in (u, w, intra, q_in, k_out, g_last)))
    # (N, B, Hk, R, C, Dv) -> (B, S, Hv, Dv)
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(b, n * chunk, hv, dv)
    return o[:, :s].astype(v.dtype)
