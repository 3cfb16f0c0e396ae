"""Plain references: float32 ``jax.numpy``, ``default_matmul_precision
("highest")``, no kernels, no cache, no sharding rules.  Imports nothing from
the program's model code.

One module per architecture sits in ``benchmark/references/<model_type>.py``,
found by the configuration file's ``model_type``; a later PR adds an
architecture by adding its module.  Each is written from the published block
equations and exports

- ``published_weights(params, cfg)``: the program's flat parameter tree under
  the published names;
- ``logits(weights, tokens, cfg)``: the forward pass;
- ``fwd_flops_per_token(cfg, seq_len)``: the forward model FLOPs a token needs
  (``lib/flops.py`` has the conventions and the shared arithmetic).

This file holds what they share and the loss over them, and the plain statement
of the distribution a served token is drawn from (``processed_distribution``:
temperature, top-k, nucleus), against which ``correct`` holds every sampled
token of a serving cell (``token_stats``, ``sampled_tokens_check``).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import import_file

F32 = jnp.float32


def load(root: str, model_type: str):
    """The reference module of ``model_type`` under the benchmark root."""
    path = os.path.join(root, "benchmark", "references", model_type + ".py")
    if not os.path.exists(path):
        raise ValueError(f"no plain reference for model_type {model_type!r}: {path} is missing")
    return import_file(path, f"_benchmark_reference_{model_type}")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def layer_norm(x, scale_bias, eps):
    scale, bias = scale_bias
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def rotate_half(x, theta):
    """Rotary embedding on (b, s, n, d): pairs (i, i + d/2), base ``theta``."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2) / d))
    ang = np.outer(np.arange(s), inv)
    cos = jnp.asarray(np.cos(ang), F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q, k, v):
    """(b, s, n, d) each -> (b, s, n*d); softmax over keys at or before the query."""
    b, s, n, d = q.shape
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / np.sqrt(d)
    mask = np.tril(np.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v).reshape(b, s, n * d)


def _nll_sum(arch, w, rows, cfg) -> jax.Array:
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        tokens, labels = rows[:, :-1], rows[:, 1:]
        logp = jax.nn.log_softmax(arch.logits(w, tokens, cfg), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def lm_loss(arch, params: Dict[str, Any], rows: np.ndarray, cfg: Dict[str, Any],
            rows_per_call: int = 1) -> float:
    """Mean next-token cross entropy of ``rows`` ((B, S+1) token ids) under the
    program's flat parameter tree ``params`` and the architecture module
    ``arch``; ``rows_per_call`` rows at a time, so the float32 logits of a
    whole batch never exist at once."""
    w = arch.published_weights(params, cfg)
    step = jax.jit(lambda w_, r_: _nll_sum(arch, w_, r_, cfg))
    total = 0.0
    for i in range(0, rows.shape[0], rows_per_call):
        total += float(step(w, jnp.asarray(rows[i:i + rows_per_call], jnp.int32)))
    return total / (rows.shape[0] * (rows.shape[1] - 1))


# ---------------------------------------------------------------------------
# the distribution a served token is drawn from, and a drawn token against it
# ---------------------------------------------------------------------------
# Written from the published definitions, float64 numpy, nothing of the program:
# temperature scaling (logits / T before the softmax); top-k (Fan et al. 2018:
# the k most probable tokens, renormalised); nucleus sampling (Holtzman et al.
# 2019, arXiv:1904.09751: the smallest set of most probable tokens whose mass
# reaches top_p, renormalised).  Tokens whose logits tie are one token for the
# cut: a tie at the cut is kept whole (rows are often bfloat16 values, where
# ties are common), as the greedy check lets tied tokens share the best.


def processed_distribution(row, temperature: float, top_k: int = 0, top_p: float = 0.0) -> np.ndarray:
    """Probabilities (V,) of one logits ``row`` under temperature, then top-k,
    then the nucleus.  ``top_k`` 0 and ``top_p`` 0 switch a filter off;
    ``temperature`` <= 0 is greedy: the largest logits share all the mass."""
    row = np.asarray(row, np.float64)
    if temperature <= 0:
        p = (row == row.max()).astype(np.float64)
        return p / p.sum()
    s = row / temperature
    if top_k > 0:
        s = np.where(s >= np.sort(s)[-min(top_k, len(s))], s, -np.inf)
    p = np.exp(s - s.max())
    p /= p.sum()
    if top_p > 0:
        order = np.argsort(-s, kind="stable")
        before = np.cumsum(p[order]) - p[order]  # mass of the tokens sorted ahead
        cut = s[order][before < top_p].min()  # the prefix reaches top_p at this logit
        p = np.where(s >= cut, p, 0.0)
        p /= p.sum()
    return p


def token_stats(row, token: int, temperature: float, top_k: int = 0, top_p: float = 0.0
                ) -> Tuple[float, float, float, float]:
    """One drawn ``token`` against the row it was drawn from: (the mass, before
    the nucleus cut, of the tokens with a STRICTLY larger logit: the token is
    inside the nucleus while this is under ``top_p``; ``ln p(token)`` under
    :func:`processed_distribution`, ``-inf`` outside its support; the mean and
    the variance of ``ln p`` of a token drawn from that distribution).  The
    same arithmetic over the row's DISTINCT values with their multiplicities
    (a row of bfloat16 values has ~2,400 of them among 50,272), so a row costs
    a sort and not five passes; ``tests/benchmark`` holds the two equal."""
    row = np.asarray(row)
    if temperature <= 0:
        raise ValueError("a greedy token is held to its row's largest logit, not to a distribution")
    values, counts = np.unique(row, return_counts=True)
    values, counts = values[::-1].astype(np.float64), counts[::-1].astype(np.float64)
    mine = int(np.searchsorted(-values, -np.float64(row[token])))
    s = values / temperature
    w = counts * np.exp(s - s[0])
    keep = len(values)
    if top_k > 0:  # the k-th largest token's value is the last one kept
        keep = int(np.searchsorted(np.cumsum(counts), min(top_k, counts.sum()))) + 1
    p = w[:keep] / w[:keep].sum()
    before = np.cumsum(p) - p
    above = float(before[mine]) if mine < keep else float("inf")  # cut by top-k: outside
    if top_p > 0:
        keep = int(np.searchsorted(before, top_p))  # values whose ``before`` < top_p
    logp = s[:keep] - s[0] - np.log(w[:keep].sum())  # of ONE token of each kept value
    mass = w[:keep] / w[:keep].sum()
    mean = float((mass * logp).sum())
    var = float((mass * logp * logp).sum() - mean * mean)
    return above, (float(logp[mine]) if mine < keep else float("-inf")), mean, var


def sampled_tokens_check(draws: Iterable[Tuple[Any, int, float, int, float]], slack: float
                         ) -> Dict[str, Any]:
    """Sampled tokens against the rows they were drawn from; ``draws`` yields
    (row, token, temperature, top_k, top_p) as the REQUEST stated them.

    ``outside``: tokens outside the nucleus by more than ``slack`` of mass (the
    mass of strictly larger logits >= top_p + slack; a float32 cumulative sum
    over 50,272 terms errs by ~1e-4, and a sampler without the nucleus lands
    there with probability ~1 - top_p - slack a token).  ``past_cut``: tokens
    outside the exact support but within the slack: counted, in neither number.
    ``z``: over the tokens inside the support, (sum of ln p(token) - its
    expectation) / its standard deviation, both exact from the rows: N(0, 1)
    for a sampler that draws from the stated distribution, whatever its stream;
    a sampler at another temperature shifts every term."""
    out = {"tokens": 0, "outside": 0, "past_cut": 0, "inside": 0,
           "logp": 0.0, "mean": 0.0, "var": 0.0}
    for row, token, temperature, top_k, top_p in draws:
        above, logp, mean, var = token_stats(row, token, temperature, top_k, top_p)
        out["tokens"] += 1
        if logp == float("-inf"):
            cut = top_p if top_p > 0 else 1.0
            out["outside" if above >= cut + slack else "past_cut"] += 1
            continue
        out["inside"] += 1
        out["logp"] += logp
        out["mean"] += mean
        out["var"] += var
    out["z"] = (out["logp"] - out["mean"]) / out["var"] ** 0.5 if out["var"] > 0 else 0.0
    return out
