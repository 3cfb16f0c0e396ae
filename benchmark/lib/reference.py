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

This file holds what they share and the loss over them.
"""

from __future__ import annotations

import os
from typing import Any, Dict

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
