"""The Gated DeltaNet mixer of a hybrid stack (qwen3_next-class models): what a
layer of kind ``"gdn"`` runs in place of attention.

    [q | k | v | z] = W_in h        widths Hk Dk | Hk Dk | Hv Dv | Hv Dv
    [b | a] = W_ba h                widths Hv | Hv
    [q | k | v] = silu(conv1d_causal_depthwise([q | k | v]))      K taps, no bias
    beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)   float32, a value head
    q, k = l2norm(q), l2norm(k) over Dk;  q = q / sqrt(Dk)
    o = gated_delta_rule(q, k, v, g, beta)                        ops/gated_delta.py, chunks of 64
    y = W_out concat_heads(w * o * rsqrt(mean(o^2) + eps) * silu(z))   over Dv of each head

(HF ``modeling_qwen3_next.py`` Qwen3NextGatedDeltaNet; no biases anywhere. The
program's in-projections are flat as written above; the published ones are
interleaved by key head, ``benchmark/references/qwen3_next.published_weights``
maps one to the other.) Imported only where a configuration has such layers.

The norms and the rule have two bodies (PR 48, PR 73), chosen by
`ops/gated_delta.scan_path` from shapes and backend alone: on a chip at the
published sizes the Pallas kernels ``gdn_fwd`` / ``gdn_bwd`` read q, k and v
where the conv wrote them and normalise q and k themselves (a ``custom_vjp``
that keeps the conv's output, the two scalars and the chunks' entering states;
under ``place.shard_kernel`` on a mesh), everywhere else `_l2norm` and the plain
chunked body under this mixer's own ``jax.checkpoint``. `path_counts` reports
which.

Scopes under ``gdn``: ``in_proj``, ``conv``, ``scan``, ``gate_norm``,
``out_proj`` (PERF.md §3; the ``gdn_*`` benchmark metrics read them).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from galvatron_tpu.models.mixers import tally
from galvatron_tpu.models.placement import LOCAL, Placement
from galvatron_tpu.ops.gated_delta import (
    L2_EPS as _L2_EPS,
    gated_delta_chunked,
    gated_delta_fused,
    scan_path,
)
from galvatron_tpu.ops.ssd import causal_conv1d, conv_path, conv_silu_fused

Params = Dict[str, Any]
F32 = jnp.float32


def gdn_dims(cfg):
    """(key width, value width, conv channels, in_proj width) of the mixer."""
    key_dim = cfg.gdn_key_heads * cfg.gdn_key_dim
    value_dim = cfg.gdn_value_heads * cfg.gdn_value_dim
    return key_dim, value_dim, 2 * key_dim + value_dim, 2 * key_dim + 2 * value_dim


def param_count(cfg) -> int:
    _, value_dim, conv_dim, in_width = gdn_dims(cfg)
    hv = cfg.gdn_value_heads
    return (cfg.hidden_size * (in_width + 2 * hv) + conv_dim * cfg.gdn_conv
            + 2 * hv + cfg.gdn_value_dim + value_dim * cfg.hidden_size)


def saved_bytes_per_token(cfg, itemsize: int) -> float:
    """What the mixer keeps for the backward, in place of an attention layer's
    qkv + context: in_proj's output, the conv's output, the delta rule's output
    and the gated product, and inside a chunk the float32 system, its solution's
    two halves and the decay-masked scores a value head."""
    _, value_dim, conv_dim, in_width = gdn_dims(cfg)
    mixer = (in_width + conv_dim + 2 * value_dim) * itemsize
    return mixer + cfg.gdn_value_heads * (
        3 * cfg.gdn_chunk + 2 * (cfg.gdn_key_dim + cfg.gdn_value_dim)) * 4


def fwd_flops_per_token(cfg) -> float:
    """The chunked delta rule's forward FLOPs beside the weights': K K^T and Q K^T
    (causal half, a key head), a value head's solve, the entering state read twice
    and written once, the causal half of scores V'. Linear in the sequence."""
    dk, dv, c = cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_chunk
    return cfg.gdn_key_heads * 2.0 * (c + 1) * dk + cfg.gdn_value_heads * (
        (c - 1.0) * (dk + dv) + 6.0 * dk * dv + (c + 1.0) * dv)


def init_params(key, cfg) -> Params:
    """The published code's initialisation: ``A_log = log(U(0, 16))``,
    ``dt_bias = 1``, gated-norm weight 1; the projections and the conv taps
    uniform in +-1/sqrt(fan_in) like every other projection of the program."""
    from galvatron_tpu.models.modeling import _dense_init

    h, hv = cfg.hidden_size, cfg.gdn_value_heads
    _, value_dim, conv_dim, in_width = gdn_dims(cfg)
    ks = jax.random.split(key, 5)
    bound = 1.0 / np.sqrt(cfg.gdn_conv)
    return {
        "in_proj": _dense_init(ks[0], h, in_width, cfg.param_dtype),
        "ba_proj": _dense_init(ks[1], h, 2 * hv, cfg.param_dtype),
        "conv_w": jax.random.uniform(
            ks[2], (cfg.gdn_conv, conv_dim), cfg.param_dtype, -bound, bound),
        # (the draw's lowest value, 0, would give -inf: 1e-4 is its floor here)
        "A_log": jnp.log(jax.random.uniform(ks[3], (hv,), cfg.param_dtype, 1e-4, 16.0)),
        "dt_bias": jnp.ones((hv,), cfg.param_dtype),
        "norm": jnp.ones((cfg.gdn_value_dim,), cfg.param_dtype),
        "out_proj": _dense_init(ks[4], value_dim, h, cfg.param_dtype),
    }


def annotations(cfg) -> Params:
    """No ``tp`` axis anywhere: tensor parallelism on a recurrent layer is
    refused (build_runtime); ZeRO shards the hidden-size dims."""
    return {
        "in_proj": ("fsdp", None), "ba_proj": ("fsdp", None), "conv_w": (None, None),
        "A_log": (None,), "dt_bias": (None,), "norm": (None,), "out_proj": (None, "fsdp"),
    }


def conv_silu(qkvz, w, cfg, place: Placement = LOCAL):
    """``silu(conv1d([q | k | v]))`` of in_proj's output (B, S, q | k | v | z):
    one window of channels at column 0. Where `ops/ssd.conv_path` says fused,
    the kernels read it out of ``qkvz`` where it lies (under
    ``place.shard_kernel`` on a mesh); everywhere else `causal_conv1d` +
    ``jax.nn.silu`` on the sliced channels. The conv has no bias: a zero one."""
    conv_dim = w.shape[1]
    bias = jnp.zeros((conv_dim,), w.dtype)
    if conv_path((conv_dim,), cfg.gdn_conv, qkvz.dtype) == "fused":
        rows, whole = (0, None), (None, None)  # batch over the data-parallel axes
        return place.shard_kernel(conv_silu_fused, [rows, whole, whole], rows)(qkvz, w, bias)
    return jax.nn.silu(causal_conv1d(qkvz[..., :conv_dim], w, bias))


def _rule_path(cfg) -> str:
    return scan_path(cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim,
                     cfg.gdn_chunk, cfg.dtype)


def path_counts(cfg) -> dict:
    """Which delta rule and which conv a configuration's Gated DeltaNet layers
    take (`ops/gated_delta.scan_path`, `ops/ssd.conv_path`: the functions
    `block` asks)."""
    layers = cfg.kinds.count("gdn")
    return {
        "scan": tally(_rule_path(cfg), layers),
        "conv": tally(conv_path((gdn_dims(cfg)[2],), cfg.gdn_conv, cfg.dtype), layers),
    }


def _l2norm(t):
    t32 = t.astype(F32)
    return t32 * jax.lax.rsqrt(jnp.sum(t32 * t32, axis=-1, keepdims=True) + _L2_EPS)


@jax.named_scope("gdn")
def block(x, p: Params, cfg, place: Placement = LOCAL):
    """(B, S, hidden) normed layer input -> the mixer's output, same shape."""
    dtype = x.dtype
    hk, hv, dk, dv = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    key_dim, value_dim, conv_dim, _ = gdn_dims(cfg)
    lead = x.shape[:2]
    with jax.named_scope("in_proj"):
        qkvz = x @ p["in_proj"].astype(dtype)
        z = qkvz[..., conv_dim:]
        # the decay and the write strength: float32 from the accumulator on
        ba = jnp.einsum("bsh,hc->bsc", x, p["ba_proj"].astype(dtype), preferred_element_type=F32)
    with jax.named_scope("conv"):
        qkv = conv_silu(qkvz, p["conv_w"], cfg, place)
    with jax.named_scope("scan"):
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(p["A_log"].astype(F32)) * jax.nn.softplus(
            ba[..., hv:] + p["dt_bias"].astype(F32))
        if _rule_path(cfg) == "fused":
            # the kernels read q, k and v out of the conv's output, normalise q and k,
            # and keep their own residuals (their three inputs and the chunks' entering
            # states); on a mesh each device runs them on its own batch rows
            rows = (0, None)
            o = place.shard_kernel(
                lambda *t: gated_delta_fused(*t, hk, dk, cfg.gdn_chunk), [rows] * 3, rows)(
                    qkv, g, beta)
        else:
            q = (_l2norm(qkv[..., :key_dim].reshape(*lead, hk, dk)) * dk ** -0.5).astype(dtype)
            k = _l2norm(qkv[..., key_dim:2 * key_dim].reshape(*lead, hk, dk)).astype(dtype)
            v = qkv[..., 2 * key_dim:].reshape(*lead, hv, dv)
            # rematerialized in the backward from its five inputs: the chunks' float32
            # systems, solutions and carried states (~300 KB a token) are then live
            # only while the rule's own backward runs, not beside the expert layer's
            rule = jax.checkpoint(lambda *t: gated_delta_chunked(*t, cfg.gdn_chunk))
            o = rule(q, k, v, g, beta)
    with jax.named_scope("gate_norm"):
        o32 = o.astype(F32)
        o32 = o32 * jax.lax.rsqrt(jnp.mean(o32 * o32, axis=-1, keepdims=True) + cfg.norm_eps)
        gated = o32 * p["norm"].astype(F32) * jax.nn.silu(z.astype(F32).reshape(*lead, hv, dv))
        y = gated.astype(dtype).reshape(*lead, value_dim)
    with jax.named_scope("out_proj"):
        return y @ p["out_proj"].astype(dtype)
