"""ibm-granite/granite-4.0-h-micro (HF ``modeling_granitemoehybrid.py``,
``model_type`` granitemoehybrid) from its published config: a pre-norm RMSNorm
stack whose layers are, by ``layer_types``, a Mamba-2 mixer or causal GQA
attention, each followed by the shared gated MLP.

    x0 = embedding_multiplier * E[tokens]
    x  = x + residual_multiplier * mixer(RMSNorm(x))
    x  = x + residual_multiplier * W_out (silu(g) * u),  [g | u] = W_in RMSNorm(x)
    logits = RMSNorm(x_L) E^T / logits_scaling                     (tied table)

Attention (``position_embedding_type`` "nope": no rotary, no table): bias-free
q, k, v, o; query head i reads key/value head ``i // (heads / kv_heads)``;
``softmax(q k^T * attention_multiplier + causal) v``. Computed a block of
queries at a time, so that heads x s x s float32 scores never exist at once
(8.6 GB at 32 x 8192 x 8192).

Mamba-2 mixer: ``[z | xBC | dt] = W_in h`` (widths d_inner | d_inner + 2 G N |
H); ``xBC = silu(conv1d(xBC) + b)``, depthwise and causal over ``mamba_d_conv``
taps; ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``;
per head ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``, ``y_t = H_t C_t + D x_t``;
``y = RMSNorm(y * silu(z)) * scale`` over all d_inner channels (gate before
the norm, one norm group); ``W_out y``. The scan here is that recurrence, one
position at a time (``lax.scan`` over the sequence): it shares nothing with
the program's chunked algorithm, and ``mamba_chunk_size`` is not read.

Departures from the published model, as in the configuration file:
``num_local_experts`` is 0, so there is no routed part to leave out; the
initialisation range is not in the published keys the catalog keeps (the
reference takes whatever weights it is handed); dropout is none.

Also here: what the SSD scan of a step needs at the least
(``ssd_scan_flops`` / ``ssd_scan_bytes``), for ``ssm_scan_roofline``.
"""

import jax
import jax.numpy as jnp

from benchmark.lib import flops
from benchmark.lib.reference import rms_norm

F32 = jnp.float32
#: queries a block of the reference attention holds scores for
QUERY_BLOCK = 512


def _dims(cfg):
    heads, hd = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    groups, state = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    d_inner = heads * hd
    return heads, hd, groups, state, d_inner, d_inner + 2 * groups * state


def published_weights(params, cfg):
    """The program's flat tree under the published names. Its fused attention
    projection is interleaved by key/value group (a group's query heads, then
    its key head, then its value head); the conv taps are stored (K, C)."""
    n, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    h = int(cfg["hidden_size"])
    hd, rep = h // n, n // kv
    out = {"embed_tokens": params["embed"]["tok"], "norm": params["final_norm"]["scale"],
           "layers": []}
    for lp in params["layers"]:
        lw = {"input_layernorm": lp["attn_norm"]["scale"],
              "post_attention_layernorm": lp["mlp_norm"]["scale"],
              "input_linear": lp["mlp"]["w13"], "output_linear": lp["mlp"]["w2"]}
        if "ssm" in lp:
            m = lp["ssm"]
            lw["mamba"] = {"in_proj": m["in_proj"], "conv1d_weight": m["conv_w"].T,
                           "conv1d_bias": m["conv_b"], "A_log": m["A_log"], "D": m["D"],
                           "dt_bias": m["dt_bias"], "norm": m["norm"], "out_proj": m["out_proj"]}
        else:
            w = lp["attn"]["wqkv"].reshape(h, kv, rep + 2, hd)
            lw["self_attn"] = {"q_proj": w[:, :, :rep].reshape(h, n * hd),
                               "k_proj": w[:, :, rep].reshape(h, kv * hd),
                               "v_proj": w[:, :, rep + 1].reshape(h, kv * hd),
                               "o_proj": lp["attn"]["wo"]}
        out["layers"].append(lw)
    return out


def attention(y, w, cfg):
    n, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    scale = float(cfg["attention_multiplier"])
    b, s, _ = y.shape
    rep = n // kv
    q = (y @ w["q_proj"]).reshape(b, s, kv, rep, -1)
    k = (y @ w["k_proj"]).reshape(b, s, kv, -1)
    v = (y @ w["v_proj"]).reshape(b, s, kv, -1)
    blk = next(c for c in range(min(QUERY_BLOCK, s), 0, -1) if s % c == 0)
    keys = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k) * scale
        seen = keys[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)

    o = jax.lax.map(block, jnp.arange(s // blk))  # (blocks, b, blk, kv, rep, d)
    return jnp.moveaxis(o, 0, 1).reshape(b, s, -1) @ w["o_proj"]


def mamba(y, w, cfg):
    heads, hd, groups, state, d_inner, conv_dim = _dims(cfg)
    b, s, _ = y.shape
    zxbcdt = y @ w["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_dim], axis=-1)
    taps = w["conv1d_weight"]  # (C, K): tap K-1 on the current position
    k = taps.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, j:j + s] * taps[:, j] for j in range(k)) + w["conv1d_bias"])
    x, b_mat, c_mat = jnp.split(xbc, [d_inner, d_inner + groups * state], axis=-1)
    x = x.reshape(b, s, groups, heads // groups, hd)
    b_mat, c_mat = b_mat.reshape(b, s, groups, state), c_mat.reshape(b, s, groups, state)
    dt = jax.nn.softplus(dt + w["dt_bias"]).reshape(b, s, groups, heads // groups)
    a = -jnp.exp(w["A_log"]).reshape(groups, heads // groups)

    def step(hstate, inp):  # hstate (b, G, R, P, N)
        x_t, b_t, c_t, dt_t = inp
        hstate = (jnp.exp(dt_t * a)[..., None, None] * hstate
                  + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :])
        return hstate, jnp.einsum("bgrpn,bgn->bgrp", hstate, c_t)

    _, ys = jax.lax.scan(
        step, jnp.zeros((b, groups, heads // groups, hd, state), F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, b_mat, c_mat, dt)))
    yh = jnp.moveaxis(ys, 0, 1) + w["D"].reshape(groups, heads // groups)[..., None] * x
    gated = yh.reshape(b, s, d_inner) * jax.nn.silu(z)
    return rms_norm(gated, w["norm"], float(cfg["rms_norm_eps"])) @ w["out_proj"]


def logits(w, tokens, cfg):
    eps, res = float(cfg["rms_norm_eps"]), float(cfg["residual_multiplier"])
    f = int(cfg["shared_intermediate_size"])
    x = w["embed_tokens"][tokens] * float(cfg["embedding_multiplier"])
    for kind, lw in zip(cfg["layer_types"], w["layers"]):
        y = rms_norm(x, lw["input_layernorm"], eps)
        mixed = mamba(y, lw["mamba"], cfg) if kind == "mamba" else attention(y, lw["self_attn"], cfg)
        x = x + res * mixed
        gu = rms_norm(x, lw["post_attention_layernorm"], eps) @ lw["input_linear"]
        x = x + res * ((jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ lw["output_linear"])
    return rms_norm(x, w["norm"], eps) @ w["embed_tokens"].T / float(cfg["logits_scaling"])


def _ssd_fwd_flops_per_token(cfg, chunk):
    """The chunked SSD's four GEMMs a token and layer, forward: the causal half
    of C B^T (once a group) and of (decay-masked scores) x inside a chunk, the
    chunk's state B^T x, and the entering state's read-out C H."""
    heads, hd, groups, state, _, _ = _dims(cfg)
    pairs = (chunk + 1) / 2
    return 2.0 * pairs * (groups * state + heads * hd) + 2 * 2.0 * heads * hd * state


def fwd_flops_per_token(cfg, seq_len):
    """Forward model FLOPs a token (``lib/flops.py``'s conventions). A Mamba-2
    layer: in_proj, the conv's taps, the chunked SSD at ``mamba_chunk_size``
    (linear in the sequence), out_proj; the attention layer: q, k, v, o and the
    causal half of QK^T and PV (quadratic); every layer the shared MLP's two
    matrices; the tied head."""
    h, f = int(cfg["hidden_size"]), int(cfg["shared_intermediate_size"])
    n, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    heads, _, _, _, d_inner, conv_dim = _dims(cfg)
    hd = h // n
    mlp = 2.0 * 3 * h * f
    ssm = (2.0 * h * (d_inner + conv_dim + heads) + 2.0 * int(cfg["mamba_d_conv"]) * conv_dim
           + _ssd_fwd_flops_per_token(cfg, int(cfg["mamba_chunk_size"])) + 2.0 * d_inner * h)
    attn = (2.0 * h * (2 * n * hd + 2 * kv * hd)
            + 2 * 2.0 * n * hd * flops.attention_pairs(seq_len) / seq_len)
    kinds = cfg["layer_types"][: int(cfg["num_hidden_layers"])]
    n_ssm = sum(k == "mamba" for k in kinds)
    return (n_ssm * (ssm + mlp) + (len(kinds) - n_ssm) * (attn + mlp)
            + 2.0 * h * int(cfg["vocab_size"]))


def _ssm_layers(cfg):
    return sum(k == "mamba" for k in cfg["layer_types"][: int(cfg["num_hidden_layers"])])


def ssd_scan_flops(cfg, tokens):
    """Operations the SSD scans of one step need, forward + backward (each GEMM
    once forward and twice backward). Recomputed GEMMs do not count."""
    return 3.0 * _ssm_layers(cfg) * tokens * _ssd_fwd_flops_per_token(
        cfg, int(cfg["mamba_chunk_size"]))


def ssd_scan_bytes(cfg, tokens, itemsize=2):
    """Least HBM traffic of the same, a step: forward reads x, B, C and dt and
    writes y; backward reads them and dy again and writes their four gradients
    (the per-head A, D and the carried states are small and left out)."""
    heads, _, groups, state, d_inner, _ = _dims(cfg)
    ins = d_inner + 2 * groups * state + heads
    return _ssm_layers(cfg) * tokens * itemsize * ((ins + d_inner) + (2 * ins + d_inner))
