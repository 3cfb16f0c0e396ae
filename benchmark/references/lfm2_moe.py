"""LiquidAI/LFM2-24B-A2B (``model_type`` lfm2_moe), written from the published config's
keys and the layer equations of ISSUE 58.  RMSNorm is ``w * x / sqrt(mean(x^2) + eps)``,
eps ``norm_eps`` 1e-5, no bias anywhere.  With ``layer_types[i]`` for layer ``i``:

    h = RMSNorm_op(x)
    "conv":            [B | C | u] = h W_in             three widths of hidden, in that order
                       z = B * u
                       c_t = sum_{j=0..2} w[j] * z_{t-2+j}   depthwise, causal, zeros before
                                                        the sequence, w[2] on position t
                       x = x + (C * c) W_out
    "full_attention":  q, k, v = h W_q, h W_k, h W_v    32 | 8 | 8 heads of 64
                       q, k = RMSNorm_64(q), RMSNorm_64(k)   each head by itself, one weight
                                                        vector of 64 for q and one for k,
                                                        BEFORE rotary
                       q, k rotated (rotate-half over the whole head, theta 1e6)
                       x = x + softmax_causal(q k^T / 8) v W_o    4 query heads a key/value head
    y = RMSNorm_ffn(x)
    i < num_dense_layers (2):  x = x + W_2 (silu(W_1 y) * W_3 y)          width 11776
    else:  s = sigmoid(y W_r) (float32, 64); the 4 experts are the top-4 of s + b
           (b SELECTS only); w_e = s_e / (sum of the four s + 1e-6), x routed_scaling_factor 1;
           x = x + sum_e w_e W_2,e (silu(W_1,e y) * W_3,e y)              width 1536
    final RMSNorm (published name ``embedding_norm``), head TIED to the embedding.

No cache and no state: the convolution runs over the whole sequence, every position's
keys and values are made once and every query sees its keys through a mask.

Departures from the published description, all in the configuration file: the held share
of the experts (``expert_share``: pairs on experts this copy does not hold are left out of
the sum, as in the program; the router scores all 64), the vocabulary slice, and the
readings under ``assumed`` (the tied head, ``embedding_norm`` at the output, the 1e-6 in
the renormalisation, rotate-half pairing, the initial selection bias).

``published_weights`` hands the program's own arrays on (no re-laid-out copy): q, k and v
stay in the program's fused projection, whose columns go by key/value head: head g's 4
query heads, its key head, its value head (``qkv_proj`` (hidden, 8, 6, 64)).  `logits`
keeps every float32 intermediate to a block (``lib/serve.compare_rows`` runs it ONCE over
16,384 positions beside 7.1 GB of weights): attention a key/value head's 4 query heads
and a block of queries at a time, the experts a block of tokens at a time, each under
``jax.lax.map``, the head a block of the vocabulary's columns at a time.

``lib/flops.py``'s served counts are a dense K/V decoder's whose EVERY layer reads and
multiplies every live position of a row.  Here 5 layers of 22 do (the attention layers)
and 17 read a state of two columns whatever the row's length, so ``serve_dims`` states a
head of ``64 x attention layers / layers``, at which the formulas give this stack's
attention and its K and V exactly; the conv layers' state (8,192 B a row and layer, read
and written a step) is left out, so the cell's three shares of the chip's peaks read
low by that, never over 100.  The exact counts of a decode step's cached attention and
of its conv mixers, which the readers of ``benchmark/metrics/_swa.py`` and
``_shortconv.py`` take, are `decode_attn_bytes` and `shortconv_step_bytes`.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.reference import F32, rms_norm

#: queries a step of the attention takes, tokens a step of the experts
QUERY_BLOCK, TOKEN_BLOCK = 1024, 1024
#: columns of the head multiplied at once (``references/sarvam_mla.py`` says why)
VOCAB_BLOCK = 32768
#: the renormalisation's guard (``assumed``)
NORM_TOPK_EPS = 1e-6


def published_weights(params, cfg):
    out = {"embed_tokens": params["embed"]["tok"], "embedding_norm": params["final_norm"]["scale"],
           "layers": []}
    for lp in params["layers"]:
        lw = {"operator_norm": lp["attn_norm"]["scale"], "ffn_norm": lp["mlp_norm"]["scale"]}
        if "shortconv" in lp:
            c = lp["shortconv"]
            lw["conv"] = {"in_proj": c["in_proj"], "conv": c["conv_w"], "out_proj": c["out_proj"]}
        else:
            a = lp["attn"]
            lw["self_attn"] = {"qkv_proj": a["wqkv"], "q_layernorm": a["q_norm"],
                               "k_layernorm": a["k_norm"], "out_proj": a["wo"]}
        m = lp["mlp"]
        if "router" in m:
            lw["feed_forward"] = {
                "gate": m["router"]["w"], "expert_bias": m["router"]["bias"],
                "experts": {"w1": m["w1"], "w3": m["w3"], "w2": m["w2"]}}
        else:  # a leading dense layer (num_dense_layers): [w1 | w3] in one matrix
            lw["feed_forward"] = {"w13": m["w13"], "w2": m["w2"]}
        out["layers"].append(lw)
    return out


def _blocks(n, size):
    """``n`` as whole blocks of at most ``size``: (blocks, block)."""
    block = math.gcd(n, size) if n % size else size
    return n // block, block


def _sizes(cfg):
    n, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    return n, kv, int(cfg["hidden_size"]) // n


def rope_tables(cfg, seq_len):
    """(cos, sin), each (seq_len, head_dim / 2): pair i turns at theta^(-2i/d)."""
    d, theta = _sizes(cfg)[2], float(cfg["rope_parameters"]["rope_theta"])
    ang = np.outer(np.arange(seq_len), theta ** (-np.arange(0, d, 2) / d))
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def _rotate(x, cos, sin):
    """Rotate-half on the last axis of (b, s, heads, d); cos, sin (s, d / 2)."""
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def short_conv(h, cw, cfg):
    """A conv layer's mixer on (b, s, hidden) -> the same: the whole sequence, zeros
    before it."""
    hidden, taps = h.shape[-1], int(cfg["conv_L_cache"])
    bcu = h @ cw["in_proj"]
    gate_b, gate_c, u = bcu[..., :hidden], bcu[..., hidden:2 * hidden], bcu[..., 2 * hidden:]
    z = jnp.pad(gate_b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    s = h.shape[1]
    c = sum(cw["conv"][j] * z[:, j:j + s] for j in range(taps))  # tap taps-1 on position t
    return (gate_c * c) @ cw["out_proj"]


def attention(h, aw, cfg, rope):
    """An attention layer's mixer on (1, s, hidden) -> (1, s, hidden)."""
    n, kv, d = _sizes(cfg)
    per = n // kv  # query heads a key/value head
    eps = float(cfg["norm_eps"])
    b, s, hidden = h.shape
    blocks, block = _blocks(s, QUERY_BLOCK)
    # a key/value head's columns of the fused projection, its rows of W_o
    wqkv = aw["qkv_proj"].reshape(hidden, kv, (per + 2) * d).transpose(1, 0, 2)
    wo = aw["out_proj"].reshape(kv, per * d, hidden)
    key_pos = jnp.arange(s)

    def group(acc, args):
        wqkv_g, wo_g = args
        qkv = (h @ wqkv_g).reshape(b, s, per + 2, d)
        q, k, v = qkv[:, :, :per], qkv[:, :, per:per + 1], qkv[:, :, per + 1]
        q, k = rms_norm(q, aw["q_layernorm"], eps), rms_norm(k, aw["k_layernorm"], eps)
        q, k = _rotate(q, *rope), _rotate(k, *rope)[:, :, 0]

        def queries(i):
            at = i * block + jnp.arange(block)
            scores = jnp.einsum("bqnd,bkd->bnqk", q[:, at], k) / math.sqrt(d)
            scores = jnp.where((key_pos[None, :] <= at[:, None])[None, None], scores, -jnp.inf)
            return jnp.einsum("bnqk,bkd->bqnd", jax.nn.softmax(scores, axis=-1), v)

        o = jax.lax.map(queries, jnp.arange(blocks))  # (blocks, b, block, per, d)
        o = jnp.moveaxis(o, 0, 1).reshape(b, s, per * d)
        return acc + o @ wo_g, None

    return jax.lax.scan(group, jnp.zeros_like(h), (wqkv, wo))[0]


def swiglu(h, w13, w2):
    f = w13.shape[-1] // 2
    gu = h @ w13
    return (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ w2


def route(y, fw, cfg):
    """(tokens, experts) combine weights over ALL the experts the router scores: 0 for
    an expert a token did not choose."""
    k, scale = int(cfg["num_experts_per_tok"]), float(cfg["routed_scaling_factor"])
    s = jax.nn.sigmoid(y @ fw["gate"])
    _, chosen = jax.lax.top_k(s + fw["expert_bias"], k)  # the bias selects, never weighs
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = scale * picked / (jnp.sum(picked, axis=-1, keepdims=True) + NORM_TOPK_EPS)
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(weights)


def moe(y, fw, cfg):
    """The expert layer on (1, s, hidden): the held experts' part of the routed sum;
    ``expert_share`` says which experts are held."""
    b, s, hidden = y.shape
    e = fw["experts"]
    held = e["w2"].shape[0]
    share = cfg.get("expert_share") or {"rank": 0, "of": 1}
    first = int(share["rank"]) * held
    blocks, block = _blocks(b * s, TOKEN_BLOCK)

    def tokens(x):
        w = route(x, fw, cfg)[:, first:first + held]  # pairs on absent experts: left out
        mid = jax.nn.silu(jnp.einsum("th,ehf->tef", x, e["w1"])) * jnp.einsum(
            "th,ehf->tef", x, e["w3"])
        return jnp.einsum("tef,efh->th", mid * w[:, :, None], e["w2"])

    out = jax.lax.map(tokens, y.reshape(blocks, block, hidden))
    return out.reshape(b, s, hidden)


def logits(w, tokens, cfg):
    eps = float(cfg["norm_eps"])
    rope = rope_tables(cfg, tokens.shape[1])
    x = w["embed_tokens"][tokens]
    for lw in w["layers"]:
        h = rms_norm(x, lw["operator_norm"], eps)
        if "conv" in lw:
            x = x + short_conv(h, lw["conv"], cfg)
        else:
            x = x + attention(h, lw["self_attn"], cfg, rope)
        y = rms_norm(x, lw["ffn_norm"], eps)
        fw = lw["feed_forward"]
        x = x + (moe(y, fw, cfg) if "gate" in fw else swiglu(y, fw["w13"], fw["w2"]))
    b, s, hidden = x.shape
    h = rms_norm(x, w["embedding_norm"], eps).reshape(b * s, hidden)  # (rows, hidden)
    head = w["embed_tokens"].T  # tied
    parts = [h @ head[:, i:i + VOCAB_BLOCK] for i in range(0, head.shape[1], VOCAB_BLOCK)]
    return jnp.concatenate(parts, axis=-1).reshape(b, s, head.shape[1])


# -- counts ---------------------------------------------------------------------------


def _layers(cfg):
    """(attention layers, conv layers, dense-MLP layers) of the layers this copy runs."""
    n = int(cfg["num_hidden_layers"])
    kinds = cfg["layer_types"][:n]
    attn = sum(1 for k in kinds if k == "full_attention")
    return attn, n - attn, min(n, int(cfg["num_dense_layers"]))


def _token_weights(cfg):
    """Weights a token is multiplied by HERE: (an attention layer's four projections, a
    conv layer's two, a dense MLP, an expert layer's router over all the experts and the
    held share's even part of the top-4)."""
    h = int(cfg["hidden_size"])
    n, kv, d = _sizes(cfg)
    share = int((cfg.get("expert_share") or {"of": 1})["of"])
    experts_all = int(cfg["num_experts"]) * share
    routed = h * experts_all + 3 * h * int(cfg["moe_intermediate_size"]) * (
        int(cfg["num_experts_per_tok"]) / share)
    return (h * (n + 2 * kv) * d + n * d * h, h * 3 * h + h * h,
            3 * h * int(cfg["intermediate_size"]), routed)


def _body_weights(cfg):
    """`_token_weights` summed over the layers this copy runs."""
    attn, conv, dense = _layers(cfg)
    w_attn, w_conv, w_dense, w_routed = _token_weights(cfg)
    return attn * w_attn + conv * w_conv + dense * w_dense + (attn + conv - dense) * w_routed


def fwd_flops_per_token(cfg, seq_len):
    """Forward model FLOPs a token of the NO-CACHE forward at ``seq_len``: the
    projections, the conv's taps and gates (a multiply-add a channel each), scores and
    values at 2 x 64 a causal pair and head in the attention layers, the router and the
    experts a token runs HERE, the head."""
    h = int(cfg["hidden_size"])
    n, _, d = _sizes(cfg)
    attn, conv, _ = _layers(cfg)
    pairs = attn * (seq_len + 1) / 2
    return (2.0 * (_body_weights(cfg) + h * int(cfg["vocab_size"]))
            + 2.0 * conv * h * (int(cfg["conv_L_cache"]) + 2) + 2 * 2.0 * n * d * pairs)


def position_share(cfg):
    """The share of this stack's layers that read and multiply a row's live positions:
    the attention layers (5 of the cell's 22)."""
    attn, conv, _ = _layers(cfg)
    return attn / (attn + conv)


def serve_dims(cfg):
    """This model's served work in the sizes ``lib/flops.py`` counts from.  Its formulas
    count K, V and a (query, live position) pair in EVERY layer, so ``head_dim`` is stated
    as 64 x `position_share`: with 32 heads a pair then counts 2 x 2 x 32 x 64 in the
    attention layers alone, and with 8 key/value heads a live position 2 x 8 x 64 x 2 B
    = 2,048 B in each of them (`least_bytes_per_position`), exactly; the conv layers'
    state is left out (the module's note).  ``ffn`` (with ``mlp_matrices`` 1): whatever a
    token's weights hold beyond the formula's four hidden x hidden projections, a layer
    on average."""
    h, layers = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    n, kv, d = _sizes(cfg)
    return {"hidden": h, "heads": n, "kv_heads": kv, "head_dim": d * position_share(cfg),
            "ffn": (_body_weights(cfg) / layers - 4 * h * (h // n) * n) / h, "mlp_matrices": 1,
            "layers": layers, "vocab": int(cfg["vocab_size"])}


def least_bytes_per_position(cfg, itemsize=2):
    """K and V a decode step must read of a row, over all layers, a live position: the
    attention layers', whatever the row's length."""
    _, kv, d = _sizes(cfg)
    return 2 * kv * d * itemsize * _layers(cfg)[0]


def served_params(cfg):
    """Parameters a forward must read whatever implements it: ``a_forward``, once however
    many tokens it holds: every layer's mixer (an attention layer's projections and two
    head norms, a conv layer's projections and taps), two norms, the dense MLPs, an expert
    layer's router (matrix and bias over ALL the experts) and every expert this copy
    HOLDS, the final norm and the head, which is the embedding (tied: one array);
    ``a_token``, once a token: its row of the embedding.  A decode step of 32 tokens (32
    pairs on 16 held experts) may leave an expert untouched; they are counted all the
    same, as the parameters of a forward."""
    h = int(cfg["hidden_size"])
    _, _, d = _sizes(cfg)
    attn, conv, dense = _layers(cfg)
    w_attn, w_conv, w_dense, _ = _token_weights(cfg)
    share = int((cfg.get("expert_share") or {"of": 1})["of"])
    held = int(cfg["num_experts"])
    expert_layer = h * held * share + held * share + 3 * h * int(cfg["moe_intermediate_size"]) * held
    body = (attn * (w_attn + 2 * d) + conv * (w_conv + int(cfg["conv_L_cache"]) * h)
            + (attn + conv) * 2 * h + dense * w_dense + (attn + conv - dense) * expert_layer)
    return {"a_forward": body + h + h * int(cfg["vocab_size"]), "a_token": h}


def decode_attn_bytes(cfg, full_live, window_live, new_positions, full_layers, window_layers,
                      itemsize=2):
    """Least HBM bytes of ONE decode step's cached attention, all attention layers: the
    positions live in the rows read once a layer (``full_live`` = the sum of the rows'
    lengths) and the step's new positions written once a layer, K and V (2 x 8 x 64 x
    ``itemsize`` = 2,048 B a position and layer in bf16).  The stack has no window layer
    (``window_live`` and ``window_layers`` 0; counted as given).  The weights are
    ``qkv_proj``'s: left out, so a share over this reads low."""
    _, kv, d = _sizes(cfg)
    per = 2 * kv * d * itemsize
    return per * (full_live * full_layers + window_live * window_layers
                  + new_positions * (full_layers + window_layers))


def shortconv_step_bytes(cfg, rows, state_layers, itemsize=2):
    """Least HBM bytes of ONE decode step's conv mixers, ``state_layers`` layers over
    ``rows`` rows of the slot cache: each layer's two projections and taps read once, each
    row's state (``conv_L_cache`` - 1 columns of hidden) read once and written once."""
    h, taps = int(cfg["hidden_size"]), int(cfg["conv_L_cache"])
    weights = h * 3 * h + h * h + taps * h
    return itemsize * state_layers * (weights + 2 * rows * (taps - 1) * h)
