"""PowerInfer/SmallThinker-21BA3B-Instruct (``model_type`` smallthinker), written
from the published config's keys and the layer equations of ISSUE 54.  RMSNorm eps
1e-6, no bias anywhere.  With ``sliding_window_layout[i]`` and ``rope_layout[i]``
(equal lists, ``0, 1, 1, 1`` repeated), for layer ``i``:

    h = RMSNorm_in(x)
    r = h W_r                                   64 logits, float32: the router reads
                                                the ATTENTION block's input
    q, k, v = h W_q, h W_k, h W_v               28 | 4 | 4 heads of 128
    rope_layout[i] = 1: q, k rotated (rotate-half, theta 1.5e6); 0: no position
                        signal at all (NoPE)
    sliding_window_layout[i] = 1: query p sees keys j with p - 4096 < j <= p;
                        0: every j <= p
    x = x + softmax(q k^T / sqrt(128)) v W_o    GQA: 7 query heads a key/value head
    y = RMSNorm_post(x)
    E = the 6 largest of r;  w = softmax over those 6 logits (float32)
        (= softmax over all 64, top-6, renormalised: moe_primary_router_apply_softmax
        and norm_topk_prob both true)
    x = x + sum_{e in E} w_e W_down,e (relu(W_gate,e y) * W_up,e y)      ReGLU, width 768
    final RMSNorm, untied head.

No cache: every position's keys and values are made once and every query sees its
keys through a mask.

Departures from the published description, all in the configuration file: the held
share of the experts (``expert_share``: pairs on experts this copy does not hold
are left out of the sum, as in the program; the router scores all 64), the
vocabulary slice, and the readings under ``assumed`` (the router's input is the
NORMED input of the attention block; the window holds the query's own position;
rotate-half pairing).  "Secondary experts" (``described_as``) have no key in the
config: none are built.

``published_weights`` hands the program's own arrays on (no re-laid-out copy): q, k
and v stay in the program's fused projection, whose columns go by key/value head:
head g's 7 query heads, its key head, its value head (``qkv_proj`` (hidden, 4, 9,
128)).  `logits` keeps every float32 intermediate to a block (``lib/serve.compare_rows``
runs it ONCE over 16,384 positions beside 4.1 GB of weights): attention a key/value
head's 7 query heads and a block of queries at a time, the experts a block of
tokens at a time, each under ``jax.lax.map``, the head a block of the vocabulary's
columns at a time.

``lib/flops.py``'s served counts are a dense K/V decoder's, LINEAR in the positions
live in a slot.  A window layer needs ``min(n, 4096)`` of a row's n positions, which
no linear count states, so ``serve_dims`` gives a LOWER bound that holds at every
length up to the slots' 16,384 (below): the cell's three shares of the chip's peaks
(``serve_mfu``, ``serve_hbm_roofline``, ``decode_step_hbm_roofline``) read low, never
over 100.  The exact counts of a decode step's cached attention, which the readers
of ``benchmark/metrics/_swa.py`` take, are `decode_attn_bytes`.
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


def published_weights(params, cfg):
    out = {"embed_tokens": params["embed"]["tok"], "norm": params["final_norm"]["scale"],
           "lm_head": params["head"]["w"], "layers": []}
    for lp in params["layers"]:
        a, m = lp["attn"], lp["mlp"]
        out["layers"].append({
            "input_layernorm": lp["attn_norm"]["scale"],
            "qkv_proj": a["wqkv"], "o_proj": a["wo"],
            "post_attention_layernorm": lp["mlp_norm"]["scale"],
            "router": m["router"]["w"],
            "experts": {"gate_proj": m["w1"], "up_proj": m["w3"], "down_proj": m["w2"]},
        })
    return out


def _blocks(n, size):
    """``n`` as whole blocks of at most ``size``: (blocks, block)."""
    block = math.gcd(n, size) if n % size else size
    return n // block, block


def _sizes(cfg):
    return (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
            int(cfg["head_dim"]))


def rope_tables(cfg, seq_len):
    """(cos, sin), each (seq_len, head_dim / 2): pair i turns at theta^(-2i/d)."""
    d, theta = int(cfg["head_dim"]), float(cfg["rope_theta"])
    ang = np.outer(np.arange(seq_len), theta ** (-np.arange(0, d, 2) / d))
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def _rotate(x, cos, sin):
    """Rotate-half on the last axis of (b, s, heads, d); cos, sin (s, d / 2)."""
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(h, lw, cfg, rope, window):
    """A layer's attention on (1, s, hidden) -> (1, s, hidden).  ``rope``: the
    (cos, sin) tables or None (NoPE); ``window``: 0 or the keys a query sees, its
    own among them."""
    n, kv, d = _sizes(cfg)
    per = n // kv  # query heads a key/value head
    b, s, hidden = h.shape
    blocks, block = _blocks(s, QUERY_BLOCK)
    # a key/value head's columns of the fused projection, its rows of W_o
    wqkv = lw["qkv_proj"].reshape(hidden, kv, (per + 2) * d).transpose(1, 0, 2)
    wo = lw["o_proj"].reshape(kv, per * d, hidden)
    key_pos = jnp.arange(s)

    def group(acc, args):
        wqkv_g, wo_g = args
        qkv = (h @ wqkv_g).reshape(b, s, per + 2, d)
        q, k, v = qkv[:, :, :per], qkv[:, :, per:per + 1], qkv[:, :, per + 1]
        if rope is not None:
            q, k = _rotate(q, *rope), _rotate(k, *rope)
        k = k[:, :, 0]

        def queries(i):
            at = i * block + jnp.arange(block)
            scores = jnp.einsum("bqnd,bkd->bnqk", q[:, at], k) / math.sqrt(d)
            seen = key_pos[None, :] <= at[:, None]
            if window:
                seen = seen & (key_pos[None, :] > at[:, None] - window)
            scores = jnp.where(seen[None, None], scores, -jnp.inf)
            return jnp.einsum("bnqk,bkd->bqnd", jax.nn.softmax(scores, axis=-1), v)

        o = jax.lax.map(queries, jnp.arange(blocks))  # (blocks, b, block, per, d)
        o = jnp.moveaxis(o, 0, 1).reshape(b, s, per * d)
        return acc + o @ wo_g, None

    return jax.lax.scan(group, jnp.zeros_like(h), (wqkv, wo))[0]


def route(r, cfg):
    """Router logits ``r`` (tokens, experts) -> combine weights over ALL the experts
    the router scores: the softmax over a token's 6 largest logits, 0 elsewhere."""
    k = int(cfg["moe_num_active_primary_experts"])
    picked, chosen = jax.lax.top_k(r, k)
    weights = jax.nn.softmax(picked, axis=-1)
    rows = jnp.arange(r.shape[0])[:, None]
    return jnp.zeros_like(r).at[rows, chosen].set(weights)


def moe(y, r, lw, cfg):
    """The expert layer on (1, s, hidden), routed by the logits ``r`` (1, s, experts)
    of the layer's attention input: the held experts' part of the sum;
    ``expert_share`` says which experts are held."""
    b, s, hidden = y.shape
    e = lw["experts"]
    held = e["down_proj"].shape[0]
    share = cfg.get("expert_share") or {"rank": 0, "of": 1}
    first = int(share["rank"]) * held
    blocks, block = _blocks(b * s, TOKEN_BLOCK)

    def tokens(args):
        x, logits = args
        w = route(logits, cfg)[:, first:first + held]  # pairs on absent experts: left out
        mid = jax.nn.relu(jnp.einsum("th,ehf->tef", x, e["gate_proj"])) * jnp.einsum(
            "th,ehf->tef", x, e["up_proj"])
        return jnp.einsum("tef,efh->th", mid * w[:, :, None], e["down_proj"])

    out = jax.lax.map(tokens, (y.reshape(blocks, block, hidden),
                               r.reshape(blocks, block, r.shape[-1])))
    return out.reshape(b, s, hidden)


def logits(w, tokens, cfg):
    eps = float(cfg["rms_norm_eps"])
    tables = rope_tables(cfg, tokens.shape[1])
    span = int(cfg["sliding_window_size"])
    x = w["embed_tokens"][tokens]
    for i, lw in enumerate(w["layers"]):
        h = rms_norm(x, lw["input_layernorm"], eps)
        r = h @ lw["router"]  # before the attention, from what the attention reads
        x = x + attention(h, lw, cfg, tables if cfg["rope_layout"][i] else None,
                          span if cfg["sliding_window_layout"][i] else 0)
        x = x + moe(rms_norm(x, lw["post_attention_layernorm"], eps), r, lw, cfg)
    b, s, hidden = x.shape
    h = rms_norm(x, w["norm"], eps).reshape(b * s, hidden)  # (rows, hidden): see VOCAB_BLOCK
    head = w["lm_head"]
    parts = [h @ head[:, i:i + VOCAB_BLOCK] for i in range(0, head.shape[1], VOCAB_BLOCK)]
    return jnp.concatenate(parts, axis=-1).reshape(b, s, head.shape[1])


# -- counts ---------------------------------------------------------------------------


def _layers(cfg):
    """(full layers, window layers) of the layers this copy runs."""
    n = int(cfg["num_hidden_layers"])
    win = sum(1 for w in cfg["sliding_window_layout"][:n] if w)
    return n - win, win


def _token_weights(cfg):
    """Weights a token is multiplied by HERE, a layer: (the four projections, the
    router over all the experts and the held share's even part of the top-6)."""
    h = int(cfg["hidden_size"])
    n, kv, d = _sizes(cfg)
    proj = h * (n + 2 * kv) * d + n * d * h
    share = int((cfg.get("expert_share") or {"of": 1})["of"])
    experts_all = int(cfg["moe_num_primary_experts"]) * share
    routed = h * experts_all + 3 * h * int(cfg["moe_ffn_hidden_size"]) * (
        int(cfg["moe_num_active_primary_experts"]) / share)
    return proj, routed


def window_pairs(seq_len, window):
    """(query, key) pairs of one sequence in a window layer: query p sees
    ``min(p + 1, window)`` keys."""
    short = min(seq_len, window)
    return short * (short + 1) // 2 + (seq_len - short) * window


def fwd_flops_per_token(cfg, seq_len):
    """Forward model FLOPs a token of the NO-CACHE forward at ``seq_len``: the
    projections, scores and values at 2 x 128 a pair and head (a full layer the
    causal half, a window layer `window_pairs`), the router and the experts a token
    runs HERE, the head."""
    h = int(cfg["hidden_size"])
    n, _, d = _sizes(cfg)
    full, win = _layers(cfg)
    proj, routed = _token_weights(cfg)
    pairs = (full * seq_len * (seq_len + 1) / 2
             + win * window_pairs(seq_len, int(cfg["sliding_window_size"]))) / seq_len
    return (2.0 * ((full + win) * (proj + routed) + h * int(cfg["vocab_size"]))
            + 2 * 2.0 * n * d * pairs)


def position_share(cfg):
    """The least share of a row's live positions a layer of this stack reads or
    multiplies, mean over its layers, at ANY length up to the published positions
    P: a full layer all of them, a window layer ``min(n, window) / n >= window / P``.
    16 layers of the published period: (4 + 12 x 4096 / 16384) / 16 = 7 / 16."""
    full, win = _layers(cfg)
    return (full + win * int(cfg["sliding_window_size"]) / int(cfg["max_position_embeddings"])
            ) / (full + win)


def serve_dims(cfg):
    """This model's served work in the sizes ``lib/flops.py`` counts from.  Its
    formulas are a dense K/V decoder's whose every layer reads and multiplies EVERY
    live position of a row, so two sizes are stated to give a LOWER bound of this
    stack's work at every length up to the slots' 16,384:

    - ``head_dim`` 56 = 128 x `position_share` (7 / 16): with 28 heads a (query, live
      position) pair then counts 2 x 2 x 28 x 56 a layer, what the full layers need
      plus a quarter of what a window layer would need if it saw the whole row
      (it needs ``min(n, 4096) / n`` of that, never less than a quarter), and with 4
      key/value heads a live position counts 2 x 4 x 56 x 2 B = 896 B a layer,
      14,336 B over 16 layers, where the exact least is ``2,048 B x (4 + 12
      min(n, 4096) / n)`` (`least_bytes_per_position`; the test holds the bound
      for every n).  So the cell's three shares of the chip's peaks read LOW by up
      to 16 / 7 on the attention's part and cannot pass 100;
    - ``ffn`` (with ``mlp_matrices`` 1): whatever a token's weights hold beyond the
      formula's four hidden x (heads x hidden // heads) projections, a layer: the
      rest of the projections (GQA, heads of 128 where 2560 / 28 is 91) and the
      router and experts of ``_token_weights``.
    """
    h, layers = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    n, kv, d = _sizes(cfg)
    proj, routed = _token_weights(cfg)
    return {"hidden": h, "heads": n, "kv_heads": kv, "head_dim": d * position_share(cfg),
            "ffn": (proj - 4 * h * (h // n) * n + routed) / h, "mlp_matrices": 1,
            "layers": layers, "vocab": int(cfg["vocab_size"])}


def least_bytes_per_position(cfg, n, itemsize=2):
    """K and V a decode step must read of a row of ``n`` live positions, over all
    layers, a live position: a full layer all n, a window layer ``min(n, window)``."""
    _, kv, d = _sizes(cfg)
    full, win = _layers(cfg)
    return 2 * kv * d * itemsize * (full + win * min(n, int(cfg["sliding_window_size"])) / n)


def served_params(cfg):
    """Parameters a forward must read whatever implements it: ``a_forward``, once
    however many tokens it holds: every layer's projections, norms, router (over
    ALL the experts) and every expert this copy HOLDS, the final norm and the untied
    head; ``a_token``, once a token: its row of the embedding.  A decode step of 32
    tokens (48 pairs on 16 held experts) may leave an expert untouched; they are
    counted all the same, as the parameters of a forward."""
    h, layers = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    proj, _ = _token_weights(cfg)
    share = int((cfg.get("expert_share") or {"of": 1})["of"])
    held = int(cfg["moe_num_primary_experts"])
    layer = proj + 2 * h + h * held * share + 3 * h * int(cfg["moe_ffn_hidden_size"]) * held
    return {"a_forward": layers * layer + h + h * int(cfg["vocab_size"]), "a_token": h}


def decode_attn_bytes(cfg, full_live, window_live, new_positions, full_layers, window_layers,
                      itemsize=2):
    """Least HBM bytes of ONE decode step's cached attention, all layers: the
    positions live in the rows read once a layer (a full layer ``full_live`` = the
    sum of the rows' lengths n, a window layer ``window_live`` = the sum of
    ``min(n, window)``) and the step's new positions written once a layer, K and V
    (2 x 4 x 128 x ``itemsize`` = 2,048 B a position and layer in bf16).  The
    weights are ``qkv_proj``'s: left out, so a share over this reads low."""
    _, kv, d = _sizes(cfg)
    per = 2 * kv * d * itemsize
    return per * (full_live * full_layers + window_live * window_layers
                  + new_positions * (full_layers + window_layers))
