"""arcee-ai/Trinity-Large-Preview (``model_type`` afmoe), written from the published
config's keys and the layer equations of ISSUE 61.  ``RMS_w(x) = x * rsqrt(mean(x^2) +
1e-5) * w``, no bias anywhere.  With ``layer_types[i]`` for layer ``i``:

    x = E[ids] * sqrt(3072)                         (``mup_enabled``)
    a = RMS_n1(x)
    q, k, v, g = a W_q, a W_k, a W_v, a W_g         48 | 8 | 8 | 48 heads of 128: g the
                                                    OUTPUT GATE, a projection of its own
    q, k = RMS_wq(q), RMS_wk(k)                     each head by itself, one gain vector
                                                    of 128 for q and one for k
    "sliding_attention": q, k rotated (rotate-half over the whole head, theta 1e4);
                         query p sees keys j with p - 4096 < j <= p
    "full_attention":    NO position signal; every j <= p
    o = softmax(q k^T / sqrt(128)) v                GQA: query head n reads key/value
                                                    head n // 6
    x = x + RMS_n2((o * sigmoid(g)) W_o)            the norm AFTER the attention
    m = RMS_n3(x)
    i < num_dense_layers:  f = W_2 (silu(W_1 m) * W_3 m)                  width 12288
    else:  s = sigmoid(m W_r) (float32, 256); the 4 experts are the top-4 of s + b
           (b SELECTS only); w_e = 2.448 * s_e / (sum of the four s + 1e-20);
           f = sum_e w_e Expert_e(m) + Shared(m), each a SwiGLU MLP of width 3072, the
           shared one added UNGATED
    x = x + RMS_n4(f)                               the norm AFTER the MLP
    logits = RMS_f(x) W_head                        untied, no scaling.

No cache: every position's keys and values are made once and every query sees its keys
through a mask.

Departures from the published description, all in the configuration file: the held share
of the experts (``expert_share``: pairs on experts this copy does not hold are left out
of the sum, as in the program; the router scores all 256 and the weights are NOT
renormalised over the held ones), the vocabulary slice, and the readings under
``assumed``, none of which the catalog row can confirm: the embedding's factor
sqrt(hidden) (``mup_enabled`` names none); the gate as a SEPARATE projection of the
query's width (``described_as`` says "gated"); per-head q/k norms with plain ``* w``
gains; rotary on the sliding layers and NO position signal on the full ones; the window
holding the query's own position; rotate-half pairing; "depth-scaled" read as how the
gains of n2 and n4 are initialised, nothing the forward computes.

``published_weights`` hands the program's own arrays on (no re-laid-out copy): q, k and v
stay in the program's fused projection, whose columns go by key/value head: head g's 6
query heads, its key head, its value head (``qkv_proj`` (hidden, 8, 8, 128)); the gate's
and W_o's go by query head.  `logits` keeps every float32 intermediate to a block
(``lib/serve.compare_rows`` runs it ONCE over 16,384 positions beside 8.6 GB of bf16
weights): attention a key/value head's 6 query heads and a block of queries at a time,
the experts HALF of the held ones and a block of tokens at a time (a layer's 32 held
experts are 3.6 GB in float32), each under ``jax.lax.map``.

``lib/flops.py``'s served counts are a dense K/V decoder's, LINEAR in the positions live
in a slot.  A window layer needs ``min(n, 4096)`` of a row's n positions, so ``serve_dims``
gives a LOWER bound that holds at every length up to the slots' capacity, as
``references/smallthinker.py``'s does; and ``served_params`` counts, of an expert layer's
held experts, the FOUR one token's forward reads in a whole deployment: a decode step of
32 tokens puts 16 pairs on the 32 held experts and touches about 12 of them, which ones
changes every step, and a count of all 32 would let a program that stops reading the
untouched ones pass 100%.  So the cell's three shares of the chip's peaks
(``serve_mfu``, ``serve_hbm_roofline``, ``decode_step_hbm_roofline``) read LOW, never
over 100.  The exact counts are `decode_attn_bytes` (a step's cached attention, the
readers of ``benchmark/metrics/_swa.py``) and `expert_step_bytes` (the experts a step
TOUCHED, from the engine's counter: ``serve_expert_hbm_roofline``).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.reference import F32, rms_norm

#: queries a step of the attention takes, tokens a step of the experts
QUERY_BLOCK, TOKEN_BLOCK = 1024, 1024
#: parts a layer's held experts are multiplied in (a part's three stacks in float32:
#: 16 x 3 x 3072 x 3072 x 4 B = 1.8 GB)
EXPERT_PARTS = 2
#: columns of the head multiplied at once (``references/sarvam_mla.py`` says why)
VOCAB_BLOCK = 32768
#: the renormalisation's guard
NORM_TOPK_EPS = 1e-20


def published_weights(params, cfg):
    out = {"embed_tokens": params["embed"]["tok"], "norm": params["final_norm"]["scale"],
           "lm_head": params["head"]["w"], "layers": []}
    for lp in params["layers"]:
        a, m = lp["attn"], lp["mlp"]
        lw = {"input_layernorm": lp["attn_norm"]["scale"],
              "post_attention_layernorm": lp["post_attn_norm"]["scale"],
              "pre_mlp_layernorm": lp["mlp_norm"]["scale"],
              "post_mlp_layernorm": lp["post_mlp_norm"]["scale"],
              "self_attn": {"qkv_proj": a["wqkv"], "gate_proj": a["wgate"], "q_norm": a["q_norm"],
                            "k_norm": a["k_norm"], "o_proj": a["wo"]}}
        if "router" in m:
            lw["mlp"] = {"router": m["router"]["w"], "expert_bias": m["router"]["bias"],
                         "experts": {"w1": m["w1"], "w3": m["w3"], "w2": m["w2"]},
                         "shared_experts": {"w13": m["shared"]["w13"], "w2": m["shared"]["w2"]}}
        else:  # a leading dense layer (num_dense_layers): [w1 | w3] in one matrix
            lw["mlp"] = {"w13": m["w13"], "w2": m["w2"]}
        out["layers"].append(lw)
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


def attention(a, aw, cfg, rope, window):
    """A layer's gated attention on (1, s, hidden) -> (1, s, hidden), before the norm
    that follows it.  ``rope``: the (cos, sin) tables or None (no position signal);
    ``window``: 0 or the keys a query sees, its own among them."""
    n, kv, d = _sizes(cfg)
    per = n // kv  # query heads a key/value head
    eps = float(cfg["rms_norm_eps"])
    b, s, hidden = a.shape
    blocks, block = _blocks(s, QUERY_BLOCK)
    # a key/value head's columns of the fused projection and of the gate, its rows of W_o
    wqkv = aw["qkv_proj"].reshape(hidden, kv, (per + 2) * d).transpose(1, 0, 2)
    wgate = aw["gate_proj"].reshape(hidden, kv, per * d).transpose(1, 0, 2)
    wo = aw["o_proj"].reshape(kv, per * d, hidden)
    key_pos = jnp.arange(s)

    def group(acc, args):
        wqkv_g, wgate_g, wo_g = args
        qkv = (a @ wqkv_g).reshape(b, s, per + 2, d)
        q, k, v = qkv[:, :, :per], qkv[:, :, per:per + 1], qkv[:, :, per + 1]
        q, k = rms_norm(q, aw["q_norm"], eps), rms_norm(k, aw["k_norm"], eps)
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
        return acc + (o * jax.nn.sigmoid(a @ wgate_g)) @ wo_g, None

    return jax.lax.scan(group, jnp.zeros_like(a), (wqkv, wgate, wo))[0]


def swiglu(m, w13, w2):
    f = w13.shape[-1] // 2
    gu = m @ w13
    return (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ w2


def route(m, fw, cfg):
    """(tokens, experts) combine weights over ALL the experts the router scores: 0 for
    an expert a token did not choose."""
    k, scale = int(cfg["num_experts_per_tok"]), float(cfg["route_scale"])
    s = jax.nn.sigmoid(m @ fw["router"])
    _, chosen = jax.lax.top_k(s + fw["expert_bias"], k)  # the bias selects, never weighs
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["route_norm"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + NORM_TOPK_EPS)
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(scale * picked)


def moe(m, fw, cfg):
    """The expert layer on (1, s, hidden): the held experts' part of the routed sum
    (``expert_share`` says which experts are held) plus the shared expert, ungated."""
    b, s, hidden = m.shape
    e = fw["experts"]
    held = e["w2"].shape[0]
    share = cfg.get("expert_share") or {"rank": 0, "of": 1}
    first = int(share["rank"]) * held
    blocks, block = _blocks(b * s, TOKEN_BLOCK)
    parts = EXPERT_PARTS if held % EXPERT_PARTS == 0 else 1
    xs = m.reshape(blocks, block, hidden)

    def part(lo, hi):
        w1, w3, w2 = e["w1"][lo:hi], e["w3"][lo:hi], e["w2"][lo:hi]

        def tokens(x):
            # pairs on absent experts: left out; the weights stay as the router made them
            w = route(x, fw, cfg)[:, first + lo:first + hi]
            mid = jax.nn.silu(jnp.einsum("th,ehf->tef", x, w1)) * jnp.einsum(
                "th,ehf->tef", x, w3)
            return jnp.einsum("tef,efh->th", mid * w[:, :, None], w2)

        return jax.lax.map(tokens, xs)

    shared = fw["shared_experts"]
    out = jax.lax.map(lambda x: swiglu(x, shared["w13"], shared["w2"]), xs)
    for i in range(parts):
        out = out + part(i * held // parts, (i + 1) * held // parts)
    return out.reshape(b, s, hidden)


def logits(w, tokens, cfg):
    eps = float(cfg["rms_norm_eps"])
    tables = rope_tables(cfg, tokens.shape[1])
    span = int(cfg["sliding_window"])
    x = w["embed_tokens"][tokens]
    if cfg["mup_enabled"]:
        x = x * math.sqrt(int(cfg["hidden_size"]))
    for i, lw in enumerate(w["layers"]):
        sliding = cfg["layer_types"][i] == "sliding_attention"
        a = rms_norm(x, lw["input_layernorm"], eps)
        y = attention(a, lw["self_attn"], cfg, tables if sliding else None, span if sliding else 0)
        x = x + rms_norm(y, lw["post_attention_layernorm"], eps)
        m = rms_norm(x, lw["pre_mlp_layernorm"], eps)
        fw = lw["mlp"]
        f = moe(m, fw, cfg) if "router" in fw else swiglu(m, fw["w13"], fw["w2"])
        x = x + rms_norm(f, lw["post_mlp_layernorm"], eps)
    b, s, hidden = x.shape
    h = rms_norm(x, w["norm"], eps).reshape(b * s, hidden)  # (rows, hidden): see VOCAB_BLOCK
    head = w["lm_head"]
    parts = [h @ head[:, i:i + VOCAB_BLOCK] for i in range(0, head.shape[1], VOCAB_BLOCK)]
    return jnp.concatenate(parts, axis=-1).reshape(b, s, head.shape[1])


# -- counts ---------------------------------------------------------------------------


def _layers(cfg):
    """(full layers, window layers, dense-MLP layers) of the layers this copy runs."""
    n = int(cfg["num_hidden_layers"])
    win = sum(1 for t in cfg["layer_types"][:n] if t == "sliding_attention")
    return n - win, win, min(n, int(cfg["num_dense_layers"]))


def _share(cfg):
    return int((cfg.get("expert_share") or {"of": 1})["of"])


def _expert_weights(cfg):
    """One expert's three matrices (the shared expert's too: the same width)."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def _token_weights(cfg):
    """Weights a token is multiplied by HERE: (a layer's five projections, gate among
    them; a dense MLP; an expert layer's router over all the experts, the held share's
    even part of the top-4 and the shared expert)."""
    h = int(cfg["hidden_size"])
    n, kv, d = _sizes(cfg)
    experts_all = int(cfg["num_experts"]) * _share(cfg)
    routed = h * experts_all + _expert_weights(cfg) * (
        int(cfg["num_experts_per_tok"]) / _share(cfg) + int(cfg["num_shared_experts"]))
    return (h * (2 * n + 2 * kv) * d + n * d * h, 3 * h * int(cfg["intermediate_size"]), routed)


def _body_weights(cfg):
    """`_token_weights` summed over the layers this copy runs."""
    full, win, dense = _layers(cfg)
    proj, w_dense, routed = _token_weights(cfg)
    return (full + win) * proj + dense * w_dense + (full + win - dense) * routed


def window_pairs(seq_len, window):
    """(query, key) pairs of one sequence in a window layer: query p sees
    ``min(p + 1, window)`` keys."""
    short = min(seq_len, window)
    return short * (short + 1) // 2 + (seq_len - short) * window


def fwd_flops_per_token(cfg, seq_len):
    """Forward model FLOPs a token of the NO-CACHE forward at ``seq_len``: the
    projections, scores and values at 2 x 128 a pair and head (a full layer the causal
    half, a window layer `window_pairs`), the MLPs a token runs HERE, the head."""
    h = int(cfg["hidden_size"])
    n, _, d = _sizes(cfg)
    full, win, _ = _layers(cfg)
    pairs = (full * seq_len * (seq_len + 1) / 2
             + win * window_pairs(seq_len, int(cfg["sliding_window"]))) / seq_len
    return 2.0 * (_body_weights(cfg) + h * int(cfg["vocab_size"])) + 2 * 2.0 * n * d * pairs


def slot_positions(cfg):
    """Positions a serving slot holds: the ``--seq_length`` of ``program_flags`` (the
    published 262,144 positions are what the model allows, not what a slot keeps)."""
    flags = cfg["program_flags"]
    return int(flags[flags.index("--seq_length") + 1])


def position_share(cfg):
    """The least share of a row's live positions a layer of this stack reads or
    multiplies, mean over its layers, at ANY length up to a slot's P positions: a full
    layer all of them, a window layer ``min(n, window) / n >= window / P``.  The cell's
    5 layers at 16,384: (1 + 4 x 4096 / 16384) / 5 = 2 / 5."""
    full, win, _ = _layers(cfg)
    return (full + win * int(cfg["sliding_window"]) / slot_positions(cfg)) / (full + win)


def serve_dims(cfg):
    """This model's served work in the sizes ``lib/flops.py`` counts from, a LOWER bound
    of this stack's work at every length up to the slots' capacity:

    - ``head_dim`` = 128 x `position_share`: a (query, live position) pair then counts
      what the full layers need plus a quarter of what a window layer would need if it
      saw the whole row (it needs ``min(n, 4096) / n`` of that, never less), and a live
      position counts 2 x 8 x 128 x 2/5 x 2 B = 1,638 B a layer, 8,192 B over 5 layers,
      where the exact least is ``4,096 B x (1 + 4 min(n, 4096) / n)``
      (`least_bytes_per_position`; the test holds the bound for every n);
    - ``ffn`` (with ``mlp_matrices`` 1): whatever a token's weights hold beyond the
      formula's four hidden x (heads x hidden // heads) projections, a layer on average:
      the rest of the projections (heads of 128 where 3072 / 48 is 64, the gate), the
      dense MLP, the router, the held share's even part of the top-4, the shared expert.
    """
    h, layers = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    n, kv, d = _sizes(cfg)
    return {"hidden": h, "heads": n, "kv_heads": kv, "head_dim": d * position_share(cfg),
            "ffn": (_body_weights(cfg) / layers - 4 * h * (h // n) * n) / h, "mlp_matrices": 1,
            "layers": layers, "vocab": int(cfg["vocab_size"])}


def least_bytes_per_position(cfg, n, itemsize=2):
    """K and V a decode step must read of a row of ``n`` live positions, over all
    layers, a live position: a full layer all n, a window layer ``min(n, window)``."""
    _, kv, d = _sizes(cfg)
    full, win, _ = _layers(cfg)
    return 2 * kv * d * itemsize * (full + win * min(n, int(cfg["sliding_window"])) / n)


def served_params(cfg):
    """Parameters a forward must read whatever implements it: ``a_forward``, once however
    many tokens it holds: every layer's five projections, two head norms and four norms,
    the dense MLPs, an expert layer's router (matrix and bias over ALL the experts), its
    shared expert and ``num_experts_per_tok`` of the experts this copy holds (the module's
    note: a lower bound of the experts a forward touches, not the 32 held), the final norm
    and the untied head; ``a_token``, once a token: its row of the embedding."""
    h = int(cfg["hidden_size"])
    _, _, d = _sizes(cfg)
    full, win, dense = _layers(cfg)
    proj, w_dense, _ = _token_weights(cfg)
    experts_all = int(cfg["num_experts"]) * _share(cfg)
    touched = min(int(cfg["num_experts"]), int(cfg["num_experts_per_tok"]))
    expert_layer = h * experts_all + experts_all + _expert_weights(cfg) * (
        touched + int(cfg["num_shared_experts"]))
    body = ((full + win) * (proj + 2 * d + 4 * h) + dense * w_dense
            + (full + win - dense) * expert_layer)
    return {"a_forward": body + h + h * int(cfg["vocab_size"]), "a_token": h}


def decode_attn_bytes(cfg, full_live, window_live, new_positions, full_layers, window_layers,
                      itemsize=2):
    """Least HBM bytes of ONE decode step's cached attention, all layers: the positions
    live in the rows read once a layer (a full layer ``full_live`` = the sum of the rows'
    lengths n, a window layer ``window_live`` = the sum of ``min(n, window)``) and the
    step's new positions written once a layer, K and V (2 x 8 x 128 x ``itemsize`` =
    4,096 B a position and layer in bf16).  The weights are ``qkv_proj``'s: left out, so
    a share over this reads low."""
    _, kv, d = _sizes(cfg)
    per = 2 * kv * d * itemsize
    return per * (full_live * full_layers + window_live * window_layers
                  + new_positions * (full_layers + window_layers))


def expert_layers(cfg):
    """Layers of this copy that carry routed experts."""
    full, win, dense = _layers(cfg)
    return full + win - dense


def expert_step_bytes(cfg, touched, itemsize=2):
    """Least HBM bytes of ONE decode step's routed experts, all expert layers: the three
    matrices of the ``touched`` held experts a layer that got a row (the engine's counter
    ``moe_held_experts_touched``, a mean over the expert layers), read once.  Rows in and
    out (a few KB) are left out, so a share over this reads low."""
    return itemsize * touched * expert_layers(cfg) * _expert_weights(cfg)
