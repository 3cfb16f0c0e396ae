"""sarvamai/sarvam-105b (``model_type`` sarvam_mla), written from the published
config's keys and the layer equations of ISSUE 51 (DeepSeek-V2's latent attention
without ``q_lora_rank``; a sigmoid router with a selection bias).  With
``h = RMSNorm(x)``, eps 1e-6, no biases anywhere:

    q = h W_q                          64 heads of [q_nope 128 | q_rope 64]
    [c | k_r] = h W_kva                512 + 64;  c~ = RMSNorm_512(c)
    [k_nope | v] = c~ W_kvb            64 heads of 128 + 128
    q_rope, k_r rotated by the YaRN table (k_r is ONE key shared by all heads)
    score = (q_nope . k_nope + q_rope . k_r) 192^-1/2 m^2,  m = 0.1 ln 40 + 1
    o = softmax_causal(score) v;  x += concat(o) W_o

    layer 0:       x += W_2 (silu(W_1 h) * W_3 h)                     width 16384
    layers 1-31:   s = sigmoid(h W_r) (float32, 128); the 8 experts are the top-8
                   of s + b (b SELECTS only); w_e = 2.5 s_e / sum_chosen s;
                   x += sum_e w_e SwiGLU_e(h) + SwiGLU_shared(h)       width 2048

    YaRN: pair i of the 64 rotary dims turns at theta^(-2i/64), blended with the
    same over 40 by a linear ramp between the pairs that make 32 turns and 1
    turn over 4096 positions (floored / ceiled); the table's own factor
    m(mscale) / m(mscale_all_dim) is 1.

This is the NON-absorbed form with no cache: every position's keys and values are
expanded from its latent.  Final RMSNorm, untied head.

Departures from the published description, all in the configuration file: the
held share of the experts (``expert_share``: pairs on experts this copy does not
hold are left out of the sum, as in the program; the router scores all 128), the
vocabulary slice, and the three readings under ``assumed`` (the latent's RMSNorm
as the only q/k norm; sigmoid scores renormalised over the chosen before the 2.5;
rotate-half pairing of the rotary dims).

Two things the serve runner forces (``lib/serve.compare_rows`` runs this ONCE over
16,384 positions beside 9 GB of weights): `published_weights` hands the program's
own arrays on (no re-laid-out copy), and `logits` keeps every float32 intermediate
to a block: attention a group of heads and a block of queries at a time, the
experts a block of tokens at a time, each under ``jax.lax.map`` so that one
block's scores are dead before the next one's are made, and the head a block of
the vocabulary's columns at a time (``VOCAB_BLOCK``).

``lib/flops.py``'s served counts are written in a dense K/V decoder's sizes;
``serve_dims`` below states THIS model's work in them (its weights a token, its
widths a pair, its latent a position), so that the serving cell's three shares of
the chip's peaks are read here as in every serving cell.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.reference import F32, rms_norm

#: heads a step of the attention takes, queries a step, tokens a step of the experts
HEAD_GROUP, QUERY_BLOCK, TOKEN_BLOCK = 8, 1024, 1024
#: columns of the head multiplied at once: the runner gathers the compared rows out
#: of the (positions, vocabulary) logits, and this chip's compiler gathers from a
#: 4 GiB float32 array through two 2 GiB copies of it (8.0 GiB of temporaries,
#: compiled for a described v5e) but from two 2 GiB halves of a (rows, vocabulary)
#: matrix in place (2.2 GiB; not so from a (1, rows, vocabulary) array)
VOCAB_BLOCK = 32768


def published_weights(params, cfg):
    out = {"embed_tokens": params["embed"]["tok"], "norm": params["final_norm"]["scale"],
           "lm_head": params["head"]["w"], "layers": []}
    for lp in params["layers"]:
        a, m = lp["mla"], lp["mlp"]
        lw = {
            "input_layernorm": lp["attn_norm"]["scale"],
            "q_proj": a["wq"], "kv_a_proj_with_mqa": a["wkva"], "kv_a_layernorm": a["kv_norm"],
            "kv_b_proj": a["wkvb"], "o_proj": a["wo"],
            "post_attention_layernorm": lp["mlp_norm"]["scale"],
        }
        if "router" in m:
            lw["mlp"] = {
                "gate": m["router"]["w"], "expert_bias": m["router"]["bias"],
                "experts": {"gate_proj": m["w1"], "up_proj": m["w3"], "down_proj": m["w2"]},
                "shared_experts": {"gate_up_proj": m["shared"]["w13"],
                                   "down_proj": m["shared"]["w2"]},
            }
        else:  # a leading dense layer (first_k_dense_replace)
            lw["mlp"] = {"gate_up_proj": m["w13"], "down_proj": m["w2"]}
        out["layers"].append(lw)
    return out


def _blocks(n, size):
    """``n`` as whole blocks of at most ``size``: (blocks, block)."""
    block = math.gcd(n, size) if n % size else size
    return n // block, block


def yarn_tables(cfg, seq_len):
    """(cos, sin), each (seq_len, d / 2), of the ``deepseek_yarn`` rotary table."""
    d, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    y = cfg["rope_scaling"]
    factor, original = float(y["factor"]), float(y["original_max_position_embeddings"])
    extra = theta ** (-np.arange(0, d, 2) / d)

    def pair_of(turns):
        return d * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(float(y["beta_fast"]))), 0)
    high = min(math.ceil(pair_of(float(y["beta_slow"]))), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = extra / factor * ramp + extra * (1.0 - ramp)
    ang = np.outer(np.arange(seq_len), inv)
    own = _mscale(factor, float(y["mscale"])) / _mscale(factor, float(y["mscale_all_dim"]))
    return jnp.asarray(np.cos(ang) * own, F32), jnp.asarray(np.sin(ang) * own, F32)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rotate(x, cos, sin):
    """Rotate-half on the last axis of (b, s, ..., d); cos, sin (s, d / 2)."""
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (cos.shape[-1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(h, lw, cfg, cos, sin):
    """The layer's attention on (1, s, hidden) -> (1, s, hidden)."""
    n, dn, dr = (int(cfg[k]) for k in ("num_attention_heads", "qk_nope_head_dim",
                                       "qk_rope_head_dim"))
    dv, r = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
    y = cfg["rope_scaling"]
    m = _mscale(float(y["factor"]), float(y["mscale_all_dim"]))
    scale = (dn + dr) ** -0.5 * m * m
    b, s, hidden = h.shape
    ckr = h @ lw["kv_a_proj_with_mqa"]
    latent = rms_norm(ckr[..., :r], lw["kv_a_layernorm"], float(cfg["rms_norm_eps"]))
    k_rope = _rotate(ckr[..., r:], cos, sin)  # (b, s, dr): one key for all heads
    groups, heads = _blocks(n, HEAD_GROUP)
    blocks, block = _blocks(s, QUERY_BLOCK)
    # a group's columns of W_q and W_kvb, its rows of W_o
    wq = lw["q_proj"].reshape(hidden, groups, heads * (dn + dr)).transpose(1, 0, 2)
    wkvb = lw["kv_b_proj"].reshape(r, groups, heads * (dn + dv)).transpose(1, 0, 2)
    wo = lw["o_proj"].reshape(groups, heads * dv, hidden)
    key_pos = jnp.arange(s)

    def group(acc, args):
        wq_g, wkvb_g, wo_g = args
        q = (h @ wq_g).reshape(b, s, heads, dn + dr)
        q_nope, q_rope = q[..., :dn], _rotate(q[..., dn:], cos, sin)
        kv = (latent @ wkvb_g).reshape(b, s, heads, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]

        def queries(i):
            at = i * block + jnp.arange(block)
            qn, qr = q_nope[:, at], q_rope[:, at]
            scores = (jnp.einsum("bqnd,bknd->bnqk", qn, k_nope)
                      + jnp.einsum("bqnd,bkd->bnqk", qr, k_rope)) * scale
            scores = jnp.where(key_pos[None, None, None, :] <= at[None, None, :, None],
                               scores, -jnp.inf)
            return jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, axis=-1), v)

        o = jax.lax.map(queries, jnp.arange(blocks))  # (blocks, b, block, heads, dv)
        o = jnp.moveaxis(o, 0, 1).reshape(b, s, heads * dv)
        return acc + o @ wo_g, None

    return jax.lax.scan(group, jnp.zeros_like(h), (wq, wkvb, wo))[0]


def swiglu(h, gate_up, down):
    f = gate_up.shape[-1] // 2
    gu = h @ gate_up
    return (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ down


def route(h, mw, cfg):
    """(tokens, experts) combine weights over ALL the experts the router scores:
    0 for an expert a token did not choose."""
    k, scale = int(cfg["num_experts_per_tok"]), float(cfg["routed_scaling_factor"])
    s = jax.nn.sigmoid(h @ mw["gate"])
    _, chosen = jax.lax.top_k(s + mw["expert_bias"], k)  # the bias selects, never weighs
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = scale * picked / jnp.sum(picked, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(weights)


def moe(h, mw, cfg):
    """The expert layer on (1, s, hidden): the held experts' part of the routed sum
    plus the shared expert; ``expert_share`` says which experts are held."""
    b, s, hidden = h.shape
    flat = h.reshape(b * s, hidden)
    held = mw["experts"]["down_proj"].shape[0]
    share = cfg.get("expert_share") or {"rank": 0, "of": 1}
    first = int(share["rank"]) * held
    blocks, block = _blocks(b * s, TOKEN_BLOCK)

    def tokens(x):
        w = route(x, mw, cfg)[:, first:first + held]  # pairs on absent experts: left out
        e = mw["experts"]
        mid = jax.nn.silu(jnp.einsum("th,ehf->tef", x, e["gate_proj"])) * jnp.einsum(
            "th,ehf->tef", x, e["up_proj"])
        routed = jnp.einsum("tef,efh->th", mid * w[:, :, None], e["down_proj"])
        sh = mw["shared_experts"]
        return routed + swiglu(x, sh["gate_up_proj"], sh["down_proj"])

    out = jax.lax.map(tokens, flat.reshape(blocks, block, hidden))
    return out.reshape(b, s, hidden)


def logits(w, tokens, cfg):
    eps = float(cfg["rms_norm_eps"])
    cos, sin = yarn_tables(cfg, tokens.shape[1])
    x = w["embed_tokens"][tokens]
    for lw in w["layers"]:
        x = x + attention(rms_norm(x, lw["input_layernorm"], eps), lw, cfg, cos, sin)
        h = rms_norm(x, lw["post_attention_layernorm"], eps)
        mw = lw["mlp"]
        if "gate" in mw:
            x = x + moe(h, mw, cfg)
        else:
            x = x + swiglu(h, mw["gate_up_proj"], mw["down_proj"])
    b, s, hidden = x.shape
    h = rms_norm(x, w["norm"], eps).reshape(b * s, hidden)  # (rows, hidden): see VOCAB_BLOCK
    head = w["lm_head"]
    parts = [h @ head[:, i:i + VOCAB_BLOCK] for i in range(0, head.shape[1], VOCAB_BLOCK)]
    return jnp.concatenate(parts, axis=-1).reshape(b, s, head.shape[1])


def _token_weights(cfg):
    """Weights a token is multiplied by HERE: (the four projections of a layer, the
    MLPs over all layers: the dense layers; of an expert layer the router, the
    shared expert and the held share's even part of the top-k)."""
    h, n = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    dn, dr, dv, r = (int(cfg[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                           "v_head_dim", "kv_lora_rank"))
    layers, dense = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    proj = h * n * (dn + dr) + h * (r + dr) + r * n * (dn + dv) + n * dv * h
    f = int(cfg["moe_intermediate_size"])
    share = (cfg.get("expert_share") or {"of": 1})["of"]
    experts_all = int(cfg["num_experts"]) * int(share)
    routed = h * experts_all + 3 * h * f * (int(cfg["num_experts_per_tok"]) / int(share)
                                            + int(cfg["num_shared_experts"]))
    return proj, dense * 3 * h * int(cfg["intermediate_size"]) + (layers - dense) * routed


def fwd_flops_per_token(cfg, seq_len):
    """Forward model FLOPs a token of the NO-CACHE forward at ``seq_len``: the
    projections (every position's keys and values expanded once), causal scores at
    dn + dr and values at dv a pair and head, the MLPs a token runs HERE
    (``_token_weights``), the head."""
    h, n, layers = (int(cfg[k]) for k in ("hidden_size", "num_attention_heads",
                                          "num_hidden_layers"))
    proj, mlp = _token_weights(cfg)
    width = sum(int(cfg[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    attn = 2.0 * n * width * (seq_len + 1) / 2
    return 2.0 * (layers * proj + mlp + h * int(cfg["vocab_size"])) + layers * attn


def serve_dims(cfg):
    """This model's served work in the sizes ``lib/flops.py`` counts from.  Its
    formulas are a dense K/V decoder's, so three sizes are EQUIVALENTS, each the one
    number at which a formula gives this model's count (the hand count in
    ``tests/benchmark/test_benchmark_sarvam.py`` holds them to it):

    - ``head_dim`` 160 = (dn + dr + dv) / 2: a (query, key) pair costs a head
      2 (dn + dr) for the score and 2 dv for the value, where the formula has
      2 x 2 head_dim.  The NON-absorbed widths: the least the model needs (the
      absorbed decode form pays 576 + 512 a pair to keep the latent narrow);
    - ``kv_heads`` 1.8 = (r + dr) / (2 head_dim): a position's cache is one latent
      of r + dr a layer (1,152 B in bf16), where the formula has K and V of
      ``kv_heads`` heads;
    - ``ffn`` (with ``mlp_matrices`` 1): whatever a token's weights hold beyond the
      formula's four hidden x hidden projections, a layer on average: the rest of
      the MLA projections (94.6 M against 67.1 M) and the MLPs of ``_token_weights``.
    """
    h, n, layers = (int(cfg[k]) for k in ("hidden_size", "num_attention_heads",
                                          "num_hidden_layers"))
    dn, dr, dv, r = (int(cfg[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                           "v_head_dim", "kv_lora_rank"))
    proj, mlp = _token_weights(cfg)
    head_dim = (dn + dr + dv) / 2
    return {"hidden": h, "heads": n, "kv_heads": (r + dr) / (2 * head_dim), "head_dim": head_dim,
            "ffn": (proj - 4 * h * (h // n) * n + mlp / layers) / h, "mlp_matrices": 1,
            "layers": layers, "vocab": int(cfg["vocab_size"])}


def served_params(cfg):
    """Parameters a forward must read whatever implements it: ``a_forward``, once
    however many tokens it holds: every layer's projections and norms, the dense
    MLPs, an expert layer's router (matrix and bias over ALL the experts), shared
    expert and every expert this copy HOLDS, the final norm and the untied head;
    ``a_token``, once a token: its row of the embedding.  The cell: 4,266,966,016
    and 4,096 (with the 65,536 x 4,096 table the program's 4,535,401,472).  A decode
    step of 32 tokens (64 pairs on 32 held experts) may leave an expert untouched;
    they are counted all the same, as the parameters of a forward, so the share
    reads a prompt chunk's bytes exactly and a sparse step's by the convention."""
    h, n, layers = (int(cfg[k]) for k in ("hidden_size", "num_attention_heads",
                                          "num_hidden_layers"))
    dense, f = int(cfg["first_k_dense_replace"]), int(cfg["moe_intermediate_size"])
    proj, _ = _token_weights(cfg)
    share = (cfg.get("expert_share") or {"of": 1})["of"]
    experts_all = int(cfg["num_experts"]) * int(share)
    expert_layer = (h * experts_all + experts_all
                    + 3 * h * f * (int(cfg["num_experts"]) + int(cfg["num_shared_experts"])))
    per_layer = proj + 2 * h + int(cfg["kv_lora_rank"])  # two norms and the latent's
    return {"a_forward": layers * per_layer + dense * 3 * h * int(cfg["intermediate_size"])
            + (layers - dense) * expert_layer + h + h * int(cfg["vocab_size"]), "a_token": h}
