"""dots-studio/dots3-note-prev (``model_type`` dots3_note; the language model: the vision
and audio towers and the MTP module are left out), written from the published config's keys
and the layer equations of ISSUE 65.  With ``h = RMSNorm(x)``, eps 1e-5, pre-norm residuals,
no biases:

    full layer (``layer_types[i] == "full_attention"``), 128 heads:
      c_q = RMSNorm_1024(h W_qa);  q = (c_q W_qb) (5120/1024)^1/2     [q_nope 128 | q_rope 64]
      [c | k_r] = h W_kva (512 + 64);  c~ = RMSNorm_512(c) (5120/512)^1/2
      [k_nope 128 | v 128] a head = c~ W_kvb;  rotary (theta 8e7) on q_rope and k_r
      indexer: qI = c_q W_Iq (64 heads of 128); kI = LayerNorm_128(h W_Ik), ONE key a
        position; rotary on the first 64 dims of both; w = h W_Iw (64);
        I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) 64^-1/2 128^-1/2, s <= t, float32
        S_t = the 2,048 positions of largest I[t, .] (all while t + 1 <= 2,048); equal
        scores: the LOWER position first (a stable argsort)
      score = (q_nope . k_nope + q_rope . k_r) 192^-1/2 over S_t only; o = softmax(score) v
      g = sigmoid(h W_g), one scalar a head;  x += concat(g_head o_head) W_o

    sliding layer: the same with the ``swa_*`` sizes (64 heads of 192 + 64 / 128, latent
      1,024 + 64, query rank 1,024, theta 5e4, scale 256^-1/2), NO indexer; query p sees the
      keys j with p - 513 < j <= p

    layer 0:      x += W_2 (silu(W_1 h) * W_3 h)                          width 13824
    layers 1-45:  s = sigmoid(h W_r) (float32, 256); the 8 experts are the top-8 of s + b
                  (b SELECTS only); w_e = s_e / sum_chosen s (x routed_scaling_factor 1);
                  x += sum_e w_e SwiGLU_e(h) + SwiGLU_shared(h)            width 1536

No cache, no kernels: every position's keys and values are expanded from its latent, and
the selection is a MASK over all keys.  Final RMSNorm, untied head.

Departures from the published description, each at its line and in the configuration
file: the held share of the experts (``expert_share``), the vocabulary slice, and the
readings under ``assumed`` (the rescale as LongCat-Flash's two factors, on q after W_qb and
on the normed latent; rotate-half pairing; the indexer reading the unscaled c_q, its
Hadamard rotation left out (orthogonal: it changes no score), its keys kept in the
compute type and not fp8; the headwise gate as one scalar a head from the block's normed
input; no indexer on the sliding layers; the window holding the query's own position; no
expert groups).

As ``sarvam_mla.py``: `published_weights` hands the program's own arrays on, and `logits`
keeps every float32 intermediate to a block (a group of heads and a block of queries of
the attention, a block of tokens of the experts, a block of the head's columns), so that
a request of 20,480 positions fits beside 8.2 GB of weights.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.reference import F32, layer_norm, rms_norm

#: heads a step of the attention takes, queries a step, tokens a step of the experts
HEAD_GROUP, QUERY_BLOCK, TOKEN_BLOCK = 8, 1024, 1024
#: columns of the head multiplied at once (``sarvam_mla.VOCAB_BLOCK`` has the reason)
VOCAB_BLOCK = 32768


def published_weights(params, cfg):
    out = {"embed_tokens": params["embed"]["tok"], "norm": params["final_norm"]["scale"],
           "lm_head": params["head"]["w"], "layers": []}
    for lp in params["layers"]:
        a, m = lp["mla"], lp["mlp"]
        lw = {
            "input_layernorm": lp["attn_norm"]["scale"],
            "q_a_proj": a["wqa"], "q_a_layernorm": a["q_norm"], "q_b_proj": a["wqb"],
            "kv_a_proj_with_mqa": a["wkva"], "kv_a_layernorm": a["kv_norm"],
            "kv_b_proj": a["wkvb"], "o_proj": a["wo"], "gate_proj": a["wgate"],
            "post_attention_layernorm": lp["mlp_norm"]["scale"],
        }
        if "index" in a:
            ix = a["index"]
            lw["indexer"] = {"wq_b": ix["wq"], "wk": ix["wk"], "weights_proj": ix["ww"],
                             "k_norm": (ix["k_norm"]["scale"], ix["k_norm"]["bias"])}
        if "router" in m:
            lw["mlp"] = {
                "gate": m["router"]["w"], "e_score_correction_bias": m["router"]["bias"],
                "experts": {"gate_proj": m["w1"], "up_proj": m["w3"], "down_proj": m["w2"]},
                "shared_experts": {"gate_up_proj": m["shared"]["w13"],
                                   "down_proj": m["shared"]["w2"]},
            }
        else:  # the leading dense layer (first_k_dense_replace)
            lw["mlp"] = {"gate_up_proj": m["w13"], "down_proj": m["w2"]}
        out["layers"].append(lw)
    return out


def _blocks(n, size):
    """``n`` as whole blocks of at most ``size``: (blocks, block)."""
    block = math.gcd(n, size) if n % size else size
    return n // block, block


def layer_sizes(cfg, sliding):
    """(heads, nope, rope, value, latent rank, query rank, theta) of a layer's type."""
    pre = "swa_" if sliding else ""
    return (int(cfg[pre + "num_attention_heads"]), int(cfg[pre + "qk_nope_head_dim"]),
            int(cfg[pre + "qk_rope_head_dim"]), int(cfg[pre + "v_head_dim"]),
            int(cfg[pre + "kv_lora_rank"]), int(cfg[pre + "q_lora_rank"]),
            float(cfg[pre + "rope_theta"]))


def rope_tables(d, theta, seq_len):
    """(cos, sin), each (seq_len, d / 2): plain rotary, no scaling (``rope_scaling`` null)."""
    ang = np.outer(np.arange(seq_len), theta ** (-np.arange(0, d, 2) / d))
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def _rotate(x, cos, sin):
    """Rotate-half on the last axis of (b, s, ..., d); cos, sin (s, d / 2).  (Assumed: the
    pairing (i, i + d/2); with seeded weights another is a permutation of columns.)"""
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (cos.shape[-1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def index_scores(h, c_q, ix, cfg, cos, sin):
    """The indexer's float32 scores I (s, s) of ONE sequence ``h`` (1, s, hidden), ``-inf``
    where the key lies after the query; a block of queries at a time."""
    hi, di = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    dr = cos.shape[-1] * 2
    s = h.shape[1]
    # (assumed: the UNSCALED c_q; the published Hadamard rotation of qI and kI left out)
    qi = (c_q @ ix["wq_b"]).reshape(1, s, hi, di)
    qi = jnp.concatenate([_rotate(qi[..., :dr], cos, sin), qi[..., dr:]], axis=-1)
    ki = layer_norm(h @ ix["wk"], ix["k_norm"], float(cfg["rms_norm_eps"]))
    ki = jnp.concatenate([_rotate(ki[..., :dr], cos, sin), ki[..., dr:]], axis=-1)[0]  # (s, di)
    w = (h @ ix["weights_proj"])[0] * (hi ** -0.5 * di ** -0.5)  # (s, hi)
    blocks, block = _blocks(s, QUERY_BLOCK)
    key_pos = jnp.arange(s)

    def queries(i):
        at = i * block + jnp.arange(block)
        dots = jnp.einsum("qjd,kd->qjk", qi[0, at], ki)
        scores = jnp.einsum("qjk,qj->qk", jax.nn.relu(dots), w[at])
        return jnp.where(key_pos[None, :] <= at[:, None], scores, -jnp.inf)

    return jax.lax.map(queries, jnp.arange(blocks)).reshape(s, s)


def selected_keys(scores, topk):
    """(s, s) bool: the ``topk`` keys a query attends, by a plain stable ``argsort`` of its
    scores (largest first, equal scores the lower position first), a block of queries at a
    time; every key at or before it while there are no more than ``topk``."""
    s = scores.shape[0]
    allowed = scores > -jnp.inf
    if topk >= s:
        return allowed
    blocks, block = _blocks(s, QUERY_BLOCK)

    def queries(rows):
        order = jnp.argsort(-rows, axis=-1, stable=True)[:, :topk]
        return jnp.zeros(rows.shape, bool).at[jnp.arange(rows.shape[0])[:, None], order].set(True)

    return jax.lax.map(queries, scores.reshape(blocks, block, s)).reshape(s, s) & allowed


def attention(h, lw, cfg, sliding, tables):
    """A layer's attention on (1, s, hidden) -> (1, s, hidden)."""
    n, dn, dr, dv, r, rq, _ = layer_sizes(cfg, sliding)
    eps = float(cfg["rms_norm_eps"])
    b, s, hidden = h.shape
    cos, sin = tables
    rescale = bool(cfg["apply_mla_qkv_lora_rescale"])
    c_q = rms_norm(h @ lw["q_a_proj"], lw["q_a_layernorm"], eps)
    ckr = h @ lw["kv_a_proj_with_mqa"]
    latent = rms_norm(ckr[..., :r], lw["kv_a_layernorm"], eps)
    if rescale:  # (assumed: LongCat-Flash's mla_scale_kv_lora, on the NORMED latent)
        latent = latent * (hidden / r) ** 0.5
    k_rope = _rotate(ckr[..., r:], cos, sin)  # (b, s, dr): one key for all heads
    key_pos = jnp.arange(s)
    if sliding:
        window = int(cfg["sliding_window_size"])  # (assumed: the query's own position inside)
        seen = ((key_pos[None, :] <= key_pos[:, None])
                & (key_pos[None, :] > key_pos[:, None] - window))[None]
    else:  # (a sequence at a time: (b, s, s))
        seen = jax.vmap(lambda h1, c1: selected_keys(
            index_scores(h1[None], c1[None], lw["indexer"], cfg, cos, sin),
            int(cfg["index_topk"])))(h, c_q)
    # (assumed: "headwise" = one scalar a head from the block's normed input)
    gate = jax.nn.sigmoid(h @ lw["gate_proj"])  # (b, s, n)
    scale = (dn + dr) ** -0.5
    groups, heads = _blocks(n, HEAD_GROUP)
    blocks, block = _blocks(s, QUERY_BLOCK)
    wq = lw["q_b_proj"].reshape(rq, groups, heads * (dn + dr)).transpose(1, 0, 2)
    wkvb = lw["kv_b_proj"].reshape(r, groups, heads * (dn + dv)).transpose(1, 0, 2)
    wo = lw["o_proj"].reshape(groups, heads * dv, hidden)
    gates = gate.reshape(b, s, groups, heads).transpose(2, 0, 1, 3)

    def group(acc, args):
        wq_g, wkvb_g, wo_g, g = args
        q = c_q @ wq_g
        if rescale:  # (assumed: mla_scale_q_lora, on q after W_qb)
            q = q * (hidden / rq) ** 0.5
        q = q.reshape(b, s, heads, dn + dr)
        q_nope, q_rope = q[..., :dn], _rotate(q[..., dn:], cos, sin)
        kv = (latent @ wkvb_g).reshape(b, s, heads, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]

        def queries(i):
            at = i * block + jnp.arange(block)
            scores = (jnp.einsum("bqnd,bknd->bnqk", q_nope[:, at], k_nope)
                      + jnp.einsum("bqnd,bkd->bnqk", q_rope[:, at], k_rope)) * scale
            scores = jnp.where(seen[:, at][:, None], scores, -jnp.inf)
            return jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, axis=-1), v)

        o = jax.lax.map(queries, jnp.arange(blocks))  # (blocks, b, block, heads, dv)
        o = jnp.moveaxis(o, 0, 1).reshape(b, s, heads, dv) * g[..., None]
        return acc + o.reshape(b, s, heads * dv) @ wo_g, None

    return jax.lax.scan(group, jnp.zeros_like(h), (wq, wkvb, wo, gates))[0]


def swiglu(h, gate_up, down):
    f = gate_up.shape[-1] // 2
    gu = h @ gate_up
    return (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ down


def route(h, mw, cfg):
    """(tokens, experts) combine weights over ALL the experts the router scores: 0 for an
    expert a token did not choose.  (Assumed: no ``n_group`` key, so no grouping.)"""
    k, scale = int(cfg["num_experts_per_tok"]), float(cfg["routed_scaling_factor"])
    s = jax.nn.sigmoid(h @ mw["gate"])
    _, chosen = jax.lax.top_k(s + mw["e_score_correction_bias"], k)  # the bias selects only
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = scale * picked / jnp.sum(picked, axis=-1, keepdims=True)  # norm_topk_prob
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(weights)


def moe(h, mw, cfg):
    """The expert layer on (1, s, hidden): the held experts' part of the routed sum plus
    the shared expert; ``expert_share`` says which experts are held (a departure: pairs on
    experts this copy does not hold are left out of the sum, as in the program)."""
    b, s, hidden = h.shape
    flat = h.reshape(b * s, hidden)
    held = mw["experts"]["down_proj"].shape[0]
    share = cfg.get("expert_share") or {"rank": 0, "of": 1}
    first = int(share["rank"]) * held
    blocks, block = _blocks(b * s, TOKEN_BLOCK)

    def tokens(x):
        w = route(x, mw, cfg)[:, first:first + held]
        e = mw["experts"]
        mid = jax.nn.silu(jnp.einsum("th,ehf->tef", x, e["gate_proj"])) * jnp.einsum(
            "th,ehf->tef", x, e["up_proj"])
        routed = jnp.einsum("tef,efh->th", mid * w[:, :, None], e["down_proj"])
        sh = mw["shared_experts"]
        return routed + swiglu(x, sh["gate_up_proj"], sh["down_proj"])

    out = jax.lax.map(tokens, flat.reshape(blocks, block, hidden))
    return out.reshape(b, s, hidden)


def _sliding(cfg):
    """Of each layer this copy runs: it is a sliding layer."""
    n = int(cfg["num_hidden_layers"])
    return [t == "sliding_attention" for t in cfg["layer_types"][:n]]


def logits(w, tokens, cfg):
    eps = float(cfg["rms_norm_eps"])
    s = tokens.shape[1]
    tables = {sl: rope_tables(layer_sizes(cfg, sl)[2], layer_sizes(cfg, sl)[6], s)
              for sl in (False, True)}
    x = w["embed_tokens"][tokens]
    for lw, sliding in zip(w["layers"], _sliding(cfg)):
        x = x + attention(rms_norm(x, lw["input_layernorm"], eps), lw, cfg, sliding,
                          tables[sliding])
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


# -- counts -----------------------------------------------------------------------------


def _layers(cfg):
    """(full layers, sliding layers, dense-MLP layers) of the layers this copy runs."""
    sliding = _sliding(cfg)
    return (len(sliding) - sum(sliding), sum(sliding),
            min(len(sliding), int(cfg["first_k_dense_replace"])))


def _share(cfg):
    return int((cfg.get("expert_share") or {"of": 1})["of"])


def _expert_weights(cfg):
    """One expert's three matrices (the shared expert's too: the same width)."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def mixer_weights(cfg, sliding):
    """A layer's attention matrices (its three norms' vectors left out): the query's two,
    W_kva, W_kvb, W_o, the gate and, on a full layer, the indexer's three."""
    h = int(cfg["hidden_size"])
    n, dn, dr, dv, r, rq, _ = layer_sizes(cfg, sliding)
    count = h * rq + rq * n * (dn + dr) + h * (r + dr) + r * n * (dn + dv) + n * dv * h + h * n
    if not sliding:
        hi, di = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
        count += rq * hi * di + h * di + h * hi
    return count


def mixer_vectors(cfg, sliding):
    """The norms' vectors of a layer's attention: q_a, kv_a and, on a full layer, the
    index key's LayerNorm (scale and bias)."""
    _, _, _, _, r, rq, _ = layer_sizes(cfg, sliding)
    return rq + r + (0 if sliding else 2 * int(cfg["index_head_dim"]))


def _body_weights(cfg):
    """Weights a token is multiplied by HERE over the layers this copy runs: the mixers,
    the dense MLP, of an expert layer the router over all the experts, the held share's
    even part of the top-8 and the shared expert."""
    h = int(cfg["hidden_size"])
    full, win, dense = _layers(cfg)
    experts_all = int(cfg["n_routed_experts"]) * _share(cfg)
    routed = h * experts_all + _expert_weights(cfg) * (
        int(cfg["num_experts_per_tok"]) / _share(cfg) + int(cfg["n_shared_experts"]))
    return (full * mixer_weights(cfg, False) + win * mixer_weights(cfg, True)
            + dense * 3 * h * int(cfg["intermediate_size"]) + (full + win - dense) * routed)


def window_pairs(seq_len, window):
    """(query, key) pairs of one sequence under a window (or a selection) of ``window``:
    query p sees ``min(p + 1, window)`` keys."""
    short = min(seq_len, window)
    return short * (short + 1) // 2 + (seq_len - short) * window


def fwd_flops_per_token(cfg, seq_len):
    """Forward model FLOPs a token of the NO-CACHE forward at ``seq_len``: the projections,
    the indexer's scores of every causal pair (2 x 64 x 128), scores and values of the
    pairs a query attends (a full layer its selection, a sliding layer its window), the
    MLPs a token runs HERE, the head."""
    h = int(cfg["hidden_size"])
    full, win, _ = _layers(cfg)
    nf, dnf, drf, dvf = layer_sizes(cfg, False)[:4]
    nw, dnw, drw, dvw = layer_sizes(cfg, True)[:4]
    index = 2.0 * int(cfg["index_n_heads"]) * int(cfg["index_head_dim"])
    pairs = (full * (index * seq_len * (seq_len + 1) / 2
                     + 2.0 * nf * (dnf + drf + dvf) * window_pairs(seq_len, int(cfg["index_topk"])))
             + win * 2.0 * nw * (dnw + drw + dvw)
             * window_pairs(seq_len, int(cfg["sliding_window_size"]))) / seq_len
    return 2.0 * (_body_weights(cfg) + h * int(cfg["vocab_size"])) + pairs


def slot_positions(cfg):
    """Positions a serving slot holds: the ``--seq_length`` of ``program_flags``."""
    flags = cfg["program_flags"]
    return int(flags[flags.index("--seq_length") + 1])


def position_bytes(cfg, sliding, itemsize=2):
    """(a position's latent, its index key) of a layer's type, bytes."""
    _, _, dr, _, r, _, _ = layer_sizes(cfg, sliding)
    return (r + dr) * itemsize, 0 if sliding else int(cfg["index_head_dim"]) * itemsize


def least_bytes_per_position(cfg, n, itemsize=2):
    """Cache bytes a decode step must read of a row of ``n`` live positions, over all
    layers, a live position: a full layer every index key and ``min(n, index_topk)``
    latents, a sliding layer ``min(n, window)`` latents."""
    full, win, _ = _layers(cfg)
    lat_f, idx = position_bytes(cfg, False, itemsize)
    lat_w, _ = position_bytes(cfg, True, itemsize)
    return (full * (idx + lat_f * min(n, int(cfg["index_topk"])) / n)
            + win * lat_w * min(n, int(cfg["sliding_window_size"])) / n)


def least_flops_per_pair(cfg, n):
    """Attention FLOPs a decode step must spend a live position of a row of ``n``, over
    all layers: a full layer the indexer's score of every position and scores and values
    of ``min(n, index_topk)``, a sliding layer of ``min(n, window)``."""
    full, win, _ = _layers(cfg)
    nf, dnf, drf, dvf = layer_sizes(cfg, False)[:4]
    nw, dnw, drw, dvw = layer_sizes(cfg, True)[:4]
    index = 2.0 * int(cfg["index_n_heads"]) * int(cfg["index_head_dim"])
    return (full * (index + 2.0 * nf * (dnf + drf + dvf) * min(n, int(cfg["index_topk"])) / n)
            + win * 2.0 * nw * (dnw + drw + dvw) * min(n, int(cfg["sliding_window_size"])) / n)


def serve_dims(cfg):
    """This model's served work in the sizes ``lib/flops.py`` counts from (a dense K/V
    decoder's), a LOWER bound of this stack's work at every length up to the slots'
    capacity P (the test holds both bounds for every n):

    - ``head_dim``: the formula's 4 x heads x head_dim x layers FLOPs a (query, live
      position) pair set to `least_flops_per_pair` at n = P, where the shares a full
      layer's selection and a sliding layer's window keep are smallest;
    - ``kv_heads``: the formula's 2 x layers x kv_heads x head_dim x 2 B a live position
      set to `least_bytes_per_position` at n = P: every index key, 2,048 / P of the full
      layers' latents, 513 / P of the sliding layers';
    - ``ffn`` (with ``mlp_matrices`` 1): whatever a token's weights hold beyond the
      formula's four hidden x (heads x hidden // heads) projections, a layer on average.
    """
    h, layers = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    n, p = int(cfg["num_attention_heads"]), slot_positions(cfg)
    head_dim = least_flops_per_pair(cfg, p) / (4.0 * n * layers)
    return {"hidden": h, "heads": n, "head_dim": head_dim,
            "kv_heads": least_bytes_per_position(cfg, p) / (2 * layers * head_dim * 2),
            "ffn": (_body_weights(cfg) / layers - 4 * h * (h // n) * n) / h, "mlp_matrices": 1,
            "layers": layers, "vocab": int(cfg["vocab_size"])}


def served_params(cfg):
    """Parameters a forward must read whatever implements it: ``a_forward``, once however
    many tokens it holds: every layer's attention matrices and norm vectors, its two block
    norms, the dense MLP, an expert layer's router (matrix and bias over ALL the experts),
    its shared expert and ``num_experts_per_tok`` of the experts this copy holds (the most
    ONE token's forward can touch: a lower bound of the experts a forward touches, not the
    32 held: ``afmoe.py``'s rule), the final norm and the untied head; ``a_token``, once a
    token: its row of the embedding."""
    h = int(cfg["hidden_size"])
    full, win, dense = _layers(cfg)
    experts_all = int(cfg["n_routed_experts"]) * _share(cfg)
    touched = min(int(cfg["n_routed_experts"]), int(cfg["num_experts_per_tok"]))
    expert_layer = h * experts_all + experts_all + _expert_weights(cfg) * (
        touched + int(cfg["n_shared_experts"]))
    body = (full * (mixer_weights(cfg, False) + mixer_vectors(cfg, False) + 2 * h)
            + win * (mixer_weights(cfg, True) + mixer_vectors(cfg, True) + 2 * h)
            + dense * 3 * h * int(cfg["intermediate_size"])
            + (full + win - dense) * expert_layer)
    return {"a_forward": body + h + h * int(cfg["vocab_size"]), "a_token": h}


def expert_layers(cfg):
    """Layers of this copy that carry routed experts."""
    full, win, dense = _layers(cfg)
    return full + win - dense


def expert_step_bytes(cfg, touched, itemsize=2):
    """Least HBM bytes of ONE decode step's routed experts, all expert layers: the three
    matrices of the ``touched`` held experts a layer that got a row (the engine's counter
    ``moe_held_experts_touched``, a mean over the expert layers), read once."""
    return itemsize * touched * expert_layers(cfg) * _expert_weights(cfg)


def dsa_step_bytes(cfg, live, selected, new, full_layers, itemsize=2):
    """Least HBM bytes of ONE decode step's sparse attention, all full layers, whatever
    implements it: the index key of every LIVE position read (the scores need each), the
    SELECTED positions' latents read, the step's ``new`` positions' latent and index key
    written.  The weights are ``qkv_proj``'s and the indexer's projections': left out, so
    a share over this reads low, never high."""
    lat, idx = position_bytes(cfg, False, itemsize)
    return full_layers * (live * idx + selected * lat + new * (lat + idx))
