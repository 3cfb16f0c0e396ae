"""Qwen/Qwen3-Next-80B-A3B-Instruct (HF ``modeling_qwen3_next.py``, ``model_type``
qwen3_next) from its published config: a pre-norm stack whose layer ``l`` is
gated softmax attention where ``(l + 1) % full_attention_interval == 0`` and a
Gated DeltaNet mixer elsewhere, each followed by the sparse expert layer.

    x <- x + Mixer(N(x));  x <- x + MoE(N'(x));  logits = N_f(x_L) W_head     (untied)

Every norm over the hidden width and over a head of q or k is the zero-centred
RMSNorm ``N(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)``.

Gated attention: ``q_proj`` gives per head ``[q | gate]``; ``q <- N_q(q)``,
``k <- N_k(k)`` over the ``head_dim`` of EACH head (one weight vector each);
rotate-half rotary on the first ``partial_rotary_factor * head_dim`` dims of each
head, base ``rope_theta``; query head i reads key/value head ``i // (heads /
kv_heads)``; ``softmax(q k^T / sqrt(head_dim) + causal) v``;
``o_proj(attn * sigmoid(gate))``. A block of queries at a time.

Gated DeltaNet: ``in_proj_qkvz`` (per KEY head ``[q | k | v x R | z x R]``, R =
value heads a key head) and ``in_proj_ba`` (per key head ``[b x R | a x R]``);
``[q | k | v]`` flat through a depthwise causal conv of ``linear_conv_kernel_dim``
taps, no bias, then SiLU; ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a +
dt_bias)``; q and k L2-normalised over their head (eps 1e-6), ``q / sqrt(d_k)``;
a value head's state S (d_k x d_v), one position at a time (``lax.scan`` over
the sequence: the RECURRENT form, which shares nothing with the program's
chunked algorithm)::

    S <- exp(g_t) S;  r_t = v_t - S^T k_t;  S <- S + k_t (beta_t r_t)^T;  o_t = S^T q_t

then ``out_proj(w * o * rsqrt(mean(o^2) + eps) * silu(z))`` over the d_v of each
value head (norm before the gate, plain weight).

Expert layer: ``p = softmax_float32(x W_g)`` over ALL published experts; the
``num_experts_per_tok`` largest are a token's choices, their weights
renormalised to sum 1 (``norm_topk_prob``); ``y = sum_{j held} w_j E_j(x) +
sigmoid(x w_s) * E_shared(x)``, every E ``down(silu(gate x) * up x)``. HELD
EXPERTS: ``cfg["expert_share"]`` = rank r of R says this copy holds experts
``[r E / R, (r + 1) E / R)``; a choice outside them adds nothing here (its
expert lives on another chip) and the partial sum goes on. A loop over the held
experts with a mask: nothing is sorted or gathered.

Departures, as in the configuration file: the multi-token-prediction module is
left out; dropout none; the reference takes whatever weights it is handed.

Also here: what the expert GEMMs of the held share and the delta rule of a step
need at the least (``expert_gemm_*``, ``gdn_scan_*``) for the roofline shares.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import flops

F32 = jnp.float32
#: queries a block of the reference attention holds scores for
QUERY_BLOCK = 512


def norm(x, w, eps):
    """Zero-centred RMSNorm over the last axis."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _gdn_dims(cfg):
    hk, hv = int(cfg["linear_num_key_heads"]), int(cfg["linear_num_value_heads"])
    return hk, hv, int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])


def kinds(cfg):
    every = int(cfg["full_attention_interval"])
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(int(cfg["num_hidden_layers"]))]


def held_range(cfg, num_experts):
    """(first, count) of the experts this copy holds among ``num_experts``."""
    share = cfg.get("expert_share") or {"rank": 0, "of": 1}
    count = num_experts // int(share["of"])
    return int(share["rank"]) * count, count


def published_weights(params, cfg):
    """The program's flat tree under the published names and layouts. Its fused
    attention projection is interleaved by key/value group (a group's query
    heads, then its key head, then its value head) and the output gate is a
    matrix of its own; its DeltaNet in-projections are flat ``[q | k | v | z]``
    and ``[b | a]``; its conv taps are stored (K, C)."""
    h = int(cfg["hidden_size"])
    n, kv, hd = (int(cfg[k]) for k in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    rep = n // kv
    hk, hv, dk, dv = _gdn_dims(cfg)
    r, fs = hv // hk, int(cfg["shared_expert_intermediate_size"])
    out = {"embed_tokens": params["embed"]["tok"], "norm": params["final_norm"]["scale"],
           "lm_head": params["head"]["w"], "layers": []}
    for lp in params["layers"]:
        mlp = lp["mlp"]
        lw = {"input_layernorm": lp["attn_norm"]["scale"],
              "post_attention_layernorm": lp["mlp_norm"]["scale"],
              "gate": mlp["router"]["w"],  # (h, E published)
              # (held, h, f), (held, h, f), (held, f, h) of the held experts, in order
              "gate_proj": mlp["w1"], "up_proj": mlp["w3"], "down_proj": mlp["w2"],
              "shared_gate_proj": mlp["shared"]["w13"][:, :fs],
              "shared_up_proj": mlp["shared"]["w13"][:, fs:],
              "shared_down_proj": mlp["shared"]["w2"], "shared_expert_gate": mlp["shared"]["gate"]}
        if "gdn" in lp:
            m = lp["gdn"]
            q, k, v, z = jnp.split(m["in_proj"], [hk * dk, 2 * hk * dk, 2 * hk * dk + hv * dv],
                                   axis=1)
            per_key_head = [q.reshape(h, hk, dk), k.reshape(h, hk, dk),
                            v.reshape(h, hk, r * dv), z.reshape(h, hk, r * dv)]
            b, a = jnp.split(m["ba_proj"], 2, axis=1)
            lw["linear_attn"] = {
                "in_proj_qkvz": jnp.concatenate(per_key_head, axis=2).reshape(h, -1),
                "in_proj_ba": jnp.concatenate(
                    [b.reshape(h, hk, r), a.reshape(h, hk, r)], axis=2).reshape(h, -1),
                "conv1d_weight": m["conv_w"].T, "A_log": m["A_log"], "dt_bias": m["dt_bias"],
                "norm": m["norm"], "out_proj": m["out_proj"]}
        else:
            a = lp["attn"]
            w = a["wqkv"].reshape(h, kv, rep + 2, hd)
            q_gate = jnp.concatenate(
                [w[:, :, :rep].reshape(h, n, hd), a["wgate"].reshape(h, n, hd)], axis=2)
            lw["self_attn"] = {"q_proj": q_gate.reshape(h, n * 2 * hd),
                               "k_proj": w[:, :, rep].reshape(h, kv * hd),
                               "v_proj": w[:, :, rep + 1].reshape(h, kv * hd),
                               "q_norm": a["q_norm"], "k_norm": a["k_norm"], "o_proj": a["wo"]}
        out["layers"].append(lw)
    return out


def partial_rotary(x, theta, rot):
    """Rotate-half rotary on the first ``rot`` dims of (b, s, n, d), pairs
    (i, i + rot/2); the other dims pass."""
    s = x.shape[1]
    inv = 1.0 / (theta ** (np.arange(0, rot, 2) / rot))
    ang = np.outer(np.arange(s), inv)
    cos = jnp.asarray(np.cos(ang), F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[None, :, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2: rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(y, w, cfg):
    n, kv, hd = (int(cfg[k]) for k in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    rot = int(hd * float(cfg["partial_rotary_factor"]))
    b, s, _ = y.shape
    rep = n // kv
    q_gate = (y @ w["q_proj"]).reshape(b, s, n, 2 * hd)
    q, gate = q_gate[..., :hd], q_gate[..., hd:].reshape(b, s, n * hd)
    q = partial_rotary(norm(q, w["q_norm"], eps), theta, rot).reshape(b, s, kv, rep, hd)
    k = partial_rotary(norm((y @ w["k_proj"]).reshape(b, s, kv, hd), w["k_norm"], eps), theta, rot)
    v = (y @ w["v_proj"]).reshape(b, s, kv, hd)
    blk = next(c for c in range(min(QUERY_BLOCK, s), 0, -1) if s % c == 0)
    keys = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k) / np.sqrt(hd)
        seen = keys[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)

    o = jax.lax.map(block, jnp.arange(s // blk))  # (blocks, b, blk, kv, rep, d)
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, n * hd)
    return (o * jax.nn.sigmoid(gate)) @ w["o_proj"]


def delta_rule_recurrent(q, k, v, g, beta):
    """q, k (b, s, H, dk) normalised and scaled, v (b, s, H, dv), g and beta
    (b, s, H) -> o (b, s, H, dv): one position at a time."""
    b, _, heads, dk = q.shape

    def step(state, inp):  # state (b, H, dk, dv)
        q_t, k_t, v_t, g_t, beta_t = inp
        state = state * jnp.exp(g_t)[..., None, None]
        r_t = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + k_t[..., :, None] * (beta_t[..., None] * r_t)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((b, heads, dk, v.shape[-1]), F32),
                        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def delta_net(y, w, cfg):
    hk, hv, dk, dv = _gdn_dims(cfg)
    r = hv // hk
    b, s, _ = y.shape
    qkvz = (y @ w["in_proj_qkvz"]).reshape(b, s, hk, 2 * dk + 2 * r * dv)
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    ba = (y @ w["in_proj_ba"]).reshape(b, s, hk, 2 * r)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(b, s, hv))
    a = ba[..., r:].reshape(b, s, hv)
    mixed = jnp.concatenate([t.reshape(b, s, -1) for t in (q, k, v)], axis=-1)
    taps = w["conv1d_weight"]  # (C, K): tap K-1 on the current position
    kt = taps.shape[1]
    padded = jnp.pad(mixed, ((0, 0), (kt - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[:, j:j + s] * taps[:, j] for j in range(kt)))
    q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(a + w["dt_bias"])
    q = jnp.repeat(l2norm(q.reshape(b, s, hk, dk)) / np.sqrt(dk), r, axis=2)
    k = jnp.repeat(l2norm(k.reshape(b, s, hk, dk)), r, axis=2)
    o = delta_rule_recurrent(q, k, v.reshape(b, s, hv, dv), g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + float(cfg["rms_norm_eps"]))
    o = o * w["norm"] * jax.nn.silu(z.reshape(b, s, hv, dv))
    return o.reshape(b, s, hv * dv) @ w["out_proj"]


def router(y, gate, top_k, renormalise):
    """(probabilities (b, s, E) float32, the choices (b, s, k), their combine
    weights (b, s, k))."""
    probs = jax.nn.softmax(y.astype(F32) @ gate.astype(F32), axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    if renormalise:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return probs, idx, top


def swiglu(y, gate_w, up_w, down_w):
    return (jax.nn.silu(y @ gate_w) * (y @ up_w)) @ down_w


def sparse_mlp(y, lw, cfg):
    """The expert layer's output on this copy: the held experts' part of the
    routed sum and the shared expert."""
    probs, idx, top = router(y, lw["gate"], int(cfg["num_experts_per_tok"]),
                             bool(cfg["norm_topk_prob"]))
    first, count = held_range(cfg, probs.shape[-1])
    # (b, s, held): a held expert's combine weight, zero where it was not chosen
    chosen = jnp.sum(jax.nn.one_hot(idx - first, count, dtype=F32) * top[..., None], axis=-2)

    def expert(acc, ew):
        gate_w, up_w, down_w, weight = ew
        return acc + weight[..., None] * swiglu(y, gate_w, up_w, down_w), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(y), (
        lw["gate_proj"], lw["up_proj"], lw["down_proj"], jnp.moveaxis(chosen, -1, 0)))
    shared = swiglu(y, lw["shared_gate_proj"], lw["shared_up_proj"], lw["shared_down_proj"])
    return routed, jax.nn.sigmoid(y @ lw["shared_expert_gate"]) * shared, (probs, idx)


def _forward(w, tokens, cfg):
    eps = float(cfg["rms_norm_eps"])
    x = w["embed_tokens"][tokens]
    routed = []
    for kind, lw in zip(kinds(cfg), w["layers"]):
        y = norm(x, lw["input_layernorm"], eps)
        x = x + (attention(y, lw["self_attn"], cfg) if kind == "full_attention"
                 else delta_net(y, lw["linear_attn"], cfg))
        part, shared, route = sparse_mlp(norm(x, lw["post_attention_layernorm"], eps), lw, cfg)
        x = x + part + shared
        routed.append(route)
    return norm(x, w["norm"], eps) @ w["lm_head"], routed


def logits(w, tokens, cfg):
    return _forward(w, tokens, cfg)[0]


def aux_loss(w, tokens, cfg):
    """HF's ``load_balancing_loss_func`` over ALL published experts (before the
    coefficient): the layers' router outputs concatenated."""
    routed = _forward(w, tokens, cfg)[1]
    e = routed[0][0].shape[-1]
    probs = jnp.concatenate([p.reshape(-1, e) for p, _ in routed])
    idx = jnp.concatenate([i.reshape(-1, i.shape[-1]) for _, i in routed])
    f = jnp.mean(jax.nn.one_hot(idx, e, dtype=F32), axis=0)  # (k, E)
    return e * jnp.sum(f * jnp.mean(probs, axis=0)[None, :])


# ---------------------------------------------------------------------------
# counts, from shapes alone
# ---------------------------------------------------------------------------


def held_pairs_per_token(cfg):
    """Routed (token, expert) pairs a token puts on the held experts when the
    load is even: ``k * held / published``."""
    return (int(cfg["num_experts_per_tok"]) * int(cfg["num_experts"])
            / int(cfg["published"]["num_experts"]))


def _delta_rule_fwd_flops_per_token(cfg):
    """The chunked delta rule's products a token and layer, forward, at the
    published chunk of 64: the causal half of K K^T and Q K^T (once a key head);
    a value head's triangular solve against [beta V | beta K exp(G)], the
    entering state read twice (W S, Q S), the causal half of scores V', and the
    chunk's state K^T V'."""
    hk, hv, dk, dv = _gdn_dims(cfg)
    chunk = 64
    pairs = (chunk + 1) / 2
    return (hk * 2 * 2.0 * pairs * dk
            + hv * (2.0 * (chunk - 1) / 2 * (dk + dv) + 3 * 2.0 * dk * dv + 2.0 * pairs * dv))


def fwd_flops_per_token(cfg, seq_len):
    """Forward model FLOPs a token (``lib/flops.py``'s conventions), of what
    THIS copy computes: a DeltaNet layer's in-projections, conv taps, delta rule
    (linear in the sequence) and out-projection; the attention layer's q (with
    its gate), k, v, o and the causal half of QK^T and PV; every layer the
    router over all published experts, ``k * held / published`` routed pairs of
    three matrices, the shared expert and its gate; the head."""
    h, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    n, kv, hd = (int(cfg[k]) for k in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    hk, hv, dk, dv = _gdn_dims(cfg)
    conv_dim = 2 * hk * dk + hv * dv
    gdn = (2.0 * h * (conv_dim + hv * dv + 2 * hv) + 2.0 * int(cfg["linear_conv_kernel_dim"])
           * conv_dim + _delta_rule_fwd_flops_per_token(cfg) + 2.0 * hv * dv * h)
    attn = (2.0 * h * (2 * n * hd + 2 * kv * hd) + 2.0 * n * hd * h
            + 2 * 2.0 * n * hd * flops.attention_pairs(seq_len) / seq_len)
    moe = (2.0 * h * int(cfg["published"]["num_experts"])
           + held_pairs_per_token(cfg) * 3 * 2.0 * h * int(cfg["moe_intermediate_size"])
           + 3 * 2.0 * h * int(cfg["shared_expert_intermediate_size"]) + 2.0 * h)
    ks = kinds(cfg)
    n_full = sum(k == "full_attention" for k in ks)
    return (len(ks) - n_full) * (gdn + moe) + n_full * (attn + moe) + 2.0 * h * v


def expert_gemm_flops(cfg, tokens):
    """Operations the held experts' GEMMs of one step need, forward + backward:
    9 GEMMs (gate, up, down; each once forward and twice backward) of
    ``2 * pairs * h * f`` a layer, ``pairs`` the even load's share of the held
    experts. Recomputed GEMMs do not count."""
    pairs = tokens * held_pairs_per_token(cfg)
    return (int(cfg["num_hidden_layers"]) * 9 * 2.0 * pairs
            * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"]))


def expert_gemm_bytes(cfg, tokens, itemsize=2):
    """Least HBM traffic of the same, a step (olmoe's count on the held share):
    every pair's row read or written at each GEMM's ends, forward and twice
    over backward, and every HELD expert's three matrices read forward, read
    again and their gradient written backward."""
    pairs = tokens * held_pairs_per_token(cfg)
    h, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    rows_fwd = pairs * (2 * h + 2 * f + f + h)
    weights = 3 * 3 * int(cfg["num_experts"]) * h * f
    return int(cfg["num_hidden_layers"]) * itemsize * (3 * rows_fwd + weights)


def _gdn_layers(cfg):
    return sum(k == "linear_attention" for k in kinds(cfg))


def gdn_scan_flops(cfg, tokens):
    """Operations the delta rules of one step need, forward + backward (each
    product once forward and twice backward). Recomputed ones do not count."""
    return 3.0 * _gdn_layers(cfg) * tokens * _delta_rule_fwd_flops_per_token(cfg)


def gdn_scan_bytes(cfg, tokens, itemsize=2):
    """Least HBM traffic of the same, a step: forward reads q, k, v, g and beta
    and writes o; backward reads them and do again and writes their five
    gradients (the carried states are small and left out)."""
    hk, hv, dk, dv = _gdn_dims(cfg)
    ins = 2 * hk * dk + hv * dv + 2 * hv
    return _gdn_layers(cfg) * tokens * itemsize * ((ins + hv * dv) + (2 * ins + hv * dv))
