"""allenai/OLMoE-1B-7B-0125-Instruct (HF ``modeling_olmoe.py``): pre-norm
RMSNorm; separate bias-free q, k, v projections; ``q_norm`` / ``k_norm``:
RMSNorm with a learned scale over the WHOLE projection width (all heads
together), before the split into heads and before rope; rotate-half rope, base
``rope_theta``; causal softmax attention scaled by 1/sqrt(d); ``o_proj``; then
the sparse MLP: ``p = softmax_float32(y Wg)``, the ``num_experts_per_tok``
largest ``p`` of a token are its combine weights AS THEY ARE
(``norm_topk_prob`` false), every chosen pair is computed (no capacity, no
drop), each expert ``down(silu(gate y) * up y)``; final RMSNorm, untied head.

The experts are a loop over all ``num_experts`` with a mask (a token's weight
for an expert it did not choose is zero): nothing is sorted or gathered, so
the reference shares no mechanism with the program's grouped GEMM.

``aux_loss`` is HF's ``load_balancing_loss_func``: the layers' router outputs
concatenated, ``E * sum_{j,e} f_{j,e} P_e`` with f the share of (layer, token)
rows whose j-th choice is e and P the mean probability.  Departures from the
paper, as in the configuration file: the router z-loss is left out (the HF
model has none either); ``clip_qkv`` is null in the published config.
"""

import jax
import jax.numpy as jnp

from benchmark.lib import flops
from benchmark.lib.reference import causal_attention, rms_norm, rotate_half

F32 = jnp.float32


def published_weights(params, cfg):
    out = {"embed_tokens": params["embed"]["tok"], "layers": [],
           "norm": params["final_norm"]["scale"], "lm_head": params["head"]["w"]}
    for lp in params["layers"]:
        wqkv, mlp = lp["attn"]["wqkv"], lp["mlp"]  # (h, 3, n*d): q, k, v slots
        out["layers"].append({
            "input_layernorm": lp["attn_norm"]["scale"],
            "q_proj": wqkv[:, 0], "k_proj": wqkv[:, 1], "v_proj": wqkv[:, 2],
            "q_norm": lp["attn"]["q_norm"], "k_norm": lp["attn"]["k_norm"],
            "o_proj": lp["attn"]["wo"],
            "post_attention_layernorm": lp["mlp_norm"]["scale"],
            "gate": mlp["router"]["w"],  # (h, E)
            # (E, h, f), (E, h, f), (E, f, h): expert e's gate_proj, up_proj, down_proj
            "gate_proj": mlp["w1"], "up_proj": mlp["w3"], "down_proj": mlp["w2"],
        })
    return out


def router(y, gate, top_k):
    """(probabilities (b, s, E) float32, the combine weight of every expert
    (b, s, E): a chosen expert's own probability, zero elsewhere, and the
    choices (b, s, k))."""
    probs = jax.nn.softmax((y.astype(F32) @ gate.astype(F32)), axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    chosen = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=F32) * top[..., None], axis=-2)
    return probs, chosen, idx


def sparse_mlp(y, lw, top_k):
    probs, chosen, idx = router(y, lw["gate"], top_k)

    def expert(acc, ew):
        gate_w, up_w, down_w, weight = ew
        out = (jax.nn.silu(y @ gate_w) * (y @ up_w)) @ down_w
        return acc + weight[..., None] * out, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(y), (
        lw["gate_proj"], lw["up_proj"], lw["down_proj"], jnp.moveaxis(chosen, -1, 0)))
    return out, (probs, idx)


def _forward(w, tokens, cfg):
    n, eps = int(cfg["num_attention_heads"]), float(cfg["rms_norm_eps"])
    theta, top_k = float(cfg["rope_theta"]), int(cfg["num_experts_per_tok"])
    b, s = tokens.shape
    x = w["embed_tokens"][tokens]
    routed = []
    for lw in w["layers"]:
        y = rms_norm(x, lw["input_layernorm"], eps)
        q = rms_norm(y @ lw["q_proj"], lw["q_norm"], eps).reshape(b, s, n, -1)
        k = rms_norm(y @ lw["k_proj"], lw["k_norm"], eps).reshape(b, s, n, -1)
        v = (y @ lw["v_proj"]).reshape(b, s, n, -1)
        x = x + causal_attention(rotate_half(q, theta), rotate_half(k, theta), v) @ lw["o_proj"]
        out, route = sparse_mlp(rms_norm(x, lw["post_attention_layernorm"], eps), lw, top_k)
        x = x + out
        routed.append(route)
    return rms_norm(x, w["norm"], eps) @ w["lm_head"], routed


def logits(w, tokens, cfg):
    return _forward(w, tokens, cfg)[0]


def aux_loss(w, tokens, cfg):
    """The load-balancing loss of ``tokens`` (before ``router_aux_loss_coef``)."""
    e = int(cfg["num_experts"])
    routed = _forward(w, tokens, cfg)[1]
    probs = jnp.concatenate([p.reshape(-1, e) for p, _ in routed])  # (layers * tokens, E)
    idx = jnp.concatenate([i.reshape(-1, i.shape[-1]) for _, i in routed])
    f = jnp.mean(jax.nn.one_hot(idx, e, dtype=F32), axis=0)  # (k, E)
    return e * jnp.sum(f * jnp.mean(probs, axis=0)[None, :])


def expert_load(w, tokens, cfg):
    """Pairs each expert gets, a layer: (layers, E)."""
    e = int(cfg["num_experts"])
    return jnp.stack([jnp.sum(jax.nn.one_hot(i, e, dtype=F32), axis=(0, 1, 2))
                      for _, i in _forward(w, tokens, cfg)[1]])


def fwd_flops_per_token(cfg, seq_len):
    """Forward model FLOPs a token (``lib/flops.py``'s conventions): q, k, v, o;
    the causal half of QK^T and PV; the router; ``num_experts_per_tok`` experts
    of three matrices; the head."""
    h, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    layers, k = int(cfg["num_hidden_layers"]), int(cfg["num_experts_per_tok"])
    proj = 2.0 * 4 * h * h
    pairs = 2 * 2.0 * h * flops.attention_pairs(seq_len) / seq_len
    route = 2.0 * h * int(cfg["num_experts"])
    experts = k * 3 * 2.0 * h * f
    return layers * (proj + pairs + route + experts) + 2.0 * h * int(cfg["vocab_size"])


def expert_gemm_flops(cfg, tokens):
    """Operations the expert GEMMs of one step need, forward + backward: 9 GEMMs
    (gate, up, down; each once forward and twice backward) of 2 * pairs * h * f,
    a layer.  Recomputed GEMMs do not count."""
    pairs = tokens * int(cfg["num_experts_per_tok"])
    return (int(cfg["num_hidden_layers"]) * 9 * 2.0 * pairs
            * int(cfg["hidden_size"]) * int(cfg["intermediate_size"]))


def expert_gemm_bytes(cfg, tokens, itemsize=2):
    """Least HBM traffic of the same, a step: every pair's row read or written
    at each GEMM's ends (forward: x in twice, g and u out, h in, y out;
    backward: the same rows as gradients, and x, g, u, h read again for the
    weight gradients), and every expert's three matrices read forward, read
    again and their gradient written backward."""
    pairs = tokens * int(cfg["num_experts_per_tok"])
    h, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    rows_fwd = pairs * (2 * h + 2 * f + f + h)
    rows_bwd = 2 * rows_fwd
    weights = 3 * 3 * int(cfg["num_experts"]) * h * f
    return int(cfg["num_hidden_layers"]) * itemsize * (rows_fwd + rows_bwd + weights)
