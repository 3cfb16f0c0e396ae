"""facebook/opt-* (modeling_opt.py, ``do_layer_norm_before``): token + learned
position embedding, pre-norm LayerNorm with bias (eps 1e-5, torch's default),
q/k/v/out projections with bias, ReLU MLP with bias, final LayerNorm, head tied
to the token embedding.

Departures, both the program's: the published position table has 2 unused
leading rows (offset 2), the program keeps the table without them, and so does
this reference; dropout (0.1 published) is not applied, the program has none
(both listed in the configuration file)."""

import jax
import jax.numpy as jnp

from benchmark.lib import flops
from benchmark.lib.reference import causal_attention, layer_norm

EPS = 1e-5


def published_weights(params, cfg):
    out = {"embed_tokens": params["embed"]["tok"], "embed_positions": params["embed"]["pos"],
           "final_layer_norm": (params["final_norm"]["scale"], params["final_norm"]["bias"]),
           "layers": []}
    for lp in params["layers"]:
        a, m = lp["attn"], lp["mlp"]
        out["layers"].append({
            "self_attn_layer_norm": (lp["attn_norm"]["scale"], lp["attn_norm"]["bias"]),
            "qkv_proj": (a["wqkv"], a["wqkv_b"]),  # (h, 3, n*d) and (3, n*d): q, k, v
            "out_proj": (a["wo"], a["wo_b"]),
            "final_layer_norm": (lp["mlp_norm"]["scale"], lp["mlp_norm"]["bias"]),
            "fc1": (m["w1"], m["w1_b"]), "fc2": (m["w2"], m["w2_b"]),
        })
    return out


def logits(w, tokens, cfg):
    n = int(cfg["num_attention_heads"])
    b, s = tokens.shape
    x = w["embed_tokens"][tokens] + w["embed_positions"][:s][None]
    for lw in w["layers"]:
        y = layer_norm(x, lw["self_attn_layer_norm"], EPS)
        wqkv, bqkv = lw["qkv_proj"]
        qkv = jnp.einsum("bsh,hcd->bscd", y, wqkv) + bqkv
        q, k, v = (qkv[:, :, i].reshape(b, s, n, -1) for i in range(3))
        wo, bo = lw["out_proj"]
        x = x + causal_attention(q, k, v) @ wo + bo
        y = layer_norm(x, lw["final_layer_norm"], EPS)
        (w1, b1), (w2, b2) = lw["fc1"], lw["fc2"]
        x = x + jax.nn.relu(y @ w1 + b1) @ w2 + b2
    return layer_norm(x, w["final_layer_norm"], EPS) @ w["embed_tokens"].T


def fwd_flops_per_token(cfg, seq_len):
    return flops.dense_decoder_fwd(
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"], ffn=cfg["ffn_dim"],
        mlp_matrices=2, layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
        seq_len=seq_len)


def serve_dims(cfg):
    """The sizes ``lib/flops.py`` counts a served forward's operations and bytes from."""
    h, n = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"hidden": h, "heads": n, "kv_heads": n, "head_dim": h // n, "ffn": int(cfg["ffn_dim"]),
            "mlp_matrices": 2, "layers": int(cfg["num_hidden_layers"]),
            "vocab": int(cfg["vocab_size"])}


def served_params(cfg):
    """Parameters a forward must read whatever implements it: ``a_forward``, once
    however many tokens it holds (every layer's four projections and two MLP
    matrices with their biases, the LayerNorms, and the tied table, which the
    head multiplies whole: the embedding rows are in it); ``a_token``, once a
    token (its position's row of the learned table).  opt-1.3b: 1,311,559,680
    and 2,048; with all 2,048 position rows the model has 1,315,753,984."""
    d = serve_dims(cfg)
    h, f = d["hidden"], d["ffn"]
    layer = (3 * h * h + 3 * h) + (h * h + h) + (h * f + f) + (f * h + h) + 2 * 2 * h
    return {"a_forward": d["layers"] * layer + 2 * h + d["vocab"] * h, "a_token": h}
