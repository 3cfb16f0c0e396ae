"""baichuan-inc/Baichuan-7B (modeling_baichuan.py): pre-norm RMSNorm, fused
``W_pack`` q|k|v, rotary embedding (rotate-half, base 10000) on q and k, causal
softmax attention scaled by 1/sqrt(d), ``o_proj``, SwiGLU MLP
``down(silu(gate(x)) * up(x))``, final RMSNorm, untied head.  No departure from
the published equations; ``rms_norm_eps`` is the configuration file's, as run."""

import jax

from benchmark.lib import flops
from benchmark.lib.reference import causal_attention, rms_norm, rotate_half


def published_weights(params, cfg):
    out = {"embed_tokens": params["embed"]["tok"], "layers": [],
           "norm": params["final_norm"]["scale"], "lm_head": params["head"]["w"]}
    for lp in params["layers"]:
        w13 = lp["mlp"]["w13"]  # the program fuses [gate | up]
        f = w13.shape[-1] // 2
        out["layers"].append({
            "input_layernorm": lp["attn_norm"]["scale"],
            "W_pack": lp["attn"]["wqkv"],  # (h, 3, n*d): one slot each for q, k, v
            "o_proj": lp["attn"]["wo"],
            "post_attention_layernorm": lp["mlp_norm"]["scale"],
            "gate_proj": w13[:, :f], "up_proj": w13[:, f:], "down_proj": lp["mlp"]["w2"],
        })
    return out


def logits(w, tokens, cfg):
    n, eps = int(cfg["num_attention_heads"]), float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    b, s = tokens.shape
    x = w["embed_tokens"][tokens]
    for lw in w["layers"]:
        y = rms_norm(x, lw["input_layernorm"], eps)
        qkv = jax.numpy.einsum("bsh,hcd->bscd", y, lw["W_pack"])
        q, k, v = (qkv[:, :, i].reshape(b, s, n, -1) for i in range(3))
        x = x + causal_attention(rotate_half(q, theta), rotate_half(k, theta), v) @ lw["o_proj"]
        y = rms_norm(x, lw["post_attention_layernorm"], eps)
        x = x + (jax.nn.silu(y @ lw["gate_proj"]) * (y @ lw["up_proj"])) @ lw["down_proj"]
    return rms_norm(x, w["norm"], eps) @ w["lm_head"]


def fwd_flops_per_token(cfg, seq_len):
    return flops.dense_decoder_fwd(
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        ffn=cfg["intermediate_size"], mlp_matrices=3, layers=cfg["num_hidden_layers"],
        vocab=cfg["vocab_size"], seq_len=seq_len)
