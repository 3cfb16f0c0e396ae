import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax, jax.numpy as jnp
from galvatron_tpu.models import modeling
from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.search.memory_fidelity import measured_train_mb

orig = modeling.mlp_block
def patched(x, p, cfg, train=True):
    if cfg.moe_experts > 0 or cfg.act_fn != "swiglu":
        return orig(x, p, cfg, train)
    f = p["w13"].shape[-1] // 2
    g = x @ p["w13"].astype(x.dtype)
    if "w13_b" in p:
        g = g + p["w13_b"].astype(x.dtype)
    swiglu = jax.checkpoint(lambda g_: jax.nn.silu(g_[..., :f]) * g_[..., f:])
    y = swiglu(g) @ p["w2"].astype(x.dtype)
    if "w2_b" in p:
        y = y + p["w2_b"].astype(x.dtype)
    return y

BIG = ModelConfig(vocab_size=8192, hidden_size=2048, num_layers=4, num_heads=16,
                  max_seq_len=2048, dtype=jnp.bfloat16, attn_impl="flash")
for which in ("base", "ckpt-swiglu"):
    modeling.mlp_block = orig if which == "base" else patched
    for tp in (1, 2):
        hp = HybridParallelConfig(layer_strategies=[LayerStrategy(tp=tp)]*4,
                                  vocab_tp=tp, mixed_precision="bf16")
        m = measured_train_mb(BIG, hp, 16)
        print(f"{which} tp{tp}: state {m['state_mb']:.0f} temp {m['temp_mb']:.0f}", flush=True)
modeling.mlp_block = orig
