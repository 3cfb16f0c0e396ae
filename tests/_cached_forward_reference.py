"""The cached forwards as they stood before the stacked cache was written in
place (PR 38): every layer's slab sliced out of the cache, rewritten whole
and re-stacked.  Kept as the plain reference the in-place forwards are held
to bit for bit on the CPU (tests/test_generation.py, tests/test_serving.py).
Slow on a chip by design (a decode step moved the cache's capacity): never
import it from the package."""

import jax
import jax.numpy as jnp

from galvatron_tpu.models import modeling
from galvatron_tpu.models.generation import KVCache
from galvatron_tpu.models.modeling import ModelConfig


def _cached_attention(q, k_cache, v_cache, q_offset, cfg: ModelConfig, alibi=None):
    """q: (B, s, nh, hd); caches: (B, Smax, kvh, hd). Delegates to
    modeling.attention_xla (same mask/softmax core); only the ALiBi bias needs
    the absolute-position rewrite here."""
    s, smax = q.shape[1], k_cache.shape[1]
    bias = None
    if alibi is not None:
        q_pos = q_offset + jnp.arange(s)
        k_pos = jnp.arange(smax)
        rel = k_pos[None, :] - q_pos[:, None]  # (s, Smax)
        bias = (alibi[:, None, None] * rel[None]).astype(jnp.float32)[None]
    return modeling.attention_xla(q, k_cache, v_cache, cfg, bias=bias, q_offset=q_offset)


def _layer_with_cache(x, p, cfg: ModelConfig, k_cache, v_cache, offset, cos_sin, alibi):
    """decoder_layer variant that reads/writes the KV cache at ``offset``.
    Returns (x_out, k_cache, v_cache)."""
    b, s, h = x.shape
    hd = cfg.head_dim
    xa = modeling.norm(x, p["attn_norm"], cfg)
    pa = p["attn"]
    q, k, v = modeling.project_qkv_heads(xa, pa, cfg)
    if cfg.pos_embed == "rope":
        cos, sin = cos_sin
        q = modeling.apply_rope(q, cos, sin)
        k = modeling.apply_rope(k, cos, sin)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, offset, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, offset, 0, 0))
    o = _cached_attention(q, k_cache, v_cache, offset, cfg, alibi=alibi)
    x = x + modeling.attn_output(o, pa, cfg, x.dtype)
    x = x + modeling.mlp_block(
        modeling.norm(x, p["mlp_norm"], cfg), p["mlp"], cfg, train=False
    )
    return x, k_cache, v_cache


def forward_with_cache(params, tokens, cfg: ModelConfig, cache: KVCache, offset):
    """Run ``tokens`` (B, s) through the model at absolute position ``offset``,
    updating the cache. Returns (logits, new_cache). ``offset`` may be traced."""
    s = tokens.shape[1]
    if cfg.pos_embed == "rope":
        # full-length tables indexed dynamically so offset can be traced
        cos_all, sin_all = modeling.rope_tables(cfg, cache.k.shape[2])
        cos = jax.lax.dynamic_slice_in_dim(cos_all, offset, s, axis=0)
        sin = jax.lax.dynamic_slice_in_dim(sin_all, offset, s, axis=0)
        cos_sin = (cos, sin)
    else:
        cos_sin = None
    alibi = (
        jnp.asarray(modeling.alibi_slopes(cfg.num_heads)) if cfg.pos_embed == "alibi" else None
    )
    x = params["embed"]["tok"].astype(cfg.dtype)[tokens]
    if cfg.pos_embed == "learned":
        pos = offset + jnp.arange(s)
        x = x + params["embed"]["pos"].astype(cfg.dtype)[pos][None]
    new_k, new_v = [], []
    for i, lp in enumerate(params["layers"]):
        x, ki, vi = _layer_with_cache(
            x, lp, cfg, cache.k[i], cache.v[i], offset, cos_sin, alibi
        )
        new_k.append(ki)
        new_v.append(vi)
    x = modeling.norm(x, params["final_norm"], cfg)
    logits = modeling.lm_head(x, params, cfg)
    return logits, KVCache(jnp.stack(new_k), jnp.stack(new_v))



def _layer_with_cache_slots(x, p, cfg: ModelConfig, k_cache, v_cache, offsets,
                            cos_sin, alibi):
    """``_layer_with_cache`` variant where ``offsets`` is (B,): row ``b``
    reads/writes its cache at its own position. Returns (x, k_cache, v_cache)."""
    b, s, h = x.shape
    xa = modeling.norm(x, p["attn_norm"], cfg)
    pa = p["attn"]
    q, k, v = modeling.project_qkv_heads(xa, pa, cfg)
    if cfg.pos_embed == "rope":
        cos, sin = cos_sin  # (B, s, hd/2) per-row tables
        q = modeling.apply_rope(q, cos, sin)
        k = modeling.apply_rope(k, cos, sin)
    row_update = jax.vmap(
        lambda c, u, o: jax.lax.dynamic_update_slice(c, u, (o, 0, 0))
    )
    k_cache = row_update(k_cache, k.astype(k_cache.dtype), offsets)
    v_cache = row_update(v_cache, v.astype(v_cache.dtype), offsets)
    bias = None
    if alibi is not None:
        q_pos = offsets[:, None] + jnp.arange(s)[None]  # (B, s)
        k_pos = jnp.arange(k_cache.shape[1])
        rel = k_pos[None, None, :] - q_pos[:, :, None]  # (B, s, Smax)
        bias = (alibi[None, :, None, None] * rel[:, None]).astype(jnp.float32)
    o = modeling.attention_xla(q, k_cache, v_cache, cfg, bias=bias, q_offset=offsets)
    x = x + modeling.attn_output(o, pa, cfg, x.dtype)
    x = x + modeling.mlp_block(
        modeling.norm(x, p["mlp_norm"], cfg), p["mlp"], cfg, train=False
    )
    return x, k_cache, v_cache


def forward_with_cache_slots(params, tokens, cfg: ModelConfig,
                             cache: KVCache, offsets):
    """Run ``tokens`` (B, s) through the model with PER-ROW absolute positions
    ``offsets`` (B,), updating row ``b`` of the cache at ``offsets[b]``.
    Returns (logits, new_cache). ``offsets`` may be traced.

    This is the forward the continuous-batching engine runs once per decode
    iteration over all slots: rows are independent requests at arbitrary
    depths into their sequences; rows holding no request are simply masked by
    the caller (their writes land at their own row's offset and are
    overwritten by the next prefill before ever becoming visible — causal
    masking keeps positions > a row's own offset invisible)."""
    b, s = tokens.shape
    smax = cache.k.shape[2]
    if cfg.pos_embed == "rope":
        cos_all, sin_all = modeling.rope_tables(cfg, smax)
        pos = offsets[:, None] + jnp.arange(s)[None]  # (B, s)
        cos_sin = (cos_all[pos], sin_all[pos])
    else:
        cos_sin = None
    alibi = (
        jnp.asarray(modeling.alibi_slopes(cfg.num_heads)) if cfg.pos_embed == "alibi" else None
    )
    x = params["embed"]["tok"].astype(cfg.dtype)[tokens]
    if cfg.pos_embed == "learned":
        pos = offsets[:, None] + jnp.arange(s)[None]
        x = x + params["embed"]["pos"].astype(cfg.dtype)[pos]
    new_k, new_v = [], []
    for i, lp in enumerate(params["layers"]):
        x, ki, vi = _layer_with_cache_slots(
            x, lp, cfg, cache.k[i], cache.v[i], offsets, cos_sin, alibi
        )
        new_k.append(ki)
        new_v.append(vi)
    x = modeling.norm(x, params["final_norm"], cfg)
    logits = modeling.lm_head(x, params, cfg)
    return logits, KVCache(jnp.stack(new_k), jnp.stack(new_v))


def prefill_chunk(params, tokens, cfg: ModelConfig, cache: KVCache, slot, offset):
    """The engine's ``_prefill_chunk`` as it stood: the request's row sliced
    out of the cache, run as a cache of one row, and written back."""
    row = KVCache(
        jax.lax.dynamic_slice_in_dim(cache.k, slot, 1, axis=1),
        jax.lax.dynamic_slice_in_dim(cache.v, slot, 1, axis=1),
    )
    logits, row = forward_with_cache(params, tokens, cfg, row, offset)
    return logits, KVCache(
        jax.lax.dynamic_update_slice_in_dim(cache.k, row.k, slot, axis=1),
        jax.lax.dynamic_update_slice_in_dim(cache.v, row.v, slot, axis=1),
    )


def random_cache(cfg: ModelConfig, rows: int, smax: int, seed: int) -> KVCache:
    """A cache of noise: a write that lands anywhere shows, and so does an
    element that should have changed and kept its bits."""
    shape = (cfg.num_layers, rows, smax, cfg.kv_heads, cfg.head_dim)
    k, v = jax.random.normal(jax.random.key(seed), (2,) + shape, cfg.dtype)
    return KVCache(k, v)
