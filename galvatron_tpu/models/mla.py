"""Latent attention (MLA) of a sarvam_mla-class model: what a layer of kind
``"mla"`` runs in place of attention, and the first kind with a cache of its own.

    q = h W_q                        n heads of [q_nope dn | q_rope dr]
    [c | k_r] = h W_kva              r + dr;  c~ = RMSNorm_r(c)
    [k_nope | v] = c~ W_kvb          n heads of dn + dv
    q_rope, k_r rotated (YaRN table); k_r is ONE key shared by all heads
    score = (q_nope . k_nope + q_rope . k_r) (dn + dr)^-1/2 m^2     m: `softmax_scale`
    o = softmax_causal(score) v;  y = concat_heads(o) W_o

(DeepSeek-V2's layer without ``q_lora_rank``; no biases; the latent's RMSNorm is
the only q/k norm.) Imported only where a configuration has such layers.

The cache is ONE array ``(layers, rows, positions, r + dr)`` in ``cfg.dtype``
holding ``[c~ | rotated k_r]`` a position (`init_cache`): 1,152 bytes a position a
layer at the published widths in bf16 against 32,768 for the K and V heads it
replaces. `cached_block` has two forms of the same mathematics:

- a prompt chunk (``slot`` given) takes the NON-ABSORBED form: the slot's latent
  up to the chunk's end is expanded through ``W_kvb`` a block of keys at a time
  and attended at widths dn + dr / dv with a running softmax, so the work follows
  the live positions, not the slot's capacity. Where `mla_prefill.chunk_path`
  says so (a chip, slots of whole key blocks, the published widths' tiling) all of
  it, the expansion included, is the kernel ``mla_chunk`` (``ops/mla_prefill.py``):
  it reads the stacked cache in place and its float32 scores, maximum, sum and
  accumulator never leave the chip. Outside that envelope it is XLA's loop
  (`_plain_chunk`), whose score blocks pass through HBM, and which is also the
  kernel's reference;
- every other window (a decode step, the verify window, `generate`) takes the
  ABSORBED form: ``q_nope W_kvb,k^T`` against ``c~``, ``q_rope`` against ``k_r``,
  the probabilities times ``c~``, then ``W_kvb,v``: no K or V of a cached
  position is ever materialised. The two products with ``W_kvb`` are XLA's; the
  middle (scores, softmax, probabilities times ``c~``) is the kernel
  ``mla_decode`` (``ops/mla_decode.py``) where `mla_decode.decode_path` says so (a
  chip, slots of whole key blocks): it reads the stacked cache in place, a row up
  to its own length, all heads off one block of the latent. Outside that envelope
  the middle is XLA's over every slot's capacity (`_plain_context`), which is also
  the kernel's reference.

Scopes under ``attn``: ``qkv_proj``, ``cache_write``, ``attn_core`` (>
``absorb``: the two absorbed products; > ``expand``: the chunk form's ``W_kvb``
expansion, which is the kernel ``mla_chunk`` where that runs; the kernel
``mla_decode`` directly under it), ``out_proj`` (PERF.md §3; the ``mla_*`` benchmark
metrics read them).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from galvatron_tpu.models import modeling
from galvatron_tpu.models.placement import LOCAL, Placement
from galvatron_tpu.ops import mla_decode, mla_prefill
from galvatron_tpu.ops.quant import QuantTensor, qmatmul

Params = Dict[str, Any]
F32 = jnp.float32
#: keys a step of the chunk form expands and attends at once
KEY_BLOCK = 1024


class LatentCache(NamedTuple):
    """``[c~ | rotated k_r]`` of every position: (layers, rows, positions, r + dr)."""

    latent: jax.Array


def dims(cfg):
    """(heads, nope, rope, value, rank) of the layer."""
    return cfg.num_heads, cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim, cfg.mla_kv_rank


def softmax_scale(cfg) -> float:
    """``(dn + dr)^-1/2 m^2``, ``m`` YaRN's factor at ``mscale_all_dim`` (1 without)."""
    _, dn, dr, _, _ = dims(cfg)
    m = modeling.yarn_mscale(cfg.rope_yarn[0], cfg.rope_yarn[5]) if cfg.rope_yarn else 1.0
    return float((dn + dr) ** -0.5 * m * m)


# -- the kind's prices (search/theoretical.py) ----------------------------------


def param_count(cfg) -> int:
    n, dn, dr, dv, r = dims(cfg)
    h = cfg.hidden_size
    return h * n * (dn + dr) + h * (r + dr) + r + r * n * (dn + dv) + n * dv * h


def saved_bytes_per_token(cfg, itemsize: int) -> float:
    """q, the latent with its key, the expanded k_nope and v, the context."""
    n, dn, dr, dv, r = dims(cfg)
    return (n * (dn + dr) + 2 * (r + dr) + n * (dn + dv) + n * dv) * itemsize


def fwd_flops_per_token(cfg) -> float:
    """Beyond the weights: scores at dn + dr and values at dv a pair and head, over
    all ``max_seq_len`` keys a token (the search counts every s x s pair)."""
    n, dn, dr, dv, _ = dims(cfg)
    return 2.0 * n * (dn + dr + dv) * cfg.max_seq_len


def cache_bytes_per_position(cfg) -> int:
    """Bytes ONE layer's cache holds a position."""
    _, _, dr, _, r = dims(cfg)
    return (r + dr) * jnp.dtype(cfg.dtype).itemsize


def init_cache(cfg, layers: int, rows: int, positions: int) -> LatentCache:
    _, _, dr, _, r = dims(cfg)
    return LatentCache(jnp.zeros((layers, rows, positions, r + dr), cfg.dtype))


# -- parameters ---------------------------------------------------------------------


def init_params(key, cfg) -> Params:
    n, dn, dr, dv, r = dims(cfg)
    h = cfg.hidden_size
    ks = jax.random.split(key, 4)
    return {
        "wq": modeling._dense_init(ks[0], h, n * (dn + dr), cfg.param_dtype),
        "wkva": modeling._dense_init(ks[1], h, r + dr, cfg.param_dtype),
        "kv_norm": jnp.ones((r,), cfg.param_dtype),
        # (a head's columns: [k_nope | v])
        "wkvb": modeling._dense_init(ks[2], r, n * (dn + dv), cfg.param_dtype),
        "wo": modeling._dense_init(ks[3], n * dv, h, cfg.param_dtype),
    }


def annotations(cfg) -> Params:
    """ZeRO shards the hidden-size dims; no dim is tensor-parallel (``lacks``)."""
    return {"wq": ("fsdp", None), "wkva": ("fsdp", None), "kv_norm": (None,),
            "wkvb": (None, None), "wo": (None, "fsdp")}


# -- the layer ------------------------------------------------------------------------


def _matmul(x, w):
    """``x @ w``; int8 weights (serving, ops.quant) dequantize inside the GEMM."""
    return qmatmul(x, w) if isinstance(w, QuantTensor) else x @ w.astype(x.dtype)


@jax.named_scope("qkv_proj")
def project(x, p: Params, cfg, cos_sin):
    """x (B, s, h) -> (q_nope (B, s, n, dn), rotated q_rope (B, s, n, dr), the
    positions' cache entries ``[c~ | rotated k_r]`` (B, s, r + dr))."""
    n, dn, dr, _, r = dims(cfg)
    b, s, _ = x.shape
    q = _matmul(x, p["wq"]).reshape(b, s, n, dn + dr)
    ckr = _matmul(x, p["wkva"])
    c = modeling._norm_impl(ckr[..., :r], {"scale": p["kv_norm"]}, cfg)  # RMSNorm over the latent
    k_r = modeling.apply_rope(ckr[..., None, r:], *cos_sin)[..., 0, :]
    return q[..., :dn], modeling.apply_rope(q[..., dn:], *cos_sin), jnp.concatenate([c, k_r], -1)


def _kvb(p: Params, cfg, dtype):
    """``W_kvb`` as (r, n, dn + dv): a head's keys' and values' expansion."""
    n, dn, _, dv, r = dims(cfg)
    return p["wkvb"].astype(dtype).reshape(r, n, dn + dv)


def _allowed(q_pos, k_pos):
    """(B | 1, 1, s, K): key j is at or before query i."""
    return (k_pos[None, None, :] <= q_pos[:, :, None])[:, None]


def _expanded_scores(q_nope, q_rope, latent, p: Params, cfg, q_pos, k_pos):
    """``latent`` (B, K, r + dr) expanded through ``W_kvb`` -> (the masked, scaled
    float32 scores (B, n, s, K) of queries at ``q_pos`` (B | 1, s) against keys at
    ``k_pos`` (K,), the values (B, K, n, dv)). ONE product over [nope | rope] with
    the shared rotary key repeated a head: the float32 scores are written once (two
    products and their sum were 3 passes over them, and the chunk form is bound by
    those passes: PERF.md section 6, PR 51)."""
    n, dn, _, _, r = dims(cfg)
    with jax.named_scope("expand"):
        kv = jnp.einsum("bkr,rnd->bknd", latent[..., :r], _kvb(p, cfg, latent.dtype))
    k_rope = jnp.broadcast_to(latent[:, :, None, r:], latent.shape[:2] + (n, latent.shape[-1] - r))
    scores = jnp.einsum("bqnd,bknd->bnqk", jnp.concatenate([q_nope, q_rope], axis=-1),
                        jnp.concatenate([kv[..., :dn], k_rope], axis=-1),
                        preferred_element_type=F32)
    scores = jnp.where(_allowed(q_pos, k_pos), scores * softmax_scale(cfg), modeling.MASKED_SCORE)
    return scores, kv[..., dn:]


def attend_expanded(q_nope, q_rope, latent, p: Params, cfg, q_pos):
    """The NON-ABSORBED form over ``latent`` (B, K, r + dr), all of it at once:
    -> (B, s, n, dv). ``q_pos`` (B | 1, s): the queries' absolute positions."""
    scores, v = _expanded_scores(q_nope, q_rope, latent, p, cfg, q_pos,
                                 jnp.arange(latent.shape[1]))
    probs = jax.nn.softmax(scores, axis=-1).astype(q_nope.dtype)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v)


def _absorbed_queries(q_nope, q_rope, wkvb_k):
    """``[q_nope W_kvb,k^T | q_rope]`` (B, s, n, r + dr): the queries against ``[c~ | k_r]``."""
    with jax.named_scope("absorb"):
        q_lat = jnp.einsum("bqnd,rnd->bqnr", q_nope, wkvb_k)
    return jnp.concatenate([q_lat, q_rope], axis=-1)


def _absorbed_values(ctx, wkvb_v):
    """The context over the latent (B, s, n, r) through ``W_kvb,v`` -> (B, s, n, dv)."""
    with jax.named_scope("absorb"):
        return jnp.einsum("bqnr,rnd->bqnd", ctx, wkvb_v)


def _plain_context(q_cat, latent, q_pos, cfg):
    """The absorbed form's middle as XLA runs it, over ALL of ``latent`` (B, K,
    r + dr): float32 scores of every position, a softmax, the probabilities in the
    compute type times the latent -> (B, s, n, r). What runs outside the kernel's
    envelope (`mla_decode.decode_path`), and the kernel's reference."""
    scores = jnp.einsum("bqnc,bkc->bnqk", q_cat, latent, preferred_element_type=F32)
    scores = jnp.where(_allowed(q_pos, jnp.arange(latent.shape[1])),
                       scores * softmax_scale(cfg), modeling.MASKED_SCORE)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_cat.dtype)
    return jnp.einsum("bnqk,bkc->bqnc", probs, latent)[..., :cfg.mla_kv_rank]


def attend_absorbed(q_nope, q_rope, latent, p: Params, cfg, q_pos):
    """The ABSORBED form over ``latent`` (B, K, r + dr) -> (B, s, n, dv): the keys'
    expansion moved onto the queries and the values' onto the context."""
    dn = cfg.mla_nope_dim
    wkvb = _kvb(p, cfg, latent.dtype)
    q_cat = _absorbed_queries(q_nope, q_rope, wkvb[..., :dn])  # against [c~ | k_r]: one pass
    return _absorbed_values(_plain_context(q_cat, latent, q_pos, cfg), wkvb[..., dn:])


def attend_window(q_nope, q_rope, stacked, layer: int, offsets, p: Params, cfg):
    """`attend_absorbed` for the windows at ``offsets`` (scalar | (B,)) of rows [0, B)
    of the stacked cache. Inside `mla_decode.decode_path`'s envelope the middle is
    the kernel `mla_decode`, which reads the stacked cache in place and a row up to
    its window's end; outside it the plain body over the layer's whole slab."""
    from galvatron_tpu.models import generation

    b, s, n = q_nope.shape[:3]
    dn, r = cfg.mla_nope_dim, cfg.mla_kv_rank
    wkvb = _kvb(p, cfg, stacked.dtype)
    q_cat = _absorbed_queries(q_nope, q_rope, wkvb[..., :dn])
    first = jnp.broadcast_to(jnp.reshape(jnp.asarray(offsets, jnp.int32), (-1,)), (b,))
    if mla_decode.decode_path(*stacked.shape[2:], s * n, r, stacked.dtype) == "kernel":
        ctx = mla_decode.latent_attention(q_cat, stacked, layer, first, rank=r,
                                          scale=softmax_scale(cfg))
    else:
        ctx = _plain_context(q_cat, generation.read_layer(stacked, layer, None),
                             first[:, None] + jnp.arange(s)[None], cfg)
    return _absorbed_values(ctx, wkvb[..., dn:])


def cache_read_positions(cfg, lengths, rows: int, positions: int, window: int = 1) -> int:
    """Positions ONE layer's attention of a decode window of ``window`` queries a
    row fetches by construction, of a cache of ``rows`` slots x ``positions``:
    ``lengths`` are the positions the windows of the rows in use attend, a row out of
    use attends position 0 of its free slot. The kernel fetches a row's length
    rounded up to the key block, the plain body every row's capacity (host
    arithmetic; `attend_window`'s own choice of body)."""
    path = mla_decode.decode_path(positions, cfg.mla_kv_rank + cfg.mla_rope_dim,
                                  window * cfg.num_heads, cfg.mla_kv_rank, cfg.dtype)
    if path != "kernel":
        return rows * positions
    block = mla_decode.KEY_BLOCK
    return (sum(-(-int(n) // block) for n in lengths) + rows - len(lengths)) * block


def key_block(positions: int) -> int:
    """Latent positions a step of the chunk form takes (`modeling.key_block` up to
    ``KEY_BLOCK``)."""
    return modeling.key_block(positions, KEY_BLOCK)


def _plain_chunk(q_nope, q_rope, stacked, layer: int, slot, offset, p: Params, cfg):
    """The chunk form as XLA runs it: a `fori_loop` over blocks of `key_block` keys up
    to the chunk's end (a traced trip count: blocks past it are neither read nor
    expanded), each expanded through ``W_kvb`` and attended with a running softmax
    whose float32 scores pass through HBM. What runs outside the kernel's envelope
    (`mla_prefill.chunk_path`), and the kernel's reference."""
    n, _, _, dv, _ = dims(cfg)
    s = q_nope.shape[1]
    positions, width = stacked.shape[2], stacked.shape[3]
    block = key_block(positions)
    q_pos = (offset + jnp.arange(s))[None]

    def scored(j):
        latent = jax.lax.dynamic_slice(
            stacked, (layer, slot, j * block, 0), (1, 1, block, width))[0]
        scores, v = _expanded_scores(q_nope, q_rope, latent, p, cfg, q_pos,
                                     j * block + jnp.arange(block))
        return scores, lambda e: jnp.einsum(
            "bnqk,bknd->bnqd", e.astype(q_nope.dtype), v, preferred_element_type=F32)

    # (block 0 holds position 0, which every query sees: the maximum is real from the start)
    o = modeling.running_softmax((offset + s + block - 1) // block, scored, (1, n, s), dv)
    return jnp.transpose(o, (0, 2, 1, 3)).astype(q_nope.dtype)


def _chunk_path(cfg, rows: int, positions: int) -> str:
    return mla_prefill.chunk_path(positions, cfg.mla_kv_rank + cfg.mla_rope_dim, rows, dims(cfg),
                                  cfg.dtype)


def attend_chunk(q_nope, q_rope, stacked, layer: int, slot, offset, p: Params, cfg):
    """`attend_expanded` for the chunk at ``offset`` of row ``slot`` of the stacked
    cache, a block of keys at a time up to the chunk's end with a running softmax.
    Inside `mla_prefill.chunk_path`'s envelope all of it is the kernel `mla_chunk`
    (the expansion included, so it runs under ``expand``), which reads the stacked
    cache in place and keeps the scores on the chip; outside it `_plain_chunk`."""
    if _chunk_path(cfg, q_nope.shape[1], stacked.shape[2]) != "kernel":
        return _plain_chunk(q_nope, q_rope, stacked, layer, slot, offset, p, cfg)
    with jax.named_scope("expand"):
        return mla_prefill.latent_chunk_attention(
            q_nope, q_rope, stacked, layer, slot, offset, _kvb(p, cfg, stacked.dtype),
            dims=dims(cfg), scale=softmax_scale(cfg))


def chunk_layout(cfg, rows: int, positions: int) -> dict:
    """Which body `attend_chunk` takes for prompt chunks of ``rows`` queries over slots
    of ``positions`` and the keys a block of that body fetches: fixed by the shapes,
    so an engine asks once when it is built (`generation.chunk_layout`)."""
    path = _chunk_path(cfg, rows, positions)
    block = mla_prefill.KEY_BLOCK if path == "kernel" else key_block(positions)
    return {"chunk_path": path, "chunk_key_block": block}


@jax.named_scope("out_proj")
def output(o, p: Params, dtype):
    b, s = o.shape[:2]
    return _matmul(o.reshape(b, s, -1).astype(dtype), p["wo"])


def block(x, p: Params, cfg, place: Placement = LOCAL):
    """The layer without a cache (training, evaluation): the non-absorbed form over
    the sequence's own keys, XLA's attention at q.k width dn + dr and p.v width dv."""
    s = x.shape[1]
    q_nope, q_rope, latent = project(x, p, cfg, modeling.rope_tables(cfg, s))
    with jax.named_scope("attn_core"):
        o = place.constrain_attn_out(
            attend_expanded(q_nope, q_rope, latent, p, cfg, jnp.arange(s)[None]))
    return output(o, p, x.dtype)


def cached_block(x, p: Params, cfg, cache: LatentCache, layer: int, starts, slot, offsets,
                 cos_sin):
    """The layer over the cache (``models/generation.forward_with_cache``): write
    the new positions' ``[c~ | k_r]`` at ``starts``, then attend over the cache:
    the chunk form for one request's prompt chunk (``slot``), the absorbed form
    otherwise. -> (y, cache)."""
    from galvatron_tpu.models import generation

    q_nope, q_rope, new = project(x, p, cfg, cos_sin)
    with jax.named_scope("cache_write"):
        stacked = generation.write_layer(cache.latent, layer, new, starts)
    with jax.named_scope("attn_core"):
        if slot is not None:
            o = attend_chunk(q_nope, q_rope, stacked, layer, slot, offsets, p, cfg)
        else:
            o = attend_window(q_nope, q_rope, stacked, layer, offsets, p, cfg)
    return output(o, p, x.dtype), LatentCache(stacked)
