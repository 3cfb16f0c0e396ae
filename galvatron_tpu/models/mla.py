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

A dots3_note-class stack (``ModelConfig.mla_q_rank`` ... ``swa_*``) adds, each by its
field of the layer's VIEW: low-rank queries with their own RMSNorm, the low-rank
rescale, a headwise sigmoid gate before ``W_o``, and

- the INDEXER of learned sparse attention on a full layer (``mla_index_topk``):
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` over the index heads j, float32,
  one index key ``kI`` a position kept in a stack of its own beside the latent
  (`LatentCache.index`); a query attends the ``mla_index_topk`` positions at or before
  it with the largest I (ties to the lower position; all of them while there are no
  more), EXACTLY (`select_mask`: no approximate top-k), and the softmax runs over those
  keys alone. Every
  form attends under the selection's MASK (`select_mask`), a block of keys at a time
  with a running softmax: a decode step scores the index keys up to the longest row's
  length and attends in the absorbed form (`attend_masked`: the kernel `mla_decode`
  with the selection as an operand, each row up to its OWN length; a gather of the
  selected latents made the chip copy the whole stack a layer: its docstring), a prompt
  chunk (and the training block) in the chunk form;
- a WINDOW layer (``attn_window``, the view's own sizes) whose latent lives in a RING
  (`LatentCache.ring`: position p at ``p mod R``, `generation.write_ring`), masked by
  the absolute position a place holds: the absorbed form over the ring for a decode
  window (`attend_ring`: the kernel `mla_decode` with the window as its ``span`` where
  `mla_decode.decode_path` says so, each row's key blocks along the ARC its window holds
  and no other; XLA's over every place of the ring elsewhere), the chunk form over the
  ring's key blocks for a prompt chunk.

The rest are XLA's bodies (`mla_chunk` takes no mask and no ring). Such
a stack's scopes lie one level deeper, under ``attn`` > ``full``
| ``window`` (the windowed K/V stacks' two words), with ``indexer``, ``select`` and
``gate`` beside the four.

Scopes under ``attn``: ``qkv_proj``, ``cache_write``, ``attn_core`` (>
``absorb``: the two absorbed products; > ``expand``: the chunk form's ``W_kvb``
expansion, which is the kernel ``mla_chunk`` where that runs; the kernel
``mla_decode`` directly under it), ``out_proj`` (PERF.md §3; the ``mla_*`` benchmark
metrics read them).
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, NamedTuple, Optional

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


#: index keys a step of the indexer's scores takes at most (fewer where a block's
#: float32 scores a head would pass `INDEX_SCORES`)
INDEX_BLOCK = 1024
INDEX_SCORES = 1 << 24


class LatentCache(NamedTuple):
    """``[c~ | rotated k_r]`` of every position: (layers, rows, positions, r + dr). A
    stack with an indexer or window layers (`stacks`) keeps more, each stack over ITS
    layers only (`generation.layer_stacks`): ``latent`` the full layers' whole slots,
    ``index`` their index keys (L_full, rows, positions, index dim), ``ring`` the window
    layers' latent (L_win, rows, R, r_w + dr_w), position p at ``p mod R``."""

    latent: jax.Array
    index: Optional[jax.Array] = None
    ring: Optional[jax.Array] = None


def dims(cfg):
    """(heads, nope, rope, value, rank) of the layer."""
    return cfg.num_heads, cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim, cfg.mla_kv_rank


def softmax_scale(cfg) -> float:
    """``(dn + dr)^-1/2 m^2``, ``m`` YaRN's factor at ``mscale_all_dim`` (1 without)."""
    _, dn, dr, _, _ = dims(cfg)
    m = modeling.yarn_mscale(cfg.rope_yarn[0], cfg.rope_yarn[5]) if cfg.rope_yarn else 1.0
    return float((dn + dr) ** -0.5 * m * m)


# -- the kind's prices (search/theoretical.py) ----------------------------------


def stacks(cfg) -> bool:
    """The stack's cache is more than one latent array a layer alike: some layer has an
    indexer (index keys beside the latent) or a window (a ring)."""
    return bool(cfg.mla_index_topk) or cfg.windowed


def param_count(cfg) -> int:
    """The mixer's parameters under ``cfg``, the LAYER's view (a window layer: its own)."""
    n, dn, dr, dv, r = dims(cfg)
    h, rq = cfg.hidden_size, cfg.mla_q_rank
    queries = h * rq + rq + rq * n * (dn + dr) if rq else h * n * (dn + dr)
    count = queries + h * (r + dr) + r + r * n * (dn + dv) + n * dv * h
    if cfg.mla_head_gate:
        count += h * n
    if cfg.mla_index_topk:
        hi, di = cfg.mla_index_heads, cfg.mla_index_dim
        count += rq * hi * di + h * di + 2 * di + h * hi
    return count


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
    """Bytes ONE layer's latent holds a position (``cfg``: the layer's view)."""
    _, _, dr, _, r = dims(cfg)
    return (r + dr) * jnp.dtype(cfg.dtype).itemsize


def _views(cfg):
    """(a full layer's view or None, a window layer's or None) of the stack."""
    views = [cfg.layer_view(i) for i in range(cfg.num_layers)]
    return (next((v for v in views if not v.attn_window), None),
            next((v for v in views if v.attn_window), None))


def init_cache(cfg, layers: int, rows: int, positions: int, tokens: int = 1) -> LatentCache:
    """``tokens``: the most positions one forward writes a row, which sizes a ring."""
    from galvatron_tpu.models import generation

    full, win = _views(cfg)
    n_win = sum(cfg.window_layers)
    n_full = layers - n_win
    cache = LatentCache(jnp.zeros(
        (n_full, rows, positions, (full or cfg).mla_kv_rank + (full or cfg).mla_rope_dim),
        cfg.dtype))
    if cfg.mla_index_topk:
        cache = cache._replace(
            index=jnp.zeros((n_full, rows, positions, cfg.mla_index_dim), cfg.dtype))
    if n_win:
        ring = generation.ring_positions(cfg, positions, tokens)
        cache = cache._replace(
            ring=jnp.zeros((n_win, rows, ring, win.mla_kv_rank + win.mla_rope_dim), cfg.dtype))
    return cache


def cache_layout(cfg, max_len: Optional[int] = None, tokens: int = 1) -> Optional[dict]:
    """What `stacks`' cache costs, by stack (None: every layer alike, the caller's own
    arithmetic): the layers of each, ONE layer's bytes a position of the full layers'
    latent, of their index keys and of the window layers' ring and, once ``max_len``
    is known, the ring's positions and a slot's bytes over all three."""
    from galvatron_tpu.models import generation

    if not stacks(cfg):
        return None
    full, win = _views(cfg)
    item = jnp.dtype(cfg.dtype).itemsize
    n_win = sum(cfg.window_layers)
    out = {"kind": "latent", "latent_stacks": True,
           "full_layers": cfg.num_layers - n_win, "window_layers": n_win,
           "window": cfg.sliding_window_size if n_win else 0,
           "latent_bytes_per_position": cache_bytes_per_position(full) if full else 0,
           "index_bytes_per_position": cfg.mla_index_dim * item if cfg.mla_index_topk else 0,
           "ring_bytes_per_position": cache_bytes_per_position(win) if win else 0,
           "index_topk": cfg.mla_index_topk, "index_heads": cfg.mla_index_heads,
           # (what `mla_decode.decode_path` asks of a full layer's decode window)
           "full_window": (full.num_heads, full.mla_kv_rank + full.mla_rope_dim,
                           full.mla_kv_rank, jnp.dtype(cfg.dtype).name) if full else None,
           # (and of a window layer's over its ring)
           "ring_window": (win.num_heads, win.mla_kv_rank + win.mla_rope_dim,
                           win.mla_kv_rank, jnp.dtype(cfg.dtype).name) if win else None}
    if max_len is not None:
        ring = generation.ring_positions(cfg, max_len, tokens) if n_win else 0
        if n_win:
            # (which body a decode step's attention over the ring takes, `attend_ring`'s own
            # question, and the key block the kernel reads it in: 0 on the plain body)
            path = _ring_path(win, ring)
            out.update(ring_positions=ring, ring_decode_path=path,
                       ring_key_block=mla_decode.ring_block(ring, win.attn_window)
                       if path == "kernel" else 0)
        out["bytes_per_slot"] = (
            out["full_layers"] * max_len * (out["latent_bytes_per_position"]
                                            + out["index_bytes_per_position"])
            + n_win * ring * out["ring_bytes_per_position"])
    return out


def step_counters(layout: dict, lengths, rows: int, positions: int, window: int = 1) -> dict:
    """What a decode iteration of `stacks`' cache works on and FETCHES by construction,
    from the rows' lengths (host arithmetic): ``lengths`` the positions the windows of
    the rows in use attend, ``rows`` x ``positions`` the slots. A full layer reads the
    index keys of EVERY row a block at a time up to the LONGEST row's end
    (`index_scores`), and the latents the same way (`attend_masked`'s loop) or, through
    the kernel `mla_decode`, each row up to its OWN length in whole key blocks (a row out
    of use one block), where its selection keeps ``min(n, topk)`` a row; a window layer
    reads every row's whole ring on the plain body and, through the kernel, the key
    blocks each row's arc touches (`mla_decode.ring_read_positions`);
    ``ring_decode_path`` says which (`attend_ring`'s own question, asked of this
    ``window``), so that an iteration's span carries it."""
    topk = layout["index_topk"] or positions
    span, ring = layout["window"], layout.get("ring_positions", 0)
    out = {"dsa_live_positions": sum(lengths),
           "dsa_selected_positions": sum(min(n, topk) for n in lengths),
           "dsa_full_layers": layout["full_layers"],
           "latent_ring_layers": layout["window_layers"],
           "dsa_latent_bytes_per_position": layout["latent_bytes_per_position"],
           "dsa_index_bytes_per_position": layout["index_bytes_per_position"],
           "latent_ring_bytes_per_position": layout["ring_bytes_per_position"]}
    longest = max(lengths, default=1)

    def read(block):  # every row's places up to the longest row's end, in whole blocks
        return rows * min(positions, -(-longest // block) * block)

    heads, width, rank, dtype = layout["full_window"]
    if mla_decode.decode_path(positions, width, window * heads, rank, dtype) == "kernel" and (
            window == 1):
        block = mla_decode.KEY_BLOCK
        out["dsa_read_positions"] = block * (
            sum(-(-n // block) for n in lengths) + rows - len(lengths))
    else:
        out["dsa_read_positions"] = read(key_block(positions))
    out["dsa_index_read_positions"] = (
        read(_index_block(rows * window * layout["index_heads"], positions))
        if layout["index_topk"] else 0)
    out["latent_ring_live_positions"] = sum(min(n, span) for n in lengths) if span else 0
    if ring:
        heads, width, rank, dtype = layout["ring_window"]
        out["ring_decode_path"] = mla_decode.decode_path(ring, width, window * heads, rank, dtype,
                                                         span=span)
    out["latent_ring_read_positions"] = (
        mla_decode.ring_read_positions(lengths, rows, ring, span, window)
        if out.get("ring_decode_path") == "kernel" else rows * ring)
    return out


# -- parameters ---------------------------------------------------------------------


def init_params(key, cfg) -> Params:
    """``cfg``: the layer's view."""
    n, dn, dr, dv, r = dims(cfg)
    h, rq = cfg.hidden_size, cfg.mla_q_rank
    ks = jax.random.split(key, 4)
    p = {
        "wkva": modeling._dense_init(ks[1], h, r + dr, cfg.param_dtype),
        "kv_norm": jnp.ones((r,), cfg.param_dtype),
        # (a head's columns: [k_nope | v])
        "wkvb": modeling._dense_init(ks[2], r, n * (dn + dv), cfg.param_dtype),
        "wo": modeling._dense_init(ks[3], n * dv, h, cfg.param_dtype),
    }
    if not rq:
        p["wq"] = modeling._dense_init(ks[0], h, n * (dn + dr), cfg.param_dtype)
        return p
    kq = jax.random.split(ks[0], 6)
    p.update(wqa=modeling._dense_init(kq[0], h, rq, cfg.param_dtype),
             q_norm=jnp.ones((rq,), cfg.param_dtype),
             wqb=modeling._dense_init(kq[1], rq, n * (dn + dr), cfg.param_dtype))
    if cfg.mla_head_gate:
        p["wgate"] = modeling._dense_init(kq[2], h, n, cfg.param_dtype)
    if cfg.mla_index_topk:
        hi, di = cfg.mla_index_heads, cfg.mla_index_dim
        p["index"] = {
            "wq": modeling._dense_init(kq[3], rq, hi * di, cfg.param_dtype),
            "wk": modeling._dense_init(kq[4], h, di, cfg.param_dtype),
            "k_norm": {"scale": jnp.ones((di,), cfg.param_dtype),
                       "bias": jnp.zeros((di,), cfg.param_dtype)},
            "ww": modeling._dense_init(kq[5], h, hi, cfg.param_dtype),
        }
    return p


def annotations(cfg) -> Params:
    """ZeRO shards the hidden-size dims; no dim is tensor-parallel (``lacks``)."""
    a = {"wkva": ("fsdp", None), "kv_norm": (None,), "wkvb": (None, None), "wo": (None, "fsdp")}
    if not cfg.mla_q_rank:
        return dict(a, wq=("fsdp", None))
    a.update(wqa=("fsdp", None), q_norm=(None,), wqb=(None, None))
    if cfg.mla_head_gate:
        a["wgate"] = ("fsdp", None)
    if cfg.mla_index_topk:
        a["index"] = {"wq": (None, None), "wk": ("fsdp", None),
                      "k_norm": {"scale": (None,), "bias": (None,)}, "ww": ("fsdp", None)}
    return a


# -- the layer ------------------------------------------------------------------------


def _matmul(x, w):
    """``x @ w``; int8 weights (serving, ops.quant) dequantize inside the GEMM."""
    return qmatmul(x, w) if isinstance(w, QuantTensor) else x @ w.astype(x.dtype)


def _queries(x, p: Params, cfg):
    """(q (B, s, n, dn + dr) before rotary; the normed query latent c_q (B, s, rq) the
    indexer reads, None where the queries are one projection)."""
    n, dn, dr, _, _ = dims(cfg)
    b, s, h = x.shape
    if not cfg.mla_q_rank:
        return _matmul(x, p["wq"]).reshape(b, s, n, dn + dr), None
    c_q = modeling._norm_impl(_matmul(x, p["wqa"]), {"scale": p["q_norm"]}, cfg)
    q = _matmul(c_q, p["wqb"])
    if cfg.mla_rescale:
        q = q * (h / cfg.mla_q_rank) ** 0.5
    return q.reshape(b, s, n, dn + dr), c_q


@jax.named_scope("qkv_proj")
def _project(x, p: Params, cfg, cos_sin):
    """`project` and the query latent beside it (`_queries`)."""
    _, dn, _, _, r = dims(cfg)
    q, c_q = _queries(x, p, cfg)
    ckr = _matmul(x, p["wkva"])
    c = modeling._norm_impl(ckr[..., :r], {"scale": p["kv_norm"]}, cfg)  # RMSNorm over the latent
    if cfg.mla_rescale:  # (on the normed latent, so on k_nope AND v; the cache holds it scaled)
        c = c * (x.shape[-1] / r) ** 0.5
    k_r = modeling.apply_rope(ckr[..., None, r:], *cos_sin)[..., 0, :]
    return (q[..., :dn], modeling.apply_rope(q[..., dn:], *cos_sin),
            jnp.concatenate([c, k_r], -1), c_q)


def project(x, p: Params, cfg, cos_sin):
    """x (B, s, h) -> (q_nope (B, s, n, dn), rotated q_rope (B, s, n, dr), the
    positions' cache entries ``[c~ | rotated k_r]`` (B, s, r + dr))."""
    return _project(x, p, cfg, cos_sin)[:3]


def _kvb(p: Params, cfg, dtype):
    """``W_kvb`` as (r, n, dn + dv): a head's keys' and values' expansion."""
    n, dn, _, dv, r = dims(cfg)
    return p["wkvb"].astype(dtype).reshape(r, n, dn + dv)


def _allowed(q_pos, k_pos):
    """(B | 1, 1, s, K): key j is at or before query i."""
    return (k_pos[None, None, :] <= q_pos[:, :, None])[:, None]


def _expanded_scores(q_nope, q_rope, latent, p: Params, cfg, q_pos, k_pos, visible=None):
    """``latent`` (B, K, r + dr) expanded through ``W_kvb`` -> (the masked, scaled
    float32 scores (B, n, s, K) of queries at ``q_pos`` (B | 1, s) against keys at
    ``k_pos`` (K,), the values (B, K, n, dv)). ONE product over [nope | rope] with
    the shared rotary key repeated a head: the float32 scores are written once (two
    products and their sum were 3 passes over them, and the chunk form is bound by
    those passes: PERF.md section 6, PR 51). ``visible`` (B | 1, s, K): the keys a
    query sees where that is not every key at or before it (a window, a selection)."""
    n, dn, _, _, r = dims(cfg)
    with jax.named_scope("expand"):
        kv = jnp.einsum("bkr,rnd->bknd", latent[..., :r], _kvb(p, cfg, latent.dtype))
    k_rope = jnp.broadcast_to(latent[:, :, None, r:], latent.shape[:2] + (n, latent.shape[-1] - r))
    scores = jnp.einsum("bqnd,bknd->bnqk", jnp.concatenate([q_nope, q_rope], axis=-1),
                        jnp.concatenate([kv[..., :dn], k_rope], axis=-1),
                        preferred_element_type=F32)
    allowed = _allowed(q_pos, k_pos) if visible is None else visible[:, None]
    scores = jnp.where(allowed, scores * softmax_scale(cfg), modeling.MASKED_SCORE)
    return scores, kv[..., dn:]


def attend_expanded(q_nope, q_rope, latent, p: Params, cfg, q_pos, visible=None):
    """The NON-ABSORBED form over ``latent`` (B, K, r + dr), all of it at once:
    -> (B, s, n, dv). ``q_pos`` (B | 1, s): the queries' absolute positions."""
    scores, v = _expanded_scores(q_nope, q_rope, latent, p, cfg, q_pos,
                                 jnp.arange(latent.shape[1]), visible)
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


def _plain_chunk(q_nope, q_rope, stacked, layer: int, slot, offset, p: Params, cfg,
                 selected=None):
    """The chunk form as XLA runs it: a `fori_loop` over blocks of `key_block` keys up
    to the chunk's end (a traced trip count: blocks past it are neither read nor
    expanded), each expanded through ``W_kvb`` and attended with a running softmax
    whose float32 scores pass through HBM. What runs outside the kernel's envelope
    (`mla_prefill.chunk_path`), and the kernel's reference. ``selected`` (1, s,
    positions): the keys the indexer kept a query, in place of every key at or before
    it; ``cfg.attn_window``: ``stacked`` is a RING, its blocks masked by the absolute
    positions they hold and read up to the chunk's end until the ring has lapped
    (`generation.chunk_key_blocks`)."""
    from galvatron_tpu.models import generation

    n, _, _, dv, _ = dims(cfg)
    s = q_nope.shape[1]
    positions, width = stacked.shape[2], stacked.shape[3]
    block = key_block(positions)
    q_pos = (offset + jnp.arange(s))[None]
    blocks = (offset + s + block - 1) // block
    if cfg.attn_window:
        blocks = jnp.minimum(positions // block, blocks)

    def scored(j):
        latent = jax.lax.dynamic_slice(
            stacked, (layer, slot, j * block, 0), (1, 1, block, width))[0]
        places = j * block + jnp.arange(block)
        visible = None
        if cfg.attn_window:
            held = generation._ring_key_positions(offset + s - 1, places, positions)
            visible = _in_window(q_pos, held, cfg.attn_window)
        elif selected is not None:
            visible = jax.lax.dynamic_slice(selected, (0, 0, j * block), (1, s, block))
        scores, v = _expanded_scores(q_nope, q_rope, latent, p, cfg, q_pos, places, visible)
        return scores, lambda e: jnp.einsum(
            "bnqk,bknd->bnqd", e.astype(q_nope.dtype), v, preferred_element_type=F32)

    # (block 0 holds position 0, which every query sees: the maximum is real from the
    # start; under a selection or a window a query's first seen key may lie in a later
    # block, and `running_softmax` shrinks what the masked blocks counted to an exact 0)
    o = modeling.running_softmax(blocks, scored, (1, n, s), dv)
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
    if stacks(cfg):  # (a selection or a window is the plain body's)
        return {"chunk_path": "plain", "chunk_key_block": key_block(positions)}
    path = _chunk_path(cfg, rows, positions)
    block = mla_prefill.KEY_BLOCK if path == "kernel" else key_block(positions)
    return {"chunk_path": path, "chunk_key_block": block}


# -- a window over a latent, the indexer and its selection (`stacks`) ---------------------


def _in_window(q_pos, k_pos, window: int):
    """(B | 1, s, K): the key at absolute position ``k_pos`` (B | 1, K; negative: the
    place holds nothing of this row yet) is at or before the query at ``q_pos`` (B | 1,
    s) and, with a window, among its last ``window`` positions, its own included."""
    kp, qp = k_pos[:, None, :], q_pos[:, :, None]
    seen = (kp <= qp) & (kp >= 0)
    return seen & (kp > qp - window) if window else seen


def _masked_context(q_cat, latent, visible, cfg):
    """`_plain_context` under a mask ``visible`` (B | 1, s, K) in place of causality."""
    scores = jnp.einsum("bqnc,bkc->bnqk", q_cat, latent, preferred_element_type=F32)
    scores = jnp.where(visible[:, None], scores * softmax_scale(cfg), modeling.MASKED_SCORE)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_cat.dtype)
    return jnp.einsum("bnqk,bkc->bqnc", probs, latent)[..., :cfg.mla_kv_rank]


def _ring_path(cfg, places: int, window: int = 1, dtype=None) -> str:
    """`mla_decode.decode_path` asked of a ring of ``places`` places (of ``dtype``; None:
    ``cfg.dtype``, what `init_cache` makes) under ``cfg``, a window layer's view, for decode
    windows of ``window`` queries a row."""
    return mla_decode.decode_path(
        places, cfg.mla_kv_rank + cfg.mla_rope_dim, window * cfg.num_heads, cfg.mla_kv_rank,
        dtype or cfg.dtype, span=cfg.attn_window)


def attend_ring(q_nope, q_rope, ring, layer: int, offsets, p: Params, cfg):
    """`attend_absorbed` for the windows at ``offsets`` (scalar | (B,)) of rows [0, B) of
    the stacked RING (L_win, rows, R, r + dr), the last ``cfg.attn_window`` positions
    seen. Inside `mla_decode.decode_path`'s envelope the middle is the kernel
    `mla_decode` over the ring where it lies, each row's key blocks along the ARC its
    window holds (`mla_decode.ring_block` places a block); outside it the plain body:
    every place of a row's ring against the absolute position it holds
    (`generation._ring_key_positions`)."""
    from galvatron_tpu.models import generation

    b, s = q_nope.shape[:2]
    dn, r, places = cfg.mla_nope_dim, cfg.mla_kv_rank, ring.shape[2]
    wkvb = _kvb(p, cfg, ring.dtype)
    q_cat = _absorbed_queries(q_nope, q_rope, wkvb[..., :dn])
    first = jnp.reshape(jnp.asarray(offsets, jnp.int32), (-1,))
    if _ring_path(cfg, places, s, ring.dtype) == "kernel":
        ctx = mla_decode.latent_attention(
            q_cat, ring, layer, jnp.broadcast_to(first, (b,)), rank=r, scale=softmax_scale(cfg),
            span=cfg.attn_window, block_k=mla_decode.ring_block(places, cfg.attn_window))
    else:
        held = generation._ring_key_positions(first + s - 1, jnp.arange(places), places)
        visible = _in_window(first[:, None] + jnp.arange(s)[None], held, cfg.attn_window)
        ctx = _masked_context(q_cat, generation.read_layer(ring, layer, None), visible, cfg)
    return _absorbed_values(ctx, wkvb[..., dn:])


def index_project(x, c_q, p: Params, cfg, cos_sin):
    """The indexer's three projections of the block's normed input ``x`` and the query
    latent ``c_q`` -> (qI (B, s, hi, di) and kI (B, s, di), rotary on the first dr dims
    of each; the heads' weights w (B, s, hi) float32, times hi^-1/2 di^-1/2)."""
    hi, di, dr = cfg.mla_index_heads, cfg.mla_index_dim, cfg.mla_rope_dim
    b, s, _ = x.shape
    ip = p["index"]

    def rotated(t):  # (B, s, heads, di)
        return jnp.concatenate([modeling.apply_rope(t[..., :dr], *cos_sin), t[..., dr:]], -1)

    qi = rotated(_matmul(c_q, ip["wq"]).reshape(b, s, hi, di))
    # (a LayerNorm, scale and bias, over the index key's di)
    ki = modeling._norm_impl(_matmul(x, ip["wk"]), ip["k_norm"], cfg.replace(norm_type="layernorm"))
    ki = rotated(ki[:, :, None])[:, :, 0]
    w = _matmul(x, ip["ww"]).astype(F32) * float(hi ** -0.5 * di ** -0.5)
    return qi, ki, w


def _index_block(queries: int, positions: int) -> int:
    """Index keys a step of `index_scores` takes: up to ``INDEX_BLOCK``, fewer where
    ``queries`` x index heads x block float32 scores would pass ``INDEX_SCORES``."""
    return modeling.key_block(positions, max(8, min(INDEX_BLOCK, INDEX_SCORES // max(1, queries))))


def index_scores(qi, w, keys, q_pos, live=None, at=None):
    """``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` in float32 of queries ``qi``
    (B, s, hi, di) at ``q_pos`` (B | 1, s) against index keys ``keys`` (B, K, di) at
    positions [0, K): -> (B, s, K), ``-inf`` where the key lies after the query. A
    block of keys at a time, so that one block's scores a head live at once, and up to
    ``live`` keys (traced; None: all K): blocks past it are not read and stay ``-inf``.
    ``at`` = (layer, slot | None): ``keys`` is the STACKED index cache (L, rows, K, di),
    read in place a block at a time: rows [0, B), or the one row ``slot``."""
    b, s, hi, di = qi.shape
    k = keys.shape[-2]
    block = _index_block(b * s * hi, k)

    def one(j, out):
        if at is None:
            kb = jax.lax.dynamic_slice_in_dim(keys, j * block, block, axis=1)
        else:
            kb = jax.lax.dynamic_slice(
                keys, (at[0], 0 if at[1] is None else at[1], j * block, 0), (1, b, block, di))[0]
        dots = jnp.einsum("bqjd,bkd->bqjk", qi, kb, preferred_element_type=F32)
        part = jnp.einsum("bqjk,bqj->bqk", jax.nn.relu(dots), w)
        k_pos = j * block + jnp.arange(block)
        part = jnp.where(k_pos[None, None, :] <= q_pos[:, :, None], part, -jnp.inf)
        return jax.lax.dynamic_update_slice_in_dim(out, part, j * block, axis=2)

    blocks = k // block if live is None else jnp.minimum(k // block, (live + block - 1) // block)
    return jax.lax.fori_loop(0, blocks, one, jnp.full((b, s, k), -jnp.inf, F32))


def select_mask(scores, topk: int):
    """The keys a query attends, (B, s, K) bool, from the indexer's ``scores`` (``-inf``
    where a key is not allowed): the ``topk`` largest, ties to the LOWER position; every
    allowed key while there are no more than ``topk``. Exact, and no sort: the k-th largest
    score a query by 32 steps of bisection over the floats' order
    (`generation._largest_threshold`: one masked count over the row a step), the keys above
    it, and of the keys that equal it the first few. (`lax.top_k` of 2,048 out of 20,480 is
    a sort of the whole row on this chip: 0.5 ms a layer for a decode step's 32 rows, and
    1,024 rows a prompt chunk.)"""
    from galvatron_tpu.models import generation

    allowed = scores > -jnp.inf
    if topk >= scores.shape[-1]:
        return allowed
    keys = generation._order_keys(scores.reshape(-1, scores.shape[-1]))
    rows = keys.shape[0]
    kth = generation._largest_threshold(keys, jnp.int32(1), jnp.full((rows,), topk, jnp.int32),
                                        jnp.ones((rows,), bool))
    keys, kth = keys.reshape(scores.shape), kth.reshape(scores.shape[:-1] + (1,))
    above = keys > kth
    ties = (keys == kth) & allowed
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    return above | (ties & (jnp.cumsum(ties, axis=-1) <= room))


def attend_masked(q_nope, q_rope, stacked, layer: int, visible, first, p: Params, cfg):
    """The ABSORBED form of rows' windows over rows [0, B) of the stacked cache under a mask
    ``visible`` (B | 1, s, positions) (the indexer's selection), a block of keys at a time
    with a running softmax up to the longest row's end (``first`` (B,): the rows' first
    queries' positions; blocks past it are not read). Every read is a ``dynamic_slice`` of the stacked array where it
    lies: a GATHER of the selected latents out of it made the chip's compiler copy the
    whole stack once a layer and step (2 x 1.5 GB = 9.8 ms of a 23.4 ms step, my chip run,
    PR 65, call 1), and fetched the rows at a seventh of the chip's rate besides."""
    b, s, n = q_nope.shape[:3]
    dn, r = cfg.mla_nope_dim, cfg.mla_kv_rank
    positions, width = stacked.shape[2], stacked.shape[3]
    block = key_block(positions)
    wkvb = _kvb(p, cfg, stacked.dtype)
    q_cat = _absorbed_queries(q_nope, q_rope, wkvb[..., :dn])
    visible = jnp.broadcast_to(visible, (b, s, positions))
    if s == 1 and mla_decode.decode_path(positions, width, n, r, stacked.dtype) == "kernel":
        # one query a row: the kernel `mla_decode` under the selection, a row read up to
        # its OWN length (the loop below reads every row up to the longest one's)
        ctx = mla_decode.latent_attention(q_cat, stacked, layer, first, rank=r,
                                          scale=softmax_scale(cfg), selected=visible)
        return _absorbed_values(ctx, wkvb[..., dn:])

    def scored(j):
        latent = jax.lax.dynamic_slice(stacked, (layer, 0, j * block, 0), (1, b, block, width))[0]
        scores = jnp.einsum("bqnc,bkc->bnqk", q_cat, latent, preferred_element_type=F32)
        seen = jax.lax.dynamic_slice_in_dim(visible, j * block, block, axis=2)
        scores = jnp.where(seen[:, None], scores * softmax_scale(cfg), modeling.MASKED_SCORE)
        return scores, lambda e: jnp.einsum(
            "bnqk,bkc->bnqc", e.astype(q_cat.dtype), latent[..., :r], preferred_element_type=F32)

    blocks = jnp.minimum(positions // block, (jnp.max(first) + s + block - 1) // block)
    ctx = modeling.running_softmax(blocks, scored, (b, n, s), r)
    return _absorbed_values(jnp.transpose(ctx, (0, 2, 1, 3)).astype(q_cat.dtype), wkvb[..., dn:])


def head_gate(o, x, p: Params, cfg):
    """``o`` (B, s, n, dv) times ``sigmoid(x W_g)``, one scalar a head, in float32."""
    if not cfg.mla_head_gate:
        return o
    with jax.named_scope("gate"):
        g = jax.nn.sigmoid(_matmul(x, p["wgate"]).astype(F32))
        return (o.astype(F32) * g[..., None]).astype(o.dtype)


@jax.named_scope("out_proj")
def output(o, p: Params, dtype):
    b, s = o.shape[:2]
    return _matmul(o.reshape(b, s, -1).astype(dtype), p["wo"])


def block(x, p: Params, cfg, place: Placement = LOCAL):
    """The layer without a cache (training, evaluation): the non-absorbed form over
    the sequence's own keys, XLA's attention at q.k width dn + dr and p.v width dv;
    under the layer's window or the indexer's selection where its view has one (the
    selection is a constant of the backward pass: the indexer's weights get no gradient
    from the language-model loss, as published)."""
    s = x.shape[1]
    cos_sin = modeling.rope_tables(cfg, s)
    q_nope, q_rope, latent, c_q = _project(x, p, cfg, cos_sin)
    q_pos, visible = jnp.arange(s)[None], None
    if cfg.attn_window:
        visible = _in_window(q_pos, q_pos, cfg.attn_window)
    elif cfg.mla_index_topk:
        with jax.named_scope("indexer"):
            qi, ki, w = index_project(x, c_q, p, cfg, cos_sin)
            scores = index_scores(qi, w, ki, q_pos)
        with jax.named_scope("select"):
            visible = jax.lax.stop_gradient(select_mask(scores, cfg.mla_index_topk))
    with jax.named_scope("attn_core"):
        o = place.constrain_attn_out(
            attend_expanded(q_nope, q_rope, latent, p, cfg, q_pos, visible))
    return output(head_gate(o, x, p, cfg), p, x.dtype)


def _cached_layer(x, p: Params, cfg, cache: LatentCache, layer: int, starts, slot, offsets,
                  cos_sin):
    """`cached_block` under its scope: a window layer over the ring, else a full layer over
    whole slots, under the indexer's selection where its view has one."""
    from galvatron_tpu.models import generation

    s = x.shape[1]
    if cfg.mla_index_topk:
        q_nope, q_rope, new, c_q = _project(x, p, cfg, cos_sin)
    else:
        q_nope, q_rope, new = project(x, p, cfg, cos_sin)
    if cfg.attn_window:
        with jax.named_scope("cache_write"):
            ring = generation.write_ring(cache.ring, layer, new, starts,
                                         aligned=jnp.ndim(offsets) == 0, axis=2)
        with jax.named_scope("attn_core"):
            if slot is not None:
                o = _plain_chunk(q_nope, q_rope, ring, layer, slot, offsets, p, cfg)
            else:
                o = attend_ring(q_nope, q_rope, ring, layer, offsets, p, cfg)
        return output(head_gate(o, x, p, cfg), p, x.dtype), cache._replace(ring=ring)
    with jax.named_scope("cache_write"):
        stacked = generation.write_layer(cache.latent, layer, new, starts)
    selected = None
    if cfg.mla_index_topk:
        first = jnp.reshape(jnp.asarray(offsets, jnp.int32), (-1,))
        q_pos = first[:, None] + jnp.arange(s)[None]
        live = jnp.max(first) + s  # (the chunk's end, or the longest row's)
        with jax.named_scope("indexer"):
            qi, ki, w = index_project(x, c_q, p, cfg, cos_sin)
            index = generation.write_layer(cache.index, layer, ki, starts)
            cache = cache._replace(index=index)

        def selection():
            with jax.named_scope("indexer"):
                scores = index_scores(qi, w, index, q_pos, live=live, at=(layer, slot))
            with jax.named_scope("select"):
                return select_mask(scores, cfg.mla_index_topk)

        if slot is None:
            selected = selection()
        else:
            # a chunk that ends within the first ``index_topk`` positions selects every key
            # at or before a query: no score is needed (the index keys are written all the same)
            selected = jax.lax.cond(
                live <= cfg.mla_index_topk,
                lambda: jnp.arange(index.shape[2])[None, None, :] <= q_pos[:, :, None], selection)
    with jax.named_scope("attn_core"):
        if selected is None and slot is not None:
            o = attend_chunk(q_nope, q_rope, stacked, layer, slot, offsets, p, cfg)
        elif selected is None:
            o = attend_window(q_nope, q_rope, stacked, layer, offsets, p, cfg)
        elif slot is not None:
            o = _plain_chunk(q_nope, q_rope, stacked, layer, slot, offsets, p, cfg, selected)
        else:
            o = attend_masked(q_nope, q_rope, stacked, layer, selected,
                              jnp.broadcast_to(first, x.shape[:1]), p, cfg)
    return output(head_gate(o, x, p, cfg), p, x.dtype), cache._replace(latent=stacked)


def cached_block(x, p: Params, cfg, cache: LatentCache, layer: int, starts, slot, offsets,
                 cos_sin):
    """The layer over the cache (``models/generation.forward_with_cache``): write
    the new positions' ``[c~ | k_r]`` at ``starts``, then attend over the cache:
    the chunk form for one request's prompt chunk (``slot``), the absorbed form
    otherwise. -> (y, cache). ``cfg`` is the layer's view; in a stack of `stacks`
    ``layer`` is the layer's place in its own stack and the scopes lie under ``full`` |
    ``window``."""
    one_stack = cache.index is None and cache.ring is None
    with contextlib.nullcontext() if one_stack else jax.named_scope(
            "window" if cfg.attn_window else "full"):
        return _cached_layer(x, p, cfg, cache, layer, starts, slot, offsets, cos_sin)
