"""Mixture-of-Experts MLPs: the switch path (top-1, capacity, expert
parallelism) and the dropless softmax top-k path (``cfg.moe_router ==
"softmax_topk"``: OLMoE-class models; second half of this file).

Switch-style Mixture-of-Experts MLP with expert parallelism.

Counterpart of the reference's ``SwitchMLP`` (reference:
galvatron/core/tensor_parallel/transformer.py:161-295): a top-1 router with
sinkhorn load balancing during training and expert weights distributed across
data-parallel ranks (expert parallelism; reference group plumbing:
site_package/megatron/core/parallel_state.py:450-478,611-621,890-901).

The TPU-native formulation is the GShard/Mesh-TensorFlow dense-dispatch
recipe rather than the reference's gather/scatter over token lists: a static
per-expert capacity C turns routing into two einsums against a (tokens,
experts, capacity) one-hot dispatch tensor, so every shape is static, the
expert FFN is one big batched matmul on the MXU, and sharding the expert
dimension over the ``ep`` mesh axes makes XLA insert the all-to-all that
Megatron's expert-parallel ``gather_from_sequence_parallel_region`` hand
codes. Tokens overflowing an expert's capacity pass through on the residual
path (standard switch-transformer semantics).

Router normalization is batch-dependent (sinkhorn balances over the routed
token group), so micro-batched execution — pipeline engines and chunked
accumulation route per micro-batch — yields slightly different assignments
than one full-batch forward (measured ~0.2% on a tiny model's eval loss at
chunks=2). This is inherent to capacity-style MoE under micro-batching (the
reference's SwitchMLP normalizes per forward call the same way), not an
engine discrepancy: at chunks=1 the pipeline path is exact against the flat
model (pinned in test_moe.py::test_moe_pipeline_parallel_parity).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from galvatron_tpu.models.placement import LOCAL, Placement

Params = Dict[str, Any]


def sinkhorn(logits: jax.Array, n_iters: int = 8) -> jax.Array:
    """Sinkhorn-normalized routing scores (balanced assignment), fixed
    iteration count for XLA (the reference iterates to tolerance on host,
    transformer.py:163-174 — data-dependent loops don't trace)."""
    cost = jnp.exp(logits - jax.lax.stop_gradient(logits.max()))
    T, E = cost.shape
    d1 = jnp.ones((E,), cost.dtype)

    def body(_, d1):
        d0 = 1.0 / (T * (cost @ d1 + 1e-8))
        return 1.0 / (E * (d0 @ cost + 1e-8))

    d1 = jax.lax.fori_loop(0, n_iters, body, d1)
    d0 = 1.0 / (T * (cost @ d1 + 1e-8))
    return cost * d0[:, None] * d1[None, :]


def moe_capacity(num_tokens: int, num_experts: int, capacity_factor: float) -> int:
    """Static per-expert token capacity, padded to a multiple of 8 for TPU
    tiling."""
    c = int(np.ceil(num_tokens / num_experts * capacity_factor))
    return max(8, (c + 7) // 8 * 8)


def route_top1(logits: jax.Array, capacity: int, *, sinkhorn_iters: int = 8,
               train: bool = True):
    """Top-1 switch routing with capacity limiting.

    During training the assignment comes from the sinkhorn-balanced scores; at
    inference it is the raw-logit argmax (the reference does the same:
    sinkhorn under no_grad for training routing, plain argmax at eval,
    transformer.py:231-246 — and sinkhorn over a tiny batch degenerates to
    uniform scores, so batch-1 decode would always pick expert 0). The gate
    value is the sigmoid of the raw logit at the chosen expert either way.

    Returns (dispatch, combine): dispatch is a (T, E, C) one-hot used to
    scatter tokens into per-expert slots; combine = dispatch · gate gathers
    expert outputs back, zero for capacity-dropped tokens.
    """
    T, E = logits.shape
    if train:
        scores = sinkhorn(logits.astype(jnp.float32), sinkhorn_iters)
    else:
        scores = logits.astype(jnp.float32)
    expert_idx = jnp.argmax(scores, axis=-1)  # (T,)
    gate = jax.nn.sigmoid(
        jnp.take_along_axis(logits.astype(jnp.float32), expert_idx[:, None], axis=1)[:, 0]
    )
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # (T, E)
    # position of each token within its expert's buffer
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # (T, E)
    pos_in_expert = jnp.sum(pos, axis=-1).astype(jnp.int32)  # (T,)
    kept = (pos_in_expert < capacity).astype(jnp.float32)
    dispatch = (
        onehot[:, :, None] * jax.nn.one_hot(pos_in_expert, capacity, dtype=jnp.float32)[:, None, :]
    ) * kept[:, None, None]  # (T, E, C)
    combine = dispatch * gate[:, None, None]
    return dispatch, combine


def _glu_gate(cfg):
    """The gate's activation of a gated expert (`modeling.glu_gate`; imported late:
    `modeling` loads this module)."""
    from galvatron_tpu.models.modeling import glu_gate

    return glu_gate(cfg)


def ungated(cfg) -> bool:
    """The experts (and the shared expert) are ``down(relu(up x)^2)``: one matrix in, no
    gate (``act_fn`` "relu2", nemotron_h). Such a layer holds ``w1`` OUT-major, (E, f,
    h), the published ``up_proj.weight``'s order (`grouped_matmul.grouped_matmul_t` says
    why), ``w2`` (E, f, h) as ever and no ``w3``; its shared expert ``w1`` (h, fs) and
    ``w2`` (fs, h)."""
    return cfg.act_fn == "relu2"


def init_moe_params(key, cfg) -> Params:
    """Router over all ``moe_experts`` + stacked expert FFN weights (leading
    dim: the experts this copy holds, ``cfg.moe_held``; all of them unless
    ``cfg.moe_share`` says less) + the shared expert where there is one."""
    h, f, e = cfg.hidden_size, cfg.expert_ffn, cfg.moe_held
    rank, of = cfg.moe_share
    if not 0 <= rank < of or cfg.moe_experts % of:
        raise ValueError(f"moe_share {cfg.moe_share}: rank r of R needs 0 <= r < R and R "
                         f"dividing moe_experts ({cfg.moe_experts})")
    ks = jax.random.split(key, 4)
    scale_in = 1.0 / np.sqrt(h)
    scale_out = 1.0 / np.sqrt(f)
    # the router stays float32 whatever the weights are held in (`_topk_local`)
    p: Params = {
        "router": {"w": jax.random.normal(ks[0], (h, cfg.moe_experts), jnp.float32) * 0.02},
        "w1": jax.random.uniform(ks[1], (e, h, f), cfg.param_dtype, -scale_in, scale_in),
        "w2": jax.random.uniform(ks[2], (e, f, h), cfg.param_dtype, -scale_out, scale_out),
    }
    if cfg.moe_router == "sigmoid_topk":
        # the selection bias (aux-loss-free balancing sets it; here it is loaded or 0)
        p["router"]["bias"] = jnp.zeros((cfg.moe_experts,), jnp.float32)
    if cfg.act_fn == "swiglu":
        p["w3"] = jax.random.uniform(ks[3], (e, h, f), cfg.param_dtype, -scale_in, scale_in)
    if ungated(cfg):
        p["w1"] = jnp.swapaxes(p["w1"], 1, 2)  # (E, f, h): `ungated`
    if cfg.moe_shared_ffn_dim:
        from galvatron_tpu.models.modeling import _dense_init

        fs = cfg.moe_shared_ffn_dim
        sk = jax.random.split(jax.random.fold_in(key, 1), 3)
        if ungated(cfg):
            p["shared"] = {"w1": _dense_init(sk[0], h, fs, cfg.param_dtype),
                           "w2": _dense_init(sk[1], fs, h, cfg.param_dtype)}
            return p
        p["shared"] = {  # [gate | up] fused, down
            "w13": _dense_init(sk[0], h, 2 * fs, cfg.param_dtype),
            "w2": _dense_init(sk[1], fs, h, cfg.param_dtype),
        }
        if cfg.moe_shared_gate:  # the gate on the whole expert
            p["shared"]["gate"] = _dense_init(sk[2], h, 1, cfg.param_dtype)
    return p


def moe_annotations(cfg) -> Params:
    """Logical axes: 'ep' shards the expert dim over the expert-parallel mesh
    axes; within an expert the FFN dims carry the usual Megatron 'tp'
    column/row sharding; 'fsdp' dims ZeRO-shard over the non-EP data axes.

    The router weight stays replicated: it is a tiny (h, E) matrix, and
    ZeRO-sharding its h dim propagates an h-sharding onto the flattened
    token activations, which forced an SPMD "involuntary full
    rematerialization" (replicate-then-repartition) on the dispatch reshape
    — measurable HBM traffic for ~zero memory savings."""
    a: Params = {
        "router": {"w": (None, None)},
        "w1": ("ep", "fsdp", "tp"),
        "w2": ("ep", "tp", "fsdp"),
    }
    if cfg.act_fn == "swiglu":
        a["w3"] = ("ep", "fsdp", "tp")
    if cfg.moe_router == "sigmoid_topk":
        a["router"]["bias"] = (None,)
    if ungated(cfg):
        a["w1"] = ("ep", "tp", "fsdp")  # (E, f, h)
    if cfg.moe_shared_ffn_dim:
        # whole on every device like the dropless experts (tp divides nothing there)
        a["shared"] = {"w1" if ungated(cfg) else "w13": ("fsdp", None), "w2": (None, "fsdp")}
        if cfg.moe_shared_gate:
            a["shared"]["gate"] = (None, None)
    return a


def moe_block(x: jax.Array, p: Params, cfg, train: bool = True,
              place: Placement = LOCAL) -> jax.Array:
    """Switch-MoE MLP on a (B, S, H) activation (SwitchMLP.forward equivalent,
    reference: transformer.py:210-295). ``place`` pins the token-major
    tensors and the per-expert buffers of an ep>1 layer, so the expert
    all-to-all happens exactly at the dispatch/combine einsums."""
    pin_tok, pin_ep = place.pin_tokens, place.pin_experts
    b, s, h = x.shape
    T = b * s
    E = cfg.moe_experts
    xt = pin_tok(x.reshape(T, h))
    logits = xt.astype(jnp.float32) @ p["router"]["w"].astype(jnp.float32)  # (T, E)
    C = moe_capacity(T, E, cfg.moe_capacity_factor)
    dispatch, combine = route_top1(
        logits, C, sinkhorn_iters=cfg.moe_sinkhorn_iters, train=train
    )
    dispatch, combine = pin_tok(dispatch), pin_tok(combine)

    # scatter tokens into per-expert buffers: (E, C, H). XLA turns the expert
    # dim's sharding mismatch (tokens batch-sharded vs experts ep-sharded)
    # into the expert-parallel all-to-all.
    xe = pin_ep(jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), xt))
    w1 = p["w1"].astype(x.dtype)
    w2 = p["w2"].astype(x.dtype)
    if cfg.act_fn == "swiglu":
        w3 = p["w3"].astype(x.dtype)
        hmid = _glu_gate(cfg)(jnp.einsum("ech,ehf->ecf", xe, w1)) * jnp.einsum(
            "ech,ehf->ecf", xe, w3
        )
    else:
        hmid = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", xe, w1), approximate=True)
    ye = pin_ep(jnp.einsum("ecf,efh->ech", hmid, w2))
    yt = pin_tok(jnp.einsum("tec,ech->th", combine.astype(x.dtype), ye))
    return yt.reshape(b, s, h)


# ---------------------------------------------------------------------------
# Dropless softmax top-k token-choice routing (cfg.moe_router == "softmax_topk")
# ---------------------------------------------------------------------------
# OLMoE-class layers: p = softmax_fp32(y Wg); the k largest p of a token are
# its combine weights as they are (no renormalisation); every chosen (token,
# expert) pair is computed.  A (tokens, experts, capacity) one-hot cannot exist
# at these sizes (16384 x 64 x 2560), so the T*k pairs are sorted by expert
# into a row buffer whose groups start on row-tile boundaries
# (ops/grouped_matmul.py), three grouped GEMMs run over it, and the results
# are gathered back.  Both directions of the permutation are gathers, forward
# and backward (a pair has one row and a row one pair), through two custom
# VJPs: autodiff's transpose of a gather is a scatter-add, which the TPU
# serializes.  Every shape is static: the buffer has T*k + E*tile rows, the
# most that whole-tile groups can need.  The sorted ORDER itself is counted, not
# sorted (`sorted_layout`).

class SortedLayout(NamedTuple):
    """Where each (token, choice) pair lives in the expert-sorted row buffer."""

    pair_row: jax.Array  # (T*k,) row of pair t*k + j
    row_pair: jax.Array  # (M,) pair of a row (0 where the row is padding)
    row_valid: jax.Array  # (M,) bool
    tile_group: jax.Array  # (M / tile,) expert of a row tile
    num_tiles: jax.Array  # (1,) row tiles in use
    sizes: jax.Array  # (E,) pairs an expert got


def buffer_rows(pairs: int, groups: int, tile: int) -> int:
    """Rows of the sorted buffer: the most that ``groups`` groups of whole
    ``tile``-row tiles can need for ``pairs`` pairs."""
    return -(-pairs // tile) * tile + groups * tile


def layer_row_tile(cfg, tokens: int, dtype=None) -> int:
    """The row tile a dropless expert layer of ``cfg`` takes for a forward of
    ``tokens`` tokens (`_topk_local` asks here; so does whoever reports it)."""
    from galvatron_tpu.ops.grouped_matmul import row_tile

    return row_tile(tokens, cfg.moe_top_k, cfg.moe_experts, dtype or cfg.dtype)


def _group_tiles(sizes, tile: int, empty_tiles: bool):
    """Whole ``tile``-row tiles of groups of ``sizes`` rows; an empty group's one or none."""
    return jnp.maximum(-(-sizes // tile), 1 if empty_tiles else 0)


#: pairs a block of the prefix count: a pair is ranked among its block's earlier pairs
#: by comparison (block x block), and blocks by a running sum of their per-group totals
COUNT_BLOCK = 256


def _tile_tables(sizes, tile: int, empty_tiles: bool, rows: int):
    """``(row_start, tile_end, tile_group)`` of groups of ``sizes`` pairs in a buffer of
    ``rows`` rows: a group's first row, the tile its tiles end before, and each tile's
    group = how many groups' ends it has passed (the last group's past them all)."""
    tiles = _group_tiles(sizes, tile, empty_tiles)
    tile_end = jnp.cumsum(tiles)
    passed = jnp.arange(rows // tile, dtype=jnp.int32)[:, None] >= tile_end[None, :]
    tile_group = jnp.minimum(jnp.sum(passed, axis=1, dtype=jnp.int32), sizes.shape[0] - 1)
    return (tile_end - tiles) * tile, tile_end, tile_group


def sorted_layout(expert_idx: jax.Array, num_experts: int, tile: int,
                  empty_tiles: bool = True) -> SortedLayout:
    """Sort the pairs of ``expert_idx`` (T, k) by expert, stably, into groups
    of whole ``tile``-row tiles; an expert without a pair still owns one
    (all-padding) tile, so that its weight gradient is written. ``empty_tiles``
    False (a forward that is never differentiated): it owns none, no tile names
    it and its weights are never fetched; ``num_tiles`` is then 0 where no expert
    got a pair. The buffer's rows are the same either way.

    Nothing is sorted: a stable sort by expert is a counting sort, a pair's row =
    its group's first row + the pairs of its group before it. Dense comparisons and
    sums, one scatter (the rows' pairs: the inverse of ``pair_row``), no sort, no
    scatter-add, no search, at any number of experts (docs/DESIGN.md)."""
    flat = expert_idx.reshape(-1).astype(jnp.int32)
    pairs = flat.shape[0]
    rows = buffer_rows(pairs, num_experts, tile)
    block = min(COUNT_BLOCK, pairs)
    blocks = -(-pairs // block)
    # (a pair past the end names no group; it ranks behind the block's real pairs)
    keys = jnp.pad(flat, (0, blocks * block - pairs), constant_values=num_experts)
    keys = keys.reshape(blocks, block)
    groups = jnp.arange(num_experts, dtype=jnp.int32)
    member = keys[None, :, :] == groups[:, None, None]  # (groups, blocks, block)
    totals = jnp.sum(member, axis=2, dtype=jnp.int32)  # a block's pairs of a group
    through = jnp.cumsum(totals, axis=1)  # a group's pairs up to a block's end
    before, sizes = through - totals, through[:, -1]
    row_start, tile_end, tile_group = _tile_tables(sizes, tile, empty_tiles, rows)
    # the pairs of its group before a pair, in its block: [j earlier][same key], summed
    earlier = jnp.arange(block)[:, None] < jnp.arange(block)[None, :]  # (j, i)
    rank = jnp.sum((keys[:, :, None] == keys[:, None, :]) & earlier[None], axis=1,
                   dtype=jnp.int32)
    base = jnp.sum(jnp.where(member, (row_start[:, None] + before)[:, :, None], 0), axis=0)
    pair_row = (base + rank).reshape(-1)[:pairs]
    row_pair = jnp.zeros((rows,), jnp.int32).at[pair_row].set(
        jnp.arange(pairs, dtype=jnp.int32), unique_indices=True, mode="promise_in_bounds")
    # a tile's rows that hold a pair: its group's, from the tile's place in the group on
    at = tile_group[:, None] == groups[None, :]
    first = jnp.arange(rows // tile, dtype=jnp.int32) * tile
    live = jnp.sum(jnp.where(at, (sizes + row_start)[None, :], 0), axis=1) - first
    valid = (jnp.arange(tile, dtype=jnp.int32)[None, :] < live[:, None]).reshape(-1)
    return SortedLayout(pair_row, row_pair, valid, tile_group, tile_end[-1:].astype(jnp.int32),
                        sizes)


def _layout_by_sort(expert_idx, num_experts: int, tile: int,
                    empty_tiles: bool = True) -> SortedLayout:
    """`sorted_layout` by a stable argsort, as it was before PR 67: the REFERENCE that
    tests/test_moe.py and experiments/ab_moe_held.py hold `sorted_layout` to, to the
    bit. Nothing in the library calls it."""
    flat = expert_idx.reshape(-1).astype(jnp.int32)
    pairs = flat.shape[0]
    rows = buffer_rows(pairs, num_experts, tile)
    sizes = jnp.bincount(flat, length=num_experts).astype(jnp.int32)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)  # sorted position -> pair
    tiles = _group_tiles(sizes, tile, empty_tiles)
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tile  # a group's first row
    pair_start = jnp.cumsum(sizes) - sizes  # its first sorted position
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(rows // tile, dtype=jnp.int32), side="right"),
        num_experts - 1).astype(jnp.int32)
    g_sorted = flat[order]
    row_sorted = row_start[g_sorted] + jnp.arange(pairs, dtype=jnp.int32) - pair_start[g_sorted]
    pair_row = jnp.zeros((pairs,), jnp.int32).at[order].set(row_sorted, unique_indices=True)
    r = jnp.arange(rows, dtype=jnp.int32)
    g_row = tile_group[r // tile]
    off = r - row_start[g_row]
    valid = off < sizes[g_row]
    row_pair = jnp.where(valid, order[jnp.clip(pair_start[g_row] + off, 0, pairs - 1)], 0)
    return SortedLayout(pair_row, row_pair, valid, tile_group, tile_end[-1:].astype(jnp.int32),
                        sizes)


def held_layout(expert_idx, held: int, tile: int, first_held: int,
                empty_tiles: bool = True) -> SortedLayout:
    """`sorted_layout` of a held share: the ``held`` experts from ``first_held``
    on, of those ``expert_idx`` names (``empty_tiles``: as there). The dropped
    pairs sort behind the held groups as one group more, whose tiles lie past
    ``num_tiles`` (``pair_row >= num_tiles * tile`` says "not held"): a dropped pair
    adds nothing forward and takes nothing backward. The buffer keeps its worst-case
    size (every pair held): shapes are static, and no pair that is held is ever
    dropped. What lies past ``num_tiles`` depends on who runs over the layout:
    the plain path (`_dispatch`, `grouped_gemm` or `forward_gemm`, `_combine`)
    writes zeros there and reads them, so it takes the layout without empty tiles
    as it is (a pair that is not held combines a zero row); `held_experts` never
    writes those rows, so they are UNDEFINED and every reader masks by index."""
    local = expert_idx.astype(jnp.int32) - first_held
    dropped = (local < 0) | (local >= held)
    full = sorted_layout(jnp.where(dropped, held, local), held + 1, tile, empty_tiles)
    held_tiles = jnp.sum(_group_tiles(full.sizes[:held], tile, empty_tiles)).astype(jnp.int32)
    rows = full.row_valid.shape[0]
    in_held = jnp.arange(rows, dtype=jnp.int32) < held_tiles * tile
    return SortedLayout(
        full.pair_row, full.row_pair, full.row_valid & in_held,
        jnp.minimum(full.tile_group, held - 1), held_tiles[None], full.sizes[:held])


@jax.custom_vjp
def _dispatch(x, row_token, row_valid, pair_row):
    """x (T, h) -> the row buffer (M, h): a pair's row is its token's activation,
    padding rows are zero."""
    return jnp.where(row_valid[:, None], x[row_token], jnp.zeros((), x.dtype))


def _dispatch_fwd(x, row_token, row_valid, pair_row):
    return _dispatch(x, row_token, row_valid, pair_row), (pair_row, x.shape[0])


def _dispatch_bwd(res, g):
    pair_row, tokens = res
    dx = jnp.sum(g[pair_row].reshape(tokens, -1, g.shape[-1]).astype(jnp.float32), axis=1)
    return dx.astype(g.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, weights, pair_row, row_pair, row_valid):
    """The row buffer y (M, h) and the combine weights (T, k) fp32 -> (T, h):
    out[t] = sum_j weights[t, j] * y[row of pair (t, j)], summed in fp32."""
    t, k = weights.shape
    picked = y[pair_row].reshape(t, k, y.shape[-1]).astype(jnp.float32)
    return jnp.sum(picked * weights[:, :, None], axis=1).astype(y.dtype)


def _combine_fwd(y, weights, pair_row, row_pair, row_valid):
    return _combine(y, weights, pair_row, row_pair, row_valid), (
        y, weights, pair_row, row_pair, row_valid)


def _combine_bwd(res, g):
    y, weights, pair_row, row_pair, row_valid = res
    t, k = weights.shape
    w_row = jnp.where(row_valid, weights.reshape(-1)[row_pair], 0.0)
    dy = (g[row_pair // k].astype(jnp.float32) * w_row[:, None]).astype(y.dtype)
    # (reducing <y, g> per row and gathering scalars instead of re-gathering y
    # measured slower on the chip: combine backward 5.65 against 5.19 ms, PR 28)
    picked = y[pair_row].reshape(t, k, y.shape[-1]).astype(jnp.float32)
    dw = jnp.sum(picked * g.astype(jnp.float32)[:, None, :], axis=-1)
    return dy, dw, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def grouped_gemm(lhs, rhs, layout: SortedLayout, tile: int, out_major: bool = False):
    """Rows of group g of ``lhs`` (M, K) by ``rhs[g]`` (E, K, N), or (``out_major``)
    by ``rhs[g].T`` of weights stored (E, N, K): the Pallas
    kernels of ops/grouped_matmul.py, which beat ``jax.lax.ragged_dot`` 1.40x
    at the OLMoE cell's shape (experiments/moe_gmm_bench.py swaps this name
    for its candidates; PERF.md §6, PR 28)."""
    from galvatron_tpu.ops.grouped_matmul import grouped_matmul, grouped_matmul_t

    product = grouped_matmul_t if out_major else grouped_matmul
    return product(lhs, rhs, layout.tile_group, layout.num_tiles, tile)


def forward_gemm(lhs, rhs, layout: SortedLayout, tile: int, out_major: bool = False):
    """`grouped_gemm` with no VJP: what the plain path of a forward that is never
    differentiated runs over a layout without empty tiles (`sorted_layout`), as
    `held_forward` is the bounded path's: the same kernels and the same zeros past
    ``num_tiles``, and a gradient raises (`grouped_matmul.forward_matmul`)."""
    from galvatron_tpu.ops.grouped_matmul import forward_matmul

    return forward_matmul(lhs, rhs, layout.tile_group, layout.num_tiles, tile_m=tile,
                          transpose_rhs=out_major)


def held_path_counts(cfg) -> dict:
    """``{"bounded": n, "worst_case": m}``: the layers of a model that holds a share of
    its experts whose share does work in proportion to the pairs it holds
    (`held_experts`), and those that run over the worst-case buffer; asks
    `ops/moe_held.held_path`, the function `_topk_local` asks. A model that holds
    every expert counts nothing."""
    counts = {"bounded": 0, "worst_case": 0}
    if cfg.moe_dropless and cfg.moe_holds_share:
        from galvatron_tpu.ops.moe_held import held_path  # (a dense run loads no kernels)

        counts[held_path(cfg.hidden_size, cfg.expert_ffn, cfg.dtype, not ungated(cfg))] = sum(
            cfg.mlp_layers)
    return counts


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def held_experts(x, weights, w13, w2, pair_row, row_pair, row_valid, tile_group, num_tiles,
                 tile: int, act: str = "silu"):
    """Dispatch, the experts' gated FFN (``act`` on the gate: "silu", SwiGLU, or
    "relu", ReGLU) and combine of a held share over
    `held_layout`, every pass bounded by the rows that hold a pair: x (T, h),
    weights (T, k) float32, w13 (E, h, 2f) = [w1 | w3] or the pair (w1, w3) as
    stored (a GEMM each, nothing joined but their outputs), w2 (E, f, h) -> (T, h).
    What `_dispatch`, three `grouped_gemm` and `_combine` compute, with the
    permutations and the elementwise passes as kernels that stop at
    ``num_tiles`` (`ops/moe_held.py`) and one backward written out, so that no
    gradient is summed by XLA over the whole buffer either."""
    return _held_forward(x, weights, w13, w2, pair_row, row_pair, row_valid, tile_group,
                         num_tiles, tile, act)[0]


def held_forward(*args):
    """`held_experts` (same arguments) as the forward body alone, with no VJP: what a
    forward that is never differentiated runs over a layout without empty tiles
    (`sorted_layout`), where a gradient must raise (Pallas has no JVP of these calls)
    and never be read out of weight blocks that no tile wrote."""
    return _held_forward(*args)[0]


def _held_forward(x, weights, w13, w2, pair_row, row_pair, row_valid, tile_group, num_tiles, tile,
                  act="silu"):
    from galvatron_tpu.ops import moe_held
    from galvatron_tpu.ops.grouped_matmul import held_matmul

    k = weights.shape[1]
    pairs = pair_row.reshape(weights.shape)
    with jax.named_scope("dispatch"):
        tile_rows = jnp.sum(row_valid.reshape(-1, tile), axis=1, dtype=jnp.int32)
        row_token = row_pair // k
        rows = moe_held.gather_rows(moe_held.to_slab(x), row_token, tile_rows, num_tiles,
                                    dtype=x.dtype, tile=tile)
    with jax.named_scope("experts"):
        if isinstance(w13, tuple):
            gate_up = jnp.concatenate(
                [held_matmul(rows, w, tile_group, num_tiles, tile_m=tile) for w in w13], axis=-1)
        else:
            gate_up = held_matmul(rows, w13, tile_group, num_tiles, tile_m=tile)
        mid = moe_held.swiglu(gate_up, num_tiles, tile=tile, act=act)
        out = held_matmul(mid, w2, tile_group, num_tiles, tile_m=tile, slab_out=True)
    with jax.named_scope("combine"):
        index = moe_held.pairs_index(pairs, num_tiles, tile=tile)
        y = moe_held.gather_pairs(out, pairs, num_tiles, weights, index, dtype=x.dtype, tile=tile)
    return y, (weights, w13, w2, rows, gate_up, mid, out, pairs, index, row_pair, row_valid,
               row_token, tile_rows, tile_group, num_tiles)


def _held_backward(tile, act, res, g):
    from galvatron_tpu.ops import moe_held
    from galvatron_tpu.ops.grouped_matmul import held_matmul, weight_grad

    (weights, w13, w2, rows, gate_up, mid, out, pairs, index, row_pair, row_valid, row_token,
     tile_rows, tile_group, num_tiles) = res
    dtype, experts = g.dtype, w2.shape[0]
    pair = isinstance(w13, tuple)  # (the backward of a pair joins it: training converts)
    if pair:
        w13 = jnp.concatenate(w13, axis=-1)
    with jax.named_scope("combine"):
        dweights = moe_held.gather_pairs(out, pairs, num_tiles, weights, index, dtype=dtype,
                                         tile=tile, other=g)
        w_row = jnp.where(row_valid, weights.reshape(-1)[row_pair], 0.0)
        dout = moe_held.gather_rows(moe_held.to_slab(g), row_token, tile_rows, num_tiles,
                                    dtype=dtype, tile=tile, scale=w_row)
    with jax.named_scope("experts"):
        dmid = held_matmul(dout, w2, tile_group, num_tiles, tile_m=tile, transpose_rhs=True)
        dw2 = weight_grad(mid, dout, tile_group, num_tiles, experts, tile_m=tile,
                          out_dtype=w2.dtype)
        dgate_up = moe_held.swiglu_bwd(gate_up, dmid, num_tiles, tile=tile, act=act)
        drows = held_matmul(dgate_up, w13, tile_group, num_tiles, tile_m=tile,
                            transpose_rhs=True, slab_out=True)
        dw13 = weight_grad(rows, dgate_up, tile_group, num_tiles, experts, tile_m=tile,
                           out_dtype=w13.dtype)
        if pair:
            dw13 = tuple(jnp.split(dw13, 2, axis=-1))
    with jax.named_scope("dispatch"):
        dx = moe_held.gather_pairs(drows, pairs, num_tiles, jnp.ones_like(weights), index,
                                   dtype=dtype, tile=tile)
    return dx, dweights, dw13, dw2, None, None, None, None, None


held_experts.defvjp(_held_forward, _held_backward)


def router_stats(probs: jax.Array, sizes: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """What the load-balancing loss needs of one layer, both (E,) fp32:
    f_e = pairs of expert e over tokens (= sum_j f_{j,e}; not differentiated)
    and P_e = mean of p_e over tokens."""
    tokens = probs.shape[0]
    return sizes.astype(jnp.float32) / tokens, jnp.mean(probs, axis=0)


def load_balancing_loss(stats, num_experts: int) -> jax.Array:
    """HF ``load_balancing_loss_func`` on the layers' ``router_stats``: the
    layers' gate outputs are concatenated there, so f and P are means over
    layers and tokens together: L_aux = E * sum_e f_e * P_e."""
    f = jnp.mean(jnp.stack([s[0] for s in stats]), axis=0)
    p = jnp.mean(jnp.stack([s[1] for s in stats]), axis=0)
    return num_experts * jnp.sum(f * p)


def load_max_over_mean(stats, num_experts: int, top_k: int,
                       held: Optional[Tuple[int, int]] = None) -> jax.Array:
    """Largest expert's pairs over the even share T*k/E, the fullest layer's;
    ``held`` = (first, count): over the held experts alone."""
    f = jnp.stack([s[0] for s in stats])
    if held is not None:
        f = f[:, held[0]:held[0] + held[1]]
    return jnp.max(f) * num_experts / top_k


def held_pairs_per_token(stats, held: Tuple[int, int]) -> jax.Array:
    """(token, expert) pairs a token puts on the held experts, mean over the
    layers: ``k * held / E`` when the load is even."""
    f = jnp.stack([s[0] for s in stats])
    return jnp.mean(jnp.sum(f[:, held[0]:held[0] + held[1]], axis=1))


def held_experts_touched(stats, held: Tuple[int, int]) -> jax.Array:
    """Held experts that got at least one pair, mean over the layers: ``count`` when
    every held expert has a row, ``count * (1 - e^-r)`` at ``r`` rows an expert on
    average under even routing. The experts whose weights a forward-only held share
    fetches, on the bounded path and on the plain one (`sorted_layout`: the others
    own no tile there); a differentiated one fetches all ``count``."""
    f = jnp.stack([s[0] for s in stats])
    return jnp.mean(jnp.sum((f[:, held[0]:held[0] + held[1]] > 0).astype(jnp.float32), axis=1))


def held_rows_share(stats) -> jax.Array:
    """Rows of the worst-case buffer whose tiles are in use (``num_tiles * tile``
    over the buffer's rows), mean over the layers: of a held share's statistics
    the third. About ``held / E`` plus the tiles' padding when the load is even."""
    return jnp.mean(jnp.stack([s[2] for s in stats]))


def live_rows_share(stats, cfg, tokens: int) -> jax.Array:
    """Of the rows a held share's kernels multiply and move (``num_tiles * tile``), the
    share that holds a pair, over the layers of a forward of ``tokens`` tokens at the
    tile the layers chose themselves: what is left of a tile once an expert's rows are
    in it (0 where no layer's share got a pair and no tile is in use). From the layers'
    statistics and static shapes alone."""
    rows = buffer_rows(tokens * cfg.moe_top_k, cfg.moe_held + 1, layer_row_tile(cfg, tokens))
    pairs = held_pairs_per_token(stats, (cfg.moe_first_held, cfg.moe_held)) * tokens
    return pairs / jnp.maximum(held_rows_share(stats) * rows, 1e-9)


def moe_topk_block(x: jax.Array, p: Params, cfg, tile: Optional[int] = None,
                   place: Placement = LOCAL, router_x: Optional[jax.Array] = None,
                   forward_only: bool = False):
    """Dropless top-k MoE MLP on (B, S, H) -> (y, router_stats). ``router_x`` (B, S,
    H): what the router reads where that is not ``x`` (``cfg.moe_router_input``
    "attn": the attention block's normed input; split over the mesh like ``x``).
    ``forward_only``: the caller never differentiates this forward (the cached
    forwards of `models/generation`), so a held share gives no tile to an expert
    without a row (`sorted_layout`) and a decode step fetches the weights of the
    experts it touched alone, on the bounded path and on the plain one;
    differentiating such a forward raises (`held_forward`, `forward_gemm`).

    On a multi-device mesh ``place.route_tokens`` runs the block on each
    device's own tokens, every expert's weights whole on every device, and
    only the statistics cross devices (a mean); expert parallelism is
    refused upstream (build_runtime)."""
    if router_x is not None:
        return place.route_tokens(
            lambda xs, p_, over: _topk_local(xs[0], p_, cfg, tile, over, router_x=xs[1],
                                             forward_only=forward_only)
        )((x, router_x), p)
    return place.route_tokens(
        lambda x_, p_, over: _topk_local(x_, p_, cfg, tile, over, forward_only=forward_only)
    )(x, p)


def router_scores(xt, router: Params, cfg) -> jax.Array:
    """A token's float32 score of every expert (T, E): the softmax of the router's
    logits, or (``sigmoid_topk``) their sigmoid. The GEMM runs at ``highest`` for the
    sigmoid router and where ``cfg.moe_router_precision`` says so: the chip's default
    runs a float32 GEMM in one bf16 pass, and a choice flips wherever two scores lie
    within a bf16 ulp. (The older softmax routers keep the default: their cells'
    programs stay as they were.)"""
    x32, w32 = xt.astype(jnp.float32), router["w"].astype(jnp.float32)
    sigmoid = cfg.moe_router == "sigmoid_topk"
    highest = sigmoid or cfg.moe_router_precision == "highest"
    logits = jnp.matmul(x32, w32, precision=jax.lax.Precision.HIGHEST if highest else None)
    return jax.nn.sigmoid(logits) if sigmoid else jax.nn.softmax(logits, axis=-1)


def _topk_local(x, p, cfg, tile, over, router_x=None, forward_only=False):
    """The block on the tokens this device holds; ``over``: the mesh axes the
    tokens are split on (the statistics are means over all of them); ``router_x``:
    what the router reads in place of ``x``, ``forward_only``: `moe_topk_block`.  The
    router's input, GEMM and softmax are fp32 whatever the compute dtype: a
    bf16 logit flips a choice wherever two probabilities lie within a bf16 ulp."""
    from galvatron_tpu.ops.moe_held import held_path

    b, s, h = x.shape
    tokens, k, e = b * s, cfg.moe_top_k, cfg.moe_experts
    tile = tile or layer_row_tile(cfg, tokens, x.dtype)  # (trace-time, by shape)
    held_share = cfg.moe_holds_share  # trace-time: all held is the branch there always was
    # a share of the experts pays for the pairs it holds (trace-time, by shape)
    bounded = held_share and held_path(h, cfg.expert_ffn, x.dtype, not ungated(cfg)) == "bounded"
    xt = x.reshape(tokens, h)
    sigmoid = cfg.moe_router == "sigmoid_topk"
    with jax.named_scope("router"):
        probs = router_scores(
            xt if router_x is None else router_x.reshape(tokens, h), p["router"], cfg)
    with jax.named_scope("dispatch"):
        if sigmoid:
            # the bias SELECTS and never weighs: top-k of s + b, weights from s,
            # renormalised over the chosen and scaled
            _, idx = jax.lax.top_k(
                probs + jax.lax.stop_gradient(p["router"]["bias"].astype(jnp.float32)), k)
            weights = jnp.take_along_axis(probs, idx, axis=-1)
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True) * cfg.moe_route_scale
        else:
            weights, idx = jax.lax.top_k(probs, k)  # weights: p's own values
            if cfg.moe_norm_topk:
                weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        with jax.named_scope("layout"):
            if held_share:
                # an expert without a row owns a tile for its weight gradient's sake
                # (`moe_tgmm`); a forward that has none to write pays a fetch of the
                # expert's weights for it on either path, so it asks for none
                layout = held_layout(idx, cfg.moe_held, tile, cfg.moe_first_held,
                                     empty_tiles=not forward_only)
            else:
                layout = sorted_layout(idx, e, tile)
    if bounded:
        with jax.named_scope("experts"):
            # weights held in another type are converted every step, and that pass
            # joins gate and up into one stack for one GEMM; held in the compute type
            # already (``cli serve --param_dtype bf16``) nothing is converted and the
            # join would copy every held expert every step (1 GB a layer at
            # sarvam-105b's widths), so each of the two gets its own GEMM
            w13 = ((p["w1"], p["w3"]) if p["w1"].dtype == x.dtype
                   else jnp.concatenate([p["w1"], p["w3"]], axis=-1).astype(x.dtype))
            w2 = p["w2"].astype(x.dtype)
        run = held_forward if forward_only else held_experts
        y = run(xt, weights, w13, w2, layout.pair_row, layout.row_pair, layout.row_valid,
                layout.tile_group, layout.num_tiles, tile, cfg.glu_act)
    else:
        with jax.named_scope("dispatch"):
            rows = _dispatch(xt, layout.row_pair // k, layout.row_valid, layout.pair_row)
        with jax.named_scope("experts"):
            # (a held share's forward-only layout leaves groups without a tile: no VJP)
            gemm = forward_gemm if forward_only and held_share else grouped_gemm
            if ungated(cfg):
                from galvatron_tpu.models.modeling import relu2

                up = gemm(rows, p["w1"].astype(x.dtype), layout, tile, out_major=True)
                with jax.named_scope("relu2"):
                    mid = relu2(up)
                out = gemm(mid, p["w2"].astype(x.dtype), layout, tile)
            else:
                w1, w3, w2 = (p[n].astype(x.dtype) for n in ("w1", "w3", "w2"))
                gate = gemm(rows, w1, layout, tile)
                up = gemm(rows, w3, layout, tile)
                out = gemm(_glu_gate(cfg)(gate) * up, w2, layout, tile)
        with jax.named_scope("combine"):
            y = _combine(out, weights, layout.pair_row, layout.row_pair, layout.row_valid)
    if cfg.moe_shared_ffn_dim:
        with jax.named_scope("shared_expert"):
            y = y + (_shared_ungated(xt, p["shared"]) if ungated(cfg)
                     else _shared_expert(xt, p["shared"], _glu_gate(cfg)))
    # the statistics are over ALL the experts the router scores, held or not
    stats = router_stats(probs, _pairs_an_expert(idx, e) if held_share else layout.sizes)
    if held_share:
        # the share of the worst-case buffer's rows whose tiles are in use: what is
        # left of the work where the path is bounded
        stats += (layout.num_tiles[0].astype(jnp.float32) * tile / layout.row_valid.shape[0],)
    if over:
        stats = tuple(jax.lax.pmean(s_, over) for s_ in stats)
    return y.reshape(b, s, h), stats


def _pairs_an_expert(idx, num_experts: int):
    """The pairs each of ``num_experts`` experts got of the choices ``idx`` (T, k), by
    comparison and sum (`jnp.bincount` is a scatter-add of the pairs: serial on the chip)."""
    return jnp.sum(idx.reshape(-1)[None, :] == jnp.arange(num_experts, dtype=idx.dtype)[:, None],
                   axis=1, dtype=jnp.int32)


def _shared_ungated(xt, p):
    """``down(relu(up x)^2)`` on (T, h): the un-gated shared expert, added as it is."""
    from galvatron_tpu.models.modeling import relu2
    from galvatron_tpu.ops.quant import project  # (a served int8 weight: its own GEMM)

    return project(relu2(project(xt, p["w1"])), p["w2"])


def _shared_expert(xt, p, act=jax.nn.silu):
    """``down(act(gate x) * up x)`` on (T, h) (``act``: `modeling.glu_gate`), times ``sigmoid(x w_gate)`` where
    the expert has a gate: the expert every token runs, whatever the router chose."""
    dtype = xt.dtype
    f = p["w13"].shape[1] // 2
    gu = xt @ p["w13"].astype(dtype)
    out = (act(gu[:, :f]) * gu[:, f:]) @ p["w2"].astype(dtype)
    if "gate" not in p:
        return out
    gate = jax.nn.sigmoid(jnp.einsum(
        "th,hc->tc", xt, p["gate"].astype(dtype), preferred_element_type=jnp.float32))
    return (out.astype(jnp.float32) * gate).astype(dtype)
