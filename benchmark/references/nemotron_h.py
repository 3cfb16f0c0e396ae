"""nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (``model_type`` nemotron_h), written from the
published config's keys and the layer equations of ISSUE 68, BLOCK BY BLOCK: block ``i`` of
``hybrid_override_pattern`` is one RMSNorm and one sublayer,

    x = E[ids]                                       no multiplier
    x = x + f_i(RMS_i(x))                            eps 1e-5, ``RMS_w(x) = x rsqrt(mean x^2 + eps) w``
    logits = RMS_f(x) W_head                         untied

with ``f_i`` by the pattern's character:

``M`` (Mamba-2):
    [z | xBC | dt] = h W_in                          4096 | 4096 + 2 x 8 x 128 | 64
    xBC = silu(conv(xBC) + b)                        depthwise, causal, 4 taps, zeros before
                                                     the sequence, the last tap on the position
    [x | B | C] = xBC                                x: 64 heads of 64; B, C: 8 groups of 128;
                                                     head j reads group j // 8
    dt = softplus(dt + dt_bias);  A = -exp(A_log)    no clamp (``time_step_*`` initialise)
    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T       a (64, 128) state a head, zero before
    y_t = H_t C_t + D x_t                            the sequence: THE RECURRENCE, a position
                                                     at a time (`recurrence`), not the chunked
                                                     form the program runs
    g = y * silu(z)
    out = (RMS_w(g) WITHIN each of the 8 groups of 512) W_out      w: 4096 gains

``*`` (attention): q, k, v = h W_q, h W_k, h W_v (32 | 2 | 2 heads of 128; query head n reads
    key/value head n // 16); softmax(q k^T / sqrt(128)) v over every j <= p; NO rotary and
    no other position signal; W_o from 4096 to 2688; no bias.

``E`` (experts): s = sigmoid(h W_g) in float32 over all 128; the 6 experts are the top-6 of
    s + b (b SELECTS only; one group of one: the grouped choice is the plain one);
    w_e = 2.5 s_e / (sum of the six s + 1e-20); f = sum_e w_e W_down_e relu(W_up_e h)^2 over
    the chosen experts this copy holds, + W_down relu(W_up h)^2 of the shared expert (width
    3712), added as it is. No gate matrix anywhere.

No cache: every position's keys and values are made once and every query sees its keys
through a mask; a Mamba-2 block runs its whole sequence from a zero state.

Departures from the published description, all in the configuration file: the held share of
the experts (``expert_share``: pairs on experts this copy does not hold are left out of the
sum, as in the program; the router scores all 128 and the weights are NOT renormalised over
the held ones), the vocabulary slice, the depth (the first ``published_blocks`` characters
of the pattern) and the readings under ``assumed``.

``published_weights`` hands the program's own arrays on (no re-laid-out copy) and maps the
program's LAYERS back to published BLOCKS: a program layer is a mixer block and, where it
has an MLP, the expert block behind it; the map is held against the pattern, so a program
whose layers were another reading of the pattern fails here and not in a tolerance. q, k
and v stay in the program's fused projection (columns by key/value head: its 16 query
heads, its key head, its value head); the conv's taps stay (taps, channels), the last tap
on the position; an expert's ``up_proj`` is (1856, 2688) as published, ``down_proj`` its
transpose's order, (1856, 2688) too.

`logits` keeps every float32 intermediate to a block (``lib/serve.compare_rows`` runs it
ONCE over a slot's 8,192 positions beside 8.9 GB of bf16 weights and 3.2 GB of cache):
attention a key/value head's 16 query heads and a block of queries at a time, the experts
HALF of the held ones and a block of tokens at a time (a layer's 32 held experts are 1.28
GB in float32), the head a block of columns at a time.

``lib/flops.py``'s served counts are a dense K/V decoder's. `serve_dims` and `served_params`
give LOWER bounds of this stack's work (a Mamba-2 block has no K and V, a state's bytes have
no term there: ``serve_hbm_roofline`` and ``decode_step_hbm_roofline`` read as floors;
`served_params` counts of the routed experts NONE: a forward of one token may choose no
expert this copy holds, so no share passes 100% when a step leaves experts untouched). The
exact counts are `decode_attn_bytes` (a step's cached attention), `expert_step_bytes` (the
experts a step TOUCHED) and `ssm_step_bytes` (a step's Mamba-2 mixers: state read and
written once, conv tail, projections once).
"""

import math

import jax
import jax.numpy as jnp

from benchmark.lib.reference import F32, rms_norm

#: queries a step of the attention takes, tokens a step of the experts
QUERY_BLOCK, TOKEN_BLOCK = 512, 1024
#: parts a layer's held experts are multiplied in
EXPERT_PARTS = 2
#: columns of the head multiplied at once
VOCAB_BLOCK = 32768
#: the renormalisation's guard
NORM_TOPK_EPS = 1e-20


def pattern(cfg):
    """The characters of the blocks this copy runs: the first ``published_blocks`` of the
    published pattern. (``num_hidden_layers`` counts the program's LAYERS in the
    configuration file, 15 for 26 blocks: ``lib/harness.check_widths`` holds it to the
    program's ``num_layers``; nothing here reads it.)"""
    return cfg["hybrid_override_pattern"][:int(cfg["published_blocks"])]


def published_weights(params, cfg):
    """The program's tree as published BLOCKS, one entry a character of `pattern`."""
    blocks = []
    for lp in params["layers"]:
        if "ssm" in lp:
            m = lp["ssm"]
            blocks.append(("M", {"norm": lp["attn_norm"]["scale"], "in_proj": m["in_proj"],
                                 "conv_w": m["conv_w"], "conv_b": m["conv_b"],
                                 "dt_bias": m["dt_bias"], "A_log": m["A_log"], "D": m["D"],
                                 "gate_norm": m["norm"], "out_proj": m["out_proj"]}))
        else:
            a = lp["attn"]
            blocks.append(("*", {"norm": lp["attn_norm"]["scale"], "qkv_proj": a["wqkv"],
                                 "o_proj": a["wo"]}))
        if "mlp" in lp:
            f = lp["mlp"]
            blocks.append(("E", {"norm": lp["mlp_norm"]["scale"], "gate": f["router"]["w"],
                                 "e_score_correction_bias": f["router"]["bias"],
                                 "up_proj": f["w1"], "down_proj": f["w2"],
                                 "shared_up": f["shared"]["w1"],
                                 "shared_down": f["shared"]["w2"]}))
    got = "".join(c for c, _ in blocks)
    if got != pattern(cfg):
        raise ValueError(f"the program's layers are the blocks {got!r}, the configuration's "
                         f"first {cfg['published_blocks']} are {pattern(cfg)!r}")
    return {"embeddings": params["embed"]["tok"], "norm_f": params["final_norm"]["scale"],
            "lm_head": params["head"]["w"], "blocks": [w for _, w in blocks]}


def _blocks(n, size):
    """``n`` as whole blocks of at most ``size``: (blocks, block)."""
    block = math.gcd(n, size) if n % size else size
    return n // block, block


def _attn_sizes(cfg):
    return (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
            int(cfg["head_dim"]))


def _ssm_sizes(cfg):
    """(heads, head size, groups, state, taps) of a Mamba-2 block."""
    return (int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"]), int(cfg["n_groups"]),
            int(cfg["ssm_state_size"]), int(cfg["conv_kernel"]))


# -- the blocks -----------------------------------------------------------------------


def recurrence(x, dt, a, b_mat, c_mat):
    """``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``, ``y_t = H_t C_t`` a position at a
    time from a zero state: x (s, H, P), dt (s, H), a (H,), b_mat / c_mat (s, G, N) ->
    y (s, H, P). Head j reads group ``j // (H / G)``."""
    s, h, p = x.shape
    g, n = b_mat.shape[1:]
    per = h // g

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        b_h, c_h = jnp.repeat(b_t, per, axis=0), jnp.repeat(c_t, per, axis=0)  # (H, N)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_h)

    return jax.lax.scan(step, jnp.zeros((h, p, n), F32), (x, dt, b_mat, c_mat))[1]


def mamba(a_in, w, cfg):
    """A Mamba-2 block's sublayer on (b, s, hidden) -> the same, a row at a time."""
    return jax.vmap(lambda row: _mamba_row(row, w, cfg))(a_in)


def _mamba_row(x_in, w, cfg):
    h, p, g, n, k = _ssm_sizes(cfg)
    d_inner, eps = h * p, float(cfg["layer_norm_epsilon"])
    s = x_in.shape[0]
    zxbcdt = x_in @ w["in_proj"]
    z, xbc, dt = (zxbcdt[:, :d_inner], zxbcdt[:, d_inner:2 * d_inner + 2 * g * n],
                  zxbcdt[:, 2 * d_inner + 2 * g * n:])
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = w["conv_b"] + sum(padded[j:j + s] * w["conv_w"][j] for j in range(k))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_inner].reshape(s, h, p)
    b_mat = xbc[:, d_inner:d_inner + g * n].reshape(s, g, n)
    c_mat = xbc[:, d_inner + g * n:].reshape(s, g, n)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(w["A_log"]), b_mat, c_mat) + w["D"][:, None] * x
    gated = (y.reshape(s, d_inner) * jax.nn.silu(z)).reshape(s, g, d_inner // g)
    gated = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
    return (gated.reshape(s, d_inner) * w["gate_norm"]) @ w["out_proj"]


def attention(a, w, cfg):
    """An attention block's sublayer on (1, s, hidden) -> the same: GQA, causal, no
    position signal."""
    n, kv, d = _attn_sizes(cfg)
    per = n // kv
    b, s, hidden = a.shape
    blocks, block = _blocks(s, QUERY_BLOCK)
    wqkv = w["qkv_proj"].reshape(hidden, kv, (per + 2) * d).transpose(1, 0, 2)
    wo = w["o_proj"].reshape(kv, per * d, hidden)
    key_pos = jnp.arange(s)

    def group(acc, args):
        wqkv_g, wo_g = args
        qkv = (a @ wqkv_g).reshape(b, s, per + 2, d)
        q, k, v = qkv[:, :, :per], qkv[:, :, per], qkv[:, :, per + 1]

        def queries(i):
            at = i * block + jnp.arange(block)
            scores = jnp.einsum("bqnd,bkd->bnqk", q[:, at], k) / math.sqrt(d)
            seen = key_pos[None, :] <= at[:, None]
            scores = jnp.where(seen[None, None], scores, -jnp.inf)
            return jnp.einsum("bnqk,bkd->bqnd", jax.nn.softmax(scores, axis=-1), v)

        o = jax.lax.map(queries, jnp.arange(blocks))  # (blocks, b, block, per, d)
        o = jnp.moveaxis(o, 0, 1).reshape(b, s, per * d)
        return acc + o @ wo_g, None

    return jax.lax.scan(group, jnp.zeros_like(a), (wqkv, wo))[0]


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def route(m, w, cfg):
    """(tokens, experts) combine weights over ALL the experts the router scores: 0 for an
    expert a token did not choose."""
    k, scale = int(cfg["num_experts_per_tok"]), float(cfg["routed_scaling_factor"])
    s = jax.nn.sigmoid(m @ w["gate"])
    _, chosen = jax.lax.top_k(s + w["e_score_correction_bias"], k)  # selects, never weighs
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + NORM_TOPK_EPS)
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(scale * picked)


def experts(m, w, cfg):
    """An expert block's sublayer on (1, s, hidden): the held experts' part of the routed
    sum (``expert_share`` says which are held) plus the shared expert, as it is."""
    b, s, hidden = m.shape
    held = w["down_proj"].shape[0]
    share = cfg.get("expert_share") or {"rank": 0, "of": 1}
    first = int(share["rank"]) * held
    blocks, block = _blocks(b * s, TOKEN_BLOCK)
    parts = EXPERT_PARTS if held % EXPERT_PARTS == 0 else 1
    xs = m.reshape(blocks, block, hidden)

    def part(lo, hi):
        up, down = w["up_proj"][lo:hi], w["down_proj"][lo:hi]  # (e, f, hidden) both

        def tokens(x):
            # pairs on absent experts: left out; the weights stay as the router made them
            weights = route(x, w, cfg)[:, first + lo:first + hi]
            mid = relu2(jnp.einsum("th,efh->tef", x, up))
            return jnp.einsum("tef,efh->th", mid * weights[:, :, None], down)

        return jax.lax.map(tokens, xs)

    out = jax.lax.map(lambda x: relu2(x @ w["shared_up"]) @ w["shared_down"], xs)
    for i in range(parts):
        out = out + part(i * held // parts, (i + 1) * held // parts)
    return out.reshape(b, s, hidden)


SUBLAYERS = {"M": mamba, "*": attention, "E": experts}


def logits(w, tokens, cfg):
    eps = float(cfg["layer_norm_epsilon"])
    x = w["embeddings"][tokens]
    for c, bw in zip(pattern(cfg), w["blocks"]):
        x = x + SUBLAYERS[c](rms_norm(x, bw["norm"], eps), bw, cfg)
    b, s, hidden = x.shape
    h = rms_norm(x, w["norm_f"], eps).reshape(b * s, hidden)
    head = w["lm_head"]
    parts = [h @ head[:, i:i + VOCAB_BLOCK] for i in range(0, head.shape[1], VOCAB_BLOCK)]
    return jnp.concatenate(parts, axis=-1).reshape(b, s, head.shape[1])


# -- counts ---------------------------------------------------------------------------


def block_counts(cfg):
    """{"M": .., "*": .., "E": ..} of the blocks this copy runs."""
    return {c: pattern(cfg).count(c) for c in "M*E"}


def _share(cfg):
    return int((cfg.get("expert_share") or {"of": 1})["of"])


def _expert_weights(cfg):
    """One routed expert's two matrices."""
    return 2 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def _shared_weights(cfg):
    return (2 * int(cfg["hidden_size"]) * int(cfg["moe_shared_expert_intermediate_size"])
            * int(cfg["n_shared_experts"]))


def _mamba_weights(cfg):
    """(projections, the rest) of a Mamba-2 block: in_proj and out_proj; taps and conv
    bias, dt_bias, A_log, D, the gate norm's gains."""
    hid = int(cfg["hidden_size"])
    h, p, g, n, k = _ssm_sizes(cfg)
    d_inner, conv_dim = h * p, h * p + 2 * g * n
    return hid * (d_inner + conv_dim + h) + d_inner * hid, conv_dim * (k + 1) + 3 * h + d_inner


def _attn_weights(cfg):
    hid = int(cfg["hidden_size"])
    n, kv, d = _attn_sizes(cfg)
    return hid * (n + 2 * kv) * d + n * d * hid


def _router_weights(cfg):
    scored = int(cfg["n_routed_experts"]) * _share(cfg)
    return int(cfg["hidden_size"]) * scored + scored


def _body_weights(cfg):
    """Weights a token is multiplied by HERE, all blocks: the mixers', a router over all
    the experts, the held share's even part of the top-6 and the shared expert."""
    count = block_counts(cfg)
    routed = _router_weights(cfg) + _shared_weights(cfg) + _expert_weights(cfg) * (
        int(cfg["num_experts_per_tok"]) / _share(cfg))
    return count["M"] * _mamba_weights(cfg)[0] + count["*"] * _attn_weights(cfg) + count["E"] * routed


def scan_flops_per_token(cfg):
    """The recurrence's own FLOPs a token and Mamba-2 block: the decay, the outer product
    and the read-out over the (H, P, N) state, 2 operations an entry each."""
    h, p, _, n, _ = _ssm_sizes(cfg)
    return 6.0 * h * p * n


def fwd_flops_per_token(cfg, seq_len):
    """Forward model FLOPs a token of the NO-CACHE forward at ``seq_len``: the weights a
    token is multiplied by, the recurrence, scores and values at 2 x 128 a pair and head
    over the causal half, the head."""
    n, _, d = _attn_sizes(cfg)
    count = block_counts(cfg)
    pairs = count["*"] * (seq_len + 1) / 2
    return (2.0 * (_body_weights(cfg) + int(cfg["hidden_size"]) * int(cfg["vocab_size"]))
            + count["M"] * scan_flops_per_token(cfg) + 2 * 2.0 * n * d * pairs)


def serve_dims(cfg):
    """This model's served work in the sizes ``lib/flops.py`` counts from, over the
    published blocks this copy runs (``layers``): ``head_dim`` = 128 x the share of the
    blocks that are attention (a live position costs K and V in those alone: 2 x 2 x 128 x
    2 B = 1,024 B a block, 3,072 B over 3 of 26), ``ffn`` (with ``mlp_matrices`` 1) whatever
    a token's weights hold beyond the formula's four hidden x (heads x hidden // heads)
    projections, a block on average. The state's bytes have no term there (the module's
    note)."""
    hid, blocks = int(cfg["hidden_size"]), int(cfg["published_blocks"])
    n, kv, d = _attn_sizes(cfg)
    return {"hidden": hid, "heads": n, "kv_heads": kv,
            "head_dim": d * block_counts(cfg)["*"] / blocks,
            "ffn": (_body_weights(cfg) / blocks - 4 * hid * (hid // n) * n) / hid,
            "mlp_matrices": 1, "layers": blocks, "vocab": int(cfg["vocab_size"])}


def least_bytes_per_position(cfg, n, itemsize=2):
    """K and V a decode step must read of a row of ``n`` live positions, over all blocks,
    a live position: the attention blocks' alone, whatever ``n``."""
    _, kv, d = _attn_sizes(cfg)
    return 2 * kv * d * itemsize * block_counts(cfg)["*"]


def served_params(cfg):
    """Parameters ANY forward must read, whatever implements it and however few tokens it
    holds: ``a_forward``: every block's norm, the Mamba-2 blocks whole, the attention
    blocks' projections, an expert block's router (matrix and bias over ALL the experts)
    and shared expert and NONE of its routed experts (one token may choose no expert this
    copy holds: the module's note), the final norm and the untied head; ``a_token``: its
    row of the embedding."""
    hid = int(cfg["hidden_size"])
    count = block_counts(cfg)
    body = (count["M"] * (sum(_mamba_weights(cfg)) + hid) + count["*"] * (_attn_weights(cfg) + hid)
            + count["E"] * (_router_weights(cfg) + _shared_weights(cfg) + hid))
    return {"a_forward": body + hid + hid * int(cfg["vocab_size"]), "a_token": hid}


def decode_attn_bytes(cfg, full_live, window_live, new_positions, full_layers, window_layers,
                      itemsize=2):
    """Least HBM bytes of ONE decode step's cached attention, all attention blocks: the
    positions live in the rows read once a block and the step's new positions written
    once, K and V (2 x 2 x 128 x ``itemsize`` = 1,024 B a position and block in bf16).
    The stack has no window layers (``window_*`` come 0). Weights left out: reads low."""
    _, kv, d = _attn_sizes(cfg)
    per = 2 * kv * d * itemsize
    return per * (full_live * full_layers + window_live * window_layers
                  + new_positions * (full_layers + window_layers))


def expert_layers(cfg):
    """Blocks of this copy that carry routed experts."""
    return block_counts(cfg)["E"]


def expert_step_bytes(cfg, touched, itemsize=2):
    """Least HBM bytes of ONE decode step's routed experts, all expert blocks: the two
    matrices of the ``touched`` held experts a block that got a row (the engine's counter
    ``moe_held_experts_touched``, a mean over the expert blocks), read once. Rows in and
    out are left out, so a share over this reads low."""
    return itemsize * touched * expert_layers(cfg) * _expert_weights(cfg)


def ssm_state_bytes(cfg, itemsize=2):
    """{"conv": .., "scan": ..}: what a row keeps of one Mamba-2 block: the conv's last
    ``taps - 1`` inputs in the compute type, the (H, P, N) state in float32."""
    h, p, g, n, k = _ssm_sizes(cfg)
    return {"conv": (k - 1) * (h * p + 2 * g * n) * itemsize, "scan": h * p * n * 4}


def ssm_step_bytes(cfg, rows, state_layers, itemsize=2):
    """Least HBM bytes of ONE decode step's Mamba-2 mixers, ``state_layers`` blocks over
    ``rows`` rows: each block's weights read once (projections, taps, vectors) at
    ``itemsize``, every row's conv tail and float32 state read once and written once.
    The step's activations (a row of 10,304 values in, 2,688 out) are left out."""
    proj, rest = _mamba_weights(cfg)
    state = sum(ssm_state_bytes(cfg, itemsize).values())
    return state_layers * (itemsize * (proj + rest) + 2 * rows * state)


def ssm_state_step_bytes(cfg, rows, state_layers, itemsize=2):
    """The part of `ssm_step_bytes` that is the state's alone: read once, written once."""
    return state_layers * 2 * rows * sum(ssm_state_bytes(cfg, itemsize).values())


def ssm_scan_step_bytes(cfg, rows, state_layers):
    """The part of `ssm_state_step_bytes` that is the float32 scan state's: what the
    single-step body itself moves (the conv tail goes through ``state_read`` /
    ``state_write``)."""
    return state_layers * 2 * rows * ssm_state_bytes(cfg)["scan"]


def ssm_chunk_scan_work(cfg, tokens, state_layers, itemsize=2):
    """(FLOPs, least HBM bytes) of the scan of ONE prompt chunk of ``tokens`` positions,
    ``state_layers`` blocks: the recurrence's own operations (`scan_flops_per_token`; the
    chunked form trades state updates for GEMMs of about the same count); x, B and C read
    and y written at ``itemsize``, dt float32, the row's float32 state read as it enters
    and written as it leaves."""
    h, p, g, n, _ = _ssm_sizes(cfg)
    moved = tokens * ((2 * h * p + 2 * g * n) * itemsize + 4 * h) + 2 * ssm_state_bytes(cfg)["scan"]
    return state_layers * tokens * scan_flops_per_token(cfg), state_layers * moved
