"""ibm-granite/granite-4.0-h-small (published ``model_type`` granitemoehybrid, "Granite 4.0-H
Small 32B-A9B"), written from the published config's keys and the layer equations of ISSUE
70. (This file's name is the configuration file's ``model_type``: ``lib/reference.load``
finds a reference by that key and ``granitemoehybrid.py`` is granite-4.0-h-micro's DENSE
training reference, whose ``num_local_experts`` is 0; nothing here imports it or the
program.) With ``m = residual_multiplier`` (0.22), every layer ``l``:

    x0 = embedding_multiplier * E[ids]                           12
    y = RMS_l(x);  u = Mamba2(y) | Attn(y) by layer_types[l];  x = x + m u
    y = RMS'_l(x); x = x + m (moe(y) + shared(y))
    logits = RMS_f(x) E^T / logits_scaling                       tied table, 16

``RMS_w(x) = x rsqrt(mean x^2 + rms_norm_eps) w``.

``Mamba2`` (one group: ``mamba_n_groups`` 1):
    [z | xBC | dt] = y W_in                          8192 | 8192 + 2 x 128 | 128 = 16768
    xBC = silu(conv(xBC) + b)                        depthwise, causal, 4 taps, zeros before
                                                     the sequence, the last tap on the position
    [x | B | C] = xBC                                x: 128 heads of 64; B, C: ONE group of 128
    dt = softplus(dt + dt_bias);  A = -exp(A_log)    no clamp
    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T       a (64, 128) state a head, zero before the
    y_t = H_t C_t + D x_t                            sequence: THE RECURRENCE, a position at a
                                                     time (`recurrence`), not the chunked form
    g = RMS_w(y * silu(z))                           over all 8192 channels (within each group,
                                                     and there is one), 8192 gains
    u = g W_out

``Attn``: q, k, v = y W_q, y W_k, y W_v (32 | 8 | 8 heads of 128; query head n reads key /
    value head n // 4); softmax(q k^T * attention_multiplier) v over every j <= p (0.0078125
    = 1/128, NOT 1/sqrt(128)); NO rotary and no other position signal
    (``position_embedding_type`` nope); W_o; no bias.

``moe``: r = y W_r in float32 over all 72 experts; the 10 largest; gates = softmax over those
    10 logits; moe = sum_e gate_e W2_e (silu(W1g_e y) * W1u_e y) over the chosen experts THIS
    COPY HOLDS (width 768). ``shared`` = Ws2 (silu(Wsg y) * Wsu y) at width 1536, added as it
    is, whatever the router chose.

No cache: every position's keys and values are made once and every query sees its keys
through a mask; a Mamba-2 layer runs its whole sequence from a zero state.

Departures from the published description, all in the configuration file: the held share of
the experts (``expert_share``: pairs on experts this copy does not hold are left out of the
sum, as in the program; the router scores all 72 and the gates are NOT renormalised over the
held ones), the vocabulary slice, the depth (``layer_types``' first ``num_hidden_layers``)
and the readings under ``assumed``.

``published_weights`` hands the program's own arrays on (no re-laid-out copy). q, k and v
stay in the program's fused projection (columns by key/value head: its 4 query heads, its
key head, its value head); the conv's taps stay (taps, channels), the last tap on the
position; an expert's published fused ``input_linear`` is the pair (gate, up), each (hidden,
768), and ``output_linear`` (768, hidden); the shared expert's ``input_linear`` is (hidden,
[gate 1536 | up 1536]).

`logits` keeps every float32 intermediate to a block (``lib/serve.compare_rows`` runs it
ONCE over a slot's 16,384 positions beside the bf16 weights): a Mamba-2 layer a block of
tokens at a time, the conv's last inputs and the recurrence's state handed from block to
block (the same recurrence: a position at a time, from zero before the sequence); attention
a key/value head's 4 query heads and a block of queries at a time; the experts a QUARTER of
the held ones and a block of tokens at a time (a layer's 36 held experts are 1.36 GB in
float32); the head a block of columns at a time.

``lib/flops.py``'s served counts are a dense K/V decoder's. `serve_dims` and `served_params`
give LOWER bounds of this stack's work (a Mamba-2 layer has no K and V, a state's bytes have
no term there: ``serve_hbm_roofline`` and ``decode_step_hbm_roofline`` read as floors;
`served_params` counts of the routed experts NONE: a forward of one token may choose no
expert this copy holds). The exact counts are `decode_attn_bytes` (a step's cached
attention), `expert_step_bytes` (the experts a step TOUCHED), `ssm_step_bytes` (a step's
Mamba-2 mixers: state read and written once, conv tail, projections once),
`ssm_chunk_scan_work` and `expert_chunk_work` (a prompt chunk's routed experts).
"""

import math

import jax
import jax.numpy as jnp

from benchmark.lib.reference import F32, rms_norm

#: queries a step of the attention takes, tokens a step of a Mamba-2 layer or of the experts
QUERY_BLOCK, TOKEN_BLOCK = 512, 1024
#: parts a layer's held experts are multiplied in
EXPERT_PARTS = 4
#: columns of the head multiplied at once
VOCAB_BLOCK = 25088


def kinds(cfg):
    """``layer_types`` of the layers this copy runs: "mamba" | "attention"."""
    return list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]


def published_weights(params, cfg):
    """The program's tree under the published names, a layer an entry of `kinds`."""
    layers = []
    for lp in params["layers"]:
        f = lp["mlp"]
        lw = {"input_layernorm": lp["attn_norm"]["scale"],
              "post_attention_layernorm": lp["mlp_norm"]["scale"],
              "router": f["router"]["w"],
              "experts_gate": f["w1"], "experts_up": f["w3"], "experts_output": f["w2"],
              "shared_input": f["shared"]["w13"], "shared_output": f["shared"]["w2"]}
        if "ssm" in lp:
            m = lp["ssm"]
            lw["mamba"] = {"in_proj": m["in_proj"], "conv_w": m["conv_w"], "conv_b": m["conv_b"],
                           "dt_bias": m["dt_bias"], "A_log": m["A_log"], "D": m["D"],
                           "norm": m["norm"], "out_proj": m["out_proj"]}
        else:
            lw["self_attn"] = {"qkv_proj": lp["attn"]["wqkv"], "o_proj": lp["attn"]["wo"]}
        layers.append(lw)
    got = ["mamba" if "mamba" in lw else "attention" for lw in layers]
    if got != kinds(cfg):
        raise ValueError(f"the program's layers are {got}, the configuration's first "
                         f"{cfg['num_hidden_layers']} layer_types are {kinds(cfg)}")
    return {"embed_tokens": params["embed"]["tok"], "norm": params["final_norm"]["scale"],
            "layers": layers}


def _blocks(n, size):
    """``n`` as whole blocks of at most ``size``: (blocks, block)."""
    block = math.gcd(n, size) if n % size else size
    return n // block, block


def _attn_sizes(cfg):
    n = int(cfg["num_attention_heads"])
    return n, int(cfg["num_key_value_heads"]), int(cfg["hidden_size"]) // n


def _ssm_sizes(cfg):
    """(heads, head size, groups, state, taps) of a Mamba-2 layer."""
    return (int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]), int(cfg["mamba_n_groups"]),
            int(cfg["mamba_d_state"]), int(cfg["mamba_d_conv"]))


def _share(cfg):
    """(rank, of) of the held share of the experts."""
    share = cfg.get("expert_share") or {"rank": 0, "of": 1}
    return int(share["rank"]), int(share["of"])


# -- the layers -----------------------------------------------------------------------


def recurrence(x, dt, a, b_mat, c_mat, state=None):
    """``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``, ``y_t = H_t C_t`` a position at a
    time: x (s, H, P), dt (s, H), a (H,), b_mat / c_mat (s, G, N) -> (y (s, H, P), the
    state the positions leave, (H, P, N)). ``state``: the state they enter with (None:
    zero, before the sequence). Head j reads group ``j // (H / G)``."""
    s, h, p = x.shape
    g, n = b_mat.shape[1:]
    per = h // g

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        b_h, c_h = jnp.repeat(b_t, per, axis=0), jnp.repeat(c_t, per, axis=0)  # (H, N)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_h)

    start = jnp.zeros((h, p, n), F32) if state is None else state
    leaving, y = jax.lax.scan(step, start, (x, dt, b_mat, c_mat))
    return y, leaving


def mamba(a_in, w, cfg):
    """A Mamba-2 layer's mixer on (b, s, hidden) -> the same, a row at a time."""
    return jax.vmap(lambda row: _mamba_row(row, w["mamba"], cfg))(a_in)


def _mamba_row(x_in, w, cfg):
    """One row, `TOKEN_BLOCK` positions at a time: what a block hands to the next is what
    the equations carry from position to position, the conv's last ``taps - 1`` inputs and
    the recurrence's state, both zero before the sequence."""
    h, p, g, n, k = _ssm_sizes(cfg)
    d_inner, eps = h * p, float(cfg["rms_norm_eps"])
    conv_dim = d_inner + 2 * g * n
    blocks, block = _blocks(x_in.shape[0], TOKEN_BLOCK)
    a = -jnp.exp(w["A_log"])

    def tokens(carry, xb):
        tail, state = carry
        zxbcdt = xb @ w["in_proj"]
        z, xbc, dt = (zxbcdt[:, :d_inner], zxbcdt[:, d_inner:d_inner + conv_dim],
                      zxbcdt[:, d_inner + conv_dim:])
        seen = jnp.concatenate([tail, xbc], axis=0)  # (taps - 1 + block, channels)
        conv = w["conv_b"] + sum(seen[j:j + block] * w["conv_w"][j] for j in range(k))
        xbc = jax.nn.silu(conv)
        x = xbc[:, :d_inner].reshape(block, h, p)
        b_mat = xbc[:, d_inner:d_inner + g * n].reshape(block, g, n)
        c_mat = xbc[:, d_inner + g * n:].reshape(block, g, n)
        y, state = recurrence(x, jax.nn.softplus(dt + w["dt_bias"]), a, b_mat, c_mat, state)
        y = y + w["D"][:, None] * x
        gated = (y.reshape(block, d_inner) * jax.nn.silu(z)).reshape(block, g, d_inner // g)
        gated = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
        out = (gated.reshape(block, d_inner) * w["norm"]) @ w["out_proj"]
        return (seen[block:], state), out

    start = (jnp.zeros((k - 1, conv_dim), F32), jnp.zeros((h, p, n), F32))
    out = jax.lax.scan(tokens, start, x_in.reshape(blocks, block, -1))[1]
    return out.reshape(x_in.shape)


def attention(a, w, cfg):
    """An attention layer's mixer on (b, s, hidden) -> the same: GQA, causal, no position
    signal, scores times ``attention_multiplier``."""
    n, kv, d = _attn_sizes(cfg)
    per, scale = n // kv, float(cfg["attention_multiplier"])
    b, s, hidden = a.shape
    blocks, block = _blocks(s, QUERY_BLOCK)
    w = w["self_attn"]
    wqkv = w["qkv_proj"].reshape(hidden, kv, (per + 2) * d).transpose(1, 0, 2)
    wo = w["o_proj"].reshape(kv, per * d, hidden)
    key_pos = jnp.arange(s)

    def group(acc, args):
        wqkv_g, wo_g = args
        qkv = (a @ wqkv_g).reshape(b, s, per + 2, d)
        q, k, v = qkv[:, :, :per], qkv[:, :, per], qkv[:, :, per + 1]

        def queries(i):
            at = i * block + jnp.arange(block)
            scores = jnp.einsum("bqnd,bkd->bnqk", q[:, at], k) * scale
            seen = key_pos[None, :] <= at[:, None]
            scores = jnp.where(seen[None, None], scores, -jnp.inf)
            return jnp.einsum("bnqk,bkd->bqnd", jax.nn.softmax(scores, axis=-1), v)

        o = jax.lax.map(queries, jnp.arange(blocks))  # (blocks, b, block, per, d)
        o = jnp.moveaxis(o, 0, 1).reshape(b, s, per * d)
        return acc + o @ wo_g, None

    return jax.lax.scan(group, jnp.zeros_like(a), (wqkv, wo))[0]


def route(y, w, cfg):
    """(tokens, experts) gates over ALL the experts the router scores: softmax over a
    token's ``num_experts_per_tok`` largest logits, 0 for an expert it did not choose."""
    r = y @ w["router"]
    top, chosen = jax.lax.top_k(r, int(cfg["num_experts_per_tok"]))
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.zeros_like(r).at[rows, chosen].set(jax.nn.softmax(top, axis=-1))


def shared_mlp(y, w):
    gate, up = jnp.split(y @ w["shared_input"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w["shared_output"]


def experts(y, w, cfg):
    """The MLP of a layer on (b, s, hidden): the held experts' part of the routed sum
    (``expert_share`` says which are held) plus the shared expert, as it is."""
    b, s, hidden = y.shape
    held = w["experts_output"].shape[0]
    first = _share(cfg)[0] * held
    blocks, block = _blocks(b * s, TOKEN_BLOCK)
    parts = EXPERT_PARTS if held % EXPERT_PARTS == 0 else 1
    xs = y.reshape(blocks, block, hidden)

    def part(lo, hi):
        gate, up, down = (w[k][lo:hi] for k in ("experts_gate", "experts_up", "experts_output"))

        def tokens(x):
            # pairs on absent experts: left out; the gates stay as the router made them
            gates = route(x, w, cfg)[:, first + lo:first + hi]
            mid = (jax.nn.silu(jnp.einsum("th,ehf->tef", x, gate))
                   * jnp.einsum("th,ehf->tef", x, up))
            return jnp.einsum("tef,efh->th", mid * gates[:, :, None], down)

        return jax.lax.map(tokens, xs)

    out = jax.lax.map(lambda x: shared_mlp(x, w), xs)
    for i in range(parts):
        out = out + part(i * held // parts, (i + 1) * held // parts)
    return out.reshape(b, s, hidden)


MIXERS = {"mamba": mamba, "attention": attention}


def logits(w, tokens, cfg):
    eps, m = float(cfg["rms_norm_eps"]), float(cfg["residual_multiplier"])
    x = float(cfg["embedding_multiplier"]) * w["embed_tokens"][tokens]
    for kind, lw in zip(kinds(cfg), w["layers"]):
        x = x + m * MIXERS[kind](rms_norm(x, lw["input_layernorm"], eps), lw, cfg)
        x = x + m * experts(rms_norm(x, lw["post_attention_layernorm"], eps), lw, cfg)
    b, s, hidden = x.shape
    h = rms_norm(x, w["norm"], eps).reshape(b * s, hidden)
    table = w["embed_tokens"]  # tied
    parts = [h @ table[i:i + VOCAB_BLOCK].T for i in range(0, table.shape[0], VOCAB_BLOCK)]
    out = jnp.concatenate(parts, axis=-1) / float(cfg["logits_scaling"])
    return out.reshape(b, s, table.shape[0])


# -- counts ---------------------------------------------------------------------------


def layer_counts(cfg):
    """{"mamba": .., "attention": ..} of the layers this copy runs."""
    return {k: kinds(cfg).count(k) for k in ("mamba", "attention")}


def _expert_weights(cfg):
    """One routed expert's three matrices."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])


def _shared_weights(cfg):
    return 3 * int(cfg["hidden_size"]) * int(cfg["shared_intermediate_size"])


def _mamba_weights(cfg):
    """(projections, the rest) of a Mamba-2 mixer: in_proj and out_proj; taps and conv
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
    """The router's matrix: over ALL the experts, held here or not."""
    return int(cfg["hidden_size"]) * int(cfg["num_local_experts"]) * _share(cfg)[1]


def _body_weights(cfg):
    """Weights a token is multiplied by HERE, all layers: the mixers', a router over all
    the experts, the held share's even part of the top-10 and the shared expert."""
    count = layer_counts(cfg)
    routed = _router_weights(cfg) + _shared_weights(cfg) + _expert_weights(cfg) * (
        int(cfg["num_experts_per_tok"]) / _share(cfg)[1])
    return (count["mamba"] * _mamba_weights(cfg)[0] + count["attention"] * _attn_weights(cfg)
            + int(cfg["num_hidden_layers"]) * routed)


def scan_flops_per_token(cfg):
    """The recurrence's own FLOPs a token and Mamba-2 layer: the decay, the outer product
    and the read-out over the (H, P, N) state, 2 operations an entry each."""
    h, p, _, n, _ = _ssm_sizes(cfg)
    return 6.0 * h * p * n


def fwd_flops_per_token(cfg, seq_len):
    """Forward model FLOPs a token of the NO-CACHE forward at ``seq_len``: the weights a
    token is multiplied by, the recurrence, scores and values at 2 x 128 a pair and head
    over the causal half, the tied head."""
    n, _, d = _attn_sizes(cfg)
    count = layer_counts(cfg)
    pairs = count["attention"] * (seq_len + 1) / 2
    return (2.0 * (_body_weights(cfg) + int(cfg["hidden_size"]) * int(cfg["vocab_size"]))
            + count["mamba"] * scan_flops_per_token(cfg) + 2 * 2.0 * n * d * pairs)


def serve_dims(cfg):
    """This model's served work in the sizes ``lib/flops.py`` counts from: ``head_dim`` =
    128 x the share of the layers that are attention (a live position costs K and V in
    those alone: 2 x 8 x 128 x 2 B = 4,096 B a layer, one layer in ten), ``ffn`` (with
    ``mlp_matrices`` 1) whatever a token's weights hold beyond the formula's four hidden x
    (heads x hidden // heads) projections, a layer on average. The state's bytes have no
    term there (the module's note)."""
    hid, layers = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    n, kv, d = _attn_sizes(cfg)
    return {"hidden": hid, "heads": n, "kv_heads": kv,
            "head_dim": d * layer_counts(cfg)["attention"] / layers,
            "ffn": (_body_weights(cfg) / layers - 4 * hid * (hid // n) * n) / hid,
            "mlp_matrices": 1, "layers": layers, "vocab": int(cfg["vocab_size"])}


def least_bytes_per_position(cfg, n, itemsize=2):
    """K and V a decode step must read of a row of ``n`` live positions, over all layers,
    a live position: the attention layers' alone, whatever ``n``."""
    _, kv, d = _attn_sizes(cfg)
    return 2 * kv * d * itemsize * layer_counts(cfg)["attention"]


def served_params(cfg):
    """Parameters ANY forward must read, whatever implements it and however few tokens it
    holds: ``a_forward``: every layer's two norms, the Mamba-2 mixers whole, the attention
    layers' projections, every layer's router (over ALL the experts) and shared expert and
    NONE of its routed experts (one token may choose no expert this copy holds: the module's
    note), the final norm and the tied table once (the head reads all of it; the
    embedding's rows are among them); ``a_token``: nothing more."""
    hid = int(cfg["hidden_size"])
    count = layer_counts(cfg)
    body = (count["mamba"] * sum(_mamba_weights(cfg)) + count["attention"] * _attn_weights(cfg)
            + int(cfg["num_hidden_layers"]) * (_router_weights(cfg) + _shared_weights(cfg)
                                               + 2 * hid))
    return {"a_forward": body + hid + hid * int(cfg["vocab_size"]), "a_token": 0}


def decode_attn_bytes(cfg, full_live, window_live, new_positions, full_layers, window_layers,
                      itemsize=2):
    """Least HBM bytes of ONE decode step's cached attention, all attention layers: the
    positions live in the rows read once a layer and the step's new positions written
    once, K and V (2 x 8 x 128 x ``itemsize`` = 4,096 B a position and layer in bf16).
    The stack has no window layers (``window_*`` come 0). Weights left out: reads low."""
    _, kv, d = _attn_sizes(cfg)
    per = 2 * kv * d * itemsize
    return per * (full_live * full_layers + window_live * window_layers
                  + new_positions * (full_layers + window_layers))


def expert_layers(cfg):
    """Layers of this copy that carry routed experts: all of them."""
    return int(cfg["num_hidden_layers"])


def expert_step_bytes(cfg, touched, itemsize=2):
    """Least HBM bytes of ONE decode step's routed experts, all layers: the three matrices
    of the ``touched`` held experts a layer that got a row (the engine's counter
    ``moe_held_experts_touched``, a mean over the layers), read once. Rows in and out are
    left out, so a share over this reads low."""
    return itemsize * touched * expert_layers(cfg) * _expert_weights(cfg)


def expert_chunk_work(cfg, pairs, touched, itemsize=2):
    """(FLOPs, least HBM bytes) of ONE prompt chunk's routed experts, all layers: ``pairs``
    (token, expert) pairs a layer on the held experts, each through the expert's three
    matrices at 2 operations an entry; the three matrices of the ``touched`` held experts a
    layer read once, and every pair's row read (hidden) and its product written (hidden) at
    ``itemsize``. The gate and up products' (pairs, 768) intermediates are left out (a
    fused body keeps them on the chip), so a share over this reads low."""
    hid = int(cfg["hidden_size"])
    flops = 2.0 * pairs * _expert_weights(cfg)
    moved = itemsize * (touched * _expert_weights(cfg) + 2 * pairs * hid)
    return expert_layers(cfg) * flops, expert_layers(cfg) * moved


def ssm_state_bytes(cfg, itemsize=2):
    """{"conv": .., "scan": ..}: what a row keeps of one Mamba-2 layer: the conv's last
    ``taps - 1`` inputs in the compute type, the (H, P, N) state in float32."""
    h, p, g, n, k = _ssm_sizes(cfg)
    return {"conv": (k - 1) * (h * p + 2 * g * n) * itemsize, "scan": h * p * n * 4}


def ssm_step_bytes(cfg, rows, state_layers, itemsize=2):
    """Least HBM bytes of ONE decode step's Mamba-2 mixers, ``state_layers`` layers over
    ``rows`` rows: each layer's weights read once (projections, taps, vectors) at
    ``itemsize``, every row's conv tail and float32 state read once and written once.
    The step's activations (a row of 16,768 values in, 4,096 out) are left out."""
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
    ``state_layers`` layers: the recurrence's own operations (`scan_flops_per_token`; the
    chunked form trades state updates for GEMMs of about the same count); x, B and C read
    and y written at ``itemsize``, dt float32, the row's float32 state read as it enters
    and written as it leaves."""
    h, p, g, n, _ = _ssm_sizes(cfg)
    moved = tokens * ((2 * h * p + 2 * g * n) * itemsize + 4 * h) + 2 * ssm_state_bytes(cfg)["scan"]
    return state_layers * tokens * scan_flops_per_token(cfg), state_layers * moved
