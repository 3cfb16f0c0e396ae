"""The gated short convolution of an LFM2-class stack (``model_type`` lfm2_moe): what
a layer of kind ``"shortconv"`` runs in place of attention.

    [B | C | u] = W_in h                          three widths of hidden, in that order
    z = B * u
    c_t = sum_j w[j] * z_{t-K+1+j}                depthwise, causal, K = shortconv_taps
                                                  (3), zeros before the sequence, tap
                                                  K - 1 on the current position
    out = W_out (C * c)

No bias, no activation (HF ``modeling_lfm2_moe.py`` Lfm2MoeShortConv with
``conv_bias`` false). The whole-sequence `block` is the training form; it runs
`ops/ssd.causal_conv1d`, the body a Mamba-2 mixer's conv takes on the CPU. The fused
conv kernels of `ops/ssd.py` apply SiLU and have run on a chip at K 4 alone, so this
kind takes the plain body everywhere and `path_counts` says so.

The kind keeps a STATE, not positions (``Mixer.state`` in `models/mixers.py`): the
last ``K - 1`` values of z a row, ``(K - 1) x hidden`` values in the compute type side
by side, one entry a row of the slot cache's state stack (`models/generation.SlotStacks`). `cached_block`
is the layer of the cached forwards: the state read at the forward's start (ZERO
where the forward starts at position 0, whatever the row held), the same
`causal_conv1d` over [state | z], the state written as of the forward's last REAL
row. The published code keeps K columns; the oldest is never read again.

Scopes under ``shortconv``: ``in_proj``, ``state_read``, ``conv``, ``state_write``,
``out_proj`` (PERF.md section 3; the ``shortconv_*`` / ``state_cache_*`` benchmark
metrics read them); the whole-sequence form opens the three it has.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from galvatron_tpu.models.mixers import tally
from galvatron_tpu.models.placement import LOCAL, Placement
from galvatron_tpu.ops.ssd import causal_conv1d

Params = Dict[str, Any]


def param_count(cfg) -> int:
    h = cfg.hidden_size
    return h * 3 * h + cfg.shortconv_taps * h + h * h


def saved_bytes_per_token(cfg, itemsize: int) -> float:
    """What the mixer keeps for the backward in place of an attention layer's qkv +
    context: in_proj's output, z, the conv's output and the gated product."""
    return 6 * cfg.hidden_size * itemsize


def fwd_flops_per_token(cfg) -> float:
    """Beside the weights': the two gates and the K taps, a multiply-add a channel each."""
    return 2.0 * cfg.hidden_size * (cfg.shortconv_taps + 2)


def path_counts(cfg) -> dict:
    """Every layer on the plain conv (`ops/ssd.causal_conv1d`): see the module's note."""
    return {"conv": tally("plain", cfg.kinds.count("shortconv"))}


def init_params(key, cfg) -> Params:
    from galvatron_tpu.models.modeling import _dense_init

    h = cfg.hidden_size
    ks = jax.random.split(key, 3)
    bound = 1.0 / np.sqrt(cfg.shortconv_taps)
    return {
        "in_proj": _dense_init(ks[0], h, 3 * h, cfg.param_dtype),
        "conv_w": jax.random.uniform(ks[1], (cfg.shortconv_taps, h), cfg.param_dtype, -bound, bound),
        "out_proj": _dense_init(ks[2], h, h, cfg.param_dtype),
    }


def annotations(cfg) -> Params:
    """No ``tp`` axis: the three gates are slices of ONE projection's columns, channel c
    of each beside the others (tensor parallelism on such a layer is refused); ZeRO
    shards the hidden-size dims."""
    return {"in_proj": ("fsdp", None), "conv_w": (None, None), "out_proj": (None, "fsdp")}


def _gates(x, p: Params):
    """The normed layer input -> (z = B * u, C), each (B, S, hidden)."""
    h = x.shape[-1]
    with jax.named_scope("in_proj"):
        bcu = x @ p["in_proj"].astype(x.dtype)
        return bcu[..., :h] * bcu[..., 2 * h:], bcu[..., h:2 * h]


def _conv(z, w):
    """`causal_conv1d` without a bias: (B, S, hidden) -> the same."""
    return causal_conv1d(z, w, jnp.zeros((w.shape[1],), jnp.float32))


def _out(c, gate, p: Params):
    with jax.named_scope("out_proj"):
        return (gate * c) @ p["out_proj"].astype(c.dtype)


@jax.named_scope("shortconv")
def block(x, p: Params, cfg, place: Placement = LOCAL):
    """(B, S, hidden) normed layer input -> the mixer's output, same shape: the whole
    sequence from a zero state. ``place`` is asked nothing: GSPMD partitions the plain
    body by itself."""
    z, gate = _gates(x, p)
    with jax.named_scope("conv"):
        c = _conv(z, p["conv_w"])
    return _out(c, gate, p)


# -- the state the cached forwards keep ------------------------------------------------


def state_shape(cfg) -> tuple:
    """What a row keeps of one layer: the last K - 1 values of z, side by side. (As (K -
    1, hidden) the chip's compiler re-laid the whole stack on its way in and out of
    every step, 2 rows to a tile of 16: compiled for a described v5e; so the rows of the
    cache are the tiles' rows.)"""
    return ((cfg.shortconv_taps - 1) * cfg.hidden_size,)


def init_state(cfg, layers: int, rows: int):
    """The state stack of ``layers`` such layers over ``rows`` rows, zero."""
    return jnp.zeros((layers, rows) + state_shape(cfg), cfg.dtype)


def state_bytes_per_row(cfg) -> int:
    """One layer's."""
    return int(np.prod(state_shape(cfg))) * jnp.dtype(cfg.dtype).itemsize


def fresh(prev, offsets):
    """The state a forward at ``offsets`` starts from: ``prev`` (B, K - 1, hidden) as the
    rows hold it, ZERO for a row whose forward starts at position 0."""
    started = jnp.reshape(jnp.asarray(offsets), (-1, 1, 1)) > 0
    return jnp.where(started, prev, jnp.zeros_like(prev))


def stored(new, dtype):
    """What is written of the state's new value: itself, in the stack's type."""
    return new.astype(dtype)


@jax.named_scope("shortconv")
def cached_block(x, p: Params, cfg, state, layer: int, slot, offsets, last):
    """The layer over the state stack ``state`` (layers, rows, (K - 1) x hidden) -> (y,
    state). ``x`` (B, s, hidden) is the normed input of the forward's ``s`` new
    positions at ``offsets`` (`generation.forward_with_cache`'s: a scalar, with ``slot``
    one row of the stack; or a row each); ``last`` (traced) is the forward's last REAL
    row of the s: rows after it are padding and do not reach the state.

    A forward that starts at position 0 reads a ZERO state whatever the row holds (the
    slot's previous request, an idle row's decode steps): no admission has to clear
    anything. The write is one ``dynamic_update_slice`` on the stacked array at a
    static layer (`generation.write_layer`'s rule)."""
    b, s, h = x.shape
    k = cfg.shortconv_taps
    z, gate = _gates(x, p)
    with jax.named_scope("state_read"):
        if slot is None:
            prev = state[layer]
        else:
            prev = jax.lax.dynamic_slice(state, (layer, slot, 0), (1, 1, (k - 1) * h))[0]
        prev = fresh(prev.reshape(b, k - 1, h), offsets).astype(z.dtype)
    with jax.named_scope("conv"):
        seen = jnp.concatenate([prev, z], axis=1)  # (B, K - 1 + s, hidden)
        c = _conv(seen, p["conv_w"])[:, k - 1:]
    with jax.named_scope("state_write"):
        # z of the K - 1 positions up to row ``last``: row t of z is row K - 1 + t of ``seen``
        new = stored(jax.lax.dynamic_slice_in_dim(seen, last + 1, k - 1, axis=1), state.dtype)
        state = jax.lax.dynamic_update_slice(
            state, new.reshape(1, b, (k - 1) * h), (layer, 0 if slot is None else slot, 0))
    return _out(c, gate, p), state
