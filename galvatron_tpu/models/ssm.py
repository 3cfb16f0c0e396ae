"""The Mamba-2 mixer of a hybrid stack (granitemoehybrid-class models): what a
layer of kind ``"ssm"`` runs in place of attention.

    [z | xBC | dt] = W_in h                      widths d_inner | d_inner + 2 G N | H
    xBC = silu(conv1d_causal_depthwise(xBC) + b)
    [x | B | C] = xBC                             x as H heads of P
    dt = softplus(dt + dt_bias),  A = -exp(A_log)
    y = SSD(x, dt, A, B, C) + D x                 ops/ssd.py, chunked
    y = RMSNorm(y * silu(z)) * scale              gate before the norm, WITHIN each of the
                                                  G groups of d_inner / G channels
    out = W_out y

(HF ``modeling_granitemoehybrid.py`` GraniteMoeHybridMambaLayer, ``modeling_nemotron_h.py``
NemotronHMamba2Mixer; no projection biases, a conv bias. Granite has one group, so its
norm is over all of d_inner; nemotron_h's 8 groups of 512 each have their own
statistics.) Imported only where a configuration has such layers
(`models/mixers.py` names this module in the kind's row and says what it
exposes), so every other model's imports stay what they were.

The whole-sequence `block` is the training form. SERVED, the kind keeps a STATE of two
parts a row and layer (``Mixer.state`` in `models/mixers.py`; `SsmState`, the ``state``
of `models/generation.SlotStacks`): the conv's last ``K - 1`` inputs, ``(K - 1) x (d_inner
+ 2 G N)`` values in the compute type side by side (36,864 B at nemotron_h's sizes), and
the scan's state, (N, H x P) FLOAT32 (2 MiB: a decay in bf16 loses the recurrence after a
few hundred steps, and the state is what carries it from step to step). `cached_block` is
the layer of the cached forwards: both parts read at the forward's start (ZERO where the
forward starts at position 0, whatever the row held), written as of the forward's last
REAL row. A forward of one position a row (a decode step) runs the SINGLE STEP,
`ops/ssd.ssd_step`, over the state stack in place; a forward of more (a prompt chunk,
``generate``'s prefill) the CHUNK form with the entering state, `ops/ssd.ssd_scan_plain`
(the fused scan kernels carry no entering state yet and the fused conv no tail, so a
served chunk takes the plain bodies: PERF.md section 7), padding after ``last`` kept out
of the state by ``dt`` 0 there.

Scopes under ``ssm``: ``in_proj``, ``conv``, ``scan``, ``gate_norm``,
``out_proj`` (PERF.md §3; the ``ssm_*`` benchmark metrics read them); the cached
forwards also ``state_read``, ``step`` (in place of ``scan`` in a decode step) and
``state_write``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from galvatron_tpu.models.mixers import tally
from galvatron_tpu.models.placement import LOCAL, Placement
from galvatron_tpu.ops import ssd
from galvatron_tpu.ops.quant import project as _project
from galvatron_tpu.ops.ssd import causal_conv1d, conv_path, conv_silu_fused, scan_path, ssd_scan

Params = Dict[str, Any]
F32 = jnp.float32


def ssm_dims(cfg):
    """(d_inner, conv channels, in_proj width) of the mixer."""
    d_inner = cfg.ssm_heads * cfg.ssm_head_dim
    conv_dim = d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return d_inner, conv_dim, d_inner + conv_dim + cfg.ssm_heads


def conv_windows(cfg):
    """Widths of x, B and C among the conv's channels."""
    gn = cfg.ssm_groups * cfg.ssm_state
    return cfg.ssm_heads * cfg.ssm_head_dim, gn, gn


def param_count(cfg) -> int:
    d_inner, conv_dim, in_width = ssm_dims(cfg)
    return (cfg.hidden_size * in_width + conv_dim * (cfg.ssm_conv + 1)
            + 3 * cfg.ssm_heads + d_inner + d_inner * cfg.hidden_size)


def saved_bytes_per_token(cfg, itemsize: int) -> float:
    """What the mixer keeps for the backward, in place of an attention layer's
    qkv + context: the in_proj output, the conv's input and output, the scan's
    output and the gated product, and the decay-masked score blocks inside a
    chunk, which are kept: heads x chunk entries a token, float32 decays and
    compute-dtype scores."""
    d_inner, conv_dim, in_width = ssm_dims(cfg)
    mixer = (in_width + 2 * conv_dim + 2 * d_inner) * itemsize
    return mixer + cfg.ssm_heads * cfg.ssm_chunk * (4 + itemsize)


def fwd_flops_per_token(cfg) -> float:
    """The chunked scan's forward FLOPs beside the weights': inside a chunk the
    causal half of C B^T and of scores x, a chunk's state, the entering state's
    read-out. Linear in the sequence."""
    hp_, n_ = cfg.ssm_heads * cfg.ssm_head_dim, cfg.ssm_state
    pairs = (cfg.ssm_chunk + 1) / 2
    return 2.0 * pairs * (cfg.ssm_groups * n_ + hp_) + 4.0 * hp_ * n_


def path_counts(cfg) -> dict:
    """Which scan and which conv a configuration's state-space layers take
    (`ops/ssd.scan_path`, `conv_path`: the functions `block` asks), and which body a
    SERVED row's single step (`ops/ssd.step_path`, what `cached_block` asks: "fused" is
    the kernel `ssm_step`)."""
    layers = cfg.kinds.count("ssm")
    sizes = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state)
    return {
        "scan": tally(scan_path(*sizes, cfg.ssm_chunk, cfg.dtype), layers),
        "conv": tally(conv_path(conv_windows(cfg), cfg.ssm_conv, cfg.dtype), layers),
        "step": tally("fused" if ssd.step_path(*sizes) == "kernel" else "plain", layers),
    }


def init_params(key, cfg) -> Params:
    """The published code's initialisation: ``A_log = log(1..H)``, ``D = 1``,
    ``dt_bias = 1``, gated-norm scale 1; the projections and the conv taps
    uniform in +-1/sqrt(fan_in) like every other projection of the program."""
    from galvatron_tpu.models.modeling import _dense_init

    h = cfg.hidden_size
    d_inner, conv_dim, in_width = ssm_dims(cfg)
    ks = jax.random.split(key, 3)
    bound = 1.0 / np.sqrt(cfg.ssm_conv)
    return {
        "in_proj": _dense_init(ks[0], h, in_width, cfg.param_dtype),
        "conv_w": jax.random.uniform(
            ks[1], (cfg.ssm_conv, conv_dim), cfg.param_dtype, -bound, bound),
        "conv_b": jnp.zeros((conv_dim,), cfg.param_dtype),
        "A_log": jnp.log(jnp.arange(1, cfg.ssm_heads + 1, dtype=cfg.param_dtype)),
        "D": jnp.ones((cfg.ssm_heads,), cfg.param_dtype),
        "dt_bias": jnp.ones((cfg.ssm_heads,), cfg.param_dtype),
        "norm": jnp.ones((d_inner,), cfg.param_dtype),
        "out_proj": _dense_init(ks[2], d_inner, h, cfg.param_dtype),
    }


def annotations(cfg) -> Params:
    """No ``tp`` axis anywhere: tensor parallelism on a state-space layer is
    refused (build_runtime); ZeRO shards the hidden-size dims."""
    return {
        "in_proj": ("fsdp", None), "conv_w": (None, None), "conv_b": (None,),
        "A_log": (None,), "D": (None,), "dt_bias": (None,), "norm": (None,),
        "out_proj": (None, "fsdp"),
    }


def conv_split(zxbcdt, w, b, cfg, place: Placement = LOCAL):
    """``silu(conv1d(xBC) + b)`` of in_proj's output (B, S, z | xBC | dt), as the
    three arrays the scan takes: x (B, S, d_inner), B and C (B, S, G N). Where
    `ops/ssd.conv_path` says fused, each is one window of channels that the
    kernels read out of ``zxbcdt`` where it lies (no slice in front, none
    behind), under ``place.shard_kernel`` on a mesh like the scan; everywhere
    else `causal_conv1d` + ``jax.nn.silu`` on the sliced channels, as ever."""
    windows = conv_windows(cfg)
    d_inner = windows[0]
    starts = (0, d_inner, d_inner + windows[1])
    if conv_path(windows, cfg.ssm_conv, zxbcdt.dtype) == "fused":
        rows, whole = (0, None), (None, None)  # batch over the data-parallel axes

        def window(a, n):
            conv = functools.partial(conv_silu_fused, col0=d_inner + a)
            return place.shard_kernel(conv, [rows, whole, whole], rows)(
                zxbcdt, w[:, a:a + n], b[a:a + n])

        return tuple(window(a, n) for a, n in zip(starts, windows))
    xbc = jax.nn.silu(causal_conv1d(zxbcdt[..., d_inner:d_inner + sum(windows)], w, b))
    return tuple(xbc[..., a:a + n] for a, n in zip(starts, windows))


@jax.named_scope("ssm")
def block(x, p: Params, cfg, place: Placement = LOCAL):
    """(B, S, hidden) normed layer input -> the mixer's output, same shape.
    ``place`` (models/placement.py) is asked for one thing: where the conv or
    the scan is the fused kernels, a mesh must run them on each device's own
    batch rows (GSPMD partitions the plain bodies by itself, a Mosaic call it
    cannot)."""
    dtype = x.dtype
    heads, hd, groups, state = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    d_inner, conv_dim, _ = ssm_dims(cfg)
    with jax.named_scope("in_proj"):
        zxbcdt = _project(x, p["in_proj"])
        z = zxbcdt[..., :d_inner]
        dt = zxbcdt[..., d_inner + conv_dim:]
    with jax.named_scope("conv"):
        xs, b_mat, c_mat = conv_split(zxbcdt, p["conv_w"], p["conv_b"], cfg, place)
    with jax.named_scope("scan"):
        lead = xs.shape[:2]
        xs = xs.reshape(*lead, heads, hd)
        b_mat = b_mat.reshape(*lead, groups, state)
        c_mat = c_mat.reshape(*lead, groups, state)
        dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))
        scan = lambda *t: ssd_scan(*t, cfg.ssm_chunk)  # noqa: E731
        if scan_path(heads, hd, groups, state, cfg.ssm_chunk, dtype) == "fused":
            # batch dim 0 over the data-parallel axes; the heads stay whole (tp is refused)
            rows = (0, None)
            scan = place.shard_kernel(scan, [rows, rows, (None, None), rows, rows], rows)
        y = scan(xs, dt, -jnp.exp(p["A_log"].astype(F32)), b_mat, c_mat)
        y = (y.astype(F32) + p["D"].astype(F32)[:, None] * xs.astype(F32)).astype(dtype)
        y = y.reshape(*lead, d_inner)
    return _gate_out(y, z, p, cfg)


def _gate_out(y, z, p: Params, cfg):
    """``W_out (RMSNorm(y * silu(z)) * scale)``, the norm within each scan group."""
    dtype = y.dtype
    with jax.named_scope("gate_norm"):
        g = y.astype(F32) * jax.nn.silu(z.astype(F32))
        grouped = g.reshape(*g.shape[:-1], cfg.ssm_groups, -1)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, axis=-1, keepdims=True) + cfg.norm_eps)
        y = (grouped.reshape(g.shape) * p["norm"].astype(F32)).astype(dtype)
    with jax.named_scope("out_proj"):
        return _project(y, p["out_proj"])


# -- the state the cached forwards keep ------------------------------------------------


class SsmState(NamedTuple):
    """The state stack of a stack's Mamba-2 layers: ``conv`` (layers, rows, (K - 1) x
    conv channels) in the compute type, ``scan`` (layers, rows, N, H x P) float32."""

    conv: jax.Array
    scan: jax.Array


def state_shapes(cfg) -> dict:
    """What a row keeps of one layer, by part: its trailing shape and type. The conv's
    tail lies side by side (`models/shortconv.state_shape`'s reason), the scan's state
    as `ops/ssd.state_shape` has it."""
    return {"conv": (((cfg.ssm_conv - 1) * ssm_dims(cfg)[1],), jnp.dtype(cfg.dtype)),
            "scan": (ssd.state_shape(cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                     jnp.dtype(F32))}


def init_state(cfg, layers: int, rows: int) -> SsmState:
    """The state stack of ``layers`` such layers over ``rows`` rows, zero."""
    return SsmState(**{part: jnp.zeros((layers, rows) + shape, dtype)
                       for part, (shape, dtype) in state_shapes(cfg).items()})


def state_part_bytes(cfg) -> dict:
    """One layer's bytes a row, by part."""
    return {part: int(np.prod(shape)) * dtype.itemsize
            for part, (shape, dtype) in state_shapes(cfg).items()}


def state_bytes_per_row(cfg) -> int:
    """One layer's, both parts."""
    return sum(state_part_bytes(cfg).values())


def _row_of(stack, layer: int, slot):
    """Layer ``layer``'s entries of a state stack: every row, or row ``slot`` alone."""
    if slot is None:
        return stack[layer]
    return jax.lax.dynamic_slice(
        stack, (layer, slot) + (0,) * (stack.ndim - 2), (1, 1) + stack.shape[2:])[0]


def _write_rows(stack, layer: int, slot, new):
    """`_row_of`'s inverse: one ``dynamic_update_slice`` on the stacked array at a static
    layer (`generation.write_layer`'s rule)."""
    return jax.lax.dynamic_update_slice(
        stack, new.astype(stack.dtype)[None],
        (layer, 0 if slot is None else slot) + (0,) * (stack.ndim - 2))


@jax.named_scope("ssm")
def cached_block(x, p: Params, cfg, state: SsmState, layer: int, slot, offsets, last):
    """The layer over the state stack -> (y, state). ``x`` (B, s, hidden) is the normed
    input of the forward's ``s`` new positions at ``offsets`` (`generation.
    forward_with_cache`'s: a scalar, with ``slot`` one row of the stack; or a row each);
    ``last`` (traced) is the forward's last REAL row of the s: rows after it are padding
    and reach neither part of the state.

    A forward that starts at position 0 reads ZERO for both parts whatever the row holds
    (the slot's previous request, an idle row's decode steps): no admission has to clear
    anything. ``s`` 1 is a decode step: the conv over [tail | the position], the single
    step over the scan's stack in place. ``s`` > 1 is a chunk: the conv over [tail | the
    chunk], the chunked scan from the entering state, ``dt`` 0 after ``last``."""
    b, s, _ = x.shape
    dtype = x.dtype
    heads, hd, groups, n, k = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
                               cfg.ssm_conv)
    d_inner, conv_dim, _ = ssm_dims(cfg)
    started = jnp.broadcast_to(jnp.reshape(jnp.asarray(offsets), (-1,)) > 0, (b,))
    with jax.named_scope("in_proj"):
        zxbcdt = _project(x, p["in_proj"])
        z = zxbcdt[..., :d_inner]
        xbc = zxbcdt[..., d_inner:d_inner + conv_dim]
        dt = jax.nn.softplus(zxbcdt[..., d_inner + conv_dim:].astype(F32)
                             + p["dt_bias"].astype(F32))
    with jax.named_scope("state_read"):
        tail = _row_of(state.conv, layer, slot).reshape(b, k - 1, conv_dim)
        tail = jnp.where(started[:, None, None], tail, jnp.zeros_like(tail)).astype(dtype)
    with jax.named_scope("conv"):
        seen = jnp.concatenate([tail, xbc], axis=1)  # (B, K - 1 + s, conv channels)
        conv = jax.nn.silu(causal_conv1d(seen, p["conv_w"], p["conv_b"])[:, k - 1:])
        xs = conv[..., :d_inner].reshape(b, s, heads, hd)
        b_mat = conv[..., d_inner:d_inner + groups * n].reshape(b, s, groups, n)
        c_mat = conv[..., d_inner + groups * n:].reshape(b, s, groups, n)
    a = -jnp.exp(p["A_log"].astype(F32))
    if s == 1:
        with jax.named_scope("step"):
            y, scan = ssd.ssd_step(state.scan, layer, xs[:, 0], dt[:, 0], a, b_mat[:, 0],
                                   c_mat[:, 0], started)
            y = y[:, None]
    else:
        with jax.named_scope("state_read"):
            entering = ssd.read_rows(state.scan, layer, slot, b)
            entering = jnp.where(started[:, None, None], entering, jnp.zeros_like(entering))
        with jax.named_scope("scan"):
            # padding after the last real row: no decay, no input, so the state the
            # scan leaves with is the state as of ``last``
            real = (jnp.arange(s) <= last)[None, :, None]
            y, leaving = ssd.ssd_scan_plain(xs, jnp.where(real, dt, 0.0), a, b_mat, c_mat,
                                            cfg.ssm_chunk, state=entering)
    with jax.named_scope("scan" if s > 1 else "step"):
        y = (y.astype(F32) + p["D"].astype(F32)[:, None] * xs.astype(F32)).astype(dtype)
        y = y.reshape(b, s, d_inner)
    with jax.named_scope("state_write"):
        # the conv's inputs of the K - 1 positions up to row ``last``: row t of the
        # forward is row K - 1 + t of ``seen``
        new_tail = jax.lax.dynamic_slice_in_dim(seen, last + 1, k - 1, axis=1)
        conv_stack = _write_rows(state.conv, layer, slot, new_tail.reshape(b, -1))
        if s > 1:
            scan = ssd.write_rows(state.scan, layer, slot, leaving)
    return _gate_out(y, z, p, cfg), SsmState(conv_stack, scan)
