"""State-space duality (SSD) scan of a Mamba-2 layer, chunked, and the causal
depthwise convolution in front of it (plain, `causal_conv1d`, and with its SiLU
as one op over two kernels, `conv_silu_fused`: the last section of this file).

The recurrence, per head (``H`` in R^{P x N}; one scalar decay a head)::

    H_t = exp(dt_t * A) * H_{t-1} + dt_t * x_t B_t^T        y_t = H_t C_t

is computed in chunks of ``chunk`` positions (Dao & Gu 2024, "Transformers are
SSMs", listing 1): inside a chunk the output is a decay-masked ``C B^T``
product (an attention-like (L, L) block a head), one state a chunk summarises
what the chunk adds, and the states are carried across chunks.

Two bodies with one contract. `ssd_scan_plain` is plain ``jax.numpy`` (autodiff
gives its backward; XLA fuses the mask, the exponentials and the casts around
four batched GEMMs and carries the states by a ``lax.scan``): the path outside
the fused kernels' envelope and the tests' oracle. `ssd_scan_fused` is a
``jax.custom_vjp`` over Pallas kernels (``ssd_fwd``, ``ssd_bwd`` and the two
small ``ssd_decay`` / ``ssd_decay_bwd``): x read and y written token-major where
the model holds them, the score blocks never outside VMEM, the state carried
in VMEM across the chunk axis of the grid. `ssd_scan` chooses between them by
`scan_path`, from shapes and backend alone. On a mesh GSPMD partitions the
plain body by itself and cannot partition a Mosaic call: `models/ssm.block`
runs the fused body under ``place.shard_kernel``, each device on its own batch
rows (tp and cp on a state-space layer are refused, so the heads and the
sequence are whole there).

Precision, both bodies: the log-decays, their cumulative sums, ``dt`` and the
carried states (and, in the backward, the carried gradient of the state) are
float32 always (a decay in bf16 loses the recurrence after a few hundred
steps); the GEMM operands are the compute dtype with float32 accumulation. B
and C are shared by the heads of a group (``G`` groups).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.ops import pallas_common

F32 = jnp.float32


def causal_conv1d(x, w, b):
    """Depthwise causal convolution over the sequence: ``x`` (B, S, C),
    ``w`` (K, C) with tap ``K-1`` on the current position (the published
    conv1d weight (C, 1, K), transposed), ``b`` (C,) ->
    ``y_t = sum_j w[j] * x_{t-K+1+j} + b``; positions before the sequence read
    zero. Accumulates in float32, returns ``x``'s dtype."""
    k, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    w32 = w.astype(F32)
    y = b.astype(F32)
    for j in range(k):
        y = y + xp[:, j:j + s].astype(F32) * w32[j]
    return y.astype(x.dtype)


def ssd_scan_plain(x, dt, a, b_mat, c_mat, chunk: int, state=None):
    """Chunked SSD: ``x`` (B, S, H, P) in the compute dtype, ``dt`` (B, S, H)
    float32 and positive, ``a`` (H,) float32 and negative, ``b_mat`` / ``c_mat``
    (B, S, G, N) -> ``y`` (B, S, H, P) in ``x``'s dtype, without the ``D x``
    skip. A sequence that ``chunk`` does not divide is padded at its end
    (``dt`` 0 there: no decay, no input; causal, so nothing earlier moves).

    ``state`` (B, N, H x P) float32 (`state_shape`'s order: the cached forwards'):
    the state the sequence ENTERS with; the call then returns ``(y, state)``, the state
    it leaves with. A position whose ``dt`` is 0 neither decays nor adds to it, which
    is how a caller keeps padding after a chunk's last real row out of the state.

    Everything between the two transposes is head-major, (B, G, R, chunks, L,
    ...) with H = G x R: the score blocks differ by head, so the batched GEMMs
    want the heads in front; written token-major the compiler inserted its
    own copies of every x-sized operand (twice the bytes written, `scan` 110 ->
    89 ms a step in the cell; PERF.md §6, PR 33). What it still inserts: one
    re-tiling copy of x a pass, nameless in a trace (PERF.md §7)."""
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2:]
    r = h // g
    pad = -s % chunk
    if pad:
        x, dt, b_mat, c_mat = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b_mat, c_mat))
    nc, dtype = (s + pad) // chunk, x.dtype
    xh = x.transpose(0, 2, 1, 3).reshape(bsz, g, r, nc, chunk, p)
    dth = dt.astype(F32).transpose(0, 2, 1).reshape(bsz, g, r, nc, chunk)
    bc = b_mat.astype(dtype).transpose(0, 2, 1, 3).reshape(bsz, g, nc, chunk, n)
    cc = c_mat.astype(dtype).transpose(0, 2, 1, 3).reshape(bsz, g, nc, chunk, n)
    # log-decays summed from each chunk's start: (B, G, R, nc, L)
    cum = jnp.cumsum(dth * a.astype(F32).reshape(g, r)[None, :, :, None, None], axis=-1)
    x32 = xh.astype(F32) * dth[..., None]  # dt_t x_t

    # 1. inside a chunk: y_l += sum_{s<=l} exp(cum_l - cum_s) (C_l . B_s) dt_s x_s
    cb = jnp.einsum("bgcln,bgcsn->bgcls", cc, bc, preferred_element_type=F32)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    scores = (cb[:, :, None] * decay).astype(dtype)
    y = jnp.einsum("bgrcls,bgrcsp->bgrclp", scores, x32.astype(dtype),
                   preferred_element_type=F32)

    # 2. what each chunk adds to the state by its end: (nc, B, G, R, P, N)
    xw = (x32 * jnp.exp(cum[..., -1:] - cum)[..., None]).astype(dtype)
    added = jnp.einsum("bgcsn,bgrcsp->cbgrpn", bc, xw, preferred_element_type=F32)

    # 3. the carry: the state entering each chunk, float32, one step a chunk
    def carry(state, inp):
        dec, add = inp
        return state * dec[..., None, None] + add, state

    start = (jnp.zeros((bsz, g, r, p, n), F32) if state is None
             else state.astype(F32).reshape(bsz, n, g, r, p).transpose(0, 2, 3, 4, 1))
    leaving, entering = jax.lax.scan(
        carry, start, (jnp.moveaxis(jnp.exp(cum[..., -1]), -1, 0), added))

    # 4. the entering state read out at every position of its chunk
    y_off = jnp.einsum("bgcln,cbgrpn->bgrclp", cc, entering.astype(dtype),
                       preferred_element_type=F32)
    y = (y + y_off * jnp.exp(cum)[..., None]).astype(dtype)
    y = y.reshape(bsz, h, nc * chunk, p).transpose(0, 2, 1, 3)[:, :s]
    if state is None:
        return y
    return y, leaving.transpose(0, 4, 1, 2, 3).reshape(bsz, n, h * p)


# -- the single step over a stack of states -----------------------------------------
#
# One decode step of a served Mamba-2 layer: every row of the slot cache advances its
# state by ONE position, ``H <- exp(dt A) H + dt B x^T``, ``y = C H``. The states of
# all such layers are one stack (layers, rows, N, H x P) float32 (`state_shape`: the
# state dimension on the sublanes, a head's P values side by side on the lanes, the
# order the fused scan's scratch has), 2 MiB a row and layer at the published sizes,
# 1.6 GB over 64 rows x 12 layers: the step reads and writes all of it, so it must do
# so ONCE and in place. `ssm_step` is a Pallas kernel over the stack itself (block
# index maps at a static layer, ``input_output_aliases``: no slab of the stack is
# sliced out or copied back); the plain body is its reference and the CPU's path.


def state_shape(heads: int, head_dim: int, state: int) -> tuple:
    """What a row keeps of one layer's scan: (N, H x P) float32."""
    return (state, heads * head_dim)


_STEP_BLOCK_BYTES = 1 << 20  # of state a grid step: in and out, two buffers each


def _step_groups(groups: int, width: int, state: int) -> int:
    """Scan groups a grid step of `ssm_step` takes: the most that keep its block of
    the state inside `_STEP_BLOCK_BYTES` (4 of 8 at nemotron_h's sizes: 1 MiB). ONE group
    is never cut: Granite 4.0-H Small's 128 x 64 = 8192 lanes go whole, a 4 MiB block (16
    MiB resident, inside `pallas_common.VMEM_LIMIT_MB`; PERF.md 6, PR 70, has the chip's
    reading and why no lane-block axis was added)."""
    gb = groups
    while gb > 1 and (gb * width * state * 4 > _STEP_BLOCK_BYTES or groups % gb):
        gb -= 1
    return gb


def step_path(heads: int, head_dim: int, groups: int, state: int) -> str:
    """``"kernel"`` or ``"plain"`` for the single step of these sizes, from the shapes
    and the backend alone (`scan_path`'s rule): the kernel on a chip where a group's
    heads fill whole 128-lane tiles and the state dimension whole sublane tiles."""
    if heads % max(groups, 1):
        return "plain"
    return rows_path(heads // groups * head_dim, state)


def rows_path(width: int, state: int) -> str:
    """``"kernel"`` or ``"plain"`` for a (N, width) float32 block of the state stack
    (`read_rows` / `write_rows` move a row's whole (N, H x P), the step a group's
    lanes of it): the kernel on a chip where ``width`` fills whole 128-lane tiles
    and ``state`` whole sublane tiles."""
    if pallas_common.use_interpret():
        return "plain"
    return "kernel" if width % _LANES == 0 and state % 8 == 0 else "plain"


def ssd_step(stack, layer: int, x, dt, a, b_mat, c_mat, started):
    """One position of every row: ``stack`` (layers, rows, N, H x P) float32, ``x``
    (rows, H, P), ``dt`` (rows, H) float32 and positive, ``a`` (H,), ``b_mat`` /
    ``c_mat`` (rows, G, N), ``started`` (rows,) bool: False reads the row's state ZERO
    whatever it holds -> ``(y (rows, H, P) float32 without the D x skip, stack)`` with
    layer ``layer``'s states advanced in place. Float32 throughout (no GEMM: the
    vector unit's work, and the memory's)."""
    rows, h, p = x.shape
    g, n = b_mat.shape[1:]
    decay = jnp.where(started[:, None], jnp.exp(dt.astype(F32) * a.astype(F32)[None]), 0.0)
    decay = jnp.repeat(decay, p, axis=1)  # (rows, H x P): a head's decay on its lanes
    dtx = (dt.astype(F32)[..., None] * x.astype(F32)).reshape(rows, h * p)
    b32, c32 = b_mat.astype(F32), c_mat.astype(F32)
    if step_path(h, p, g, n) == "kernel":
        y, stack = _step_call(stack, layer, decay, dtx, b32, c32)
    else:
        y, stack = ssd_step_plain(stack, layer, decay, dtx, b32, c32)
    return y.reshape(rows, h, p), stack


def ssd_step_plain(stack, layer: int, decay, dtx, b32, c32):
    """`ssd_step`'s body in plain ``jax.numpy``: the kernel's reference."""
    rows, g, n = b32.shape
    prev = stack[layer].reshape(rows, n, g, -1)
    # (``where``, not a product with 0: what an unstarted row holds may be anything)
    decay = decay.reshape(rows, 1, g, -1)
    new = (jnp.where(decay > 0, prev * decay, 0.0)
           + jnp.einsum("bgn,bgq->bngq", b32, dtx.reshape(rows, g, -1)))
    y = jnp.einsum("bgn,bngq->bgq", c32, new).reshape(rows, -1)
    stack = jax.lax.dynamic_update_slice(
        stack, new.reshape((1, rows, n, -1)).astype(stack.dtype), (layer, 0, 0, 0))
    return y, stack


def _step_kernel(s_ref, decay_ref, dtx_ref, b_ref, c_ref, out_ref, y_ref, *, width):
    for i in range(b_ref.shape[1]):  # the block's groups: B and C differ by group
        lanes = slice(i * width, (i + 1) * width)
        decay = decay_ref[0, :, lanes]  # (1, width)
        new = (jnp.where(decay > 0, s_ref[0, 0, :, lanes] * decay, 0.0)
               + b_ref[0, i] * dtx_ref[0, :, lanes])  # (N, 1) x (1, width)
        out_ref[0, 0, :, lanes] = new.astype(out_ref.dtype)
        y_ref[0, :, lanes] = jnp.sum(new * c_ref[0, i], axis=0, keepdims=True)


def _step_call(stack, layer, decay, dtx, b32, c32):
    rows, g, n = b32.shape
    width = stack.shape[3] // g
    gb = _step_groups(g, width, n)
    states = pl.BlockSpec((1, 1, n, gb * width), lambda r, j: (layer, r, 0, j))
    lanes = pl.BlockSpec((1, 1, gb * width), lambda r, j: (r, 0, j))
    group = pl.BlockSpec((1, gb, n, 1), lambda r, j: (r, j, 0, 0))
    out, y = pl.pallas_call(
        functools.partial(_step_kernel, width=width),
        grid=(rows, g // gb), in_specs=[states, lanes, lanes, group, group],
        out_specs=[states, lanes],
        out_shape=[jax.ShapeDtypeStruct(stack.shape, stack.dtype),
                   jax.ShapeDtypeStruct((rows, 1, stack.shape[3]), F32)],
        input_output_aliases={0: 0},
        compiler_params=pallas_common.compiler_params(
            dimension_semantics=("parallel", "parallel")),
        interpret=pallas_common.use_interpret(), name="ssm_step",
    )(stack, decay[:, None], dtx[:, None], b32[..., None], c32[..., None])
    return y[:, 0], out


# A prompt chunk reads the state its row ENTERS with out of the stack and writes back the
# state it LEAVES with. As ``dynamic_slice`` / ``dynamic_update_slice`` the chip's compiler
# gave the WHOLE stack the layout its consumer liked (the chunked scan's batched GEMMs want
# the state dimension minor) and copied all 1.6 GB of it into that layout and back around
# every chunk (compiled for a described v5e; the chip's first traced run: two copies of
# f32[12,64,128,4096], 53 ms a chunk each). A Mosaic call takes its operands as they lie,
# so on a chip the rows go through two small kernels, `ssm_state_read` / `ssm_state_write`
# (a row a grid step, the stack itself the operand, written in place), and the compiler is
# left to re-lay the 2 MiB it was handed.


def _first_row(slot):
    """``slot`` (traced, or None: row 0) as the (1,) int32 a kernel prefetches."""
    return jnp.zeros((1,), jnp.int32) if slot is None else jnp.reshape(slot, (1,)).astype(jnp.int32)


def read_rows(stack, layer: int, slot, rows: int):
    """Rows [slot, slot + rows) of layer ``layer`` of the state stack (layers, rows, N,
    H x P) -> (rows, N, H x P); ``slot`` traced (None: 0)."""
    slot = _first_row(slot)
    n, width = stack.shape[2:]
    if rows_path(width, n) != "kernel":
        return jax.lax.dynamic_slice(stack, (layer, slot[0], 0, 0), (1, rows, n, width))[0]

    def kernel(slot_ref, s_ref, out_ref):
        del slot_ref
        out_ref[...] = s_ref[0]

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((rows, n, width), stack.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows,),
            in_specs=[pl.BlockSpec((1, 1, n, width), lambda i, at: (layer, at[0] + i, 0, 0))],
            out_specs=pl.BlockSpec((1, n, width), lambda i, at: (i, 0, 0))),
        compiler_params=pallas_common.compiler_params(dimension_semantics=("arbitrary",)),
        interpret=pallas_common.use_interpret(), name="ssm_state_read",
    )(slot, stack)


def write_rows(stack, layer: int, slot, new):
    """`read_rows`' inverse: ``new`` (rows, N, H x P) into rows [slot, slot + rows) of
    layer ``layer``, in place -> the stack."""
    slot = _first_row(slot)
    rows, n, width = new.shape
    new = new.astype(stack.dtype)
    if rows_path(width, n) != "kernel":
        return jax.lax.dynamic_update_slice(stack, new[None], (layer, slot[0], 0, 0))

    def kernel(slot_ref, new_ref, s_ref, out_ref):
        del slot_ref, s_ref  # (the stack is the output's alias: nothing of it is read)
        out_ref[0] = new_ref[...]

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(stack.shape, stack.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows,),
            in_specs=[pl.BlockSpec((1, n, width), lambda i, at: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=pl.BlockSpec((1, 1, n, width), lambda i, at: (layer, at[0] + i, 0, 0))),
        input_output_aliases={2: 0},
        compiler_params=pallas_common.compiler_params(dimension_semantics=("arbitrary",)),
        interpret=pallas_common.use_interpret(), name="ssm_state_write",
    )(slot, new, stack)


# -- the fused kernels ---------------------------------------------------------
#
# One grid step is one chunk of one block of heads: grid (batch, head block,
# chunk), the chunk axis sequential ("arbitrary"), the state of the block's
# heads, float32 (N, heads x P), in VMEM scratch from one chunk to the next; the
# heads of a block (at most `_MAX_HEAD_BLOCK`) are the only thing unrolled.
# x, y, dy and dx are (L, heads x P) blocks of the token-major (B, S, H x P)
# arrays, B and C the (L, N) blocks of the block's group, all through index maps:
# nothing is transposed outside a kernel. dt comes as the model holds it,
# (B, S, H): `ssd_decay` turns a chunk of it into head-major rows (a position a
# lane) of dt and of the log-decays summed from the chunk's start, by float32
# adds (no MXU pass); `ssd_fwd` / `ssd_bwd` turn the rows of their heads into
# columns (a position a sublane) with one aligned (128, L) transpose a step and
# broadcast two of them a head over the lanes. `ssd_decay_bwd` takes the
# kernels' d dt and d cum back to (B, S, H).
#
# Residuals of the backward (what the forward rule keeps): the operands, the
# two (B, H, S) float32 row arrays (2 MB each at the cell's shape: B 1, S 8192,
# H 64, P 64, N 128, chunks of 256), and the state entering every chunk,
# (B, S / L, N, H x P) float32 = 67 MB there. Nothing of size H x chunks x L x L
# is ever kept or written: the backward recomputes its score blocks from x, B, C,
# dt as `flash_bwd_blocked` does its own.

_NEG = -1e30  # exp() of it is 0: the mask of the score blocks
_LANES = 128
_ROWS = 128  # rows of the scratch the per-position vectors are transposed through
# Heads a grid step. 16 ran 4% faster and 32 another 4% (0.42 / 1.07 and 0.38 / 1.03 ms a
# layer forward / backward against 0.47 / 1.16; PERF.md §6, PR 34), but a block's heads are
# unrolled in the kernel's text: a program traces and lowers it three times (forward,
# the forward that keeps the states, backward), ~0.4 s of set-up at 8 (PERF.md §6, PR 34)
_MAX_HEAD_BLOCK = 8


def _head_block(r: int, p: int) -> int:
    """Heads a grid step: the largest power of two up to ``_MAX_HEAD_BLOCK``
    that divides the heads of a group (a block never spans two groups, so
    one ``C B^T`` serves it) and fills whole 128-lane tiles of x; 0 if none."""
    hb = _MAX_HEAD_BLOCK
    while hb and (r % hb or (hb * p) % _LANES):
        hb //= 2
    return hb


def _fused_vmem_mb(hb: int, p: int, n: int, chunk: int, itemsize: int) -> float:
    """What a backward step holds in VMEM, reckoned from the shapes: the blocks
    of x, dy and dx, of B, C, dB and dC and of the entering state (two buffers
    each), the carried gradient, and the float32 temporaries of a tile (a few
    score blocks, a few (L, heads x P) products)."""
    width = hb * p
    blocks = 2 * (3 * chunk * width * itemsize + 2 * chunk * n * itemsize
                  + 2 * chunk * n * 4 + n * width * 4)
    scratch = n * width * 4 + _ROWS * chunk * 4
    temps = 6 * chunk * chunk * 4 + 6 * chunk * width * 4
    return (blocks + scratch + temps) / 2**20


def scan_path(heads: int, head_dim: int, groups: int, state: int, chunk: int, dtype) -> str:
    """``"fused"`` or ``"plain"`` for a Mamba-2 scan of these sizes, from the
    shapes and the backend alone: no flag, no environment variable, no model's
    name. `ssd_scan` and the trainer's ``ssm_scan_path`` counter both ask
    here. The fused kernels take, and everything else takes the plain body:

    - a chip (`pallas_common.use_interpret`'s rule, the one switch of this
      repo's kernels: on the CPU they run interpreted, which only the tests
      that call `ssd_scan_fused` themselves want);
    - ``chunk`` a multiple of 128: the (L, L) score blocks and the rows of dt
      tile the 128 lanes (a sequence is padded to whole chunks either way);
    - ``head_dim`` 64 or 128: two heads or one fill a 128-lane tile of x;
    - ``state`` a multiple of 128: a group's (L, N) block of B and C is cut out
      of (B, S, G x N) by lanes (N 64 keeps the plain body: never run here);
    - heads in whole groups, a group's heads a multiple of a head block that
      fills whole tiles (`_head_block`: one ``C B^T`` serves a block);
    - bf16 or float32 compute;
    - a VMEM charge (`_fused_vmem_mb`, 7.6 MB at the granite sizes) inside the
      budget `flash_attention._seq_envelope` reckons with.
    """
    dtype = jnp.dtype(dtype)
    if (pallas_common.use_interpret() or heads % max(groups, 1)
            or dtype not in (jnp.bfloat16, jnp.float32)):
        return "plain"
    hb = _head_block(heads // groups, head_dim)
    inside = (chunk % _LANES == 0 and head_dim in (64, 128) and state % _LANES == 0 and hb > 0
              and 1.1 * _fused_vmem_mb(hb, head_dim, state, chunk, dtype.itemsize)
              <= pallas_common.VMEM_LIMIT_MB)
    return "fused" if inside else "plain"


def ssd_scan(x, dt, a, b_mat, c_mat, chunk: int):
    """`ssd_scan_plain`'s contract; the fused kernels where `scan_path` says
    so, the plain body everywhere else."""
    (h, p), (g, n) = x.shape[2:], b_mat.shape[2:]
    if scan_path(h, p, g, n, chunk, x.dtype) == "fused":
        return ssd_scan_fused(x, dt, a, b_mat, c_mat, chunk)
    return ssd_scan_plain(x, dt, a, b_mat, c_mat, chunk)


def ssd_scan_fused(x, dt, a, b_mat, c_mat, chunk: int):
    """`ssd_scan_plain`'s contract through the kernels (`_ssd_core`), for sizes
    inside `scan_path`'s envelope. Outside the kernels only the padding to
    whole chunks and reshapes that move nothing."""
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2:]
    pad = -s % chunk
    if pad:
        x, dt, b_mat, c_mat = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b_mat, c_mat))
    sp, dtype = s + pad, x.dtype
    y = _ssd_core(x.reshape(bsz, sp, h * p), dt.astype(F32), a.astype(F32).reshape(h, 1),
                  b_mat.astype(dtype).reshape(bsz, sp, g * n),
                  c_mat.astype(dtype).reshape(bsz, sp, g * n), chunk, n)
    return y.reshape(bsz, sp, h, p)[:, :s]


def _columns(rows_scr, rows):
    """(hb, L) float32 rows, a position a lane -> (L, 128): column
    ``q * hp + i`` is row i of the q-th array (hp: hb rounded up to 8), a
    position a sublane. Through a (128, L) scratch and one aligned transpose."""
    hb = rows[0].shape[0]
    hp = -(-hb // 8) * 8
    for q, r in enumerate(rows):
        rows_scr[q * hp:q * hp + hb, :] = r
    return rows_scr[...].T, hp


def _tile(cols, hp, p, t):
    """What the t-th 128-lane tile of a head block needs of its heads' dt and
    summed log-decays, a position a sublane. Two lane broadcasts a head (its
    cum and its dt column: the costly part of a step, PERF.md §6, PR 34);
    every other per-position factor is an exponential of the broadcast cum.

    -> heads, ``own(i, v)`` v on the lanes of head i and 0 on the other's,
    ``crep[i]`` cum of head i over the lanes, and ``dt``, ``exp(cum)``,
    ``exp(cum_last - cum)``, each lane holding the value of the head it belongs to."""
    chunk = cols.shape[0]
    hpt = _LANES // p
    heads = range(t * hpt, (t + 1) * hpt)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def own(i, v):  # v with the lanes of the tile's other head zeroed
        if hpt == 1:
            return v
        return jnp.where((lane >= (i - heads[0]) * p) & (lane < (i - heads[0] + 1) * p), v, 0)

    def pick(vals):
        out = vals[0]
        for k, v in enumerate(vals[1:], 1):
            out = jnp.where(lane >= k * p, v, out)
        return out

    def rep(q, i):
        return jnp.broadcast_to(cols[:, q * hp + i:q * hp + i + 1], (chunk, _LANES))

    def last(v):  # the chunk's last position over the lanes, (1, 128); a slice of
        # a broadcast folds to a (1, 1) that Mosaic cannot spread both ways
        at_end = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
        return jnp.sum(jnp.where(at_end, v, 0.0), axis=0, keepdims=True)

    crep = {i: rep(0, i) for i in heads}
    dt_l = pick([rep(1, i) for i in heads])
    ecum_l = pick([jnp.exp(crep[i]) for i in heads])
    grow_l = pick([jnp.exp(last(crep[i]) - crep[i]) for i in heads])
    return heads, own, crep, dt_l, ecum_l, grow_l


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=F32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _decay(crep, cum_ref, i):
    """(L, L) float32 of head i of the block: exp(cum_l - cum_s) where s <= l,
    0 above the diagonal; ``crep`` (L, 128) is cum_l over the lanes, cum_s is
    read from the rows a lane tile at a time (a slice of a loaded row at lane
    128 keeps a layout Mosaic will not broadcast)."""
    chunk = crep.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, _LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, _LANES), 1)
    return jnp.concatenate(
        [jnp.exp(jnp.where(row >= col + k, crep - cum_ref[0, 0, i:i + 1, k:k + _LANES], _NEG))
         for k in range(0, chunk, _LANES)], axis=1)


def _fwd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, y_ref, *rest, p, keep_states):
    state, rows_scr = rest[-2:]
    chunk, dtype = x_ref.shape[1], x_ref.dtype
    hb = dt_ref.shape[2]

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        state[...] = jnp.zeros_like(state)

    entering = state[...]  # (N, hb x P) float32
    if keep_states:
        rest[0][0, 0] = entering
    cols, hp = _columns(rows_scr, (cum_ref[0, 0], dt_ref[0, 0]))  # (hb, L) each
    b, c = b_ref[0], c_ref[0]
    b_t = b.T  # (N, L), once for the block's tiles
    cb = _dot(c, b, _NT)  # (L, L): C_l . B_s, once for the block's group
    read = _dot(c, entering.astype(dtype), _NN)  # (L, hb x P): C_l . state
    for t in range(hb * p // _LANES):
        lanes = slice(t * _LANES, (t + 1) * _LANES)
        heads, own, crep, dt_l, ecum_l, grow_l = _tile(cols, hp, p, t)
        x32 = x_ref[0, :, lanes].astype(F32)
        xdt = (x32 * dt_l).astype(dtype)
        y = ecum_l * read[:, lanes]
        for i in heads:
            scores = (cb * _decay(crep[i], cum_ref, i)).astype(dtype)
            y += _dot(scores, own(i, xdt), _NN)
        y_ref[0, :, lanes] = y.astype(dtype)
        grown = ecum_l[chunk - 1:chunk, :]  # exp(cum_last) a head, (1, 128)
        state[:, lanes] = entering[:, lanes] * grown + _dot(
            b_t, (x32 * (dt_l * grow_l)).astype(dtype), _NN)


def _specs(bsz, sp, h, p, g, n, chunk, hb, rev):
    """Block specs shared by the two kernels; ``rev`` walks the chunks from
    the last to the first."""
    nc, r = sp // chunk, h // g
    ch = (lambda c: nc - 1 - c) if rev else (lambda c: c)
    tokens = pl.BlockSpec((1, chunk, hb * p), lambda b, j, c: (b, ch(c), j))
    rows = pl.BlockSpec((1, 1, hb, chunk), lambda b, j, c: (b, j, 0, ch(c)))
    group = pl.BlockSpec((1, chunk, n), lambda b, j, c: (b, ch(c), (j * hb) // r))
    states = pl.BlockSpec((1, 1, n, hb * p), lambda b, j, c: (b, ch(c), 0, j))
    return (bsz, h // hb, nc), tokens, rows, group, states


def _running_sum(v, reverse=False):
    """Inclusive running sum along the lanes of (rows, L) float32 (from the
    last lane backwards if ``reverse``): log2(L) shifted float32 adds, no MXU pass."""
    n = v.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    k = 1
    while k < n:
        if reverse:
            v = v + jnp.where(lane < n - k, pltpu.roll(v, n - k, 1), 0.0)
        else:
            v = v + jnp.where(lane >= k, pltpu.roll(v, k, 1), 0.0)
        k *= 2
    return v


def _decay_kernel(dt_ref, a_ref, dtr_ref, cum_ref, pad_scr):
    """One chunk of dt, (L, H) as the model holds it -> head-major rows
    (H / hb, hb, L) of dt and of the log-decays summed from the chunk's start."""
    h = dt_ref.shape[2]
    hb = dtr_ref.shape[2]
    pad_scr[:, :h] = dt_ref[0]
    rows = pad_scr[...].T[:h]  # (H, L)
    cum = _running_sum(rows * a_ref[...])
    for t in range(h // hb):
        dtr_ref[0, t] = rows[t * hb:(t + 1) * hb]
        cum_ref[0, t] = cum[t * hb:(t + 1) * hb]


def _decay_bwd_kernel(ddt_ref, dcum_ref, a_ref, ddt_out, dla_out, pad_scr):
    """The way back: head-major rows of the kernels' d dt and d cum -> (L, H)
    of d(dt a) (the running sum's transpose: a sum from the chunk's end) and of
    the whole d dt."""
    h = ddt_out.shape[2]
    hb = ddt_ref.shape[2]
    for q, ref in enumerate((ddt_ref, dcum_ref)):
        for t in range(h // hb):
            pad_scr[q, t * hb:(t + 1) * hb, :] = ref[0, t]
    dla = _running_sum(pad_scr[1, :h], reverse=True)
    pad_scr[0, :h] = pad_scr[0, :h] + a_ref[...] * dla
    pad_scr[1, :h] = dla
    ddt_out[0] = pad_scr[0].T[:, :h]
    dla_out[0] = pad_scr[1].T[:, :h]


def _decay_specs(bsz, sp, h, chunk, hb):
    natural = pl.BlockSpec((1, chunk, h), lambda b, c: (b, c, 0))
    rows = pl.BlockSpec((1, h // hb, hb, chunk), lambda b, c: (b, 0, 0, c))
    heads = pl.BlockSpec((h, 1), lambda b, c: (0, 0))
    return (bsz, sp // chunk), natural, rows, heads, -(-h // _LANES) * _LANES


def _decay_call(dt, a, chunk, hb):
    bsz, sp, h = dt.shape
    grid, natural, rows, heads, hpad = _decay_specs(bsz, sp, h, chunk, hb)
    shape = jax.ShapeDtypeStruct((bsz, h // hb, hb, sp), F32)
    return pl.pallas_call(
        _decay_kernel, grid=grid, in_specs=[natural, heads], out_specs=[rows, rows],
        out_shape=[shape, shape], scratch_shapes=[pltpu.VMEM((chunk, hpad), F32)],
        compiler_params=pallas_common.compiler_params(dimension_semantics=("parallel", "parallel")),
        interpret=pallas_common.use_interpret(), name="ssd_decay",
    )(dt, a)


def _decay_bwd_call(ddt, dcum, a, chunk):
    bsz, nhb, hb, sp = ddt.shape
    h = nhb * hb
    grid, natural, rows, heads, hpad = _decay_specs(bsz, sp, h, chunk, hb)
    shape = jax.ShapeDtypeStruct((bsz, sp, h), F32)
    return pl.pallas_call(
        _decay_bwd_kernel, grid=grid, in_specs=[rows, rows, heads], out_specs=[natural, natural],
        out_shape=[shape, shape], scratch_shapes=[pltpu.VMEM((2, hpad, chunk), F32)],
        compiler_params=pallas_common.compiler_params(dimension_semantics=("parallel", "parallel")),
        interpret=pallas_common.use_interpret(), name="ssd_decay_bwd",
    )(ddt, dcum, a)


def _sizes(x, dtr, bm, n):
    bsz, sp, width = x.shape
    nhb, hb = dtr.shape[1:3]
    h = nhb * hb
    return bsz, sp, h, width // h, bm.shape[2] // n, hb


def _fwd_call(x, dtr, cum, bm, cm, chunk, n, keep_states):
    bsz, sp, h, p, g, hb = _sizes(x, dtr, bm, n)
    grid, tokens, rows, group, states = _specs(bsz, sp, h, p, g, n, chunk, hb, rev=False)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [tokens]
    if keep_states:
        out_shape.append(jax.ShapeDtypeStruct((bsz, sp // chunk, n, h * p), F32))
        out_specs.append(states)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, keep_states=keep_states),
        grid=grid, in_specs=[tokens, rows, rows, group, group],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, hb * p), F32), pltpu.VMEM((_ROWS, chunk), F32)],
        compiler_params=pallas_common.compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_common.use_interpret(), name="ssd_fwd",
    )(x, dtr, cum, bm, cm)


def _bwd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, dy_ref, st_ref,
                dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref, dstate, rows_scr, *, p):
    chunk, dtype = x_ref.shape[1], x_ref.dtype
    hb = dt_ref.shape[2]

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        dstate[...] = jnp.zeros_like(dstate)

    leaving = dstate[...]  # gradient of the state this chunk leaves, (N, hb x P) float32
    entering = st_ref[0, 0]  # the state that entered it in the forward
    cols, hp = _columns(rows_scr, (cum_ref[0, 0], dt_ref[0, 0]))  # (hb, L) each
    b, c = b_ref[0], c_ref[0]
    c_t = c.T  # (N, L), once for the block's tiles
    cb = _dot(c, b, _NT)
    read = _dot(c, entering.astype(dtype), _NN)  # (L, hb x P): C_l . state
    pushed = _dot(b, leaving.astype(dtype), _NN)  # (L, hb x P): B_s . dstate
    dg = jnp.zeros((chunk, chunk), F32)  # gradient of C B^T, summed over the block's heads
    dc = jnp.zeros(c.shape, F32)
    db = jnp.zeros(b.shape, F32)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    for t in range(hb * p // _LANES):
        lanes = slice(t * _LANES, (t + 1) * _LANES)
        heads, own, crep, dt_l, ecum_l, grow_l = _tile(cols, hp, p, t)
        w_l = dt_l * grow_l
        x32 = x_ref[0, :, lanes].astype(F32)
        dy = dy_ref[0, :, lanes]
        dy32 = dy.astype(F32)
        xdt = (x32 * dt_l).astype(dtype)
        dxdt = jnp.zeros((chunk, _LANES), F32)  # gradient of dt x
        y = ecum_l * read[:, lanes]  # the forward's output again, float32
        for i in heads:
            dyi = own(i, dy)
            decay = _decay(crep[i], cum_ref, i)
            scores = (cb * decay).astype(dtype)
            dxdt += _dot(scores, dyi, _TN)
            dg += _dot(dyi, xdt, _NT) * decay
            y += _dot(scores, own(i, xdt), _NN)
        push = pushed[:, lanes]
        dx_ref[0, :, lanes] = (dt_l * dxdt + w_l * push).astype(dtype)
        # per position and head, summed over the head's P lanes through a
        # transpose (a position a lane again, as the outputs want it):
        #   d dt  = x . (dxdt + grow push)
        #   d cum = dy . y - (dt x) . dxdt - w x . push
        # (cum_l scales all of y_l, cum_s what position s sends on: the two
        # sums over the score block, taken from products the kernel already has;
        # dt x as the GEMMs saw it, rounded, so that the diagonal cancels)
        xpush = x32 * push
        ddt_t = (x32 * dxdt + grow_l * xpush).T  # (128, L)
        dcum_t = (dy32 * y - xdt.astype(F32) * dxdt - w_l * xpush).T
        # cum_last moves the carried state and every w of the chunk
        grown = ecum_l[chunk - 1:chunk, :]  # exp(cum_last) a head, (1, 128)
        dlast_t = (jnp.sum(leaving[:, lanes] * entering[:, lanes] * grown, axis=0, keepdims=True)
                   + jnp.sum(w_l * xpush, axis=0, keepdims=True))
        for i in heads:
            at = slice((i - heads[0]) * p, (i - heads[0] + 1) * p)
            ddt_ref[0, 0, i:i + 1, :] = jnp.sum(ddt_t[at], axis=0, keepdims=True)
            dlast = jnp.sum(dlast_t[:, at], axis=1, keepdims=True)  # (1, 1)
            dcum_ref[0, 0, i:i + 1, :] = (jnp.sum(dcum_t[at], axis=0, keepdims=True)
                                          + jnp.where(last, dlast, 0.0))
        dz = (dy32 * ecum_l).astype(dtype)  # exp(cum) dy
        dstate[:, lanes] = leaving[:, lanes] * grown + _dot(c_t, dz, _NN)
        dc += _dot(dz, entering[:, lanes].astype(dtype), _NT)
        db += _dot((x32 * w_l).astype(dtype), leaving[:, lanes].astype(dtype), _NT)
    dgc = dg.astype(dtype)
    dc_ref[0, 0] = dc + _dot(dgc, b, _NN)
    db_ref[0, 0] = db + _dot(dgc, c, _TN)


def _bwd_call(x, dtr, cum, bm, cm, dy, states, chunk, n):
    bsz, sp, h, p, g, hb = _sizes(x, dtr, bm, n)
    grid, tokens, rows, group, st = _specs(bsz, sp, h, p, g, n, chunk, hb, rev=True)
    nc, per = sp // chunk, h // g // hb  # head blocks a group
    # dB and dC: one partial a head block, laid so that a sum over axis 1 is (B, S, G x N)
    partial = pl.BlockSpec((1, 1, chunk, n), lambda b, j, c: (b, j % per, nc - 1 - c, j // per))
    part_shape = jax.ShapeDtypeStruct((bsz, per, sp, g * n), F32)
    dx, ddt, dcum, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        grid=grid, in_specs=[tokens, rows, rows, group, group, tokens, st],
        out_specs=[tokens, rows, rows, partial, partial],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(dtr.shape, F32),
                   jax.ShapeDtypeStruct(dtr.shape, F32), part_shape, part_shape],
        scratch_shapes=[pltpu.VMEM((n, hb * p), F32), pltpu.VMEM((_ROWS, chunk), F32)],
        compiler_params=pallas_common.compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_common.use_interpret(), name="ssd_bwd",
    )(x, dtr, cum, bm, cm, dy, states)
    return dx, ddt, dcum, db.sum(axis=1).astype(bm.dtype), dc.sum(axis=1).astype(cm.dtype)


def _forward(x, dt, a, bm, cm, chunk, n, keep_states):
    h = a.shape[0]
    hb = _head_block(h * n // bm.shape[2], x.shape[2] // h)
    dtr, cum = _decay_call(dt, a, chunk, hb)
    return dtr, cum, _fwd_call(x, dtr, cum, bm, cm, chunk, n, keep_states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_core(x, dt, a, bm, cm, chunk, n):
    """x (B, S, H x P), dt (B, S, H) float32, a (H, 1) float32, B and C
    (B, S, G x N) in x's dtype, S in whole chunks -> y like x."""
    return _forward(x, dt, a, bm, cm, chunk, n, keep_states=False)[2][0]


def _core_fwd(x, dt, a, bm, cm, chunk, n):
    dtr, cum, (y, states) = _forward(x, dt, a, bm, cm, chunk, n, keep_states=True)
    return y, (x, dt, a, dtr, cum, bm, cm, states)


def _core_bwd(chunk, n, res, dy):
    x, dt, a, dtr, cum, bm, cm, states = res
    dx, ddt, dcum, db, dc = _bwd_call(x, dtr, cum, bm, cm, dy, states, chunk, n)
    ddt, dla = _decay_bwd_call(ddt, dcum, a, chunk)  # d dt whole, d(dt a)
    return dx, ddt, jnp.sum(dla * dt, axis=(0, 1)).reshape(a.shape), db, dc


_ssd_core.defvjp(_core_fwd, _core_bwd)


# -- the causal conv and its SiLU as one op ---------------------------------------
#
# ``silu(causal_conv1d(x, w, b))`` as a ``jax.custom_vjp`` over two kernels,
# ``ssm_conv_fwd`` and ``ssm_conv_bwd``: x is read once forward and once
# backward, in the compute dtype, where the plain path pads it, takes K slices
# shifted by sublanes, converts each to float32 and leaves autodiff K float32
# pads and K full-size reductions (PERF.md §6, PR 40). The op reads a window of
# channels out of a wider array in place (``col0``: `models/ssm.block` hands
# it in_proj's whole output, and takes x, B and C as three windows, so neither
# the slice in front nor the slices behind are copies).
#
# Grid (batch, channel block, sequence block), the sequence axis sequential. A
# grid step walks its block in strips of `_CONV_STRIP` rows that stay in
# registers; the shifts are rolls along the sublanes of a strip with the 8 rows
# next to it on top (forward: the rows before, carried from strip to strip and,
# in VMEM scratch, from block to block) or below (backward, which walks the
# sequence from its end: the first rows of ``dpre`` of the strip after). The
# backward recomputes the pre-activation from x (its only (S, C)-sized
# residual is the op's own input; the rows before a block come through a second,
# 16-row view of x) and sums ``dw`` and ``db`` over the sequence in the float32
# output block that stays in VMEM, 8 partial rows a tap, folded outside.
#
# Precision: float32 taps, accumulation, SiLU and SiLU', ONE rounding to the
# compute dtype at the end (the plain path rounds the conv's result and then
# the SiLU's; the backward here sees the unrounded pre-activation).

_HALO = 8  # float32 rows a strip takes from its neighbour: one sublane tile, K - 1 <= 8
_HALO_VIEW = 16  # rows of the view of x before a block: one tile of a 16-bit dtype
_MAX_TAPS = 4
_CONV_STRIP = 32
_CONV_BLOCK_S = 1024
_CONV_BLOCK_C = 512


def _conv_blocks(s: int, channels: int, col0: int):
    """(sequence block, channel block) of a window ``channels`` wide that
    starts at column ``col0``: the widest channel block of whole lane tiles
    that divides both; a sequence shorter than a block is one block of whole
    strips."""
    tc = _CONV_BLOCK_C
    while tc > _LANES and (channels % tc or col0 % tc):
        tc //= 2
    return min(_CONV_BLOCK_S, -(-s // _CONV_STRIP) * _CONV_STRIP), tc


def _conv_vmem_mb(ts: int, tc: int, k: int, itemsize: int) -> float:
    """What a backward step holds in VMEM: the blocks of x, the cotangent and
    dx and the view before x (two buffers each), the sums' block, the carried
    rows, and a strip's float32 temporaries should they all spill."""
    blocks = 2 * ((3 * ts + _HALO_VIEW) * tc * itemsize + (k + 1) * _HALO * tc * 4)
    return (blocks + _HALO * tc * 4 + (2 * k + 8) * (_CONV_STRIP + _HALO) * tc * 4) / 2**20


def conv_path(windows, k: int, dtype) -> str:
    """``"fused"`` or ``"plain"`` for the conv + SiLU in front of a scan, from
    the shapes and the backend alone, as `scan_path`: `models/ssm.block`
    and its `path_counts` both ask here. ``windows``:
    the widths of the channel groups the mixer takes apart (x, B, C). Fused:

    - a chip (`pallas_common.use_interpret`'s rule);
    - every window whole 128-lane tiles (so the channels are, and each window
      starts on a tile of in_proj's output if the first does);
    - at most `_MAX_TAPS` taps: the K - 1 rows before a strip come with the
      8 rows it takes over, and dw's K rows and db's share one output block;
    - bf16 or float32 compute;
    - a VMEM charge (`_conv_vmem_mb`, 7.5 MB at the granite sizes) inside
      `flash_attention._seq_envelope`'s budget.

    Everything else takes `causal_conv1d` + ``jax.nn.silu``."""
    dtype = jnp.dtype(dtype)
    if (pallas_common.use_interpret() or dtype not in (jnp.bfloat16, jnp.float32)
            or not 1 <= k <= _MAX_TAPS or any(w <= 0 or w % _LANES for w in windows)):
        return "plain"
    ts, tc = _conv_blocks(_CONV_BLOCK_S, max(windows), 0)
    inside = 1.1 * _conv_vmem_mb(ts, tc, k, dtype.itemsize) <= pallas_common.VMEM_LIMIT_MB
    return "fused" if inside else "plain"


def conv_silu_fused(x, w, b, col0: int = 0):
    """``silu(causal_conv1d(x[..., col0:col0 + C], w, b))`` through the kernels:
    ``x`` (B, S, W) with W >= col0 + C, ``w`` (K, C), ``b`` (C,) -> (B, S, C) in
    ``x``'s dtype, for sizes inside `conv_path`'s envelope (C and ``col0`` whole
    lane tiles). A sequence is padded at its end to whole blocks (causal: nothing
    earlier moves; the cotangent of the padding is zero)."""
    s = x.shape[1]
    pad = -s % _conv_blocks(s, w.shape[1], col0)[0]
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return _conv_core(x, w, b, col0)[:, :s]


def _down(x, before, j):
    """Row t of the result is row t - j of ``x`` (R, C); above row 0 the last
    rows of ``before`` (8, C)."""
    if j == 0:
        return x
    return pltpu.roll(jnp.concatenate([before, x], axis=0), j, 0)[_HALO:]


def _up(d, after, j):
    """Row t of the result is row t + j of ``d`` (R, C); below its last row the
    first rows of ``after`` (8, C)."""
    if j == 0:
        return d
    rows = d.shape[0]
    return pltpu.roll(jnp.concatenate([d, after], axis=0), rows + _HALO - j, 0)[:rows]


def _conv_fwd_kernel(x_ref, w_ref, b_ref, y_ref, tail, *, k):
    strip = _CONV_STRIP

    @pl.when(pl.program_id(2) == 0)
    def _start():  # before the sequence: zeros
        tail[...] = jnp.zeros_like(tail)

    taps = [w_ref[j:j + 1, :] for j in range(k)]
    bias = b_ref[...]

    def piece(i, before):
        r = pl.multiple_of(i * strip, strip)
        x = x_ref[0, pl.ds(r, strip), :].astype(F32)
        pre = bias + taps[k - 1] * x
        for j in range(1, k):
            pre = pre + taps[k - 1 - j] * _down(x, before, j)
        y_ref[0, pl.ds(r, strip), :] = (pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)
        return x[strip - _HALO:]

    tail[...] = jax.lax.fori_loop(0, x_ref.shape[1] // strip, piece, tail[...])


def _fold(v):
    """(R, C) -> (8, C): the rows summed eight apart (whole-register adds; the
    last eight are summed outside the kernel)."""
    return sum(v[i:i + _HALO] for i in range(0, v.shape[0], _HALO))


def _conv_bwd_kernel(x_ref, view_ref, g_ref, w_ref, b_ref, dx_ref, sums_ref, head, *, k):
    strip = _CONV_STRIP
    step = pl.program_id(2)  # the blocks come from the sequence's end

    @pl.when(step == 0)
    def _start():  # after the sequence: no cotangent
        head[...] = jnp.zeros_like(head)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    taps = [w_ref[j:j + 1, :] for j in range(k)]
    bias = b_ref[...]

    def piece(r, before, after):
        x = x_ref[0, pl.ds(r, strip), :].astype(F32)
        moved = [_down(x, before, j) for j in range(k)]  # x_{t-j}
        pre = bias + taps[k - 1] * x
        for j in range(1, k):
            pre = pre + taps[k - 1 - j] * moved[j]
        sig = jax.nn.sigmoid(pre)
        dpre = g_ref[0, pl.ds(r, strip), :].astype(F32) * (sig * (1.0 + pre * (1.0 - sig)))
        dx = taps[k - 1] * dpre
        for j in range(1, k):
            dx = dx + taps[k - 1 - j] * _up(dpre, after, j)
        dx_ref[0, pl.ds(r, strip), :] = dx.astype(dx_ref.dtype)
        for j in range(k):  # dw[K-1-j] = sum_t dpre_t x_{t-j}
            rows = slice((k - 1 - j) * _HALO, (k - j) * _HALO)
            sums_ref[0, rows, :] += _fold(dpre * moved[j])
        sums_ref[0, k * _HALO:, :] += _fold(dpre)
        return dpre[:_HALO]

    strips = x_ref.shape[1] // strip

    def inner(i, after):
        r = pl.multiple_of((strips - 1 - i) * strip, strip)
        above = x_ref[0, pl.ds(pl.multiple_of(r - _HALO_VIEW, _HALO_VIEW), _HALO_VIEW), :]
        return piece(r, above.astype(F32)[_HALO_VIEW - _HALO:], after)

    after = jax.lax.fori_loop(0, strips - 1, inner, head[...])
    # the block's first strip: the rows before it are another block's, or none
    above = view_ref[0].astype(F32)[_HALO_VIEW - _HALO:]
    first = step == pl.num_programs(2) - 1
    head[...] = piece(0, jnp.where(first, 0.0, above), after)


def _conv_small(w, b, tc):
    """The taps and the bias as the kernels read them, float32 (8, C) (rows K..7
    zero) and (1, C), and their specs: a channel block, whatever the batch row
    and the sequence block."""
    vec = lambda rows: pl.BlockSpec((rows, tc), lambda b_, c, s: (0, c))  # noqa: E731
    w8 = jnp.pad(w.astype(F32), ((0, _HALO - w.shape[0]), (0, 0)))
    return (w8, b.astype(F32).reshape(1, -1)), [vec(_HALO), vec(1)]


def _conv_fwd_call(x, w, b, col0):
    ts, tc = _conv_blocks(x.shape[1], w.shape[1], col0)
    c0 = col0 // tc
    small, small_specs = _conv_small(w, b, tc)
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, k=w.shape[0]),
        grid=(x.shape[0], w.shape[1] // tc, x.shape[1] // ts),
        in_specs=[pl.BlockSpec((1, ts, tc), lambda b_, c, s: (b_, s, c0 + c)), *small_specs],
        out_specs=pl.BlockSpec((1, ts, tc), lambda b_, c, s: (b_, s, c)),
        out_shape=jax.ShapeDtypeStruct((*x.shape[:2], w.shape[1]), x.dtype),
        scratch_shapes=[pltpu.VMEM((_HALO, tc), F32)],
        compiler_params=pallas_common.compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_common.use_interpret(), name="ssm_conv_fwd",
    )(x, *small)


def _conv_bwd_call(x, w, b, col0, g):
    ts, tc = _conv_blocks(x.shape[1], w.shape[1], col0)
    (bsz, sp), (k, channels) = x.shape[:2], w.shape
    c0, last, per = col0 // tc, sp // ts - 1, ts // _HALO_VIEW
    small, small_specs = _conv_small(w, b, tc)
    window = pl.BlockSpec((1, ts, tc), lambda b_, c, s: (b_, last - s, c0 + c))
    own = pl.BlockSpec((1, ts, tc), lambda b_, c, s: (b_, last - s, c))
    view = pl.BlockSpec((1, _HALO_VIEW, tc),
                        lambda b_, c, s: (b_, jnp.maximum((last - s) * per - 1, 0), c0 + c))
    rows = (k + 1) * _HALO
    dx, sums = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, k=k),
        grid=(bsz, channels // tc, sp // ts),
        in_specs=[window, view, own, *small_specs],
        out_specs=[own, pl.BlockSpec((1, rows, tc), lambda b_, c, s: (b_, 0, c))],
        out_shape=[jax.ShapeDtypeStruct(g.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, rows, channels), F32)],
        scratch_shapes=[pltpu.VMEM((_HALO, tc), F32)],
        compiler_params=pallas_common.compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_common.use_interpret(), name="ssm_conv_bwd",
    )(x, x, g, *small)
    sums = sums.reshape(bsz, k + 1, _HALO, channels).sum(axis=(0, 2))
    return dx, sums[:k].astype(w.dtype), sums[k].astype(b.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_core(x, w, b, col0):
    """x (B, S, W) with S in whole blocks, w (K, C), b (C,) -> silu(conv) of
    the channels col0 .. col0 + C, (B, S, C) in x's dtype."""
    return _conv_fwd_call(x, w, b, col0)


def _conv_core_fwd(x, w, b, col0):
    return _conv_fwd_call(x, w, b, col0), (x, w, b)


def _conv_core_bwd(col0, res, g):
    x, w, b = res
    dx, dw, db = _conv_bwd_call(x, w, b, col0, g)
    right = x.shape[2] - col0 - w.shape[1]
    if col0 or right:  # the window's place in the wider array
        dx = jnp.pad(dx, ((0, 0), (0, 0), (col0, right)))
    return dx, dw, db


_conv_core.defvjp(_conv_core_fwd, _conv_core_bwd)
