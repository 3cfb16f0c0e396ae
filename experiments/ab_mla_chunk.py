"""A prompt chunk's latent attention on the chip: the chunk form (expansion through
``W_kvb``, scores at dn + dr, running softmax, values at dv) at the shape of
`sarvam-105b_serve_long_above_knee` (a chunk of 1,024 queries x 64 heads against a
slot of a 5-layer stacked cache of 32 x 16,384 x 576, bf16), the plain body
(`mla._plain_chunk`: XLA's loop over key blocks, float32 scores through HBM)
against the kernel `mla_chunk` (`ops/mla_prefill.latent_chunk_attention`) in two
places for the expansion:

(a) INSIDE the kernel, a head a grid step (what `ops/mla_prefill.py` keeps), at
    several key blocks (PR 53's first two rounds also walked the queries and keys of
    a block in sub-blocks, which lost and left: PERF.md section 6);
(b) XLA's GEMM over the live keys, its output head-major in HBM for a kernel that
    only attends (``_attend_expanded`` below, the same block body). This is (b) at
    its BEST: one GEMM over all live keys of a static length and no loop-carried
    state, which a traced chunk end would need.

    chiprun --chips 1 -- python experiments/ab_mla_chunk.py [--blocks 512,1024,2048]

Cases: the chunk ends at 1,024, 4,096 and 12,288 live keys (1, 4 and 12 live key
blocks), and one slid-left chunk whose offset is no multiple of anything (4,096 -
1,024 - 37). For each: ms a layer of each body, the TFLOP/s the chunk's least
arithmetic (expansion + causal scores and values) makes of that time (197 is the
chip's), the largest relative difference to the plain body, and the largest device
operations of the plain body and the kept kernel.

One JSON line a measurement, the table at the end; no CPU fallback (``--tiny`` is
the CPU rehearsal at small shapes, interpreted: its times mean nothing).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from experiments.ab_ssd import device_ops, rel, timed  # noqa: E402
from galvatron_tpu.models import mla  # noqa: E402
from galvatron_tpu.models.modeling import PRESETS  # noqa: E402
from galvatron_tpu.ops import mla_prefill, pallas_common  # noqa: E402

F32 = jnp.float32
LAYER, SLOT = 2, 5


def _expanded_kernel(offset_ref, q_ref, kv_ref, kr_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, nope):
    """Variant (b)'s kernel: a head's expanded ``[k_nope^T ; v^T]`` block and the
    shared rotary key come from HBM; the rest is `mla_prefill._attend_block`."""
    j = pl.program_id(1)
    offset, block_k = offset_ref[0], kv_ref.shape[1]
    start = j * block_k
    mla_prefill._init(j, m_ref, l_ref, acc_ref)

    def accumulate(masked):
        k_t = jnp.concatenate([kv_ref[:nope, :], kr_ref[...]], axis=0)
        mla_prefill._attend_block(q_ref, k_t, kv_ref[nope:, :], m_ref, l_ref, acc_ref, offset, start,
                                  scale=scale, masked=masked)

    whole = start + block_k <= offset + 1
    pl.when(whole)(functools.partial(accumulate, False))
    pl.when(jnp.logical_not(whole))(functools.partial(accumulate, True))
    mla_prefill._finalize(j, o_ref, l_ref, acc_ref)


def _attend_expanded(q_nope, q_rope, stacked, offset, wkvb, *, live, dims, scale, block_k):
    """(b): expand keys [0, ``live``) of the slot through XLA's GEMM, then attend."""
    n, dn, dr, dv, r = dims
    s = q_nope.shape[1]
    q = jnp.transpose(jnp.concatenate([q_nope, q_rope], axis=-1)[0], (1, 0, 2))
    latent_t = jnp.swapaxes(stacked, 2, 3)[LAYER, SLOT, :, :live]  # (r + dr, live)
    with jax.named_scope("expand"):
        kv_t = jnp.einsum("rnd,rk->ndk", wkvb, latent_t[:r])  # (n, dn + dv, live)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, live // block_k),
        in_specs=[
            pl.BlockSpec((None, s, dn + dr), lambda h, j, *_: (h, 0, 0)),
            pl.BlockSpec((None, dn + dv, block_k), lambda h, j, *_: (h, 0, j)),
            pl.BlockSpec((dr, block_k), lambda h, j, *_: (0, j)),
        ],
        out_specs=pl.BlockSpec((s, dv), lambda h, j, *_: (0, h)),
        scratch_shapes=[pltpu.VMEM((s, 1), F32), pltpu.VMEM((s, 1), F32),
                        pltpu.VMEM((s, dv), F32)],
    )
    out = pl.pallas_call(
        functools.partial(_expanded_kernel, scale=scale, nope=dn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, n * dv), q.dtype),
        compiler_params=pallas_common.compiler_params(dimension_semantics=("parallel", "arbitrary")),
        interpret=pallas_common.use_interpret(),
        name="mla_chunk_expanded",
    )(jnp.reshape(offset, (1,)), q, kv_t, latent_t[r:])
    return out.reshape(1, s, n, dv)


def chunk_flops(dims, s: int, live: int, block: int) -> float:
    """The chunk's least arithmetic: the expansion of the live key blocks a head, and
    the pairs at or before the diagonal at widths dn + dr and dv."""
    n, dn, dr, dv, r = dims
    keys = -(-live // block) * block
    pairs = s * (live - s) + s * (s + 1) / 2
    return n * (2.0 * keys * r * (dn + dv) + 2.0 * pairs * (dn + dr + dv))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="512,1024,2048", help="key blocks of variant (a)")
    ap.add_argument("--ops", type=int, default=6, help="device operations listed a body")
    ap.add_argument("--tiny", action="store_true", help="small shapes, any backend (a rehearsal)")
    args = ap.parse_args(argv)
    tiny = args.tiny
    if not tiny and jax.devices()[0].platform != "tpu":
        raise SystemExit("ab_mla_chunk: needs a TPU")
    if tiny:
        from tests.test_mla import small_cfg

        cfg, layers, rows, positions, s = small_cfg(dtype=jnp.bfloat16), 3, 6, 256, 32
        shrink = 32
    else:
        cfg, layers, rows, positions, s = (
            PRESETS["sarvam-105b"].replace(dtype=jnp.bfloat16), 5, 32, 16384, 1024)
        shrink = 1
    dims = mla.dims(cfg)
    n, dn, dr, dv, r = dims
    scale = mla.softmax_scale(cfg)
    block = mla_prefill.KEY_BLOCK // shrink
    ks = jax.random.split(jax.random.key(0), 4)
    p = mla.init_params(ks[0], cfg)
    p = jax.tree.map(lambda a: a.astype(cfg.dtype), p)
    wkvb = mla._kvb(p, cfg, cfg.dtype)
    # (a unit-variance latent as the latent's RMSNorm leaves it; queries of the
    # projection's own scale)
    stacked = jax.random.normal(ks[1], (layers, rows, positions, r + dr), cfg.dtype)
    q_nope = jax.random.normal(ks[2], (1, s, n, dn), cfg.dtype)
    q_rope = jax.random.normal(ks[3], (1, s, n, dr), cfg.dtype)
    lives = [s, 4 * s, 12 * s] if not tiny else [s, 4 * s]
    cases = [(f"live_{live}", live - s, live) for live in lives]
    cases.append(("slid_left", 4 * s - s - 37, -(-(4 * s - 37) // block) * block))

    def plain(qn, qr, c, off):
        return mla._plain_chunk(qn, qr, c, LAYER, jnp.int32(SLOT), off, p, cfg)

    def inside(block_k):
        def body(qn, qr, c, off):
            # the kernel has one key block, its module's: another is tried by setting
            # it while this body is traced (the tests do the same)
            kept, mla_prefill.KEY_BLOCK = mla_prefill.KEY_BLOCK, block_k
            try:
                with jax.named_scope("expand"):
                    return mla_prefill.latent_chunk_attention(
                        qn, qr, c, LAYER, jnp.int32(SLOT), off, wkvb, dims=dims, scale=scale)
            finally:
                mla_prefill.KEY_BLOCK = kept
        return body

    def expanded(live):
        def body(qn, qr, c, off):
            return _attend_expanded(qn, qr, c, off, wkvb, live=live, dims=dims, scale=scale,
                                    block_k=block)
        return body

    out_rows = []
    for name, offset, live in cases:
        operands = (q_nope, q_rope, stacked, jnp.int32(offset))
        flops = chunk_flops(dims, s, offset + s, block)
        want = jax.jit(plain)(*operands)
        bodies = [("plain", plain)]
        bodies += [(f"a_{b}", inside(b)) for b in (int(v) // shrink for v in args.blocks.split(","))]
        bodies += [(f"b_{block}", expanded(live))]
        for body, fn in bodies:
            fn = jax.jit(fn)
            got = fn(*operands)
            ms = timed(fn, *operands, iters=3 if tiny else 20)
            row = {"case": name, "offset": offset, "live_keys": offset + s, "body": body,
                   "ms_a_layer": ms, "tflops": flops / ms / 1e9,
                   "rel_to_plain": rel(got.astype(F32), want.astype(F32)),
                   "finite": bool(jnp.isfinite(got.astype(F32)).all())}
            if not tiny and name == "live_4096" and body in ("plain", f"a_{block}", f"b_{block}"):
                row["device_ops_ms"] = device_ops(fn, operands, top=args.ops)
            print(json.dumps(row), flush=True)
            out_rows.append(row)
    print("| case | live keys | body | ms a layer | TFLOP/s | rel to plain |")
    print("| --- | --- | --- | --- | --- | --- |")
    for row in out_rows:
        print(f"| {row['case']} | {row['live_keys']} | {row['body']} | {row['ms_a_layer']:.3f} | "
              f"{row['tflops']:.1f} | {row['rel_to_plain']:.4f} |")
    worst = max(row["rel_to_plain"] for row in out_rows)
    ok = all(row["finite"] for row in out_rows) and worst < 0.05
    print(json.dumps({"ok": ok, "worst_rel_to_plain": worst,
                      "device": str(np.asarray(jax.devices())[0])}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
