"""Digest of the lowered text of the RoPE stacked-qkv flash kernels, no chip needed.

What refused PR 26 was a RoPE program that was no longer the parent's. This lowers
``jax.grad`` of ``flash_attention_qkv`` with RoPE at the one-chip cells' shapes for a
described v5e (real Mosaic), and prints a sha256 of the StableHLO text in which every
Mosaic payload (base64 MLIR bytecode in ``backend_config``) is replaced by the kernel's
assembly WITHOUT debug locations: the payload carries the file paths and line numbers of
the Python that traced it, so the raw text differs between two checkouts of one program
and even between two calls in one process.

Run it in a copy of the parent commit and in the change; equal digests = the same program:

    JAX_PLATFORMS=cpu python experiments/flash_text_digest.py [--dump DIR]

PERF.md §6 records the digests of PR 27 (jax 0.9.0; another jax prints other text).
"""

import argparse
import base64
import hashlib
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax._src.interpreters import mlir  # noqa: E402
from jax._src.lib import tpu  # noqa: E402
from jax._src.lib.mlir import ir  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from galvatron_tpu.ops import flash_attention as fa  # noqa: E402
from galvatron_tpu.ops import pallas_common  # noqa: E402

SHAPES = ((2, 3, 32, 4096, 128), (16, 3, 32, 512, 128))  # baichuan-7b_s4096, _s512
_PAYLOAD = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def mosaic_asm(payload_b64: str) -> str:
    ctx = mlir.JaxIrContext()
    ctx.append_dialect_registry(mlir.upstream_dialects)
    ctx.load_all_available_dialects()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(payload_b64))
        return module.operation.get_asm(enable_debug_info=False)


def normalised(text: str) -> str:
    kernels = []

    def swap(match):
        kernels.append(mosaic_asm(match.group(1)))
        return '\\22body\\22: \\22<kernel %d>\\22' % (len(kernels) - 1)

    return _PAYLOAD.sub(swap, text) + "".join(
        "\n\n// kernel %d\n%s" % (i, asm) for i, asm in enumerate(kernels))


def lowered_text(shape, device) -> str:
    s, d = shape[3], shape[4]
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=device)
    table = jax.ShapeDtypeStruct((s, d // 2), jnp.float32, sharding=device)

    def loss(x, cos, sin):
        return jnp.sum(fa.flash_attention_qkv(x, rope=(cos, sin)).astype(jnp.float32))

    text = jax.jit(jax.grad(loss)).lower(qkv, table, table).as_text()
    if "tpu_custom_call" not in text:
        raise SystemExit("the lowered text holds no Mosaic kernel")
    return text


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dump", help="directory to write the normalised texts to")
    args = ap.parse_args()
    pallas_common.use_interpret = lambda: False  # the CPU is the backend here; lower the real kernels
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    device = SingleDeviceSharding(topo.devices[0])
    for shape in SHAPES:
        text = normalised(lowered_text(shape, device))
        print(shape, "sha256", hashlib.sha256(text.encode()).hexdigest(), "bytes", len(text), flush=True)
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(args.dump, "rope_qkv_s%d.txt" % shape[3]), "w") as f:
                f.write(text)


if __name__ == "__main__":
    main()
