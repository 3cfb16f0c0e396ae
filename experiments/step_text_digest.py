"""Digest of the lowered text of every benchmark cell's programs, no chip needed.

A cell a change must leave alone is protected only if its lowered program is the same
text (PERF.md §6, PR 26/27). For each cell of ``BENCHMARK.json`` this builds what the
cell's ``train()`` call builds -- the configuration's ``program_flags``, the traffic's
batch and sequence, bf16, flash attention (what ``--attn_impl auto`` resolves to on a
TPU), and for a searched cell the plan ``cli search`` emits for the cell's arguments --
lowers ``rt.train_step`` for a described v5e (1 chip, or the 2x2 mesh) with the real
Mosaic kernels, and prints a sha256 of the text normalised as ``flash_text_digest.py``
does (kernel payloads replaced by their assembly without debug locations). A serving
cell has two programs, the engine's prompt chunk and decode step (the AOT registry's
``serving_prefill`` / ``serving_decode`` at the traffic's ``serve_flags``), printed as
``<cell>/<program>``.

Run it in a ``git archive`` of the parent commit and in the change; equal digests = the
same program:

    JAX_PLATFORMS=cpu python experiments/step_text_digest.py [--cell NAME] [--dump DIR]

PERF.md §6 records the digests (jax 0.9.0; another jax prints other text).
"""

import argparse
import hashlib
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402

from benchmark.lib import harness  # noqa: E402  (the manifest's reader; no other part of it)
from flash_text_digest import normalised  # noqa: E402  (experiments/ is sys.path[0])


def plan_flags(cell, config, traffic, out_dir):
    """The train flags of the cell's plan, as ``benchmark/lib/harness.resolve_plan``
    makes them; a searched plan is searched here with the same arguments."""
    plan = traffic["plan"]
    if plan == "single":
        return []
    if "file" in plan:
        return ["--galvatron_config_path", os.path.join(ROOT, plan["file"])]
    from galvatron_tpu import cli

    path = os.path.join(out_dir, cell["name"] + "_plan.json")
    rc = cli.main(["search", *config["program_flags"], "--num_devices", str(cell["chips"]),
                   "--seq_length", str(traffic["seq_len"]), "--mixed_precision", "bf16",
                   "--attn_impl", "flash", *plan["search"], "--output_config_path", path])
    if rc or not os.path.exists(path):
        raise SystemExit("search returned %s and left no plan at %s" % (rc, path))
    return ["--galvatron_config_path", path]


def lowered_step(cell, config, traffic, topo, out_dir):
    from galvatron_tpu.core.arguments import (
        adam_config_from_args,
        hybrid_config_from_args,
        initialize_galvatron,
        model_config_from_args,
        resolve_attn_impl,
    )
    from galvatron_tpu.core.checkpoint import abstract_state_of
    from galvatron_tpu.models.modeling import batch_row_width
    from galvatron_tpu.parallel.hybrid import build_runtime
    from galvatron_tpu.parallel.mesh import build_mesh

    ns = initialize_galvatron("train", [
        *config["program_flags"], "--seq_length", str(traffic["seq_len"]),
        "--global_train_batch_size", str(traffic["global_batch"]),
        "--mixed_precision", "bf16", "--attn_impl", "flash",
        *plan_flags(cell, config, traffic, out_dir), *traffic.get("train_flags", [])])
    cfg = resolve_attn_impl(model_config_from_args(ns), ns)
    hp = hybrid_config_from_args(ns, cfg.total_layers, cell["chips"])
    mesh, axes = build_mesh(pp=hp.pp, devices=list(topo.devices[:cell["chips"]]))
    rt = build_runtime(cfg, hp, mesh=mesh, axes=axes, adam=adam_config_from_args(ns),
                       global_batch_size=ns.global_train_batch_size, seq_len=cfg.sample_len)
    batch = jax.ShapeDtypeStruct(
        (ns.global_train_batch_size, batch_row_width(cfg, cfg.sample_len)), jnp.int32,
        sharding=rt.batch_sharding)
    lowered = rt.train_step.lower(abstract_state_of(rt), batch)
    if "tpu_custom_call" not in lowered.as_text():
        raise SystemExit("the lowered step of %s holds no Mosaic kernel" % cell["name"])
    return lowered


def lowered_serving(config, traffic, topo):
    """``{program: lowered}`` of a serving cell: what ``benchmark/lib/serve.build_engine``
    makes of the configuration's ``program_flags`` and the traffic's ``serve_flags``,
    as the AOT registry declares the slot engine's two programs."""
    from galvatron_tpu.aot import registry
    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args
    from galvatron_tpu.serving import engine  # noqa: F401  (registers the serving family)

    ns = initialize_galvatron("serve", [*config["program_flags"], *traffic["serve_flags"]])
    cfg = model_config_from_args(ns)
    ctx = registry.ProgramContext(cfg=cfg, num_slots=ns.num_slots,
                                  prefill_chunk=ns.prefill_chunk, max_seq_len=cfg.max_seq_len)
    one_chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    out = {}
    for spec in registry.enumerate_programs(ctx, include=("serving",)):
        if spec.name in ("serving_prefill", "serving_decode"):
            args = [a if a is cfg else jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), a)
                for a in spec.args]
            out[spec.name] = spec.fn.lower(*args)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cell", action="append", help="only this cell (may repeat)")
    ap.add_argument("--dump", help="directory to write the normalised texts to")
    ap.add_argument("--compile", action="store_true",
                    help="also compile the step for the described chip and print what the "
                         "compiler plans of its memory (no chip: a plan, not a measurement)")
    args = ap.parse_args()
    from galvatron_tpu.aot.cache import persistent_cache_off
    from galvatron_tpu.ops import pallas_common

    # the CPU is the backend here; lower the real kernels (every kernel module, and
    # every choice of a kernel over its plain body, asks this one switch)
    pallas_common.use_interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    with tempfile.TemporaryDirectory() as out_dir, persistent_cache_off():
        for name in args.cell or [w["name"] for w in harness.load_manifest(ROOT)["workloads"]]:
            cell, config, traffic = harness.load_cell(ROOT, name)
            if traffic.get("kind", "train") == "serve":
                programs = {f"{name}/{program}": lowered for program, lowered in
                            lowered_serving(config, traffic, topo).items()}
            else:
                programs = {name: lowered_step(cell, config, traffic, topo, out_dir)}
            for label, lowered in programs.items():
                text = normalised(lowered.as_text())
                print(label, "sha256", hashlib.sha256(text.encode()).hexdigest(),
                      "bytes", len(text), flush=True)
                if args.dump:
                    os.makedirs(args.dump, exist_ok=True)
                    with open(os.path.join(args.dump, label.replace("/", ".") + ".txt"), "w") as f:
                        f.write(text)
                if args.compile:
                    mem = lowered.compile().memory_analysis()
                    gib = {k: round(getattr(mem, k) / 2**30, 3) for k in (
                        "argument_size_in_bytes", "output_size_in_bytes", "alias_size_in_bytes",
                        "temp_size_in_bytes")}
                    print(label, "compiler's plan, GiB:", gib, "arguments + temporaries",
                          round((mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2**30, 3),
                          flush=True)


if __name__ == "__main__":
    main()
