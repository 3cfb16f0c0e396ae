"""Training loop driver shared by all model-family entries.

The train_dist.py body of the reference (reference:
models/llama_hf/train_dist.py:16-90): resolve model config → hybrid strategy
→ construct hybrid model → dataloader → Adam → iterate forward_backward with
profiler hooks. Plus what the reference lacks: checkpoint save/resume, and
the resilience layer around it — every exit mode (normal completion, SIGTERM,
unhandled exception, anomaly abort) lands a committed, resumable checkpoint,
and a non-finite loss is skipped/aborted by policy (core/resilience.py)
instead of silently poisoning the optimizer state.
"""

from __future__ import annotations

import argparse
import collections
import math
import os
import time

import jax
import numpy as np

from galvatron_tpu.core import faults
from galvatron_tpu.core import peer_store as peer_store_mod
from galvatron_tpu.core.arguments import hybrid_config_from_args, model_config_from_args
from galvatron_tpu.core.checkpoint import (
    CheckpointCorruptError,
    latest_step,
    portable_flat_state,
    read_manifest,
    restore_checkpoint_portable,
    restore_from_flat_leaves,
    save_checkpoint_portable,
    step_path,
    uncommitted_steps,
)
from galvatron_tpu.core.preemption import PreemptionListener
from galvatron_tpu.core.dataloader import build_dataloader
from galvatron_tpu.core.resilience import AnomalyAbort, AnomalySentinel
from galvatron_tpu.parallel.hybrid import build_runtime
from galvatron_tpu.profiling.runtime import RuntimeProfiler


def train(ns: argparse.Namespace, verbose: bool = True) -> dict:
    from galvatron_tpu.obs import tracing as obs_tracing

    # --xla_overlap: the curated latency-hiding flag set must land in
    # XLA_FLAGS before _train_impl's first backend touch (distributed init,
    # mesh build) — a later append would be silently ignored by the already-
    # initialized runtime. The applied set rides the manifest fingerprint.
    from galvatron_tpu.parallel.mesh import apply_xla_overlap

    ns.xla_overlap_applied = apply_xla_overlap(getattr(ns, "xla_overlap", "off"))

    # span tracer lifecycle wrapper: enable happens out here so that a
    # setup failure ANYWHERE in _train_impl (corrupt restore, loader build,
    # sidecar bind, ...) cannot leak the enabled process-wide singleton into
    # a later run — which would silently force per-iter syncs and record
    # spans nobody exports. --flight_dir arms tracing too: a flight
    # recorder with no span ring would be a silent no-op exactly when the
    # operator asked for crash forensics — and so does --step_timeout_s:
    # the hang watchdog's whole output IS the flight dump it takes on fire.
    tracer = obs_tracing.tracer
    tracer_owned = False
    if (
        getattr(ns, "trace_spans", None)
        or getattr(ns, "flight_dir", None)
        or getattr(ns, "step_timeout_s", 0)
    ):
        tracer.enable(capacity=getattr(ns, "trace_ring", 4096))
        tracer_owned = True
    try:
        return _train_impl(ns, verbose, tracer, tracer_owned)
    except BaseException as e:
        # _train_impl's own finally exports + dumps on every path that
        # reached the training loop; the tracer still being enabled here
        # means SETUP crashed before that try was entered — the forensics
        # the flags promise (a corrupt-restore fallback trail, most
        # commonly) must still land before the ring is dropped
        if tracer_owned and tracer.enabled:
            _export_obs_artifacts(
                ns, tracer, e, extra={"phase": "setup"}, verbose=verbose
            )
        raise
    finally:
        if tracer_owned and tracer.enabled:
            tracer.disable()
            tracer.clear()


def _export_obs_artifacts(ns, tracer, exc, extra=None, verbose=True) -> None:
    """Flight-recorder dump (exceptional exits only) + span-trace export.
    Best-effort by contract: callers sit in crash/teardown paths where an
    observability failure must never mask the original exception."""
    try:
        if exc is not None:
            fdir = getattr(ns, "flight_dir", None)
            if not fdir and getattr(ns, "trace_spans", None):
                fdir = os.path.dirname(os.path.abspath(ns.trace_spans))
            if fdir:
                from galvatron_tpu.obs.flight import dump_flight

                fpath = dump_flight(
                    fdir, tracer,
                    reason=f"{type(exc).__name__}: {str(exc)[:200]}",
                    extra=extra,
                )
                if fpath:
                    print(f"flight recorder → {fpath}")
        if getattr(ns, "trace_spans", None) and jax.process_index() == 0:
            out = tracer.export_chrome_trace(ns.trace_spans)
            if verbose:
                print(f"span trace → {out}")
    except Exception as obs_err:  # noqa: BLE001 — observability is best-effort
        print(f"observability export failed: {obs_err!r}")


def _read_plan_doc(ns: argparse.Namespace) -> dict:
    """The plan file as a document ({} without one, or where it cannot be read:
    plan_check has refused a bad one before this is asked)."""
    if not ns.galvatron_config_path:
        return {}
    import json

    try:
        with open(ns.galvatron_config_path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    return doc if isinstance(doc, dict) else {}


def _price_plan_as_run(plan_doc: dict, cfg, hp, world: int, global_bsz: int) -> dict:
    """``search/price.price_plan`` of the plan this run trains.  Where the plan
    document carries the search's own ``search_price`` for the batch trained
    (the rule ``search_cost_ms`` follows) that is it; otherwise the plan is
    priced here from the basis ``cli search --analytic_costs 1`` uses
    (``theoretical.price_model_plan``).  ``basis["source"]`` says which;
    ``{"error": why}`` where the model cannot be priced."""
    doc_price = plan_doc.get("search_price")
    if isinstance(doc_price, dict) and plan_doc.get("global_bsz") == global_bsz:
        return {**doc_price, "basis": {**doc_price.get("basis", {}), "source": "plan_file"}}
    try:
        from galvatron_tpu.search.theoretical import price_model_plan

        price = price_model_plan(cfg, hp, world, global_bsz)
    except Exception as e:  # noqa: BLE001 — a price is an observation, never a crash source
        return {"error": f"{type(e).__name__}: {e}"}
    price["basis"]["source"] = "trainer"
    return price


def _train_impl(ns: argparse.Namespace, verbose: bool, tracer,
                tracer_owned: bool) -> dict:
    faults.init_from_env()  # chaos hooks: no-ops unless GALVATRON_FAULTS is set
    if getattr(ns, "multihost", 0):
        # join the multi-host job (TPU pods: coordinator/process id are
        # auto-detected from the TPU metadata; DCN carries the collectives) —
        # the reference's torch.distributed.init_process_group role
        # (site_package/megatron/initialize.py _initialize_distributed)
        jax.distributed.initialize()
    hf_params = None
    if getattr(ns, "load_hf", None):
        # pretrained HF weights: the model shape comes from the HF config
        # (the reference builds its model FROM the HF checkpoint the same
        # way — models/llama_hf/train_dist.py)
        from galvatron_tpu.models.convert import load_hf_llama

        hf_params, cfg = load_hf_llama(ns.load_hf)
        # weight-bearing dims come from the HF config; the training sequence
        # length is still the user's call (shorter contexts train fine). The
        # learned-pos table must follow the override, or the imported state
        # would disagree with the runtime's nominal shapes and break resume.
        if getattr(ns, "seq_length", None) and ns.seq_length != cfg.max_seq_len:
            if "pos" in hf_params.get("embed", {}):
                if ns.seq_length > cfg.max_seq_len:
                    raise ValueError(
                        f"--seq_length {ns.seq_length} exceeds the checkpoint's "
                        f"learned-position table ({cfg.max_seq_len})"
                    )
                hf_params["embed"]["pos"] = hf_params["embed"]["pos"][: ns.seq_length]
            cfg = cfg.replace(max_seq_len=ns.seq_length)
    else:
        cfg = model_config_from_args(ns)
    from galvatron_tpu.core.arguments import resolve_attn_impl

    # data-pipeline flags (galvatron_tpu/data/): packing rides the model
    # config (split_batch / attention masking / position reset key off it),
    # and must be set BEFORE attn resolution so 'auto' lands on the
    # segment-maskable xla path instead of flash
    if getattr(ns, "pack_sequences", 0):
        cfg = cfg.replace(pack_sequences=True)
    use_data_pipe = bool(
        getattr(ns, "data_mixture", None)
        or cfg.pack_sequences
        or getattr(ns, "prefetch_depth", 0)
    )
    if use_data_pipe:
        if not (getattr(ns, "data_mixture", None) or getattr(ns, "data_path", None)):
            raise ValueError(
                "--pack_sequences/--prefetch_depth/--data_mixture need a real "
                "corpus: pass --data_path or --data_mixture"
            )
        if getattr(ns, "rampup_batch_size", None):
            raise ValueError(
                "--rampup_batch_size is incompatible with the data pipeline "
                "(mixture/packing/prefetch): the sample-domain cursor is "
                "defined at one global batch size"
            )
    cfg = resolve_attn_impl(cfg, ns)
    world = len(jax.devices())
    from galvatron_tpu.analysis import plan_check

    if ns.galvatron_config_path:
        # fail-fast BEFORE any mesh is built: a bad plan surfaces as
        # structured GTA… diagnostics in milliseconds instead of a cryptic
        # compiler abort (or a silent memory blowout) minutes into startup.
        # The file is checked directly so even plans that fail to decode
        # report field provenance rather than a bare codec ValueError.
        plan_check.ensure_valid(
            ns.galvatron_config_path, model_config=cfg, world_size=world,
            global_bsz=ns.global_train_batch_size,
            context=f"refusing to start: {ns.galvatron_config_path}",
            verbose=verbose,
        )
    hp = hybrid_config_from_args(ns, cfg.total_layers, world)
    if not ns.galvatron_config_path:
        plan_check.ensure_valid(
            hp, model_config=cfg, world_size=world,
            global_bsz=ns.global_train_batch_size,
            context="refusing to start: invalid hybrid-parallel flags",
            verbose=verbose,
        )
    from galvatron_tpu.core.arguments import adam_config_from_args

    # shared with the elastic prewarm: the optimizer terms are constants in
    # the compiled train_step, so they are part of the program's identity
    adam = adam_config_from_args(ns)
    rampup = None
    if getattr(ns, "rampup_batch_size", None):
        from galvatron_tpu.core.schedules import BatchSizeRampup

        if hp.pp > 1:
            raise ValueError("--rampup_batch_size requires pp=1 (static pipeline shapes)")
        start, inc, samples = ns.rampup_batch_size
        rampup = BatchSizeRampup(
            start=start, increment=inc, rampup_samples=samples,
            target=ns.global_train_batch_size,
        )
        for bs in rampup.sizes():
            if bs % world != 0:
                raise ValueError(
                    f"rampup batch size {bs} must be divisible by the device "
                    f"count {world} (global batches shard over all data axes)"
                )
            if bs % max(1, hp.chunks) != 0:
                raise ValueError(
                    f"rampup batch size {bs} must be divisible by chunks "
                    f"{hp.chunks} (micro-batch gradient accumulation)"
                )
    seq = cfg.sample_len
    mesh = axes = None
    if getattr(ns, "num_slices", 0) and ns.num_slices > 1:
        # multislice: slice-major device order puts pp + the major data axes
        # across the DCN boundary (parallel/mesh.build_mesh)
        from galvatron_tpu.parallel.mesh import build_mesh

        mesh, axes = build_mesh(pp=hp.pp, num_slices=ns.num_slices)
    with tracer.span("build_runtime") as build_span:
        rt = build_runtime(
            cfg, hp, mesh=mesh, axes=axes, adam=adam,
            global_batch_size=ns.global_train_batch_size, seq_len=seq,
        )
        from galvatron_tpu.models import mixers
        from galvatron_tpu.models.moe import held_path_counts

        # which bodies the run's layers take, for the span and the manifest:
        layer_paths = {
            # projection seams of the plan's tp_overlap layers that run the
            # collective-matmul ring / the plain einsum (the ring's shape test)
            "tp_overlap_seams": rt.tp_overlap_seams,
            # how many decoder layers of each kind (a hybrid stack: one granite
            # period is nine state-space layers and one of attention)
            "layer_kinds": dict(collections.Counter(rt.cfg.kinds)),
            # "<kind>_scan_path", "<kind>_conv_path" for every registered kind: the
            # fused kernels or the plain body; a kind the stack lacks loads nothing
            **mixers.path_counts(rt.cfg),
            # the layers whose held share of the experts does work in proportion to
            # the pairs it holds, and those over the worst-case buffer (models/moe.py)
            "moe_held_path": held_path_counts(rt.cfg),
        }
        build_span.set(**layer_paths)
        # what the search's cost model charges THIS plan, term by term,
        # whatever the plan's source (a file, flags, the defaults): the drift
        # gauge's anchor and what a reader sets the device's time beside
        plan_doc = _read_plan_doc(ns)
        plan_price = None
        if tracer.enabled or getattr(ns, "metrics_path", None):
            with tracer.span("plan_price"):
                plan_price = _price_plan_as_run(plan_doc, cfg, hp, world, ns.global_train_batch_size)
            build_span.set(plan_price=plan_price)
        from galvatron_tpu.obs import flight

        flight.note_plan_price(plan_price)

    from galvatron_tpu.obs import tracing as obs_tracing
    from galvatron_tpu.utils.metrics import SCHEMA_VERSION, MetricsLogger

    # opened before restore so a corrupt-latest fallback (ckpt_fallback) is
    # visible in the same JSONL stream as the training events. Multihost:
    # O_APPEND does not serialize cross-process writers on network
    # filesystems, so the JSONL sink is process-0-only (the other hosts get
    # a no-op logger; see MetricsLogger's docstring).
    metrics_path = getattr(ns, "metrics_path", None)
    if metrics_path and jax.process_index() != 0:
        metrics_path = None
    metrics = MetricsLogger(metrics_path)
    if plan_price is not None:
        from galvatron_tpu.search.price import flat

        metrics.log("plan_price", **flat(plan_price))
    # in-memory peer replication client (core/peer_store.py): armed by the
    # elastic supervisor under --peer_replicate (env carries the store
    # addresses + this peer's ring rank). None = the RAM tier is off and
    # every recovery path below degrades to disk-only exactly as before.
    peer_client = peer_store_mod.client_from_env()
    # topology + plan fingerprint: rides every manifest so a restart can
    # tell "same world, same plan" from "the pod shrank under me" (GTA017)
    # and from a legal cross-plan resume. mesh_shape/axes are forensic;
    # world_size is the gate (plan_check.check_topology_fingerprint).
    from galvatron_tpu.core.strategy import plan_hash

    fingerprint = {
        "world_size": world,
        "mesh_shape": [int(x) for x in rt.mesh.devices.shape],
        "mesh_axes": [str(a) for a in rt.mesh.axis_names],
        "plan_hash": plan_hash(hp),
        "global_bsz": int(ns.global_train_batch_size),
        # scheduler provenance (--xla_overlap): mode + the flags actually
        # appended, so a perf delta across manifests is attributable
        "xla_overlap": getattr(ns, "xla_overlap", "off"),
        "xla_overlap_flags": list(getattr(ns, "xla_overlap_applied", []) or []),
        **layer_paths,
    }
    # JAX's persistent compile cache is always on, at the one place
    # resolve_compile_cache_dir names (JAX_COMPILATION_CACHE_DIR, else an
    # explicit --compile_cache_dir, else <repo>/.jax_cache; the flag's
    # 0/off/none sentinel wires none).
    # AOT compile subsystem (galvatron_tpu/aot; DESIGN.md § AOT compile
    # subsystem): an explicit --compile_cache_dir also arms the startup
    # consult — AOT-compile the programs THIS run will dispatch (always
    # train_step; init_state only when a fresh init is coming — a resume
    # never calls it, and eval_loss belongs to `cli warmup`, not a train
    # run), and account plan-keyed hit/miss in the artifact manifest.
    # Running BEFORE restore/init means the init compile below is already a
    # cache deserialize, the loop's first step pays no XLA compile, and a
    # proven-warm start shrinks the watchdog's first-step compile grace to
    # the normal deadline. Without the flag there is no manifest and no
    # extra lowering.
    from galvatron_tpu.aot.cache import (
        ArtifactStore,
        enable_persistent_cache,
        resolve_compile_cache_dir,
    )

    aot_dir = resolve_compile_cache_dir(ns)
    try:
        cache_dir = enable_persistent_cache(aot_dir)
    except OSError as e:  # read-only mount: costs only warmth
        cache_dir = aot_dir = None
        print(f"warning: compile cache unavailable ({e}); compiling cold")
    if verbose:
        print(f"compile cache: {cache_dir or 'disabled'}")
    aot_warm_hint = False
    aot_summ = None
    if getattr(ns, "compile_cache_dir", None):
        # best-effort by contract, like the elastic prewarm: a cache-
        # infrastructure failure (read-only mount, torn store) costs only
        # warmth — the run must still train cold
        try:
            if aot_dir:
                from galvatron_tpu.aot import warmup as aot_warmup

                will_restore = bool(ns.load and latest_step(ns.load) is not None)
                include = ["train_step"]
                if not will_restore and hf_params is None:
                    include.append("init_state")
                store = ArtifactStore(cache_dir)
                t0_warm = time.perf_counter()
                aot_reports = aot_warmup.warmup_runtime(
                    rt, ns.global_train_batch_size, seq, store=store,
                    plan=hp, model_cfg=cfg, include=include, verbose=verbose,
                )
                startup_ms = round((time.perf_counter() - t0_warm) * 1000.0, 1)
                for r in aot_reports:
                    metrics.log(
                        "compile_cache", program=r["program"], key=r["key"],
                        status=r["status"], hit=bool(r.get("cache_hit")),
                        compile_ms=r["compile_ms"],
                    )
                aot_summ = aot_warmup.summarize(aot_reports)
                aot_summ["startup_compile_ms"] = startup_ms
                ts_rep = next(
                    (r for r in aot_reports if r["program"] == "train_step"),
                    None,
                )
                # warm ONLY when the step program itself was served from the
                # manifest-known cache: hits on secondary programs must not
                # shave the grace the first step's real compile still needs
                aot_warm_hint = bool(
                    ts_rep
                    and ts_rep["status"] == "compiled"
                    and ts_rep["cache_hit"]
                )
                metrics.log(
                    "aot_warmup", warm_hint=aot_warm_hint, cache_dir=store.dir,
                    **aot_summ,
                )
                if verbose:
                    print(
                        f"aot warmup: {aot_summ['hits']} hits / "
                        f"{aot_summ['misses']} misses, {startup_ms:.0f} ms "
                        f"startup compile "
                        f"({'warm' if aot_warm_hint else 'cold'} start)"
                    )
        except Exception as e:  # noqa: BLE001 — warmth only, never the run
            aot_warm_hint = False
            aot_summ = None
            metrics.log(
                "aot_warmup", warm_hint=False, cache_dir=aot_dir,
                error=f"{type(e).__name__}: {e}"[:200],
            )
            print(f"warning: aot warmup failed ({type(e).__name__}: {e}); "
                  "starting cold")
    start_step = 0
    batch_offset = 0
    saved_data_state = None  # checkpoint's data-pipeline cursor (if any)
    # two-tier restore: the in-memory peer replica (core/peer_store.py) is
    # consulted FIRST and used when it is NEWER than the newest committed
    # disk step — host-loss recovery must not round-trip through storage,
    # and after a storage outage the replica may be the only record of the
    # last interval. A replica that fails its digest/structure check falls
    # back to the disk tier with a ckpt_fallback event, exactly as a
    # corrupt disk step falls back to an older one.
    restored_from = None
    meta: dict = {}
    disk_latest = latest_step(ns.load) if ns.load else None
    if peer_client is not None:
        rec = None
        try:
            rec = peer_client.get_newest()
        except Exception as peer_err:  # noqa: BLE001 — the RAM tier is optional
            print(f"peer store unreachable, using disk tier: {peer_err!r}")
        if rec is not None and (
            disk_latest is None or int(rec[0].get("step", -1)) > disk_latest
        ):
            h, payload = rec
            try:
                leaves = peer_store_mod.deserialize_state(payload, h)
                state = restore_from_flat_leaves(rt, leaves)
                start_step = int(h.get("step", 0))
                meta = dict(h.get("meta") or {})
                restored_from = "peer"
                if verbose:
                    print(f"restored step {start_step} from the in-memory "
                          f"peer replica ({int(h.get('nbytes', 0))} bytes)")
            except (peer_store_mod.ReplicaCorruptError,
                    CheckpointCorruptError) as e:
                metrics.log("ckpt_fallback", step=int(h.get("step", -1)),
                            error=str(e)[:300], source="peer")
                tracer.instant("ckpt_fallback", step=int(h.get("step", -1)),
                               source="peer")
                print(f"peer replica corrupt, falling back to disk: "
                      f"{str(e)[:200]}")
                meta = {}
    if restored_from is None and ns.load and disk_latest is not None:
        state = restore_checkpoint_portable(ns.load, rt, metrics=metrics)
        start_step = int(np.asarray(state["step"]))
        m = read_manifest(step_path(ns.load, start_step))
        meta = m.get("meta") if m and isinstance(m.get("meta"), dict) else {}
        restored_from = "disk"
    if restored_from is not None:
        # stream position ≠ optimizer step once anomaly skips happened: a
        # skipped batch was consumed but produced no update. Both tiers
        # record batches-consumed in their meta (manifest / replica header).
        batch_offset = start_step
        if meta:
            batch_offset = int(meta.get("batches_consumed", start_step))
        if isinstance(meta.get("data_state"), dict):
            saved_data_state = meta["data_state"]
        saved_fp = meta.get("fingerprint")
        if isinstance(saved_fp, dict):
            from galvatron_tpu.analysis.plan_check import (
                PlanError,
                check_topology_fingerprint,
            )

            diags = check_topology_fingerprint(saved_fp, world, source=ns.load)
            if diags and not getattr(ns, "allow_topology_change", False):
                # the plan this run would train was never searched for the
                # live mesh — refuse, pointing at the supervised path that
                # re-plans automatically (run-elastic sets the allow flag
                # after installing a validated plan for THIS topology)
                raise PlanError(
                    diags,
                    context=f"refusing to resume {ns.load} on a changed topology",
                )
            if diags:
                metrics.log(
                    "topology_resume", step=start_step,
                    old_world=saved_fp.get("world_size"), new_world=world,
                    old_plan=saved_fp.get("plan_hash"),
                    new_plan=fingerprint["plan_hash"],
                )
                tracer.instant(
                    "topology_resume", step=start_step,
                    old_world=saved_fp.get("world_size"), new_world=world,
                )
                if verbose:
                    print(
                        f"topology-change resume: {saved_fp.get('world_size')} "
                        f"→ {world} devices (checkpoint resharded portably)"
                    )
            elif saved_fp.get("plan_hash") not in (None, fingerprint["plan_hash"]):
                # cross-plan resume on the SAME topology is the portable
                # checkpoint working as designed — an event, not an error
                metrics.log(
                    "plan_change", step=start_step,
                    old_plan=saved_fp.get("plan_hash"),
                    new_plan=fingerprint["plan_hash"],
                )
                tracer.instant("plan_change", step=start_step)
        # sample-domain resume: the batch domain is only meaningful at the
        # batch size that consumed it — after a re-plan (or an operator
        # decision) changed the global batch, the cursor converts through
        # samples so no example is skipped or replayed
        rec_bsz = meta.get("global_bsz")
        if not rec_bsz and isinstance(saved_fp, dict):
            rec_bsz = saved_fp.get("global_bsz")
        samples_rec = meta.get("samples_consumed")
        if (
            samples_rec is not None
            and rec_bsz
            and int(rec_bsz) != ns.global_train_batch_size
        ):
            if getattr(ns, "rampup_batch_size", None):
                raise ValueError(
                    "cannot combine --rampup_batch_size with a changed "
                    f"--global_train_batch_size on resume (checkpoint "
                    f"records bsz {rec_bsz}): the rampup schedule replays "
                    "in the batch domain"
                )
            if int(samples_rec) % ns.global_train_batch_size:
                raise ValueError(
                    f"cannot resume at --global_train_batch_size "
                    f"{ns.global_train_batch_size}: the checkpoint consumed "
                    f"{samples_rec} samples (at bsz {rec_bsz}), which is not "
                    f"divisible — a partial batch would be skipped or "
                    f"replayed. Pick a batch size dividing {samples_rec}."
                )
            batch_offset = int(samples_rec) // ns.global_train_batch_size
            if verbose:
                print(
                    f"sample-domain resume: {samples_rec} samples consumed "
                    f"at bsz {rec_bsz} → batch cursor {batch_offset} at "
                    f"bsz {ns.global_train_batch_size}"
                )
        # recovery provenance: which tier restored and where the cursor
        # landed. The chaos harness derives MTTR (supervisor child_exit ts →
        # this record's ts) and steps-lost (fault step − resume_batches)
        # from it, so it fires on every resume, not only post-failure ones.
        metrics.log("recovery", step=start_step, source=restored_from,
                    resume_batches=batch_offset,
                    resume_samples=meta.get("samples_consumed"))
        if verbose:
            src = ns.load if restored_from == "disk" else "peer replica"
            print(f"resumed from {src} at step {start_step}")
    elif ns.load and uncommitted_steps(ns.load):
        # pre-manifest legacy dirs must not silently restart from scratch
        raise FileNotFoundError(
            f"--load {ns.load}: steps {uncommitted_steps(ns.load)} exist but "
            "none carry a manifest (pre-commit-protocol saves, or partial "
            "writes). Refusing to silently start from step 0 — restore one "
            "explicitly (checkpoint.restore_checkpoint_portable(..., step=N)) "
            "and re-save to commit it, or point --load elsewhere."
        )
    elif hf_params is not None:
        state = rt.init_state_from(hf_params)
        if verbose:
            print(f"initialized from HF checkpoint {ns.load_hf}")
    else:
        with tracer.span("init_state") as init_sp:
            # traced: the span closes on the realized state, not the dispatch
            state = init_sp.sync(rt.init_state(jax.random.key(ns.seed)))

    # start_batch fast-forwards by index arithmetic so resume sees the batches
    # an uninterrupted run would (reference has no resume at all); the offset
    # is batches CONSUMED, not optimizer steps — they diverge after skips
    data_pipe = None
    if saved_data_state is not None and not use_data_pipe:
        # the checkpoint was trained through the data pipeline; resuming
        # without its flags would silently continue a real-corpus run on
        # synthetic tokens (or unpacked windows), bypassing the per-source
        # verification the subsystem promises
        raise ValueError(
            f"--load {ns.load}: the checkpoint records a data-pipeline cursor "
            f"(sources {sorted(saved_data_state.get('per_source_consumed', {}))}"
            f"{', packed' if saved_data_state.get('packed') else ''}) but this "
            "run passes none of --data_mixture/--pack_sequences/"
            "--prefetch_depth. Resume with the original data flags, or point "
            "--load elsewhere."
        )
    with tracer.span("data_open"):
        if use_data_pipe:
            # production input path (galvatron_tpu/data/): sharded corpora,
            # deterministic mixture, sequence packing, async device prefetch.
            # The pipeline applies rt.shard_batch itself (on the prefetch thread
            # when armed), so the loop's data span is a dequeue. A restored
            # checkpoint's per-source cursor is verified against the rebuilt
            # schedule — a changed mixture fails loudly instead of silently
            # replaying or skipping samples.
            from galvatron_tpu.data import build_data_pipeline

            data_pipe = build_data_pipeline(
                cfg, ns.global_train_batch_size, seq, seed=ns.seed,
                start_batch=batch_offset,
                data_path=getattr(ns, "data_path", None),
                mixture=getattr(ns, "data_mixture", None),
                pack=cfg.pack_sequences,
                prefetch_depth=getattr(ns, "prefetch_depth", 0),
                put_fn=rt.shard_batch,
                resume_state=saved_data_state,
            )
            loader = iter(data_pipe)
        else:
            loader = build_dataloader(
                cfg, ns.global_train_batch_size, seq, seed=ns.seed, start_batch=batch_offset,
                data_path=getattr(ns, "data_path", None),
            )
    from galvatron_tpu.core.signals import GracefulExitHandler

    # per-iter host syncs (float(loss) every step) serialize dispatch with
    # device compute; only sync each iteration when the user asked for
    # per-iter observables (loss curves, per-iter metrics) or armed the
    # anomaly sentinel (which must classify the realized loss). Otherwise let
    # dispatch run free and time a window (TPU-idiomatic async training).
    sentinel = AnomalySentinel(getattr(ns, "anomaly_max_skips", 0))
    # span tracing syncs each iteration too: spans measure realized step
    # time, and an async span would just time dispatch (documented
    # observational overhead of tracing ON)
    # the sidecar is a per-iteration observable too: without the sync its
    # loss/iter_ms/mfu gauges would stay None (windowed profiling measures
    # nothing until the end of the run) — an operator who opened a metrics
    # port asked for live numbers. Process-0-gated like the server itself.
    obs_on = bool(getattr(ns, "obs_port", 0)) and jax.process_index() == 0
    # the hang watchdog bounds REALIZED step time: without a per-iter sync
    # the loop would run ahead of a stalled collective by the dispatch
    # depth and the deadline would measure dispatch, not the hang
    watchdog_on = bool(getattr(ns, "step_timeout_s", 0.0))
    # cost-model fidelity anchor: the plan's predicted step time — the plan
    # file's search_cost_ms (written by SearchEngine.save_result; it only
    # applies when training the searched batch size), else the total of the
    # price the trainer put on the plan itself (a flag plan, the defaults) —
    # read ONCE here so the per-iter drift gauge and the end-of-run report
    # share it.
    predicted_ms = None
    if plan_doc.get("global_bsz") == ns.global_train_batch_size:
        predicted_ms = plan_doc.get("search_cost_ms")
    if predicted_ms is None and plan_price and "error" not in plan_price:
        predicted_ms = plan_price["basis"]["total_ms"]
    # step-time-drift SLO (obs/slo.py): sustained (iter_ms - predicted)/
    # predicted past the flag's threshold raises a burn-rate breach — the
    # drift gauge is ROADMAP item 2's online re-plan signal. Drift needs
    # the realized per-iter time, so arming it joins sync_each below.
    train_slo = None
    slo_drift_on = (
        bool(getattr(ns, "slo_step_time_drift", 0.0))
        and jax.process_index() == 0
    )
    if slo_drift_on:
        from galvatron_tpu.obs.slo import SLOEngine, build_training_rules

        _slo_dir = ns.save or (
            os.path.dirname(metrics.path) or "." if metrics.path else None
        )
        train_slo = SLOEngine(
            rules=build_training_rules(ns),
            events_path=(os.path.join(_slo_dir, "slo_events.jsonl")
                         if _slo_dir else None),
            source="trainer",
        )
    # metrics.path, not ns.metrics_path: on a pod only process 0 owns the
    # JSONL sink — the other hosts must not pay a per-iter sync for a no-op
    # logger (their sentinel/tracing terms still apply to all hosts alike)
    sync_each = bool(
        ns.check_loss or metrics.path or sentinel.armed or tracer.enabled
        or obs_on or watchdog_on or slo_drift_on
    )
    prof = RuntimeProfiler(warmup_iters=1, windowed=not sync_each)
    # step accounting (obs/stepstats.py): tokens/s + achieved TFLOP/s + MFU
    # per train_iter record and for the sidecar/summary — derived, no
    # extra measurement
    from galvatron_tpu.obs.stepstats import StepStats

    stepstats = StepStats(
        cfg, ns.global_train_batch_size, seq, hp=hp,
        peak_tflops_override=getattr(ns, "peak_tflops", 0.0),
    )
    # jax.profiler trace of the training loop (op/kernel timeline viewable in
    # TensorBoard/Perfetto) — the tracing counterpart of the reference's
    # torch.profiler + CUDA-event instrumentation (SURVEY §5). Started after
    # the warmup iteration so compile/warmup spans don't drown the timeline.
    trace_dir = getattr(ns, "trace_dir", None)
    trace_started = False
    # step-bounded profiler window (--profile_steps A:B) — the precise
    # alternative to the whole-run --trace_dir capture; when both are given
    # the window wins (profiler traces cannot nest)
    pw = None
    if getattr(ns, "profile_steps", None):
        import tempfile

        from galvatron_tpu.obs.flight import ProfilerWindow, parse_profile_steps

        a, b = parse_profile_steps(ns.profile_steps)
        pw = ProfilerWindow(
            trace_dir or tempfile.mkdtemp(prefix="galvatron_profile_"), a, b
        )

    def _log_profile_window(rec):
        # where the window went and which steps it covers: without
        # --trace_dir it is a mkdtemp nothing else names
        if rec:
            metrics.log("profile_window", **rec)
    obs_server = train_obs = None
    if obs_on:
        # headless-run scrape endpoint: GET /metrics + /healthz on a sidecar
        # thread (process 0 only on a pod — one scrape target per job).
        # Started LAST in setup: everything after this point down to the
        # main try is pure arithmetic, so a setup failure cannot strand the
        # listener thread on its port
        from galvatron_tpu.obs.prom import ObsServer, TrainStats

        train_obs = TrainStats()
        obs_server = ObsServer(train_obs.render, port=ns.obs_port)
        if verbose:
            print(f"obs sidecar: http://127.0.0.1:{obs_server.port}/metrics")
    if train_obs is not None and aot_summ is not None:
        train_obs.compile_cache_hits = aot_summ["hits"]
        train_obs.compile_cache_misses = aot_summ["misses"]
        train_obs.startup_compile_ms = aot_summ["startup_compile_ms"]
    losses = []
    # consumed-samples bookkeeping: under rampup, replay the schedule from
    # step 0 so a resumed run sees exactly the sizes (and per-size stream
    # positions) an uninterrupted run would
    consumed = 0
    batches_at_size: dict = {}
    if rampup is not None:
        for _ in range(batch_offset):
            b = rampup(consumed)
            batches_at_size[b] = batches_at_size.get(b, 0) + 1
            consumed += b
    else:
        consumed = batch_offset * ns.global_train_batch_size
    consumed_at_start = consumed
    # samples actually COUNTED into manifests (increments with iters_run,
    # one fetched batch at a time — `consumed` runs one batch ahead inside
    # an iteration, and a crash between the two must not claim a sample
    # the stream never delivered)
    samples_done = consumed
    cur_bs = ns.global_train_batch_size
    keep_n = getattr(ns, "keep_last_n", 0)
    # due-based save schedule instead of a bare modulus: an anomaly-skipped
    # iteration `continue`s past the save point, and a modulus would then
    # silently double the checkpoint cadence exactly when the run is unstable
    next_save_at = (
        (batch_offset // ns.save_interval + 1) * ns.save_interval
        if ns.save and ns.save_interval else None
    )
    # `it` counts BATCHES globally (train_iters bounds batches consumed, so
    # a crash+resume run trains exactly the batches an uninterrupted run
    # would); the optimizer step lags by every anomaly skip, pre-crash skips
    # included — resuming at start_step instead would silently re-grant the
    # skipped iterations and re-log train_iter steps the first run already
    # emitted for different batches
    prior_skips = batch_offset - start_step
    iters_run = 0

    def _save_meta(batches=None, samples=None):
        # one schema for every save path (interval, exit, watchdog): the
        # stream cursor in BOTH domains plus the topology fingerprint. The
        # watchdog passes its snapshot's cursors; everyone else defaults to
        # the live ones.
        batches = batch_offset + iters_run if batches is None else batches
        samples = samples_done if samples is None else samples
        meta = {
            "batches_consumed": batches,
            "samples_consumed": samples,
            "global_bsz": int(ns.global_train_batch_size),
            "fingerprint": fingerprint,
        }
        if data_pipe is not None:
            # per-source mixture cursor: derived from the sample position, so
            # a resumed run can VERIFY it replays/skips nothing per source
            # (state() is pure in the position — watchdog-thread safe)
            meta["data_state"] = data_pipe.state(samples)
        return meta

    def _push_replica(st, step) -> bool:
        # RAM tier of the two-tier checkpoint: serialize the SAME portable
        # flat state the disk checkpoint would hold and hand it to a peer
        # host's in-memory store (ring neighbor). Best-effort by contract —
        # a dead peer degrades to disk-only, never fails the step.
        if peer_client is None:
            return False
        try:
            flat = portable_flat_state(st, rt)
            payload, header = peer_store_mod.serialize_state(
                flat, step, meta=_save_meta()
            )
            peer_client.put(payload, header)
            metrics.log("peer_replicate", step=step, nbytes=header["nbytes"])
            return True
        except Exception as e:  # noqa: BLE001 — replication is best-effort
            metrics.log(
                "peer_replicate_failed", step=step, error=str(e)[:300]
            )
            if verbose:
                print(f"peer replication failed at step {step}: {e!r}")
            return False

    # hang watchdog (--step_timeout_s; core/watchdog.py): armed around each
    # step, fires on a stalled collective — stacks + flight dump + a
    # best-effort emergency save of the last BOUND state (the holder is
    # invalidated across each donating dispatch), then exit EXIT_HANG so
    # the elastic supervisor restarts instead of burning the pod silently
    wd = holder = None
    if watchdog_on:
        import contextlib
        import sys as _sys

        from galvatron_tpu.core import watchdog as wdmod

        holder = wdmod.StateHolder()
        holder.set(state, step=start_step, batches=batch_offset, samples=consumed)

        def _on_hang(step_it):
            stacks = wdmod.dump_all_stacks()
            print(
                f"watchdog: step {step_it} exceeded --step_timeout_s "
                f"{ns.step_timeout_s}s; all-thread stacks:\n{stacks}",
                file=_sys.stderr, flush=True,
            )
            tracer.instant("watchdog_hang", step=step_it)
            snap_h = holder.snapshot()
            try:
                metrics.log(
                    "watchdog_hang", step=step_it,
                    save_possible=snap_h is not None,
                )
            except Exception:
                pass  # the JSONL sink must not block the forensics below
            fdir = getattr(ns, "flight_dir", None)
            if not fdir and getattr(ns, "trace_spans", None):
                fdir = os.path.dirname(os.path.abspath(ns.trace_spans))
            if not fdir:
                fdir = ns.save
            if fdir:
                from galvatron_tpu.obs.flight import dump_flight

                fpath = dump_flight(
                    fdir, tracer,
                    reason=f"watchdog hang at step {step_it} "
                           f"(deadline {ns.step_timeout_s}s)",
                    extra={"step": step_it, "stacks": stacks[-20000:]},
                )
                if fpath:
                    print(f"flight recorder → {fpath}", file=_sys.stderr, flush=True)
            if ns.save and snap_h is not None:
                # on a REAL stalled collective the held buffers may be
                # unreachable and this save may fail or block — best-effort
                # by contract; the last committed interval save is the floor
                try:
                    save_checkpoint_portable(
                        ns.save, snap_h["state"], snap_h["step"], rt,
                        keep_last_n=keep_n,
                        meta=_save_meta(
                            batches=snap_h["batches"], samples=snap_h["samples"]
                        ),
                    )
                    print(
                        f"watchdog emergency checkpoint step {snap_h['step']} "
                        f"→ {ns.save}", file=_sys.stderr, flush=True,
                    )
                except Exception as save_err:  # noqa: BLE001
                    print(f"watchdog emergency save failed: {save_err!r}",
                          file=_sys.stderr, flush=True)
            # HangWatchdog os._exits with EXIT_HANG when this returns

        wd = wdmod.HangWatchdog(
            ns.step_timeout_s, _on_hang,
            # proven-warm compile cache (startup AOT warmup hit, e.g. after
            # an elastic re-plan prewarm): the first step carries no XLA
            # compile, so it gets the NORMAL deadline — a real first-step
            # hang on a restarted child is detected in seconds, not 10x
            first_step_scale=1.0 if aot_warm_hint else None,
        )

        @contextlib.contextmanager
        def _watchdog_step(it):
            # a rampup batch-size transition recompiles the step: give it
            # the compile-length (warmup) deadline, or the transition of a
            # healthy run would be declared a hang
            wd.arm(
                it,
                warmup=rampup is not None and rampup(consumed) != cur_bs,
            )
            try:
                yield
            finally:
                wd.disarm()
            # normal exits only (incl. the anomaly-skip `continue`): rebind
            # the holder to the now-valid state. On an exception `state`
            # may still name donated buffers — the holder stays invalid and
            # the crash path's own exit save (bound post-rebind) takes over.
            holder.set(
                state,
                step=it + 1 - prior_skips - sentinel.total_skips,
                batches=batch_offset + iters_run,
                samples=samples_done,
            )
    else:
        import contextlib

        def _watchdog_step(it):  # noqa: ARG001 — uniform call site
            return contextlib.nullcontext()

    train_exc = None
    # preemption notice listener (core/preemption.py): the notice FILE
    # stands in for the cloud metadata server's eviction flag; SIGTERM
    # keeps riding the GracefulExitHandler branch below. Either way the
    # loop drains at the next step boundary and the exit path's replicated
    # save is the grace window's "expedited save".
    preempt_listener = PreemptionListener(
        None,
        notice_file=getattr(ns, "preempt_notice_file", None),
        grace_s=getattr(ns, "preempt_grace_s", 30.0) or 30.0,
        # poll every step: one os.path.exists is noise next to a dispatch,
        # and any throttle longer than a step can miss the notice entirely
        # on a fast (or simulated) mesh
        poll_interval_s=0.0,
    )
    # supervisor-side heartbeat (core/watchdog.py): one beat per step so a
    # child too wedged for its own in-process watchdog is still detectable
    from galvatron_tpu.core.watchdog import HEARTBEAT_ENV, beat_heartbeat

    hb_file = os.environ.get(HEARTBEAT_ENV)
    try:
        with GracefulExitHandler() as exit_handler:
            for it in range(batch_offset, ns.train_iters):
                if hb_file:
                    beat_heartbeat(hb_file, it)
                if exit_handler.signaled is not None:
                    if verbose:
                        print(f"signal {exit_handler.signaled} received; stopping at iter {it}")
                    break
                notice = preempt_listener.check()
                if notice is not None:
                    if verbose:
                        print(
                            f"preemption notice ({notice}) received; draining "
                            f"at iter {it} (grace "
                            f"{preempt_listener.grace_s:.0f}s)"
                        )
                    metrics.log("preempt_notice", step=it, reason=notice,
                                grace_s=float(preempt_listener.grace_s))
                    tracer.instant("preempt_notice", step=it, reason=notice)
                    break
                # start after the warmup/compile iteration so the timeline
                # shows steady-state steps, not one giant compile span (a
                # --profile_steps window supersedes the whole-run capture:
                # profiler traces cannot nest)
                if trace_dir and pw is None and not trace_started and iters_run >= 1:
                    jax.profiler.start_trace(trace_dir)
                    trace_started = True
                    tracer.profiling = True
                if pw is not None:
                    # stop is checked at the loop TOP (previous iteration's
                    # index) so an anomaly-skip `continue` cannot carry the
                    # window past its STOP boundary; the run-end close lives
                    # in the finally below
                    _log_profile_window(pw.maybe_stop(it - 1, verbose=verbose))
                    pw.maybe_start(it)
                with _watchdog_step(it), tracer.span("step", step=it):
                    if rampup is not None:
                        bs = rampup(consumed)
                        if bs != cur_bs or it == batch_offset:
                            cur_bs = bs
                            loader = build_dataloader(
                                cfg, bs, seq, seed=ns.seed + bs,
                                start_batch=batches_at_size.get(bs, 0),
                                data_path=getattr(ns, "data_path", None),
                            )
                        batches_at_size[bs] = batches_at_size.get(bs, 0) + 1
                        consumed += bs
                    else:
                        consumed += cur_bs
                    with tracer.span("data", step=it):
                        # the data pipeline already device-put the batch (on
                        # its prefetch thread when armed) — the span measures
                        # a dequeue, which is the point of the prefetcher
                        batch = (
                            next(loader)
                            if data_pipe is not None
                            else rt.shard_batch(next(loader))
                        )
                    pipe_meta = data_pipe.last_meta if data_pipe is not None else {}
                    # counted only once the batch is actually consumed: iters_run
                    # feeds the batches_consumed manifest record, and a crash in
                    # the fetch itself must not make resume skip a real batch
                    iters_run += 1
                    samples_done += cur_bs
                    # chaos hooks (core/faults.py): a simulated preemption
                    # SIGTERM mid-step, and a simulated stalled collective
                    # (sleep) that the armed watchdog must convert into a
                    # flight dump + emergency save + hang-coded exit. Both
                    # sit BEFORE the donating dispatch: the fetched batch is
                    # counted but untrained, exactly a real preemption's
                    # window, and the watchdog's holder is still valid.
                    faults.maybe_preempt(it)
                    # harsher chaos tiers: kill_host_mid_step SIGKILLs this
                    # process with no grace at all (recovery must come from
                    # the peer replica or the last committed checkpoint);
                    # preempt_with_grace writes the NOTICE file a real cloud
                    # eviction would, exercising the drain path above
                    faults.maybe_kill_host(it)
                    faults.maybe_preempt_notice(
                        it, getattr(ns, "preempt_notice_file", None)
                    )
                    faults.maybe_hang(it)
                    # rollback copy — the train step donates its input buffers,
                    # so a discarded update is unrecoverable without it (None
                    # when the sentinel is disarmed: no memory cost)
                    snap = sentinel.snapshot(state)
                    prof.begin_iter()
                    if holder is not None:
                        # the dispatch below donates `state`: an emergency
                        # save between here and the post-step rebind would
                        # read freed buffers
                        holder.invalidate()
                    with tracer.span("fwd_bwd", step=it):
                        new_state, loss = rt.train_step(state, batch)
                    # rebind NOW: the old buffers were donated into train_step,
                    # so `state` must never name them again — an XLA error
                    # surfacing at float(loss) below would otherwise hand the
                    # emergency-save path deleted arrays
                    state = new_state
                    with tracer.span("sync", step=it) as sync_sp:
                        # always hand end_iter the loss: per-iter mode syncs each
                        # step (sync_each implies that's wanted); windowed mode syncs
                        # ONCE, to close the warmup — without it the window would
                        # open while warmup compute is still in flight and overstate
                        # avg iter time
                        prof.end_iter(loss)
                        loss_val = float(loss) if sync_each else None  # gta: disable=GTL101 — deliberate sync, gated by sync_each (off unless per-iter observables, span tracing, or the anomaly sentinel need the realized loss)
                        sync_sp.sync(loss)
                        # a dropless top-k MoE step leaves its auxiliary loss and
                        # expert load in the state: fetched with the loss, after
                        # the same sync, never by a sync of their own
                        moe_vals = (
                            {k: float(v) for k, v in state["moe_stats"].items()}
                            if sync_each and "moe_stats" in state else {}
                        )
                    # injection sits OUTSIDE the armed gate: chaos jobs force a
                    # NaN observation with or without the sentinel (a disarmed
                    # run must drive the stringified-JSONL divergence path too)
                    if loss_val is not None and faults.force_nan(it):
                        loss_val = float("nan")
                    if sentinel.armed:
                        verdict = sentinel.observe(loss_val, it)
                        if verdict != "ok":
                            # discard the poisoned update: drop the batch, roll
                            # the state back to the pre-step snapshot
                            state = snap
                            if verdict == "abort":
                                raise AnomalyAbort(
                                    it, sentinel.consecutive, sentinel.max_skips
                                )
                            # loss serialized as a string: bare NaN/Infinity is
                            # not valid JSON and would break strict JSONL readers
                            metrics.log(
                                "anomaly_skip", step=it, loss=str(loss_val),
                                consecutive=sentinel.consecutive,
                            )
                            tracer.instant(
                                "anomaly_skip", step=it, loss=str(loss_val),
                                consecutive=sentinel.consecutive,
                            )
                            if train_obs is not None:
                                train_obs.anomaly_skips = sentinel.total_skips
                            if verbose:
                                print(
                                    f"iter {it}: non-finite loss; update skipped "
                                    f"({sentinel.consecutive}/{sentinel.max_skips})"
                                )
                            continue
                    if sync_each:
                        losses.append(loss_val)
                        if verbose:
                            print(f"iter {it}: loss {loss_val:.4f}")
                    iter_ms = prof.iter_times_ms[-1] if prof.iter_times_ms else None
                    stat = (
                        stepstats.per_iter(
                            iter_ms, cur_bs,
                            nonpad_tokens=pipe_meta.get("nonpad_tokens"),
                        )
                        if metrics.path or train_obs is not None
                        else {}
                    )
                    # step-time drift vs the plan's prediction: the signed
                    # ratio the re-planner (ROADMAP item 2) and the drift
                    # SLO both consume
                    drift = (
                        (iter_ms - predicted_ms) / predicted_ms
                        if predicted_ms and iter_ms is not None
                        else None
                    )
                    if metrics.path:
                        metrics.log(
                            "train_iter", schema=SCHEMA_VERSION, step=it,
                            # a disarmed run can still diverge: bare NaN/Infinity
                            # is not valid JSON (same reason anomaly_skip
                            # stringifies), so non-finite losses log as strings
                            loss=(
                                loss_val
                                if loss_val is None or math.isfinite(loss_val)
                                else str(loss_val)
                            ),
                            batch_size=cur_bs,
                            iter_ms=iter_ms,
                            **moe_vals,
                            **stat,
                            **({"step_time_drift": round(drift, 4)}
                               if drift is not None else {}),
                        )
                    if train_slo is not None and drift is not None:
                        train_slo.observe_drift("step_time_drift", drift,
                                                step=it)
                    if train_obs is not None:
                        train_obs.iterations += 1
                        if loss_val is not None:
                            train_obs.last_loss = loss_val
                        if iter_ms is not None:
                            train_obs.last_iter_ms = iter_ms
                            train_obs.predicted_iter_ms = predicted_ms
                            train_obs.step_time_drift = drift
                            train_obs.tokens_per_s = stat.get("tokens_per_s")
                            train_obs.tflops_per_device = stat.get("tflops_per_device")
                            train_obs.mfu = stat.get("mfu")
                            train_obs.hfu = stat.get("hfu")
                            train_obs.packing_efficiency = stat.get(
                                "packing_efficiency"
                            )
                    if next_save_at is not None and (it + 1) >= next_save_at:
                        # dir name = the state's actual optimizer step: skipped
                        # iterations (this run's AND pre-crash ones) advanced
                        # `it` but not the state, and the exit-save dedup
                        # compares latest_step against it
                        actual_step = it + 1 - prior_skips - sentinel.total_skips
                        if wd is not None:
                            # the save legitimately outlasts a step deadline
                            # (large state, slow GCS); killed mid-commit it
                            # would deterministically repeat at this step
                            # until the restart budget ran out — same
                            # stand-down the exit save gets
                            wd.disarm()
                        # RAM tier first: the replica must exist BEFORE the
                        # disk commit so a storage outage (or a kill during
                        # the commit) still leaves this step recoverable
                        replicated = _push_replica(state, actual_step)
                        try:
                            save_checkpoint_portable(
                                ns.save, state, actual_step, rt,
                                keep_last_n=keep_n,
                                meta=_save_meta(),
                            )
                        except OSError as save_err:
                            if not replicated:
                                raise
                            # storage down but the peer replica landed: the
                            # run keeps training on the RAM tier alone and
                            # retries disk at the next due save / exit save
                            metrics.log(
                                "save_degraded_to_peer", step=actual_step,
                                error=str(save_err)[:300],
                            )
                            print(
                                f"warning: disk save at step {actual_step} "
                                f"failed ({save_err}); continuing on peer "
                                f"replica", flush=True,
                            )
                        else:
                            if train_obs is not None:
                                train_obs.checkpoints_saved += 1
                            if verbose:
                                print(f"saved step {actual_step} → {ns.save}")
                        next_save_at = (
                            (it + 1) // ns.save_interval + 1
                        ) * ns.save_interval
        prof.finish(loss if iters_run else None)
    except BaseException as e:
        train_exc = e
        raise
    finally:
        # the watchdog stands down FIRST: the exit checkpoint below can
        # legitimately outlast --step_timeout_s, and an armed deadline
        # firing mid-commit would turn a clean exit into a hang-coded kill
        if wd is not None:
            wd.close()
        # the prefetch thread stands down SECOND, on every exit path — a
        # producer blocked on its bounded queue must not sit on buffers (or
        # keep touching the corpus) while the exit checkpoint commits; the
        # end-of-run mixture/packing summary lands in the JSONL first
        if data_pipe is not None:
            try:
                metrics.log("data_pipeline", **data_pipe.summary(samples_done))
            except Exception:
                pass  # observability must not block the shutdown chain
            data_pipe.close()
        # always close the trace — an exception mid-loop must not lose the
        # captured data or wedge the process-wide profiler state. Guarded:
        # a stop_trace failure (e.g. flushing to broken storage) must not
        # rob the crash path of its emergency checkpoint below, nor mask
        # the original training exception
        if pw is not None:
            if pw.active:
                pw.last_step = batch_offset + iters_run - 1
            _log_profile_window(pw.close(verbose=verbose))
        if trace_started:
            tracer.profiling = False
            try:
                jax.profiler.stop_trace()
                if verbose:
                    print(f"jax.profiler trace → {trace_dir}")
            except Exception as trace_err:
                print(f"failed to close jax.profiler trace: {trace_err!r}")
        # checkpoint on exit — normal completion, signal-stop (the
        # reference's dist_signal_handler checkpoint-then-exit pattern,
        # there unused), unhandled exception, or anomaly abort: every exit
        # mode lands a committed, resumable checkpoint
        try:
            # the save itself is collective on a multi-controller pod
            # (orbax write + commit barrier), so it is only safe when every
            # process reaches this path with the same verdict: normal
            # completion, signal-stop (preemption signals all hosts), and
            # AnomalyAbort (decided on the globally-reduced loss) are
            # replicated; an arbitrary exception may be host-local (one
            # host's dataloader shard failing), and entering the collective
            # save alone would hang inside this finally with the traceback
            # never printed. There the exception surfaces instead.
            replicated_exit = (
                train_exc is None
                or isinstance(train_exc, AnomalyAbort)
                or jax.process_count() == 1
            )
            if ns.save and not replicated_exit:
                print(
                    "skipping exit checkpoint: exception on a multi-host run "
                    "may be host-local and the save is collective"
                )
            if ns.save and replicated_exit:
                final_step = int(np.asarray(state["step"]))
                batches_now = batch_offset + iters_run
                # dedup on step AND stream position: trailing anomaly-skipped
                # batches advance batches_consumed without advancing the
                # optimizer step, and skipping the re-save would leave the
                # committed meta stale — resume would then replay the skipped
                # batches (deterministically poisoned data could loop the
                # skip budget on every restart instead of progressing)
                already_committed = latest_step(ns.save) == final_step
                if already_committed:
                    m = read_manifest(step_path(ns.save, final_step))
                    meta = m.get("meta") if m else None
                    already_committed = isinstance(meta, dict) and int(
                        meta.get("batches_consumed", -1)
                    ) == batches_now
                if not already_committed:
                    # push the RAM tier before the disk commit: if the disk
                    # exit save raises (storage still out during a drain),
                    # the peer replica carries the final step into the next
                    # incarnation
                    _push_replica(state, final_step)
                    save_checkpoint_portable(
                        ns.save, state, final_step, rt, keep_last_n=keep_n,
                        meta=_save_meta(),
                    )
                if train_exc is not None:
                    # the event fires even when the write was skipped (e.g.
                    # an anomaly abort whose last-good state an interval
                    # save already committed) — the operator signal is the
                    # exceptional exit, not the redundant write
                    metrics.log(
                        "emergency_save", step=final_step,
                        already_committed=already_committed,
                        reason=f"{type(train_exc).__name__}: "
                               f"{str(train_exc)[:200]}",
                    )
                    print(f"emergency checkpoint step {final_step} → {ns.save}")
                elif verbose and not already_committed:
                    print(f"saved step {final_step} → {ns.save}")
        except Exception as save_err:
            # best-effort: a failed exit save must not mask the original error
            print(f"exit checkpoint failed: {save_err!r}")
        finally:
            # crash runs flush their JSONL tail too
            metrics.close()
        # observability teardown: flight dump on exceptional exits, span
        # export, sidecar shutdown — all best-effort, never masking the
        # original exception (the emergency checkpoint above already ran)
        try:
            _export_obs_artifacts(
                ns, tracer, train_exc,
                extra={"iter": batch_offset + iters_run}, verbose=verbose,
            )
        finally:
            if obs_server is not None:
                obs_server.close()
            if train_slo is not None:
                train_slo.close()
            if tracer_owned:
                # this run turned tracing on; turn it off (and drop the
                # ring) so spans cannot leak into a later run in-process
                tracer.disable()
                tracer.clear()
    # throughput from actual samples processed (rampup runs at smaller sizes)
    avg_bs = (consumed - consumed_at_start) / iters_run if iters_run else 0
    # cost-model fidelity: predicted-vs-measured iteration time when training
    # the searched strategy at its searched batch size (SURVEY §6);
    # predicted_ms was resolved once before the loop — the per-iter drift
    # gauge/SLO and this report read the same anchor
    report = (
        prof.report(avg_bs, seq, predicted_ms=predicted_ms, step_stats=stepstats)
        if prof.iter_times_ms
        else ""
    )
    if verbose and report:
        print(report)
    return {
        "losses": losses,
        "iter_ms": prof.avg_iter_ms if prof.iter_times_ms else None,
        "state": state,
        # the runtime the steps ran on (chip_smoke.py reads its compiled
        # step program and mesh)
        "runtime": rt,
        # the elastic child maps this to EXIT_PREEMPTED: a signal-stop run
        # completed nothing abnormal, but the supervisor must restart it.
        # A notice-file drain (no signal delivered) reports its reason in
        # the same slot — the supervisor treats both as a preemption.
        "signaled": (
            exit_handler.signaled
            if exit_handler.signaled is not None
            else preempt_listener.reason
        ),
    }
