"""Unified CLI: python -m galvatron_tpu.cli <mode> [--model_size ...] ...

Modes mirror the reference's per-model entry scripts (reference L7,
models/<name>/{train_dist,search_dist,profiler}.py + profile_hardware):

  train             hybrid-parallel training (train_dist equivalent)
  run-elastic       train under the preemption-aware elastic supervisor
                    (core/elastic.py): child exits are classified
                    (completed / preempted-save / anomaly / watchdog hang /
                    crash) into restart-with-jittered-backoff or give-up
                    decisions, a topology change (pod shrink) triggers an
                    automatic re-search + portable resume under the new
                    plan, and --step_timeout_s arms a hang watchdog;
                    --peer_replicate N keeps an in-memory peer replica of
                    every interval save (core/peer_store.py) so a host
                    killed without grace restores from RAM, a preemption
                    NOTICE (--preempt_notice_file / SIGTERM) drains within
                    --preempt_grace_s, a shrink continues at degraded DP
                    width down to --degraded_min_dp, and
                    --heartbeat_timeout_s kills+restarts a child whose
                    per-step heartbeat goes stale
  peer-store        run one in-memory peer checkpoint store daemon
                    (core/peer_store.py serve; the elastic supervisor
                    spawns these itself under --peer_replicate)
  search            parallelism optimization → galvatron_config JSON
  profile           model computation/memory profiling → JSON
  profile-hardware  ICI bandwidth + overlap sweep → JSON
  check-plan        static plan validation (analysis/plan_check.py): reject a
                    bad strategy JSON in milliseconds with stable GTA…
                    diagnostics — no device, no XLA compile; CI runs it over
                    configs/
  warmup            AOT-compile every registered program of the given plan
                    JSON(s) from abstract shapes into the persistent
                    compile-artifact cache (galvatron_tpu/aot): a later
                    trainer start / elastic restart / serving cold-start on
                    the same plan pays a cache lookup instead of XLA
                    compiles; per-program lower_ms/compile_ms +
                    memory_analysis peak-buffer stats land in a JSONL
                    report, with the comm footprint beside it
  audit-comm        static HLO collective audit (analysis/comm_audit.py):
                    AOT-lower (never compile/execute) every program of the
                    given plan JSON(s) on a forced CPU world, extract the
                    collective footprint from the StableHLO text, gate the
                    cost model's per-term comm volumes against it
                    (predicted_over_lowered, GTC001) and lint for
                    partitioner-inserted resharding the plan never asked
                    for (GTC003/010/011/012); CI runs it over configs/
  trace-export      convert a crash flight-recorder dump (flight_<ts>.json)
                    or raw span records into Chrome trace-event JSON loadable
                    in Perfetto / chrome://tracing (obs/tracing.py);
                    --merge DIR fuses every dump under a directory into ONE
                    clock-aligned multi-process timeline (obs/correlate.py)
  generate          KV-cache text generation from a checkpoint (or random init)
  serve             REST generation server (text_generation_server equivalent);
                    continuous-batching engine by default (--num_slots,
                    --prefill_chunk, --request_ttl_s; --num_slots 0 = legacy
                    serialized path)
  serve-fleet       resilient multi-replica router (serving/fleet.py):
                    fronts N `serve` replica subprocesses with health-driven
                    least-loaded dispatch, mid-flight failover inside the
                    end-to-end deadline (--retry_budget), supervised replica
                    restarts under the shared core/restart_policy.py table,
                    and rolling drain (POST /drain?rolling=1) for
                    zero-downtime deploys
  export-hf         trainer checkpoint → HuggingFace-format checkpoint

The per-model modules (galvatron_tpu.models.<family>) re-export these with
family defaults, mirroring the reference's directory-per-model layout.
"""

from __future__ import annotations

import json
import os
import sys
from typing import List, Optional


def main(argv: Optional[List[str]] = None, model_default: Optional[str] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    mode, rest = argv[0], argv[1:]

    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args

    if mode == "train":
        from galvatron_tpu.core.trainer import train

        ns = initialize_galvatron("train", rest, model_default)
        train(ns)
        return 0

    if mode == "run-elastic":
        # the supervisor parses the SAME train flags (plus --max_restarts /
        # --step_timeout_s / --replan_*) and forwards them verbatim to each
        # child, so a train command line becomes elastic by renaming the mode
        from galvatron_tpu.core.elastic import run_elastic

        return run_elastic(rest, model_default)

    if mode == "peer-store":
        # standalone daemon entry (multi-host deployments run one per host;
        # the sim supervisor spawns its own): `cli peer-store serve ...`
        from galvatron_tpu.core.peer_store import main as peer_store_main

        return peer_store_main(rest)

    if mode == "search":
        ns = initialize_galvatron("search", rest, model_default)
        from galvatron_tpu.core.arguments import resolve_execution_config

        # profile the exact execution config the training run will use
        # (kernel + dtype) — otherwise predicted-vs-measured fidelity is
        # broken by construction
        cfg = resolve_execution_config(model_config_from_args(ns), ns)
        from galvatron_tpu.models.mixers import has_mixer_layers
        from galvatron_tpu.profiling.model import profile_model
        from galvatron_tpu.search.cost_model import ProfiledHardware
        from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace
        from galvatron_tpu.utils.config_utils import (
            load_profiled_hardware,
            load_profiled_model,
        )

        if bool(ns.time_profile_path) != bool(ns.memory_profile_path):
            print(
                "error: --time_profile_path and --memory_profile_path must be "
                "given together (got only one; refusing to silently re-profile)"
            )
            return 2
        if ns.time_profile_path and ns.memory_profile_path:
            costs = load_profiled_model(ns.time_profile_path, ns.memory_profile_path)
        elif ns.analytic_costs or ns.check_cost_model or has_mixer_layers(cfg):
            from galvatron_tpu.search.theoretical import analytic_model_costs

            if has_mixer_layers(cfg):
                print("hybrid stack: the in-process profiler measures one layer kind; "
                      "each kind is priced analytically")
            print("using analytic (unprofiled) model costs")
            costs = analytic_model_costs(cfg)
        else:
            print("no profiled model data given; profiling in-process (measured on this host)")
            costs = profile_model(cfg, bsz=ns.min_bsz)
        hw = (
            load_profiled_hardware(ns.hardware_profile_path)
            if ns.hardware_profile_path
            else ProfiledHardware()
        )
        sspace = SearchSpace(
            world_size=ns.num_devices,
            max_tp=ns.max_tp_deg,
            allow_sp=not ns.disable_sp,
            allow_ckpt=not ns.disable_ckpt,
            allow_zero2=not ns.disable_sdp,
            allow_zero3=not ns.disable_sdp,
            allow_strided=not ns.disable_tp_consec,
            allow_cp=bool(ns.enable_cp),
            allow_ep=bool(ns.enable_ep),
            max_ep=ns.max_ep_deg,
            moe_experts=cfg.moe_experts,
            max_vpp=ns.max_vpp_deg,
        )
        from galvatron_tpu.search.search_engine import apply_search_space

        apply_search_space(sspace, ns.search_space)
        eng = SearchEngine(
            costs, hw, num_layers=cfg.total_layers, space=sspace,
            memory_budget_mb=ns.memory_constraint_gb * 1024.0,
            mixed_precision=ns.mixed_precision,
            section_pipeline=bool(cfg.swin_depths),
            model_config=cfg, model_name=ns.model_size,
        )
        if ns.check_cost_model:
            bsz = ns.settle_bsz if ns.settle_bsz > 0 else ns.min_bsz
            print(eng.check_cost_model(bsz, chunks=1, pp=1))
            from galvatron_tpu.search.theoretical import report as theo_report
            from galvatron_tpu.core.strategy import LayerStrategy as _LS

            print(theo_report(cfg, _LS(), ns.num_devices).lines())
            return 0
        if ns.settle_bsz > 0:
            bszs = [ns.settle_bsz]
        else:
            if ns.bsz_scale < 2:
                print(f"error: --bsz_scale must be >= 2, got {ns.bsz_scale}")
                return 2
            rec = 0
            if ns.recommend_min_bsz:
                # PRUNE the grid (drop points below the recommendation) —
                # shifting its anchor would skip points ABOVE it too
                rec = min(eng.recommend_min_bsz(), ns.max_bsz)
                if rec > ns.min_bsz:
                    print(f"recommend_min_bsz: pruning sweep below {rec}")
            bszs, b = [], ns.min_bsz
            while b <= ns.max_bsz:
                if b >= rec:
                    bszs.append(b)
                b *= ns.bsz_scale
            if not bszs:
                bszs = [ns.max_bsz]  # rec sat between the last grid point and the cap
        if ns.validate_top_k > 0:
            # one sweep serves both the saved result and the validation
            # candidates (search_topk ranks by predicted throughput, same
            # criterion search() maximizes)
            cands = eng.search_topk(
                bszs, k=ns.validate_top_k, max_chunks=ns.max_chunks, verbose=True
            )
            res = cands[0] if cands else None
        else:
            cands = None
            res = eng.search(bszs, max_chunks=ns.max_chunks, verbose=True)
        if res is None:
            print("no feasible strategy under the memory budget")
            return 1
        if cands:
            print(
                f"Max throughput = {res.throughput_samples_per_s:.2f} samples/s "
                f"(bsz {res.global_bsz})"
            )
            _validate_search(cands, cfg, ns)
        if ns.report_homogeneity_gap and res.config.pp > 1 and res.config.vpp == 1:
            g = eng.homogeneity_gap(
                res.config.pp, res.global_bsz, res.config.chunks,
                res.config.pipeline_type,
            )
            if g is None:
                print("homogeneity gap: n/a (not defined for this "
                      "shape/schedule, or the per-stage DP is infeasible)")
            else:
                print(
                    f"homogeneity gap: restricted {g['restricted_ms']:.1f} ms vs "
                    f"unrestricted per-stage {g['unrestricted_ms']:.1f} ms "
                    f"(delta {g['delta_pct']:+.3f}%)"
                )
                res.details["homogeneity_gap_pct"] = g["delta_pct"]
        elif ns.report_homogeneity_gap and res.config.vpp > 1:
            print("homogeneity gap: n/a for interleaved (vpp>1) schedules")
        out = ns.output_config_path or f"galvatron_config_{ns.model_size}_{ns.num_devices}dev.json"
        eng.save_result(res, out)
        print(f"saved searched strategy → {out}")
        return 0

    if mode == "profile":
        ns = initialize_galvatron("profile", rest, model_default)
        cfg = model_config_from_args(ns)
        # same attention + dtype resolution as the trainer: profile the
        # program the training run will actually use (flash on accelerators —
        # the xla path materializes (heads, S, S) fp32 probs and OOMs at real
        # shapes; fp32 compute would overstate bf16 layer times ~2x)
        from galvatron_tpu.core.arguments import resolve_execution_config

        cfg = resolve_execution_config(cfg, ns)
        from galvatron_tpu.profiling.model import profile_model

        prefix = ns.output_prefix or f"profile_{ns.model_size}"
        if bool(ns.layernum_min) != bool(ns.layernum_max):
            print("error: --layernum_min and --layernum_max must be given "
                  "together (0,0 = adaptive basis)")
            return 2
        costs = profile_model(
            cfg, bsz=ns.profile_batch_size,
            layernums=(ns.layernum_min, ns.layernum_max) if ns.layernum_max else None,
            measure_time=ns.profile_type in ("computation", "both"),
        )
        from galvatron_tpu.utils.config_utils import save_profiled_model

        comp = f"{prefix}_computation.json" if ns.profile_type in ("computation", "both") else None
        mem = f"{prefix}_memory.json" if ns.profile_type in ("memory", "both") else None
        save_profiled_model(costs, comp, mem)
        print(f"saved → {', '.join(p for p in (comp, mem) if p)}")
        return 0

    if mode == "profile-hardware":
        ns = initialize_galvatron("profile_hardware", rest, model_default)
        from galvatron_tpu.profiling.hardware import profile_hardware

        hw = profile_hardware(
            msg_mb=ns.profile_size_mb, out_path=ns.hardware_output_path,
            num_slices=ns.num_slices or None,
        )
        print(f"allreduce: {hw.allreduce_bw}")
        print(f"p2p: {hw.p2p_bw}")
        print(f"overlap_coe: {hw.overlap_coe}")
        print(f"saved → {ns.hardware_output_path}")
        return 0

    if mode == "export-hf":
        ns = initialize_galvatron("export_hf", rest, model_default)
        if not ns.output_dir:
            print("error: export-hf needs --output_dir")
            return 2
        cfg = model_config_from_args(ns)
        from galvatron_tpu.models.convert import to_hf_gpt2, to_hf_llama

        if cfg.act_fn == "relu":
            print(
                "error: export-hf does not support the OPT family — the +2 "
                "position offset dropped at import cannot be reconstructed "
                "for HF's padded-position rows"
            )
            return 2
        if not cfg.causal or cfg.objective != "clm" or cfg.image_size:
            print(
                "error: export-hf exports causal LM decoders only "
                "(encoder/vision families have no HF causal-LM counterpart)"
            )
            return 2
        # architecture by config shape: GPT-2-style (learned positions +
        # biases + gelu) exports as GPT2LMHeadModel, else LlamaForCausalLM
        gpt2_style = (
            cfg.pos_embed == "learned" and cfg.use_bias and cfg.act_fn == "gelu"
        )
        params = _load_or_init_params(ns, cfg)  # validates shape vs config
        sd = (to_hf_gpt2 if gpt2_style else to_hf_llama)(params, cfg)
        import numpy as _np

        os.makedirs(ns.output_dir, exist_ok=True)
        try:
            import torch

            if gpt2_style:
                from transformers import GPT2Config, GPT2LMHeadModel

                hf_cfg = GPT2Config(
                    vocab_size=cfg.vocab_size, n_embd=cfg.hidden_size,
                    n_layer=cfg.num_layers, n_head=cfg.num_heads,
                    n_inner=cfg.ffn, n_positions=cfg.max_seq_len,
                    layer_norm_epsilon=cfg.norm_eps,
                )
                model = GPT2LMHeadModel(hf_cfg)
            else:
                from transformers import LlamaConfig, LlamaForCausalLM

                hf_cfg = LlamaConfig(
                    vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
                    intermediate_size=cfg.ffn, num_hidden_layers=cfg.num_layers,
                    num_attention_heads=cfg.num_heads, num_key_value_heads=cfg.kv_heads,
                    max_position_embeddings=cfg.max_seq_len,
                    rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
                    tie_word_embeddings=cfg.tie_word_embeddings,
                )
                model = LlamaForCausalLM(hf_cfg)
            model.load_state_dict({k: torch.tensor(v) for k, v in sd.items()})
            model.save_pretrained(ns.output_dir)
            print(f"exported HF checkpoint → {ns.output_dir}")
        except ImportError:
            _np.savez(os.path.join(ns.output_dir, "state_dict.npz"), **sd)
            print(f"transformers unavailable; wrote raw state dict → "
                  f"{ns.output_dir}/state_dict.npz")
        return 0

    if mode == "check-plan":
        ns = initialize_galvatron("check_plan", rest, model_default)
        return _check_plan_mode(ns)

    if mode == "warmup":
        ns = initialize_galvatron("warmup", rest, model_default)
        return _warmup_mode(ns)

    if mode == "audit-comm":
        ns = initialize_galvatron("audit_comm", rest, model_default)
        return _audit_comm_mode(ns)

    if mode == "trace-export":
        ns = initialize_galvatron("trace_export", rest, model_default)
        return _trace_export_mode(ns)

    if mode == "serve-fleet":
        # the multi-replica router (serving/fleet.py): parses the serve
        # flags plus the fleet group, forwards everything non-fleet
        # verbatim to N replica `cli serve` subprocesses
        from galvatron_tpu.serving.fleet import serve_fleet_main

        ns = initialize_galvatron("serve_fleet", rest, model_default)
        return serve_fleet_main(ns, rest)

    if mode in ("generate", "serve"):
        import jax

        from galvatron_tpu.models.tokenizer import build_tokenizer

        ns = initialize_galvatron(mode, rest, model_default)
        tok = build_tokenizer(ns.tokenizer)
        if getattr(ns, "load_hf", None):
            if getattr(ns, "load", None):
                raise ValueError(
                    "--load and --load_hf are mutually exclusive here: pick "
                    "the fine-tuned trainer checkpoint (--load) or the raw "
                    "pretrained HF weights (--load_hf)"
                )
            from galvatron_tpu.models.convert import load_hf_llama

            params, cfg = load_hf_llama(ns.load_hf)
            if tok.vocab_size > cfg.vocab_size:
                raise ValueError(
                    f"tokenizer vocab {tok.vocab_size} exceeds the pretrained "
                    f"embedding {cfg.vocab_size} — ids past the table would "
                    "silently clamp; use the checkpoint's own tokenizer"
                )
        else:
            cfg = model_config_from_args(ns)
            if tok.vocab_size > cfg.vocab_size:
                cfg = cfg.replace(vocab_size=tok.vocab_size)
            params = _load_or_init_params(ns, cfg)
        # an EXPLICIT --attn_impl reaches the executed config ('auto' keeps
        # the model's own default — serving was designed on the xla path and
        # must not silently switch kernels by backend); the plan-free
        # `cli warmup` serving sweep applies the identical rule so the warmed
        # program keys are the keys this engine consults
        if getattr(ns, "attn_impl", "auto") != "auto":
            cfg = cfg.replace(attn_impl=ns.attn_impl)
        if mode == "generate":
            from galvatron_tpu.models import generation

            prompts = ns.prompt or ["Hello"]
            outs = generation.generate_np(
                params, cfg, [tok.encode(p) for p in prompts],
                max_new_tokens=ns.max_new_tokens, temperature=ns.temperature,
                top_k=ns.top_k, top_p=ns.top_p,
                eos_id=tok.eos_id if tok.eos_id is not None else -1,
                pad_id=tok.pad_id if tok.pad_id is not None else 0,
                key=jax.random.key(ns.seed),
            )
            for p, o in zip(prompts, outs):
                print(json.dumps({"prompt": p, "completion": tok.decode(o[len(tok.encode(p)):])}))
            return 0
        from galvatron_tpu.server import GenerationService, run_server

        # chaos hooks (engine_crash_at_iter / prefill_fail_at /
        # slow_decode_ms / client_stall): no-ops unless GALVATRON_FAULTS is
        # set — same contract as the trainer
        from galvatron_tpu.core import faults as _faults

        _faults.init_from_env()
        engine = None
        if getattr(ns, "flight_dir", None):
            # --flight_dir alone arms span tracing (same contract as the
            # trainer): a crash flight dump with an empty ring is a no-op
            from galvatron_tpu.obs.tracing import tracer as _tracer

            if not _tracer.enabled:
                _tracer.enable()
        if ns.num_slots > 0:
            from galvatron_tpu.serving import Engine

            engine = Engine(
                params, cfg,
                num_slots=ns.num_slots,
                prefill_chunk=ns.prefill_chunk,
                max_queue=ns.max_queue,
                request_ttl_s=ns.request_ttl_s if ns.request_ttl_s > 0 else None,
                eos_id=tok.eos_id if tok.eos_id is not None else -1,
                pad_id=tok.pad_id if tok.pad_id is not None else 0,
                seed=ns.seed,
                deadline_policy=ns.deadline_policy,
                max_engine_restarts=ns.max_engine_restarts,
                drain_timeout_s=ns.drain_timeout_s,
                flight_dir=ns.flight_dir,
                kv_block_size=ns.kv_block_size,
                kv_num_blocks=ns.kv_num_blocks,
                prefix_cache=ns.prefix_cache == "on",
                serve_quant=ns.serve_quant,
                quant_drift_max=ns.quant_drift_max,
                spec_decode_k=ns.spec_decode_k,
                spec_drafter=ns.spec_drafter,
            )
            if engine.quant_parity is not None:
                qp = engine.quant_parity
                print(
                    f"serving quant: int8 per-channel, max-abs logit drift "
                    f"{qp['max_abs_logit_drift']} (bound {qp['drift_bound']}), "
                    f"greedy agreement {qp['greedy_agree_frac']:.2%} over "
                    f"{qp['probe_positions']} probe positions", flush=True,
                )
        service = GenerationService(params, cfg, tok, ns.max_new_tokens,
                                    ns.seed, engine=engine)
        if getattr(ns, "slo", 0):
            # server-side SLO engine: this replica observes TTFT (the router
            # cannot see first-token time through a non-streaming proxy) plus
            # its own availability/deadline outcomes. Events land beside the
            # flight dumps when --flight_dir is set; gauges + /healthz
            # degraded_reasons work either way.
            from galvatron_tpu.obs.slo import SLOEngine, build_serving_rules

            service.slo = SLOEngine(
                rules=build_serving_rules(ns),
                events_path=(os.path.join(ns.flight_dir, "slo_events.jsonl")
                             if getattr(ns, "flight_dir", None) else None),
                source="server",
            )
        import threading as _threading

        listening = _threading.Event()
        if engine is not None:
            # startup readiness gating: the server LISTENS first (so a
            # router/load-balancer can poll /readyz and get an honest 503
            # "starting"), then the engine warms on a side thread — the
            # persistent-cache warm start plus one real generation through
            # the scheduler, so the jitted programs genuinely exist — and
            # only then does /readyz flip to 200. Direct /api clients are
            # still accepted while starting; they simply share the compile,
            # exactly the old lazy-first-request behavior.
            service.starting = True
            _threading.Thread(
                target=_serve_warmup, args=(ns, engine, service, listening),
                name="serve-warmup", daemon=True,
            ).start()
        run_server(
            service,
            port=ns.port, host=ns.host, max_pending=ns.max_pending,
            drain_timeout_s=ns.drain_timeout_s, ready_event=listening,
        )
        # a drained SIGTERM/POST-/drain shutdown exits 0: zero-downtime
        # rollouts treat this process as cleanly replaceable
        return 0

    print(
        f"unknown mode {mode!r}; expected "
        "train|run-elastic|peer-store|search|profile|profile-hardware|"
        "check-plan|warmup|audit-comm|trace-export|generate|serve|serve-fleet|"
        "export-hf"
    )
    return 2


def _serve_warmup(ns, engine, service, listening) -> None:
    """`cli serve` startup warm (side thread): persistent-cache warm start
    of the pinned programs (when a cache is wired), then ONE real
    generation through the scheduler so the jitted entry points exist —
    only then does ``service.starting`` clear and ``/readyz`` report ready.
    Warmth is best-effort: any failure degrades to the lazy-compile path
    (the first request pays it) but never blocks readiness forever."""
    listening.wait(timeout=60.0)
    try:
        # the persistent cache is always placed like the trainer's
        # (aot/cache.resolve_compile_cache_dir); the flag arms the AOT warm
        # start of the pinned programs, and its '0'/'off'/'none' disables
        from galvatron_tpu.aot import warmup as aot_warmup
        from galvatron_tpu.aot.cache import (
            ArtifactStore,
            enable_persistent_cache,
            resolve_compile_cache_dir,
        )

        eff = enable_persistent_cache(resolve_compile_cache_dir(ns))
        print(f"compile cache: {eff or 'disabled'}", flush=True)
        if getattr(ns, "compile_cache_dir", None) and eff:
            reports = engine.warm_start(ArtifactStore(eff))
            s = aot_warmup.summarize(reports)
            print(
                f"serving warm-start: {s['compiled']}/{s['programs']} "
                f"programs ({s['hits']} cache hits, "
                f"{s['total_compile_ms']:.0f} ms)", flush=True,
            )
        # the first scheduler iteration: an AOT lower/compile populates the
        # persistent cache but not the jit call cache — one real request
        # proves the engine serves before /readyz says so
        engine.generate([[1]], max_new_tokens=2)
    except Exception as e:  # noqa: BLE001 — warmth is optional, serving is not
        print(f"serving warm-start failed (first request compiles lazily): "
              f"{type(e).__name__}: {str(e)[:200]}", flush=True)
    finally:
        service.starting = False
        print("serving ready: warm start complete, /readyz now 200",
              flush=True)


def _warmup_mode(ns) -> int:
    """AOT-warm every registered program for the given plan JSON(s).

    Per-plan and per-program failure isolation: a plan that fails static
    validation is skipped with its diagnostics, a program that fails to
    compile (a kernel or plan the backend's compiler refuses) degrades to
    a warning — the sweep itself never aborts.  rc 0 when at least one
    program compiled (or reported a hit), else 1."""
    from galvatron_tpu.aot import warmup as aot_warmup

    if ns.force_world:
        aot_warmup.force_cpu_world(ns.force_world)
    import jax

    from galvatron_tpu.analysis import plan_check
    from galvatron_tpu.analysis.diagnostics import errors, format_report
    from galvatron_tpu.aot.cache import (
        ArtifactStore,
        enable_persistent_cache,
        resolve_compile_cache_dir,
    )
    from galvatron_tpu.core.arguments import model_config_from_args
    from galvatron_tpu.core.strategy import HybridParallelConfig

    # same placement and sentinel rules as train/serve: '0'/'off'/'none'
    # disables the persistent layer — the sweep still compiles (a
    # compile-only run is a legitimate memory-feasibility check) but
    # persists and accounts nothing
    wdir = resolve_compile_cache_dir(ns)
    store = None
    if wdir:
        eff = enable_persistent_cache(wdir)
        store = ArtifactStore(eff)
        print(f"compile cache: {eff}")
    else:
        print("compile cache: disabled")
    include = [s.strip() for s in (ns.include or "").split(",") if s.strip()] or None
    world = jax.device_count()
    paths = list(ns.config_paths or []) + list(ns.galvatron_config_path or [])
    all_reports = []
    # when a report is requested, ride the lowering we are doing anyway:
    # extract each program's collective footprint from the StableHLO text
    # (zero extra lower/compile work) and write it beside the report
    footprints = []
    sink = None
    if ns.report:
        from galvatron_tpu.analysis import comm_audit

        def sink(spec, text):  # noqa: E306
            footprints.append(comm_audit.extract_footprint(text, program=spec.name))
    if not paths:
        # plan-free warmup: serving/generate families from the model flags
        from galvatron_tpu.aot import registry as aot_registry
        from galvatron_tpu.models.modeling import PRESETS

        base = PRESETS.get(ns.model_size or "llama-0.3b")
        if base is None:
            print(f"error: unknown --model_size {ns.model_size!r}")
            return 2
        # mirror `cli serve`/`generate` EXACTLY, not the trainer: those
        # surfaces run the model's own attn/dtype defaults and apply only an
        # explicit --attn_impl, so resolving 'auto' here (flash on
        # accelerators) would warm keys the serving engine never consults
        cfg = model_config_from_args(ns, base=base)
        if getattr(ns, "attn_impl", "auto") != "auto":
            cfg = cfg.replace(attn_impl=ns.attn_impl)
        ctx = aot_registry.ProgramContext(
            cfg=cfg, num_slots=ns.num_slots, prefill_chunk=ns.prefill_chunk,
            kv_block_size=getattr(ns, "kv_block_size", 16),
            kv_num_blocks=getattr(ns, "kv_num_blocks", 0),
            serve_quant=getattr(ns, "serve_quant", "off"),
            spec_decode_k=getattr(ns, "spec_decode_k", 0),
        )
        specs = aot_registry.enumerate_programs(ctx, include=include)
        all_reports += aot_warmup.warmup_programs(
            specs, store, model_cfg=cfg, serialize=bool(ns.serialize),
            footprint_sink=sink,
        )
    for path in paths:
        print(f"== {path}")
        try:
            with open(path) as f:
                d = json.load(f)
        except (OSError, ValueError) as e:
            print(f"warmup: cannot read {path}: {e}; skipping")
            continue
        plan_world = int(d.get("num_devices") or 0)
        if plan_world and plan_world != world:
            print(
                f"warmup: {path} was searched for {plan_world} devices but "
                f"this backend has {world}; skipping (re-run under "
                f"--force_world {plan_world} on CPU, or on the right mesh)"
            )
            continue
        # resolve the plan's self-describing model shape (same rules as
        # check-plan: explicit --model_size wins, else the embedded
        # model_config, else the model_size provenance key)
        cfg = _warmup_model_config(ns, d, path)
        if cfg is None:
            continue
        bsz = ns.global_train_batch_size or int(d.get("global_bsz") or 8)
        diags = plan_check.check_plan(
            d, source=path, model_config=cfg, world_size=world, global_bsz=bsz,
        )
        if errors(diags):
            print(format_report(diags))
            print(f"warmup: {path} fails static validation; skipping")
            continue
        hp = HybridParallelConfig.from_json_dict(d)
        # exact optimizer mirror (core/elastic.py prewarm does the same):
        # the adam constants are burned into the compiled step, so a sweep
        # warmed with different hyperparameters would never hit for the run
        from galvatron_tpu.core.arguments import adam_config_from_args

        all_reports += aot_warmup.warmup_plan(
            cfg, hp, global_bsz=bsz, store=store, include=include,
            num_slots=ns.num_slots, prefill_chunk=ns.prefill_chunk,
            kv_block_size=getattr(ns, "kv_block_size", 16),
            kv_num_blocks=getattr(ns, "kv_num_blocks", 0),
            serve_quant=getattr(ns, "serve_quant", "off"),
            spec_decode_k=getattr(ns, "spec_decode_k", 0),
            adam=adam_config_from_args(ns),
            serialize=bool(ns.serialize),
            footprint_sink=sink,
        )
    summary = aot_warmup.summarize(all_reports)
    manifest_note = (
        f"manifest: {store.stats()['entries']} entries" if store is not None
        else "manifest: disabled"
    )
    print(
        f"warmup: {summary['programs']} programs — {summary['hits']} hits, "
        f"{summary['misses']} misses, {summary['failed']} failed, "
        f"{summary['total_compile_ms']:.0f} ms total compile ({manifest_note})"
    )
    if ns.report:
        aot_warmup.write_report(ns.report, all_reports)
        print(f"report → {ns.report}")
        if footprints:
            from galvatron_tpu.analysis import comm_audit

            fp_path = ns.report + ".footprint.jsonl"
            comm_audit.write_footprint_jsonl(fp_path, footprints)
            print(f"comm footprint → {fp_path}")
    return 0 if summary["compiled"] > 0 else 1


def _warmup_model_config(ns, d: dict, path: str):
    """check-plan's model-resolution rules, shared shape: explicit
    --model_size > embedded model_config > the JSON's model_size key.

    Keep the precedence in lockstep with _check_plan_mode's resolution
    block (the failure handling legitimately differs: check-plan degrades
    to structural-only diagnostics, a warmup sweep skips the plan) — a
    drift here warms keys computed from a different effective model than
    the one check-plan/trainer validate against."""
    from galvatron_tpu.core.arguments import model_config_from_args
    from galvatron_tpu.models.modeling import PRESETS, ModelConfig

    model_size = ns.model_size or d.get("model_size")
    shape = d.get("model_config")
    shape = shape if isinstance(shape, dict) else None
    base = PRESETS.get(model_size) if model_size else None
    if ns.model_size and base is None:
        print(f"error: unknown --model_size {ns.model_size!r}")
        return None
    if not ns.model_size and shape is not None:
        from galvatron_tpu.analysis.plan_check import apply_model_shape

        base = apply_model_shape(base if base is not None else ModelConfig(), shape)
    if base is None:
        print(f"warmup: {path} names no resolvable model "
              f"(model_size {model_size!r}, no embedded model_config); skipping")
        return None
    from galvatron_tpu.core.arguments import resolve_execution_config

    cfg = model_config_from_args(ns, base=base)
    # mirror the trainer's own resolution (pack_sequences rides the model
    # config BEFORE attention resolution, core/elastic.py prewarm idem)
    if getattr(ns, "pack_sequences", 0):
        cfg = cfg.replace(pack_sequences=True)
    return resolve_execution_config(cfg, ns)


def _audit_comm_mode(ns) -> int:
    """Static HLO collective audit of strategy JSON(s) — lower-only.

    Forces a CPU world of the first plan's ``num_devices`` before the first
    backend touch (no hardware, no compile, no execute), then per plan:
    AOT-lower every program, extract the collective footprint, run the
    fidelity gate and the resharding lint.  rc 0 = every audited plan
    clean, 1 = GTC errors (or any GTC finding under --strict), 2 = usage
    error (no configs, unreadable JSON, unresolvable model)."""
    from galvatron_tpu.aot import warmup as aot_warmup

    paths = list(ns.config_paths or []) + list(ns.galvatron_config_path or [])
    if not paths:
        print("audit-comm: no strategy JSONs given")
        return 2
    plans = []
    for path in paths:
        try:
            with open(path) as f:
                plans.append((path, json.load(f)))
        except (OSError, ValueError) as e:
            print(f"audit-comm: cannot read {path}: {e}")
            return 2
    # the audit world comes from the plans themselves: force the CPU
    # platform before the first backend touch (lower-only — any host works)
    world = int(plans[0][1].get("num_devices") or 0) or 8
    aot_warmup.force_cpu_world(world)
    import jax

    from galvatron_tpu.analysis import comm_audit, plan_check
    from galvatron_tpu.analysis.diagnostics import errors, format_report
    from galvatron_tpu.core.strategy import HybridParallelConfig

    include = [s.strip() for s in (ns.include or "").split(",") if s.strip()] or None
    world = jax.device_count()
    all_footprints = []
    rc = 0
    audited = 0
    for path, d in plans:
        print(f"== {path}")
        plan_world = int(d.get("num_devices") or 0) or world
        if plan_world != world:
            # one process = one forced world; same skip rule as warmup so a
            # sweep over mixed-world configs audits what it can (the final
            # audited/total line keeps the gap visible)
            print(
                f"audit-comm: {path} targets {plan_world} devices but this "
                f"audit world is {world}; skipping (audit it in its own "
                f"invocation)"
            )
            continue
        cfg = _warmup_model_config(ns, d, path)
        if cfg is None:
            rc = max(rc, 2)
            continue
        bsz = ns.global_train_batch_size or int(d.get("global_bsz") or 8)
        diags = plan_check.check_plan(
            d, source=path, model_config=cfg, world_size=world, global_bsz=bsz,
        )
        if errors(diags):
            print(format_report(diags))
            print(f"audit-comm: {path} fails static validation")
            rc = max(rc, 1)
            continue
        try:
            hp = HybridParallelConfig.from_json_dict(d)
        except (ValueError, KeyError) as e:
            print(f"audit-comm: {path} does not decode: {e}")
            rc = max(rc, 2)
            continue
        res = comm_audit.audit_plan(
            cfg, hp, world=world, global_bsz=bsz, include=include,
            tolerance=ns.tolerance, source=path, verbose=True,
        )
        audited += 1
        print(comm_audit.format_fidelity_table(res.rows))
        if res.diagnostics:
            print(format_report(res.diagnostics, clean=""))
        all_footprints += res.footprints
        if errors(res.diagnostics) or (ns.strict and res.diagnostics):
            rc = max(rc, 1)
    if ns.report and all_footprints:
        comm_audit.write_footprint_jsonl(ns.report, all_footprints)
        print(f"comm footprint → {ns.report}")
    if not audited and rc == 0:
        print("audit-comm: no plan audited")
        return 2
    print(f"audit-comm: {audited}/{len(plans)} plan(s) audited, rc {rc}")
    return rc


def _trace_export_mode(ns) -> int:
    """Flight dump / span records → Chrome trace-event JSON (Perfetto).

    ``--merge`` fuses every ``flight_*.json`` under a directory into ONE
    timeline (obs/correlate.py): per-process pid track groups, clocks
    aligned via each dump's ``epoch_wall`` anchor — a fleet request's
    trace_id visibly hops router → replica-A → replica-B. Torn dumps are
    skipped with a warning (same contract as ``read_metrics``' torn tail).
    """
    if getattr(ns, "merge", False):
        from galvatron_tpu.obs.correlate import merge_directory

        try:
            out, used = merge_directory(ns.input_path, ns.output)
        except ValueError as e:
            print(f"error: {e}")
            return 2
        print(f"merged {len(used)} flight dump(s) → {out} "
              "(load in Perfetto or chrome://tracing)")
        return 0
    from galvatron_tpu.obs.flight import FLIGHT_SCHEMA
    from galvatron_tpu.obs.tracing import chrome_trace

    try:
        with open(ns.input_path) as f:
            doc = json.load(f)
    except OSError as e:
        print(f"error: cannot read {ns.input_path}: {e}")
        return 2
    except ValueError as e:
        # torn/partial dump (crash mid-write): diagnose, don't traceback —
        # the merge path skips these; single-file export has nothing left
        lineno = getattr(e, "lineno", "?")
        print(f"error: {ns.input_path}: torn/partial flight dump (crash "
              f"mid-write?) — JSON parse failed at line {lineno}")
        return 2
    if isinstance(doc, dict) and doc.get("schema") == FLIGHT_SCHEMA:
        spans = doc.get("spans", [])
    elif isinstance(doc, dict) and "traceEvents" in doc:
        print(f"{ns.input_path} is already Chrome trace-event JSON; nothing to do")
        return 2
    elif isinstance(doc, list):
        spans = doc
    else:
        print(
            f"error: {ns.input_path} is neither a {FLIGHT_SCHEMA} flight dump "
            "nor a JSON list of span records"
        )
        return 2
    out = ns.output or ns.input_path + ".trace.json"
    with open(out, "w") as f:
        json.dump(chrome_trace(spans), f)
    print(f"wrote {len(spans)} events → {out} (load in Perfetto or chrome://tracing)")
    return 0


def _check_plan_mode(ns) -> int:
    """Validate strategy JSONs statically; exit 1 on any error diagnostic
    (warnings too under --strict). Model/world/batch/budget default to the
    JSON's own provenance keys (search-emitted configs are self-describing)."""
    from galvatron_tpu.analysis import plan_check
    from galvatron_tpu.analysis.diagnostics import errors, format_report, warnings
    from galvatron_tpu.core.arguments import model_config_from_args

    paths = list(ns.config_paths or []) + list(ns.galvatron_config_path or [])
    if not paths:
        print("error: check-plan needs at least one strategy JSON path")
        return 2
    rc = 0
    cli_model_size = ns.model_size  # per-file JSON defaults must not leak across files
    for path in paths:
        try:
            with open(path) as f:
                d = json.load(f)
            if not isinstance(d, dict):
                d = {}
        except (OSError, ValueError):
            d = {}  # check_plan reports the parse failure as GTA002
        model_size = cli_model_size or d.get("model_size")
        shape = d.get("model_config")
        shape = shape if isinstance(shape, dict) else None
        cfg = None
        base = None
        if model_size:
            from galvatron_tpu.models.modeling import PRESETS

            base = PRESETS.get(model_size)
            if base is None and cli_model_size:
                # an explicit model the user asked to validate against —
                # falling back to anything else would answer a different
                # question with a confident exit code
                print(f"error: unknown --model_size {cli_model_size!r}")
                return 2
            if base is None and shape is None:
                print(f"{path}: unknown model_size {model_size!r} and no "
                      "embedded model_config; running structural checks only")
        if cli_model_size:
            # an EXPLICIT --model_size asks "does this plan fit THAT model" —
            # the plan's embedded shape must not overlay it (it would make
            # validation against a different model silently vacuous)
            if shape is not None:
                print(f"{path}: validating against --model_size "
                      f"{cli_model_size} (plan's embedded model_config "
                      "shape ignored)")
        elif shape is not None:
            # no explicit model: the plan's embedded EFFECTIVE shape is the
            # default (covers search-time overrides like --num_layers);
            # explicit per-field flags still win below
            from galvatron_tpu.analysis.plan_check import apply_model_shape
            from galvatron_tpu.models.modeling import ModelConfig

            base = apply_model_shape(base if base is not None else ModelConfig(), shape)
        if base is not None:
            cfg = model_config_from_args(ns, base=base)
        def _num(v):
            # provenance keys come from arbitrary hand-edited JSON: a
            # string-typed "8" must not crash the tool whose job is turning
            # malformed configs into diagnostics
            try:
                return float(v)
            except (TypeError, ValueError):
                return 0.0

        world = int(ns.num_devices or _num(d.get("num_devices")))
        budget_gb = ns.memory_constraint_gb or _num(d.get("memory_constraint_gb"))
        diags = plan_check.check_plan(
            # already decoded above — re-reading the file would duplicate
            # I/O and race a concurrent rewrite; the path branch is kept
            # only to surface the parse failure as GTA002
            d if d else path,
            source=path,
            model_config=cfg,
            world_size=world or None,
            global_bsz=ns.global_bsz or None,
            memory_budget_mb=budget_gb * 1024.0 or None,
            abstract_pass=not ns.no_abstract_pass,
        )
        scope = []
        if cfg is None:
            scope.append("no model config: structural checks only")
        if not world:
            scope.append("no num_devices: topology checks skipped")
        tag = f"  ({'; '.join(scope)})" if scope else ""
        print(f"== {path}{tag}")
        print(format_report(diags))
        if errors(diags) or (ns.strict and warnings(diags)):
            rc = 1
    return rc


def _validate_search(cands, cfg, ns):
    """Measured validation of the predicted ranking: train the top-k searched
    candidates a few steps each and report predicted vs measured iteration
    time. Ordering compares THROUGHPUT (the criterion the search maximizes —
    candidates may differ in global batch size, so iteration time alone is
    not comparable). The reference's check_cost_model stops at printed
    predictions ("for developers", search_engine.py:369-421); this closes
    the loop on real steps."""
    import jax

    from galvatron_tpu.profiling.model import measure_strategy_ms

    world = len(jax.devices())
    if world != ns.num_devices:
        print(
            f"--validate_top_k skipped: search was for {ns.num_devices} "
            f"devices but this host has {world}"
        )
        return
    rows = []
    for r in cands:
        try:
            ms = measure_strategy_ms(cfg, r.config, r.global_bsz)
        except Exception as e:  # candidate may not fit this host's memory
            print(f"  candidate pp={r.config.pp} failed to run: {str(e)[:120]}")
            continue
        rows.append((r, r.global_bsz / (ms / 1000.0)))
        print(
            f"  pp={r.config.pp} chunks={r.config.chunks} "
            f"{r.config.pipeline_type} vpp={r.config.vpp} bsz={r.global_bsz}: "
            f"predicted {r.cost_ms:.1f} ms, measured {ms:.1f} ms "
            f"(fidelity {r.cost_ms / ms:.3f})"
        )
    if len(rows) >= 2:
        pred_order = [
            id(r) for r, _ in sorted(rows, key=lambda x: -x[0].throughput_samples_per_s)
        ]
        meas_order = [id(r) for r, _ in sorted(rows, key=lambda x: -x[1])]
        agree = sum(a == b for a, b in zip(pred_order, meas_order))
        print(
            f"predicted-vs-measured rank agreement: {agree}/{len(rows)} "
            f"positions (best candidate "
            f"{'confirmed' if pred_order[0] == meas_order[0] else 'NOT fastest measured'})"
        )


def _load_or_init_params(ns, cfg):
    """Params from a trainer checkpoint (--load) or fresh random init."""
    import jax

    from galvatron_tpu.models import modeling

    if getattr(ns, "load", None):
        from galvatron_tpu.core.checkpoint import restore_raw_checkpoint

        # verified restore with newest→oldest fallback: a corrupt latest
        # checkpoint cannot silently serve garbage weights
        raw, _step = restore_raw_checkpoint(os.path.abspath(ns.load))
        params = raw["params"] if isinstance(raw, dict) and "params" in raw else raw
        # validate against the model config before silently generating garbage
        abstract = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
        got, want = _shape_map(params), _shape_map(abstract)
        if got != want:
            diff = {k: (got.get(k), want.get(k)) for k in sorted(set(got) | set(want))
                    if got.get(k) != want.get(k)}
            raise ValueError(
                f"checkpoint under {ns.load} does not match the model config "
                f"(e.g. --vocab_size/--tokenizer mismatch); got vs want: {diff}"
            )
        if getattr(ns, "param_dtype", "fp32") != "fp32":
            # held ONCE at the width the configuration says (a router's stays float32)
            want = {_path_key(path): leaf.dtype
                    for path, leaf in jax.tree_util.tree_flatten_with_path(abstract)[0]}
            params = jax.tree_util.tree_map_with_path(
                lambda path, leaf: leaf.astype(want[_path_key(path)]), params)
        return params
    return modeling.init_model_params(jax.random.key(0), cfg)


def _path_key(path) -> str:
    """A tree path with list indices and '0'-style dict keys normalized."""
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _shape_map(tree):
    """path → shape (`_path_key`)."""
    import jax

    return {_path_key(path): tuple(getattr(leaf, "shape", ()))
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


if __name__ == "__main__":
    raise SystemExit(main())
