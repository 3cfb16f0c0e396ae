"""Argument system for the four operating modes.

Counterpart of the reference's two-tier flag system (reference:
galvatron/core/arguments.py:5-313 — Megatron argparse + galvatron
training/profile/search/hardware-profile groups, initialize_galvatron modes).
No vendored Megatron here: one argparse tree with mode-specific groups, plus
the JSON artifacts (model meta-config, profiled data, searched strategy) as
the interchange format.
"""

from __future__ import annotations

import argparse
from typing import List, Optional, Sequence

from galvatron_tpu.models.modeling import PRESETS


def _add_model_args(p: argparse.ArgumentParser):
    g = p.add_argument_group("model")
    g.add_argument("--model_size", type=str, default="llama-0.3b", choices=sorted(PRESETS))
    g.add_argument(
        "--set_model_config_manually", type=int, default=0,
        help="1 = require the full manual model config (vocab/hidden/layers/heads); "
        "0 = preset sizes, with any explicitly-passed flags overriding",
    )
    g.add_argument("--vocab_size", type=int, default=None)
    g.add_argument("--hidden_size", type=int, default=None)
    g.add_argument("--num_layers", type=int, default=None)
    g.add_argument("--num_heads", type=int, default=None)
    g.add_argument("--num_kv_heads", type=int, default=None)
    g.add_argument("--ffn_dim", type=int, default=None)
    g.add_argument("--seq_length", type=int, default=None)
    g.add_argument("--enc_layers", type=int, default=None,
                   help="encoder layers (enc-dec families; 0 = decoder-only)")
    g.add_argument("--enc_seq", type=int, default=None)
    g.add_argument("--image_size", type=int, default=None,
                   help="vision families: input image side (pixels)")
    g.add_argument("--patch_size", type=int, default=None)
    g.add_argument("--num_classes", type=int, default=None)
    g.add_argument("--swin_window", type=int, default=None)
    g.add_argument("--swin_depths", type=str, default=None,
                   help="comma list, e.g. 2,2,18,2 (must sum to --num_layers)")
    g.add_argument("--moe_experts", type=int, default=None,
                   help="switch-MoE expert count (0/None = dense MLP)")
    g.add_argument("--moe_capacity_factor", type=float, default=None)
    g.add_argument("--moe_dense_layers", type=int, default=None,
                   help="leading layers whose MLP is the plain one of --ffn_dim where the rest "
                        "are expert layers (a model cut in depth may keep fewer of them)")
    g.add_argument("--moe_share", type=str, default=None, metavar="R/N",
                   help="dropless top-k MoE: this copy holds rank R of N contiguous shares "
                        "of the experts (one rank of an N-way expert-parallel deployment); "
                        "the router still scores all of them")


def _add_step_program_args(p: argparse.ArgumentParser):
    """Flags burned into the compiled step program — ONE group shared by the
    train modes and `cli warmup`, because every one of them is a
    `aot/cache.program_key` term (optimizer constants, compute dtype,
    attention kernel, recompute policy, packing): a warmup sweep that could
    not express them would warm keys no real run ever asks for."""
    g = p.add_argument_group("step program")
    g.add_argument("--lr", type=float, default=1e-4)
    g.add_argument("--min_lr", type=float, default=0.0)
    g.add_argument("--lr_warmup_iters", type=int, default=0)
    g.add_argument("--lr_decay_iters", type=int, default=0, help="0 = no decay")
    g.add_argument("--lr_decay_style", type=str, default="cosine",
                   choices=["constant", "linear", "cosine"])
    g.add_argument("--weight_decay", type=float, default=0.01)
    g.add_argument("--grad_clip", type=float, default=1.0)
    g.add_argument("--mixed_precision", type=str, default="bf16",
                   choices=["fp32", "bf16", "fp16"],
                   help="fp16 adds dynamic loss scaling (skip-on-overflow); "
                   "bf16 is the TPU-native choice")
    g.add_argument("--attn_impl", type=str, default="auto", choices=["auto", "flash", "xla"])
    g.add_argument(
        "--mlp_recompute", type=str, default="policy",
        choices=["off", "gate", "policy"],
        help="activation-memory recompute over the MLP/norm/loss regions "
        "(DESIGN.md 'Activation memory accounting'): 'policy' saves the "
        "swiglu/gelu gate exactly once per layer and rematerializes the "
        "fp32-widened norm/cross-entropy buffers; 'gate' remats only the "
        "activation product; 'off' restores the pre-policy behaviour",
    )
    g.add_argument("--pack_sequences", type=int, default=0,
                   help="1 = greedy first-fit packing of documents into "
                   "fixed-seq_len rows with segment ids: cross-document "
                   "attention blocked, per-segment position reset, loss "
                   "masked at boundaries; true-token MFU + "
                   "packing_efficiency reported. Needs --data_path or "
                   "--data_mixture and the xla attention path")


def _add_training_args(p: argparse.ArgumentParser):
    """(reference: galvatron_training_args, core/arguments.py:44-137)"""
    _add_step_program_args(p)
    g = p.add_argument_group("training")
    g.add_argument("--global_train_batch_size", type=int, default=8)
    g.add_argument("--train_iters", type=int, default=10)
    g.add_argument(
        "--rampup_batch_size", type=int, nargs=3, default=None,
        metavar=("START", "INCREMENT", "SAMPLES"),
        help="global-batch-size ramp-up (reference: megatron microbatches.py); "
        "pp=1 only — each size change recompiles the step",
    )
    g.add_argument("--seed", type=int, default=1234)
    g.add_argument("--num_slices", type=int, default=0,
                   help="TPU multislice: order the mesh slice-major so pp "
                   "and the major data axes cross the DCN boundary "
                   "(0/1 = single slice)")
    g.add_argument("--multihost", type=int, default=0,
                   help="1 = jax.distributed.initialize() (TPU pod slices; "
                   "every host runs the same command)")
    g.add_argument("--check_loss", type=int, default=0)
    g.add_argument("--profile", type=int, default=0, help="print per-iter time/memory")
    g.add_argument("--trace_dir", type=str, default=None,
                   help="capture a jax.profiler trace of the measured "
                   "iterations to this directory (XLA op/kernel timeline; "
                   "the torch.profiler/CUDA-events counterpart, SURVEY §5)")
    # observability layer (obs/, DESIGN.md § Observability)
    g.add_argument("--trace_spans", type=str, default=None,
                   help="enable host-side span tracing (step/data/fwd_bwd/"
                   "sync/ckpt) and export "
                   "a Chrome trace-event / Perfetto JSON to this path on "
                   "exit; adds one host sync per iteration while enabled "
                   "(OFF = zero added syncs)")
    g.add_argument("--trace_ring", type=int, default=4096,
                   help="span-tracer ring capacity (also the flight "
                   "recorder's last-N window)")
    g.add_argument("--profile_steps", type=str, default=None,
                   metavar="START:STOP",
                   help="capture a jax.profiler window over iterations "
                   "[START, STOP) into --trace_dir (or a temp dir) — the "
                   "bounded alternative to tracing the whole run")
    g.add_argument("--obs_port", type=int, default=0,
                   help="serve GET /metrics (Prometheus text) + /healthz on "
                   "this port (loopback) from a sidecar thread for headless "
                   "training runs; implies one host sync per iteration so "
                   "the loss/iter_ms/MFU gauges are live (0 = off)")
    g.add_argument("--flight_dir", type=str, default=None,
                   help="crash flight-recorder directory: an exceptional "
                   "exit dumps the last --trace_ring spans to "
                   "flight_<ts>.json here. Arms span tracing by itself "
                   "(same per-iter sync as --trace_spans); with only "
                   "--trace_spans set, dumps land alongside that path")
    g.add_argument("--peak_tflops", type=float, default=0.0,
                   help="per-device peak dense TFLOP/s for MFU (default: "
                   "auto from the TPU generation, or the "
                   "GALVATRON_PEAK_TFLOPS env; unknown = mfu omitted)")
    g.add_argument("--slo_step_time_drift", type=float, default=0.0,
                   help="arm the trainer's step-time-drift SLO (obs/slo.py): "
                   "a step is 'bad' when measured iter time exceeds the "
                   "plan's predicted step time by more than this fraction "
                   "(e.g. 0.25 = 25%% slow); sustained drift over both burn "
                   "windows raises an slo_breach event. The predicted step "
                   "time is the plan file's search_cost_ms where a search "
                   "recorded one for the batch trained; for any other plan "
                   "(flags, the defaults) it is the total of the price the "
                   "trainer puts on the plan itself (search/price.price_plan "
                   "on analytic costs: the plan_price record). The drift "
                   "gauge is ROADMAP item 2's online re-plan signal. "
                   "Implies a per-iter sync. 0 = off")
    # hybrid-parallel GLOBAL flags (used when no galvatron_config_path)
    g.add_argument("--pp_deg", type=int, default=1)
    g.add_argument("--pp_division", type=_int_list, default=None,
                   help="comma-separated layers per pipeline stage (uneven "
                   "divisions supported; default: balanced split)")
    g.add_argument("--vpp_deg", type=int, default=1,
                   help="virtual pipeline chunks per device (interleaved "
                   "schedule; needs layers %% (pp*vpp) == 0 and chunks %% pp == 0)")
    g.add_argument("--global_tp_deg", type=int, default=1)
    g.add_argument("--global_tp_consec", type=int, default=1)
    g.add_argument("--sdp", type=int, default=0, help="1 = zero3 on all layers")
    g.add_argument("--default_dp_type", type=str, default="ddp", choices=["ddp", "zero2", "zero3"])
    g.add_argument(
        "--global_checkpoint", type=int, default=0, choices=[0, 1, 2],
        help="0 = off, 1 = full-layer remat, 2 = selective (attention-core-only "
        "recompute; reference: Megatron --recompute-granularity selective)",
    )
    g.add_argument("--sequence_parallel", type=int, default=0)
    g.add_argument("--global_tp_overlap", type=int, default=0,
                   help="1 = decomposed collective-matmul on the TP "
                   "projection seams of every tp>1 layer "
                   "(ops/collective_matmul.py): the qkv/MLP-up seq "
                   "all-gather and the output-projection reduce "
                   "pipeline behind the GEMM chunks via shard_map/ppermute "
                   "rings instead of blocking in GSPMD (DESIGN.md 'Overlap')")
    g.add_argument("--grad_overlap", type=int, default=0,
                   help="1 = async ZeRO gradient overlap: zero2/zero3 "
                   "gradient reduce-scatters are pinned per-layer into the "
                   "backward graph (one bucket per layer, issued as that "
                   "layer's backward completes) instead of trailing the "
                   "whole backward (sharding.overlap_grad_sync)")
    g.add_argument("--xla_overlap", type=str, default="off",
                   choices=["off", "auto", "aggressive"],
                   help="curated XLA latency-hiding-scheduler flag set "
                   "appended to XLA_FLAGS before backend init (TPU only; "
                   "parallel/mesh.apply_xla_overlap). Recorded in the run "
                   "manifest and BENCH extra fields for reproducibility")
    g.add_argument("--context_parallel_deg", type=int, default=1)
    g.add_argument("--context_parallel_impl", type=str, default="ring",
                   choices=["ring", "a2a"],
                   help="ring = K/V rotation; a2a = Ulysses sequence/head "
                   "all-to-all (needs num_heads divisible by the CP degree)")
    g.add_argument("--chunks", type=int, default=-1, help="-1 = heuristic")
    g.add_argument("--pipeline_type", type=str, default="gpipe", choices=["gpipe", "pipedream_flush"])
    g.add_argument("--vocab_tp", type=int, default=1)
    g.add_argument("--embed_sdp", type=int, default=0)
    g.add_argument("--galvatron_config_path", type=str, default=None)
    # AOT compile subsystem (galvatron_tpu/aot; DESIGN.md § AOT compile
    # subsystem): the ONE shared persistent-compile-cache wiring
    g.add_argument("--compile_cache_dir", type=str, default=None,
                   help="persistent compile-artifact cache directory "
                   "(aot/cache.py): startup AOT-compiles every registered "
                   "program, accounts plan-keyed hit/miss in the manifest, "
                   "and a warm start shrinks the watchdog's first-step "
                   "compile grace. JAX's persistent cache itself is always "
                   "on: at JAX_COMPILATION_CACHE_DIR when that is set "
                   "(whatever this flag says), else here, else "
                   "<repo>/.jax_cache; '0'/'off'/'none' disables")
    # checkpoint/resume (capability the reference only gestures at; SURVEY §5)
    g.add_argument("--data_path", type=str, default=None,
                   help="corpus prefix: a sharded manifest "
                   "(<prefix>.shards.json, galvatron_tpu.data) or a legacy "
                   "single-file <prefix>.bin/.idx.json pair; default = "
                   "synthetic tokens")
    # production data pipeline (galvatron_tpu/data/; DESIGN.md § Data pipeline)
    g.add_argument("--data_mixture", type=str, default=None,
                   help="deterministic weighted multi-corpus mixture: a JSON "
                   "file ({'sources': [{'name','prefix','weight'}, ...]}, see "
                   "configs/data/) or inline 'prefix=weight,prefix=weight'. "
                   "Position-addressable — per-source consumption is exact "
                   "across preempt/resume and batch-size changes")
    g.add_argument("--prefetch_depth", type=int, default=0,
                   help="async input prefetch: a background host thread "
                   "assembles + device-transfers batch k+1 while step k "
                   "runs (bounded at this many in-flight batches; 2 = "
                   "double buffering). 0 = synchronous fetch. Needs "
                   "--data_path or --data_mixture")
    g.add_argument("--metrics_path", type=str, default=None,
                   help="JSONL structured metrics sink (per-iter loss/time)")
    g.add_argument("--save", type=str, default=None, help="checkpoint directory")
    g.add_argument("--keep_last_n", type=int, default=0,
                   help="checkpoint retention: after each committed save, "
                   "prune all but the newest N committed steps (0 = keep all)")
    g.add_argument("--anomaly_max_skips", type=int, default=0,
                   help="non-finite-loss policy (core/resilience.py): skip up "
                   "to N consecutive NaN/Inf updates (state rolled back, batch "
                   "dropped), then abort with an emergency checkpoint; 0 = "
                   "disarmed (no rollback snapshot, no per-iter loss sync)")
    g.add_argument("--load", type=str, default=None, help="resume directory")
    g.add_argument("--load_hf", type=str, default=None,
                   help="initialize weights from a local HuggingFace "
                   "LLaMA-architecture checkpoint directory (models/convert.py; "
                   "overrides the model shape from the HF config)")
    g.add_argument("--save_interval", type=int, default=0)
    # elastic training (core/elastic.py + core/watchdog.py; docs/DESIGN.md
    # § Elastic training). --step_timeout_s is read by the trainer itself
    # (any run can arm the watchdog); the rest steer the run-elastic
    # supervisor and its child's topology re-plan.
    g.add_argument("--step_timeout_s", type=float, default=0.0,
                   help="hang watchdog: a train step exceeding this deadline "
                   "dumps all-thread stacks + the flight ring, attempts an "
                   "emergency save of the last bound state, and exits with "
                   "the hang code (77) so run-elastic restarts instead of "
                   "burning the pod on a stalled collective. The first step "
                   "of a process gets 10x (XLA compile). Implies a per-iter "
                   "sync. 0 = off")
    g.add_argument("--max_restarts", type=int, default=10,
                   help="run-elastic: give up after this many CONSECUTIVE "
                   "restarts without progress (a newer committed checkpoint "
                   "step resets the counter; preemptions that saved always "
                   "progress)")
    g.add_argument("--restart_backoff_s", type=float, default=1.0,
                   help="run-elastic: base of the full-jitter exponential "
                   "backoff before crash/hang restarts (preempted-save "
                   "children restart immediately)")
    g.add_argument("--restart_backoff_cap_s", type=float, default=60.0,
                   help="run-elastic: backoff ceiling")
    g.add_argument("--replan_search_space", type=str, default="full",
                   choices=["full", "dp+tp", "dp+pp", "3d", "dp", "tp", "pp", "sdp"],
                   help="topology-change re-plan: restrict the re-search to "
                   "this strategy subspace (same presets as search "
                   "--search_space)")
    g.add_argument("--replan_memory_gb", type=float, default=16.0,
                   help="topology-change re-plan: per-device memory budget "
                   "for the re-search (no profile exists for a mesh that "
                   "appeared mid-run; analytic costs are used)")
    # preemption-aware recovery (core/peer_store.py + core/preemption.py;
    # docs/DESIGN.md § Recovery paths)
    g.add_argument("--peer_replicate", type=int, default=0,
                   help="run-elastic: in-memory peer checkpoint replication "
                   "— spawn this many peer-store host processes and have the "
                   "child ring-replicate its state to a neighbor's RAM after "
                   "every interval save; a killed host resumes from the "
                   "newest surviving replica without touching storage, and a "
                   "storage outage degrades to the RAM tier instead of "
                   "failing the save. 0 = off")
    g.add_argument("--preempt_grace_s", type=float, default=30.0,
                   help="grace window after a preemption notice (SIGTERM or "
                   "the notice file): the trainer drains — finishes the "
                   "in-flight step, pushes the peer replica, commits an "
                   "expedited save — and exits preempted (75) before it "
                   "expires")
    g.add_argument("--preempt_notice_file", type=str, default=None,
                   help="pollable preemption-notice path (stands in for the "
                   "cloud metadata server): its existence is the eviction "
                   "notice; also settable via GALVATRON_PREEMPT_NOTICE")
    g.add_argument("--degraded_min_dp", type=int, default=1,
                   help="degraded-mesh continuation floor: after a peer "
                   "loss, continue at reduced DP width (global batch "
                   "preserved via grad accumulation) only while the width "
                   "stays >= this; below it the re-plan is infeasible and "
                   "the supervisor gives up (waiting beats limping)")
    g.add_argument("--heartbeat_timeout_s", type=float, default=0.0,
                   help="run-elastic: supervisor-side heartbeat watchdog — "
                   "the child touches a heartbeat file every step; no beat "
                   "for this many seconds and the supervisor SIGKILLs the "
                   "child and restarts it as a hang (the last line of "
                   "defense when the child is too wedged for its own "
                   "--step_timeout_s watchdog). First beat gets a "
                   "compile-length grace (20x, min 120s). 0 = off")


def _add_search_args(p: argparse.ArgumentParser):
    """(reference: galvatron_search_args, core/arguments.py:226-313)"""
    g = p.add_argument_group("search")
    g.add_argument("--num_devices", type=int, default=8)
    g.add_argument("--memory_constraint_gb", type=float, default=16.0)
    g.add_argument("--min_bsz", type=int, default=8)
    g.add_argument("--max_bsz", type=int, default=64)
    g.add_argument("--bsz_scale", type=int, default=2)
    g.add_argument("--settle_bsz", type=int, default=-1, help="search exactly this bsz")
    g.add_argument("--recommend_min_bsz", type=int, default=0,
                   help="1 = raise the sweep's min bsz to 65%% of the "
                   "pure-strategy baselines' max feasible batch (reference "
                   "recommend_min_bsz pruning — pure search-time saving)")
    g.add_argument("--max_chunks", type=int, default=64)
    g.add_argument("--search_space", type=str, default="full",
                   choices=["full", "dp+tp", "dp+pp", "3d", "dp", "tp", "pp", "sdp"])
    g.add_argument("--disable_sdp", type=int, default=0)
    g.add_argument("--disable_ckpt", type=int, default=0)
    g.add_argument("--disable_sp", type=int, default=0)
    g.add_argument("--disable_tp_consec", type=int, default=0)
    g.add_argument("--enable_cp", type=int, default=0)
    g.add_argument("--enable_ep", type=int, default=0,
                   help="search expert parallelism (MoE models)")
    g.add_argument("--max_ep_deg", type=int, default=8)
    g.add_argument("--max_tp_deg", type=int, default=8)
    g.add_argument("--max_vpp_deg", type=int, default=1,
                   help="search interleaved virtual-stage degrees up to this "
                   "(powers of two; 1 = plain schedules only)")
    g.add_argument("--analytic_costs", type=int, default=0,
                   help="1 = search on analytic (unprofiled) model costs "
                   "(theoretical_memory_usage equivalent)")
    g.add_argument("--check_cost_model", type=int, default=0,
                   help="print the predicted per-strategy memory/time table "
                   "instead of searching (developer harness)")
    g.add_argument("--time_profile_path", type=str, default=None)
    g.add_argument("--memory_profile_path", type=str, default=None)
    g.add_argument("--hardware_profile_path", type=str, default=None)
    g.add_argument("--output_config_path", type=str, default=None)
    # execution config for the in-process profile + cost model: must match
    # what the training run will use (resolve_execution_config)
    g.add_argument("--mixed_precision", type=str, default="bf16",
                   choices=["fp32", "fp16", "bf16"])
    g.add_argument("--attn_impl", type=str, default="auto",
                   choices=["auto", "flash", "xla"])
    g.add_argument("--validate_top_k", type=int, default=0,
                   help="after searching, TRAIN the top-k candidates a few "
                   "steps each on this host's devices and report measured vs "
                   "predicted iteration time and whether the predicted "
                   "ranking holds (requires --num_devices == local devices)")
    g.add_argument("--report_homogeneity_gap", type=int, default=0,
                   help="after searching a pp>1 config, run per-stage DPs "
                   "with stage-specific memory (the reference's unrestricted "
                   "per-stage placement) and report/record the predicted "
                   "cost of this runtime's cross-stage position sharing")


def _add_profile_args(p: argparse.ArgumentParser):
    """(reference: galvatron_profile_args, core/arguments.py:139-184)"""
    g = p.add_argument_group("profile")
    g.add_argument("--profile_type", type=str, default="both",
                   choices=["computation", "memory", "both"])
    g.add_argument("--profile_batch_size", type=int, default=8)
    g.add_argument("--layernum_min", type=int, default=0,
                   help="0 = adaptive (scales with the model's layer count)")
    g.add_argument("--layernum_max", type=int, default=0)
    g.add_argument("--output_prefix", type=str, default=None)
    # (--mixed_precision / --attn_impl come from the training group, which the
    # profile parser includes — build_parser)


def _add_generate_args(p: argparse.ArgumentParser):
    """(reference: megatron text-generation flags + text_generation_server.py)"""
    g = p.add_argument_group("generate")
    g.add_argument("--load", type=str, default=None, help="checkpoint directory (trainer state)")
    g.add_argument("--load_hf", type=str, default=None,
                   help="local HuggingFace LLaMA-architecture checkpoint directory")
    g.add_argument("--tokenizer", type=str, default="byte",
                   help="'byte' or a local transformers tokenizer path")
    g.add_argument("--prompt", type=str, action="append", default=None)
    g.add_argument("--max_new_tokens", type=int, default=64)
    g.add_argument("--temperature", type=float, default=0.0)
    g.add_argument("--top_k", type=int, default=0)
    g.add_argument("--top_p", type=float, default=0.0)
    g.add_argument("--seed", type=int, default=1234)
    g.add_argument("--attn_impl", type=str, default="auto",
                   choices=["auto", "flash", "xla"],
                   help="attention kernel override; 'auto' keeps the model's "
                   "own default (serving never switches kernels by backend). "
                   "A program-key term: pass the same value to `cli warmup`")
    g.add_argument("--param_dtype", type=str, default="fp32", choices=["fp32", "bf16"],
                   help="what the weights are initialised, loaded and held in: bf16 "
                   "holds them ONCE at the compute width (no per-step conversion); "
                   "a router's matrix and bias stay float32. A program-key term")
    g.add_argument("--port", type=int, default=5000)
    g.add_argument("--host", type=str, default="127.0.0.1")
    # serve: continuous-batching engine (serving.Engine); 0 slots = legacy
    # serialized path (generate_np under the global lock)
    g.add_argument("--num_slots", type=int, default=4,
                   help="KV-cache slots = max concurrently decoding requests "
                   "(0 disables the engine: serialized single-shot path)")
    g.add_argument("--prefill_chunk", type=int, default=32,
                   help="prompt tokens prefilled per jitted chunk when a "
                   "request joins its slot (one compiled program per size)")
    g.add_argument("--kv_num_blocks", type=int, default=0,
                   help="paged KV backend (serving/paged_kv.py): device "
                   "block-pool size including the reserved null block; 0 = "
                   "contiguous slot cache, -1 = auto-size to the slot "
                   "cache's HBM footprint. A program-key term: pass the "
                   "same value to `cli warmup`")
    g.add_argument("--kv_block_size", type=int, default=16,
                   help="paged KV backend: tokens per block (prefix sharing "
                   "is block-granular, so smaller blocks share more and "
                   "table/gather overhead grows)")
    g.add_argument("--prefix_cache", type=str, default="on",
                   choices=["on", "off"],
                   help="paged KV backend: keep refcount-0 prompt blocks "
                   "registered for copy-on-write prefix sharing (LRU-"
                   "evicted under pool pressure); off = blocks free "
                   "immediately on retirement")
    g.add_argument("--serve_quant", type=str, default="off",
                   choices=["off", "int8"],
                   help="serve: weight quantization for the engine (ops/"
                   "quant.py): int8 = per-channel symmetric absmax weights "
                   "dequantized inside the matmuls (fp32 accumulate), "
                   "quantized ONCE at load and parity-gated against "
                   "--quant_drift_max. A program-key term: pass the same "
                   "value to `cli warmup`")
    g.add_argument("--quant_drift_max", type=float, default=1.0,
                   help="serve: max-abs logit drift the int8 engine may show "
                   "vs fp on the load-time probe forward before it refuses "
                   "to serve (the measured drift + greedy agreement land in "
                   "stats()/healthz either way)")
    g.add_argument("--spec_decode_k", type=int, default=0,
                   help="serve: speculative decoding draft length — the "
                   "drafter proposes up to k tokens per slot per iteration "
                   "and ONE (B,1+k) verify forward scores them (rejection "
                   "sampling keeps the output distribution exact; greedy is "
                   "bit-identical). 0 = off. A program-key term: pass the "
                   "same value to `cli warmup`")
    g.add_argument("--spec_drafter", type=str, default="prompt_lookup",
                   choices=["prompt_lookup"],
                   help="serve: draft source for --spec_decode_k (serving/"
                   "speculative.py): prompt_lookup = checkpoint-free n-gram "
                   "continuation from the request's own prompt+generation")
    g.add_argument("--request_ttl_s", type=float, default=30.0,
                   help="end-to-end request deadline: a request that "
                   "out-waits it in queue 503s, and one still decoding past "
                   "it is stopped at the next iteration (--deadline_policy "
                   "decides partial-vs-fail); <=0: no deadline")
    g.add_argument("--deadline_policy", type=str, default="partial",
                   choices=["partial", "fail"],
                   help="over-deadline DECODING requests: 'partial' returns "
                   "the text generated so far marked truncated=deadline; "
                   "'fail' 503s them (either way the slot frees immediately)")
    g.add_argument("--max_queue", type=int, default=64,
                   help="admission queue depth; beyond it requests fail "
                   "fast with 503 (engine path's max_pending equivalent)")
    g.add_argument("--max_pending", type=int, default=8,
                   help="legacy path: bound on queued /api requests")
    g.add_argument("--drain_timeout_s", type=float, default=30.0,
                   help="graceful drain bound (SIGTERM or POST /drain): "
                   "in-flight requests get this long to finish after "
                   "admission closes; stragglers are failed and the "
                   "process still exits 0 on time")
    g.add_argument("--max_engine_restarts", type=int, default=3,
                   help="serve: consecutive no-progress in-process engine "
                   "restarts (crash supervision) before the engine gives "
                   "up and /readyz goes permanently unready; a completed "
                   "request between crashes resets the budget")
    g.add_argument("--flight_dir", type=str, default=None,
                   help="serve: write a flight-recorder dump (tracer ring) "
                   "on every engine crash/restart; arms span tracing like "
                   "the trainer flag of the same name")
    g.add_argument("--compile_cache_dir", type=str, default=None,
                   help="serve: persistent compile cache (aot/cache.py); the "
                   "engine warm-starts its two pinned programs before "
                   "accepting traffic, so a restarted server's first request "
                   "pays a cache deserialize, not two XLA compiles. The "
                   "cache is placed as for train (JAX_COMPILATION_CACHE_DIR "
                   "> this flag > <repo>/.jax_cache)")
    # SLO burn-rate engine (obs/slo.py). Deliberately NOT fleet-only flags:
    # serve-fleet forwards them verbatim to every replica, so the router
    # (availability/deadline from dispatch outcomes) and the replicas
    # (server-side TTFT) alert on one coherent rule set.
    g.add_argument("--slo", type=int, default=0,
                   help="1 = arm the SLO burn-rate engine (obs/slo.py): "
                   "availability / TTFT p99 / deadline-miss rules evaluated "
                   "over fast+slow sliding windows; breaches land in "
                   "slo_events.jsonl, /metrics gauges, and /healthz "
                   "degraded_reasons")
    g.add_argument("--slo_ttft_p99_s", type=float, default=None,
                   help="TTFT target (seconds) for the ttft_p99 rule "
                   "(default: the rule table's 2.0s)")
    g.add_argument("--slo_availability", type=float, default=None,
                   help="availability target fraction (default 0.99)")
    g.add_argument("--slo_deadline_miss_ratio", type=float, default=None,
                   help="minimum fraction of requests that must finish "
                   "within their end-to-end deadline (default 0.95)")
    g.add_argument("--slo_window_fast_s", type=float, default=None,
                   help="fast burn-rate window (default 30s)")
    g.add_argument("--slo_window_slow_s", type=float, default=None,
                   help="slow burn-rate window (default 300s)")
    g.add_argument("--output_dir", type=str, default=None,
                   help="export-hf: directory for the HF-format checkpoint")


def _add_fleet_args(p: argparse.ArgumentParser):
    """serve-fleet: the multi-replica router (serving/fleet.py). Every
    non-fleet flag forwards verbatim to the replica `cli serve` processes."""
    g = p.add_argument_group("serve-fleet")
    g.add_argument("--replicas", type=int, default=2,
                   help="engine replica subprocesses the router fronts")
    g.add_argument("--replica_ports", type=str, default="",
                   help="comma list of fixed replica ports (one per "
                   "--replicas); empty = ephemeral ports parsed from each "
                   "replica's listening line")
    g.add_argument("--retry_budget", type=int, default=2,
                   help="max re-dispatches per request after a replica dies "
                   "or refuses mid-flight (bounds the poison-request "
                   "cascade); each retry carries the REMAINING end-to-end "
                   "deadline and counts into the response's retried_from")
    g.add_argument("--fleet_max_pending", type=int, default=0,
                   help="fleet-wide shared admission bound (one coherent "
                   "503 fleet_saturated + Retry-After); 0 = replicas x "
                   "num_slots x 4")
    g.add_argument("--max_replica_restarts", type=int, default=3,
                   help="consecutive no-progress restarts per replica "
                   "before it is given up (fleet degrades to the remaining "
                   "capacity); completions in the dead incarnation beyond "
                   "its startup warm probe reset the budget — the shared "
                   "core/restart_policy.py table")
    g.add_argument("--replica_restart_backoff_s", type=float, default=0.5,
                   help="full-jitter backoff base for replica respawns")
    g.add_argument("--probe_interval_s", type=float, default=0.25,
                   help="per-replica /healthz probe cadence driving the "
                   "STARTING/READY/DRAINING/DEAD state machine")
    g.add_argument("--session_affinity", type=int, default=0,
                   help="1 = pin requests carrying a 'session' body key to "
                   "a stable replica (hash), falling back to least-loaded "
                   "when that replica is out")
    g.add_argument("--rolling_drain", type=int, default=1,
                   help="fleet SHUTDOWN style (SIGTERM / plain POST "
                   "/drain): 1 drains replicas one at a time so siblings "
                   "absorb shed work until the last; 0 drains all at once. "
                   "POST /drain?rolling=1 is the zero-downtime DEPLOY roll "
                   "(drain + respawn each replica, fleet keeps serving)")
    g.add_argument("--fleet_dir", type=str, default=None,
                   help="router working dir: per-replica logs + flight "
                   "dump dirs (the post-drain audit reads both)")
    g.add_argument("--replica_faults", type=str, default="",
                   help="GALVATRON_FAULTS spec installed in every REPLICA "
                   "(e.g. slow_decode_ms=25); the router's own "
                   "GALVATRON_FAULTS never leaks into replicas")


def _add_check_plan_args(p: argparse.ArgumentParser):
    """Static plan validation (analysis/plan_check.py; no device, no compile)."""
    g = p.add_argument_group("check-plan")
    g.add_argument("config_paths", nargs="*",
                   help="strategy JSON files to validate (galvatron_config schema)")
    g.add_argument("--galvatron_config_path", type=str, action="append",
                   default=None, help="additional strategy JSON (repeatable)")
    g.add_argument("--num_devices", type=int, default=0,
                   help="mesh size to validate against; 0 = the JSON's own "
                   "num_devices key (emitted by the search engine)")
    g.add_argument("--global_bsz", type=int, default=0,
                   help="global batch for the divisibility checks; 0 = the "
                   "JSON's own global_bsz key")
    g.add_argument("--memory_constraint_gb", type=float, default=0.0,
                   help="per-device budget for the feasibility check; 0 = "
                   "the JSON's own memory_constraint_gb key (else skipped)")
    g.add_argument("--strict", type=int, default=0,
                   help="1 = warnings (unknown keys, silent replication) "
                   "also fail the check")
    g.add_argument("--no_abstract_pass", type=int, default=0,
                   help="1 = skip the eval_shape/AbstractMesh sharding pass")


def _add_warmup_args(p: argparse.ArgumentParser):
    """AOT warmup sweep (aot/warmup.py): plan JSONs → compiled artifacts."""
    g = p.add_argument_group("warmup")
    g.add_argument("config_paths", nargs="*",
                   help="strategy JSON files whose programs to AOT-compile "
                   "(self-describing search-emitted configs resolve their "
                   "own model/bsz/world); none = plan-free families only "
                   "(serving, generate)")
    g.add_argument("--galvatron_config_path", type=str, action="append",
                   default=None, help="additional strategy JSON (repeatable)")
    g.add_argument("--global_train_batch_size", type=int, default=0,
                   help="0 = each plan's own global_bsz provenance key")
    g.add_argument("--compile_cache_dir", type=str, default=None,
                   help="persistent compile-artifact cache directory (the "
                   "manifest with hit/miss accounting lives beside jax's "
                   "cache entries). JAX_COMPILATION_CACHE_DIR, when set, "
                   "wins over this flag; unset = <repo>/.jax_cache "
                   "('0'/'off'/'none' disables persistence)")
    g.add_argument("--report", type=str, default=None,
                   help="write the per-program JSONL report (compile_ms, "
                   "cache_hit, memory_analysis peak buffers, GTA015 "
                   "predicted-vs-compiled memory) to this path")
    g.add_argument("--include", type=str, default="",
                   help="comma list of families/programs to warm (e.g. "
                   "'trainer' or 'train_step,serving_decode'); default all")
    g.add_argument("--force_world", type=int, default=0,
                   help="simulate an N-device CPU platform before the first "
                   "backend touch (same bootstrap as the elastic sim world) "
                   "so plans for an N-device mesh warm on any host; 0 = the "
                   "live backend")
    g.add_argument("--serialize", type=int, default=0,
                   help="1 = also persist serialized AOT executables beside "
                   "the manifest where the backend supports it")
    g.add_argument("--num_slots", type=int, default=4,
                   help="serving-family shapes: KV-cache slots")
    g.add_argument("--prefill_chunk", type=int, default=32,
                   help="serving-family shapes: prefill chunk length")
    g.add_argument("--kv_num_blocks", type=int, default=0,
                   help="serving-family shapes: paged KV pool size (0 = "
                   "slot backend programs, -1 = slot-HBM-equivalent pool); "
                   "match the serve flag or the warm artifacts miss")
    g.add_argument("--kv_block_size", type=int, default=16,
                   help="serving-family shapes: paged KV tokens per block")
    g.add_argument("--serve_quant", type=str, default="off",
                   choices=["off", "int8"],
                   help="serving-family numerics: int8 derives the quantized "
                   "params avals into every serving program key; match the "
                   "serve flag or the warm artifacts miss")
    g.add_argument("--spec_decode_k", type=int, default=0,
                   help="serving-family shapes: speculative draft length — "
                   "adds the (num_slots, 1+k) decode_verify program; match "
                   "the serve flag or the warm artifacts miss")


def _add_audit_comm_args(p: argparse.ArgumentParser):
    """HLO collective audit (analysis/comm_audit.py): lower-only, no compile."""
    g = p.add_argument_group("audit-comm")
    g.add_argument("config_paths", nargs="*",
                   help="strategy JSON files to audit (self-describing "
                   "search-emitted configs resolve their own model/bsz/world)")
    g.add_argument("--galvatron_config_path", type=str, action="append",
                   default=None, help="additional strategy JSON (repeatable)")
    g.add_argument("--global_train_batch_size", type=int, default=0,
                   help="0 = each plan's own global_bsz provenance key")
    g.add_argument("--tolerance", type=float, default=3.0,
                   help="fidelity band: predicted/lowered outside "
                   "[1/t, t] is a GTC001")
    g.add_argument("--include", type=str, default="",
                   help="comma list of families/programs to lower "
                   "(default: trainer)")
    g.add_argument("--report", type=str, default=None,
                   help="write the per-program comm-footprint JSONL to this "
                   "path (the artifact CI uploads)")
    g.add_argument("--strict", type=int, default=0,
                   help="1 = warnings (GTC002/003/005/010/011/012) also "
                   "fail the audit")


def _add_trace_export_args(p: argparse.ArgumentParser):
    """Span/flight dump → Chrome trace-event JSON (obs/tracing.py)."""
    g = p.add_argument_group("trace-export")
    g.add_argument("input_path",
                   help="a flight_<ts>.json dump (obs/flight.py) or a raw "
                   "span-record JSON list; with --merge, a DIRECTORY "
                   "searched recursively for flight_*.json dumps")
    g.add_argument("--output", "-o", type=str, default=None,
                   help="output path (default: <input>.trace.json; merge: "
                   "<dir>/merged.trace.json)")
    g.add_argument("--merge", action="store_true",
                   help="fuse every flight_*.json under input_path into ONE "
                   "Perfetto timeline (obs/correlate.py): each dump becomes "
                   "a pid-keyed track group, clocks aligned via the dumps' "
                   "epoch_wall anchors, so a fleet request's trace_id hops "
                   "router → replica → failover replica on one view. Torn "
                   "dumps are skipped with a warning, not fatal")


def _add_hardware_args(p: argparse.ArgumentParser):
    """(reference: galvatron_profile_hardware_args, core/arguments.py:186-223)"""
    g = p.add_argument_group("profile-hardware")
    g.add_argument("--profile_size_mb", type=float, default=64.0)
    g.add_argument("--hardware_output_path", type=str, default="hardware_config.json")
    g.add_argument("--num_slices", type=int, default=0,
                   help="profile on the slice-major multislice mesh so "
                   "DCN-crossing groups are measured as such (0 = "
                   "auto-detect from device slice indices)")


def build_parser(mode: str, model_default: Optional[str] = None) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(f"galvatron_tpu {mode}")
    _add_model_args(p)
    if model_default:
        p.set_defaults(model_size=model_default)
    if mode in ("train", "train_dist"):
        _add_training_args(p)
    elif mode == "search":
        _add_search_args(p)
    elif mode == "profile":
        _add_profile_args(p)
        _add_training_args(p)
    elif mode == "profile_hardware":
        _add_hardware_args(p)
    elif mode == "check_plan":
        _add_check_plan_args(p)
        # model flags come from the shared model group; None (not the preset
        # default) so the JSON's own model_size key can win when no flag is
        # given — unless a per-family entry pinned its default above
        if not model_default:
            p.set_defaults(model_size=None)
    elif mode == "warmup":
        _add_warmup_args(p)
        # every step-program flag is a program_key term: the warmup surface
        # must be able to express the exact run it is warming for
        _add_step_program_args(p)
        # same self-describing-plan default as check-plan
        if not model_default:
            p.set_defaults(model_size=None)
    elif mode == "audit_comm":
        _add_audit_comm_args(p)
        # same self-describing-plan default as check-plan
        if not model_default:
            p.set_defaults(model_size=None)
    elif mode == "trace_export":
        _add_trace_export_args(p)
    elif mode in ("generate", "serve", "export_hf"):
        _add_generate_args(p)
    elif mode == "serve_fleet":
        _add_generate_args(p)
        _add_fleet_args(p)
    else:
        raise ValueError(f"unknown mode {mode}")
    return p


def initialize_galvatron(mode: str, args: Optional[Sequence[str]] = None,
                         model_default: Optional[str] = None) -> argparse.Namespace:
    """(reference: initialize_galvatron, core/arguments.py:5-27)"""
    return build_parser(mode, model_default).parse_args(args)


def model_config_from_args(ns: argparse.Namespace, base=None):
    """Meta-config resolution (reference: config_from_meta/set_model_config,
    models/*/meta_configs/config_utils.py:13-46). ``base`` overrides the
    preset lookup (check-plan: a plan's embedded effective shape) — explicit
    CLI flags still win over it."""
    import dataclasses

    cfg = base if base is not None else PRESETS[ns.model_size]
    overrides = {}
    for field, attr in [
        ("vocab_size", "vocab_size"), ("hidden_size", "hidden_size"),
        ("num_layers", "num_layers"), ("num_heads", "num_heads"),
        ("num_kv_heads", "num_kv_heads"), ("ffn_dim", "ffn_dim"),
        ("max_seq_len", "seq_length"),
        ("enc_layers", "enc_layers"), ("enc_seq", "enc_seq"),
        ("image_size", "image_size"), ("patch_size", "patch_size"),
        ("num_classes", "num_classes"), ("swin_window", "swin_window"),
        ("moe_experts", "moe_experts"),
        ("moe_capacity_factor", "moe_capacity_factor"),
        ("moe_dense_layers", "moe_dense_layers"),
    ]:
        v = getattr(ns, attr, None)
        if v is not None:
            overrides[field] = v
    if getattr(ns, "moe_share", None):
        rank, _, of = str(ns.moe_share).partition("/")
        overrides["moe_share"] = (int(rank), int(of))
    if getattr(ns, "param_dtype", "fp32") == "bf16":  # (generate / serve)
        import jax.numpy as jnp

        overrides["param_dtype"] = jnp.bfloat16
    if getattr(ns, "swin_depths", None):
        overrides["swin_depths"] = tuple(
            int(d) for d in str(ns.swin_depths).split(",") if d
        )
    if getattr(ns, "set_model_config_manually", 0):
        required = ("vocab_size", "hidden_size", "num_layers", "num_heads")
        missing = [f for f in required if f not in overrides]
        if missing:
            raise ValueError(
                f"--set_model_config_manually 1 requires {missing} to be passed"
            )
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def resolve_attn_impl(cfg, ns: argparse.Namespace):
    """Apply --attn_impl to the model config; 'auto' = flash on accelerators,
    the model's own default on CPU. One rule shared by the trainer and the
    profiler so the profiled kernel is always the kernel training uses."""
    import jax

    impl = getattr(ns, "attn_impl", "auto")
    if impl != "auto":
        return cfg.replace(attn_impl=impl)
    if getattr(cfg, "pack_sequences", False):
        # packed sequences need the segment-masked einsum path; 'auto' must
        # not pick the flash kernels (build_runtime would refuse them loudly)
        return cfg.replace(attn_impl="xla")
    if getattr(cfg, "windowed", False):
        # a window is a mask of XLA's attention alone (mixers.limits refuses the rest)
        return cfg.replace(attn_impl="xla")
    if jax.default_backend() != "cpu":
        return cfg.replace(attn_impl="flash")
    return cfg


def resolve_execution_config(cfg, ns: argparse.Namespace):
    """Attention kernel + compute dtype from the flags — the single rule the
    trainer, the model profiler, and the search engine's in-process profiling
    all share, so the profiled program is the program training runs (the
    reference guarantees this by profiling through train_dist.py itself,
    core/profiler.py:194-240)."""
    import jax.numpy as jnp

    cfg = resolve_attn_impl(cfg, ns)
    mp = getattr(ns, "mixed_precision", None)
    if mp:
        dt = {"bf16": jnp.bfloat16, "fp16": jnp.float16, "fp32": jnp.float32}[mp]
        cfg = cfg.replace(dtype=dt)
    return cfg


def _int_list(text: str):
    """argparse type for comma-separated ints (trailing commas tolerated)."""
    try:
        out = [int(x) for x in text.split(",") if x.strip()]
    except ValueError:
        raise argparse.ArgumentTypeError(f"expected comma-separated ints, got {text!r}")
    if not out:
        raise argparse.ArgumentTypeError(f"expected comma-separated ints, got {text!r}")
    return out


def adam_config_from_args(ns: argparse.Namespace):
    """Optimizer config from the training flags — ONE construction shared by
    the trainer and the AOT prewarm (core/elastic.py): the lr/decay terms are
    burned into the compiled train_step as constants, so a prewarm built
    from different optimizer hyperparameters would warm a program the run
    never asks for."""
    from galvatron_tpu.core.optim import AdamConfig

    lr_schedule = None
    if getattr(ns, "lr_warmup_iters", 0) or getattr(ns, "lr_decay_iters", 0):
        from galvatron_tpu.core.schedules import LRSchedule

        lr_schedule = LRSchedule(
            lr=ns.lr, min_lr=ns.min_lr, warmup_iters=ns.lr_warmup_iters,
            decay_iters=ns.lr_decay_iters, decay_style=ns.lr_decay_style,
        )
    return AdamConfig(
        lr=ns.lr, weight_decay=ns.weight_decay, grad_clip=ns.grad_clip,
        lr_schedule=lr_schedule,
    )


def hybrid_config_from_args(ns: argparse.Namespace, num_layers: int, world: int):
    """GLOBAL-flags → uniform strategy, or JSON file → per-layer strategies
    (reference: the two config modes of get_hybrid_parallel_configs_api,
    core/hybrid_parallel_config.py:13-87)."""
    from galvatron_tpu.core.strategy import HybridParallelConfig

    if ns.galvatron_config_path:
        hp = HybridParallelConfig.load(ns.galvatron_config_path)
        if hp.num_layers != num_layers:
            raise ValueError(
                f"config has {hp.num_layers} layers, model has {num_layers}"
            )
    else:
        dp_type = "zero3" if ns.sdp else ns.default_dp_type
        chunks = ns.chunks if ns.chunks > 0 else default_chunks(
            ns.global_train_batch_size, ns.pp_deg, world
        )
        hp = HybridParallelConfig.uniform(
            num_layers,
            pp=ns.pp_deg,
            vpp=ns.vpp_deg,
            tp=ns.global_tp_deg,
            tp_consec=bool(ns.global_tp_consec),
            dp_type=dp_type,
            ckpt=ns.global_checkpoint,
            sp=bool(ns.sequence_parallel),
            cp=ns.context_parallel_deg,
            cp_impl=ns.context_parallel_impl,
            tp_overlap=bool(getattr(ns, "global_tp_overlap", 0)),
            grad_overlap=bool(getattr(ns, "grad_overlap", 0)),
            chunks=chunks,
            pipeline_type=ns.pipeline_type,
            vocab_tp=ns.vocab_tp,
            embed_dp_type="zero3" if ns.embed_sdp else "ddp",
            mixed_precision=ns.mixed_precision,
            mlp_recompute=getattr(ns, "mlp_recompute", "policy"),
        )
        if getattr(ns, "pp_division", None):
            hp.pp_division = ns.pp_division
    return hp


def default_chunks(global_bsz: int, pp: int, world: int) -> int:
    """Micro-batch count heuristic (reference: get_chunks,
    core/hybrid_parallel_config.py:220-230): enough chunks to keep the
    pipeline filled, bounded by the local batch."""
    if pp == 1:
        return 1
    if pp > world or world % pp != 0:
        raise ValueError(f"pp={pp} must divide the device count {world}")
    local = max(1, global_bsz // (world // pp))
    return min(local, 2 * pp)
