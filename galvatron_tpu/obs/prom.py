"""Prometheus text exposition (v0.0.4) + the headless-trainer sidecar.

Renders the stats the system already keeps — ``utils.metrics.Counters``,
``QuantileWindow`` readouts, arbitrary gauges — in the Prometheus text
format, so a scraper pointed at ``GET /metrics`` (served by ``server.py``
next to ``/healthz``, or by the ``--obs_port`` sidecar on a headless
training run) gets standard, labeled families instead of bespoke JSON.

Metric names (DESIGN.md § Observability has the full table):

  galvatron_server_requests_total{outcome=...}     server request counters
  galvatron_serving_*_total                        engine counters
  galvatron_serving_ttft_seconds{quantile=...}     TTFT readout
  galvatron_serving_{queue_depth,active_slots,occupancy,tokens_per_s}
  galvatron_train_*                                trainer sidecar gauges
  galvatron_hbm_bytes{device=...,kind=...}         HBM gauges
"""

from __future__ import annotations

import json
import math
import os
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional

CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")


def _fmt_value(v: Any) -> Optional[str]:
    if v is None:
        return None
    if isinstance(v, bool):
        return "1" if v else "0"
    f = float(v)
    if math.isnan(f):
        return "NaN"
    if math.isinf(f):
        return "+Inf" if f > 0 else "-Inf"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _escape_label(v: Any) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


class PromText:
    """Accumulates samples; emits one ``# HELP``/``# TYPE`` header per family
    (first add wins) and validates names — a malformed family would make the
    whole scrape unparseable."""

    def __init__(self, prefix: str = "galvatron_"):
        self.prefix = prefix
        self._lines: list = []
        self._declared: set = set()

    def add(self, name: str, value: Any, *, labels: Optional[Dict[str, Any]] = None,
            mtype: str = "gauge", help_: str = "") -> None:
        fv = _fmt_value(value)
        if fv is None:
            return
        full = self.prefix + name
        if not _NAME_RE.match(full):
            raise ValueError(f"invalid metric name {full!r}")
        if full not in self._declared:
            self._declared.add(full)
            if help_:
                self._lines.append(f"# HELP {full} {help_}")
            self._lines.append(f"# TYPE {full} {mtype}")
        label_s = ""
        if labels:
            for k in labels:
                if not _LABEL_RE.match(k):
                    raise ValueError(f"invalid label name {k!r}")
            label_s = (
                "{" + ",".join(f'{k}="{_escape_label(v)}"' for k, v in labels.items()) + "}"
            )
        self._lines.append(f"{full}{label_s} {fv}")

    def add_histogram(self, name: str, snap: Optional[Dict[str, Any]], *,
                      labels: Optional[Dict[str, Any]] = None,
                      help_: str = "") -> None:
        """Emit one Prometheus histogram from a ``utils.metrics.Histogram``
        snapshot: ``<name>_bucket{le=...}`` (cumulative, ending ``+Inf``),
        ``<name>_sum``, ``<name>_count``. Skipped entirely when ``snap`` is
        None/empty — an absent histogram must not emit a torn family."""
        if not snap or not snap.get("buckets"):
            return
        full = self.prefix + name
        if not _NAME_RE.match(full):
            raise ValueError(f"invalid metric name {full!r}")
        if full not in self._declared:
            self._declared.add(full)
            if help_:
                self._lines.append(f"# HELP {full} {help_}")
            self._lines.append(f"# TYPE {full} histogram")
        base = dict(labels or {})
        bounds = sorted(
            (k for k in snap["buckets"] if k != "+Inf"), key=float
        )
        for b in bounds:
            le = _fmt_value(float(b))
            self._emit_sample(f"{full}_bucket", {**base, "le": le},
                              snap["buckets"][b])
        self._emit_sample(f"{full}_bucket", {**base, "le": "+Inf"},
                          snap["buckets"]["+Inf"])
        self._emit_sample(f"{full}_sum", base, snap.get("sum", 0.0))
        self._emit_sample(f"{full}_count", base, snap.get("count", 0))

    def _emit_sample(self, full: str, labels: Dict[str, Any], value: Any) -> None:
        fv = _fmt_value(value)
        if fv is None:
            return
        label_s = ""
        if labels:
            for k in labels:
                if not _LABEL_RE.match(k):
                    raise ValueError(f"invalid label name {k!r}")
            label_s = (
                "{" + ",".join(f'{k}="{_escape_label(v)}"' for k, v in labels.items()) + "}"
            )
        self._lines.append(f"{full}{label_s} {fv}")

    def render(self) -> str:
        return "\n".join(self._lines) + "\n"


def render_hbm(out: PromText) -> None:
    from galvatron_tpu.obs.stepstats import hbm_gauges

    for key, v in hbm_gauges().items():
        dev, _, kind = key.partition("_")
        out.add("hbm_bytes", v, labels={"device": dev, "kind": kind},
                help_="per-device HBM usage where the backend reports it")


def render_slo(out: PromText, slo) -> None:
    """Emit the SLO engine's per-rule gauges (obs/slo.py): burn rates,
    breach state and totals, one row per rule. No-op when no engine is
    armed — the families simply don't exist then."""
    if slo is None:
        return
    for row in slo.gauges():
        labels = {"rule": row["rule"]}
        out.add("slo_burn_rate_fast", row.get("burn_fast"), labels=labels,
                help_="error-budget burn rate over the fast window")
        out.add("slo_burn_rate_slow", row.get("burn_slow"), labels=labels,
                help_="error-budget burn rate over the slow window")
        out.add("slo_breached", 1 if row.get("breached") else 0, labels=labels,
                help_="1 while the rule's fast AND slow burn rates exceed "
                "their thresholds")
        out.add("slo_breaches_total", row.get("breaches_total"), labels=labels,
                mtype="counter", help_="breach events raised by this rule")
        out.add("slo_value", row.get("value"), labels=labels,
                help_="rule-specific observed value (ratio or seconds)")


def server_metrics_text(service) -> str:
    """Exposition for ``server.GenerationService``: request counters, the
    legacy gate, and — with the continuous-batching engine — the full serving
    stats incl. TTFT quantiles."""
    out = PromText()
    out.add("server_uptime_seconds", time.time() - service.started_at,
            help_="seconds since the generation service started")
    for outcome, v in service.counters.snapshot().items():
        out.add("server_requests_total", v, labels={"outcome": outcome},
                mtype="counter", help_="API requests by outcome")
    if service.gate is not None:
        g = service.gate.snapshot()
        out.add("server_gate_in_use", g["in_use"])
        out.add("server_gate_capacity", g["capacity"])
        out.add("server_gate_rejected_total", g["rejected"], mtype="counter")
    out.add("server_ready", 1 if service.ready else 0,
            help_="accepting new work (0 while draining or engine dead)")
    out.add("server_draining", 1 if service.draining else 0)
    eng = service.engine
    if eng is not None:
        s = eng.stats()
        for name in ("steps", "prefill_chunks", "prefill_tokens",
                     "tokens_generated", "submitted", "admitted", "completed",
                     "failed", "expired", "expired_decode", "cancelled",
                     "cancelled_disconnect", "shed"):
            out.add(f"serving_{name}_total", s[name], mtype="counter")
        out.add("serving_rejected_queue_full_total", s["rejected_queue_full"],
                mtype="counter")
        out.add("serving_engine_restarts_total", s["engine_restarts"],
                mtype="counter",
                help_="in-process engine crash-supervision restarts "
                "(serving/resilience.py)")
        for name in ("queue_depth", "queue_capacity", "active_slots",
                     "num_slots", "occupancy", "tokens_per_s",
                     "tokens_per_s_last_step"):
            out.add(f"serving_{name}", s[name])
        out.add("serving_queue_saturated", s["queue_saturated"])
        out.add("serving_draining", s["draining"])
        for q, key in (("0.5", "ttft_p50_s"), ("0.95", "ttft_p95_s")):
            out.add("serving_ttft_seconds", s[key], labels={"quantile": q},
                    help_="time-to-first-token over the recent-request window")
        out.add("serving_max_seq_len_effective", s.get("max_seq_len_effective"),
                help_="cache capacity actually in force (a requested "
                "max_seq_len above the model's is clamped, with a warning)")
        # paged-KV backend families (serving/paged_kv.py) — absent entirely
        # under the slot backend, so a scraper keys on family presence
        if "kv_blocks_total" in s:
            out.add("kv_block_size", s["kv_block_size"],
                    help_="tokens per KV block (--kv_block_size)")
            out.add("kv_blocks_total", s["kv_blocks_total"],
                    help_="device KV block pool size, incl. the reserved "
                    "null block")
            out.add("kv_blocks_free", s["kv_blocks_free"])
            out.add("kv_blocks_cached", s["kv_blocks_cached"],
                    help_="refcount-0 prefix blocks held in the LRU "
                    "(reclaimable without losing correctness)")
            out.add("kv_blocks_active", s["kv_blocks_active"],
                    help_="blocks referenced by at least one live request")
            for name in ("hits", "misses", "evictions"):
                out.add(f"prefix_cache_{name}_total",
                        s.get(f"prefix_cache_{name}"), mtype="counter",
                        help_="prefix-cache block matches at admission "
                        "(cumulative across engine resets)"
                        if name == "hits" else "")
            out.add("kv_cow_copies_total", s.get("cow_copies"),
                    mtype="counter",
                    help_="copy-on-write block copies (shared block written)")
            for rid, held in sorted((s.get("blocks_held") or {}).items()):
                out.add("kv_blocks_held", held, labels={"rid": rid},
                        help_="blocks reserved by each live request "
                        "(rid label; rows exist only while the request "
                        "holds a slot)")
        # cumulative histograms beside the quantile gauges: quantiles are a
        # single-process readout; buckets aggregate across replicas (the
        # fleet router sums them — fleet_metrics_text)
        out.add_histogram("serving_ttft_hist_seconds", s.get("ttft_hist"),
                          help_="time-to-first-token (cumulative buckets)")
        out.add_histogram("serving_latency_hist_seconds", s.get("latency_hist"),
                          help_="request e2e latency, submit to completion "
                          "(cumulative buckets)")
        # decode-step observability: the per-ITERATION hot path, plus the
        # speculative-decoding draft economy and the quant/spec numerics
        # config the replica is actually serving under (config in labels —
        # a fleet scrape diffing this row across replicas is the cheap
        # cross-replica consistency check)
        out.add_histogram("serving_decode_step_hist_seconds",
                          s.get("decode_step_hist"),
                          help_="per-iteration decode step latency "
                          "(cumulative buckets, finer than the "
                          "request-level histograms)")
        for name in ("draft_proposed", "draft_accepted", "spec_steps",
                     "spec_fallbacks"):
            out.add(f"serving_{name}_total", s.get(name), mtype="counter",
                    help_="speculative-decoding draft tokens proposed by "
                    "the prompt-lookup drafter"
                    if name == "draft_proposed" else "")
        for name, where in (("draws_device", "on the device by the engine's "
                             "one sampler program"),
                            ("draws_host", "on the host (the speculative "
                             "engine's path)")):
            out.add(f"serving_{name}_total", s.get(name), mtype="counter",
                    help_=f"tokens drawn {where}")
        for name, what in (("steps_ahead", "decode steps dispatched before the "
                            "previous step's ids were on the host"),
                           ("row_steps_wasted", "row-steps dispatched for a row that "
                            "the bookkeeping, one iteration late, then retired")):
            out.add(f"serving_{name}_total", s.get(name), mtype="counter", help_=what)
        out.add("serving_accepted_tokens_per_step",
                s.get("accepted_tokens_per_step"),
                help_="tokens emitted per decode iteration (batched over "
                "slots, so ~active-slot width without spec); rising above "
                "that width means speculative acceptance is paying")
        out.add("serving_draft_acceptance_rate",
                s.get("draft_acceptance_rate"),
                help_="draft_accepted / draft_proposed (cumulative)")
        if "serve_quant" in s:
            out.add("serving_numerics_info", 1, labels={
                "serve_quant": s.get("serve_quant"),
                "spec_decode_k": s.get("spec_decode_k"),
                "spec_drafter": s.get("spec_drafter") or "off",
            }, help_="serving numerics/speed config (constant 1; config "
                     "in labels)")
        qp = s.get("quant_parity") or {}
        out.add("serving_quant_max_abs_logit_drift",
                qp.get("max_abs_logit_drift"),
                help_="int8-vs-fp logit drift measured on the load-time "
                "parity probe (gate bound in "
                "serving_quant_drift_bound)")
        out.add("serving_quant_drift_bound", qp.get("drift_bound"))
        out.add("serving_quant_greedy_agree_frac", qp.get("greedy_agree_frac"),
                help_="fraction of parity-probe positions whose int8 "
                "greedy token matches fp")
        # runtime lock validator counters (analysis/locks.py) — present only
        # when GALVATRON_LOCK_CHECK armed the instrumented primitives; lock
        # name in a label so one family covers the whole control plane
        for lname, row in sorted((s.get("lock_stats") or {}).items()):
            labels = {"lock": lname}
            out.add("lock_hold_ms", row.get("hold_ms"), labels=labels,
                    mtype="counter",
                    help_="cumulative milliseconds each named lock was held "
                    "(GALVATRON_LOCK_CHECK=1 only)")
            out.add("lock_contended_total", row.get("contended_total"),
                    labels=labels, mtype="counter",
                    help_="acquisitions that had to wait (uncontended "
                    "fast path failed)")
            out.add("lock_acquired_total", row.get("acquired_total"),
                    labels=labels, mtype="counter")
    render_slo(out, getattr(service, "slo", None))
    c = service.cfg
    out.add("model_info", 1, labels={
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "num_layers": c.num_layers, "num_heads": c.num_heads,
        "max_seq_len": c.max_seq_len,
    }, help_="model shape (constant 1; shape in labels)")
    render_hbm(out)
    return out.render()


def fleet_metrics_text(router) -> str:
    """Exposition for ``serving.fleet.FleetRouter``: fleet-level request
    counters, the shared admission gate, and one state/restart row per
    replica — the scrape a load balancer's operator actually needs (is the
    fleet degraded? is one replica crash-looping?)."""
    out = PromText()
    out.add("fleet_uptime_seconds", time.time() - router.started_at)
    out.add("fleet_replicas", len(router.replicas),
            help_="configured replica count")
    out.add("fleet_ready_replicas", router.ready_count(),
            help_="replicas currently dispatchable (READY + reachable)")
    out.add("fleet_ready", 1 if router.ready else 0)
    out.add("fleet_draining", 1 if router.draining else 0)
    for name, v in router.counters.snapshot().items():
        if name == "replica_restarts":
            # exposed ONLY as the per-replica labeled family below — a
            # second unlabeled sample under the same name would split the
            # family and double-count on sum()
            continue
        out.add(f"fleet_{name}_total", v, mtype="counter",
                help_="fleet router request accounting" if name == "dispatched"
                else "")
    g = router.gate.snapshot()
    out.add("fleet_gate_in_use", g["in_use"])
    out.add("fleet_gate_capacity", g["capacity"])
    for r in router.replicas:
        labels = {"idx": r.idx}
        out.add("fleet_replica_state_info", 1,
                labels={**labels, "state": r.state},
                help_="per-replica lifecycle state (in labels)")
        out.add("fleet_replica_restarts_total", r.restarts_total,
                labels=labels, mtype="counter")
        out.add("fleet_replica_outstanding", r.outstanding, labels=labels,
                help_="router-side in-flight dispatches on this replica")
        s = (r.last_health.get("serving") or {})
        out.add("fleet_replica_queue_depth", s.get("queue_depth"),
                labels=labels)
        out.add("fleet_replica_active_slots", s.get("active_slots"),
                labels=labels)
        out.add("fleet_replica_completed_total", s.get("completed"),
                labels=labels, mtype="counter",
                help_="completions of the replica's CURRENT incarnation")
    # --- aggregation: the router is the fleet's single scrape target -------
    # per-replica labeled serving families (label scheme: replica="<idx>")
    # plus fleet-level sums; TTFT/latency aggregate as HISTOGRAMS because
    # bucket counts sum across replicas — quantiles don't.
    replica_stats = [
        (r, (r.last_health.get("serving") or {})) for r in router.replicas
    ]
    for name in ("tokens_generated", "completed", "failed", "expired",
                 "prefix_cache_hits", "prefix_cache_misses",
                 "prefix_cache_evictions", "draft_proposed",
                 "draft_accepted", "spec_steps", "spec_fallbacks"):
        total = 0
        seen = False
        for r, s in replica_stats:
            v = s.get(name)
            if v is None:
                continue
            seen = True
            total += v
            out.add(f"fleet_serving_{name}_total", v,
                    labels={"replica": r.idx}, mtype="counter",
                    help_="per-replica engine counter (replica label); the "
                    "unlabeled-sum lives in fleet_serving_*_sum_total"
                    if name == "tokens_generated" else "")
        if seen:
            out.add(f"fleet_serving_{name}_sum_total", total, mtype="counter",
                    help_="sum over currently-reachable replicas")
    # per-replica-only rate gauges: a cross-replica SUM of a rate is
    # meaningless, so these get no `_sum` twin (the summable raw counters
    # draft_proposed/draft_accepted are in the counter rollup above)
    for name in ("accepted_tokens_per_step", "draft_acceptance_rate"):
        for r, s in replica_stats:
            out.add(f"fleet_serving_{name}", s.get(name),
                    labels={"replica": r.idx})
    for name in ("queue_depth", "active_slots", "tokens_per_s",
                 "kv_blocks_total", "kv_blocks_free"):
        total = 0.0
        seen = False
        for r, s in replica_stats:
            v = s.get(name)
            if v is None:
                continue
            seen = True
            total += v
            out.add(f"fleet_serving_{name}", v, labels={"replica": r.idx})
        if seen:
            out.add(f"fleet_serving_{name}_sum", total)
    from galvatron_tpu.utils.metrics import Histogram

    for hist_key, fam in (("ttft_hist", "fleet_ttft_hist_seconds"),
                          ("latency_hist", "fleet_latency_hist_seconds"),
                          ("decode_step_hist",
                           "fleet_decode_step_hist_seconds")):
        snaps = [s[hist_key] for _, s in replica_stats if s.get(hist_key)]
        for r, s in replica_stats:
            if s.get(hist_key):
                out.add_histogram(fam, s[hist_key], labels={"replica": r.idx})
        if snaps:
            out.add_histogram(
                f"{fam}_fleet",
                Histogram.merge_snapshots(snaps),
                help_="fleet-level distribution: per-replica bucket counts "
                "summed (the reason histograms exist beside the quantile "
                "gauges)")
    # lock validator rollup: per-(replica, lock) rows plus a fleet-level sum
    # per lock name — a lock hot on ONE replica (skewed traffic) and a lock
    # hot on ALL of them (systemic contention) read differently
    lock_rollup: Dict[str, List[float]] = {}
    for r, s in replica_stats:
        for lname, row in sorted((s.get("lock_stats") or {}).items()):
            labels = {"replica": r.idx, "lock": lname}
            out.add("fleet_lock_hold_ms", row.get("hold_ms"), labels=labels,
                    mtype="counter",
                    help_="cumulative lock hold milliseconds per replica "
                    "(GALVATRON_LOCK_CHECK=1 replicas only)")
            out.add("fleet_lock_contended_total", row.get("contended_total"),
                    labels=labels, mtype="counter")
            agg = lock_rollup.setdefault(lname, [0.0, 0.0])
            agg[0] += float(row.get("hold_ms") or 0.0)
            agg[1] += float(row.get("contended_total") or 0.0)
    for lname, (hold, cont) in sorted(lock_rollup.items()):
        out.add("fleet_lock_hold_ms_sum", hold, labels={"lock": lname},
                mtype="counter",
                help_="sum over currently-reachable replicas")
        out.add("fleet_lock_contended_sum_total", cont,
                labels={"lock": lname}, mtype="counter")
    render_slo(out, getattr(router, "slo", None))
    return out.render()


class TrainStats:
    """Mutable per-run gauge set the trainer updates each iteration and the
    sidecar renders on scrape. Plain attribute writes under the GIL — the
    trainer loop must not pay a lock for observability."""

    def __init__(self):
        self.started_at = time.time()
        self.iterations = 0
        self.last_loss: Optional[float] = None
        self.last_iter_ms: Optional[float] = None
        self.tokens_per_s: Optional[float] = None
        self.tflops_per_device: Optional[float] = None
        self.mfu: Optional[float] = None
        self.hfu: Optional[float] = None
        self.anomaly_skips = 0
        self.checkpoints_saved = 0
        self.packing_efficiency: Optional[float] = None
        # AOT compile subsystem (galvatron_tpu/aot): startup warmup accounting
        self.compile_cache_hits: Optional[int] = None
        self.compile_cache_misses: Optional[int] = None
        self.startup_compile_ms: Optional[float] = None
        # predicted-vs-observed step time (obs/slo.py step_time_drift rule):
        # the quantitative signal ROADMAP item 2's online re-planner triggers
        # on — positive means the plan is running slower than the cost model
        # promised
        self.predicted_iter_ms: Optional[float] = None
        self.step_time_drift: Optional[float] = None

    def render(self) -> str:
        out = PromText()
        out.add("train_uptime_seconds", time.time() - self.started_at)
        out.add("train_iterations_total", self.iterations, mtype="counter",
                help_="optimizer iterations completed this run")
        out.add("train_anomaly_skips_total", self.anomaly_skips, mtype="counter")
        out.add("train_checkpoints_saved_total", self.checkpoints_saved,
                mtype="counter")
        loss = self.last_loss
        out.add("train_last_loss", loss if loss is None or math.isfinite(loss)
                else float("nan"))
        out.add("train_last_iter_ms", self.last_iter_ms)
        out.add("train_tokens_per_s", self.tokens_per_s)
        out.add("train_tflops_per_device", self.tflops_per_device,
                help_="achieved model TFLOP/s per device")
        out.add("train_mfu", self.mfu, help_="model FLOPs utilization (PaLM convention)")
        out.add("train_hfu", self.hfu, help_="hardware FLOPs utilization (incl. remat)")
        out.add("train_packing_efficiency", self.packing_efficiency,
                help_="non-pad fraction of packed input rows (None-skipped "
                "when sequence packing is off)")
        out.add("train_compile_cache_hits", self.compile_cache_hits,
                mtype="counter",
                help_="startup AOT warmup programs served warm from the "
                "compile-artifact cache (galvatron_tpu/aot)")
        out.add("train_compile_cache_misses", self.compile_cache_misses,
                mtype="counter",
                help_="startup AOT warmup programs that paid a real XLA compile")
        out.add("train_startup_compile_ms", self.startup_compile_ms,
                help_="wall ms the startup AOT warmup spent compiling "
                "(deserialization only on a warm start)")
        out.add("train_predicted_iter_ms", self.predicted_iter_ms,
                help_="cost model's predicted step time for the active plan")
        out.add("train_step_time_drift", self.step_time_drift,
                help_="(iter_ms - predicted_ms) / predicted_ms — the re-plan "
                "trigger signal (ROADMAP item 2)")
        render_hbm(out)
        return out.render()


class ElasticStats:
    """Supervisor-side gauge set for elastic training (`core/elastic.py`):
    the ``--obs_port`` sidecar of a supervised run is owned by the
    SUPERVISOR (the child gets its port stripped — two listeners on one
    port), and what an operator needs from it is the restart story: is this
    a re-planning topology resume or a crash loop? Rendered on ``/metrics``
    and, as plain JSON, on ``/healthz`` (:meth:`health`)."""

    def __init__(self):
        self.started_at = time.time()
        self.restarts_total = 0
        self.replans_total = 0
        self.last_exit_mode: Optional[str] = None
        self.last_exit_code: Optional[int] = None
        self.watchdog_armed = False  # current child launched with --step_timeout_s
        self.child_alive = False
        self.current_plan_hash: Optional[str] = None
        self.world_size: Optional[int] = None
        self.last_step: Optional[int] = None
        # preemption-aware recovery story (core/peer_store.py tier): how
        # many times the run restored, from where, and how long it was down
        self.recoveries_total = 0
        self.last_recovery_source: Optional[str] = None  # "peer" | "disk"
        self.last_recovery_ms: Optional[float] = None
        # fleet-wide aggregation: the supervisor owns the ONLY sidecar port
        # of a supervised run, so the child's train gauges must surface here
        # — the supervisor injects --metrics_path into the child and tails
        # the newest train_iter record at scrape time (no IPC, no second
        # port; the JSONL file is already the cross-restart contract)
        self.child_metrics_path: Optional[str] = None

    def child_train_gauges(self) -> Dict[str, Any]:
        """Newest ``train_iter`` record from the child's metrics JSONL —
        read on scrape (tail ~64KB), tolerant of a torn tail and of future
        schema fields. Empty dict before the child's first iteration."""
        path = self.child_metrics_path
        if not path:
            return {}
        try:
            size = os.path.getsize(path)
            with open(path, "rb") as f:
                f.seek(max(0, size - 65536))
                lines = f.read().split(b"\n")
        except OSError:
            return {}
        for raw in reversed(lines):
            raw = raw.strip()
            if not raw:
                continue
            try:
                rec = json.loads(raw)
            except ValueError:
                continue  # torn tail mid-write: walk back one record
            if rec.get("event") == "train_iter":
                return rec
        return {}

    def health(self) -> Dict[str, Any]:
        """The ``/healthz`` JSON body — the same supervisor state, scrapeless."""
        return {
            "status": "ok",
            "restarts_total": self.restarts_total,
            "replans_total": self.replans_total,
            "last_exit_mode": self.last_exit_mode,
            "last_exit_code": self.last_exit_code,
            "watchdog_armed": self.watchdog_armed,
            "child_alive": self.child_alive,
            "current_plan_hash": self.current_plan_hash,
            "world_size": self.world_size,
            "last_step": self.last_step,
            "recoveries_total": self.recoveries_total,
            "last_recovery_source": self.last_recovery_source,
            "last_recovery_ms": self.last_recovery_ms,
        }

    def render(self) -> str:
        out = PromText()
        out.add("elastic_uptime_seconds", time.time() - self.started_at)
        out.add("elastic_restarts_total", self.restarts_total, mtype="counter",
                help_="child restarts issued by the elastic supervisor")
        out.add("elastic_replans_total", self.replans_total, mtype="counter",
                help_="topology-change re-plans (GTA017 resumes)")
        out.add("elastic_child_alive", self.child_alive)
        out.add("elastic_watchdog_armed", self.watchdog_armed,
                help_="current child runs under a --step_timeout_s hang watchdog")
        if self.last_exit_mode is not None:
            out.add("elastic_last_exit_mode_info", 1,
                    labels={"mode": self.last_exit_mode,
                            "code": self.last_exit_code},
                    help_="most recent child exit classification (mode in labels)")
        if self.current_plan_hash is not None:
            out.add("elastic_current_plan_info", 1,
                    labels={"plan_hash": self.current_plan_hash},
                    help_="plan hash the run is currently training under")
        out.add("elastic_world_size", self.world_size)
        out.add("elastic_last_step", self.last_step,
                help_="newest committed checkpoint step")
        # child train gauges, aggregated through the JSONL metrics file so a
        # pod dashboard needs ONE port for the whole supervised run
        rec = self.child_train_gauges()
        out.add("elastic_child_step", rec.get("step"),
                help_="child trainer's newest logged iteration")
        out.add("elastic_child_loss", rec.get("loss"))
        out.add("elastic_child_iter_ms", rec.get("iter_ms"))
        out.add("elastic_child_mfu", rec.get("mfu"),
                help_="child trainer's model FLOPs utilization")
        out.add("elastic_child_tokens_per_s", rec.get("tokens_per_s"))
        out.add("elastic_child_step_time_drift", rec.get("step_time_drift"),
                help_="child's predicted-vs-observed step-time drift (the "
                "re-plan trigger, surfaced at the supervisor)")
        # recovery story: restores observed across child restarts (source
        # "peer" = in-memory replica beat disk; MTTR = child death → child
        # `recovery` event, the operator's actual downtime)
        out.add("elastic_recoveries_total", self.recoveries_total,
                mtype="counter",
                help_="child restores observed (peer replica or disk)")
        if self.last_recovery_source is not None:
            out.add("elastic_last_recovery_info", 1,
                    labels={"source": self.last_recovery_source},
                    help_="where the most recent restore came from")
        out.add("elastic_last_recovery_ms", self.last_recovery_ms,
                help_="most recent MTTR: previous child exit to this "
                "child's recovery event, wall ms")
        # transient-I/O retry telemetry (core/retry.py): a rising retry
        # rate is storage flakiness BEFORE it becomes an outage
        from galvatron_tpu.core.retry import RETRY_COUNTERS

        out.add("galvatron_io_retries_total",
                RETRY_COUNTERS.get("io_retry"), mtype="counter",
                help_="transient-I/O attempts that were retried")
        out.add("galvatron_io_retry_give_ups_total",
                RETRY_COUNTERS.get("io_give_up"), mtype="counter",
                help_="retry-protected calls that exhausted their budget")
        return out.render()


class ObsServer:
    """Sidecar HTTP listener for headless runs (``--obs_port``): serves
    ``GET /metrics`` (Prometheus text from ``metrics_fn``) and ``GET
    /healthz`` on its own daemon thread, so a training job with no serving
    stack is still scrapeable. ``health_fn`` (optional) supplies the
    ``/healthz`` JSON body — the elastic supervisor publishes its restart
    state there. ``port=0`` binds an ephemeral port (tests)."""

    def __init__(self, metrics_fn: Callable[[], str], port: int = 0,
                 host: str = "127.0.0.1",
                 health_fn: Optional[Callable[[], Dict[str, Any]]] = None):
        # loopback by default, matching run_server: an unauthenticated
        # telemetry endpoint must not silently bind all interfaces
        self.metrics_fn = metrics_fn
        self.health_fn = health_fn
        obs = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):
                path = self.path.split("?")[0].rstrip("/")
                try:
                    if path == "/metrics":
                        body = obs.metrics_fn().encode()
                        ctype = CONTENT_TYPE
                    elif path == "/healthz":
                        doc = obs.health_fn() if obs.health_fn else {"status": "ok"}
                        body = json.dumps(doc).encode()
                        ctype = "application/json"
                    else:
                        body = b'{"error": "use /metrics or /healthz"}'
                        self._send(404, body, "application/json")
                        return
                    self._send(200, body, ctype)
                except Exception as e:  # noqa: BLE001 — scrape must not kill the run
                    self._send(500, f"# render error: {e}\n".encode(), "text/plain")

            def _send(self, code, body, ctype):
                try:
                    self.send_response(code)
                    self.send_header("Content-Type", ctype)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                except (BrokenPipeError, ConnectionResetError, OSError):
                    self.close_connection = True

            def log_message(self, *a):  # quiet
                pass

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="obs-sidecar", daemon=True
        )
        self._thread.start()

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=10)
