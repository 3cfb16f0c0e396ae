"""Elastic training: a preemption-aware supervisor around ``train()``.

Production TPU pods change topology under a run — preemptions, slice
shrinks, maintenance events are the dominant failure mode (`core/faults.py`
says so; Varuna EuroSys '21 and Bamboo NSDI '23 build whole systems around
it). PR 1 made single-topology crashes survivable and the observability
layer made runs legible; this module closes the loop by treating a topology
change as a *re-search event*: when the world shrinks from 8 to 4 devices,
re-run the DP for the new mesh and resume the portable checkpoint under
the new plan.

Two entry points:

- **supervisor** (``cli run-elastic`` → :func:`run_elastic`): spawns the
  training run as a child process, classifies every exit, and decides
  restart / backoff / give-up. It deliberately never touches the JAX
  backend (on a real pod the child owns the devices), so all
  topology-sensitive work happens child-side.
- **child** (``python -m galvatron_tpu.core.elastic child …`` →
  :func:`child_main`): compares the checkpoint's topology fingerprint
  against the live ``jax.device_count()`` (GTA017), re-plans on mismatch
  (`search/replan.py`: cache hit or a fresh ``SearchEngine`` run), then
  runs ``train()`` — which resumes via ``restore_checkpoint_portable``
  (resharding is free) with the data cursor converted from the batch
  domain to the sample domain (trainer) — and exits with a
  mode-describing code.

Exit-code contract (child → supervisor)::

    0                 completed      train_iters reached; supervision done
    75 EXIT_PREEMPTED preempted      SIGTERM/SIGINT observed; state saved
    76 EXIT_ANOMALY   anomaly_abort  AnomalyAbort (NaN budget exhausted)
    77 EXIT_HANG      hang           watchdog-declared stalled step
    78 EXIT_REPLAN_INFEASIBLE        no plan fits the live topology
    anything else     crash          unhandled exception / hard kill

Decisions: ``completed`` ends the run; ``anomaly_abort`` and
``replan_infeasible`` give up immediately (the skip budget already proved
restarting replays the same poison — resume never re-grants skips — and
an infeasible re-search is deterministic); ``preempted`` restarts immediately
(the child checkpointed; backoff would only waste the pod); ``crash`` and
``hang`` restart under `core/retry.py`-style exponential backoff with full
jitter, bounded by ``--max_restarts`` *consecutive restarts without
progress* — a newer committed checkpoint step resets the crash-loop
counter, so a month-long run is never budgeted like a boot loop. Every
decision is a tracer event, a JSONL record (``<save>/elastic_events.jsonl``)
and a flight-recorder note.

Chaos simulation: ``GALVATRON_FAULTS`` is handed to the FIRST child only
(the injected fault happens once; recovery must then be fault-free), and
``GALVATRON_FAULTS_WORLD="8,4"`` runs child k on a virtual CPU platform of
the k-th width (clamped to the last entry) — a reproducible 8→4 shrink on
any host, across real process restarts.

Preemption-aware extensions (this supervisor side):

- ``--peer_replicate N`` spawns N in-memory peer-store daemons
  (`core/peer_store.py`) standing in for surviving hosts' RAM; every child
  gets their addresses (``GALVATRON_PEER_STORE``) and ring-replicates each
  interval save. A child killed without grace then restores from the
  replica — newer than anything disk holds when storage was out.
- ``--heartbeat_timeout_s T`` makes the default spawn a monitored
  ``Popen``: the child beats ``<save>/heartbeat`` every step
  (``GALVATRON_HEARTBEAT_FILE``) and a stale beat gets the child SIGKILLed
  and accounted as a hang — the last line of defense when the child is too
  wedged for its own in-process watchdog.
- a graceful preemption WITH progress is a *free* restart
  (`core/restart_policy.py`): spot capacity can be evicted more than
  ``--max_restarts`` times in a healthy week.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

from galvatron_tpu.core.watchdog import EXIT_HANG

EXIT_COMPLETED = 0
EXIT_PREEMPTED = 75  # EX_TEMPFAIL: the child saved and expects to be rerun
EXIT_ANOMALY = 76
# no feasible plan exists for the live topology under the re-plan budget:
# restarting would re-run the identical doomed search — supervisor gives up
EXIT_REPLAN_INFEASIBLE = 78

_EXIT_MODES = {
    EXIT_COMPLETED: "completed",
    EXIT_PREEMPTED: "preempted",
    EXIT_ANOMALY: "anomaly_abort",
    EXIT_HANG: "hang",
    EXIT_REPLAN_INFEASIBLE: "replan_infeasible",
}

#: child-side env var: force an N-device virtual CPU platform (set by the
#: supervisor from GALVATRON_FAULTS_WORLD; never set on real hardware)
SIM_WORLD_ENV = "GALVATRON_ELASTIC_SIM_WORLD"


def classify_exit(returncode: int) -> str:
    """Child exit → mode name (negative = killed by signal = crash)."""
    return _EXIT_MODES.get(returncode, "crash")


# ---------------------------------------------------------------------------
# child
# ---------------------------------------------------------------------------


def _bootstrap_sim_world() -> None:
    """Apply the supervisor's simulated-topology override BEFORE the first
    backend touch. Delegates to ``aot/warmup.force_cpu_world`` — the one
    copy of the XLA_FLAGS + platform-pin recipe (the program key hashes the
    resulting XLA_FLAGS tokens, so the warmup and elastic recipes must
    never drift apart)."""
    n = os.environ.get(SIM_WORLD_ENV)
    if not n:
        return
    from galvatron_tpu.aot.warmup import force_cpu_world

    force_cpu_world(int(n))


def prepare_topology(ns, verbose: bool = True) -> Optional[Dict[str, Any]]:
    """Child-side pre-train resolution of a topology change.

    Reads the newest committed checkpoint's topology fingerprint and
    compares it with the live device count. On mismatch (GTA017) a plan for
    the live mesh is resolved — from the plan caches or a fresh search —
    validated, and installed as ``ns.galvatron_config_path``;
    ``ns.allow_topology_change`` marks the resume as supervised so the
    trainer's own GTA017 gate admits it. Returns a summary dict when a
    re-plan happened, else None."""
    load = getattr(ns, "load", None)
    if not load:
        return None
    fp = _read_fingerprint(load)
    if not fp:
        return None  # no committed step, or a pre-elastic checkpoint

    import jax

    from galvatron_tpu.analysis import plan_check
    from galvatron_tpu.analysis.diagnostics import format_report
    from galvatron_tpu.obs.tracing import tracer

    world = jax.device_count()
    diags = plan_check.check_topology_fingerprint(fp, world, source=load)
    if not diags:
        # same topology: keep PLAN CONTINUITY. After an earlier restart
        # re-planned (shrink), this restart sees a matching world and the
        # ORIGINAL argv flags — which describe the pre-shrink plan; without
        # this, one more crash silently abandons the re-searched strategy.
        adopt_recorded_plan(ns, fp, world, verbose=verbose)
        return None
    # topology changed: this is the re-search event
    if verbose:
        print(format_report(diags))
    from galvatron_tpu.core.arguments import (
        model_config_from_args,
        resolve_execution_config,
    )
    from galvatron_tpu.search.replan import resolve_plan_for_topology

    cfg = resolve_execution_config(model_config_from_args(ns), ns)
    from galvatron_tpu.search.replan import default_cache_dirs

    replan_dir = os.path.join(os.path.abspath(load), "replans")
    plan_path, source = resolve_plan_for_topology(
        cfg,
        world,
        int(ns.global_train_batch_size),
        cache_dirs=default_cache_dirs(load),
        out_dir=replan_dir,
        model_name=getattr(ns, "model_size", "") or "",
        search_space=getattr(ns, "replan_search_space", "full"),
        memory_gb=getattr(ns, "replan_memory_gb", 16.0),
        mixed_precision=getattr(ns, "mixed_precision", "bf16"),
        verbose=verbose,
    )
    # validate against the LIVE topology before handing it to the trainer
    # (a cached plan passed check_plan in the lookup; a searched one was
    # self-checked by save_result — this re-check is the belt to those
    # braces, and gives file provenance on failure)
    plan_check.ensure_valid(
        plan_path, model_config=cfg, world_size=world,
        global_bsz=ns.global_train_batch_size,
        memory_budget_mb=getattr(ns, "replan_memory_gb", 16.0) * 1024.0,
        context=f"re-planned strategy invalid for the live mesh: {plan_path}",
        verbose=verbose,
    )
    ns.galvatron_config_path = plan_path
    ns.allow_topology_change = True
    tracer.instant(
        "replan", old_world=fp.get("world_size"), new_world=world,
        plan=plan_path, source=source,
    )
    info = {
        "old_world": fp.get("world_size"),
        "new_world": world,
        "plan_path": plan_path,
        "source": source,
        "old_plan_hash": fp.get("plan_hash"),
    }
    # prewarm the NEW plan's programs as part of the re-plan, BEFORE
    # training starts (galvatron_tpu/aot): restart downtime under a fresh
    # strategy becomes a cache lookup, and the trainer's startup consult
    # then proves the programs warm — shrinking the watchdog's first-step
    # compile grace to the normal deadline
    info["prewarm"] = _prewarm_plan(ns, plan_path, verbose=verbose)
    if verbose:
        print(
            f"topology change: {fp.get('world_size')} → {world} devices; "
            f"resuming under {plan_path} ({source})"
        )
    return info


def _prewarm_plan(ns, plan_path: str, verbose: bool = True) -> Optional[Dict[str, Any]]:
    """AOT-compile the plan's trainer programs into the compile-artifact
    cache (aot/warmup.py).  Best-effort by contract: a prewarm failure costs
    only warmth — the child trains exactly as it would have cold."""
    from galvatron_tpu.aot.cache import resolve_compile_cache_dir

    cache_dir = resolve_compile_cache_dir(ns)
    if not cache_dir:
        return None
    try:
        from galvatron_tpu.aot import warmup as aot_warmup
        from galvatron_tpu.aot.cache import ArtifactStore, enable_persistent_cache
        from galvatron_tpu.core.arguments import (
            adam_config_from_args,
            model_config_from_args,
            resolve_execution_config,
        )
        from galvatron_tpu.core.strategy import HybridParallelConfig
        from galvatron_tpu.obs.tracing import tracer

        # mirror the trainer's own config resolution (pack_sequences rides
        # the model config BEFORE attention resolution) so the prewarmed
        # programs are the programs the run will ask for
        cfg = model_config_from_args(ns)
        if getattr(ns, "pack_sequences", 0):
            cfg = cfg.replace(pack_sequences=True)
        cfg = resolve_execution_config(cfg, ns)
        store = ArtifactStore(enable_persistent_cache(cache_dir))
        # train_step only: a re-planned child RESUMES (restore, never init),
        # and eval_loss belongs to `cli warmup` — the step program is the
        # whole first-step compile the restart would otherwise pay
        reports = aot_warmup.warmup_plan(
            cfg, HybridParallelConfig.load(plan_path),
            global_bsz=int(ns.global_train_batch_size),
            store=store, include=("train_step",),
            adam=adam_config_from_args(ns), verbose=verbose,
        )
        # hand the SAME store to the trainer: its startup consult now
        # reports hits and arms the reduced first-step watchdog grace
        ns.compile_cache_dir = store.dir
        summ = aot_warmup.summarize(reports)
        tracer.instant("replan_prewarm", **summ)
        if verbose:
            print(
                f"re-plan prewarm: {summ['compiled']}/{summ['programs']} "
                f"programs warm ({summ['total_compile_ms']:.0f} ms compile)"
            )
        return summ
    except Exception as e:  # noqa: BLE001 — warmth is optional, training is not
        print(f"re-plan prewarm failed (continuing cold): "
              f"{type(e).__name__}: {str(e)[:200]}")
        return None


def adopt_recorded_plan(ns, fp: Dict[str, Any], world: int,
                        verbose: bool = True) -> Optional[str]:
    """Same-topology restart: if the checkpoint's recorded ``plan_hash``
    differs from the plan the argv flags produce, adopt the cached plan
    file with that hash (``<ckpt>/replans/`` first, then
    ``configs/strategies/``) so the run keeps training the strategy it was
    actually on. No hash-matching file → the argv plan proceeds (a legal
    cross-plan resume; the trainer logs ``plan_change``). Returns the
    adopted path, or None."""
    want = fp.get("plan_hash")
    if not want or not getattr(ns, "load", None):
        return None
    from galvatron_tpu.core.arguments import (
        hybrid_config_from_args,
        model_config_from_args,
        resolve_execution_config,
    )
    from galvatron_tpu.core.strategy import plan_hash

    try:
        cfg = resolve_execution_config(model_config_from_args(ns), ns)
        if plan_hash(hybrid_config_from_args(ns, cfg.total_layers, world)) == want:
            return None  # argv already describes the recorded plan
    except Exception:
        return None  # argv plan undecodable here: the trainer will report it
    from galvatron_tpu.search.replan import default_cache_dirs, find_plan_by_hash

    path = find_plan_by_hash(default_cache_dirs(ns.load), want)
    if path is not None:
        ns.galvatron_config_path = path
        if verbose:
            print(f"plan continuity: resuming under the checkpoint's "
                  f"recorded plan {path}")
    return path


def child_main(argv: List[str], model_default: Optional[str] = None) -> int:
    """One supervised training attempt; returns the exit-contract code.

    Everything that must see the live backend happens here: the simulated-
    world bootstrap, the fingerprint comparison, the re-plan, and
    ``train()`` itself. ``AnomalyAbort`` maps to its code; any other
    exception prints its traceback and maps to a hard crash (nonzero from
    ``__main__``)."""
    _bootstrap_sim_world()
    from galvatron_tpu.core.arguments import initialize_galvatron
    from galvatron_tpu.core.resilience import AnomalyAbort

    ns = initialize_galvatron("train", argv, model_default)
    # a supervised child running under the hang watchdog consults the
    # compile-artifact cache automatically: the warm hint exists to shrink
    # the watchdog's blind first-step compile grace, and the restart
    # lifecycle is exactly where a warm program cache pays. Without a
    # watchdog the consult stays opt-in (--compile_cache_dir) — the first
    # step then compiles lazily exactly as before, still served by any
    # configured persistent cache. The re-plan path prewarms + arms the
    # consult regardless (prepare_topology).
    if not getattr(ns, "compile_cache_dir", None) and getattr(ns, "step_timeout_s", 0):
        from galvatron_tpu.aot.cache import resolve_compile_cache_dir

        resolved = resolve_compile_cache_dir(ns)
        if resolved:
            ns.compile_cache_dir = resolved
    from galvatron_tpu.search.replan import ReplanInfeasibleError

    try:
        prepare_topology(ns)
        from galvatron_tpu.core.trainer import train

        out = train(ns)
    except AnomalyAbort as e:
        print(f"anomaly abort: {e}", file=sys.stderr, flush=True)
        return EXIT_ANOMALY
    except ReplanInfeasibleError as e:
        print(f"re-plan infeasible: {e}", file=sys.stderr, flush=True)
        return EXIT_REPLAN_INFEASIBLE
    if out.get("signaled") is not None:
        return EXIT_PREEMPTED
    return EXIT_COMPLETED


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------


def _read_fingerprint(save_dir: Optional[str]) -> Dict[str, Any]:
    """Newest committed checkpoint's fingerprint meta — pure file reads, no
    backend; shared by the supervisor's gauges and the child's GTA017 gate
    (one extraction, so the two views cannot diverge). Empty dict when
    there is no committed step or the checkpoint predates fingerprints."""
    if not save_dir:
        return {}
    from galvatron_tpu.core.checkpoint import latest_step, read_manifest, step_path

    step = latest_step(save_dir)
    if step is None:
        return {}
    m = read_manifest(step_path(save_dir, step))
    meta = m.get("meta") if m and isinstance(m.get("meta"), dict) else {}
    fp = meta.get("fingerprint")
    return fp if isinstance(fp, dict) else {}


def child_pythonpath_env(base_env: Dict[str, str]) -> Dict[str, str]:
    """Child-process env with the repo root on PYTHONPATH regardless of the
    child's cwd. Join only a NON-EMPTY inherited value: "<root>:" would put
    an empty entry — i.e. the child's cwd — on sys.path, letting a stray
    json.py in the operator's launch dir shadow the stdlib only inside
    children. Shared by this supervisor and the fleet router's replica
    spawns (serving/fleet.py) — one copy of the rule."""
    env = dict(base_env)
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    prior = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = root + os.pathsep + prior if prior else root
    return env


def _child_env(base_env: Dict[str, str], attempt: int, worlds: List[int]) -> Dict[str, str]:
    env = child_pythonpath_env(base_env)
    if worlds:
        env[SIM_WORLD_ENV] = str(worlds[min(attempt, len(worlds) - 1)])
    if attempt > 0:
        # chaos injection is one-shot: the fault happened; the restarted
        # child proves RECOVERY, and re-arming kill_mid_save=1 in every
        # child would turn one injected fault into an injected crash loop
        env.pop("GALVATRON_FAULTS", None)
    return env


def run_elastic(
    argv: List[str],
    model_default: Optional[str] = None,
    spawn=None,
) -> int:
    """The supervisor loop (``cli run-elastic``). Returns a process exit
    code: 0 when a child completed, 1 on give-up (anomaly abort, restart
    budget exhausted, or a re-plan that found nothing feasible).

    ``spawn`` (tests) replaces the subprocess launch: a callable
    ``(cmd, env) -> returncode``."""
    from galvatron_tpu.core import faults
    from galvatron_tpu.core.arguments import initialize_galvatron
    from galvatron_tpu.core.restart_policy import RestartPolicy
    from galvatron_tpu.obs.tracing import tracer
    from galvatron_tpu.utils.metrics import MetricsLogger

    ns = initialize_galvatron("train", argv, model_default)
    # supervisor decisions are forensic events: with crash forensics asked
    # for (--flight_dir / --trace_spans) the tracer ring records them; the
    # JSONL event log below is unconditional when --save exists
    own_tracer = False
    if getattr(ns, "flight_dir", None) or getattr(ns, "trace_spans", None):
        if not tracer.enabled:
            tracer.enable(capacity=getattr(ns, "trace_ring", 4096))
            own_tracer = True
    events = MetricsLogger(
        os.path.join(ns.save, "elastic_events.jsonl") if ns.save else None
    )
    from galvatron_tpu.obs.prom import ElasticStats, ObsServer

    stats = ElasticStats()
    stats.watchdog_armed = bool(getattr(ns, "step_timeout_s", 0))
    obs_server = None
    if getattr(ns, "obs_port", 0):
        # the SUPERVISOR owns the sidecar port (the child gets --obs_port 0
        # appended — two listeners on one port is a bind error): an operator
        # scraping a supervised run needs the restart story, not one
        # child-lifetime of gauges that dies with every preemption
        obs_server = ObsServer(stats.render, port=ns.obs_port, health_fn=stats.health)
        run_elastic.last_obs_port = obs_server.port  # tests scrape the ephemeral port
        print(f"elastic supervisor sidecar: http://127.0.0.1:{obs_server.port}/healthz")
        # child train-gauge aggregation: the child logs train_iter JSONL to
        # --metrics_path and the sidecar tails the last 64KB at scrape time
        # (prom.ElasticStats.child_train_gauges) — mfu/tokens_per_s
        # survive on the supervisor's scrape target across child restarts
        # with no IPC and no second port. A user-passed --metrics_path is
        # honored; otherwise one is injected beside the checkpoints.
    # the child metrics JSONL is always placed (sidecar or not): the
    # supervisor's recovery accounting below tails it for the child's
    # `recovery` events, which is how MTTR becomes a supervisor-side fact
    if getattr(ns, "metrics_path", None):
        stats.child_metrics_path = ns.metrics_path
    elif ns.save:
        stats.child_metrics_path = os.path.join(ns.save, "train_metrics.jsonl")
    worlds = faults.world_schedule()
    # the shared supervisor decision table (core/restart_policy.py):
    # consecutive-no-progress budget, progress-resets-streak, full-jitter
    # backoff — identical arithmetic to the serving EngineSupervisor and
    # the fleet router's replica supervision
    policy = RestartPolicy(
        max_restarts=ns.max_restarts,
        backoff_s=ns.restart_backoff_s,
        backoff_cap_s=ns.restart_backoff_cap_s,
    )
    user_spawn = spawn is not None
    if spawn is None:
        spawn = lambda c, env: subprocess.call(c, env=env)  # noqa: E731

    def _child_cmd() -> List[str]:
        # the preemption lifecycle IS resume: once the run's own --save dir
        # holds a committed step, every child restarts from it (overriding,
        # argparse last-wins, an explicit --load warm start that is now
        # stale). Before the first save, the user's --load (or a fresh
        # init) applies.
        child_argv = list(argv) + ["--obs_port", "0"]
        if stats.child_metrics_path and not getattr(ns, "metrics_path", None):
            # injected (not user-passed): give the child the sidecar's
            # tail target so its train_iter gauges aggregate upward
            child_argv += ["--metrics_path", stats.child_metrics_path]
        if ns.save and (
            not getattr(ns, "load", None) or _last_step(ns.save) is not None
        ):
            child_argv += ["--load", ns.save]
        return [sys.executable, "-m", "galvatron_tpu.core.elastic", "child"] + child_argv

    def note(event: str, **fields):
        events.log(event, **fields)
        tracer.instant(f"elastic_{event}", **fields)

    # --- in-memory peer replica tier (--peer_replicate N) ---------------
    # N peer-store daemons stand in for the OTHER hosts of the slice: their
    # RAM outlives any one child, so a child killed without grace restores
    # from its ring neighbor instead of the last disk commit. Best-effort
    # by contract — a daemon that fails to come up degrades the run to
    # disk-only, it never blocks training.
    from galvatron_tpu.core import peer_store as peer_store_mod

    peer_n = int(getattr(ns, "peer_replicate", 0) or 0)
    peer_procs: List[subprocess.Popen] = []
    peer_addrs: List[str] = []
    if peer_n > 0:
        import tempfile

        ann_dir = tempfile.mkdtemp(prefix="galvatron_peers_")
        try:
            for i in range(peer_n):
                ann = os.path.join(ann_dir, f"peer{i}.addr")
                peer_procs.append(subprocess.Popen(
                    [sys.executable, "-m", "galvatron_tpu.core.peer_store",
                     "serve", "--announce", ann],
                    env=child_pythonpath_env(os.environ),
                ))
                deadline = time.monotonic() + 30.0
                while not (os.path.exists(ann) and os.path.getsize(ann)):
                    if peer_procs[-1].poll() is not None:
                        raise RuntimeError(
                            f"peer store {i} exited rc={peer_procs[-1].returncode}"
                        )
                    if time.monotonic() > deadline:
                        raise RuntimeError(f"peer store {i} never announced")
                    time.sleep(0.05)
                with open(ann) as f:
                    peer_addrs.append(f.read().strip())
            note("peer_store_start", count=peer_n,
                 addrs=",".join(peer_addrs))
        except Exception as e:  # noqa: BLE001 — RAM tier is optional
            print(f"run-elastic: peer stores unavailable ({e}); "
                  f"continuing disk-only", file=sys.stderr, flush=True)
            for p in peer_procs:
                p.kill()
            peer_procs, peer_addrs = [], []

    # --- supervisor-side heartbeat watchdog (--heartbeat_timeout_s) -----
    from galvatron_tpu.core.watchdog import HEARTBEAT_ENV, HeartbeatMonitor

    hb_timeout = float(getattr(ns, "heartbeat_timeout_s", 0) or 0)
    hb_path = None
    if hb_timeout > 0:
        hb_path = (
            os.path.join(ns.save, "heartbeat") if ns.save
            else os.path.join(
                __import__("tempfile").gettempdir(),
                f"galvatron_hb_{os.getpid()}",
            )
        )
        stats.watchdog_armed = True
    if hb_timeout > 0 and not user_spawn:
        def spawn(cmd, env, _hb=hb_path):  # noqa: F811 — monitored default
            # fresh file per child: a stale beat from the previous
            # incarnation must not vouch for this one
            try:
                os.remove(_hb)
            except OSError:
                pass
            mon = HeartbeatMonitor(
                _hb,
                # the first beat waits out XLA compilation — same
                # compile-length grace reasoning as HangWatchdog's warmup
                first_beat_grace_s=max(20.0 * hb_timeout, 120.0),
            )
            proc = subprocess.Popen(cmd, env=env)
            poll_s = max(0.05, min(0.5, hb_timeout / 4.0))
            while True:
                rc = proc.poll()
                if rc is not None:
                    return rc
                if mon.stale(hb_timeout):
                    age = mon.last_beat_age_s()
                    note("watchdog_kill", reason="heartbeat_stale",
                         age_s=None if age is None else round(age, 2),
                         timeout_s=hb_timeout)
                    print(
                        f"run-elastic: child heartbeat stale "
                        f"(> {hb_timeout}s); killing child",
                        file=sys.stderr, flush=True,
                    )
                    proc.kill()
                    proc.wait()
                    return EXIT_HANG
                time.sleep(poll_s)

    attempt = 0  # children launched so far
    rc_final = 1
    prev_exit_ts: Optional[float] = None  # wall time the last child died
    recovery_seen_ts = 0.0  # newest child `recovery` event already counted
    note("supervisor_start", max_restarts=ns.max_restarts,
         step_timeout_s=float(getattr(ns, "step_timeout_s", 0) or 0),
         sim_worlds=",".join(map(str, worlds)) if worlds else None)
    try:
        while True:
            prev_step = _last_step(ns.save)
            env = _child_env(os.environ, attempt, worlds)
            if peer_addrs:
                env[peer_store_mod.ADDRS_ENV] = ",".join(peer_addrs)
                env[peer_store_mod.RANK_ENV] = "0"
            if hb_path:
                env[HEARTBEAT_ENV] = hb_path
            stats.child_alive = True
            stats.world_size = int(env[SIM_WORLD_ENV]) if SIM_WORLD_ENV in env else None
            note("child_start", attempt=attempt,
                 world=stats.world_size, resumed_from=prev_step)
            rc = spawn(_child_cmd(), env)
            stats.child_alive = False
            exit_ts = time.time()
            # recovery accounting: the child logs a `recovery` event when it
            # restored (peer replica or disk); MTTR is that event's wall
            # time minus the PREVIOUS child's death — the operator's "how
            # long was the run actually down".
            for ev in _scan_recoveries(stats.child_metrics_path,
                                       recovery_seen_ts):
                recovery_seen_ts = max(recovery_seen_ts, float(ev.get("ts") or 0.0))
                stats.recoveries_total += 1
                stats.last_recovery_source = ev.get("source")
                mttr_ms = None
                if prev_exit_ts is not None and isinstance(
                    ev.get("ts"), (int, float)
                ):
                    mttr_ms = max(0.0, (ev["ts"] - prev_exit_ts) * 1000.0)
                    stats.last_recovery_ms = mttr_ms
                note("recovery_observed", source=ev.get("source"),
                     step=ev.get("step"),
                     mttr_ms=None if mttr_ms is None else round(mttr_ms, 1))
            prev_exit_ts = exit_ts
            mode = classify_exit(rc)
            new_step = _last_step(ns.save)
            progressed = new_step is not None and (
                prev_step is None or new_step > prev_step
            )
            fp = _read_fingerprint(ns.save)
            stats.last_exit_mode = mode
            stats.last_exit_code = rc
            stats.last_step = new_step
            if fp.get("plan_hash"):
                if stats.current_plan_hash not in (None, fp["plan_hash"]):
                    stats.replans_total += 1
                stats.current_plan_hash = fp["plan_hash"]
            note("child_exit", attempt=attempt, code=rc, mode=mode,
                 step=new_step, progressed=progressed,
                 plan_hash=fp.get("plan_hash"))
            attempt += 1
            if mode == "completed":
                print(f"run-elastic: completed after {attempt} attempt(s), "
                      f"{stats.restarts_total} restart(s)")
                note("supervisor_done", attempts=attempt,
                     restarts=stats.restarts_total, step=new_step)
                rc_final = 0
                break
            if mode == "anomaly_abort":
                # the skip budget is already resume-aware (never re-granted):
                # restarting replays the same poisoned data into an
                # exhausted budget — a decision only an operator can change
                print("run-elastic: giving up — anomaly abort (NaN skip "
                      "budget exhausted; restarting would replay the same "
                      "data)", file=sys.stderr, flush=True)
                note("give_up", reason="anomaly_abort", attempts=attempt)
                break
            if mode == "replan_infeasible":
                # deterministic: the identical search would fail on every
                # restart — only --replan_memory_gb / a bigger mesh fixes it
                print("run-elastic: giving up — no feasible plan for the "
                      "live topology under --replan_memory_gb",
                      file=sys.stderr, flush=True)
                note("give_up", reason="replan_infeasible", attempts=attempt)
                break
            # preempted children checkpointed and exited on a signal: restart
            # immediately — a preemption is the *expected* lifecycle, and
            # backoff here only donates pod-hours to the void (the failure
            # still counts against the no-progress budget)
            decision = policy.on_failure(
                progressed, immediate=(mode == "preempted"),
                # a graceful preemption that made progress is the platform's
                # EXPECTED lifecycle, not a failure of the run: it costs no
                # restart budget (spot capacity can be evicted more than
                # --max_restarts times in a healthy week). Preemptions
                # WITHOUT progress still count — a preempt-loop that never
                # advances must exhaust the budget.
                free=(mode == "preempted" and progressed),
            )
            if decision.give_up:
                print(f"run-elastic: giving up — {decision.consecutive} "
                      f"consecutive restarts without progress "
                      f"(--max_restarts {ns.max_restarts})",
                      file=sys.stderr, flush=True)
                note("give_up", reason="restart_budget", attempts=attempt,
                     consecutive=decision.consecutive)
                break
            # the eviction notice belongs to the OLD placement: a real
            # rescheduled host starts with a clean metadata flag, so the
            # supervisor clears the simulated one — a stale notice would
            # make every restarted child drain immediately, a preempt loop
            # that never advances
            notice_path = getattr(ns, "preempt_notice_file", None) or \
                os.environ.get("GALVATRON_PREEMPT_NOTICE")
            if mode == "preempted" and notice_path:
                try:
                    os.remove(notice_path)
                    note("preempt_notice_cleared", path=notice_path)
                except FileNotFoundError:
                    pass
                except OSError:
                    pass
            delay = decision.backoff_s
            stats.restarts_total += 1
            note("restart", attempt=attempt, mode=mode,
                 consecutive=decision.consecutive, backoff_s=round(delay, 3))
            print(f"run-elastic: child exit {rc} ({mode}); restart "
                  f"{stats.restarts_total} in {delay:.2f}s")
            if delay:
                time.sleep(delay)
    finally:
        if ns.save and getattr(ns, "flight_dir", None):
            from galvatron_tpu.obs.flight import dump_flight

            dump_flight(
                ns.flight_dir, tracer,
                reason=f"supervisor exit rc={rc_final} "
                       f"(last child: {stats.last_exit_mode})",
                extra={"restarts_total": stats.restarts_total},
            )
        # peer-store daemons die with their supervisor: their whole point is
        # RAM that outlives any one CHILD — an orphaned daemon after the
        # run would just hold a stale replica nobody can restore
        for p in peer_procs:
            try:
                p.terminate()
                p.wait(timeout=5)
            except Exception:  # noqa: BLE001 — teardown is best-effort
                p.kill()
        events.close()
        if obs_server is not None:
            obs_server.close()
        if own_tracer:
            tracer.disable()
            tracer.clear()
    return rc_final


def _scan_recoveries(metrics_path: Optional[str],
                     since_ts: float) -> List[Dict[str, Any]]:
    """Child ``recovery`` events newer than ``since_ts`` from the child's
    train-metrics JSONL. Pure file read, tolerant of a missing/torn file —
    recovery accounting must never take down the supervisor."""
    if not metrics_path or not os.path.exists(metrics_path):
        return []
    from galvatron_tpu.utils.metrics import read_metrics

    try:
        recs = read_metrics(metrics_path)
    except Exception:  # noqa: BLE001 — accounting is best-effort
        return []
    return [
        r for r in recs
        if r.get("event") == "recovery"
        and float(r.get("ts") or 0.0) > since_ts
    ]


def _last_step(save_dir: Optional[str]) -> Optional[int]:
    if not save_dir:
        return None
    from galvatron_tpu.core.checkpoint import latest_step

    return latest_step(save_dir)


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "child":
        return child_main(argv[1:])
    return run_elastic(argv)


if __name__ == "__main__":
    raise SystemExit(main())
