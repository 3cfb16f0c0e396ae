"""Elastic training: the preemption-aware supervisor proven end-to-end.

The chaos proofs spawn REAL child processes (a topology change needs a
fresh backend, exactly like a real restart): a run killed mid-step resumes
on a *different device count* under a freshly searched plan with
bit-identical restored params and no sample-domain data loss/replay; an
injected hang is converted by the watchdog into a flight dump + emergency
save + supervised restart. Decision-matrix coverage (budget, backoff,
give-up) runs in-process against a spawn stub — the supervisor itself
never touches the JAX backend.
"""

import json
import os
import subprocess
import sys
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.core import faults
from galvatron_tpu.core.arguments import initialize_galvatron
from galvatron_tpu.core.checkpoint import (
    committed_steps,
    read_manifest,
    save_checkpoint,
    step_path,
)
from galvatron_tpu.core.elastic import (
    EXIT_ANOMALY,
    EXIT_COMPLETED,
    EXIT_HANG,
    EXIT_PREEMPTED,
    SIM_WORLD_ENV,
    classify_exit,
    run_elastic,
)
from galvatron_tpu.core.strategy import HybridParallelConfig, plan_hash
from galvatron_tpu.core.watchdog import HangWatchdog, StateHolder, dump_all_stacks
from galvatron_tpu.utils.metrics import read_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = [
    "--model_size", "llama-0.3b", "--num_layers", "2", "--hidden_size", "32",
    "--num_heads", "2", "--ffn_dim", "64", "--vocab_size", "128",
    "--seq_length", "16", "--global_train_batch_size", "8",
    "--mixed_precision", "fp32",
]


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture()
def child_env(monkeypatch):
    """Env the supervisor hands its children: persistent compile cache (the
    suite is compile-bound) and a clean fault slate."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache"))
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    monkeypatch.delenv("GALVATRON_FAULTS", raising=False)
    monkeypatch.delenv("GALVATRON_FAULTS_WORLD", raising=False)
    return monkeypatch


def run_child(args, world=None, faults_spec=None, timeout=180):
    """One supervised training attempt as a real subprocess (the unit the
    supervisor spawns), on a simulated ``world``-device CPU platform."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if world is not None:
        env[SIM_WORLD_ENV] = str(world)
    if faults_spec:
        env["GALVATRON_FAULTS"] = faults_spec
    else:
        env.pop("GALVATRON_FAULTS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "galvatron_tpu.core.elastic", "child"] + args,
        env=env, cwd=REPO, timeout=timeout,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    return proc.returncode, proc.stdout


def events_of(save_dir):
    return read_metrics(os.path.join(save_dir, "elastic_events.jsonl"))


# ---------------------------------------------------------------------------
# e2e chaos proof: preempt → shrink 8→4 → re-plan → bit-identical resume
# ---------------------------------------------------------------------------


def test_preempt_shrink_replan_resume(tmp_path, child_env):
    ck = str(tmp_path / "ck")
    ck2 = str(tmp_path / "fidelity")
    base = TINY + ["--global_tp_deg", "2", "--save", ck, "--load", ck,
                   "--replan_search_space", "dp+tp"]

    # phase A: 8 devices under plan A (tp2); SIGTERM delivered to self
    # mid-step at batch 2 → graceful save + EXIT_PREEMPTED
    rc, out = run_child(base + ["--train_iters", "6"], world=8,
                        faults_spec="preempt_at_step=2")
    assert rc == EXIT_PREEMPTED, out
    assert committed_steps(ck) == [3]
    m3 = read_manifest(step_path(ck, 3))
    fp = m3["meta"]["fingerprint"]
    assert fp["world_size"] == 8 and m3["meta"]["samples_consumed"] == 24

    # phase B: the world HALVED. train_iters == batches consumed, so this
    # child re-plans, restores with resharding, runs zero new batches and
    # exit-saves to a fresh dir — restore fidelity isolated from training.
    rc, out = run_child(
        TINY + ["--global_tp_deg", "2", "--replan_search_space", "dp+tp",
                "--load", ck, "--save", ck2, "--train_iters", "3"],
        world=4,
    )
    assert rc == EXIT_COMPLETED, out
    assert "GTA017" in out and "topology change: 8 → 4" in out
    # the re-searched plan landed in the run's replan cache, self-described
    replans = os.listdir(os.path.join(ck, "replans"))
    assert len(replans) == 1 and replans[0].endswith("4dev_bsz8.json")
    with open(os.path.join(ck, "replans", replans[0])) as f:
        plan_d = json.load(f)
    assert plan_d["num_devices"] == 4 and plan_d["global_bsz"] == 8

    # bit-identical restored params post-reshard: the manifests carry
    # per-leaf sha256 of the host-gathered arrays — layout-independent, so
    # digest equality IS bitwise state equality across the 8→4 reshard
    assert committed_steps(ck2) == [3]
    got = read_manifest(step_path(ck2, 3))["leaves"]
    want = m3["leaves"]
    assert got == want
    # and the sample-domain cursor survived untouched: nothing consumed
    meta2 = read_manifest(step_path(ck2, 3))["meta"]
    assert meta2["samples_consumed"] == 24 and meta2["batches_consumed"] == 3
    assert meta2["fingerprint"]["world_size"] == 4

    # phase C: the supervisor finishes the run at world 4 — the re-plan is
    # a CACHE hit (no second search), and training covers exactly batches
    # 3..5: the cursor never duplicates or drops a batch
    mpath = str(tmp_path / "m.jsonl")
    child_env.setenv("GALVATRON_FAULTS_WORLD", "4")
    rc = run_elastic(base + ["--train_iters", "6", "--max_restarts", "3",
                             "--restart_backoff_s", "0.05",
                             "--metrics_path", mpath])
    assert rc == 0
    assert committed_steps(ck)[-1] == 6
    assert len(os.listdir(os.path.join(ck, "replans"))) == 1  # cache hit
    m6 = read_manifest(step_path(ck, 6))["meta"]
    assert m6["batches_consumed"] == 6 and m6["samples_consumed"] == 48
    assert m6["fingerprint"]["world_size"] == 4
    # the plan trained under is exactly the re-searched one
    assert m6["fingerprint"]["plan_hash"] == plan_hash(plan_d)
    iters = [r["step"] for r in read_metrics(mpath) if r["event"] == "train_iter"]
    assert iters == [3, 4, 5]
    evs = events_of(ck)
    assert [e["mode"] for e in evs if e["event"] == "child_exit"] == ["completed"]


# ---------------------------------------------------------------------------
# e2e: injected hang → watchdog → flight dump + emergency save + restart
# ---------------------------------------------------------------------------


def test_watchdog_hang_flight_emergency_restart(tmp_path, child_env):
    ck = str(tmp_path / "ck")
    fdir = str(tmp_path / "flight")
    child_env.setenv("GALVATRON_FAULTS", "hang_at_step=1,hang_s=60")
    child_env.setenv("GALVATRON_FAULTS_WORLD", "2")
    rc = run_elastic(
        TINY + ["--train_iters", "3", "--save", ck, "--flight_dir", fdir,
                "--step_timeout_s", "2", "--max_restarts", "3",
                "--restart_backoff_s", "0.05"]
    )
    assert rc == 0
    # the hang child left an emergency checkpoint of the last bound state
    # (step 1 — the hanging batch produced no update and is replayed);
    # the restarted child finished the run
    assert committed_steps(ck) == [1, 3]
    evs = events_of(ck)
    modes = [e["mode"] for e in evs if e["event"] == "child_exit"]
    assert modes == ["hang", "completed"]
    assert [e["code"] for e in evs if e["event"] == "child_exit"][0] == EXIT_HANG
    # the flight recorder captured the hang with all-thread stacks
    dumps = []
    for fn in os.listdir(fdir):
        with open(os.path.join(fdir, fn)) as f:
            dumps.append(json.load(f))
    hang = [d for d in dumps if "watchdog hang at step 1" in d.get("reason", "")]
    assert len(hang) == 1
    assert "maybe_hang" in hang[0]["extra"]["stacks"]  # the stalled frame itself
    # the emergency save is resumable: step 1's meta replays the hung batch
    m1 = read_manifest(step_path(ck, 1))["meta"]
    assert m1["batches_consumed"] == 1 and m1["samples_consumed"] == 8


def test_supervisor_gives_up_without_progress(tmp_path, child_env):
    """A child that crashes before ever committing exhausts --max_restarts
    consecutive restarts and the supervisor gives up (crash loop, not a
    preemption lifecycle)."""
    bad = tmp_path / "bad"
    (bad / "step_7").mkdir(parents=True)  # legacy dir: trainer refuses loudly
    ck = str(tmp_path / "ck")
    child_env.setenv("GALVATRON_FAULTS_WORLD", "1")
    rc = run_elastic(
        TINY + ["--train_iters", "2", "--load", str(bad), "--save", ck,
                "--max_restarts", "1", "--restart_backoff_s", "0.01",
                "--restart_backoff_cap_s", "0.05"]
    )
    assert rc == 1
    evs = events_of(ck)
    gu = [e for e in evs if e["event"] == "give_up"]
    assert len(gu) == 1 and gu[0]["reason"] == "restart_budget"
    assert gu[0]["attempts"] == 2  # initial + 1 budgeted restart
    assert all(e["mode"] == "crash" for e in evs if e["event"] == "child_exit")


def test_supervisor_never_touches_a_backend(tmp_path, child_env):
    """One process for each chip: a parent that has touched JAX holds the
    chip and its children then fail or hang.  The supervisor runs under a
    platform name no backend answers to, so ANY backend touch on its side
    (``jax.devices()``, ``default_backend()``, a stray jit) is an error;
    the children bootstrap their own simulated world and train normally."""
    env = dict(os.environ, JAX_PLATFORMS="no_such_platform")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env[SIM_WORLD_ENV] = "8"
    proc = subprocess.run(
        [sys.executable, "-m", "galvatron_tpu.cli", "run-elastic"] + TINY
        + ["--train_iters", "2", "--save", str(tmp_path / "ck"), "--max_restarts", "0"],
        env=env, cwd=REPO, timeout=240,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    assert proc.returncode == 0, proc.stdout[-3000:]
    assert "completed after 1 attempt(s), 0 restart(s)" in proc.stdout


# ---------------------------------------------------------------------------
# supervisor decision matrix (in-process spawn stub — no subprocesses)
# ---------------------------------------------------------------------------


def small_state(v: float, step: int):
    return {
        "params": {"w": jnp.full((8,), v, jnp.float32)},
        "step": jnp.asarray(step, jnp.int32),
    }


def stub_spawn(script, save_dir=None):
    """Scripted child: each call pops (exit_code, step_to_commit|None)."""
    calls = []

    def spawn(cmd, env):
        code, step = script.pop(0)
        calls.append((list(cmd), dict(env)))
        if step is not None and save_dir:
            save_checkpoint(save_dir, small_state(float(step), step), step)
        return code

    spawn.calls = calls
    return spawn


def test_decision_anomaly_gives_up_immediately(tmp_path):
    ck = str(tmp_path / "ck")
    spawn = stub_spawn([(EXIT_ANOMALY, None)], ck)
    rc = run_elastic(TINY + ["--save", ck, "--max_restarts", "5"], spawn=spawn)
    assert rc == 1 and len(spawn.calls) == 1  # no restart: replay is futile
    gu = [e for e in events_of(ck) if e["event"] == "give_up"]
    assert gu and gu[0]["reason"] == "anomaly_abort"


def test_decision_replan_infeasible_gives_up_immediately(tmp_path):
    """A doomed re-search is deterministic: restarting would re-run the
    identical search to the identical failure — no crash loop."""
    from galvatron_tpu.core.elastic import EXIT_REPLAN_INFEASIBLE

    ck = str(tmp_path / "ck")
    spawn = stub_spawn([(EXIT_REPLAN_INFEASIBLE, None)], ck)
    rc = run_elastic(TINY + ["--save", ck, "--max_restarts", "5"], spawn=spawn)
    assert rc == 1 and len(spawn.calls) == 1
    gu = [e for e in events_of(ck) if e["event"] == "give_up"]
    assert gu and gu[0]["reason"] == "replan_infeasible"


def test_decision_progress_resets_restart_budget(tmp_path):
    """4 crashes with max_restarts=2 still complete, because each crash
    committed a NEWER step — a month-long run with occasional crashes is
    not a boot loop. The 'consecutive' counter in the events proves the
    reset."""
    ck = str(tmp_path / "ck")
    script = [(1, 1), (1, 2), (1, 3), (1, 4), (EXIT_COMPLETED, 5)]
    spawn = stub_spawn(script, ck)
    rc = run_elastic(
        TINY + ["--save", ck, "--max_restarts", "2",
                "--restart_backoff_s", "0.01", "--restart_backoff_cap_s", "0.02"],
        spawn=spawn,
    )
    assert rc == 0 and len(spawn.calls) == 5
    cons = [e["consecutive"] for e in events_of(ck) if e["event"] == "restart"]
    assert cons == [1, 1, 1, 1]


def test_decision_preempted_restarts_immediately_and_strips_faults(tmp_path, monkeypatch):
    """Preempted-save children restart with zero backoff, and the chaos env
    is delivered to the FIRST child only (the injected fault happened; the
    recovery run must be fault-free)."""
    monkeypatch.setenv("GALVATRON_FAULTS", "kill_mid_save=1")
    ck = str(tmp_path / "ck")
    spawn = stub_spawn([(EXIT_PREEMPTED, 1), (EXIT_COMPLETED, 2)], ck)
    rc = run_elastic(TINY + ["--save", ck, "--max_restarts", "3"], spawn=spawn)
    assert rc == 0
    rs = [e for e in events_of(ck) if e["event"] == "restart"]
    assert len(rs) == 1 and rs[0]["backoff_s"] == 0.0
    assert "GALVATRON_FAULTS" in spawn.calls[0][1]  # first child: injected
    assert "GALVATRON_FAULTS" not in spawn.calls[1][1]  # restart: clean
    # resume wiring: every child is pointed at the run's own checkpoint dir
    assert spawn.calls[0][0][-2:] == ["--load", ck]


def test_supervisor_sidecar_exposes_state(tmp_path):
    """/healthz and /metrics on --obs_port carry the supervisor state an
    operator needs to tell a re-planning restart from a crash loop."""
    import socket

    ck = str(tmp_path / "ck")
    seen = {}
    with socket.socket() as s:  # an ephemeral port (0 means "sidecar off")
        s.bind(("127.0.0.1", 0))
        free_port = s.getsockname()[1]

    def spawn(cmd, env):
        port = run_elastic.last_obs_port
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz") as r:
            seen["health"] = json.loads(r.read())
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics") as r:
            seen["metrics"] = r.read().decode()
        save_checkpoint(ck, small_state(1.0, 1), 1)
        return EXIT_COMPLETED

    rc = run_elastic(
        TINY + ["--save", ck, "--obs_port", str(free_port),
                "--step_timeout_s", "5"],
        spawn=spawn,
    )
    assert rc == 0
    h = seen["health"]
    assert h["status"] == "ok" and h["restarts_total"] == 0
    assert h["watchdog_armed"] is True and h["child_alive"] is True
    assert "galvatron_elastic_restarts_total 0" in seen["metrics"]
    assert "galvatron_elastic_watchdog_armed 1" in seen["metrics"]


# ---------------------------------------------------------------------------
# units: exit contract, watchdog, fingerprints, plan hash, world schedule
# ---------------------------------------------------------------------------


def test_classify_exit_contract():
    assert classify_exit(EXIT_COMPLETED) == "completed"
    assert classify_exit(EXIT_PREEMPTED) == "preempted"
    assert classify_exit(EXIT_ANOMALY) == "anomaly_abort"
    assert classify_exit(EXIT_HANG) == "hang"
    assert classify_exit(1) == "crash"
    assert classify_exit(-9) == "crash"  # SIGKILLed child


def test_world_schedule_parsing(monkeypatch):
    assert faults.world_schedule("8,4") == [8, 4]
    assert faults.world_schedule(" 8 , 4 ,2") == [8, 4, 2]
    assert faults.world_schedule("") == []
    monkeypatch.setenv(faults.WORLD_ENV_VAR, "16")
    assert faults.world_schedule() == [16]
    with pytest.raises(ValueError):
        faults.world_schedule("eight")
    with pytest.raises(ValueError):
        faults.world_schedule("0")


def test_watchdog_fires_once_after_deadline():
    fired = []
    wd = HangWatchdog(0.15, fired.append, exit_code=None, warmup_scale=1.0,
                      poll_s=0.02)
    try:
        wd.arm(7)
        import time

        time.sleep(0.6)
        assert fired == [7] and wd.fired
    finally:
        wd.close()


def test_watchdog_disarm_prevents_firing_and_warmup_scales():
    fired = []
    wd = HangWatchdog(0.2, fired.append, exit_code=None, warmup_scale=10.0,
                      poll_s=0.02)
    try:
        import time

        wd.arm(0)  # warmup step: deadline 2s, not 0.2s
        time.sleep(0.5)
        assert not fired  # compile-length step survives
        wd.disarm()
        wd.arm(1)  # steady state: 0.2s deadline applies
        time.sleep(0.1)
        wd.disarm()  # fast step: disarmed before the deadline
        time.sleep(0.4)
        assert not fired
        wd.arm(2)
        time.sleep(0.7)
        assert fired == [2]
    finally:
        wd.close()


def test_watchdog_explicit_warmup_rearms_compile_deadline():
    """warmup=True (the trainer's rampup-transition signal) applies the
    compile-length deadline to a LATER step too — a known recompile must
    not be declared a hang just because it isn't the first step."""
    import time

    fired = []
    wd = HangWatchdog(0.15, fired.append, exit_code=None, warmup_scale=10.0,
                      poll_s=0.02)
    try:
        wd.arm(0)
        wd.disarm()  # first (automatic-warmup) step done
        wd.arm(5, warmup=True)  # recompiling step: 1.5s deadline, not 0.15s
        time.sleep(0.5)
        assert not fired
        wd.disarm()
    finally:
        wd.close()


def test_child_env_pythonpath_no_empty_entry(monkeypatch):
    """'<root>:' would put the child's cwd on sys.path (empty entry); the
    inherited value is joined only when non-empty."""
    from galvatron_tpu.core.elastic import _child_env

    env = _child_env({"HOME": "/root"}, attempt=0, worlds=[])
    assert not env["PYTHONPATH"].endswith(os.pathsep)
    assert REPO == env["PYTHONPATH"]
    env2 = _child_env({"PYTHONPATH": "/opt/x"}, attempt=0, worlds=[])
    assert env2["PYTHONPATH"] == REPO + os.pathsep + "/opt/x"


def test_cached_plan_rejected_over_live_memory_budget(tmp_path):
    """A cached plan searched under a BIGGER budget must not be adopted on
    shrunken devices: the lookup validates against the live re-plan budget
    (GTA015), not the candidate's own embedded record."""
    from galvatron_tpu.search.replan import find_cached_plan

    cd = tmp_path / "cache"
    cd.mkdir()
    d = HybridParallelConfig.uniform(2, tp=1).to_json_dict()
    d.update(num_devices=4, global_bsz=8, memory_mb=8192.0,
             memory_constraint_gb=16.0)  # its OWN budget would pass
    with open(cd / "plan.json", "w") as f:
        json.dump(d, f)
    dirs = [str(cd)]
    assert find_cached_plan(dirs, None, "", 4, 8,
                            memory_budget_mb=4096.0, verbose=False) is None
    assert find_cached_plan(dirs, None, "", 4, 8,
                            memory_budget_mb=16384.0, verbose=False) is not None


def test_state_holder_invalidation():
    h = StateHolder()
    assert h.snapshot() is None
    h.set({"w": 1}, step=3, batches=5, samples=40)
    snap = h.snapshot()
    assert snap["step"] == 3 and snap["batches"] == 5 and snap["state"] == {"w": 1}
    h.invalidate()  # donation in flight: saving now would read freed buffers
    assert h.snapshot() is None
    h.set({"w": 2}, step=4, batches=6, samples=48)
    assert h.snapshot()["step"] == 4


def test_dump_all_stacks_sees_this_frame():
    txt = dump_all_stacks()
    assert "test_dump_all_stacks_sees_this_frame" in txt


def test_check_topology_fingerprint_gta017():
    from galvatron_tpu.analysis.plan_check import check_topology_fingerprint

    fp = {"world_size": 8, "plan_hash": "sha256:x", "global_bsz": 8}
    diags = check_topology_fingerprint(fp, 4)
    assert len(diags) == 1 and diags[0].code == "GTA017"
    assert diags[0].severity == "error" and "8 devices" in diags[0].message
    assert check_topology_fingerprint(fp, 8) == []
    # garbage fingerprints degrade to "nothing to compare", never crash
    assert check_topology_fingerprint({"world_size": "many"}, 4) == []
    assert check_topology_fingerprint("not-a-dict", 4) == []


def test_plan_hash_ignores_provenance_and_ordering():
    hp = HybridParallelConfig.uniform(2, tp=2, sp=True, chunks=2)
    d = hp.to_json_dict()
    h0 = plan_hash(hp)
    assert plan_hash(d) == h0
    # provenance keys (what save_result adds) never change the hash
    d2 = dict(d, num_devices=8, search_cost_ms=1.25, model_size="llama-0.3b")
    assert plan_hash(d2) == h0
    # a semantic change does
    assert plan_hash(HybridParallelConfig.uniform(2, tp=1, chunks=2)) != h0


def test_trainer_refuses_changed_topology_without_supervision(tmp_path):
    """Plain `train` on a changed world surfaces GTA017 instead of silently
    training an unsearched parallelization; the supervised path (the
    allow_topology_change flag the elastic child sets after installing a
    validated plan) resumes with a topology_resume event."""
    from galvatron_tpu.analysis.plan_check import PlanError
    from galvatron_tpu.core.trainer import train

    ck = str(tmp_path / "ck")
    ns = initialize_galvatron("train", TINY + ["--train_iters", "1", "--save", ck])
    train(ns, verbose=False)
    # simulate "the pod changed": rewrite the recorded world (meta is not
    # digest-guarded; leaves are untouched)
    mpath = os.path.join(step_path(ck, 1), "manifest.json")
    with open(mpath) as f:
        m = json.load(f)
    m["meta"]["fingerprint"]["world_size"] = 16
    with open(mpath, "w") as f:
        json.dump(m, f)

    ns2 = initialize_galvatron(
        "train", TINY + ["--train_iters", "2", "--save", ck, "--load", ck]
    )
    with pytest.raises(PlanError, match="GTA017"):
        train(ns2, verbose=False)

    mjson = str(tmp_path / "m.jsonl")
    ns3 = initialize_galvatron(
        "train",
        TINY + ["--train_iters", "2", "--save", ck, "--load", ck,
                "--metrics_path", mjson],
    )
    ns3.allow_topology_change = True
    out = train(ns3, verbose=False)
    assert int(np.asarray(out["state"]["step"])) == 2
    tr = [r for r in read_metrics(mjson) if r["event"] == "topology_resume"]
    assert len(tr) == 1 and tr[0]["old_world"] == 16 and tr[0]["new_world"] == 8


def test_sample_domain_resume_converts_cursor(tmp_path):
    """A changed global batch size resumes through the sample domain: the
    cursor lands exactly where the consumed samples end — no example is
    dropped or replayed — and a non-dividing batch size is refused."""
    from galvatron_tpu.core.trainer import train

    ck = str(tmp_path / "ck")
    big = TINY[:-4] + ["--global_train_batch_size", "16", "--mixed_precision", "fp32"]
    ns = initialize_galvatron("train", big + ["--train_iters", "2", "--save", ck])
    train(ns, verbose=False)
    m = read_manifest(step_path(ck, 2))["meta"]
    assert m["samples_consumed"] == 32 and m["global_bsz"] == 16

    mjson = str(tmp_path / "m.jsonl")
    ns2 = initialize_galvatron(
        "train", TINY + ["--train_iters", "6", "--save", ck, "--load", ck,
                         "--metrics_path", mjson]
    )  # bsz 8: cursor 32/8 = 4
    out = train(ns2, verbose=False)
    assert int(np.asarray(out["state"]["step"])) == 4  # 2 restored + 2 new
    iters = [r["step"] for r in read_metrics(mjson) if r["event"] == "train_iter"]
    assert iters == [4, 5]
    m2 = read_manifest(step_path(ck, 4))["meta"]
    assert m2["samples_consumed"] == 48 and m2["batches_consumed"] == 6

    # 48 samples % 32 != 0: a partial batch would be dropped or replayed
    ns3 = initialize_galvatron(
        "train", TINY[:-4] + ["--global_train_batch_size", "32",
                              "--mixed_precision", "fp32",
                              "--train_iters", "4", "--save", ck, "--load", ck]
    )
    with pytest.raises(ValueError, match="not.*divisible|divisib"):
        train(ns3, verbose=False)


def test_preempt_fault_in_process(tmp_path):
    """preempt_at_step delivers SIGTERM to self mid-step: the graceful
    handler latches it, the exit save commits, and the result reports the
    signal (what the child maps to EXIT_PREEMPTED)."""
    from galvatron_tpu.core.trainer import train

    ck = str(tmp_path / "ck")
    faults.configure(preempt_at_step=1)
    ns = initialize_galvatron("train", TINY + ["--train_iters", "5", "--save", ck])
    out = train(ns, verbose=False)
    assert out["signaled"] is not None
    # batch 1 was fetched and trained before the latch was polled: 2 steps
    assert committed_steps(ck) == [2]
    assert read_manifest(step_path(ck, 2))["meta"]["batches_consumed"] == 2


def test_adopt_recorded_plan_keeps_continuity(tmp_path):
    """After a re-plan, a SAME-topology restart must keep training the
    re-searched plan, not silently fall back to the original argv flags;
    when the argv flags already describe the recorded plan, nothing is
    adopted."""
    from galvatron_tpu.core.elastic import adopt_recorded_plan

    ck = tmp_path / "ck"
    (ck / "replans").mkdir(parents=True)
    plan = HybridParallelConfig.uniform(
        2, tp=2, sp=True, vocab_tp=2, mixed_precision="fp32"
    )
    ppath = str(ck / "replans" / "replan_llama-0.3b_8dev_bsz8.json")
    plan.save(ppath)
    fp = {"world_size": 8, "plan_hash": plan_hash(plan), "global_bsz": 8}

    ns = initialize_galvatron("train", TINY + ["--load", str(ck)])  # argv: tp1
    assert adopt_recorded_plan(ns, fp, 8) == ppath
    assert ns.galvatron_config_path == ppath

    ns2 = initialize_galvatron(
        "train", TINY + ["--load", str(ck), "--global_tp_deg", "2",
                         "--sequence_parallel", "1", "--vocab_tp", "2"]
    )  # argv DESCRIBES the recorded plan (uniform tp2+sp, vocab_tp 2)
    assert adopt_recorded_plan(ns2, fp, 8) is None
    assert ns2.galvatron_config_path is None

    # recorded hash with no cached file: cross-plan resume proceeds on argv
    ns3 = initialize_galvatron("train", TINY + ["--load", str(ck)])
    assert adopt_recorded_plan(ns3, {"plan_hash": "sha256:gone"}, 8) is None
    assert ns3.galvatron_config_path is None


def test_elastic_stats_render_and_health():
    from galvatron_tpu.obs.prom import ElasticStats

    s = ElasticStats()
    s.restarts_total = 2
    s.last_exit_mode = "hang"
    s.last_exit_code = EXIT_HANG
    s.watchdog_armed = True
    s.current_plan_hash = "sha256:abc"
    text = s.render()
    assert "galvatron_elastic_restarts_total 2" in text
    assert 'mode="hang"' in text and 'plan_hash="sha256:abc"' in text
    h = s.health()
    assert h["restarts_total"] == 2 and h["last_exit_mode"] == "hang"
    assert h["current_plan_hash"] == "sha256:abc"
