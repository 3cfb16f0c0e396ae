"""Preemption-aware training: peer replication, notices, degraded meshes.

Unit coverage for the new recovery arithmetic (degraded-width continuation,
retry budgets, heartbeat staleness, free preemption restarts) plus the
in-memory peer store's wire protocol, and e2e chaos proofs spawning REAL
children (same contract as tests/test_elastic.py):

- ``preempt_with_grace`` — notice file → drain → EXIT_PREEMPTED → resume
- ``storage_outage + kill_host_mid_step`` — disk save fails, the replica
  lands in a peer store, SIGKILL mid-step, the restarted child restores
  from the PEER (disk has nothing) and finishes with steps_lost <
  save_interval
- corrupt replica → ``ckpt_fallback`` (source=peer) → disk restore
- heartbeat watchdog — a hang with NO in-process --step_timeout_s is still
  detected supervisor-side and converted into a restart
"""

import json
import os
import time

import pytest

from galvatron_tpu.core import faults, peer_store
from galvatron_tpu.core.checkpoint import (
    committed_steps,
    read_manifest,
    step_path,
)
from galvatron_tpu.core.elastic import EXIT_COMPLETED, EXIT_PREEMPTED, run_elastic
from galvatron_tpu.core.peer_store import (
    PeerStoreClient,
    PeerStoreServer,
    ReplicaCorruptError,
    deserialize_state,
    ring_neighbor,
    serialize_state,
)
from galvatron_tpu.core.preemption import PreemptionListener, degraded_continuation
from galvatron_tpu.core.restart_policy import RestartPolicy
from galvatron_tpu.core.retry import RETRY_COUNTERS, RetryPolicy, with_retries
from galvatron_tpu.core.watchdog import HeartbeatMonitor, beat_heartbeat
from galvatron_tpu.utils.metrics import read_metrics

from tests.test_elastic import TINY, child_env, events_of, run_child  # noqa: F401


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# degraded-mesh continuation arithmetic
# ---------------------------------------------------------------------------


def test_degraded_halves_dp_doubles_accumulation():
    p = degraded_continuation(old_dp=8, new_dp=4, global_bsz=64, chunks=2)
    assert p.feasible
    assert p.per_replica_bsz == 16
    # proportional scale-up: 2 chunks * 8/4 = 4 chunks, 4 samples each
    assert p.new_chunks == 4 and p.micro_bsz == 4
    # the invariant: per-replica work times width reproduces the global batch
    assert p.new_chunks * p.micro_bsz * p.new_dp == 64
    assert p.accum_scale == 2.0


def test_degraded_walks_up_to_divisible_chunks():
    # want = ceil(3*6/4) = 5, but 12 % 5 != 0 → walk up to 6
    p = degraded_continuation(old_dp=6, new_dp=4, global_bsz=48, chunks=3)
    assert p.feasible and p.per_replica_bsz == 12
    assert p.new_chunks == 6 and p.micro_bsz == 2


def test_degraded_min_dp_floor_and_divisibility():
    p = degraded_continuation(8, 1, 64, min_dp=2)
    assert not p.feasible and "degraded_min_dp" in p.reason
    p = degraded_continuation(8, 3, 64)
    assert not p.feasible and "not divisible" in p.reason
    p = degraded_continuation(8, 0, 64)
    assert not p.feasible


def test_degraded_same_width_is_identity():
    p = degraded_continuation(4, 4, 32, chunks=2)
    assert p.feasible and p.new_chunks == 2 and p.micro_bsz == 4
    assert p.accum_scale == 1.0


# ---------------------------------------------------------------------------
# peer store: wire protocol, newest-wins, corruption detection
# ---------------------------------------------------------------------------


def _dummy_state(v=1.0, step=7):
    import numpy as np

    return {"params": {"w": np.full((8,), v, np.float32)},
            "step": np.asarray(step, np.int32)}


def test_peer_store_roundtrip_and_newest_wins(tmp_path):
    srv = PeerStoreServer().start()
    try:
        cli = PeerStoreClient([srv.addr], rank=0)
        assert cli.ping()["ok"]
        for step in (3, 5):  # newest-wins per peer: 5 replaces 3
            payload, header = serialize_state(
                _dummy_state(float(step), step), step,
                meta={"batches_consumed": step},
            )
            cli.put(payload, header)
        got = cli.get_newest()
        assert got is not None
        header, payload = got
        assert header["step"] == 5
        assert header["meta"]["batches_consumed"] == 5
        leaves = deserialize_state(payload, header)
        import numpy as np

        w = [v for k, v in leaves.items() if "w" in k]
        assert len(w) == 1 and np.allclose(w[0], 5.0)
        assert len(srv.stats()) == 1  # 3 was superseded, not kept
        assert srv.stats()[0]["step"] == 5
    finally:
        srv.close()


def test_peer_store_corrupt_replica_detected(tmp_path):
    srv = PeerStoreServer().start()
    try:
        cli = PeerStoreClient([srv.addr], rank=0)
        payload, header = serialize_state(_dummy_state(), 7)
        cli.put(payload, header)
        srv.corrupt_replica(0)  # flip bytes mid-payload, keep the header
        header2, payload2 = cli.get_newest()
        with pytest.raises(ReplicaCorruptError):
            deserialize_state(payload2, header2)
    finally:
        srv.close()


def test_peer_store_get_newest_across_stores_and_dead_peers():
    a, b = PeerStoreServer().start(), PeerStoreServer().start()
    try:
        # rank 0's ring neighbor is store 1; a dead address must degrade,
        # not fail the lookup
        cli = PeerStoreClient([a.addr, b.addr, "127.0.0.1:1"], rank=0,
                              timeout_s=0.5)
        payload, header = serialize_state(_dummy_state(), 11)
        cli.put(payload, header)
        assert len(a.stats()) == 0  # ring: the put went to b
        assert len(b.stats()) == 1
        got = cli.get_newest()
        assert got is not None and got[0]["step"] == 11
    finally:
        a.close()
        b.close()


def test_ring_neighbor():
    assert [ring_neighbor(r, 3) for r in range(3)] == [1, 2, 0]
    assert ring_neighbor(0, 1) == 0  # degenerate: replicate to self


# ---------------------------------------------------------------------------
# preemption listener
# ---------------------------------------------------------------------------


def test_listener_latches_notice_file(tmp_path):
    notice = str(tmp_path / "notice")
    lst = PreemptionListener(None, notice_file=notice, grace_s=30.0,
                             poll_interval_s=0.0)
    assert lst.check() is None and not lst.noticed
    with open(notice, "w") as f:
        f.write("evicted\n")
    assert lst.check() == "notice"
    assert lst.noticed and lst.reason == "notice"
    assert 0.0 < lst.remaining_s() <= 30.0
    os.remove(notice)
    assert lst.check() == "notice"  # latched: the notice never un-happens


def test_listener_observes_sigterm_via_exit_handler():
    class FakeHandler:
        signaled = None

    h = FakeHandler()
    lst = PreemptionListener(h, grace_s=5.0)
    assert lst.check() is None
    h.signaled = 15
    assert lst.check() == "sigterm" and lst.reason == "sigterm"


# ---------------------------------------------------------------------------
# retry budget + counters; heartbeat monitor; free preemption restarts
# ---------------------------------------------------------------------------


def test_retry_budget_caps_wall_clock():
    before = RETRY_COUNTERS.snapshot()
    calls = []

    def fail():
        calls.append(1)
        raise OSError("transient")

    pol = RetryPolicy(attempts=50, base_delay_s=5.0, jitter="none",
                      max_elapsed_s=0.01)
    t0 = time.monotonic()
    with pytest.raises(OSError) as ei:
        with_retries(fail, pol, describe="budgeted op")
    # the 5s backoff would blow the 10ms budget: give up after attempt 1,
    # never sleeping
    assert time.monotonic() - t0 < 2.0
    assert len(calls) == 1
    if hasattr(ei.value, "add_note"):  # exception notes are 3.11+
        notes = "".join(getattr(ei.value, "__notes__", []))
        assert "retry budget 0.01s" in notes and "after 1 attempt" in notes
    after = RETRY_COUNTERS.snapshot()
    assert after["io_give_up"] == before["io_give_up"] + 1
    assert after["io_retry"] == before["io_retry"]  # no retry fit the budget


def test_retry_counters_count_retries():
    before = RETRY_COUNTERS.snapshot()
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) < 3:
            raise OSError("transient")
        return "ok"

    pol = RetryPolicy(attempts=5, base_delay_s=0.0, jitter="none")
    assert with_retries(flaky, pol) == "ok"
    after = RETRY_COUNTERS.snapshot()
    assert after["io_retry"] == before["io_retry"] + 2
    assert after["io_give_up"] == before["io_give_up"]


def test_heartbeat_monitor_staleness(tmp_path):
    hb = str(tmp_path / "hb")
    mon = HeartbeatMonitor(hb, first_beat_grace_s=1000.0)
    # no beat yet: the compile-length grace applies, not the timeout
    assert mon.last_beat_age_s() is None
    assert not mon.stale(0.001)
    beat_heartbeat(hb, 3)
    with open(hb) as f:
        step, _ts = f.read().split()
    assert step == "3"
    assert mon.last_beat_age_s() < 5.0
    assert not mon.stale(60.0)
    os.utime(hb, (time.time() - 100, time.time() - 100))  # age the beat
    assert mon.stale(60.0) and not mon.stale(1000.0)


def test_restart_policy_free_preemptions_cost_nothing():
    pol = RestartPolicy(max_restarts=1)
    # more graceful-with-progress preemptions than the whole budget
    for _ in range(5):
        d = pol.on_failure(progressed=True, immediate=True, free=True)
        assert d.restart and d.consecutive == 0 and d.backoff_s == 0.0
    # a preemption WITHOUT progress still burns budget
    assert pol.on_failure(progressed=False, immediate=True, free=True).restart
    assert pol.on_failure(progressed=False, immediate=True, free=True).give_up


# ---------------------------------------------------------------------------
# e2e: preemption notice → drain → EXIT_PREEMPTED → resume to completion
# ---------------------------------------------------------------------------


def test_preempt_with_grace_drains_and_resumes(tmp_path, child_env):
    ck = str(tmp_path / "ck")
    notice = str(tmp_path / "notice")
    mpath = str(tmp_path / "m.jsonl")
    args = TINY + ["--train_iters", "4", "--save", ck, "--load", ck,
                   "--preempt_notice_file", notice, "--preempt_grace_s", "20",
                   "--metrics_path", mpath]
    # child 1: the chaos hook writes the notice file at batch 2 — the loop
    # must drain at the NEXT step boundary and exit with the preempted code
    rc, out = run_child(args, world=1, faults_spec="preempt_with_grace=2")
    assert rc == EXIT_PREEMPTED, out
    assert "preemption notice (notice)" in out and "draining" in out
    recs = read_metrics(mpath)
    pn = [r for r in recs if r["event"] == "preempt_notice"]
    assert pn and pn[0]["step"] == 3 and pn[0]["reason"] == "notice"
    assert pn[0]["grace_s"] == 20.0
    # the drain committed everything consumed: batch 2 trained, then exit
    last = committed_steps(ck)[-1]
    meta = read_manifest(step_path(ck, last))["meta"]
    assert meta["batches_consumed"] == 3
    # child 2: notice file still present would re-drain immediately — a
    # real platform clears it with the new capacity; mirror that
    os.remove(notice)
    rc, out = run_child(args, world=1)
    assert rc == EXIT_COMPLETED, out
    final = committed_steps(ck)[-1]
    assert read_manifest(step_path(ck, final))["meta"]["batches_consumed"] == 4


# ---------------------------------------------------------------------------
# e2e: storage outage + host kill → recovery from the in-memory peer replica
# ---------------------------------------------------------------------------


def test_kill_host_recovers_from_peer_replica(tmp_path, child_env):
    """The pillar proof: disk save FAILS (storage outage), the replica lands
    in a peer store, the host is SIGKILLed mid-step, and the restarted child
    restores from the PEER at the replicated step — steps_lost <
    save_interval even though disk held nothing at all."""
    ck = str(tmp_path / "ck")
    child_env.setenv("GALVATRON_FAULTS",
                     "storage_outage=1,kill_host_mid_step=3")
    child_env.setenv("GALVATRON_FAULTS_WORLD", "2")
    rc = run_elastic(
        TINY + ["--train_iters", "4", "--save", ck, "--save_interval", "2",
                "--peer_replicate", "3", "--max_restarts", "3",
                "--restart_backoff_s", "0.05"]
    )
    assert rc == 0
    evs = events_of(ck)
    assert [e["mode"] for e in evs if e["event"] == "child_exit"] == [
        "crash", "completed"
    ]
    assert any(e["event"] == "peer_store_start" and e["count"] == 3
               for e in evs)
    recs = read_metrics(os.path.join(ck, "train_metrics.jsonl"))
    # child 1: the interval save at step 2 lost its disk commit to the
    # outage but pushed the replica first
    assert any(r["event"] == "peer_replicate" and r["step"] == 2
               for r in recs)
    assert any(r["event"] == "save_degraded_to_peer" and r["step"] == 2
               for r in recs)
    # child 2: restored from the PEER (disk had no committed step at all)
    rec = [r for r in recs if r["event"] == "recovery"]
    assert rec and rec[0]["source"] == "peer" and rec[0]["step"] == 2
    assert rec[0]["resume_batches"] == 2
    # steps_lost: killed at batch 3, resumed at batch 2 → 1 < save_interval
    assert 3 - rec[0]["resume_batches"] < 2
    # the supervisor accounted the recovery with a measured MTTR
    ro = [e for e in evs if e["event"] == "recovery_observed"]
    assert ro and ro[0]["source"] == "peer" and ro[0]["mttr_ms"] > 0
    # the finished run committed step 4 to disk (outage was one-shot)
    assert committed_steps(ck) == [4]
    meta = read_manifest(step_path(ck, 4))["meta"]
    assert meta["batches_consumed"] == 4 and meta["samples_consumed"] == 32


# ---------------------------------------------------------------------------
# e2e: corrupt peer replica → ckpt_fallback → disk restore
# ---------------------------------------------------------------------------


def test_corrupt_replica_falls_back_to_disk(tmp_path, child_env):
    ck = str(tmp_path / "ck")
    mpath = str(tmp_path / "m.jsonl")
    # seed a DISK checkpoint the fallback can land on
    rc, out = run_child(TINY + ["--train_iters", "2", "--save", ck],
                        world=1)
    assert rc == EXIT_COMPLETED, out
    disk_step = committed_steps(ck)[-1]
    srv = PeerStoreServer().start()
    try:
        # a replica CLAIMING to be newer than disk, then corrupted in store
        payload, header = serialize_state(
            _dummy_state(9.0, 99), 99, meta={"batches_consumed": 99}
        )
        PeerStoreClient([srv.addr], rank=0).put(payload, header)
        srv.corrupt_replica(0)
        child_env.setenv(peer_store.ADDRS_ENV, srv.addr)
        child_env.setenv(peer_store.RANK_ENV, "0")
        rc, out = run_child(
            TINY + ["--train_iters", "4", "--save", ck, "--load", ck,
                    "--metrics_path", mpath],
            world=1,
        )
        assert rc == EXIT_COMPLETED, out
    finally:
        srv.close()
    recs = read_metrics(mpath)
    fb = [r for r in recs if r["event"] == "ckpt_fallback"]
    assert fb and fb[0].get("source") == "peer"
    rec = [r for r in recs if r["event"] == "recovery"]
    assert rec and rec[0]["source"] == "disk" and rec[0]["step"] == disk_step
    assert committed_steps(ck)[-1] == 4


# ---------------------------------------------------------------------------
# e2e: heartbeat watchdog — supervisor-side hang detection, no step_timeout
# ---------------------------------------------------------------------------


def test_heartbeat_watchdog_kills_hung_child(tmp_path, child_env):
    """A child hung with NO in-process watchdog (--step_timeout_s unset)
    stops beating; the supervisor's monitored spawn SIGKILLs it, accounts
    the exit as a hang, and the restart finishes the run."""
    ck = str(tmp_path / "ck")
    # a hang no run could wait out (the child is killed: the 600 s cost nothing), and a
    # heartbeat a loaded host can keep: the child beats once a step, and under the driver's
    # six workers the RESTARTED child's step with its save took over 3 s and was killed as
    # a second "hang" (modes ['hang', 'hang', 'completed']: the ledger's `rcs` [1])
    child_env.setenv("GALVATRON_FAULTS", "hang_at_step=2,hang_s=600")
    child_env.setenv("GALVATRON_FAULTS_WORLD", "1")
    t0 = time.monotonic()
    rc = run_elastic(
        TINY + ["--train_iters", "3", "--save", ck, "--save_interval", "2",
                "--heartbeat_timeout_s", "15", "--max_restarts", "3",
                "--restart_backoff_s", "0.05"]
    )
    assert rc == 0
    # detection beat the injected hang: the whole restart ended in under half of it
    assert time.monotonic() - t0 < 300
    evs = events_of(ck)
    kills = [e for e in evs if e["event"] == "watchdog_kill"]
    assert kills and kills[0]["reason"] == "heartbeat_stale"
    modes = [e["mode"] for e in evs if e["event"] == "child_exit"]
    assert modes == ["hang", "completed"]
    assert committed_steps(ck)[-1] == 3


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-v"]))
