"""Timers, metrics sink, graceful-exit signal handling (SURVEY §5 aux
subsystems the rebuild adds: megatron timers.py / tensorboard-writer /
dist_signal_handler.py equivalents)."""

import os
import signal
import time

import numpy as np
import pytest

from galvatron_tpu.core.signals import GracefulExitHandler
from galvatron_tpu.utils.metrics import MetricsLogger, read_metrics
from galvatron_tpu.utils.timers import Timers


def test_timers_accumulate_and_reset():
    t = Timers()
    t("work").start()
    time.sleep(0.01)
    t("work").stop()
    t("work").start()
    time.sleep(0.01)
    t("work").stop()
    assert t("work").count == 2
    e = t("work").elapsed(reset=True)
    assert 0.015 < e < 1.0
    assert t("work").elapsed() == 0.0
    with pytest.raises(RuntimeError):
        t("work").stop()
    s = t.log_string(["work"])
    assert s.startswith("time (ms)")


def test_metrics_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with MetricsLogger(path) as m:
        m.log("train_iter", step=0, loss=3.5, batch_size=8)
        m.log("train_iter", step=1, loss=np.float32(3.25), iter_ms=None)
        with pytest.raises(TypeError):
            m.log("bad", step=2, loss=[1, 2])
    recs = read_metrics(path)
    assert len(recs) == 2
    assert recs[0]["loss"] == 3.5 and recs[0]["step"] == 0
    assert isinstance(recs[1]["loss"], float)  # numpy scalar cast to python


def test_metrics_noop_without_path():
    m = MetricsLogger(None)
    rec = m.log("x", step=1, v=2)
    assert rec["v"] == 2
    m.close()


def test_graceful_exit_latches_sigterm():
    with GracefulExitHandler([signal.SIGTERM]) as h:
        assert h.signaled is None
        os.kill(os.getpid(), signal.SIGTERM)
        # handler runs synchronously in the main thread on delivery
        assert h.signaled == signal.SIGTERM
    # prior handler restored: sending again must not re-latch
    h2 = GracefulExitHandler([signal.SIGTERM])
    assert h2.signaled is None


def test_trainer_stops_and_checkpoints_on_signal(tmp_path):
    """SIGTERM mid-training → loop stops early, final checkpoint written."""
    from galvatron_tpu.core.arguments import initialize_galvatron
    from galvatron_tpu.core import trainer as trainer_mod
    from galvatron_tpu.core.checkpoint import latest_step

    save = str(tmp_path / "ckpt")
    metrics_path = str(tmp_path / "metrics.jsonl")
    ns = initialize_galvatron(
        "train",
        [
            "--model_size", "llama-0.3b", "--num_layers", "2", "--hidden_size", "64",
            "--num_heads", "4", "--vocab_size", "128", "--seq_length", "16",
            "--global_train_batch_size", "8", "--train_iters", "50",
            "--mixed_precision", "fp32", "--save", save, "--metrics_path", metrics_path,
        ],
    )

    # deliver SIGTERM after the 3rd iteration via a profiler-hook side effect
    orig_begin = trainer_mod.RuntimeProfiler.begin_iter
    count = {"n": 0}

    def begin_and_signal(self):
        count["n"] += 1
        if count["n"] == 3:
            os.kill(os.getpid(), signal.SIGTERM)
        return orig_begin(self)

    trainer_mod.RuntimeProfiler.begin_iter = begin_and_signal
    try:
        out = trainer_mod.train(ns, verbose=False)
    finally:
        trainer_mod.RuntimeProfiler.begin_iter = orig_begin
    final = int(np.asarray(out["state"]["step"]))
    assert final == 3  # stopped right after the signaled iteration
    assert latest_step(save) == 3  # checkpoint-on-exit
    # three iterations logged (the run's one plan_price record, PR 56, is no iteration)
    recs = [r for r in read_metrics(metrics_path) if r["event"] == "train_iter"]
    assert len(recs) == 3 and recs[-1]["step"] == 2


def test_trainer_jax_profiler_trace(tmp_path):
    """--trace_dir captures a jax.profiler trace of the training loop
    (SURVEY §5 tracing parity: the reference instruments with torch.profiler
    and CUDA events; here the XLA op timeline is the artifact)."""
    from galvatron_tpu.core.arguments import initialize_galvatron
    from galvatron_tpu.core import trainer as trainer_mod

    trace_dir = str(tmp_path / "trace")
    ns = initialize_galvatron(
        "train",
        [
            "--model_size", "llama-0.3b", "--num_layers", "2", "--hidden_size", "64",
            "--num_heads", "4", "--vocab_size", "128", "--seq_length", "16",
            "--global_train_batch_size", "8", "--train_iters", "3",
            "--mixed_precision", "fp32", "--trace_dir", trace_dir,
        ],
    )
    trainer_mod.train(ns, verbose=False)
    captured = [
        os.path.join(r, f) for r, _, fs in os.walk(trace_dir) for f in fs
    ]
    assert captured, "trace dir is empty — no profile captured"


# ---------------------------------------------------------------------------
# PR 6 satellites: running-timer readout, torn JSONL tails, window/counter
# concurrency contracts
# ---------------------------------------------------------------------------


def test_timer_elapsed_running_interval():
    """elapsed() on a RUNNING timer raises unless running_ok=True, which
    includes the open interval — a crash dump mid-span must not silently
    under-report the phase that crashed."""
    t = Timers()
    t("phase").start()
    time.sleep(0.01)
    with pytest.raises(RuntimeError):
        t("phase").elapsed()
    e = t("phase").elapsed(running_ok=True)
    assert e >= 0.01
    # reset restarts the open interval at now: no double counting
    t("phase").elapsed(reset=True, running_ok=True)
    e2 = t("phase").elapsed(running_ok=True)
    assert e2 < e
    # log_string mid-phase reads running timers deliberately (running_ok)
    t("other").start()
    s = t.log_string(["other"])
    assert s.startswith("time (ms)")
    t("other").stop()
    t("phase").stop()
    assert t("phase").elapsed() >= 0.0  # stopped: plain readout works again


def test_read_metrics_skips_torn_final_line(tmp_path):
    """A crash mid-write leaves a partial final record; the reader skips it
    with a warning instead of raising JSONDecodeError."""
    path = str(tmp_path / "m.jsonl")
    with MetricsLogger(path) as m:
        m.log("train_iter", step=0, loss=1.0)
        m.log("train_iter", step=1, loss=2.0)
    with open(path, "a") as f:
        f.write('{"event": "train_iter", "step": 2, "los')  # torn tail
    with pytest.warns(UserWarning, match="torn final"):
        recs = read_metrics(path)
    assert [r["step"] for r in recs] == [0, 1]


def test_metrics_reopen_repairs_torn_tail(tmp_path):
    """Crash-then-resume: reopening a file whose last line is torn must start
    the new stream on a fresh line — otherwise the resumed run's first record
    merges into the partial one, turning a skippable torn TAIL into mid-file
    corruption the reader refuses."""
    path = str(tmp_path / "m.jsonl")
    with MetricsLogger(path) as m:
        m.log("train_iter", step=0, loss=1.0)
    with open(path, "a") as f:
        f.write('{"event": "train_iter", "step": 1, "los')  # crash mid-write
    with pytest.warns(UserWarning, match="dropping torn"):
        m = MetricsLogger(path)  # resume: unparseable tail truncated away
    with m:
        m.log("train_iter", step=1, loss=2.0)
        m.log("train_iter", step=2, loss=3.0)
    recs = read_metrics(path)  # clean JSONL again — no warning, no raise
    assert [r["step"] for r in recs] == [0, 1, 2]
    # a COMPLETE record that merely lost its newline is terminated, not lost
    with open(path, "rb+") as f:
        f.seek(-1, os.SEEK_END)
        f.truncate()  # strip the final newline only
    with MetricsLogger(path) as m:
        m.log("train_iter", step=3, loss=4.0)
    assert [r["step"] for r in read_metrics(path)] == [0, 1, 2, 3]


def test_read_metrics_mid_file_corruption_still_raises(tmp_path):
    """Only the FINAL line can be a torn tail; garbage mid-file is real
    corruption and must not be silently dropped."""
    path = str(tmp_path / "m.jsonl")
    with open(path, "w") as f:
        f.write('{"event": "a", "step": 0}\n')
        f.write("\n")  # blank lines are tolerated and not counted as records
        f.write('{"event": "b", "st\n')  # torn in the middle: physical line 3
        f.write('{"event": "c", "step": 2}\n')
    with pytest.raises(ValueError, match="line 3"):
        read_metrics(path)


def test_quantile_window_ring_wraparound():
    """n > size: the ring keeps the newest ``size`` samples; quantiles are
    computed over exactly that window."""
    from galvatron_tpu.utils.metrics import QuantileWindow

    qw = QuantileWindow(size=8)
    for x in range(100):  # 92..99 survive
        qw.add(float(x))
    assert qw._n == 100 and len(qw._buf) == 8
    assert qw.quantile(0.0) == 92.0
    assert qw.quantile(1.0) == 99.0
    s = qw.summary()
    assert s["n"] == 100 and 92.0 <= s["p50"] <= 99.0


def test_counters_concurrent_increment():
    """Counters.inc from many threads loses no updates."""
    import threading

    from galvatron_tpu.utils.metrics import Counters

    c = Counters("x")
    n_threads, per_thread = 8, 500

    def worker():
        for _ in range(per_thread):
            c.inc("x")

    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.get("x") == n_threads * per_thread


def test_quantile_sort_happens_outside_the_lock():
    """Pin the hot-path contract: ``quantile()`` snapshots under the lock and
    sorts OUTSIDE it, so a reader computing quantiles over a large window can
    never stall ``add()`` on the serving engine's loop. The rendezvous holds
    the reader between its snapshot and its sort; add() must complete while
    the reader is parked there (it would deadlock under a lock-held sort)."""
    import threading

    from galvatron_tpu.utils.metrics import QuantileWindow

    qw = QuantileWindow(size=64)
    for x in range(64):
        qw.add(float(x))
    in_sort_phase = threading.Event()
    release_reader = threading.Event()
    orig_snapshot = qw._snapshot

    def parked_snapshot():
        buf = orig_snapshot()  # acquires and RELEASES the lock
        in_sort_phase.set()
        assert release_reader.wait(timeout=10), "add() never released us"
        return buf

    qw._snapshot = parked_snapshot
    result = {}

    def reader():
        result["q"] = qw.quantile(0.5)

    t = threading.Thread(target=reader)
    t.start()
    assert in_sort_phase.wait(timeout=10)
    # the reader is parked where its sort would run; add() must not block
    done = threading.Event()

    def writer():
        qw.add(1000.0)
        done.set()

    w = threading.Thread(target=writer)
    w.start()
    assert done.wait(timeout=5), "add() blocked while quantile() was sorting"
    release_reader.set()
    t.join(timeout=10)
    w.join(timeout=10)
    assert result["q"] is not None


def test_concurrent_add_and_quantile_smoke():
    """Thread-safety smoke: hammer add() and quantile() concurrently — no
    exceptions, all samples within the observed value range."""
    import threading

    from galvatron_tpu.utils.metrics import QuantileWindow

    qw = QuantileWindow(size=128)
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        while not stop.is_set():
            qw.add(float(i % 1000))
            i += 1

    def reader():
        try:
            while not stop.is_set():
                q = qw.quantile(0.95)
                assert q is None or 0.0 <= q <= 999.0
        except Exception as e:  # pragma: no cover - failure path
            errors.append(e)

    ts = [threading.Thread(target=writer), threading.Thread(target=reader)]
    for t in ts:
        t.start()
    time.sleep(0.2)
    stop.set()
    for t in ts:
        t.join(timeout=10)
    assert not errors
