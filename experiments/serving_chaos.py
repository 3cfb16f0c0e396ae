"""Serving chaos harness: drive `cli serve` through injected failures at the
process surface and assert the resilience contract held.

Mirrors the chaos-elastic pattern (Makefile `chaos` / CI `chaos-elastic`):
each scenario runs a REAL `cli serve` subprocess on a tiny CPU model, arms
`GALVATRON_FAULTS`, fires concurrent HTTP clients, and must end with

- drained slots (the server's exit line reports ``leaked=False``),
- process exit 0,
- a flight-recorder dump present under ``--flight_dir``.

Scenarios::

    crash    engine_crash_at_iter mid-load: in-flight requests get
             well-formed 503s (detail=engine_restarted), the engine
             restarts in-process, later requests succeed, POST /drain
             finishes the run cleanly.
    stall    client_stall: a dead client's request is cancelled at the next
             decode iteration (cancelled_disconnect counts it, the slot
             frees), then a clean drain.
    sigterm  SIGTERM mid-load: in-flight requests complete, the process
             exits 0 inside --drain_timeout_s (zero-downtime shutdown).
    evict    paged backend under block-pool pressure: queue_full 503s
             carry Retry-After, the LRU evicts cold prefix blocks, an
             engine crash warm-restarts the paged programs from the
             artifact store, and the drain leaks zero blocks.  With
             ``--serve_quant int8`` the same run proves QUANTIZED crash
             recovery: the program keys carry the int8 avals, so the warm
             hits can only come from re-warming the quantized keys.

Fleet scenarios (``--fleet``, or the ``fleet-`` prefixed names) drive a
real ``cli serve-fleet`` router over 3 replica subprocesses:

    fleet-kill     kill one of three replicas mid-decode (the router-side
                   ``kill_replica_at_dispatch`` chaos key): ZERO requests
                   lost — in-flight work on the dead replica re-dispatches
                   to a sibling and completes within its deadline with
                   ``retried_from >= 1``, the replica restarts WARM
                   (manifest hits from the shared compile-artifact store),
                   and the final fleet drain audits exit 0 + zero leaked
                   slots + a flight dump on every replica.
    fleet-rolling  POST /drain?rolling=1 under sustained load: replicas
                   drain one at a time while the rest keep serving — 100%
                   of admitted requests served, every drained process
                   exits 0, the fleet is back at full strength after the
                   roll, then a full drain ends the run with exit 0 and
                   the served/shed/expired/failed outcome partition
                   summing to the request total.

Usage: ``python experiments/serving_chaos.py
crash|stall|sigterm|fleet-kill|fleet-rolling [--out_dir D]``
(``<name> --fleet`` maps ``kill``/``rolling`` to the fleet scenarios.)
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

SERVE_ARGS = [
    "--port", "0", "--num_slots", "2", "--prefill_chunk", "8",
    "--num_layers", "1", "--hidden_size", "32", "--num_heads", "2",
    "--ffn_dim", "64", "--seq_length", "64",
    "--request_ttl_s", "120", "--drain_timeout_s", "30",
]


def start_server(out_dir: str, faults: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu", GALVATRON_FAULTS=faults)
    proc = subprocess.Popen(
        [sys.executable, "-m", "galvatron_tpu.cli", "serve",
         *SERVE_ARGS, "--flight_dir", os.path.join(out_dir, "flight")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    port = None
    for line in proc.stdout:
        m = re.search(r"listening on http://[^:]+:(\d+)/api", line)
        if m:
            port = int(m.group(1))
            break
    if port is None:
        proc.kill()
        raise SystemExit("server never came up")
    # the server listens BEFORE its warm start (readiness gating): wait for
    # /readyz like a load balancer would, so the scenarios drive a warm
    # engine instead of racing the startup probe
    deadline = time.time() + 120
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/readyz", timeout=10
            ) as r:
                if json.loads(r.read()).get("ready"):
                    break
        except Exception:  # noqa: BLE001 — 503 while starting
            pass
        time.sleep(0.1)
    return proc, port


def post(port, body, timeout=90):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def healthz(port):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/healthz", timeout=30
    ) as r:
        return json.loads(r.read())


def drain(port):
    urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{port}/drain", data=b"", method="POST",
    ), timeout=30)


def fire_clients(port, n, tokens, results):
    def one(i):
        try:
            results.append(("ok", post(
                port, {"prompts": [f"chaos {i}"], "tokens_to_generate": tokens}
            )))
        except urllib.error.HTTPError as e:
            results.append(("http", e.code, json.loads(e.read() or b"{}")))
        except Exception as e:  # noqa: BLE001 — dropped conns are outcomes too
            results.append(("err", repr(e)))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    return threads


def wait_exit(proc, timeout=60) -> tuple:
    """(rc, remaining stdout) — the drained exit line lives in stdout."""
    rest = proc.stdout.read()
    rc = proc.wait(timeout=timeout)
    return rc, rest


def check_common(name, rc, out, out_dir):
    assert rc == 0, f"{name}: expected exit 0, got {rc}\n{out[-2000:]}"
    assert "server drained: leaked=False" in out, \
        f"{name}: no clean drain audit in output\n{out[-2000:]}"
    flight = os.path.join(out_dir, "flight")
    dumps = [f for f in os.listdir(flight)] if os.path.isdir(flight) else []
    assert any(f.startswith("flight_") for f in dumps), \
        f"{name}: no flight dump under {flight}"
    print(f"{name}: ok (exit 0, zero leaked slots, flight dump present)")


def scenario_crash(out_dir):
    proc, port = start_server(
        out_dir, "engine_crash_at_iter=8,slow_decode_ms=10")
    results = []
    threads = fire_clients(port, 6, 16, results)
    for t in threads:
        t.join(timeout=120)
    restarted = [r for r in results
                 if r[0] == "http" and r[2].get("detail") == "engine_restarted"]
    assert restarted, f"crash caught no in-flight request: {results}"
    after = post(port, {"prompts": ["recovered"], "tokens_to_generate": 4})
    assert after["text"], after
    h = healthz(port)
    assert h["serving"]["engine_restarts"] >= 1, h["serving"]
    drain(port)
    rc, out = wait_exit(proc)
    check_common("crash", rc, out, out_dir)
    print(f"  {len(restarted)} in-flight 503(engine_restarted), "
          f"{sum(1 for r in results if r[0] == 'ok')} served, "
          f"restarts={h['serving']['engine_restarts']}")


def scenario_stall(out_dir):
    proc, port = start_server(out_dir, "client_stall=1,slow_decode_ms=25")
    results = []
    threads = fire_clients(port, 3, 20, results)
    deadline = time.time() + 60
    while time.time() < deadline:
        if healthz(port)["serving"]["cancelled_disconnect"] >= 1:
            break
        time.sleep(0.1)
    for t in threads:
        t.join(timeout=120)
    h = healthz(port)
    assert h["serving"]["cancelled_disconnect"] >= 1, h["serving"]
    assert h["serving"]["active_slots"] == 0, h["serving"]
    drain(port)
    rc, out = wait_exit(proc)
    check_common("stall", rc, out, out_dir)
    print(f"  cancelled_disconnect={h['serving']['cancelled_disconnect']}, "
          f"slots freed")


def scenario_sigterm(out_dir):
    proc, port = start_server(out_dir, "slow_decode_ms=25")
    results = []
    threads = fire_clients(port, 3, 16, results)
    deadline = time.time() + 60
    while time.time() < deadline:
        if healthz(port)["serving"]["active_slots"] > 0:
            break
        time.sleep(0.05)
    t0 = time.monotonic()
    proc.send_signal(signal.SIGTERM)
    rc, out = wait_exit(proc)
    elapsed = time.monotonic() - t0
    for t in threads:
        t.join(timeout=120)
    check_common("sigterm", rc, out, out_dir)
    assert elapsed < 45.0, f"drain overran: {elapsed:.1f}s"
    served = [r for r in results if r[0] == "ok"]
    assert served, f"in-flight requests did not complete: {results}"
    print(f"  {len(served)} in-flight completed through the drain, "
          f"exit in {elapsed:.1f}s")


def scenario_evict(out_dir):
    """Eviction-under-pressure on the PAGED backend: a block pool sized to
    hold roughly one worst-case sequence, long decodes saturating it, and a
    client burst behind a 2-deep queue.  The contract under pressure:

    - overflow clients get 503 queue_full WITH a Retry-After hint (the
      paged admission gate leaves a too-big head request queued, so
      "busy" has a meaningful come-back time),
    - distinct completed prompts pile refcount-0 prefix blocks into the
      LRU until admission must EVICT (prefix_cache_evictions >= 1),
    - an engine crash mid-load warm-restarts the PAGED program pair from
      the artifact store (restart_warm cache hits over /healthz, plus the
      startup warm-start log line),
    - the paged metric families ride /metrics and pass the exposition
      linter,
    - the final drain leaks nothing: the server's leaked=False line now
      includes the block-partition audit (free/owned/cached disjoint,
      zero blocks still owned).
    """
    paged_args = [
        "--port", "0", "--num_slots", "2", "--prefill_chunk", "8",
        "--num_layers", "1", "--hidden_size", "32", "--num_heads", "2",
        "--ffn_dim", "64", "--seq_length", "64",
        # pool: 9 usable blocks of 8 tokens — one worst-case request below
        # reserves 6, so a second concurrent one cannot be admitted
        "--kv_block_size", "8", "--kv_num_blocks", "10",
        "--max_queue", "2",
        "--request_ttl_s", "120", "--drain_timeout_s", "30",
    ]
    # --serve_quant int8 makes this the quantized-recovery proof: the paged
    # programs' keys now carry the int8 params avals + serve_quant term, so
    # the warm-restart hits below can only come from re-warming the
    # QUANTIZED keys (a stale fp artifact cannot satisfy them)
    int8 = "int8" in EXTRA_SERVE_ARGS
    paged_args += EXTRA_SERVE_ARGS
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               GALVATRON_FAULTS="engine_crash_at_iter=10,slow_decode_ms=30")
    proc = subprocess.Popen(
        [sys.executable, "-m", "galvatron_tpu.cli", "serve", *paged_args,
         "--flight_dir", os.path.join(out_dir, "flight"),
         "--compile_cache_dir", os.path.join(out_dir, "cache")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    port = None
    saw_parity = False
    for line in proc.stdout:
        # the load-time parity line prints at engine construction, BEFORE
        # "listening on" — it must be caught here, not in the drain tail
        saw_parity |= "serving quant: int8 per-channel" in line
        m = re.search(r"listening on http://[^:]+:(\d+)/api", line)
        if m:
            port = int(m.group(1))
            break
    if port is None:
        proc.kill()
        raise SystemExit("paged server never came up")
    assert saw_parity or not int8, \
        "evict(int8): engine came up without the load-time parity line"
    deadline = time.time() + 120
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/readyz", timeout=10
            ) as r:
                if json.loads(r.read()).get("ready"):
                    break
        except Exception:  # noqa: BLE001 — 503 while starting
            pass
        time.sleep(0.1)

    outcomes = {"ok": 0, "queue_full": 0, "engine_restarted": 0, "other": 0}
    retry_after = []
    lock = threading.Lock()

    def one(i):
        # distinct prompts: each completed request leaves a DIFFERENT
        # refcount-0 prefix block in the LRU, so the pool must evict
        body = json.dumps({"prompts": [f"chaos {i}"],
                           "tokens_to_generate": 40}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api", data=body,
            headers={"Content-Type": "application/json"}, method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=120) as r:
                json.loads(r.read())
            kind = "ok"
        except urllib.error.HTTPError as e:
            detail = json.loads(e.read() or b"{}").get("detail", "")
            kind = detail if detail in ("queue_full", "engine_restarted") \
                else "other"
            ra = e.headers.get("Retry-After")
            with lock:
                if detail == "queue_full" and ra is not None:
                    retry_after.append(ra)
        except Exception:  # noqa: BLE001 — dropped conns are outcomes too
            kind = "other"
        with lock:
            outcomes[kind] += 1

    # two waves: the first saturates the pool + queue (the shed), the
    # second (after the crash window) proves recovery + forces eviction
    threads = [threading.Thread(target=one, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    threads = [threading.Thread(target=one, args=(i,)) for i in range(8, 14)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)

    total = sum(outcomes.values())
    assert total == 14, outcomes  # outcome partition sums to the burst
    assert outcomes["ok"] >= 1, outcomes
    assert outcomes["queue_full"] >= 1, \
        f"pool pressure never shed at the queue: {outcomes}"
    assert retry_after and all(float(ra) > 0 for ra in retry_after), \
        f"queue_full 503s carried no Retry-After hint: {retry_after}"

    # deterministic eviction pressure: how much the concurrent waves shed
    # at the queue is CPU-speed dependent (a slower engine — e.g. int8
    # dequant on a host without an int8 datapath — sheds more and completes
    # fewer distinct prompts), so top up with SEQUENTIAL distinct prompts:
    # each always admits and leaves different refcount-0 prefix blocks in
    # the 9-block pool, so a bounded number of them forces the LRU to evict
    for i in range(100, 108):
        if healthz(port)["serving"]["prefix_cache_evictions"] >= 1:
            break
        try:
            post(port, {"prompts": [f"evict filler {i}"],
                        "tokens_to_generate": 24}, timeout=120)
        except Exception:  # noqa: BLE001 — a straggler 503 is not the point
            pass

    h = healthz(port)
    s = h["serving"]
    assert s["kv_backend"] == "paged", s
    if int8:
        # the replica advertises the numerics config it actually serves
        # under, and the load-time parity probe's measured drift rode along
        assert s["serve_quant"] == "int8", s
        qp = s.get("quant_parity") or {}
        assert qp.get("max_abs_logit_drift") is not None, s
        assert qp["max_abs_logit_drift"] <= qp["drift_bound"], qp
    assert s["engine_restarts"] >= 1, s
    # warm restart of the PAGED programs: the in-process supervisor re-hit
    # both artifacts in the store (recorded at the startup warm-start)
    assert s.get("restart_warm"), s
    assert s["restart_warm"]["hits"] >= 1, s["restart_warm"]
    assert s["prefix_cache_evictions"] >= 1, \
        f"saturation never evicted a cached prefix block: {s}"
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30
    ) as r:
        text = r.read().decode()
    for fam in ("galvatron_kv_blocks_total", "galvatron_kv_blocks_free",
                "galvatron_prefix_cache_hits_total",
                "galvatron_prefix_cache_evictions_total"):
        assert fam in text, f"missing {fam} in /metrics"
    _lint_metrics(f"http://127.0.0.1:{port}/metrics")

    drain(port)
    rc, out = wait_exit(proc)
    check_common("evict", rc, out, out_dir)
    assert "serving warm-start: 3/3" in out, \
        f"evict: paged programs never warm-started\n{out[-2000:]}"
    print(f"  {outcomes['ok']} served, {outcomes['queue_full']} shed with "
          f"Retry-After, {outcomes['engine_restarted']} crash 503s, "
          f"evictions={s['prefix_cache_evictions']}, restart warm hits="
          f"{s['restart_warm']['hits']}, zero leaked blocks"
          + (", int8 parity-gated" if int8 else ""))


# ---------------------------------------------------------------------------
# fleet scenarios: a real `cli serve-fleet` router over 3 replicas
# ---------------------------------------------------------------------------

FLEET_SERVE_ARGS = [
    "--num_slots", "2", "--prefill_chunk", "8",
    "--num_layers", "1", "--hidden_size", "32", "--num_heads", "2",
    "--ffn_dim", "64", "--seq_length", "64",
    "--request_ttl_s", "120", "--drain_timeout_s", "30",
]


def start_fleet(out_dir, router_faults="", replicas=3,
                replica_faults="slow_decode_ms=30", extra_args=()):
    """Spawn `cli serve-fleet`; returns (proc, port, lines) where ``lines``
    is the live stdout accumulator (a reader thread keeps the pipe drained
    — the rolling-drain audit line arrives long after the listening line)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if router_faults:
        env["GALVATRON_FAULTS"] = router_faults
    else:
        env.pop("GALVATRON_FAULTS", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "galvatron_tpu.cli", "serve-fleet",
         *FLEET_SERVE_ARGS, "--replicas", str(replicas),
         "--fleet_dir", os.path.join(out_dir, "fleet"),
         "--compile_cache_dir", os.path.join(out_dir, "cache"),
         "--retry_budget", "2", "--replica_restart_backoff_s", "0.05",
         "--replica_faults", replica_faults, *extra_args],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    lines = []
    got_port = threading.Event()
    port_holder = []

    def pump():
        for line in proc.stdout:
            lines.append(line)
            m = re.search(r"fleet router listening on http://[^:]+:(\d+)/api",
                          line)
            if m:
                port_holder.append(int(m.group(1)))
                got_port.set()
        got_port.set()

    threading.Thread(target=pump, daemon=True).start()
    if not got_port.wait(timeout=120) or not port_holder:
        proc.kill()
        raise SystemExit("fleet router never came up:\n" + "".join(lines[-50:]))
    return proc, port_holder[0], lines


def wait_fleet_exit(proc, lines, timeout=120):
    """(rc, full stdout) — the pump thread owns the pipe (``wait_exit``'s
    blocking read would fight it), so the exit just joins the accumulator."""
    rc = proc.wait(timeout=timeout)
    time.sleep(0.3)  # let the pump drain the tail through EOF
    return rc, "".join(lines)


def wait_fleet_ready(port, replicas, timeout=300):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            h = healthz(port)
            if h["fleet"]["ready_replicas"] >= replicas:
                return h
        except Exception:  # noqa: BLE001 — router still binding
            pass
        time.sleep(0.2)
    raise SystemExit(f"fleet never reached {replicas} ready replicas")


def check_fleet_drained(name, rc, out, out_dir, replicas=3):
    assert rc == 0, f"{name}: expected exit 0, got {rc}\n{out[-3000:]}"
    m = re.search(r"fleet drained: ok=True audit=(\{.*\})", out)
    assert m, f"{name}: no clean fleet drain audit in output\n{out[-3000:]}"
    audit = json.loads(m.group(1))
    per = {a["idx"]: a for a in audit["replicas"] if "exit_code" in a}
    for idx, a in per.items():
        assert a["exit_code"] == 0, (name, idx, a)
        assert a["clean_drain"] and a["flight_dump"], (name, idx, a)
    print(f"{name}: fleet drained ok ({len(per)} replicas exit 0, zero "
          f"leaked slots, flight dumps present)")
    return audit


def _lint_metrics(url_or_path):
    """Run the exposition linter as CI would (obs/aggregate.py CLI)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-m", "galvatron_tpu.obs.aggregate", "lint",
         url_or_path],
        cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True,
    )
    assert r.returncode == 0, \
        f"exposition lint failed for {url_or_path}:\n{r.stdout}{r.stderr}"


def scenario_fleet_kill(out_dir):
    """Kill one of three replicas mid-decode: zero requests lost, the
    killed replica's in-flight work re-dispatches and completes within
    deadline (retried_from >= 1), the replica restarts WARM from the
    shared artifact store, and the fleet drains clean. Runs with tracing
    armed (--flight_dir) so the post-drain merge-export proves the
    fleet-wide trace: the failed-over request's trace_id appears on the
    router track AND the replica track that finally served it."""
    proc, port, lines = start_fleet(
        out_dir, router_faults="kill_replica_at_dispatch=2",
        extra_args=("--flight_dir", os.path.join(out_dir, "router-flight"),
                    "--slo", "1"))
    try:
        wait_fleet_ready(port, 3)
        results = []
        threads = fire_clients(port, 6, 16, results)
        for t in threads:
            t.join(timeout=180)
        ok = [r for r in results if r[0] == "ok"]
        assert len(ok) == len(results), \
            f"fleet-kill lost requests: {results}"
        retried = [r for r in ok if r[1].get("retried_from", 0) >= 1]
        assert retried, f"no request failed over (retried_from>=1): {results}"
        # the killed replica restarts and the fleet recovers to 3 READY
        h = wait_fleet_ready(port, 3, timeout=180)
        assert h["requests"]["replica_restarts"] >= 1, h["requests"]
        restarted = [r for r in h["replica"] if r["restarts"] >= 1]
        assert restarted, h["replica"]
        # warm restart: the respawned replica's serve log reports cache
        # hits from the shared compile-artifact store
        idx = restarted[0]["idx"]
        log = open(os.path.join(out_dir, "fleet",
                                f"replica-{idx}.log")).read()
        warm_lines = re.findall(r"serving warm-start: .*\((\d+) cache hits",
                                log)
        assert len(warm_lines) >= 2, f"replica {idx} log:\n{log[-2000:]}"
        assert int(warm_lines[-1]) >= 1, \
            f"restart was not warm: {warm_lines} \n{log[-2000:]}"
        # metrics aggregation: the router is the single scrape target —
        # per-replica-labeled families, fleet sums, and cumulative TTFT/
        # latency histogram buckets (the fleet merge needs a probe cycle
        # to refresh each replica's snapshot)
        deadline = time.time() + 60
        text = ""
        while time.time() < deadline:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30
            ) as r:
                text = r.read().decode()
            if "galvatron_fleet_ttft_hist_seconds_fleet_bucket" in text:
                break
            time.sleep(0.5)
        assert 'galvatron_fleet_serving_completed_total{replica="0"}' in text, \
            text[-2000:]
        assert "galvatron_fleet_serving_completed_sum_total" in text, \
            text[-2000:]
        assert "galvatron_fleet_ttft_hist_seconds_fleet_bucket" in text, \
            text[-2000:]
        assert "galvatron_slo_breached" in text, text[-2000:]
        _lint_metrics(f"http://127.0.0.1:{port}/metrics")
        _lint_metrics(f"http://127.0.0.1:{h['replica'][0]['port']}/metrics")
        drain(port)
        rc, out = wait_fleet_exit(proc, lines, timeout=150)
        audit = check_fleet_drained("fleet-kill", rc, out, out_dir)
        assert audit["requests"]["served"] >= 6, audit["requests"]
        check_merged_trace(out_dir)
        print(f"  {len(retried)} failovers (retried_from>=1), "
              f"replica {idx} restarted warm "
              f"({warm_lines[-1]} cache hits), merged trace shows the "
              f"failover hop")
    finally:
        if proc.poll() is None:
            proc.kill()


def check_merged_trace(out_dir):
    """Post-drain: `cli trace-export --merge` over every flight dump the
    fleet left (router + per-replica) must yield ONE timeline where the
    failed-over request's trace_id spans the router's pid track and the
    pid track of the replica that served the retry (the failover hop).
    The originally-targeted replica was SIGKILLed — its in-memory span
    ring died with it, which is exactly why the dumps that DID land must
    still tell the story end to end."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    merged_path = os.path.join(out_dir, "merged.trace.json")
    r = subprocess.run(
        [sys.executable, "-m", "galvatron_tpu.cli", "trace-export",
         "--merge", out_dir, "-o", merged_path],
        cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True,
    )
    assert r.returncode == 0, f"merge-export failed:\n{r.stdout}{r.stderr}"
    merged = json.load(open(merged_path))
    events = merged.get("traceEvents", [])
    ids = {}
    for ev in events:
        t = (ev.get("args") or {}).get("trace_id")
        if t:
            ids.setdefault(t, set()).add(ev.get("pid"))
    assert ids, "merged timeline carries no trace ids"
    all_pids = {p for pids in ids.values() for p in pids}
    assert len(all_pids) >= 2, \
        f"trace ids never crossed a process boundary: {ids}"
    failover_ids = {
        (ev.get("args") or {}).get("trace_id")
        for ev in events if ev.get("name") == "fleet_failover"
    } - {None}
    assert failover_ids, "router recorded no fleet_failover with a trace_id"
    hop = [t for t in failover_ids if len(ids.get(t, ())) >= 2]
    assert hop, (
        f"failover trace never reached a second process track: "
        f"{ {t: sorted(ids.get(t, ())) for t in failover_ids} }"
    )
    print(f"  merged {merged_path}: {len(ids)} trace ids over "
          f"{len(all_pids)} process tracks; failover trace "
          f"{hop[0]} spans {sorted(ids[hop[0]])}")


def scenario_fleet_rolling(out_dir):
    """Rolling drain under sustained load: 100% of admitted requests
    served, every replica exits 0, the fleet stays up through the roll,
    and the outcome partition sums to the request total."""
    proc, port, lines = start_fleet(out_dir,
                                    replica_faults="slow_decode_ms=10")
    try:
        wait_fleet_ready(port, 3)
        stop = threading.Event()
        outcomes = {"ok": 0, "http": [], "err": []}
        lock = threading.Lock()

        def loadgen(i):
            j = 0
            while not stop.is_set():
                try:
                    post(port, {"prompts": [f"roll {i}-{j}"],
                                "tokens_to_generate": 8, "ttl_s": 60.0},
                         timeout=120)
                    with lock:
                        outcomes["ok"] += 1
                except urllib.error.HTTPError as e:
                    with lock:
                        outcomes["http"].append(
                            (e.code,
                             json.loads(e.read() or b"{}").get("detail")))
                except Exception as e:  # noqa: BLE001 — outcomes, not raises
                    with lock:
                        outcomes["err"].append(repr(e))
                j += 1

        threads = [threading.Thread(target=loadgen, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        time.sleep(1.0)
        urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}/drain?rolling=1", data=b"",
            method="POST",
        ), timeout=30)
        deadline = time.time() + 300
        while time.time() < deadline:
            if any("fleet rolling drain: ok=" in l for l in lines):
                break
            time.sleep(0.2)
        roll_line = next(
            (l for l in lines if "fleet rolling drain: ok=" in l), None)
        assert roll_line is not None, (
            "rolling drain never completed:\n" + "".join(lines[-50:]))
        assert "ok=True" in roll_line, roll_line
        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join(timeout=120)
        # 100% of admitted requests served: the deploy itself failed none
        assert not outcomes["http"] and not outcomes["err"], outcomes
        assert outcomes["ok"] > 0, outcomes
        h = wait_fleet_ready(port, 3, timeout=120)  # back at full strength
        served = h["requests"]["served"]
        # outcome partition: every dispatch-side outcome sums to what the
        # router admitted (client-side: all ok)
        req = h["requests"]
        total_outcomes = (req["served"] + req["expired"] + req["failed"]
                          + req["client_error"]
                          + req["rejected_saturated"]
                          + req["rejected_unready"]
                          + req["rejected_draining"])
        assert req["served"] == outcomes["ok"], (req, outcomes)
        assert total_outcomes == outcomes["ok"], (req, outcomes)
        drain(port)
        rc, out = wait_fleet_exit(proc, lines, timeout=150)
        check_fleet_drained("fleet-rolling", rc, out, out_dir)
        print(f"  {outcomes['ok']} requests served through the roll "
              f"(0 failed), partition {total_outcomes}=={served} served")
    finally:
        if proc.poll() is None:
            proc.kill()


SCENARIOS = {"crash": scenario_crash, "stall": scenario_stall,
             "sigterm": scenario_sigterm, "evict": scenario_evict,
             "fleet-kill": scenario_fleet_kill,
             "fleet-rolling": scenario_fleet_rolling}

#: extra `cli serve` argv every scenario's replica inherits — set by
#: --serve_quant so CI can re-run a scenario against the quantized engine
#: (the int8-specific assertions in scenario_evict key on it)
EXTRA_SERVE_ARGS: list = []


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("serving_chaos")
    ap.add_argument("scenario",
                    choices=sorted(SCENARIOS) + ["kill", "rolling"])
    ap.add_argument("--fleet", action="store_true",
                    help="map kill/rolling to the fleet- scenarios")
    ap.add_argument("--serve_quant", default="off", choices=["off", "int8"],
                    help="run the scenario's engine quantized: the warm "
                    "restarts then prove recovery of the int8 program keys")
    ap.add_argument("--out_dir", default=None)
    ns = ap.parse_args(argv)
    scenario = ns.scenario
    if ns.fleet and not scenario.startswith("fleet-"):
        scenario = f"fleet-{scenario}"
    if scenario not in SCENARIOS:
        ap.error(f"unknown scenario {scenario!r}")
    if ns.serve_quant != "off":
        EXTRA_SERVE_ARGS.extend(["--serve_quant", ns.serve_quant])
    out_dir = ns.out_dir or f"/tmp/serving_chaos_{scenario}"
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    SCENARIOS[scenario](out_dir)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
