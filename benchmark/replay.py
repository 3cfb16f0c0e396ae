#!/usr/bin/env python3
"""Replay a serving cell's schedule off the chip, through a model of the engine.

    python benchmark/replay.py --workload <serve cell> [--engine engine_ms|judges_up_to|a,b,c]
                               [--traffic FILE] [--seeds 8] [--seconds 51]

No chip, no JAX, no run calls it (it stands beside ``sweep_knee.py`` and
``control.py``).  ``lib/traffic.schedule`` of the cell for a seed goes through
an engine whose iteration is what ``serving/engine.py``'s is: admissions first
(every queued request that finds a free slot, its prompt prefilled in chunks of
``--prefill_chunk`` at ``prefill_chunk`` ms a chunk, the batch standing still
meanwhile), then one draw a slot in use at ``per_slot`` ms each (a token is
stamped when it is drawn; a request that has its length frees its slot), then
the shared forward and the loop's own time, ``per_iteration`` ms.  The window
is the cell's own rule (``window.opens`` + ``settle_s``, ``--seconds`` long).

It answers, before a chip is asked, what a traffic file will read: tokens/s in
the window, the slots' occupancy, the queue's depth at the window's first and
last iteration, and whether the rows ``correct`` keeps have room.  The three
times of an engine come from the traffic file's ``knee`` block (``engine_ms``:
read on the chip; ``judges_up_to``: the fastest engine the cell is meant to
judge) or from ``--engine per_slot,per_iteration,prefill_chunk``.  What it
prints is a model's reading and never a device number.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from collections import deque
from statistics import median, quantiles
from typing import Any, Dict, List, Sequence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import harness, traffic  # noqa: E402

#: how often the runner looks whether every slot is in use (``serve.offer``)
POLL_S = 0.02


def flag(spec: Dict[str, Any], name: str) -> int:
    flags = spec["serve_flags"]
    return int(flags[flags.index(name) + 1])


def replay(spec: Dict[str, Any], seed: int, engine_ms: Dict[str, float], seconds: float,
           vocab_size: int = 50272) -> Dict[str, Any]:
    """One seed's schedule through the engine ``engine_ms`` describes
    (``per_slot``, ``per_iteration``, ``prefill_chunk``, in ms).  Returns the
    window's tokens/s, occupancy (%), queue depths, admissions, and what
    ``correct`` would have of the captured requests."""
    per_slot, per_iter, per_chunk = (float(engine_ms[k]) / 1e3
                                     for k in ("per_slot", "per_iteration", "prefill_chunk"))
    slots, chunk = flag(spec, "--num_slots"), flag(spec, "--prefill_chunk")
    win = spec["window"]
    settle = float(win["settle_s"])
    todo = deque(traffic.schedule(seed, spec, vocab_size, traffic.horizon_s(spec, seconds)))
    rows_kept, room = int(spec["correct"]["rows_kept"]), 0
    queue: deque = deque()
    active: List[Dict[str, Any]] = []
    t = 0.0
    t_open = settle if win["opens"] == "traffic_start" else None
    stamps: List[float] = []
    iterations: List[Dict[str, float]] = []   # start, slots in use, queue depth at entry
    admitted: List[Dict[str, Any]] = []

    def arrive(now: float) -> None:
        nonlocal room
        while todo and todo[0]["due_s"] <= now:
            r = todo.popleft()
            # rows are reserved when a request is submitted, in the order of arrival
            r["has_room"] = bool(r["capture"]) and room + r["max_new_tokens"] <= rows_kept
            if r["has_room"]:
                room += r["max_new_tokens"]
            queue.append(r)

    while todo or queue or active:
        arrive(t)
        if not queue and not active:
            t = todo[0]["due_s"]  # an idle engine sleeps until the next arrival
            continue
        if t_open is not None and t >= t_open + seconds:
            break
        iterations.append({"start": t, "active": len(active), "queued": len(queue)})
        while queue and len(active) < slots:
            r = queue.popleft()
            r["left"], r["admitted_s"] = r["max_new_tokens"], t
            t += math.ceil(len(r["tokens"]) / chunk) * per_chunk
            active.append(r)
            admitted.append(r)
            arrive(t)
        if t_open is None and len(active) == slots:
            # the runner sees it at its next look, then waits ``settle_s``
            t_open = math.ceil(t / POLL_S) * POLL_S + settle
        in_use = len(active)
        iterations[-1]["sampled"] = in_use
        going = []
        for r in active:
            t += per_slot
            stamps.append(t)
            r["left"] -= 1
            if r["left"]:
                going.append(r)
            else:
                r["finished_s"] = t
        active = going
        if active:
            t += per_iter

    if t_open is None:
        raise harness.BenchmarkError(f"seed {seed}: the {slots} slots were never all in use")
    t_close = t_open + seconds
    inside = [it for it in iterations if t_open <= it["start"] < t_close]
    kept = [r for r in admitted if r["capture"] and r["admitted_s"] < t_close]
    return {
        "seed": seed,
        "tokens_per_s": sum(1 for s in stamps if t_open <= s < t_close) / seconds,
        "occupancy": 100.0 * sum(it["sampled"] for it in inside) / len(inside) / slots,
        "queue_first": int(inside[0]["queued"]), "queue_last": int(inside[-1]["queued"]),
        "opens_s": t_open,
        "admitted_in_window": sum(1 for r in admitted if t_open <= r["admitted_s"] < t_close),
        "captured": len(kept),
        # rows written by the window's close, had every captured request found room
        "captured_rows": sum(r["max_new_tokens"] - r["left"] for r in kept),
        "captured_finished": sum(1 for r in kept
                                 if r["has_room"] and r.get("finished_s", t_close) < t_close),
    }


def spread(values: Sequence[float]) -> float:
    """The distance between the quartiles as a share of the median: the
    driver's measure of a set's spread."""
    q = quantiles(values, n=4)
    return (q[2] - q[0]) / median(values)


def summary(spec: Dict[str, Any], engine_ms: Dict[str, float], seeds: Sequence[int],
            seconds: float) -> Dict[str, Any]:
    runs = [replay(spec, s, engine_ms, seconds) for s in seeds]
    rates = [r["tokens_per_s"] for r in runs]
    return {"runs": runs, "tokens_per_s_median": median(rates), "tokens_per_s_min": min(rates),
            "tokens_per_s_max": max(rates), "spread": spread(rates),
            "occupancy_min": min(r["occupancy"] for r in runs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--traffic", default=None,
                    help="a traffic file to read in place of the cell's own (an older one, say)")
    ap.add_argument("--engine", default="engine_ms",
                    help="a key of the file's knee block, or per_slot,per_iteration,prefill_chunk in ms")
    ap.add_argument("--seeds", type=int, default=8, help="how many seeds, from --seed on")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None, help="default: the manifest's run_seconds")
    args = ap.parse_args(argv)

    _, _, spec = harness.load_cell(ROOT, args.workload)
    if args.traffic:
        with open(args.traffic) as f:
            spec = json.load(f)
    if args.engine in spec.get("knee", {}):
        engine_ms = spec["knee"][args.engine]
    else:
        engine_ms = dict(zip(("per_slot", "per_iteration", "prefill_chunk"),
                             (float(x) for x in args.engine.split(","))))
    seconds = args.seconds or float(harness.load_manifest(ROOT)["run_seconds"])
    out = summary(spec, engine_ms, range(args.seed, args.seed + args.seeds), seconds)
    for r in out.pop("runs"):
        print("REPLAY " + json.dumps(r), flush=True)
    print("SUMMARY " + json.dumps({"workload": args.workload, "engine_ms": engine_ms,
                                   "seconds": seconds, "model_not_device": True, **out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
