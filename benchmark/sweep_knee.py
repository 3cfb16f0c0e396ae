#!/usr/bin/env python3
"""Find the knee of a serving cell, once, on the chip (not part of a run).

    python benchmark/sweep_knee.py --workload <serve cell> --rates 0.3,0.4,... [--seconds 60]

One process, one engine.  For each rate the cell's own traffic (its lengths,
sampling, burst and engine flags; the rate replaced) is offered for
``--seconds``; the engine is emptied between rates.  A rate is sustained where
no request expired in the queue, was refused or failed and the queue's depth
at the end is at most the slot count (a request cut short by its end-to-end
deadline while decoding is printed apart: at 87 ms a token a 448-token answer
outlives the constructor's 30 s whatever the load).  Prints one line a rate and the knee; the table
goes into PERF.md and the number into the traffic file's ``knee`` block.  The
knee as a number, ``sustained_rps``, is what the engine completes with every
slot in use (the most tokens/s any rate read) over the mix's mean answer: the
rule by the queue alone admits a rate whose queue has only not yet grown past
the slots in 60 s (PERF.md section 6, PR 35).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests a second")
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=2**31 + 35)
    args = ap.parse_args(argv)

    import jax

    from benchmark.lib import harness, serve, traffic
    from benchmark.lib.stats import percentile

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep_knee: needs a TPU; a knee from a CPU run names no device number")
    _, config, spec = harness.load_cell(ROOT, args.workload)
    engine, _, _ = serve.build_engine(config, spec, args.seed)
    slots = engine.slots.num_slots
    engine.generate([[1] * (engine.prefill_chunk + 1)], max_new_tokens=2)
    rows, knee = [], None
    for rate in (float(r) for r in args.rates.split(",")):
        at = json.loads(json.dumps(spec))
        at["arrivals"]["rate_rps"] = rate
        requests = traffic.schedule(args.seed, at, int(config["vocab_size"]), args.seconds + 5)
        before = engine.scheduler.counters.snapshot()
        run = serve.offer(engine, requests, {"opens": "traffic_start", "settle_s": 0}, args.seconds)
        depth = engine.scheduler.depth
        after = engine.scheduler.counters.snapshot()
        records = run["gen"].records
        num = serve.window_numbers(records, run["t_open"], run["t_close"])
        bad = sum(after[k] - before[k] for k in ("expired", "rejected_queue_full", "failed"))
        refused = sum(1 for r in records if r["error"])
        ok = bad == 0 and refused == 0 and depth <= slots
        row = {"rate_rps": rate, "submitted": len(records),
               "completed": after["completed"] - before["completed"],
               "expired_or_refused": bad + refused,
               "cut_by_deadline": after["expired_decode"] - before["expired_decode"],
               "queue_depth_end": depth, "tokens_per_s": num["tokens"] / args.seconds,
               "ttft_p50_ms": 1e3 * percentile(num["ttft_s"], 50) if num["ttft_s"] else None,
               "ttft_p95_ms": 1e3 * percentile(num["ttft_s"], 95) if num["ttft_s"] else None,
               "itl_p50_ms": 1e3 * percentile(num["itl_s"], 50) if num["itl_s"] else None,
               "itl_p95_ms": 1e3 * percentile(num["itl_s"], 95) if num["itl_s"] else None,
               "sustained": ok}
        print("SWEEP " + json.dumps(row), flush=True)
        rows.append(row)
        if ok:
            knee = rate if knee is None else max(knee, rate)
        serve.cancel_open(records)
        deadline = time.time() + 60
        while ((engine.slots.active_count or not engine.scheduler.empty())
               and time.time() < deadline):
            time.sleep(0.05)
    answer = traffic.mean_output_len(spec)
    most = max(r["tokens_per_s"] for r in rows)
    print("KNEE " + json.dumps({"workload": args.workload, "knee_rps": knee, "num_slots": slots,
                                "seconds": args.seconds, "saturated_tokens_per_s": most,
                                "mean_answer_tokens": answer, "sustained_rps": most / answer}),
          flush=True)
    serve.release_cache(engine)
    engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
