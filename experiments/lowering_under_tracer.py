#!/usr/bin/env python3
"""Does the tracer slow a trace-and-lower?  (ROADMAP A12's first suspect.)

    python experiments/lowering_under_tracer.py [--program serving_decode] [--reps 3]

One process: the serving step program at ``opt-1.3b`` widths (8 slots x 2048,
abstract arguments, nothing compiled or run) is traced and lowered ``--reps``
times in each of three states, jax's caches cleared before every one: the
tracer never enabled (no ``jax.monitoring`` listener installed: what an
untraced benchmark run is), the tracer on (listeners recording ``jax_trace`` /
``jax_lower`` spans), the tracer off again (listeners installed, returning at
once).  Prints the seconds of every lowering and each state's median.
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--program", default="serving_decode")
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args(argv)

    import jax

    from galvatron_tpu.aot import registry
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.obs import tracing
    from galvatron_tpu.serving import engine  # noqa: F401  (registers the serving family)

    ctx = registry.ProgramContext(cfg=PRESETS["opt-1.3b"], num_slots=8, prefill_chunk=256,
                                  max_seq_len=2048)
    spec, = [sp for sp in registry.enumerate_programs(ctx, include=("serving",))
             if sp.name == args.program]
    print(f"device: {jax.devices()[0].platform} {jax.devices()[0].device_kind}; "
          f"program {args.program}", flush=True)

    def lower_s() -> float:
        jax.clear_caches()
        t0 = time.perf_counter()
        spec.fn.lower(*spec.args)
        return time.perf_counter() - t0

    lower_s()  # imports and first-use costs of jax itself: not counted
    assert not tracing._jax_listeners_installed
    for state in ("never enabled", "on", "off again"):
        if state == "on":
            tracing.tracer.enable(capacity=1 << 17)
        elif state == "off again":
            spans = sum(1 for r in tracing.tracer.snapshot() if r["name"].startswith("jax_"))
            tracing.tracer.disable()
            print(f"  ({spans} jax_* spans recorded while on)")
        secs = [lower_s() for _ in range(args.reps)]
        print(f"tracer {state:14s}: " + " ".join(f"{s:.3f}" for s in secs)
              + f"  median {statistics.median(secs):.3f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
