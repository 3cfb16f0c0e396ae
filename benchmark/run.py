#!/usr/bin/env python3
"""One run of one benchmark cell on the TPU this process is started on.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no children.  It loads, warms up, measures for ``--seconds`` and
prints one JSON object as the last line of its output.  There is no CPU
fallback: without a TPU, with fewer or more chips than the cell names, or on a
device that ``lib/peaks.json`` does not list, it exits non-zero and prints no
result.  ``--out DIR`` keeps the run's files (corpus, metrics, spans, trace,
searched plan) in DIR; without it they live in a temporary directory that is
removed at the end.
"""

from __future__ import annotations

import time

T_START = time.time()  # set-up counts from here: before any heavy import

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--out", default=None, help="keep the run's files here")
    args = ap.parse_args(argv)

    from benchmark.lib import harness

    cell, _, _ = harness.load_cell(ROOT, args.workload)

    import jax

    devs = jax.devices()
    d0 = devs[0]
    harness.say(f"device: platform={d0.platform} kind={d0.device_kind} count={len(devs)} "
                f"jax={jax.__version__}")
    if d0.platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU, JAX reports platform {d0.platform!r}; "
                         "there is no CPU fallback")
    if len(devs) != cell["chips"]:
        raise SystemExit(f"benchmark: cell {args.workload} is defined on {cell['chips']} "
                         f"chip(s) and the trainer spans every device; JAX reports {len(devs)}")
    peaks = harness.load_peaks(ROOT)
    if d0.device_kind not in peaks:
        raise SystemExit(f"benchmark: device kind {d0.device_kind!r} is not in lib/peaks.json "
                         f"({sorted(peaks)}); add its published peaks with their source")

    out_dir = args.out or tempfile.mkdtemp(prefix="galvatron_bench_")
    try:
        result = harness.run(ROOT, args.workload, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), out_dir=out_dir, t_start=T_START,
                             peaks_row=peaks[d0.device_kind])
    finally:
        if not args.out:
            shutil.rmtree(out_dir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
