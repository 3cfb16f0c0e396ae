#!/usr/bin/env python3
"""Read what ``correct`` compares, over seeds, for the program and its control.

    python benchmark/control.py --workload <serve cell> --seeds 1,2,3 [--seconds 30] [--modes sound,int8,hot,no_nucleus]

One process (set-up is long): for every seed and mode one run of the cell
through ``run_serve_cell`` at the cell's own load.  Mode ``sound`` is the cell
as it is.  Mode ``int8`` is the control: the program's own path in the nearest
precision below the bfloat16 the configuration serves in, ``--serve_quant
int8`` (per-channel int8 weights in every layer's GEMMs, bfloat16
activations), switched on and nothing else changed.  Modes ``hot`` and
``no_nucleus`` plant a sampler's fault through the engine's public taps
(``lib/faults.py``): the engine draws every sampled request at temperature 1.0,
or with no ``top_p``, where the request states the traffic file's; they give the
upper readings of ``sampled_logprob_z`` and ``sampled_outside_nucleus`` at the
cell's own size.  Prints, a run, the mean
divergence of the engine's softmax from the float32 reference's over the
compared rows (and what is read beside it), and at the end the largest of the sound runs
and the smallest of the control's: the limit in the traffic file lies between
them (PERF.md section 6).  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

INT8 = ("--serve_quant", "int8", "--quant_drift_max", "1e9")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--modes", default="sound,int8", help="sound and/or int8, comma-separated")
    args = ap.parse_args(argv)

    import jax

    from benchmark.lib import faults, serve

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("control: needs a TPU; the limits are set from chip readings")
    planted = {"sound": None, "int8": None, "hot": faults.hot, "no_nucleus": faults.no_nucleus}
    read = {mode: [] for mode in planted}
    for seed in (int(x) for x in args.seeds.split(",")):
        for mode in args.modes.split(","):
            out_dir = tempfile.mkdtemp(prefix="galvatron_control_")
            try:
                with faults.submitting(planted[mode]):
                    res = serve.run_serve_cell(
                        ROOT, args.workload, seed=seed, seconds=args.seconds, trace=False,
                        out_dir=out_dir, t_start=time.time(),
                        overrides=INT8 if mode == "int8" else ())
            finally:
                shutil.rmtree(out_dir, ignore_errors=True)
            read[mode].append(res["compared"]["logits_kl"])
            print("CONTROL " + json.dumps({"mode": mode, "seed": seed, "correct": res["correct"],
                                           "failed": res["failed"], **res["compared"]}), flush=True)
    print("READINGS " + json.dumps({
        "workload": args.workload, "sound_largest": max(read["sound"], default=None),
        "control_smallest": min(read["int8"], default=None), **read}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
