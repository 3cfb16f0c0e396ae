#!/usr/bin/env python3
"""Digest of the rows a serving cell's engine serves, on the chip: are they the parent's?

`logits_kl` of a same-seed pair says so only while both sides finish the same requests
inside the window; a change that makes the engine faster finishes others, and the two
numbers are then means over different rows (PERF.md section 6, PR 62).  This asks the
question directly: the cell's engine as the benchmark builds it (`benchmark/lib/serve.
build_engine`: the configuration's flags, the traffic file's, weights from the seed),
``--requests`` GREEDY requests submitted at once (temperature 0: every token is its
row's argmax, so the sequences depend on no stream and on no iteration's make-up;
prompts of ``--prompt`` tokens drawn from the seed, through
whole and partial chunks, ``--new`` tokens each), every row each token was drawn from
kept by the engine's own tap (`submit_request(capture_logits=)`), and a sha256 of each
request's rows and tokens printed.

    chiprun --chips 1 -- python experiments/served_rows_digest.py \
        --workload trinity-large-preview_serve_agent_above_knee --seed 2147620009

Run it in a ``git archive`` of the parent (this file laid over it) and in the change,
in one call: equal digests = the same float32 rows, bit for bit.  One JSON line a
request and one for the run; needs the chip the cell needs (no CPU fallback).

Where a change means to move the rows by a rounding (another kernel for the same
mathematics), ``--rows PREFIX`` says how far: the first run keeps each request's rows and
tokens under ``PREFIX.<i>.npz``, a later run with the same ``PREFIX`` compares its own with
them (``vs_kept``: the tokens up to the first that differs, and over those rows the largest
difference of a logit, the mean KL of the two softmaxes, how many rows are the same bits, and
the largest difference in the first row, which the chunk program wrote, and in the later ones,
which the decode program wrote).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402


def _kept(path, rows, tokens) -> dict:
    """Keep ``rows`` and ``tokens`` at ``path``, or, where an earlier run kept its own
    there, how far these are from them: over the rows up to the first token that differs
    (after it the two requests attend other positions)."""
    if not os.path.exists(path):
        np.savez(path, rows=rows, tokens=np.asarray(tokens, np.int64))
        return {"kept": path}
    with np.load(path) as other:
        theirs, their_tokens = other["rows"], other["tokens"].tolist()
    same = next((j for j, (a, b) in enumerate(zip(tokens, their_tokens)) if a != b),
                min(len(tokens), len(their_tokens)))
    n = min(same + 1, len(rows), len(theirs))  # (the row the differing token was drawn from too)

    def log_softmax(x):
        x = x.astype(np.float64)
        x = x - x.max(-1, keepdims=True)
        return x - np.log(np.exp(x).sum(-1, keepdims=True))

    ours, kept = log_softmax(rows[:n]), log_softmax(theirs[:n])
    kl = float((np.exp(kept) * (kept - ours)).sum(-1).mean())
    # row 0 is the prompt's last row (the chunk program's head), the others a decode step's
    far = np.abs(rows[:n] - theirs[:n]).max(-1)
    return {"vs_kept": {"same_tokens": same, "of": len(their_tokens), "rows": n,
                        "max_abs_logit_diff": float(far.max()), "mean_kl": kl,
                        "rows_same_bits": int((far == 0).sum()),
                        "first_row_max_abs_diff": float(far[0]),
                        "later_rows_max_abs_diff": float(far[1:].max()) if n > 1 else None}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt", default="1400,2600", help="shortest,longest prompt")
    ap.add_argument("--new", type=int, default=64, help="tokens served a request")
    ap.add_argument("--rows", default=None, help="keep the rows under this prefix, or compare "
                    "with the rows an earlier run kept there")
    args = ap.parse_args(argv)

    import jax

    from benchmark.lib import harness
    from benchmark.lib import serve as serve_lib

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("served_rows_digest: needs a TPU")
    _, config, spec = harness.load_cell(ROOT, args.workload)
    engine, cfg, _ = serve_lib.build_engine(config, spec, args.seed)
    rng = np.random.default_rng(np.random.SeedSequence([args.seed, 0xD16E]))
    low, high = (int(n) for n in args.prompt.split(","))
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).tolist()
               for n in rng.integers(low, high + 1, args.requests)]
    bufs = [np.zeros((args.new, cfg.vocab_size), np.float32) for _ in prompts]
    whole = hashlib.sha256()
    try:
        reqs = [engine.submit_request(p, args.new, temperature=0.0, capture_logits=b,
                                      ttl_s=3600.0) for p, b in zip(prompts, bufs)]
        for i, (req, buf) in enumerate(zip(reqs, bufs)):
            req.future.result(timeout=1800)
            tokens = [int(t) for t in req.generated]
            rows = hashlib.sha256(np.ascontiguousarray(buf[:req.logits_rows]).tobytes())
            rows.update(np.asarray(tokens, np.int64).tobytes())
            whole.update(rows.digest())
            line = {"request": i, "prompt": len(prompts[i]), "tokens": len(tokens),
                    "rows": int(req.logits_rows), "finite": bool(np.isfinite(buf).all()),
                    "greedy": [int(np.argmax(r)) for r in buf[:len(tokens)]] == tokens,
                    "sha256": rows.hexdigest()}
            if args.rows:
                line.update(_kept(f"{args.rows}.{i}.npz", buf[:req.logits_rows], tokens))
            print(json.dumps(line), flush=True)
    finally:
        engine.close()
    print(json.dumps({"workload": args.workload, "seed": args.seed, "sha256": whole.hexdigest(),
                      "device": str(jax.devices()[0])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
