"""OLMoE at its published widths on the chip: the program's logits (bf16
compute, flash kernels, sorted rows + grouped GEMM) against the plain float32
reference, on one seeded 4096-token row, ALL positions.

    chiprun --chips 1 -- python experiments/olmoe_logits_check.py [--seeds 3]

One layer (the benchmark's cut), seeded random weights as the program
initialises them, a row of the benchmark's Zipf corpus.  Two kinds of
difference have to be told apart.  bf16 rounding moves every logit a little.
A *routing flip* moves a few positions a lot: the program's router reads
bf16 activations, the reference float32 ones, so where a token's 8th and 9th
probabilities lie within that rounding the two choose a different eighth
expert, and in an untrained one-layer model (embedding of deviation 0.02,
so the MLP's output is most of the residual stream) one expert of eight is a
tenth of the logit.  Both are what bf16 does to this model, not a fault: the
float32 parity tests (tests/test_olmoe.py) hold the same code to 1e-5.  So
the script reads the program's routing too and reports, per seed: the share
of positions with a flip; the largest logit difference over the positions
WITHOUT one, as a share of the largest reference logit; the root-mean-square
difference over all positions as a share of the RMS logit.  The same three
for the reference computed with its weights rounded to float8 (e5m2, the
nearest precision below the configuration's bf16) must fail the limits.

Limits: from the chip run recorded in PERF.md §6 (PR 28), each with its reason below.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.lib import corpus, reference  # noqa: E402
from galvatron_tpu.models import modeling  # noqa: E402

#: positions whose top-8 differs.  A flip needs the 8th and 9th of 64
#: probabilities within bf16's rounding of the router's input (2^-9 a
#: component).  The chip gave 3.3%, 4.5%, 4.2% over three seeds and 39-42% with
#: float8 weights (my chip run, PR 28, call 3): 10% is 2.2x the first, a quarter of the second.
MAX_FLIP_SHARE = 0.10
#: largest |program - reference| over the positions without a flip, as a share
#: of the largest |reference logit|: a logit is a 2048-term dot product of
#: bf16-rounded activations behind one attention and one MoE layer.  The chip
#: gave 6.8e-3, 8.3e-3, 8.1e-3 (about two bf16 ulps, 2^-8 = 3.9e-3, of the
#: largest logit); float8 weights 9.2e-2 to 1.1e-1.
TOL_SAME_ROUTING = 2e-2
#: RMS difference over ALL positions as a share of the RMS logit (flips
#: included: where one expert of eight differs the logit moves by up to 14% of
#: the largest).  The chip gave 2.3e-2 to 2.5e-2; float8 weights 1.0e-1 to 1.1e-1.
TOL_RMS = 5e-2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on any backend: checks the script, measures nothing")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        raise SystemExit("olmoe_logits_check: needs a TPU (the CPU parity tests are tests/test_olmoe.py)")
    with open(os.path.join(ROOT, "benchmark", "configs", "olmoe-1b-7b.json")) as f:
        config = json.load(f)
    arch = reference.load(ROOT, config["model_type"])
    cfg = modeling.PRESETS["olmoe-1b-7b"].replace(
        num_layers=int(config["num_hidden_layers"]), attn_impl="flash", max_seq_len=args.seq)
    if args.rehearse:
        config = dict(config, hidden_size=256, num_attention_heads=2, intermediate_size=128,
                      vocab_size=512)
        cfg = cfg.replace(hidden_size=256, num_heads=2, ffn_dim=128, vocab_size=512)
    program = jax.jit(lambda p, t: modeling.forward(p, t, cfg).astype(jnp.float32))

    def program_routing(p, t):
        """The experts the program's one layer chooses: its own embed,
        attention, norm and router arithmetic (bf16 activations, fp32 router)."""
        lp = p["layers"][0]
        x = modeling.embed(t, p, cfg)
        x = x + modeling.attn_block(modeling.norm(x, lp["attn_norm"], cfg), lp["attn"], cfg,
                                    modeling.rope_tables(cfg, t.shape[1]), None)
        y = modeling.norm(x, lp["mlp_norm"], cfg).reshape(-1, cfg.hidden_size)
        probs = jax.nn.softmax(
            y.astype(jnp.float32) @ lp["mlp"]["router"]["w"].astype(jnp.float32), axis=-1)
        return jnp.sort(jax.lax.top_k(probs, cfg.moe_top_k)[1], axis=-1)

    def plain(p, t, low):
        with jax.default_matmul_precision("highest"):
            w = arch.published_weights(p, config)
            if low:  # weights through float8 (e5m2), arithmetic as before
                w = jax.tree.map(lambda a: a.astype(jnp.float8_e5m2), w)
            w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
            logits, routed = arch._forward(w, t, config)
            return logits, jnp.sort(routed[0][1], axis=-1).reshape(-1, cfg.moe_top_k)

    plain = jax.jit(plain, static_argnums=2)
    program_routing = jax.jit(program_routing)
    limits = {"flip_share": MAX_FLIP_SHARE, "same_routing_max_rel": TOL_SAME_ROUTING,
              "rms_rel": TOL_RMS}
    worst = dict.fromkeys(limits, 0.0)
    for seed in range(2147483700, 2147483700 + args.seeds):
        params = jax.jit(lambda k: modeling.init_model_params(k, cfg))(jax.random.key(seed))
        tokens = jnp.asarray(corpus.windows(corpus.make_tokens(
            seed, 1 << 16, cfg.vocab_size, zipf_a=1.0, follow_p=0.5), args.seq, 1)[:, :-1], jnp.int32)
        want, routed = (np.asarray(a) for a in plain(params, tokens, False))
        low, low_routed = plain(params, tokens, True)
        rows = {}
        for name, got, chose in (("program", program(params, tokens), program_routing(params, tokens)),
                                 ("float8", low, low_routed)):
            diff = np.abs(np.asarray(got) - want)[0]  # (positions, vocab)
            flipped = np.any(np.asarray(chose) != routed, axis=-1)
            rows[name] = {
                "flip_share": float(flipped.mean()),
                "same_routing_max_rel": float(diff[~flipped].max() / np.abs(want).max()),
                "flipped_max_rel": float(diff[flipped].max() / np.abs(want).max()) if flipped.any() else 0.0,
                "rms_rel": float(np.sqrt((diff ** 2).mean()) / np.sqrt((want ** 2).mean())),
            }
        for k in worst:
            worst[k] = max(worst[k], rows["program"][k])
        ok = all(rows["program"][k] <= v for k, v in limits.items()) and not any(
            rows["float8"][k] <= v for k, v in limits.items())
        print(json.dumps({"seed": seed, "seq": args.seq, "largest_reference_logit":
                          float(np.abs(want).max()), **rows, "limits": limits, "ok": ok}), flush=True)
        if not ok and not args.rehearse:
            return 1
    print(json.dumps({"worst_program": worst, "limits": limits}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
