#!/usr/bin/env python3
"""What sets the floor of ``logits_kl`` in `nemotron-3-nano-30b-a3b_serve_chat_above_knee`
(sound runs read a relative error of 0.12 where rounding alone would give 1e-2), and what
the statistic could tell of the float32 scan state under another initialiser (PR 68).

    chiprun --chips 1 -- python experiments/nemotron_correct_floor.py [--seed N] [--rows 4]

The plain reference ALONE, at the cell's published widths, on the weights the benchmark
makes (``benchmark/lib/serve.make_weights``: every array normal 0.02), float32 at
``highest``; nothing of the program runs but its parameter tree's shapes.  ``--rows``
sequences of 1,024 corpus tokens; positions from 128 on are compared (a prompt is at
least 128 tokens), as ``lib/serve.compare_rows`` compares them (rows centred, the mean
Kullback-Leibler divergence from the sound forward's softmax, the relative error).

For each of two weight sets -- ``harness`` (as made) and ``published_init`` (the same, but
every Mamba-2 block's ``A_log`` = log U(1, 16), ``dt_bias`` = softplus^-1 of a step
log-uniform in [time_step_min, time_step_max] floored at time_step_floor, ``D`` = 1: the
published initialiser, which ``make_weights`` does not follow) -- it runs

- ``sound``: float32, every choice free: the others are compared with it;
- ``bf16_free``: the residual stream and every sublayer's input rounded to bfloat16 (what
  the program's compute type does to them), the router's top-6 free;
- ``bf16_replay``: the same rounding, every token's top-6 REPLAYED from ``sound``: what
  rounding alone costs when no choice flips;
- ``state_bf16``: float32 everywhere, the scan state rounded to bfloat16 after every
  position (a state kept below the configuration's float32);
- ``state_stale``: float32 everywhere, the scan starting from the state another sequence
  left (the same positions in reverse order) in place of zeros: a slot not reset;

and prints each one's readings, the share of (token, expert block) pairs whose OWN scores'
top-6 differs from ``sound``'s (under ``bf16_replay``: the flips that were overruled), and the time constant 1 / (dt |A|) the recurrence has (median
over heads and positions of the first Mamba-2 block).  PERF.md section 6 has the readings.
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

CELL = "nemotron-3-nano-30b-a3b_serve_chat_above_knee"
SEQ, FROM = 1024, 128
F32 = jnp.float32


def rounded(t):
    """float32 values rounded to bfloat16's 8 bits (to nearest, ties to even) BY THEIR BITS:
    a pair of converts is what the compiler may take out (``xla_allow_excess_precision``;
    the first chip run read exactly 0 for a state rounded so)."""
    bits = jax.lax.bitcast_convert_type(t.astype(F32), jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, F32)


def recurrence(x, dt, a, b_mat, c_mat, *, state_bf16=False, stale=False):
    """`references/nemotron_h.recurrence` with the two faults: the kept state rounded to
    bfloat16 after a position's read-out; a first state that is what the reversed
    sequence leaves."""
    s, h, p = x.shape
    g, n = b_mat.shape[1:]
    per = h // g

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        b_h, c_h = jnp.repeat(b_t, per, axis=0), jnp.repeat(c_t, per, axis=0)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        y = jnp.einsum("hpn,hn->hp", state, c_h)
        return (rounded(state) if state_bf16 else state), y

    first = jnp.zeros((h, p, n), F32)
    if stale:
        first = jax.lax.scan(step, first, (x[::-1], dt[::-1], b_mat[::-1], c_mat[::-1]))[0]
    return jax.lax.scan(step, first, (x, dt, b_mat, c_mat))[1]


def published_init(w, config, seed):
    """``w`` with every Mamba-2 block's per-head vectors drawn as the published
    initialiser draws them."""
    rng = np.random.default_rng(seed)
    lo, hi, floor = (float(config[k]) for k in ("time_step_min", "time_step_max", "time_step_floor"))
    blocks = []
    for bw in w["blocks"]:
        if "A_log" in bw:
            heads = bw["A_log"].shape[0]
            dt = np.maximum(np.exp(rng.uniform(np.log(lo), np.log(hi), heads)), floor)
            bw = dict(bw, A_log=jnp.asarray(np.log(rng.uniform(1.0, 16.0, heads)), bw["A_log"].dtype),
                      dt_bias=jnp.asarray(dt + np.log(-np.expm1(-dt)), bw["dt_bias"].dtype),
                      D=jnp.ones_like(bw["D"]))
        blocks.append(bw)
    return dict(w, blocks=blocks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3000000101)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests/test_nemotron.py's small configuration, 64 positions: the CPU's")
    ns = ap.parse_args(argv)

    from benchmark.lib import corpus, harness, reference, serve
    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args

    global SEQ, FROM
    _, config, spec = harness.load_cell(ROOT, CELL)
    arch = reference.load(ROOT, config["model_type"])
    if ns.rehearse:
        from tests import test_nemotron as small

        SEQ, FROM = 64, 8
        cfg = small.small_cfg()
        config = dict(small.ref_cfg(cfg), **{k: config[k] for k in (
            "time_step_min", "time_step_max", "time_step_floor")})
    else:
        cfg = model_config_from_args(initialize_galvatron(
            "serve", [*config["program_flags"], *spec["serve_flags"]]))
    params = serve.make_weights(cfg, ns.seed)
    made = arch.published_weights(params, config)
    eps, top = float(config["layer_norm_epsilon"]), int(config["num_experts_per_tok"])
    pattern = arch.pattern(config)
    tokens = corpus.make_tokens(ns.seed, ns.rows * SEQ, int(config["vocab_size"]),
                                zipf_a=spec["corpus"]["zipf_a"], follow_p=spec["corpus"]["follow_p"])
    tokens = jnp.asarray(tokens.reshape(ns.rows, SEQ), jnp.int32)

    forced = [None]  # the top-6 the patched router hands out: a tracer of the block's program
    real_route, real_recurrence = arch.route, arch.recurrence

    def route(m, bw, cfg):
        s = jax.nn.sigmoid(m @ bw["gate"])
        picked = jnp.take_along_axis(s, forced[0], axis=-1)
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + arch.NORM_TOPK_EPS)
        at = jnp.arange(m.shape[0])[:, None]
        return jnp.zeros_like(s).at[at, forced[0]].set(float(cfg["routed_scaling_factor"]) * picked)

    def block(kind, round_, faults):
        """One published block as a compiled program: (x, weights, replayed top-6 or
        None) -> (x, the top-6 the block's own scores give)."""
        def run(x, bw, replay):
            with jax.default_matmul_precision("highest"):
                bw = jax.tree.map(lambda t: t.astype(F32), bw)
                m = reference.rms_norm(x, bw["norm"], eps)
                m = rounded(m) if round_ else m
                chosen = jnp.zeros((SEQ, top), jnp.int32)
                if kind == "E":
                    s = jax.nn.sigmoid(m[0] @ bw["gate"])
                    chosen = jax.lax.top_k(s + bw["e_score_correction_bias"], top)[1]
                    forced[0] = chosen if replay is None else replay
                    arch.route = route
                arch.recurrence = lambda *t: recurrence(*t, **faults)
                try:
                    x = x + arch.SUBLAYERS[kind](m, bw, config)
                finally:
                    arch.route, arch.recurrence = real_route, real_recurrence
                return (rounded(x) if round_ else x), chosen
        return jax.jit(run)

    def head(x, w):
        with jax.default_matmul_precision("highest"):
            h = reference.rms_norm(x[0], w["norm_f"].astype(F32), eps)
            return h @ w["lm_head"].astype(F32)

    head = jax.jit(head)

    programs = {}

    def program(kind, round_, faults):
        key = (kind, round_, tuple(sorted(faults.items())))
        if key not in programs:
            programs[key] = block(kind, round_, faults)
        return programs[key]

    def forward(w, row, round_=False, replay=None, **faults):
        x = w["embeddings"][row][None].astype(F32)
        x = rounded(x) if round_ else x
        chosen, e = [], 0
        for kind, bw in zip(pattern, w["blocks"]):
            given = None if replay is None or kind != "E" else replay[e]
            x, c = program(kind, round_, faults)(x, bw, given)
            if kind == "E":
                chosen.append(c)
                e += 1
        return head(x, w)[FROM:], jnp.stack(chosen)

    @jax.jit
    def compare(got, ref):
        ref_c, got_c = ref - ref.mean(-1, keepdims=True), got - got.mean(-1, keepdims=True)
        logp = jax.nn.log_softmax(ref_c)
        kl = jnp.sum(jnp.exp(logp) * (logp - jax.nn.log_softmax(got_c)), -1)
        return jnp.sum((got_c - ref_c) ** 2), jnp.sum(ref_c ** 2), jnp.sum(kl)

    modes = {"bf16_free": dict(round_=True), "bf16_replay": dict(round_=True, replay=True),
             "state_bf16": dict(state_bf16=True), "state_stale": dict(stale=True)}
    out = {}
    for name, w in (("harness", made), ("published_init", published_init(made, config, ns.seed))):
        first = next(bw for bw in w["blocks"] if "A_log" in bw)
        x0 = reference.rms_norm(w["embeddings"][tokens[0]].astype(F32), first["norm"].astype(F32), eps)
        cols = first["in_proj"].shape[1]
        dt = jax.nn.softplus(x0 @ first["in_proj"][:, cols - first["A_log"].shape[0]:].astype(F32)
                             + first["dt_bias"].astype(F32))
        tau = 1.0 / (dt * jnp.exp(first["A_log"].astype(F32)))
        sums = {mode: np.zeros(5) for mode in modes}
        for r in range(ns.rows):
            sound, chosen = forward(w, tokens[r])
            for mode, kw in modes.items():
                kw = dict(kw)
                if kw.pop("replay", False):
                    kw["replay"] = chosen
                got, mine = forward(w, tokens[r], **kw)
                differs = jnp.any(jnp.sort(mine, -1) != jnp.sort(chosen, -1), axis=-1)[:, FROM:]
                e2, r2, kl = compare(got, sound)
                sums[mode] += np.array([float(e2), float(r2), float(kl), float(differs.sum()),
                                        float(jnp.any(differs, axis=0).sum())])
        n = ns.rows * (SEQ - FROM)
        out[name] = {"time_constant_positions_p50": float(jnp.median(tau)),
                     "time_constant_positions_p90": float(jnp.percentile(tau, 90))}
        for mode, (e2, r2, kl, pairs, rows) in sums.items():
            out[name][mode] = {"logits_rel_err": (e2 / r2) ** 0.5, "logits_kl": kl / n,
                               "flipped_pairs_share": pairs / (n * chosen.shape[0]),
                               "rows_with_a_flip_share": rows / n}
        print(name, json.dumps(out[name]), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "nemotron_correct_floor.json"), "w") as f:
        json.dump({"seed": ns.seed, "rows": ns.rows, "positions": [FROM, SEQ], "device":
                   jax.devices()[0].device_kind, "readings": out}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
