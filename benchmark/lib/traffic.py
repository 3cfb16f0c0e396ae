"""Serving traffic: one general open-loop generator, driven by a traffic file.

A traffic file with ``"kind": "serve"`` holds parameters only (lengths, rate,
burst, sampling); a later PR adds a mix by adding a file.  Every seed offers
the SAME multiset of (prompt length, output length, sampling) pairs and the
same multiset of gaps between arrivals; the seed decides their order, the
arrival times that follow from that order, and the token ids.  So two seeds
differ as two minutes of one service differ, not as two services do.

``grid(spec)`` is the fixed part: ``n`` pairs, the quantiles of two
log-normals, paired by a fixed stride so that long prompts do not all meet
long outputs.  ``schedule(seed, spec, vocab)`` is the seeded part: the grid
and the gaps, each visited in an order shuffled afresh for every cycle of
``n`` requests.  Greedy requests (what ``correct`` is decided on) sit at fixed
grid places, the longest request among them.  They are greedy through the
sampler's own path: ``greedy_temperature`` (1e-4) with the others' ``top_p``
leaves the engine's best token all the probability (tokens whose bfloat16
logits tie share it), and costs the host what a sampled request costs, so a
slot's time does not depend on which kind holds it.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

from benchmark.lib import corpus


def _lognormal_quantiles(n: int, median: float, sigma: float, lo: int, hi: int) -> List[int]:
    z = [NormalDist().inv_cdf((j + 0.5) / n) for j in range(n)]
    return [int(min(hi, max(lo, round(median * math.exp(sigma * zj))))) for zj in z]


def grid(spec: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The ``n`` request shapes every cycle offers, in grid order (by prompt
    length).  ``greedy`` marks every ``greedy_every``-th place counted from the
    top, so the longest prompt is a greedy request."""
    lengths, sampling = spec["lengths"], spec["sampling"]
    n = int(lengths["grid"])
    prompts = _lognormal_quantiles(n, **lengths["prompt"])
    outputs = _lognormal_quantiles(n, **lengths["output"])
    stride = int(lengths["pair_stride"])
    if math.gcd(stride, n) != 1:
        raise ValueError(f"pair_stride {stride} must be coprime to the grid's {n} places")
    every = int(sampling["greedy_every"])
    if n % every:
        raise ValueError(f"greedy_every {every} must divide the grid's {n} places")
    out = []
    for j in range(n):
        p, o = prompts[j], outputs[(j * stride) % n]
        o = min(o, int(lengths["max_total"]) - p)
        if o < 1:
            raise ValueError(f"grid place {j}: prompt {p} leaves no room under max_total")
        out.append({"prompt_len": p, "output_len": o, "greedy": (n - 1 - j) % every == 0})
    return out


def gaps(spec: Dict[str, Any]) -> List[float]:
    """The ``n`` gaps between arrivals of one cycle: the quantiles of the
    exponential law (a Poisson process's gaps), scaled so that a cycle lasts
    exactly ``n / rate_rps`` seconds."""
    n = int(spec["lengths"]["grid"])
    q = [-math.log(1.0 - (j + 0.5) / n) for j in range(n)]
    scale = n / float(spec["arrivals"]["rate_rps"]) / sum(q)
    return [g * scale for g in q]


def mean_output_len(spec: Dict[str, Any]) -> float:
    """Tokens in a mean answer of the mix: what turns tokens/s into requests/s."""
    shapes = grid(spec)
    return sum(sh["output_len"] for sh in shapes) / len(shapes)


def horizon_s(spec: Dict[str, Any], seconds: float) -> float:
    """How far a run's schedule is drawn: the window and what lies around it,
    and half a minute to spare for the way to the window's opening."""
    win = spec["window"]
    return float(win["settle_s"]) + seconds + float(win.get("first_token_grace_s", 0.0)) + 30.0


def schedule(seed: int, spec: Dict[str, Any], vocab_size: int, horizon_s: float
             ) -> List[Dict[str, Any]]:
    """Requests due in ``[0, horizon_s)``: ``burst_at_start`` of them at 0 (the
    service is met busy, not empty), then one after every gap.  Each is
    ``{"i", "due_s", "tokens", "max_new_tokens", "temperature", "top_p",
    "greedy", "capture"}``; the token ids are windows of the seeded Zipf corpus
    the training cells use.  ``capture`` marks the requests whose logits rows
    the runner keeps for ``correct``: every ``correct.capture_every``-th
    arrival, from a place the seed draws; coprime to ``greedy_every``, so that
    sampled and greedy requests are both among them whatever that place."""
    shapes, gap = grid(spec), gaps(spec)
    n = len(shapes)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x5E12FE]))
    cs = spec["corpus"]
    stream = corpus.make_tokens(int(seed), int(cs["tokens"]), vocab_size,
                                zipf_a=cs["zipf_a"], follow_p=cs["follow_p"])
    sampling = spec["sampling"]
    burst = int(spec["arrivals"].get("burst_at_start", 0))
    out: List[Dict[str, Any]] = []
    t, i = 0.0, 0
    every = int(sampling["greedy_every"])
    capture_every = int(spec["correct"]["capture_every"])
    if math.gcd(capture_every, every) != 1:
        # the greedy tokens ``correct`` holds exactly are those of captured requests
        raise ValueError(f"correct.capture_every {capture_every} must be coprime to greedy_every "
                         f"{every}: from some places the seed draws no greedy request's rows "
                         f"would be kept")
    capture_from = int(rng.integers(0, capture_every))
    greedy = [j for j, sh in enumerate(shapes) if sh["greedy"]]
    sampled = [j for j, sh in enumerate(shapes) if not sh["greedy"]]
    while t < horizon_s:
        # every ``greedy_every``-th arrival is a greedy request: any stretch of a
        # run holds its share of what ``correct`` is decided on
        g, s_, gap_order = rng.permutation(greedy), rng.permutation(sampled), rng.permutation(n)
        order = [int(g[k // every]) if k % every == 0 else int(s_[k - k // every - 1])
                 for k in range(n)]
        for k in range(n):
            if i >= burst:
                t += gap[gap_order[k]]
            if t >= horizon_s:
                break
            shape = shapes[order[k]]
            start = int(rng.integers(0, len(stream) - shape["prompt_len"]))
            out.append({
                "i": i, "due_s": t, "greedy": shape["greedy"],
                "capture": (i + capture_from) % capture_every == 0,
                "tokens": stream[start:start + shape["prompt_len"]].tolist(),
                "max_new_tokens": shape["output_len"],
                "temperature": float(sampling["greedy_temperature" if shape["greedy"]
                                              else "temperature"]),
                "top_p": float(sampling["top_p"]),
            })
            i += 1
    return out
