"""The serving half of the benchmark on the CPU, tiny: the traffic generator
(a function of the seed over one fixed multiset), the window's accounting, the
whole serve run from a temporary copy of the benchmark's directories, and that
``correct`` comes out false when the timed path is broken underneath or the
engine serves its own int8 weights (the control).  No number from these runs is a device number."""

import json
import os
import shutil
import sys
import time
from collections import Counter

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.lib import faults, harness, reference, serve, traffic  # noqa: E402

TINY_CONFIG = {
    "model_type": "opt", "hidden_size": 256, "ffn_dim": 1024, "num_attention_heads": 4,
    "num_hidden_layers": 4, "tie_word_embeddings": True, "vocab_size": 2048,
    "program_flags": ["--model_size", "opt-1.3b", "--num_layers", "4", "--hidden_size", "256",
                      "--num_heads", "4", "--ffn_dim", "1024", "--vocab_size", "2048",
                      "--seq_length", "128"],
}
#: small, but wide and deep enough that the layers, not the embedding, make the
#: logits.  Here (CPU, seeds 104-109, 134-183 compared rows a run) the mean
#: divergence of the engine's softmax from the float32 reference's reads 2.7e-6
#: to 3.7e-6, and the engine's own ``--serve_quant int8`` (the control) 1.2e-5 to
#: 1.8e-5 (the rows' relative error: 0.0073-0.0085 against 0.0155-0.0188).  The
#: cells' limit is set from chip readings: PERF.md section 6.
TINY_KL_MAX = 6.5e-6
INT8 = ("--serve_quant", "int8", "--quant_drift_max", "1e9")
TINY_TRAFFIC = {
    "kind": "serve",
    "lengths": {"grid": 8, "pair_stride": 3, "max_total": 120,
                "prompt": {"median": 24, "sigma": 0.7, "lo": 4, "hi": 80},
                "output": {"median": 12, "sigma": 0.5, "lo": 4, "hi": 40}},
    "sampling": {"temperature": 0.8, "top_p": 0.95, "greedy_every": 4, "greedy_temperature": 1e-4},
    "corpus": {"tokens": 4096, "zipf_a": 1.0, "follow_p": 0.5},
    "arrivals": {"process": "exponential_gap_quantiles", "rate_rps": 150.0, "burst_at_start": 8},
    "serve_flags": ["--num_slots", "4", "--prefill_chunk", "16", "--max_queue", "4096",
                    "--request_ttl_s", "0"],
    "window": {"opens": "all_slots_used", "settle_s": 0.2, "first_token_grace_s": 0},
    "correct": {"requests": 12, "capture_every": 3, "rows_kept": 4096,
                "logits_kl_max": TINY_KL_MAX},
    "why": "tiny CPU rehearsal",
}
STEADY_TRAFFIC = dict(
    TINY_TRAFFIC, arrivals=dict(TINY_TRAFFIC["arrivals"], rate_rps=20.0, burst_at_start=2),
    serve_flags=["--num_slots", "4", "--prefill_chunk", "16"],
    window={"opens": "traffic_start", "settle_s": 0.3, "first_token_grace_s": 2})


def build_tiny_root(root):
    """A copy of the benchmark's directories plus files only: one tiny
    configuration, two tiny serving mixes (above the knee; below it, on the
    window rule that opens with the traffic) and their cells."""
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = harness.load_manifest(REPO)
    with open(os.path.join(root, "benchmark/configs/tiny-opt.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    manifest["configs"].append({"name": "tiny-opt", "source": "test", "reduced": [], "why": "t",
                                "file": "benchmark/configs/tiny-opt.json"})
    like = "opt-1.3b_serve_above_knee"
    # a tail no cell is judged on yet, so that the runner's reading of it is driven
    manifest["end_to_end"].append({"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
                                   "bound": 0.1, "source": "host_clock",
                                   "workloads": ["tiny_steady"]})
    for tname, spec in (("tiny_peak", TINY_TRAFFIC), ("tiny_steady", STEADY_TRAFFIC)):
        with open(os.path.join(root, f"benchmark/traffic/{tname}.json"), "w") as f:
            json.dump(spec, f)
        manifest["workloads"].append({"name": tname, "config": "tiny-opt", "traffic": tname,
                                      "chips": 1, "why": "t"})
        for entry in manifest["end_to_end"] + manifest["per_layer"]:
            if like in entry.get("workloads", []):
                entry["workloads"].append(tname)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return build_tiny_root(str(tmp_path_factory.mktemp("serve") / "root"))


def _run(root, cell, tmp_path, seed=104, seconds=1.0, trace=False, **kw):
    return harness.run(root, cell, seed=seed, seconds=seconds, trace=trace,
                       out_dir=str(tmp_path / f"{cell}_{seed}_{int(trace)}"),
                       t_start=time.time(), **kw)


# --- the traffic ------------------------------------------------------------


def _real_spec():
    return harness.load_cell(REPO, "opt-1.3b_serve_above_knee")[2]


def test_schedule_is_a_function_of_the_seed():
    spec = _real_spec()
    a = traffic.schedule(2**31 + 5, spec, 50272, 60.0)
    b = traffic.schedule(2**31 + 5, spec, 50272, 60.0)
    c = traffic.schedule(2**31 + 6, spec, 50272, 60.0)
    assert a == b and a != c
    assert all(0 <= t < 50272 for r in a for t in r["tokens"])


def test_every_seed_offers_the_same_lengths_and_gaps():
    """Whole cycles of two seeds hold the same multiset of (prompt, output,
    sampling) and of gaps; only the order and the token ids differ."""
    spec = _real_spec()
    n, burst = spec["lengths"]["grid"], spec["arrivals"]["burst_at_start"]

    def shapes_and_gaps(seed):
        reqs = traffic.schedule(seed, spec, 50272, 400.0)
        whole = reqs[:len(reqs) // n * n]
        shapes = Counter((len(r["tokens"]), r["max_new_tokens"], r["temperature"], r["top_p"])
                         for r in whole)
        # the first cycle wholly past the burst: all of its gaps are there
        c = -(-burst // n)
        due = [r["due_s"] for r in reqs[c * n - 1:(c + 1) * n]]
        return shapes, sorted(round(b - a, 9) for a, b in zip(due, due[1:]))

    (s1, g1), (s2, g2) = shapes_and_gaps(11), shapes_and_gaps(2**31 + 12)
    assert s1 == s2 and g1 == g2 == sorted(round(g, 9) for g in traffic.gaps(spec))
    assert sum(g1) == pytest.approx(n / spec["arrivals"]["rate_rps"])
    first = traffic.schedule(11, spec, 50272, 400.0)
    assert [r["due_s"] for r in first[:burst]] == [0.0] * burst


def test_grid_keeps_the_published_positions_and_spreads_greedy_and_kept_requests():
    spec = _real_spec()
    shapes = traffic.grid(spec)
    every = spec["sampling"]["greedy_every"]
    assert all(s["prompt_len"] + s["output_len"] <= 1984 < 2048 for s in shapes)
    assert shapes[-1]["greedy"] and shapes[-1]["prompt_len"] == max(s["prompt_len"] for s in shapes)
    reqs = traffic.schedule(7, spec, 50272, 120.0)
    assert all(r["greedy"] == (r["i"] % every == 0) for r in reqs)
    # greedy through the sampler's own path: the host pays the same for either kind
    assert all((r["temperature"], r["top_p"]) == ((1e-4 if r["greedy"] else 0.8), 0.95)
               for r in reqs)
    # rows are kept for every ``capture_every``-th arrival, from a place the seed draws
    n, kept = spec["correct"]["capture_every"], [r["capture"] for r in reqs]
    assert kept in [[(k + c) % n == 0 for k in range(len(kept))] for c in range(n)]


# --- the window's accounting ---------------------------------------------------


class _Timed:
    """What the accounting reads of a ``Request``: when each token was drawn."""

    def __init__(self, stamps):
        self.token_times = list(stamps)


def _record(i, due, stamps, **kw):
    return dict({"i": i, "due": due, "req": _Timed(stamps), "error": None}, **kw)


def test_window_accounting_counts_nothing_outside_the_window():
    records = [
        _record(0, 5.0, [6.0, 9.9, 10.0, 10.5, 19.99, 20.0, 21.0]),   # due before the window
        _record(1, 12.0, [12.5, 13.0, 25.0]),                            # due inside it
        _record(2, 19.0, []),                                            # due inside, no token yet
        _record(3, 20.0, [20.5]),                                        # due at its close: outside
    ]
    num = serve.window_numbers(records, 10.0, 20.0)
    assert num["tokens"] == 3 + 2
    assert [r["i"] for r in num["due_in"]] == [1, 2]
    assert num["ttft_s"] == [0.5]
    assert sorted(round(g, 6) for g in num["itl_s"]) == sorted([0.1, 0.5, 9.49, 0.5])
    # a request the engine refused has no ``Request``: it is due, and has no token
    refused = dict(_record(4, 11.0, []), req=None, error="ValueError")
    assert serve.window_numbers([refused], 10.0, 20.0) == {
        "tokens": 0, "ttft_s": [], "itl_s": [], "due_in": [refused]}


# --- the rows of the engine's tap ---------------------------------------------------


def test_room_for_rows_is_handed_out_once_in_the_order_asked():
    store = serve.RowStore(6, 4)
    a, b = store.take(2), store.take(3)
    assert a.shape == (2, 4) and b.shape == (3, 4) and a.dtype == np.float32 and a.flags.writeable
    a[:] = 1.0
    b[:] = 2.0  # views of the one array made during set-up, side by side
    assert store.buf[:5].tolist() == [[1.0] * 4] * 2 + [[2.0] * 4] * 3 and store.used == 5
    # a request that finds no room for all its rows is timed only
    assert store.take(2) is None and store.used == 5 and store.take(1).shape == (1, 4)


def test_greedy_tokens_are_held_to_the_rows_the_tap_kept():
    rows = np.array([[0, 2, 2, 1], [0, 2, 2, 1], [0, 2, 2, 1], [9, 9, 9, 9]], np.float32)
    assert serve.not_best(rows, [1, 2, 3]) == 1  # ties share the best; 3 is not it
    assert serve.not_best(rows, [1, 2]) == 0 and serve.not_best(rows, []) == 0


# --- the list that stamped, kept for ``tests/test_serving_obs.py`` -------------------


def test_stamped_tokens_note_the_time_of_every_append():
    toks = serve.StampedTokens()
    t0 = time.time()
    for k in range(3):
        toks.append(k)
    assert list(toks) == [0, 1, 2] and [1, 2] + toks == [1, 2, 0, 1, 2]
    assert len(toks.stamps) == 3 and t0 <= toks.stamps[0] <= toks.stamps[-1] <= time.time()


class _Req:
    def __init__(self, generated=()):
        self.slot, self.max_new_tokens, self.generated = 1, 4, list(generated)


class _Eng:
    def __init__(self):
        self._last_logits = np.array([[0, 0, 0, 0], [0, 2, 2, 1]], np.float32)


def test_rows_go_into_the_room_reserved_for_them():
    eng, req, store = _Eng(), _Req(), serve.RowStore(6, 4)
    toks = serve.stamp(eng, req, store=store)
    for tok in (1, 3):
        req.generated.append(tok)
        eng._last_logits[1, 0] += 1  # the engine's buffer moves on; the kept row is a copy
    assert toks.lines == [0, 1] and store.used == 4
    assert store.buf[0].tolist() == [0, 2, 2, 1] and store.buf[1].tolist() == [1, 2, 2, 1]
    # a second request finds room for 2 of its 4 tokens: it is stamped only
    other = _Req()
    late = serve.stamp(eng, other, store=store)
    other.generated.append(2)
    assert late.lines == [None] and len(late.stamps) == 1 and store.used == 4


def test_greedy_tokens_are_held_to_the_row_they_were_drawn_from():
    eng, req = _Eng(), _Req()
    toks = serve.stamp(eng, req, greedy=True)
    for tok in (1, 2, 3):  # ties share the best; 3 is not it
        req.generated.append(tok)
    assert (toks.checked, toks.not_best) == (3, 1) and toks.lines == [None] * 3
    plain = serve.stamp(eng, _Req())
    plain.append(0)
    assert (plain.checked, plain.not_best) == (0, 0)


@pytest.mark.parametrize("kept", [False, True])
def test_a_token_appended_before_the_swap_is_neither_lost_nor_stamped_twice(kept):
    """The engine's loop got to a request before the generator handed it the
    stamping list: what it had appended is carried over once, in order, and
    what it appends afterwards lands in the same list, in the line after."""
    eng, req = _Eng(), _Req([7, 8])
    store = serve.RowStore(8, 4) if kept else None
    toks = serve.stamp(eng, req, store=store)
    assert req.generated is toks and list(toks) == [7, 8] and len(toks.stamps) == 2
    req.generated.append(9)
    assert list(toks) == [7, 8, 9] and len(toks.stamps) == 3
    assert toks.lines == ([None, None, 2] if kept else [None] * 3)
    assert [1] + toks == [1, 7, 8, 9]


# --- weights from the seed --------------------------------------------------------


def test_weights_are_a_function_of_the_seed_in_the_programs_tree():
    import jax

    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args
    from galvatron_tpu.models import modeling

    cfg = model_config_from_args(initialize_galvatron("serve", TINY_CONFIG["program_flags"]))
    a, b, c = (serve.make_weights(cfg, s) for s in (2**32 + 3, 2**32 + 3, 4))
    want = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    assert jax.tree.structure(a) == jax.tree.structure(want)
    assert all(x.shape == w.shape and x.dtype == w.dtype
               for x, w in zip(jax.tree.leaves(a), jax.tree.leaves(want)))
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(a["embed"]["tok"], c["embed"]["tok"])
    # norm scales sit around one, biases are not zero
    assert abs(float(a["final_norm"]["scale"].mean()) - 1) < 0.02
    assert float(abs(a["layers"][0]["attn"]["wo_b"]).max()) > 0


# --- whole runs ----------------------------------------------------------------------


def test_dispatch_by_the_traffic_files_kind(tiny_root, monkeypatch):
    seen = []
    monkeypatch.setattr(serve, "run_serve_cell", lambda root, name, **kw: seen.append(name))
    monkeypatch.setattr(harness, "run_cell", lambda root, name, **kw: seen.append("train:" + name))
    harness.run(tiny_root, "tiny_peak")
    harness.run(tiny_root, "baichuan-7b_s512")
    assert seen == ["tiny_peak", "train:baichuan-7b_s512"]


@pytest.mark.parametrize("cell,e2e", [
    ("tiny_peak", {"serve_tokens_per_s_per_chip", "setup_s"}),
    ("tiny_steady", {"serve_tokens_per_s_per_chip", "ttft_p95_ms", "setup_s"}),
])
def test_whole_serve_run_tiny(tiny_root, tmp_path, cell, e2e):
    """Weights, engine, warm-up, open loop, window, drain, reference: both
    output forms, each cell with the metrics BENCHMARK.json lists it under."""
    end = _run(tiny_root, cell, tmp_path)
    assert end["correct"] is True and end["failed"] == 0 and end["attempted"] > 0
    want = {m["name"] for m in harness.load_manifest(tiny_root)["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(end["metrics"]) == want == e2e
    assert all(m["value"] > 0 for m in end["metrics"].values())
    assert set(end["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    cmp = end["compared"]
    assert cmp["rows"] > 0 and 0 < cmp["logits_kl"] <= TINY_KL_MAX
    assert cmp["greedy_served"] > 0 and cmp["greedy_not_best"] == 0
    assert cmp["sampled_tokens"] > 0 and cmp["sampled_outside_nucleus"] == 0
    assert abs(cmp["sampled_logprob_z"]) <= serve.LOGPROB_Z_MAX
    assert all(cmp["checks"].values()) and list(end)[-1] == "compared"
    assert set(cmp["limits"]) == {"logits_kl", "greedy_not_best", "sampled_outside_nucleus",
                                  "sampled_logprob_z"}
    json.dumps(end)

    traced = _run(tiny_root, cell, tmp_path, seed=105, trace=True)
    assert traced["correct"] is True and list(traced)[-1] == "compared"
    got = set(traced["metrics"])
    assert not got & {"setup_s", "serve_tokens_per_s_per_chip", "ttft_p95_ms",
                      "tokens_per_s_per_chip"}
    # the serving readers, under one name each whatever the cell, and the set-up
    # readers every cell answers (from the runner's marks and the ``jax_*`` spans)
    assert {"decode_step_ms_p50", "host_sample_ms_p50", "prefill_chunk_ms_p50",
            "engine_iteration_ms_p50", "slot_occupancy_share", "itl_p50_ms", "itl_p95_ms",
            "compile_s", "runtime_build_s"} <= got
    # no training reader answers in a serving cell
    assert not got & {"step_ms_p50", "mfu", "data_wait_share", "device_idle_share",
                      "hbm_peak_gib", "compiles_in_window"}
    assert 0 < traced["metrics"]["slot_occupancy_share"]["value"] <= 100
    assert 0 < traced["metrics"]["compile_s"]["value"] < traced["metrics"]["compile_s"]["value"] \
        + traced["metrics"]["runtime_build_s"]["value"]
    assert harness.xplane.find_trace(str(tmp_path / f"{cell}_105_1" / "profile"))


@pytest.mark.parametrize("seed", [104, 106, 107])
def test_the_control_is_not_correct(tiny_root, tmp_path, seed):
    """The control: the engine's own ``--serve_quant int8`` switched on, the
    rest of the run as it is.  Its logits rows lie farther from the float32
    reference's than the limit that the bfloat16 engine stays under; its
    tokens, lengths and slots are sound."""
    res = _run(tiny_root, "tiny_peak", tmp_path, seed=seed, seconds=1.5, overrides=INT8)
    cmp = res["compared"]
    assert res["correct"] is False and res["failed"] == 0
    assert cmp["logits_kl"] > TINY_KL_MAX and cmp["greedy_not_best"] == 0


def test_a_cache_offset_off_by_one_is_not_correct(tiny_root, tmp_path, monkeypatch):
    """The timed path broken underneath: every decode step writes and reads
    its slot one position late, the rest of the run as it is."""
    from galvatron_tpu.serving import engine as engine_mod

    real = engine_mod._decode_step
    monkeypatch.setattr(engine_mod, "_decode_step",
                        lambda params, cfg, cache, tokens, offsets:
                        real(params, cfg, cache, tokens, offsets + 1))
    res = _run(tiny_root, "tiny_peak", tmp_path, seed=104)
    assert res["correct"] is False and res["compared"]["logits_kl"] > 100 * TINY_KL_MAX


def _only_failed(res):
    """The checks of ``correct`` that came out false."""
    return sorted(k for k, ok in res["compared"]["checks"].items() if not ok)


def test_an_altered_token_is_not_correct(tiny_root, tmp_path):
    """A token altered where it is produced, through the engine's public taps
    (``benchmark/lib/faults.py``: ``submit_request`` -> ``Request.generated``,
    ``capture_logits``): the last token of every kept greedy request is handed
    over as the runner-up of the row the tap has just written.  The last token
    feeds no later step, so lengths, slots, the engine and every logits row stay
    sound; one greedy token a kept request is not the best of its row, and no
    other check fails."""
    with faults.last_token(faults.runner_up_if_greedy):
        res = _run(tiny_root, "tiny_peak", tmp_path, seed=105)
    assert res["correct"] is False and res["failed"] == 0
    cmp = res["compared"]
    assert cmp["greedy_not_best"] == cmp["greedy_requests"] > 0
    assert cmp["greedy_served"] > cmp["greedy_requests"]
    assert cmp["logits_kl"] <= TINY_KL_MAX
    assert _only_failed(res) == ["greedy_tokens"]


def test_a_token_outside_the_nucleus_is_not_correct(tiny_root, tmp_path):
    """The same seam, a sampled request: its last token is handed over as the id
    with the smallest logit of its row, far outside the nucleus it was to be
    drawn from.  ``sampled_outside_nucleus`` counts one a kept sampled request
    and no other check fails (a token outside the support is not in z)."""
    with faults.last_token(faults.outside_nucleus_if_sampled):
        res = _run(tiny_root, "tiny_peak", tmp_path, seed=105)
    assert res["correct"] is False and res["failed"] == 0
    cmp = res["compared"]
    assert cmp["sampled_outside_nucleus"] == cmp["sampled_requests"] > 0
    assert abs(cmp["sampled_logprob_z"]) <= serve.LOGPROB_Z_MAX
    assert cmp["greedy_not_best"] == 0 and cmp["logits_kl"] <= TINY_KL_MAX
    assert _only_failed(res) == ["sampled_tokens"]


def test_a_sampler_that_ignores_top_p_is_not_correct(tiny_root, tmp_path):
    """The engine draws every sampled request without its nucleus (the request
    states 0.95, the engine is handed 0): some of its tokens land past the cut,
    and nothing else of the run is touched."""
    with faults.submitting(faults.no_nucleus):
        res = _run(tiny_root, "tiny_peak", tmp_path, seed=108, seconds=1.5)
    assert res["correct"] is False and res["failed"] == 0
    assert res["compared"]["sampled_outside_nucleus"] > 0
    assert _only_failed(res) == ["sampled_tokens"]


def test_the_seam_restores_the_engine(tiny_root):
    from galvatron_tpu.serving import Engine

    real = Engine.submit_request
    with faults.submitting(faults.hot):
        assert Engine.submit_request is not real
    assert Engine.submit_request is real
    assert faults.hot({"temperature": 0.8, "top_p": 0.95}) == {"temperature": 1.0, "top_p": 0.95}
    assert faults.hot({"temperature": 1e-4}) == {"temperature": 1e-4}  # a greedy request stays
    assert faults.no_nucleus({"temperature": 0.8, "top_p": 0.95})["top_p"] == 0.0


# --- the sampled tokens' check at the function -----------------------------------------
# rows and tokens made here, at the serving cell's vocabulary and logit variance
# (``opt-1.3b``: 50,272 ids, ``initial_logit_variance`` 0.8192, bfloat16 values in
# float32 as the engine's tap keeps them); the request states 0.8 / 0.95


def _cell_rows(rng, n):
    import ml_dtypes

    _, config, spec = harness.load_cell(REPO, "opt-1.3b_serve_above_knee")
    sd = float(config["initial_logit_variance"]) ** 0.5
    assert (spec["sampling"]["temperature"], spec["sampling"]["top_p"]) == (0.8, 0.95)
    for _ in range(n):
        yield (sd * rng.standard_normal(int(config["vocab_size"]))
               ).astype(ml_dtypes.bfloat16).astype(np.float32)


def _drawn(rng, n, temperature=0.8, top_p=0.95):
    """(row, token, 0.8, 0, 0.95): the token drawn by numpy from the plain
    statement of the distribution at ``temperature`` / ``top_p``; what the
    request STATED is 0.8 / 0.95 whatever it was drawn at."""
    for row in _cell_rows(rng, n):
        p = reference.processed_distribution(row, temperature, 0, top_p)
        yield row, int(rng.choice(len(p), p=p)), 0.8, 0, 0.95


@pytest.mark.parametrize("seed", range(20))
def test_tokens_drawn_as_stated_pass_the_sampled_check(seed):
    got = reference.sampled_tokens_check(_drawn(np.random.default_rng(seed), 60),
                                         serve.NUCLEUS_SLACK)
    assert got["tokens"] == got["inside"] == 60 and got["outside"] == got["past_cut"] == 0
    assert abs(got["z"]) < serve.LOGPROB_Z_MAX


def test_tokens_drawn_at_the_wrong_temperature_fail_by_z():
    """Temperature 1.0 where the request says 0.8: every term shifts by ~0.26
    against a deviation of ~1.0, so 1,500 tokens (what a 51 s window keeps) read
    |z| ~ 9; the hotter draws also land past the cut now and then."""
    got = reference.sampled_tokens_check(
        _drawn(np.random.default_rng(42), 1500, temperature=1.0), serve.NUCLEUS_SLACK)
    assert abs(got["z"]) > serve.LOGPROB_Z_MAX
    assert 0.2 < (got["mean"] - got["logp"]) / got["inside"] < 0.32


def test_tokens_drawn_without_the_nucleus_fall_outside_it():
    """No nucleus at all: a token lands outside with probability ~4.9%."""
    got = reference.sampled_tokens_check(
        _drawn(np.random.default_rng(43), 300, top_p=0.0), serve.NUCLEUS_SLACK)
    assert 4 <= got["outside"] <= 40 and got["tokens"] == 300


def test_a_narrower_nucleus_is_not_caught():
    """What the check cannot see (PERF.md section 2): top_p 0.90 for 0.95 keeps
    every token inside and shifts z by ~4 over 1,500 tokens, under the limit;
    600 tokens here read well under it."""
    got = reference.sampled_tokens_check(
        _drawn(np.random.default_rng(44), 600, top_p=0.90), serve.NUCLEUS_SLACK)
    assert got["outside"] == 0 and abs(got["z"]) < serve.LOGPROB_Z_MAX


@pytest.mark.parametrize("temperature,top_k,top_p", [
    (0.8, 0, 0.95), (0.8, 50, 0.9), (1.3, 1000, 0.0), (0.8, 0, 0.0), (0.5, 7, 0.5)])
def test_token_stats_are_the_plain_distributions(temperature, top_k, top_p):
    """``token_stats`` (over a row's distinct values) against
    ``processed_distribution`` (the plain statement): ln p of a token inside the
    support, -inf outside it, the mass of strictly larger logits, the mean and
    the variance of ln p."""
    rng = np.random.default_rng(7)
    for row in _cell_rows(rng, 3):
        p = reference.processed_distribution(row, temperature, top_k, top_p)
        assert p.sum() == pytest.approx(1.0, abs=1e-12)
        sup = p > 0
        logp = np.log(p[sup])
        mean = float((p[sup] * logp).sum())
        var = float((p[sup] * logp * logp).sum() - mean * mean)
        full = reference.processed_distribution(row, temperature, top_k, 0.0)  # before the nucleus
        for tok in (int(rng.choice(len(p), p=p)), int(np.argmax(row)), int(np.argmin(row))):
            above, got, m, v = reference.token_stats(row, tok, temperature, top_k, top_p)
            assert (m, v) == (pytest.approx(mean, abs=1e-9), pytest.approx(var, abs=1e-9))
            if sup[tok]:
                assert got == pytest.approx(float(np.log(p[tok])), abs=1e-9)
                assert above == pytest.approx(float(full[row > row[tok]].sum()), abs=1e-9)
                assert top_p == 0 or above < top_p
            else:
                assert got == float("-inf") and (above == float("inf") or above >= top_p)


def test_the_nucleus_keeps_ties_at_the_cut_and_greedy_shares_the_best():
    row = np.log(np.array([0.4, 0.3, 0.1, 0.1, 0.1]))
    # the prefix {0.4, 0.3} reaches 0.7 exactly at its second token
    assert (reference.processed_distribution(row, 1.0, 0, 0.7) > 0).tolist() == [1, 1, 0, 0, 0]
    # 0.75 needs one of the three tied tokens: all three are kept
    assert (reference.processed_distribution(row, 1.0, 0, 0.75) > 0).tolist() == [1] * 5
    assert reference.processed_distribution(row, 1.0, 2, 0.0).tolist() == pytest.approx(
        [4 / 7, 3 / 7, 0, 0, 0])
    assert reference.processed_distribution([1.0, 3.0, 3.0], 0.0).tolist() == [0, 0.5, 0.5]
    above, logp, _, _ = reference.token_stats(row, 3, 1.0, 0, 0.75)
    assert above == pytest.approx(0.7) and logp == pytest.approx(np.log(0.1))
    assert reference.token_stats(row, 3, 1.0, 0, 0.7)[1] == float("-inf")
    with pytest.raises(ValueError):
        reference.token_stats(row, 0, 0.0)


def test_a_request_that_expires_counts_as_failed(tiny_root, tmp_path, monkeypatch):
    """A TTL the traffic cannot meet: expired requests are failed ones and
    the run is not correct."""
    spec = dict(STEADY_TRAFFIC, serve_flags=["--num_slots", "1", "--prefill_chunk", "16",
                                             "--request_ttl_s", "0.05"])
    path = os.path.join(tiny_root, "benchmark/traffic/tiny_steady.json")
    with open(path, "w") as f:
        json.dump(spec, f)
    try:
        res = _run(tiny_root, "tiny_steady", tmp_path, seed=106)
    finally:
        with open(path, "w") as f:
            json.dump(STEADY_TRAFFIC, f)
    assert res["failed"] > 0 and res["correct"] is False


# --- the readers ---------------------------------------------------------------------


def test_serving_readers_leave_a_training_context_alone():
    """A training cell's context has no ``serve``: every serving reader
    returns None, so its metric is left out (never a 0)."""
    ctx = {"spans": [{"name": "sample", "start": 0.0, "end": 1.0, "step": 3, "args": {}}],
           "setup_spans": [], "trace": None, "memory_peak_bytes": 1 << 30, "say": print,
           "traffic": {"seq_len": 8}}
    mods = [m for m in harness.discover_metrics(REPO) if m.MOVES == "serve_tokens_per_s_per_chip"]
    assert len(mods) >= 22  # 7 of PR 35, 7 of PR 39, 8 of PR 42, and what later PRs add
    assert all(m.compute(ctx) is None for m in mods)
