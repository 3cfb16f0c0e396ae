"""The serving engine's draw on the device (PR 46): ``generation.kept_rows`` /
``draw_rows`` against the float64 statement ``generation.host_probs``, the
engine's one sampler program (``_sample_rows``) over every mix of requests, a
request's tokens as a function of (seed, rid, token index) and its rows alone,
the tap on the rows the device drew from, what a fresh engine compiles, and
``KVSlots.reset``. CPU, toy models; no number here is a device number."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy import stats as sp_stats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from galvatron_tpu.models import generation, modeling  # noqa: E402
from galvatron_tpu.models.modeling import ModelConfig  # noqa: E402
from galvatron_tpu.obs.tracing import tracer  # noqa: E402
from galvatron_tpu.serving import Engine  # noqa: E402
from galvatron_tpu.serving import engine as engine_mod  # noqa: E402
from galvatron_tpu.serving.kv_slots import SlotKVCache  # noqa: E402

CELL_VOCAB = 50272  # opt-1.3b's, the serving cell's width
CFG = ModelConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4, ffn_dim=128,
                  max_seq_len=64)
BACKENDS = {"slot": {}, "paged": {"kv_num_blocks": -1, "kv_block_size": 8}}

_kept = jax.jit(generation.kept_rows)


def _rows(vocab: int) -> np.ndarray:
    """Four seeded rows a width: near-flat bfloat16 logits (the cell's kind:
    ~2,400 distinct values, so ties everywhere), a coarse grid (exact ties at
    every threshold), one dominant token, and a smooth ramp."""
    rng = np.random.default_rng(vocab)
    flat = np.asarray(jnp.asarray(0.8 * rng.standard_normal(vocab), jnp.bfloat16), np.float32)
    grid = (rng.integers(0, 7, vocab) / 2.0).astype(np.float32)
    peak = flat.copy()
    peak[vocab // 3] = 40.0
    ramp = np.linspace(-4.0, 4.0, vocab, dtype=np.float32)[rng.permutation(vocab)]
    return np.stack([flat, grid, peak, ramp])


def _mass_above(row, temperature, top_k):
    """float64: for each token the mass of the strictly larger ones, after the
    top-k cut (what the nucleus compares with top_p)."""
    p = generation.host_probs(row, temperature, top_k, 0.0)
    order = np.argsort(-p, kind="stable")
    sorted_p = p[order]
    cum = np.cumsum(sorted_p) - sorted_p
    first = np.searchsorted(-sorted_p, -sorted_p, side="left")  # a tie group's first place
    above = np.empty_like(p)
    above[order] = cum[first]
    return above


def _params(n, temperature=0.0, top_k=0, top_p=0.0):
    return (np.full(n, temperature, np.float32), np.full(n, top_k, np.int32),
            np.full(n, top_p, np.float32))


# --- the distribution: the kept set is host_probs's ---------------------------------------


@pytest.mark.parametrize("top_k", [0, 1, 50])
@pytest.mark.parametrize("top_p", [0.0, 0.5, 0.95, 1.0])
@pytest.mark.parametrize("temperature", [0.8, 1.0, 1e-4, 0.0])
@pytest.mark.parametrize("vocab", [CELL_VOCAB, 97])
def test_kept_set_is_the_float64_references(vocab, temperature, top_p, top_k):
    rows = _rows(vocab)
    if temperature <= 0:
        # greedy: no support to compare; the draw is the row's best token
        ids = engine_mod._sample_rows(rows, *_tables(len(rows), temperature, top_k, top_p))
        assert list(np.asarray(ids)) == list(rows.argmax(-1))
        return
    _, keep = _kept(rows, *_params(len(rows), temperature, top_k, top_p))
    keep = np.asarray(keep)
    for row, got in zip(rows, keep):
        want = generation.host_probs(row, temperature, top_k, top_p) > 0
        # (a token whose probability underflows float64 has none to compare)
        alive = generation.host_probs(row, temperature, 0, 0.0) > 0
        differ = np.flatnonzero((want != got) & alive)
        if differ.size:
            # float32 sums may put a tie group whose mass above lies within
            # rounding of top_p on the other side of the cut, and nothing else
            # (at top_p 1.0 the float64 statement itself rounds a dominant
            # token's tail away; the device keeps it)
            assert top_p > 0
            assert np.abs(_mass_above(row, temperature, top_k)[differ] - top_p).max() < 2e-6
        assert got.any()


def _tables(n, temperature=0.0, top_k=0, top_p=0.0, *, active=None, rid=None, index=None,
            seed=0, ids=None):
    """``_sample_rows``'s two operand tables for ``n`` slots, and the ids a slot that
    is not active keeps (0 unless given)."""
    knobs = np.zeros((2, n), np.float32)
    ints = np.zeros((6, n), np.uint32)
    knobs[engine_mod._TEMPERATURE], knobs[engine_mod._TOP_P] = temperature, top_p
    ints[engine_mod._ACTIVE] = 1 if active is None else active
    ints[engine_mod._TOP_K] = top_k
    ints[engine_mod._RID] = np.arange(n) if rid is None else rid
    ints[engine_mod._INDEX] = 0 if index is None else index
    ints[engine_mod._SEED_LO], ints[engine_mod._SEED_HI] = seed & 0xFFFFFFFF, seed >> 32
    return knobs, ints, np.zeros(n, np.int32) if ids is None else np.asarray(ids, np.int32)


@pytest.mark.parametrize("temperature", [0.0, -1.0])
def test_greedy_is_argmax_ties_to_the_first(temperature):
    rows = _rows(97)
    rows[1, [5, 60]] = rows[1].max() + 1.0  # two best tokens: the first wins, as np.argmax
    ids = engine_mod._sample_rows(rows, *_tables(4, temperature, top_k=3, top_p=0.5))
    assert list(np.asarray(ids)) == list(rows.argmax(-1)) and int(ids[1]) == 5


@pytest.mark.parametrize("temperature,top_k,top_p", [
    (0.8, 0, 0.95), (1.0, 0, 0.0), (0.8, 12, 0.0), (1.3, 20, 0.7), (1e-4, 0, 0.95)])
def test_draws_follow_host_probs_by_chi_square(temperature, top_k, top_p):
    """20,480 draws of ONE row (2,048 slots x 10 token indices): no token outside
    the support, and the frequencies pass a chi-square against ``host_probs``."""
    vocab, slots = 64, 2048
    rng = np.random.default_rng(7)
    row = (1.5 * rng.standard_normal(vocab)).astype(np.float32)
    row[[3, 9]] = row.max() + 0.25  # two tied best tokens share at temperature 1e-4
    rows = np.tile(row, (slots, 1))
    counts = np.zeros(vocab, np.int64)
    for index in range(10):
        ids = engine_mod._sample_rows(rows, *_tables(slots, temperature, top_k, top_p,
                                                     index=index, seed=2**31 + 11))
        counts += np.bincount(np.asarray(ids), minlength=vocab)
    p = generation.host_probs(row, temperature, top_k, top_p)
    assert counts[p == 0].sum() == 0
    n = counts.sum()
    assert n == 20480
    big = p * n >= 5
    observed = np.append(counts[big], counts[~big].sum())
    expected = np.append(p[big] * n, p[~big].sum() * n)
    observed, expected = observed[expected > 0], expected[expected > 0]
    if len(expected) > 1:
        assert sp_stats.chisquare(observed, expected).pvalue > 1e-3
    else:
        assert observed[0] == n


def test_each_row_of_a_mixed_batch_gets_its_own_result():
    """Greedy, nucleus, top-k and inactive rows in one call: every active row
    draws what it draws with all its neighbours switched off; an inactive one 0."""
    rows = np.tile(_rows(97), (2, 1))  # 8 slots
    temperature = np.array([0.0, 0.8, 0.8, 1.0, 1e-4, 0.0, 0.9, 0.8], np.float32)
    top_k = np.array([0, 0, 5, 0, 0, 7, 3, 0], np.uint32)
    top_p = np.array([0.0, 0.95, 0.0, 0.5, 0.95, 0.3, 0.9, 0.0], np.float32)
    active = np.array([1, 1, 1, 0, 1, 1, 1, 0], np.uint32)
    rid, index = np.arange(40, 48), np.arange(8)
    tables = lambda act: _tables(8, temperature, top_k, top_p, active=act, rid=rid,  # noqa: E731
                                 index=index, seed=5)
    together = np.asarray(engine_mod._sample_rows(rows, *tables(active)))
    assert together[3] == together[7] == 0
    assert together[0] == rows[0].argmax() and together[5] == rows[5].argmax()
    for slot in np.flatnonzero(active):
        alone = np.asarray(engine_mod._sample_rows(rows, *tables(np.arange(8) == slot)))
        assert alone[slot] == together[slot]
        assert not np.delete(alone, slot).any()
        p = generation.host_probs(rows[slot], temperature[slot], int(top_k[slot]), top_p[slot])
        assert p[together[slot]] > 0
    # the stream is the (seed, rid, index)'s: another index, other draws somewhere
    later = np.asarray(engine_mod._sample_rows(rows, *_tables(
        8, temperature, top_k, top_p, active=active, rid=rid, index=index + 1, seed=5)))
    assert (later != together).any()
    # a slot that is not active keeps the id it is handed (the last draws, which the
    # engine leaves on the device): a draw for one admitted slot merges into them
    held = np.arange(100, 108)
    merged = np.asarray(engine_mod._sample_rows(rows, *_tables(
        8, temperature, top_k, top_p, active=np.arange(8) == 2, rid=rid, index=index, seed=5,
        ids=held)))
    assert merged[2] == together[2] and list(np.delete(merged, 2)) == list(np.delete(held, 2))


def test_the_sampler_is_one_program_whatever_the_mix():
    from galvatron_tpu.analysis.guards import cache_sizes

    rows = _rows(97)
    engine_mod._sample_rows(rows, *_tables(4))
    before = cache_sizes((engine_mod._sample_rows,))
    for t, k, p in [(0.8, 0, 0.95), (1.0, 4, 0.0), (0.0, 0, 0.0), (1e-4, 2, 0.5)]:
        engine_mod._sample_rows(rows, *_tables(4, t, k, p, active=[1, 0, 1, 1], seed=2**40))
    assert cache_sizes((engine_mod._sample_rows,)) == before


# --- the engine: a request's tokens are its own ---------------------------------------------


@pytest.fixture(scope="module")
def params():
    return modeling.init_model_params(jax.random.key(0), CFG)


def _engine(params, backend="slot", **kw):
    kw.setdefault("num_slots", 16)
    return Engine(params, CFG, prefill_chunk=8, start_loop=False, seed=2**31 + 7,
                  **BACKENDS[backend], **kw)


def _drive(eng, reqs, limit=400):
    for _ in range(limit):
        if all(r.future.done() for r in reqs):
            return
        eng.step_once()
    raise AssertionError("the engine did not finish its requests")


def _probe(eng, rid=10**6 + 3, **kw):
    """The request under test, with a request id of the test's choosing."""
    req = eng.submit_request([5, 9, 2, 7, 1, 3, 8, 4, 6, 11], 12, temperature=0.8, top_p=0.95,
                             **kw)
    req.rid = rid  # before any iteration has seen it
    return req


def _admitted(eng, req):
    """One iteration: everything queued takes a slot; the slot ``req`` took."""
    eng.step_once()
    assert req.slot is not None
    return req.slot


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_a_requests_tokens_depend_on_seed_rid_and_rows_alone(params, backend):
    """Served alone, beside 15 others of every kind, and in another slot: the
    same tokens."""
    eng = _engine(params, backend)
    try:
        alone = _probe(eng)
        slots = [_admitted(eng, alone)]
        _drive(eng, [alone])
        rng = np.random.default_rng(3)
        others = [eng.submit_request(list(rng.integers(1, 128, rng.integers(2, 14))),
                                     int(rng.integers(3, 16)), temperature=float(t),
                                     top_k=int(k), top_p=float(p))
                  for t, k, p in zip(rng.choice([0.0, 0.8, 1.0, 1e-4], 15),
                                     rng.choice([0, 0, 5], 15), rng.choice([0.0, 0.95, 0.5], 15))]
        crowded = _probe(eng)
        slots.append(_admitted(eng, crowded))
        assert eng.slots.active_count == 16
        _drive(eng, others + [crowded])
        # three requests ahead of it: the probe takes the fourth slot handed out
        ahead = [eng.submit_request([1, 2, 3], 2) for _ in range(3)]
        moved = _probe(eng)
        slots.append(_admitted(eng, moved))
        _drive(eng, ahead + [moved])
    finally:
        eng.close()
    assert alone.generated == crowded.generated == moved.generated
    assert len(alone.generated) == 12 and len(set(slots)) == 3, slots


def test_another_seed_or_rid_draws_another_stream(params):
    outs = []
    for seed, rid in [(1, 50), (2, 50), (1, 51)]:
        eng = Engine(params, CFG, num_slots=2, prefill_chunk=8, start_loop=False, seed=seed)
        try:
            req = _probe(eng, rid=rid)
            _drive(eng, [req])
        finally:
            eng.close()
        outs.append(req.generated)
    assert outs[0] != outs[1] and outs[0] != outs[2]


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_a_tapped_row_is_the_row_its_token_was_drawn_from(params, backend):
    """A greedy token is its tapped row's argmax, a sampled one lies inside its
    tapped row's support, and the rows are the model's own: the plain forward
    over prompt + answer gives them."""
    eng = _engine(params, backend, num_slots=4)
    asks = [dict(), dict(temperature=0.8, top_p=0.6), dict(temperature=1.0, top_k=4),
            dict(temperature=1e-4, top_p=0.95), dict(temperature=0.9, top_k=6, top_p=0.8)]
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [9, 8, 7, 6, 5, 4, 3, 2, 1, 12, 13], [4, 4], [11, 3, 5]]
    try:
        bufs = [np.full((9, CFG.vocab_size), np.nan, np.float32) for _ in asks]
        reqs = [eng.submit_request(p, 9, capture_logits=b, **a)
                for p, a, b in zip(prompts, asks, bufs)]
        untapped = eng.submit_request([3, 1, 4, 1, 5], 6, temperature=0.8)
        _drive(eng, reqs + [untapped])
    finally:
        eng.close()
    fwd = jax.jit(lambda toks: modeling.forward(params, toks, CFG))
    for req, ask, buf, prompt in zip(reqs, asks, bufs, prompts):
        assert req.logits_rows == len(req.generated) == 9
        seq = np.asarray(prompt + req.generated, np.int32)
        model_rows = np.asarray(fwd(seq[None]))[0, len(prompt) - 1:-1]
        np.testing.assert_allclose(buf, model_rows, rtol=2e-4, atol=2e-4)
        for row, tok in zip(buf, req.generated):
            if not ask:
                assert tok == row.argmax()
            else:
                p = generation.host_probs(row, ask["temperature"], ask.get("top_k", 0),
                                          ask.get("top_p", 0.0))
                assert p[tok] > 0
    assert len(untapped.generated) == 6 and untapped.logits_rows == 0


# --- set-up: what a fresh engine compiles ---------------------------------------------------


def _compiled(since=0):
    """(span name, fun_name) of the ring's lowerings and compiles from ``since`` on."""
    return [(r["name"], r["args"].get("fun_name")) for r in tracer.snapshot()[since:]
            if r["ph"] == "X" and r["name"] in ("jax_lower", "jax_compile")]


def test_the_first_warm_up_request_compiles_everything_and_nothing_eager():
    """The benchmark's first warm-up request (greedy, a prompt of two chunks, two
    tokens) on a model no other test compiled: at most four programs, none an
    eager primitive's; after it a sampled, a top-k and a tapped request lower
    and compile nothing."""
    cfg = CFG.replace(vocab_size=176)
    params = modeling.init_model_params(jax.random.key(2), cfg)
    eng = Engine(params, cfg, num_slots=4, prefill_chunk=8, start_loop=False, seed=2**31 + 1)
    assert not tracer.enabled
    tracer.clear()  # (another file's traced run may have left its ring behind)
    tracer.enable(capacity=1 << 14)
    try:
        warm = eng.submit_request(list(range(1, 10)), 2)  # prefill_chunk + 1 tokens
        _drive(eng, [warm])
        first = _compiled()
        mark = len(tracer.snapshot())
        later = [eng.submit_request([1, 2, 3], 3, temperature=0.8, top_p=0.95),
                 eng.submit_request([1, 2, 3], 3, temperature=0.8, top_p=0.95),
                 eng.submit_request([4, 5], 4, temperature=1.0, top_k=5),
                 eng.submit_request([6, 7, 8], 3, temperature=0.7, top_p=0.9,
                                    capture_logits=np.zeros((3, 176), np.float32))]
        _drive(eng, later)
        assert eng._last_logits.shape == (4, 176)  # a probe's read of the rows: a copy
        after = _compiled(mark)
        stats = eng.stats()
    finally:
        tracer.disable()
        tracer.clear()
        eng.close()
    names = {fun for _, fun in first}
    assert names == {"jit(_prefill_chunk)", "jit(_decode_step)", "jit(_sample_rows)"}
    assert len(names) <= 4 and sorted(n for n, _ in first) == ["jax_compile"] * 3 + ["jax_lower"] * 3
    assert after == []
    assert stats["draws_device"] == 2 + 3 + 3 + 4 + 3 and stats["draws_host"] == 0


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_the_speculative_engine_still_draws_on_the_host(params, backend):
    eng = _engine(params, backend, num_slots=2, spec_decode_k=2)
    try:
        reqs = [eng.submit_request([1, 2, 3, 1, 2, 3, 1, 2], 8),
                eng.submit_request([7, 8, 9], 6, temperature=0.8, top_p=0.9)]
        _drive(eng, reqs)
        stats = eng.stats()
        assert eng._last_logits is eng._host_rows
    finally:
        eng.close()
    assert stats["draws_host"] > 0 and stats["draws_device"] == 0
    assert [len(r.generated) for r in reqs] == [8, 6]


def test_the_aot_registry_declares_the_sampler_with_the_engines_shapes(params):
    from galvatron_tpu.aot import registry as aot_registry

    ctx = aot_registry.ProgramContext(cfg=CFG, num_slots=16, prefill_chunk=8)
    specs = {s.name: s for s in aot_registry.enumerate_programs(ctx, include=("serving",))}
    rows, knobs, ints, ids = specs["serving_sample"].args
    assert specs["serving_sample"].fn is engine_mod._sample_rows
    assert (rows.shape, knobs.shape, ints.shape, ids.shape) == ((16, 128), (2, 16), (6, 16), (16,))
    assert rows.dtype == jnp.dtype(CFG.dtype) and ints.dtype == jnp.uint32 and ids.dtype == jnp.int32
    assert specs["serving_prefill"].args[6].shape == (16, 128)  # the rows the row lands in
    paged = {s.name for s in aot_registry.enumerate_programs(
        aot_registry.ProgramContext(cfg=CFG, num_slots=16, prefill_chunk=8, kv_num_blocks=-1),
        include=("serving",))}
    assert paged == {"serving_paged_prefill", "serving_paged_decode", "serving_sample"}


# --- KVSlots.reset ----------------------------------------------------------------------------


@pytest.mark.parametrize("dropped_by_the_caller", [False, True])
def test_reset_lets_go_of_the_old_cache_before_it_builds_the_new(monkeypatch,
                                                                   dropped_by_the_caller):
    """Two caches do not fit a chip beside the weights at a deployment's size:
    when ``init_kv_cache`` runs, ``reset`` holds none (and the benchmark's runner
    may have set ``cache = None`` already)."""
    slots = SlotKVCache(CFG, 2, 32)
    slots.alloc()
    held = []
    real = generation.init_kv_cache

    def watched(*a, **k):
        held.append(slots.cache)
        return real(*a, **k)

    monkeypatch.setattr(generation, "init_kv_cache", watched)
    if dropped_by_the_caller:
        slots.cache = None
    slots.reset()
    assert held == [None]
    assert slots.cache is not None and slots.cache.k.shape[1:3] == (2, 32)
    assert slots.active_count == 0 and slots.audit()["ok"]
