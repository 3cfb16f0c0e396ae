"""Production data subsystem tests (galvatron_tpu/data/; DESIGN.md § Data
pipeline): shard format, deterministic mixtures + sample-domain cursor
exactness, sequence packing (bit-exact packed-vs-padded gradient parity and
the cross-document-attention leak test), async prefetch lifecycle, and the
trainer-level preempt→resume per-source contract."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.data import (
    AsyncPrefetcher,
    MixtureDataset,
    PackedDataset,
    build_data_pipeline,
    open_token_dataset,
    pack_documents,
    parse_mixture,
    write_sharded_dataset,
)
from galvatron_tpu.data.packing import WindowedDataset, packed_batch_meta
from galvatron_tpu.models import modeling
from galvatron_tpu.models.modeling import ModelConfig


def make_corpus(tmp_path, name, n_docs, lens=(4, 28), vocab=128, seed=0,
                shard_tokens=512):
    rng = np.random.RandomState(seed)
    docs = [list(rng.randint(1, vocab, rng.randint(*lens))) for _ in range(n_docs)]
    prefix = str(tmp_path / name)
    write_sharded_dataset(prefix, docs, vocab, shard_tokens=shard_tokens)
    return prefix, docs


def tiny_cfg(**kw):
    base = dict(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=16, ffn_dim=64, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    base.update(kw)
    return ModelConfig(**base)


class _PipeCfg:  # the duck type build_data_pipeline reads
    image_size = 0
    objective = "clm"
    enc_layers = 0
    vocab_size = 128


# ---------------------------------------------------------------------------
# Shard format
# ---------------------------------------------------------------------------


def test_sharded_roundtrip_multifile(tmp_path):
    prefix, docs = make_corpus(tmp_path, "c", 120, shard_tokens=256)
    ds = open_token_dataset(prefix)
    assert len(ds.meta["shards"]) > 1, "corpus should span multiple shards"
    assert ds.num_docs == 120
    assert ds.num_tokens == sum(len(d) for d in docs)
    for i in (0, 57, 119):
        np.testing.assert_array_equal(ds.doc(i), docs[i])
    np.testing.assert_array_equal(ds.doc_lengths, [len(d) for d in docs])


def test_sharded_corrupt_shard_rejected(tmp_path):
    prefix, _ = make_corpus(tmp_path, "c", 30)
    sh = json.load(open(prefix + ".shards.json"))["shards"][0]["file"]
    with open(tmp_path / sh, "ab") as f:
        f.write(b"\x00\x00")
    with pytest.raises(ValueError, match="corrupt|records"):
        open_token_dataset(prefix)


def test_legacy_prefix_opens_through_same_entry(tmp_path):
    from galvatron_tpu.core.data import write_indexed_dataset

    docs = [[1, 2, 3], [4, 5], list(range(50, 90))]
    prefix = str(tmp_path / "legacy")
    write_indexed_dataset(prefix, docs, 128)
    ds = open_token_dataset(prefix)
    assert ds.num_docs == 3
    np.testing.assert_array_equal(ds.doc(2), docs[2])
    np.testing.assert_array_equal(ds.doc_lengths, [3, 2, 40])


def test_manifest_commit_is_atomic(tmp_path):
    prefix, _ = make_corpus(tmp_path, "c", 10)
    assert not os.path.exists(prefix + ".shards.json.tmp")


# ---------------------------------------------------------------------------
# Packing
# ---------------------------------------------------------------------------


def test_pack_documents_first_fit_and_long_doc_split():
    rows = pack_documents(np.array([5, 3, 9, 2, 4]), capacity=8)
    placed = sorted(p for row in rows for p in row)
    # 9-token doc splits into an 8 piece + a 1 piece; everything placed once
    assert (2, 0, 8) in placed and (2, 8, 1) in placed
    total = sum(p[2] for row in rows for p in row)
    assert total == 5 + 3 + 9 + 2 + 4
    for row in rows:
        assert sum(p[2] for p in row) <= 8


def test_packed_dataset_rows_and_efficiency(tmp_path):
    prefix, docs = make_corpus(tmp_path, "c", 200)
    pk = PackedDataset(open_token_dataset(prefix), seq_len=64)
    # mixed short docs: waste must sit below the 10% acceptance bar
    assert pk.packing_efficiency >= 0.9
    row = pk.sample(0)
    s1 = 65
    tokens, seg = row[:s1], row[s1:]
    assert row.shape == (2 * s1,) and row.dtype == np.int32
    # segments 1-based, monotone, padding (0) only at the tail
    nz = seg[seg > 0]
    assert nz[0] == 1 and (np.diff(nz) >= 0).all() and (np.diff(nz) <= 1).all()
    pad_start = len(nz)
    assert (seg[pad_start:] == 0).all() and (tokens[pad_start:] == 0).all()
    # row contents are the original documents back to back
    for seg_id in np.unique(nz):
        piece = tokens[seg == seg_id]
        assert any(
            np.array_equal(piece, np.asarray(d[: len(piece)])) for d in docs
        ), f"segment {seg_id} is not a document prefix"


def test_packed_batch_meta_counts_input_positions():
    s1 = 9
    row = np.zeros(2 * s1, np.int32)
    row[s1 : s1 + 5] = 1  # 5 real positions, 4 pad — 5 of the 8 INPUT slots
    m = packed_batch_meta(row[None])
    assert m["raw_tokens"] == 8
    assert m["nonpad_tokens"] == 5
    assert m["packing_efficiency"] == pytest.approx(5 / 8)


# ---------------------------------------------------------------------------
# Mixture determinism + cursor
# ---------------------------------------------------------------------------


def test_parse_mixture_forms(tmp_path):
    inline = parse_mixture("/p/web=0.7,/p/books=0.3")
    assert [s.weight for s in inline] == [0.7, 0.3]
    path = tmp_path / "m.json"
    path.write_text(json.dumps({"sources": [
        {"name": "a", "prefix": "/p/a", "weight": 2},
        {"prefix": "/p/b"},
    ]}))
    parsed = parse_mixture(str(path))
    assert parsed[0].name == "a" and parsed[1].name == "b"
    with pytest.raises(ValueError, match="duplicate"):
        parse_mixture("/p/x=1,/p/x=2")


def _mixture(tmp_path, seed=7, pack=True):
    pa, _ = make_corpus(tmp_path, "a", 150, seed=1)
    pb, _ = make_corpus(tmp_path, "b", 100, seed=2)
    mk = (lambda p: PackedDataset(open_token_dataset(p), 32)) if pack else (
        lambda p: WindowedDataset(open_token_dataset(p), 32))
    return MixtureDataset(["a", "b"], [mk(pa), mk(pb)], [0.75, 0.25], seed=seed)


def test_mixture_ratio_bound_at_every_prefix(tmp_path):
    mix = _mixture(tmp_path)
    for k in (1, 7, 40, 163, 500):
        c = mix.counts_at(k)
        # error-feedback assignment: realized ratio within ±1 sample of the
        # weight at EVERY prefix, not just in expectation
        assert abs(c["a"] - 0.75 * k) <= 1, (k, c)
        assert abs(c["b"] - 0.25 * k) <= 1, (k, c)
        assert c["a"] + c["b"] == k


def test_mixture_position_addressable_and_deterministic(tmp_path):
    m1 = _mixture(tmp_path, seed=7)
    m2 = _mixture(tmp_path, seed=7)
    # random-access equals sequential access equals a fresh instance
    seq = [m1.sample(k).copy() for k in range(60)]
    for k in (59, 3, 31, 0):
        np.testing.assert_array_equal(m2.sample(k), seq[k])
    m3 = _mixture(tmp_path, seed=8)
    assert any(
        not np.array_equal(m3.sample(k), seq[k]) for k in range(20)
    ), "seed must change the interleave"


def test_mixture_epochs_reshuffle_per_source(tmp_path):
    pa, _ = make_corpus(tmp_path, "a", 40, seed=1)
    pk = PackedDataset(open_token_dataset(pa), 32)
    n = pk.num_samples
    mix = MixtureDataset(["a"], [pk], [1.0], seed=3)
    e0 = [mix.sample(k).tobytes() for k in range(n)]
    e1 = [mix.sample(n + k).tobytes() for k in range(n)]
    assert sorted(e0) == sorted(e1), "an epoch must cover the same rows"
    assert e0 != e1, "epoch order must re-shuffle, not replay epoch 0"


def test_cursor_converts_exactly_across_batch_size(tmp_path):
    pa, _ = make_corpus(tmp_path, "a", 150, seed=1)
    pb, _ = make_corpus(tmp_path, "b", 100, seed=2)
    mixture = f"{pa}=0.75,{pb}=0.25"
    p8 = build_data_pipeline(_PipeCfg, 8, 32, seed=7, mixture=mixture, pack=True)
    for _ in range(5):
        next(p8)
    st = p8.state(40)
    # resume the same stream at bsz 4 from the converted cursor (40/4 = 10)
    p4 = build_data_pipeline(
        _PipeCfg, 4, 32, seed=7, mixture=mixture, pack=True,
        start_batch=10, resume_state=st,
    )
    ref = _mixture(tmp_path, seed=7)
    np.testing.assert_array_equal(
        next(p4), np.stack([ref.sample(40 + r) for r in range(4)])
    )
    # a changed mixture is refused with the per-source mismatch spelled out
    with pytest.raises(ValueError, match="per-source consumption mismatch"):
        build_data_pipeline(
            _PipeCfg, 4, 32, seed=7, mixture=f"{pa}=0.25,{pb}=0.75",
            pack=True, start_batch=10, resume_state=st,
        )
    # so is a packed checkpoint resumed unpacked: same cursor, different rows
    with pytest.raises(ValueError, match="pack_sequences"):
        build_data_pipeline(
            _PipeCfg, 4, 32, seed=7, mixture=mixture,
            pack=False, start_batch=10, resume_state=st,
        )


def test_empty_corpus_refused(tmp_path):
    with pytest.raises(ValueError, match="no non-empty documents"):
        write_sharded_dataset(str(tmp_path / "empty"), [[], []], 128)


# ---------------------------------------------------------------------------
# Packed-model contracts (parity + leak)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pos_embed", ["rope", "learned"])
def test_packed_vs_padded_gradient_parity_bitexact(pos_embed):
    """A batch whose documents pack trivially (each row one full-row document)
    must produce BIT-IDENTICAL loss and grads to the unpacked path."""
    cfg = tiny_cfg(pos_embed=pos_embed)
    params = modeling.init_model_params(jax.random.key(0), cfg)
    rng = np.random.RandomState(1)
    toks = rng.randint(0, 128, (4, 17)).astype(np.int32)
    packed = np.concatenate([toks, np.ones((4, 17), np.int32)], axis=1)
    # eager on purpose (a tiny model): op by op the two paths do the same arithmetic in the
    # same order, which two separately compiled programs need not (5.96e-08 apart when jitted)
    l_u, g_u = jax.value_and_grad(modeling.lm_loss)(params, jnp.asarray(toks), cfg)  # eager
    l_p, g_p = jax.value_and_grad(modeling.lm_loss)(  # eager
        params, jnp.asarray(packed), cfg.replace(pack_sequences=True)
    )
    assert float(l_u) == float(l_p)
    for a, b in zip(jax.tree.leaves(g_u), jax.tree.leaves(g_p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_parity_through_hybrid_engine():
    """Engine-level parity on the GSPMD (pp=1) path with tp=2: one train_step
    on the packed batch must match the unpacked step bit-for-bit (loss AND
    every updated parameter)."""
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.parallel.hybrid import build_runtime

    cfg = tiny_cfg()
    rng = np.random.RandomState(2)
    toks = rng.randint(0, 128, (8, 17)).astype(np.int32)
    packed = np.concatenate([toks, np.ones((8, 17), np.int32)], axis=1)
    rt_u = build_runtime(
        cfg, HybridParallelConfig.uniform(2, tp=2, mixed_precision="fp32"),
        global_batch_size=8,
    )
    rt_p = build_runtime(
        cfg.replace(pack_sequences=True),
        HybridParallelConfig.uniform(2, tp=2, mixed_precision="fp32"),
        global_batch_size=8,
    )
    s_u = rt_u.init_state(jax.random.key(0))
    s_p = rt_p.init_state(jax.random.key(0))
    n_u, l_u = rt_u.train_step(s_u, rt_u.shard_batch(toks))
    n_p, l_p = rt_p.train_step(s_p, rt_p.shard_batch(packed))
    assert float(l_u) == float(l_p)
    for a, b in zip(jax.tree.leaves(n_u["params"]), jax.tree.leaves(n_p["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_parity_through_1f1b_engine():
    """Same contract through the pipedream-flush schedule (pp=2, chunks=2) —
    segment ids ride the schedule's clock arithmetic, including the
    recompute-backward. Skipped where this container cannot compile CPU-sim
    pipelines (the repeated-field compiler_options limitation)."""
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.parallel.hybrid import build_runtime

    cfg = tiny_cfg()
    rng = np.random.RandomState(3)
    toks = rng.randint(0, 128, (8, 17)).astype(np.int32)
    packed = np.concatenate([toks, np.ones((8, 17), np.int32)], axis=1)

    def run(c, batch):
        rt = build_runtime(
            c,
            HybridParallelConfig.uniform(
                2, pp=2, chunks=2, pipeline_type="pipedream_flush",
                mixed_precision="fp32",
            ),
            global_batch_size=8,
        )
        state = rt.init_state(jax.random.key(0))
        new, loss = rt.train_step(state, rt.shard_batch(batch))
        flat = rt.flatten_params(new["params"])
        return float(loss), jax.tree.leaves(flat)

    l_u, p_u = run(cfg, toks)
    l_p, p_p = run(cfg.replace(pack_sequences=True), packed)
    assert l_u == l_p
    for a, b in zip(p_u, p_p):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cross_document_attention_leak_blocked():
    """A sentinel token flipped in segment A must not change a single logit
    in segment B of the same packed row (and must change A's own logits)."""
    cfg = tiny_cfg(pack_sequences=True)
    params = modeling.init_model_params(jax.random.key(0), cfg)
    toks = np.zeros((1, 16), np.int32)
    seg = np.zeros((1, 16), np.int32)
    toks[0, :8] = np.arange(1, 9); seg[0, :8] = 1
    toks[0, 8:14] = np.arange(20, 26); seg[0, 8:14] = 2
    from tests._stack_harness import forward

    logits = forward(params, jnp.asarray(np.concatenate([toks, seg], 1)), cfg)
    toks2 = toks.copy()
    toks2[0, 3] = 99  # sentinel in segment A
    logits2 = forward(params, jnp.asarray(np.concatenate([toks2, seg], 1)), cfg)
    np.testing.assert_array_equal(
        np.asarray(logits[0, 8:14]), np.asarray(logits2[0, 8:14])
    )
    assert not np.array_equal(np.asarray(logits[0, 3:8]), np.asarray(logits2[0, 3:8]))
    # padding is unreachable too: a pad-token change cannot move real logits
    toks3 = toks.copy()
    toks3[0, 15] = 77
    logits3 = forward(params, jnp.asarray(np.concatenate([toks3, seg], 1)), cfg)
    np.testing.assert_array_equal(
        np.asarray(logits[0, :14]), np.asarray(logits3[0, :14])
    )


def test_positions_reset_per_segment():
    seg = jnp.asarray([[1, 1, 1, 2, 2, 3, 0, 0]])
    np.testing.assert_array_equal(
        np.asarray(modeling.positions_from_segments(seg))[0],
        [0, 1, 2, 0, 1, 0, 0, 1],
    )


def test_packed_label_masking_at_boundaries():
    cfg = tiny_cfg(pack_sequences=True, max_seq_len=8)
    toks = np.arange(1, 10, dtype=np.int32)[None]  # (1, 9)
    seg = np.asarray([[1, 1, 1, 2, 2, 2, 3, 0, 0]], np.int32)
    _, labels = modeling.split_batch(
        jnp.asarray(np.concatenate([toks, seg], 1)), cfg
    )
    # label[i] = tokens[i+1] iff same segment and not padding
    np.testing.assert_array_equal(
        np.asarray(labels)[0], [2, 3, -100, 5, 6, -100, -100, -100]
    )


def test_packing_rejected_where_mask_cannot_reach():
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.parallel.hybrid import build_runtime

    cfg = tiny_cfg(pack_sequences=True)
    with pytest.raises(ValueError, match="attn_impl='xla'"):
        build_runtime(
            cfg.replace(attn_impl="flash"),
            HybridParallelConfig.uniform(2, mixed_precision="fp32"),
            global_batch_size=8,
        )
    with pytest.raises(ValueError, match="context parallelism"):
        build_runtime(
            cfg, HybridParallelConfig.uniform(2, cp=2, mixed_precision="fp32"),
            global_batch_size=8,
        )


# ---------------------------------------------------------------------------
# Prefetch lifecycle
# ---------------------------------------------------------------------------


def test_prefetch_matches_synchronous_stream(tmp_path):
    pa, _ = make_corpus(tmp_path, "a", 120, seed=1)
    sync = build_data_pipeline(_PipeCfg, 8, 32, seed=5, data_path=pa, pack=True)
    pre = build_data_pipeline(
        _PipeCfg, 8, 32, seed=5, data_path=pa, pack=True, prefetch_depth=2
    )
    try:
        for _ in range(6):
            a, b = next(sync), next(pre)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert sync.last_meta["nonpad_tokens"] == pre.last_meta["nonpad_tokens"]
    finally:
        pre.close()
        sync.close()


def test_prefetch_close_is_idempotent_and_joins(tmp_path):
    pa, _ = make_corpus(tmp_path, "a", 60, seed=1)
    pipe = build_data_pipeline(
        _PipeCfg, 4, 32, seed=5, data_path=pa, pack=True, prefetch_depth=2
    )
    next(pipe)
    t = pipe._prefetcher._thread
    pipe.close()
    assert not t.is_alive(), "prefetch thread must join on close()"
    pipe.close()  # idempotent


def test_prefetch_propagates_producer_exception():
    calls = {"n": 0}

    def make_item():
        calls["n"] += 1
        if calls["n"] >= 3:
            raise RuntimeError("corrupt shard mid-stream")
        return np.zeros(4, np.int32), {}

    pre = AsyncPrefetcher(make_item, lambda b: b, depth=1)
    got = 0
    with pytest.raises(RuntimeError, match="corrupt shard"):
        for _ in range(5):
            next(pre)
            got += 1
    assert got == 2
    assert not pre._thread.is_alive()


def test_prefetch_batches_are_fresh_buffers(tmp_path):
    """GTL103 discipline: the producer must never hand out the same backing
    buffer twice (mutation-after-dispatch is the serving-corruption class)."""
    pa, _ = make_corpus(tmp_path, "a", 60, seed=1)
    seen = []
    pipe = build_data_pipeline(
        _PipeCfg, 4, 32, seed=5, data_path=pa, pack=True,
        put_fn=lambda b: seen.append(b) or b,
    )
    next(pipe); next(pipe)
    assert seen[0] is not seen[1]
    assert not np.shares_memory(seen[0], seen[1])
    pipe.close()


# ---------------------------------------------------------------------------
# Trainer integration: preempt→resume per-source exactness
# ---------------------------------------------------------------------------


def _train_args(tmp_path, mixture_path, extra):
    return [
        "train", "--model_size", "llama-0.3b", "--hidden_size", "32",
        "--num_layers", "2", "--num_heads", "2", "--ffn_dim", "64",
        "--vocab_size", "128", "--seq_length", "32",
        "--global_train_batch_size", "8", "--mixed_precision", "fp32",
        "--data_mixture", mixture_path, "--pack_sequences", "1",
        "--prefetch_depth", "2",
    ] + extra


@pytest.mark.slow
def test_elastic_preempt_resume_per_source_exactness(tmp_path, monkeypatch):
    """The acceptance scenario under the supervisor itself: a mid-run
    preemption SIGTERM under `run-elastic` must restart, finish, and land a
    final per-source cursor identical to an uninterrupted run's — zero
    samples replayed, zero skipped, per source. (The tier-1 variant of this
    contract is test_trainer_resume_replays_and_skips_nothing_per_source,
    which exercises the same resume code path without subprocesses.)"""
    from galvatron_tpu.core.checkpoint import latest_step, read_manifest, step_path
    from galvatron_tpu.core.elastic import run_elastic
    from galvatron_tpu.utils.metrics import read_metrics

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", os.path.join(repo, ".jax_cache"))
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    monkeypatch.setenv("GALVATRON_FAULTS", "preempt_at_step=2")  # first child only
    monkeypatch.setenv("GALVATRON_FAULTS_WORLD", "8")

    make_corpus(tmp_path, "web", 250, seed=1)
    make_corpus(tmp_path, "books", 150, seed=2)
    mix = str(tmp_path / "mix.json")
    json.dump({"sources": [
        {"name": "web", "prefix": str(tmp_path / "web"), "weight": 0.7},
        {"name": "books", "prefix": str(tmp_path / "books"), "weight": 0.3},
    ]}, open(mix, "w"))
    ckpt = str(tmp_path / "ck")
    mpath = str(tmp_path / "m.jsonl")
    rc = run_elastic(_train_args(tmp_path, mix, [
        "--train_iters", "4", "--save", ckpt, "--save_interval", "2",
        "--max_restarts", "3", "--restart_backoff_s", "0.05",
        "--metrics_path", mpath,
    ])[1:])  # run_elastic takes the train flags without the mode word
    assert rc == 0
    meta = read_manifest(step_path(ckpt, latest_step(ckpt)))["meta"]
    ds = meta["data_state"]
    assert ds["position"] == 32 == meta["samples_consumed"]
    # uninterrupted reference cursor over the same mixture
    ref = build_data_pipeline(
        _PipeCfg, 8, 32, seed=1234, mixture=mix, pack=True
    )
    try:
        assert ds["per_source_consumed"] == ref.dataset.counts_at(32)
    finally:
        ref.close()
    # the preempted run's restart re-logged no step and dropped none
    steps = [r["step"] for r in read_metrics(mpath) if r["event"] == "train_iter"]
    assert sorted(set(steps)) == steps == list(range(len(steps)))


def test_trainer_resume_replays_and_skips_nothing_per_source(tmp_path):
    """2-iter run + save, resume to 4: the resumed JSONL must equal the
    uninterrupted run's tail bit-for-bit, and the final checkpoint's
    per-source counters must match the uninterrupted cursor exactly."""
    from galvatron_tpu.cli import main as cli_main
    from galvatron_tpu.core.checkpoint import latest_step, read_manifest, step_path
    from galvatron_tpu.utils.metrics import read_metrics

    make_corpus(tmp_path, "web", 250, seed=1)
    make_corpus(tmp_path, "books", 150, seed=2)
    mix = str(tmp_path / "mix.json")
    json.dump({"sources": [
        {"name": "web", "prefix": str(tmp_path / "web"), "weight": 0.7},
        {"name": "books", "prefix": str(tmp_path / "books"), "weight": 0.3},
    ]}, open(mix, "w"))
    ckpt = str(tmp_path / "ckpt")
    m_full, m_res = str(tmp_path / "full.jsonl"), str(tmp_path / "res.jsonl")

    assert cli_main(_train_args(tmp_path, mix, [
        "--train_iters", "4", "--metrics_path", m_full])) == 0
    assert cli_main(_train_args(tmp_path, mix, [
        "--train_iters", "2", "--save", ckpt, "--save_interval", "2"])) == 0
    assert cli_main(_train_args(tmp_path, mix, [
        "--train_iters", "4", "--save", ckpt, "--load", ckpt,
        "--save_interval", "2", "--metrics_path", m_res])) == 0

    full = [r for r in read_metrics(m_full) if r["event"] == "train_iter"]
    res = [r for r in read_metrics(m_res) if r["event"] == "train_iter"]
    assert [r["loss"] for r in full][2:] == [r["loss"] for r in res]
    assert [r["step"] for r in res] == [2, 3]

    meta = read_manifest(step_path(ckpt, latest_step(ckpt)))["meta"]
    ds = meta["data_state"]
    assert ds["position"] == 32 == meta["samples_consumed"]
    c = ds["per_source_consumed"]
    assert c["web"] + c["books"] == 32
    assert abs(c["web"] - 0.7 * 32) <= 1
    # the uninterrupted run derives the same cursor: zero replays, zero skips
    summary = [r for r in read_metrics(m_full) if r["event"] == "data_pipeline"]
    assert summary and summary[0]["consumed_web"] == c["web"]
    assert summary[0]["consumed_books"] == c["books"]
    # packing efficiency surfaced per-iteration and >= the acceptance bar
    effs = [r["packing_efficiency"] for r in full if r.get("packing_efficiency")]
    assert effs and min(effs) >= 0.9
    # resuming WITHOUT the data-pipeline flags must refuse, not silently
    # continue the real-corpus checkpoint on synthetic tokens
    with pytest.raises(ValueError, match="data-pipeline cursor"):
        cli_main([
            "train", "--model_size", "llama-0.3b", "--hidden_size", "32",
            "--num_layers", "2", "--num_heads", "2", "--ffn_dim", "64",
            "--vocab_size", "128", "--seq_length", "32",
            "--global_train_batch_size", "8", "--mixed_precision", "fp32",
            "--train_iters", "6", "--save", ckpt, "--load", ckpt,
        ])
