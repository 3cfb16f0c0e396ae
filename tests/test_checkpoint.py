"""Checkpoint/resume diagnostics (core/checkpoint.py): restore-failure
classification for known parameter-layout migrations."""


def test_legacy_layout_message_gating():
    """The bias-layout relabel fires only when the error names a missing bias
    leaf; unrelated restore failures (corrupt file, IO) surface verbatim, and
    missing-bias errors are not mislabeled as the wqkv-layout change."""
    import jax

    from galvatron_tpu.core.checkpoint import _legacy_layout_message

    biased = {
        "layers": [
            {
                "attn": {
                    "wqkv": jax.ShapeDtypeStruct((4, 3, 4), "float32"),
                    "wqkv_b": jax.ShapeDtypeStruct((4,), "float32"),
                }
            }
        ]
    }
    # orbax-style structure mismatch naming the bias leaf (its leaf reprs
    # mention "shape" too -- must pick the bias message, not the wqkv one)
    msg = _legacy_layout_message(
        biased,
        "Dict key mismatch; target: MISSING layers[0].attn.wqkv_b "
        "Source: ShapeDtypeStruct(shape=(4,), dtype=float32)",
    )
    assert msg and "projection biases" in msg
    # non-structural failure on the same tree -> no relabel
    assert _legacy_layout_message(biased, "failed to deserialize array: corrupt chunk") is None
    # structural failure not naming a bias leaf -> no bias relabel
    plain = {"layers": [{"attn": {"wo": jax.ShapeDtypeStruct((4, 4), "float32")}}]}
    assert _legacy_layout_message(plain, "Dict key mismatch; missing keys: x") is None
    # genuine wqkv shape mismatch (no missing keys) still gets the wqkv message
    msg2 = _legacy_layout_message(biased, "shape mismatch for layers[0].attn.wqkv")
    assert msg2 and "fused-QKV" in msg2


def test_legacy_layout_message_requires_missing_key():
    """Errors that mention a bias leaf WITHOUT a missing-key mismatch (shape
    conflict, corrupt array) surface verbatim — no migration relabel."""
    import jax

    from galvatron_tpu.core.checkpoint import _legacy_layout_message

    biased = {"layers": [{"attn": {"wqkv_b": jax.ShapeDtypeStruct((4,), "float32")}}]}
    assert (
        _legacy_layout_message(
            biased, "corrupt chunk deserializing layers[0].attn.wqkv_b"
        )
        is None
    )


def test_portable_checkpoint_cross_layout_resume(tmp_path):
    """Checkpoints are saved in the flat-layers layout regardless of engine,
    so a run saved at one (pp, vpp, schedule) resumes at any other — the
    eval loss of every restored layout matches the source exactly."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from galvatron_tpu.core.checkpoint import (
        restore_checkpoint_portable,
        save_checkpoint_portable,
    )
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import ModelConfig
    from galvatron_tpu.parallel.hybrid import build_runtime

    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, num_layers=4, num_heads=4,
        ffn_dim=128, max_seq_len=16, dtype=jnp.float32,
    )
    adam = AdamConfig(lr=1e-3)
    batch = jnp.asarray(
        np.random.RandomState(0).randint(0, 128, (8, 17)), jnp.int32
    )

    def rt_for(**kw):
        hp = HybridParallelConfig.uniform(4, mixed_precision="fp32", **kw)
        return build_runtime(cfg, hp, adam=adam, global_batch_size=8, seq_len=16)

    # train 2 steps under pp=2 1F1B, save portable
    src = rt_for(pp=2, tp=1, chunks=2, pipeline_type="pipedream_flush")
    state = src.init_state(jax.random.key(0))
    for _ in range(2):
        state, _ = src.train_step(state, batch)
    ref_loss = float(src.eval_loss(state, batch))
    ck = str(tmp_path / "portable")
    save_checkpoint_portable(ck, state, 2, src)

    # restore into: flat GSPMD (pp=1), gpipe pp=2, interleaved 1F1B pp=2 vpp=2
    targets = {
        "pp1": rt_for(tp=2, dp_type="zero3", vocab_tp=2),
        "gpipe_pp2": rt_for(pp=2, tp=1, chunks=2, pipeline_type="gpipe"),
        "il_1f1b": rt_for(pp=2, vpp=2, tp=1, chunks=2, pipeline_type="pipedream_flush"),
    }
    for name, rt in targets.items():
        restored = restore_checkpoint_portable(ck, rt, step=2)
        assert int(np.asarray(restored["step"])) == 2
        got = float(rt.eval_loss(restored, batch))
        np.testing.assert_allclose(got, ref_loss, rtol=3e-5, atol=3e-5, err_msg=name)
        # resumed training continues sanely (opt moments restored too):
        # train_step returns the pre-update loss, so step twice
        st2, _ = rt.train_step(restored, batch)
        st2, l2 = rt.train_step(st2, batch)
        assert np.isfinite(float(l2)) and float(l2) < ref_loss


def test_portable_checkpoint_swin_cross_schedule_resume(tmp_path):
    """The K-section engines save the same flat-layers portable layout in
    both schedule orderings: a Swin run trained under the coupled 1F1B
    resumes under gpipe (and flat pp=1) with the exact eval loss."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from galvatron_tpu.core.checkpoint import (
        restore_checkpoint_portable,
        save_checkpoint_portable,
    )
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import ModelConfig
    from galvatron_tpu.parallel.hybrid import build_runtime

    from _vision_common import SWIN_TINY as cfg, make_vision_batches

    adam = AdamConfig(lr=1e-3)
    batch = make_vision_batches(cfg, seed=0, n=1)[0]

    def rt_for(**kw):
        hp = HybridParallelConfig.uniform(4, mixed_precision="fp32", **kw)
        return build_runtime(cfg, hp, adam=adam, global_batch_size=8)

    src = rt_for(pp=2, chunks=2, pipeline_type="pipedream_flush")
    state = src.init_state(jax.random.key(0))
    for _ in range(2):
        state, _ = src.train_step(state, batch)
    ref_loss = float(src.eval_loss(state, batch))
    ck = str(tmp_path / "portable_swin")
    save_checkpoint_portable(ck, state, 2, src)

    for name, rt in {
        "gpipe_pp2": rt_for(pp=2, chunks=2, pipeline_type="gpipe"),
        "pp1": rt_for(tp=2, vocab_tp=2),
    }.items():
        restored = restore_checkpoint_portable(ck, rt, step=2)
        assert int(np.asarray(restored["step"])) == 2
        got = float(rt.eval_loss(restored, batch))
        np.testing.assert_allclose(got, ref_loss, rtol=3e-5, atol=3e-5, err_msg=name)
        st2, _ = rt.train_step(restored, batch)
        st2, l2 = rt.train_step(st2, batch)
        assert np.isfinite(float(l2)) and float(l2) < ref_loss


def test_positive_layout_detection_survives_reworded_exceptions(tmp_path, monkeypatch):
    """Flat-vs-stacked restore is chosen STRUCTURALLY from the orbax
    checkpoint metadata (_checkpoint_layout), with exception-text
    classification only as a last-resort guard for unreadable metadata — so
    an orbax release that rewords its structure-mismatch message cannot flip
    restore behavior. Adversarial setup: any restore attempted against the
    WRONG layout raises a message sharing no words with the classifier's
    mismatch vocabulary; both layouts must still restore correctly, and a
    checkpoint matching neither layout must fail with the actionable
    migration message rather than the gibberish."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from galvatron_tpu.core import checkpoint as ck
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import ModelConfig
    from galvatron_tpu.parallel.hybrid import build_runtime

    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, num_layers=4, num_heads=4,
        ffn_dim=128, max_seq_len=16, dtype=jnp.float32,
    )
    hp = HybridParallelConfig.uniform(4, pp=2, chunks=2, mixed_precision="fp32")
    rt = build_runtime(cfg, hp, adam=AdamConfig(lr=1e-3), global_batch_size=8, seq_len=16)
    state = rt.init_state(jax.random.key(0))
    flat_dir, stacked_dir = str(tmp_path / "flat"), str(tmp_path / "stacked")
    ck.save_checkpoint_portable(flat_dir, state, 1, rt)
    ck.save_checkpoint(stacked_dir, state, 1)  # engine-native stacked layout

    flat_keys = ck._tree_keypaths(ck.flat_abstract_state_of(rt))
    stacked_keys = ck._tree_keypaths(ck.abstract_state_of(rt))
    assert flat_keys != stacked_keys  # pp=2 stacks stages; layouts differ
    # positive structural detection fires on real metadata for BOTH layouts
    assert ck._checkpoint_layout(flat_dir, 1, ck.flat_abstract_state_of(rt),
                                 ck.abstract_state_of(rt)) == "flat"
    assert ck._checkpoint_layout(stacked_dir, 1, ck.flat_abstract_state_of(rt),
                                 ck.abstract_state_of(rt)) == "stacked"

    on_disk = {flat_dir: flat_keys, stacked_dir: stacked_keys}
    orig_restore = ck.restore_checkpoint

    def adversarial_restore(ckpt_dir, abstract_state, step=None):
        want = ck._tree_keypaths(abstract_state)
        have = on_disk[ckpt_dir.rstrip("/")]
        if want != have:
            # no 'missing'/'mismatch'/'shape'/... vocabulary — the substring
            # guard cannot classify this
            raise RuntimeError("qux kaboom, incompatible trees (code 77)")
        return orig_restore(ckpt_dir, abstract_state, step)

    monkeypatch.setattr(ck, "restore_checkpoint", adversarial_restore)

    ref = float(rt.eval_loss(state, jnp.zeros((8, 17), jnp.int32)))
    for d in (flat_dir, stacked_dir):
        restored = ck.restore_checkpoint_portable(d, rt, step=1)
        got = float(rt.eval_loss(restored, jnp.zeros((8, 17), jnp.int32)))
        np.testing.assert_allclose(got, ref, rtol=3e-5, atol=3e-5, err_msg=d)

    # a checkpoint matching NEITHER layout (different depth) fails with the
    # actionable message from positive detection, not the reworded gibberish
    cfg6 = cfg.replace(num_layers=6)
    rt6 = build_runtime(
        cfg6, HybridParallelConfig.uniform(6, pp=2, chunks=2, mixed_precision="fp32"),
        adam=AdamConfig(lr=1e-3), global_batch_size=8, seq_len=16,
    )
    other_dir = str(tmp_path / "other")
    ck.save_checkpoint_portable(other_dir, rt6.init_state(jax.random.key(1)), 1, rt6)
    on_disk[other_dir] = ck._tree_keypaths(ck.flat_abstract_state_of(rt6))
    try:
        ck.restore_checkpoint_portable(other_dir, rt, step=1)
        raise AssertionError("expected ValueError for neither-layout checkpoint")
    except ValueError as e:
        assert "neither" in str(e)


def test_manifest_records_a_scalar_the_same_whoever_can_gather_it():
    """A pod writes structure-only records for leaves no one process can gather
    (``test_multihost.py``); the process that restores the step alone gathers
    them and verifies against that manifest: the two records of a scalar
    (``step``, ``opt.count``) must carry the same shape."""
    import numpy as np

    from galvatron_tpu.core.checkpoint import _leaf_digest, verify_manifest

    class PodScalar:  # what a process of a multi-host job sees of a global scalar
        is_fully_addressable = False
        shape = ()
        dtype = np.int32

    whole = _leaf_digest(np.int32(3))
    pod = _leaf_digest(PodScalar())
    assert (pod["shape"], pod["dtype"], pod["digest"]) == (whole["shape"], whole["dtype"], None)
    assert verify_manifest({"leaves": {"['step']": pod}}, {"step": np.int32(3)}) == []
