"""Kernel correctness tests: Pallas flash attention (interpret mode on CPU)
and ring attention vs the einsum reference (build plan step 7/11)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.models import modeling
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.ops.flash_attention import flash_attention


def ref_attention(q, k, v, causal=True):
    cfg = ModelConfig(num_heads=q.shape[2], hidden_size=q.shape[2] * q.shape[3])
    return modeling.attention_xla(q, k, v, cfg)


def rand_qkv(key, b=2, s=128, n=2, d=32, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    shape = (b, s, n, d)
    return tuple(jax.random.normal(ks[i], shape, dtype) for i in range(3))


def test_flash_forward_matches_reference():
    q, k, v = rand_qkv(jax.random.key(0))
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_forward_uneven_blocks():
    q, k, v = rand_qkv(jax.random.key(1), s=128)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=32)
    ref = ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_backward_matches_reference():
    q, k, v = rand_qkv(jax.random.key(2), s=64)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=32, block_k=32) ** 2).sum()

    def f_ref(q, k, v):
        return (ref_attention(q, k, v) ** 2).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)


def test_flash_fallback_on_untileable_shape():
    q, k, v = rand_qkv(jax.random.key(3), s=48)  # 48 % 32 != 0
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def _rope_tables(s, d):
    cfg = ModelConfig(num_heads=2, hidden_size=2 * d, max_seq_len=s)
    return modeling.rope_tables(cfg, s)


def test_flash_fused_rope_matches_external_rope():
    """RoPE fused into the kernels (q/k rotated in VMEM) must equal the
    materialized apply_rope → attention path, forward and gradients (the
    backward counter-rotates dq/dk back to raw coordinates)."""
    q, k, v = rand_qkv(jax.random.key(4), s=128, d=32)
    cos, sin = _rope_tables(128, 32)

    def f_fused(q, k, v):
        return (
            flash_attention(q, k, v, causal=True, block_q=32, block_k=64, rope=(cos, sin)) ** 2
        ).sum()

    def f_ref(q, k, v):
        qr = modeling.apply_rope(q, cos, sin)
        kr = modeling.apply_rope(k, cos, sin)
        return (ref_attention(qr, kr, v) ** 2).sum()

    np.testing.assert_allclose(
        float(f_fused(q, k, v)), float(f_ref(q, k, v)), rtol=2e-5
    )
    g_fused = jax.grad(f_fused, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)


def test_flash_blocked_causal_path_matches_reference():
    """The blocked-causal forward (one pallas call per q block, scale folded
    into the q-side rope tables, additive triangular bias) is the production
    path for causal+rope with equal tileable blocks — pin it against the
    materialized-rope reference, forward AND gradients (the backward runs the
    grid kernels from the blocked forward's saved LSE)."""
    from galvatron_tpu.ops import flash_attention as fa

    s, d = 128, 32
    q, k, v = rand_qkv(jax.random.key(7), s=s, d=d)
    cos, sin = _rope_tables(s, d)
    assert fa._use_blocked(s, d, True, 32, 32)

    def f_blocked(q, k, v):
        return (
            flash_attention(q, k, v, causal=True, block_q=32, block_k=32, rope=(cos, sin)) ** 2
        ).sum()

    def f_ref(q, k, v):
        qr = modeling.apply_rope(q, cos, sin)
        kr = modeling.apply_rope(k, cos, sin)
        return (ref_attention(qr, kr, v) ** 2).sum()

    np.testing.assert_allclose(float(f_blocked(q, k, v)), float(f_ref(q, k, v)), rtol=2e-5)
    g_blocked = jax.grad(f_blocked, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_blocked, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)
    # the gate scales with head_dim and unroll count, not bare seq length
    # (the s*d envelope is 8192*128 under the raised vmem_limit_bytes:
    # BASELINE.md, "Round-4 VMEM discovery")
    assert not fa._use_blocked(16384, 128, True, 1024, 1024)
    assert not fa._use_blocked(8192, 256, True, 1024, 1024)
    assert not fa._use_blocked(4096, 128, True, 128, 128)
    assert fa._use_blocked(8192, 128, True, 1024, 1024)
    assert fa._use_blocked(2048, 128, True, 1024, 1024)
    # the combined backward now shares the 8k envelope (measured -9%/-15%
    # on the full train step at s=4096/8192 vs the grid kernels)
    assert fa._use_blocked_bwd(4096, 128, True, 1024, 1024)
    assert fa._use_blocked_bwd(8192, 128, True, 1024, 1024)
    assert not fa._use_blocked_bwd(16384, 128, True, 1024, 1024)
    # each envelope's threshold is derived from its own measured scoped
    # charge: the bwd 8k extension charges ~43 MB (21.4 MB at s=4096 anchor),
    # so a 32-42 MB budget must NOT admit it (it passes the fwd's ~24 MB
    # gate but would fail the bwd compile), while s=4096 (21.4 MB) fits
    bwd_cands = (8192 * 128, 4096 * 128)
    assert fa._seq_envelope(fa._BWD_MB_PER_SXD, bwd_cands, 2048 * 128, budget_mb=35) == 4096 * 128
    assert fa._seq_envelope(fa._BWD_MB_PER_SXD, bwd_cands, 2048 * 128, budget_mb=48) == 8192 * 128
    assert fa._seq_envelope(fa._BWD_MB_PER_SXD, bwd_cands, 2048 * 128, budget_mb=16) == 2048 * 128
    assert fa._seq_envelope(fa._FWD_MB_PER_SXD, (8192 * 128,), 4096 * 128, budget_mb=35) == 8192 * 128
    assert fa._seq_envelope(fa._FWD_MB_PER_SXD, (8192 * 128,), 4096 * 128, budget_mb=16) == 4096 * 128
    # a budget below even the floor's charge disables the blocked path
    # instead of risking a compile-time Mosaic VMEM failure
    assert fa._seq_envelope(fa._FWD_MB_PER_SXD, (8192 * 128,), 4096 * 128, budget_mb=12) == 0
    assert fa._seq_envelope(fa._BWD_MB_PER_SXD, bwd_cands, 2048 * 128, budget_mb=5) == 0


def test_headmajor_attn_block_matches_legacy_path():
    """The head-major wiring (einsum projections + flash_attention_hm) is the
    default production path for flash models — pin it against the legacy
    project->transpose->flash path for (a) MHA blocked layout with qkv/wo
    biases, (b) GQA interleaved layout."""
    for kvh, bias in [(None, True), (2, False)]:
        cfg = ModelConfig(
            vocab_size=64, hidden_size=64, num_heads=4, num_kv_heads=kvh,
            ffn_dim=128, max_seq_len=64, attn_impl="flash", use_bias=bias,
        )
        key = jax.random.key(10 if bias else 11)
        p = modeling.init_layer_params(key, cfg)["attn"]
        if bias:  # init zeros them; randomize so the broadcast is exercised
            p = dict(p)
            p["wqkv_b"] = jax.random.normal(jax.random.key(13), p["wqkv_b"].shape)
            p["wo_b"] = jax.random.normal(jax.random.key(14), p["wo_b"].shape)
        x = jax.random.normal(jax.random.key(12), (2, 64, 64), jnp.float32)
        cos_sin = modeling.rope_tables(cfg, 64)
        assert cfg.flash_headmajor
        got = modeling.attn_block(x, p, cfg, cos_sin)
        ref = modeling.attn_block(x, p, cfg.replace(flash_headmajor=False), cos_sin)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"kvh={kvh} bias={bias}",
        )


def test_flash_qkv_stacked_matches_reference():
    """The stacked-qkv entry (flash_attention_qkv: kernels consume the fused
    projection's (b, 3, h, s, d) output via index-mapped block specs) is the
    default production path for blocked MHA — pin forward AND gradients
    (its custom VJP feeds the stacked residual to the combined blocked
    backward, which emits a stacked dqkv directly) against the
    materialized-rope reference."""
    from galvatron_tpu.ops.flash_attention import (
        flash_attention_qkv,
        flash_qkv_supported,
    )

    s, d = 128, 32
    q, k, v = rand_qkv(jax.random.key(8), s=s, d=d)
    cos, sin = _rope_tables(s, d)
    assert flash_qkv_supported(s, d, True)
    # (b, s, n, d) triple -> stacked (b, 3, n, s, d) head-major
    qkv = jnp.stack(
        [jnp.transpose(t, (0, 2, 1, 3)) for t in (q, k, v)], axis=1
    )

    def f_stacked(qkv_):
        out = flash_attention_qkv(qkv_, rope=(cos, sin), block_q=32)
        return (out.astype(jnp.float32) ** 2).sum()

    def f_ref(q_, k_, v_):
        qr = modeling.apply_rope(q_, cos, sin)
        kr = modeling.apply_rope(k_, cos, sin)
        return (ref_attention(qr, kr, v_) ** 2).sum()

    np.testing.assert_allclose(float(f_stacked(qkv)), float(f_ref(q, k, v)), rtol=2e-5)
    dqkv = jax.grad(f_stacked)(qkv)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for c, g in enumerate(g_ref):
        np.testing.assert_allclose(
            np.asarray(jnp.transpose(dqkv[:, c], (0, 2, 1, 3))), np.asarray(g),
            rtol=5e-4, atol=5e-4, err_msg=f"slot {c}",
        )


def test_flash_bwd_subblock_ratio():
    """The combined blocked backward tiles q in sub-blocks smaller than the
    k block on VMEM-constrained shapes (ratio = bk/bq_sub > 1); the
    diagonal-straddling sub-blocks then mask with a static row offset.
    Force ratio=2 and pin gradients against the materialized-rope
    reference (the default-config tests all run ratio=1)."""
    from galvatron_tpu.ops import flash_attention as fa

    s, d = 128, 32
    q, k, v = rand_qkv(jax.random.key(11), s=s, d=d)
    cos, sin = _rope_tables(s, d)

    def f_flash(q_, k_, v_):
        out = fa.flash_attention(
            q_, k_, v_, causal=True, block_q=64, block_k=64, rope=(cos, sin)
        )
        return (out.astype(jnp.float32) ** 2).sum()

    def f_ref(q_, k_, v_):
        qr = modeling.apply_rope(q_, cos, sin)
        kr = modeling.apply_rope(k_, cos, sin)
        return (ref_attention(qr, kr, v_) ** 2).sum()

    orig = fa._BWD_BQ_SUB
    fa._BWD_BQ_SUB = 32
    try:
        assert fa._use_blocked_bwd(s, d, True, 64, 64)
        g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    finally:
        fa._BWD_BQ_SUB = orig
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for name, gf, gr in zip("qkv", g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=5e-4, atol=5e-4, err_msg=name
        )


def _eqns(jaxpr, into_kernels=False):
    """Every equation of ``jaxpr`` and of the jaxprs its equations carry
    (pjit, custom_vjp, ...), in program order; a pallas_call's body only
    when asked."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name == "pallas_call" and not into_kernels:
            continue
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, into_kernels)


def _pallas_calls(fn, *args):
    """The pallas_call equations ``fn`` traces to, in program order."""
    return [e for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr) if e.primitive.name == "pallas_call"]


def _kernel_names(fn, *args):
    return [e.params["name"] for e in _pallas_calls(fn, *args)]


def _ref_attention_hm(q, k, v):
    """float32 causal reference, head-major (b, h, s, d); GQA k/v repeated."""
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    t = lambda x: jnp.transpose(x, (0, 2, 1, 3))  # noqa: E731
    return t(ref_attention(t(q), t(k), t(v)))


@pytest.mark.parametrize("entry", ["qkv", "hm", "hm_gqa"])
@pytest.mark.parametrize("s,d,bq_sub", [
    (1024, 64, 512), (2048, 64, 512), (1024, 128, 512), (2048, 128, 512), (1024, 64, 256)])
def test_flash_blocked_no_rope_matches_reference(entry, s, d, bq_sub, monkeypatch):
    """Causal attention WITHOUT RoPE (gpt / opt: learned positions) takes the
    blocked family too: no table operands, the scale on the fp32 score block.
    Forward and dq / dk / dv against the float32 reference at the tolerances
    the grid kernels' tests use, through the stacked entry, the head-major
    entry and GQA (kv_rep 2) through ``_flash``, at the blocks production
    takes: 512-row forward calls (2-4 of them) under block_q 1024 and a
    (512, 512) backward; the last case forces the backward's sub-block ratio
    to 2 (the static row offset of the diagonal mask)."""
    from galvatron_tpu.ops import flash_attention as fa

    bq = min(1024, s)
    monkeypatch.setattr(fa, "_BWD_BQ_SUB_NO_ROPE", bq_sub)
    assert fa._use_blocked_bwd(s, d, True, bq, bq)
    assert fa._no_rope_rows(bq, s) == 512 and fa._bwd_blocks(bq, rope=False) == (512, bq_sub)
    h, kvh = 2, (1 if entry == "hm_gqa" else 2)
    ks = jax.random.split(jax.random.key(s + d), 3)
    q = jax.random.normal(ks[0], (1, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, kvh, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, kvh, s, d), jnp.float32)

    if entry == "qkv":
        def out_flash(q_, k_, v_):
            return fa.flash_attention_qkv(jnp.stack([q_, k_, v_], axis=1))
    else:
        def out_flash(q_, k_, v_):
            return fa.flash_attention_hm(q_, k_, v_, causal=True)

    def loss(fn):
        return lambda *a: (fn(*a) ** 2).sum()

    grad = jax.grad(loss(out_flash), argnums=(0, 1, 2))
    fwd = "flash_fwd_qkv" if entry == "qkv" else "flash_fwd_blocked"
    assert _kernel_names(grad, q, k, v) == [fwd] * (s // 512) + ["flash_bwd_blocked"]
    g_flash = grad(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out_flash(q, k, v)), np.asarray(_ref_attention_hm(q, k, v)),
        rtol=2e-5, atol=2e-5,
    )
    g_ref = jax.grad(loss(_ref_attention_hm), argnums=(0, 1, 2))(q, k, v)
    for name, gf, gr in zip(("dq", "dk", "dv"), g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=5e-4, atol=5e-4, err_msg=name
        )


_G, _B = "grid", "blocked"


@pytest.mark.parametrize("rope", [True, False], ids=["rope", "no_rope"])
@pytest.mark.parametrize("causal,s,d,block,family", [
    (True, 2048, 64, 1024, _B),    # opt-1.3b under tp 4: the four-chip cell
    (True, 4096, 128, 1024, _B),   # baichuan-7b at its context
    (True, 512, 128, 512, _B),
    (True, 8192, 64, 1024, _B),    # a d-64 slab pads to 128 lanes: the edge
    (True, 8192, 128, 1024, _B),
    (False, 2048, 64, 1024, _G),   # non-causal (bert, vit, t5's encoder)
    (False, 512, 128, 512, _G),
    (True, 16384, 64, 1024, _G),   # beyond the s*d envelope and the unroll
    (True, 16384, 128, 1024, _G),
    (True, 8192, 256, 1024, _G),
    (True, 4096, 128, 128, _G),    # 32 row blocks: unroll > 8
    (True, 1536, 64, 1024, _G),    # s does not tile the block
])
def test_flash_selector_table(rope, causal, s, d, block, family):
    """Which family serves a shape: causal inside the envelopes -> blocked,
    with RoPE or without; non-causal or beyond them -> grid. The selectors see
    shapes only (``rope`` picks the instance inside the blocked family)."""
    from galvatron_tpu.ops import flash_attention as fa

    assert fa._use_blocked(s, d, causal, block, block) == (family == _B)
    assert fa._use_blocked_bwd(s, d, causal, block, block) == (family == _B)
    if s > 2048 or d > 128 or s % block:
        return  # the names below need a trace; the large shapes add nothing
    x = jax.ShapeDtypeStruct((1, 2, s, d), jnp.bfloat16)
    t = jax.ShapeDtypeStruct((s, d // 2), jnp.float32)

    def loss(q, k, v, *tables):
        out = fa._flash(q, k, v, tables or None, 1.0, causal, block, block)
        return out.astype(jnp.float32).sum()

    names = set(_kernel_names(jax.grad(loss, argnums=(0, 1, 2)), x, x, x, *([t, t] * rope)))
    want = {_B: {"flash_fwd_blocked", "flash_bwd_blocked"},
            _G: {"flash_fwd_grid", "flash_bwd_dkv", "flash_bwd_dq"}}
    assert names == want[family], names


#: equations of the RoPE kernel bodies at baichuan-7b_s4096's shape
#: (2, 3, 32, 4096, 128), counted at 4365126 (PR 25's tree, before the no-RoPE
#: instance): forward row block 4 of 4; the combined backward
_ROPE_BODY_EQNS = {"flash_fwd_qkv": 106, "flash_bwd_blocked": 2139}


def test_flash_rope_kernel_bodies_do_not_grow(monkeypatch):
    """What refused PR 26: a generalisation of the blocked bodies that puts
    work into the RoPE instance shows in ``setup_s`` (the bodies are unrolled:
    4 forward calls a layer and 72 backward pairs at s 4096, traced and
    lowered by Python each time) and in the kernels. The RoPE bodies count no
    more equations than at the parent; the no-RoPE bodies, held to the same
    blocks, count fewer. Whoever adds a window, a segment mask or a 192/128
    head here: give the new case a trace-time branch and leave these numbers
    alone."""
    from galvatron_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_NO_ROPE_BQ", 1024)
    monkeypatch.setattr(fa, "_BWD_BQ_SUB_NO_ROPE", fa._BWD_BQ_SUB)
    b, h, s, d = 2, 32, 4096, 128
    qkv = jax.ShapeDtypeStruct((b, 3, h, s, d), jnp.bfloat16)
    t = jax.ShapeDtypeStruct((s, d // 2), jnp.float32)

    def body_eqns(*tables):
        fn = jax.grad(lambda x, *tb: fa.flash_attention_qkv(
            x, rope=tb or None).astype(jnp.float32).sum())
        calls = _pallas_calls(fn, qkv, *tables)
        assert [c.params["name"] for c in calls] == ["flash_fwd_qkv"] * 4 + ["flash_bwd_blocked"]
        return {c.params["name"]: sum(1 for _ in _eqns(c.params["jaxpr"], into_kernels=True))
                for c in calls[3:]}

    rope, no_rope = body_eqns(t, t), body_eqns()
    for name, at_parent in _ROPE_BODY_EQNS.items():
        assert rope[name] <= at_parent, (name, rope[name], at_parent)
        assert no_rope[name] < rope[name], (name, no_rope[name], rope[name])


def test_flash_grid_causal_gradients_match_reference():
    """Causal shapes the blocked family does not take (here unequal blocks)
    keep the grid kernels, forward and backward."""
    q, k, v = rand_qkv(jax.random.key(21), s=128)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=64, block_k=32) ** 2).sum()

    def f_ref(q, k, v):
        return (ref_attention(q, k, v) ** 2).sum()

    grad = jax.grad(f_flash, argnums=(0, 1, 2))
    assert set(_kernel_names(grad, q, k, v)) == {"flash_fwd_grid", "flash_bwd_dkv", "flash_bwd_dq"}
    for a, b in zip(grad(q, k, v), jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)


def test_flash_fallback_preserves_causal_and_scale():
    """The untileable-shape fallback must honor causal=False (encoder models)
    and a caller-supplied sm_scale — regression: it used to rebuild a default
    (causal=True, 1/sqrt(d)) config, silently causally masking encoders."""
    q, k, v = rand_qkv(jax.random.key(6), s=48, d=32)  # 48 % 32 != 0
    out = flash_attention(q, k, v, causal=False, sm_scale=0.25, block_q=32, block_k=32)
    cfg = ModelConfig(num_heads=2, hidden_size=64, causal=False)
    ref = modeling.attention_xla(q * (0.25 * np.sqrt(32)), k, v, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    # and the causal mask really is off: last query attends to the last key
    out_causal = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert not np.allclose(np.asarray(out), np.asarray(out_causal), atol=1e-3)


def test_flash_fused_rope_fallback_applies_rope():
    """The untileable-shape fallback must still apply the rope it was asked
    to fuse."""
    q, k, v = rand_qkv(jax.random.key(5), s=48, d=32)
    cos, sin = _rope_tables(48, 32)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, rope=(cos, sin))
    ref = ref_attention(modeling.apply_rope(q, cos, sin), modeling.apply_rope(k, cos, sin), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ring_attention_matches_reference():
    from galvatron_tpu.parallel.mesh import build_mesh
    from galvatron_tpu.parallel.ring import ring_attention

    mesh, axes = build_mesh(pp=1)
    q, k, v = rand_qkv(jax.random.key(4), s=64)
    cp_axes = ("x2",)  # ring of 2

    @jax.jit
    def run(q, k, v):
        return ring_attention(q, k, v, mesh, cp_axes)

    out = run(q, k, v)
    ref = ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ring_attention_grad_matches_reference():
    from galvatron_tpu.parallel.mesh import build_mesh
    from galvatron_tpu.parallel.ring import ring_attention

    mesh, axes = build_mesh(pp=1)
    q, k, v = rand_qkv(jax.random.key(5), s=64, b=1)
    cp_axes = ("x1", "x2")  # ring of 4 over two mesh axes

    g_ring = jax.jit(
        jax.grad(lambda q, k, v: (ring_attention(q, k, v, mesh, cp_axes) ** 2).sum(), (0, 1, 2))
    )(q, k, v)
    g_ref = jax.grad(lambda q, k, v: (ref_attention(q, k, v) ** 2).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)


def test_cp_layer_in_hybrid_runtime():
    """cp>1 layer strategy end-to-end through the runtime."""
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
    from galvatron_tpu.parallel.hybrid import build_runtime
    from tests.test_hybrid_runtime import make_batches, reference_losses
    from tests._train_common import CFG

    hp = HybridParallelConfig(
        pp=1,
        layer_strategies=[LayerStrategy(cp=2)] * 4,
        vocab_tp=1,
        mixed_precision="fp32",
    )
    rt = build_runtime(CFG, hp, adam=AdamConfig(lr=1e-3), global_batch_size=8, seq_len=32)
    state = rt.init_state(jax.random.key(0))
    batches = make_batches()
    ref = reference_losses(CFG, batches)
    losses = []
    for b in batches:
        state, loss = rt.train_step(state, b)
        losses.append(float(loss))
    np.testing.assert_allclose(losses, ref, rtol=2e-4, atol=2e-4)


def test_cp_layer_under_pipeline_parallelism():
    """cp>1 inside a pp>1 pipeline: the ring/a2a shard_maps nest inside the
    pipeline's manual-'pp' region (regression: the nested shard_map used the
    concrete mesh and lax.axis_index, both of which shardy rejects inside a
    manual region — pp+cp combos failed to trace). Parity against the plain
    pp=2 trajectory (same micro-batching; chunked loss differs from the
    full-batch reference by averaging semantics, so cp must be compared at
    equal chunking)."""
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
    from galvatron_tpu.parallel.hybrid import build_runtime
    from tests.test_hybrid_runtime import make_batches
    from tests._train_common import CFG

    batches = make_batches()

    def run(ls):
        hp = HybridParallelConfig(
            pp=2, chunks=2, layer_strategies=ls, vocab_tp=1, mixed_precision="fp32"
        )
        rt = build_runtime(CFG, hp, adam=AdamConfig(lr=1e-3), global_batch_size=8, seq_len=32)
        state = rt.init_state(jax.random.key(0))
        losses = []
        for b in batches:
            state, loss = rt.train_step(state, b)
            losses.append(float(loss))
        return losses

    ref = run([LayerStrategy()] * 4)
    for impl in ("ring", "a2a"):
        got = run([LayerStrategy(cp=2, cp_impl=impl)] * 4)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4, err_msg=impl)


def test_ring_flash_block_size_selection():
    """Ring hops run the Pallas flash kernels whenever the local sequence
    tiles to a power of two; otherwise the einsum online-softmax fallback."""
    from galvatron_tpu.parallel.ring import _flash_block_size

    assert _flash_block_size(2048) == 1024
    assert _flash_block_size(96) == 32
    assert _flash_block_size(16) == 16
    assert _flash_block_size(12) == 0  # falls back to einsum ring
    assert _flash_block_size(7) == 0


def test_ring_attention_einsum_fallback_matches_reference():
    """Non-tiling local sequence (24/2 = 12) takes the einsum ring and still
    matches the single-device reference."""
    from galvatron_tpu.parallel.mesh import build_mesh
    from galvatron_tpu.parallel.ring import ring_attention

    mesh, axes = build_mesh(pp=1)
    q, k, v = rand_qkv(jax.random.key(7), s=24)
    out = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh, ("x2",)))(q, k, v)
    ref = ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ring_flash_larger_ring_grad():
    """cp=8 (every CPU-sim device) through the flash-block ring, fwd + grad."""
    from galvatron_tpu.parallel.mesh import build_mesh
    from galvatron_tpu.parallel.ring import ring_attention

    mesh, axes = build_mesh(pp=1)
    q, k, v = rand_qkv(jax.random.key(8), b=1, s=128)
    cp_axes = ("x0", "x1", "x2")  # ring of 8; local seq 16
    out = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh, cp_axes))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_attention(q, k, v)), rtol=2e-5, atol=2e-5
    )
    g_ring = jax.jit(
        jax.grad(lambda q, k, v: (ring_attention(q, k, v, mesh, cp_axes) ** 2).sum(), (0, 1, 2))
    )(q, k, v)
    g_ref = jax.grad(lambda q, k, v: (ref_attention(q, k, v) ** 2).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)


def test_ulysses_attention_matches_reference():
    from galvatron_tpu.parallel.mesh import build_mesh
    from galvatron_tpu.parallel.ulysses import ulysses_attention

    mesh, axes = build_mesh(pp=1)
    q, k, v = rand_qkv(jax.random.key(6), s=64)  # n=2 heads, cp=2
    cfg = ModelConfig(num_heads=2, hidden_size=64)
    cp_axes = ("x2",)

    out = jax.jit(lambda q, k, v: ulysses_attention(q, k, v, cfg, mesh, cp_axes))(q, k, v)
    ref = ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ulysses_attention_grad_matches_reference():
    from galvatron_tpu.parallel.mesh import build_mesh
    from galvatron_tpu.parallel.ulysses import ulysses_attention

    mesh, axes = build_mesh(pp=1)
    q, k, v = rand_qkv(jax.random.key(7), s=64, b=1, n=4)
    cfg = ModelConfig(num_heads=4, hidden_size=128)
    cp_axes = ("x1", "x2")  # cp=4

    g_u = jax.jit(
        jax.grad(
            lambda q, k, v: (ulysses_attention(q, k, v, cfg, mesh, cp_axes) ** 2).sum(),
            (0, 1, 2),
        )
    )(q, k, v)
    g_ref = jax.grad(lambda q, k, v: (ref_attention(q, k, v) ** 2).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(g_u, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)


def test_ulysses_head_divisibility_error():
    from galvatron_tpu.parallel.mesh import build_mesh
    from galvatron_tpu.parallel.ulysses import ulysses_attention

    mesh, axes = build_mesh(pp=1)
    q, k, v = rand_qkv(jax.random.key(8), s=32, n=2)
    cfg = ModelConfig(num_heads=2, hidden_size=64)
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention(q, k, v, cfg, mesh, ("x0", "x1", "x2"))  # cp=8 > 2 heads


def test_ulysses_layer_in_hybrid_runtime():
    """cp_impl='a2a' layer strategy end-to-end through the runtime."""
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
    from galvatron_tpu.parallel.hybrid import build_runtime
    from tests.test_hybrid_runtime import make_batches, reference_losses
    from tests._train_common import CFG

    hp = HybridParallelConfig(
        pp=1,
        layer_strategies=[LayerStrategy(cp=2, cp_impl="a2a")] * 4,
        vocab_tp=1,
        mixed_precision="fp32",
    )
    rt = build_runtime(CFG, hp, adam=AdamConfig(lr=1e-3), global_batch_size=8, seq_len=32)
    state = rt.init_state(jax.random.key(0))
    batches = make_batches()
    ref = reference_losses(CFG, batches)
    losses = []
    for b in batches:
        state, loss = rt.train_step(state, b)
        losses.append(float(loss))
    np.testing.assert_allclose(losses, ref, rtol=2e-4, atol=2e-4)


def test_flash_non_causal_matches_reference():
    q, k, v = rand_qkv(jax.random.key(9), s=64)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    cfg = ModelConfig(num_heads=2, hidden_size=64, causal=False)
    ref = modeling.attention_xla(q, k, v, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["ring", "a2a"])
def test_cp_composes_with_pipeline_parallelism(impl):
    """cp=2 layers under pp=2 (chunks=2) reproduce the flat single-device
    AdamW trajectory on identical weights — context parallelism composes
    with the pipeline engines, both implementations (the fix that pinned
    the attention-context sharding inside the pipelined stage fns)."""
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
    from galvatron_tpu.parallel.hybrid import build_runtime
    from tests.test_hybrid_runtime import make_batches, reference_losses
    from tests._train_common import ADAM, CFG

    batches = make_batches()
    flat = modeling.init_model_params(jax.random.key(0), CFG)
    ref = reference_losses(CFG, batches)

    hp = HybridParallelConfig(
        pp=2, chunks=2,
        layer_strategies=[LayerStrategy(cp=2, cp_impl=impl)] * 4,
        vocab_tp=1, mixed_precision="fp32",
    )
    rt = build_runtime(CFG, hp, adam=ADAM, global_batch_size=8, seq_len=32)
    st = rt.init_state_from(flat)
    losses = []
    for b in batches:
        st, loss = rt.train_step(st, rt.shard_batch(b))
        losses.append(float(loss))
    np.testing.assert_allclose(losses, ref, rtol=2e-4, atol=2e-4)


def test_flash_gqa_native_matches_repeated():
    """GQA-native kernels (grouped K/V, h -> h//rep index maps) must match
    the repeated-K/V path exactly — forward AND gradients (whose dk/dv are
    the exact group sums), blocked-causal and grid paths."""
    from galvatron_tpu.ops.flash_attention import flash_attention_hm

    b, n, kvh, s, d = 2, 4, 2, 128, 32
    ks = jax.random.split(jax.random.key(21), 3)
    q = jax.random.normal(ks[0], (b, n, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, kvh, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, kvh, s, d), jnp.float32)
    cos, sin = _rope_tables(s, d)

    def rep(x):
        return jnp.broadcast_to(x[:, :, None], (b, kvh, n // kvh, s, d)).reshape(
            b, n, s, d
        )

    for rope in [(cos, sin), None]:  # blocked-causal path / grid path
        def f_native(q, k, v):
            return (flash_attention_hm(q, k, v, causal=True, rope=rope) ** 2).sum()

        def f_rep(q, k, v):
            return (flash_attention_hm(q, rep(k), rep(v), causal=True, rope=rope) ** 2).sum()

        np.testing.assert_allclose(
            float(f_native(q, k, v)), float(f_rep(q, k, v)), rtol=2e-5
        )
        gn = jax.grad(f_native, argnums=(0, 1, 2))(q, k, v)
        # rep() inside f_rep: autodiff through the broadcast group-sums the
        # repeated-path dk/dv, so both sides are grouped (b, kvh, s, d)
        gr = jax.grad(f_rep, argnums=(0, 1, 2))(q, k, v)
        for a, bb in zip(gn, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(bb), rtol=5e-4, atol=5e-4
            )


def test_gqa_flash_tp_exceeding_kv_heads_trains():
    """tp > kv_heads on a GQA flash model: the shard_map shards the head dim
    over tp, so grouped K/V (kv_heads < tp) must be repeated first — the
    guard in _attn_block_headmajor (review regression: the GQA-native change
    initially broke every tp>kv_heads flash config)."""
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
    from galvatron_tpu.parallel.hybrid import build_runtime

    cfg = ModelConfig(
        vocab_size=128, hidden_size=128, num_heads=8, num_kv_heads=2,
        ffn_dim=256, max_seq_len=32, attn_impl="flash",
    )
    hp = HybridParallelConfig(
        layer_strategies=[LayerStrategy(tp=4, dp_type="zero3")] * 2,
        vocab_tp=4, mixed_precision="fp32",
    )
    cfg = cfg.replace(num_layers=2, dtype=jnp.float32)
    rt = build_runtime(cfg, hp, adam=AdamConfig(lr=3e-3), global_batch_size=8, seq_len=32)
    state = rt.init_state(jax.random.key(0))
    batch = jnp.asarray(np.random.RandomState(0).randint(0, 128, (8, 33)), jnp.int32)
    l0 = None
    for _ in range(4):
        state, loss = rt.train_step(state, batch)
        l0 = l0 if l0 is not None else float(loss)
    assert np.isfinite(float(loss)) and float(loss) < l0


def test_decode_attention_matches_full_attention_last_row():
    """q_len==1 decode fast path (ops/flash_attention.decode_attention):
    against the FULL causal attention's last row — same keys, same mask —
    for MHA and GQA head layouts, and against the flash kernel path."""
    from galvatron_tpu.ops.flash_attention import decode_attention

    rng = np.random.RandomState(0)
    for kv_heads in (8, 2):  # MHA / GQA
        b, s, n, d = 2, 32, 8, 16
        q = jnp.asarray(rng.standard_normal((b, s, n, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, s, kv_heads, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, s, kv_heads, d)), jnp.float32)
        cfg = ModelConfig(
            num_heads=n, num_kv_heads=kv_heads, hidden_size=n * d, causal=True
        )
        ref = modeling.attention_xla(q, k, v, cfg)[:, s - 1 : s]
        out = decode_attention(q[:, s - 1 : s], k, v, q_offset=s - 1)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )
    # flash parity at a tileable shape: decode row vs kernel's last row
    q, k, v = rand_qkv(jax.random.key(7), s=64)
    full = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    out = decode_attention(q[:, 63:64], k, v, q_offset=63)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(full[:, 63:64]), rtol=2e-5, atol=2e-5
    )


def test_decode_attention_per_row_offsets_mask_cache_tail():
    """(B,) q_offset: each batch row masks its own cache tail — row b must
    equal attention over only its first offset+1 cache entries (stale slots
    past the write point never leak in: the serving cache contract)."""
    from galvatron_tpu.ops.flash_attention import decode_attention

    rng = np.random.RandomState(1)
    b, s, n, d = 2, 16, 4, 8
    q1 = jnp.asarray(rng.standard_normal((b, 1, n, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, n, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, n, d)), jnp.float32)
    offs = jnp.asarray([4, 11])
    out = decode_attention(q1, k, v, q_offset=offs)
    cfg = ModelConfig(num_heads=n, hidden_size=n * d, causal=True)
    for i, o in enumerate([4, 11]):
        ref = modeling.attention_xla(
            q1[i : i + 1], k[i : i + 1, : o + 1], v[i : i + 1, : o + 1],
            cfg, q_offset=o,
        )
        np.testing.assert_allclose(
            np.asarray(out[i : i + 1]), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


def test_attention_xla_dispatches_decode_path_consistently():
    """attention_xla with q_len==1 routes to decode_attention; the dispatch
    must be value-invisible next to the einsum path it replaces (computed
    here by disabling the causal fast-path conditions one at a time)."""
    rng = np.random.RandomState(2)
    b, s, n, kvh, d = 2, 12, 4, 2, 8
    cfg = ModelConfig(num_heads=n, num_kv_heads=kvh, hidden_size=n * d, causal=True)
    q1 = jnp.asarray(rng.standard_normal((b, 1, n, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)
    fast = modeling.attention_xla(q1, k, v, cfg, q_offset=s - 1)
    # zero bias forces the general einsum path without changing the values
    slow = modeling.attention_xla(
        q1, k, v, cfg, q_offset=s - 1, bias=jnp.zeros((b, n, 1, s), jnp.float32)
    )
    np.testing.assert_allclose(
        np.asarray(fast), np.asarray(slow), rtol=2e-5, atol=2e-5
    )
