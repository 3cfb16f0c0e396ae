"""Quantized serving (per-channel int8) + speculative decoding: quantizer
units, the measured parity gates at modeling and engine level, speculative
greedy bit-parity vs ``generate_np`` (incl. mid-window rejection and the
cache-tail headroom fallback), the declared-program-set pins (recompile
guard + AOT enumeration + key separation), fleet numerics consistency,
metric exposition, and the DESIGN/README doc sync."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.models import generation
from galvatron_tpu.ops import quant
from galvatron_tpu.ops.quant import (
    QuantParityError,
    QuantTensor,
    quantize_int8,
    quantize_params,
)
from galvatron_tpu.serving import Engine, PromptLookupDrafter, make_drafter
from galvatron_tpu.serving.engine import (
    _decode_step,
    _decode_verify,
    _prefill_chunk,
)

from tests._serving_common import CFG, params, prompts as _prompts  # noqa: F401  (`params`: a fixture)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _repetitive_prompts(n, period=3, length=12):
    """The shape prompt-lookup drafting exists for: a repeating n-gram, so
    the drafter's suffix match finds an earlier occurrence immediately."""
    return [[2 + (j % period) + i for j in range(length)] for i in range(n)]


# ---------------------------------------------------------------------------
# quantizer units
# ---------------------------------------------------------------------------


def test_quantize_int8_scale_shape_dtype_and_roundtrip():
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(32, 24), jnp.float32)
    qt = quantize_int8(w)
    assert qt.q.dtype == jnp.int8 and qt.q.shape == (32, 24)
    assert qt.scale.dtype == jnp.float32 and qt.scale.shape == (24,)
    assert int(jnp.max(jnp.abs(qt.q))) <= 127
    # rounding error is bounded by half a quantization step per channel
    err = np.abs(np.asarray(qt.dequantize()) - np.asarray(w))
    bound = np.asarray(qt.scale) / 2 + 1e-6
    assert np.all(err <= bound[None, :])
    # the QuantTensor impersonation contract the modeling seams rely on
    assert qt.shape == w.shape and qt.ndim == 2 and qt.astype(jnp.bfloat16) is qt


def test_quantize_int8_blocked_wqkv_scale_shape():
    """The blocked wqkv is (h, 3, n*hd): every trailing dim is an output
    channel, so the scale is (3, n*hd) — one per (proj, channel) pair."""
    w = jnp.asarray(np.random.RandomState(1).randn(64, 3, 48), jnp.float32)
    qt = quantize_int8(w)
    assert qt.scale.shape == (3, 48)
    err = np.abs(np.asarray(qt.dequantize()) - np.asarray(w))
    assert np.all(err <= np.asarray(qt.scale)[None] / 2 + 1e-6)


def test_quantize_int8_zero_channel_no_nan():
    w = np.random.RandomState(2).randn(16, 8).astype(np.float32)
    w[:, 3] = 0.0  # an all-zero output channel: scale would be 0
    qt = quantize_int8(jnp.asarray(w))
    assert float(qt.scale[3]) == 0.0
    deq = np.asarray(qt.dequantize())
    assert np.all(np.isfinite(deq)) and np.all(deq[:, 3] == 0.0)
    # and through the matmul: exact zeros, not NaN
    y = np.asarray(quant.qmatmul(jnp.ones((2, 16), jnp.float32), qt))
    assert np.all(np.isfinite(y)) and np.all(y[:, 3] == 0.0)


def test_qeinsum_rejects_non_trailing_output_axes():
    qt = quantize_int8(jnp.ones((8, 4), jnp.float32))
    with pytest.raises(ValueError, match="trailing"):
        quant.qeinsum("ab,bc->ca", jnp.ones((2, 8), jnp.float32), qt)


def test_quantize_params_targets_gemms_only(params):
    qp = quantize_params(params, CFG)
    for lp in qp["layers"]:
        assert isinstance(lp["attn"]["wqkv"], QuantTensor)
        assert isinstance(lp["attn"]["wo"], QuantTensor)
        assert isinstance(lp["mlp"]["w13"], QuantTensor)
        assert isinstance(lp["mlp"]["w2"], QuantTensor)
        # norms and biases stay fp
        for k, v in lp.items():
            if k not in ("attn", "mlp", "cross"):
                for leaf in jax.tree_util.tree_leaves(v):
                    assert not isinstance(leaf, QuantTensor)
    # embedding table is a gather — never quantized
    for leaf in jax.tree_util.tree_leaves(qp["embed"]):
        assert not isinstance(leaf, QuantTensor)
    frac = quant.quantized_fraction(qp)
    assert 0.0 < frac < 1.0
    # works under eval_shape (the AOT key derivation path)
    abs_q = jax.eval_shape(lambda p: quantize_params(p, CFG), params)
    lq = abs_q["layers"][0]["attn"]["wqkv"]
    assert lq.q.dtype == jnp.int8 and lq.scale.dtype == jnp.float32


# ---------------------------------------------------------------------------
# parity gates: modeling level, then engine level
# ---------------------------------------------------------------------------


def test_parity_report_measures_and_gates(params):
    qp = quantize_params(params, CFG)
    rep = quant.parity_report(params, qp, CFG, drift_max=10.0)
    assert rep["max_abs_logit_drift"] < 10.0
    assert 0.0 <= rep["greedy_agree_frac"] <= 1.0
    assert rep["drift_bound"] == 10.0 and rep["probe_positions"] >= 1
    with pytest.raises(QuantParityError, match="drift"):
        quant.parity_report(params, qp, CFG, drift_max=1e-12)


def test_engine_int8_gate_and_stats(params):
    with pytest.raises(QuantParityError):
        Engine(params, CFG, num_slots=1, serve_quant="int8",
               quant_drift_max=1e-12, start_loop=False).close()
    with pytest.raises(ValueError, match="serve_quant"):
        Engine(params, CFG, num_slots=1, serve_quant="int4",
               start_loop=False)
    with Engine(params, CFG, num_slots=2, serve_quant="int8",
                quant_drift_max=10.0) as eng:
        st = eng.stats()
        assert st["serve_quant"] == "int8"
        assert st["quant_parity"]["max_abs_logit_drift"] <= 10.0
        # engine-level drift gate held end-to-end: greedy through the
        # quantized engine stays within the probe's measured behavior —
        # generation completes and the output is deterministic
        prompts = _prompts(3, seed=5)
        out1 = eng.generate(prompts, max_new_tokens=5)
        out2 = eng.generate(prompts, max_new_tokens=5)
    assert out1 == out2


# ---------------------------------------------------------------------------
# the drafter
# ---------------------------------------------------------------------------


def test_prompt_lookup_drafter_basics():
    d = PromptLookupDrafter(ngram_max=3, ngram_min=1)
    # suffix [5,6] last occurred earlier, followed by 7, 8
    assert d.draft([5, 6, 7, 8, 5, 6], 2) == [7, 8]
    # longest-suffix-first: the trigram match wins over a shorter one
    toks = [1, 2, 3, 9, 1, 2, 3]
    assert d.draft(toks, 1) == [9]
    # no earlier occurrence → no draft
    assert d.draft([1, 2, 3, 4], 3) == []
    # k bounds the proposal even when more context follows the match
    assert len(d.draft([4, 5, 6, 7, 8, 4, 5], 1)) <= 1
    assert make_drafter("prompt_lookup").name == "prompt_lookup"
    with pytest.raises(ValueError):
        make_drafter("nonexistent")


# ---------------------------------------------------------------------------
# speculative decoding: greedy bit-parity
# ---------------------------------------------------------------------------


def test_spec_greedy_matches_generate_np(params):
    """The exactness contract: greedy speculative output is bit-identical
    to the single-shot path, on drafter-friendly (repetitive) AND
    drafter-hostile (random) prompts, with slot reuse."""
    prompts = _repetitive_prompts(3) + _prompts(3, seed=7)
    ref = generation.generate_np(params, CFG, prompts, max_new_tokens=10)
    with Engine(params, CFG, num_slots=2, prefill_chunk=4,
                spec_decode_k=3) as eng:
        out = eng.generate(prompts, max_new_tokens=10)
        st = eng.stats()
    assert out == ref
    assert st["spec_decode_k"] == 3 and st["spec_drafter"] == "prompt_lookup"
    assert st["draft_proposed"] > 0  # the spec path actually ran


def test_spec_accepts_on_repetitive_prompts(params):
    """On self-repeating traffic the drafter must actually pay: accepted
    drafts > 0 and the acceptance accounting is internally consistent."""
    prompts = _repetitive_prompts(2, period=2, length=16)
    with Engine(params, CFG, num_slots=2, prefill_chunk=8,
                spec_decode_k=4) as eng:
        out = eng.generate(prompts, max_new_tokens=12)
        st = eng.stats()
    assert out == generation.generate_np(params, CFG, prompts,
                                         max_new_tokens=12)
    assert st["draft_accepted"] > 0
    assert st["draft_accepted"] <= st["draft_proposed"]
    assert st["draft_acceptance_rate"] == pytest.approx(
        st["draft_accepted"] / st["draft_proposed"], abs=1e-3)
    assert st["spec_steps"] > 0


class _OracleDrafter:
    """Deterministic drafter for forcing acceptance/rejection patterns:
    drafts the reference continuation for ``good`` positions then a
    guaranteed-wrong token, so a k>1 window rejects mid-window."""

    name = "oracle"

    def __init__(self, refs, good=1):
        self.refs = {tuple(r[:i]): r[i] for r in refs for i in range(len(r))}
        self.good = good

    def draft(self, tokens, k):
        out = []
        cur = list(tokens)
        for j in range(k):
            nxt = self.refs.get(tuple(cur))
            if nxt is None:
                break
            if j >= self.good:
                nxt = (nxt + 1) % CFG.vocab_size  # wrong on purpose
            out.append(nxt)
            cur.append(nxt)
        return out


def test_spec_mid_window_rejection_still_bit_exact(params):
    """k=3 drafts whose position-1 token is deliberately wrong: the verify
    step must accept position 0, reject position 1, resample from the
    residual — and the final output still bit-matches generate_np."""
    prompts = _prompts(2, seed=11)
    n_new = 8
    ref = generation.generate_np(params, CFG, prompts, max_new_tokens=n_new)
    eng = Engine(params, CFG, num_slots=2, prefill_chunk=8,
                 spec_decode_k=3, start_loop=False)
    eng.drafter = _OracleDrafter(ref, good=1)
    futs = [eng.submit(p, n_new) for p in prompts]
    for _ in range(200):
        if all(f.done() for f in futs):
            break
        eng.step_once()
    out = [f.result(timeout=1) for f in futs]
    st = eng.stats()
    eng.close()
    assert out == ref
    # every window proposed ≥ 2 tokens and rejected at position 1
    assert 0 < st["draft_accepted"] < st["draft_proposed"]


def test_spec_headroom_fallback_near_cache_tail(params):
    """A row within k tokens of the cache end must fall back to plain
    decode (dynamic_update_slice clamps out-of-range starts — a silently
    misplaced verify window would corrupt the KV): the fallback counter
    moves and the output still bit-matches."""
    smax = 16
    prompt = _prompts(1, lo=8, hi=9, seed=13)[0]  # len 8
    n_new = smax - len(prompt)  # decode to the very last position
    ref = generation.generate_np(params, CFG, [prompt], max_new_tokens=n_new)
    with Engine(params, CFG, num_slots=1, prefill_chunk=8, max_seq_len=smax,
                spec_decode_k=8) as eng:
        out = eng.generate([prompt], max_new_tokens=n_new)
        st = eng.stats()
    assert out == ref
    # off+1+k > smax from the first decode step on: every iteration fell back
    assert st["spec_fallbacks"] > 0 and st["draft_proposed"] == 0


def test_spec_with_paged_backend_and_int8(params):
    """Paged KV × speculative × int8: the full stack still produces
    deterministic greedy output equal to the identically-quantized
    non-speculative engine (spec is never a numerics change)."""
    prompts = _repetitive_prompts(2) + _prompts(2, seed=17)
    kw = dict(num_slots=2, prefill_chunk=8, serve_quant="int8",
              quant_drift_max=10.0)
    with Engine(params, CFG, kv_num_blocks=-1, kv_block_size=8,
                spec_decode_k=3, **kw) as eng:
        out_spec = eng.generate(prompts, max_new_tokens=8)
        st = eng.stats()
    with Engine(params, CFG, **kw) as eng:
        out_plain = eng.generate(prompts, max_new_tokens=8)
    assert out_spec == out_plain
    assert st["draft_proposed"] > 0
    assert st["kv_blocks_total"] > 0  # really the paged backend


# ---------------------------------------------------------------------------
# declared program set: recompile guard, AOT enumeration, key separation
# ---------------------------------------------------------------------------


def test_recompile_guard_pins_declared_set_with_spec(params):
    """After warmup, mixed traffic through a speculative engine compiles
    NOTHING new: prefill + decode + decode_verify is the whole set."""
    from galvatron_tpu.analysis import recompile_guard

    with Engine(params, CFG, num_slots=2, prefill_chunk=4,
                spec_decode_k=3) as eng:
        # warm all three programs (repetitive prompts force verify steps;
        # random ones keep the plain-decode path warm too)
        eng.generate(_repetitive_prompts(2) + _prompts(2, seed=19),
                     max_new_tokens=6)
        with recompile_guard(_prefill_chunk, _decode_step, _decode_verify,
                             label="spec traffic mix"):
            eng.generate(_repetitive_prompts(3, period=2)
                         + _prompts(3, seed=23), max_new_tokens=8)
        eng.assert_cache_bounded()


def test_aot_enumerates_verify_program_per_backend():
    from galvatron_tpu.aot import registry as aot_registry

    base = dict(cfg=CFG, num_slots=2, prefill_chunk=4)
    names = {s.name for s in aot_registry.enumerate_programs(
        aot_registry.ProgramContext(**base, spec_decode_k=3),
        include=("serving",))}
    assert names == {"serving_prefill", "serving_decode",
                     "serving_decode_verify"}
    paged = {s.name for s in aot_registry.enumerate_programs(
        aot_registry.ProgramContext(**base, spec_decode_k=3,
                                    kv_num_blocks=-1),
        include=("serving",))}
    assert paged == {"serving_paged_prefill", "serving_paged_decode",
                     "serving_paged_decode_verify"}
    # spec off → the two forwards and the device's draw (a speculating engine
    # draws on the host and declares no sampler)
    off = {s.name for s in aot_registry.enumerate_programs(
        aot_registry.ProgramContext(**base), include=("serving",))}
    assert off == {"serving_prefill", "serving_decode", "serving_sample"}
    # the verify program's token aval carries k: (num_slots, 1+k)
    spec = next(s for s in aot_registry.enumerate_programs(
        aot_registry.ProgramContext(**base, spec_decode_k=3),
        include=("serving_decode_verify",)))
    tok_aval = spec.args[3]
    assert tuple(tok_aval.shape) == (2, 4)


def test_int8_changes_every_serving_program_key():
    from galvatron_tpu.aot import cache as aot_cache
    from galvatron_tpu.aot import registry as aot_registry

    def keys(serve_quant):
        ctx = aot_registry.ProgramContext(
            cfg=CFG, num_slots=2, prefill_chunk=4, serve_quant=serve_quant)
        out = {}
        for s in aot_registry.enumerate_programs(ctx, include=("serving",)):
            out[s.name] = aot_cache.program_key(
                s.name, model_cfg=s.meta.get("exec_cfg", CFG),
                abstract_args=s.args, abstract_kwargs=s.kwargs,
                donate=s.meta.get("donate"), extra=s.meta.get("key_extra"),
            )
        return out

    fp, q = keys("off"), keys("int8")
    assert fp.keys() == q.keys()
    for name in fp:
        assert fp[name] != q[name], f"{name}: int8 must change the key"


def test_warmup_plan_compiles_verify_and_quant_programs(tmp_path):
    """`cli warmup --serve_quant int8 --spec_decode_k k` sweeps the
    extended declared set — the artifacts a quantized speculative engine
    warm-starts from."""
    from galvatron_tpu.aot import warmup as aot_warmup
    from galvatron_tpu.aot.cache import ArtifactStore

    store = ArtifactStore(str(tmp_path / "aot"))
    reports = aot_warmup.warmup_plan(
        CFG, None, global_bsz=1, store=store, include=("serving",),
        num_slots=2, prefill_chunk=4, serve_quant="int8", spec_decode_k=2,
        verbose=False,
    )
    by_name = {r["program"]: r for r in reports}
    assert set(by_name) == {"serving_prefill", "serving_decode",
                            "serving_decode_verify"}
    assert all(r["status"] == "compiled" for r in by_name.values()), by_name


# ---------------------------------------------------------------------------
# fleet numerics consistency
# ---------------------------------------------------------------------------


def _stub_fleet(tmp_path, configs):
    from galvatron_tpu.serving.fleet import FleetRouter

    router = FleetRouter([], replicas=len(configs),
                         fleet_dir=str(tmp_path / "fleet"))
    for r, c in zip(router.replicas, configs):
        r.last_health = {"serving": c}
    return router


def test_fleet_health_flags_numerics_mismatch(tmp_path):
    mixed = _stub_fleet(tmp_path, [
        {"serve_quant": "int8", "spec_decode_k": 3,
         "spec_drafter": "prompt_lookup"},
        {"serve_quant": "off", "spec_decode_k": 0, "spec_drafter": None},
    ])
    h = mixed.health()
    assert h["numerics"]["consistent"] is False
    assert "numerics_config_mismatch" in h["degraded_reasons"]

    same = _stub_fleet(tmp_path, [
        {"serve_quant": "int8", "spec_decode_k": 2,
         "spec_drafter": "prompt_lookup"},
        {"serve_quant": "int8", "spec_decode_k": 2,
         "spec_drafter": "prompt_lookup"},
    ])
    h = same.health()
    assert h["numerics"]["consistent"] is True
    assert "numerics_config_mismatch" not in h.get("degraded_reasons", [])
    # replicas that predate the config advertisement simply don't vote
    legacy = _stub_fleet(tmp_path, [{"queue_depth": 0}, {"queue_depth": 1}])
    assert "numerics" not in legacy.health()


# ---------------------------------------------------------------------------
# metric exposition
# ---------------------------------------------------------------------------


def test_metrics_exposition_carries_spec_quant_families(params):
    from galvatron_tpu.models.tokenizer import ByteTokenizer
    from galvatron_tpu.obs.aggregate import exposition_lint
    from galvatron_tpu.obs.prom import server_metrics_text
    from galvatron_tpu.server import GenerationService

    with Engine(params, CFG, num_slots=2, prefill_chunk=8,
                serve_quant="int8", quant_drift_max=10.0,
                spec_decode_k=3) as eng:
        eng.generate(_repetitive_prompts(2), max_new_tokens=6)
        svc = GenerationService(params, CFG, ByteTokenizer(), engine=eng)
        text = server_metrics_text(svc)
    assert exposition_lint(text) == []
    for fam in ("galvatron_serving_draft_proposed_total",
                "galvatron_serving_draft_accepted_total",
                "galvatron_serving_spec_steps_total",
                "galvatron_serving_spec_fallbacks_total",
                "galvatron_serving_accepted_tokens_per_step",
                "galvatron_serving_draft_acceptance_rate",
                "galvatron_serving_decode_step_hist_seconds_bucket",
                "galvatron_serving_numerics_info",
                "galvatron_serving_quant_max_abs_logit_drift",
                "galvatron_serving_quant_greedy_agree_frac"):
        assert fam in text, fam
    assert 'serve_quant="int8"' in text


def test_fleet_metrics_roll_up_spec_families(tmp_path):
    from galvatron_tpu.obs.aggregate import exposition_lint
    from galvatron_tpu.obs.prom import fleet_metrics_text

    snap = {"buckets": {"0.005": 3, "0.05": 5, "+Inf": 5},
            "sum": 0.04, "count": 5}
    router = _stub_fleet(tmp_path, [
        {"serve_quant": "off", "spec_decode_k": 2,
         "spec_drafter": "prompt_lookup", "draft_proposed": 10,
         "draft_accepted": 7, "spec_steps": 4, "spec_fallbacks": 1,
         "accepted_tokens_per_step": 2.1, "draft_acceptance_rate": 0.7,
         "decode_step_hist": snap},
        {"serve_quant": "off", "spec_decode_k": 2,
         "spec_drafter": "prompt_lookup", "draft_proposed": 6,
         "draft_accepted": 3, "spec_steps": 2, "spec_fallbacks": 0,
         "accepted_tokens_per_step": 1.5, "draft_acceptance_rate": 0.5,
         "decode_step_hist": snap},
    ])
    text = fleet_metrics_text(router)
    assert exposition_lint(text) == []
    # per-replica labeled counters + the unlabeled fleet sum
    assert 'galvatron_fleet_serving_draft_proposed_total{replica="0"} 10' in text
    assert "galvatron_fleet_serving_draft_proposed_sum_total 16" in text
    assert "galvatron_fleet_serving_draft_accepted_sum_total 10" in text
    # rate gauges are per-replica ONLY (a summed rate is meaningless)
    assert 'galvatron_fleet_serving_accepted_tokens_per_step{replica="0"}' in text
    assert "galvatron_fleet_serving_accepted_tokens_per_step_sum" not in text
    # decode-step histogram merges like ttft: per-replica rows + fleet merge
    assert 'galvatron_fleet_decode_step_hist_seconds_bucket{replica="0",le="0.005"} 3' in text
    assert 'galvatron_fleet_decode_step_hist_seconds_fleet_bucket{le="0.005"} 6' in text


# ---------------------------------------------------------------------------
# doc sync
# ---------------------------------------------------------------------------


def test_design_doc_quant_spec_sections_in_sync():
    text = open(os.path.join(REPO, "docs", "DESIGN.md")).read()
    mq = re.search(r"## Quantized serving\n(.*?)\n## ", text, re.S)
    assert mq, "DESIGN.md has no '## Quantized serving' section"
    for term in ("--serve_quant", "per-channel", "absmax",
                 "--quant_drift_max", "QuantParityError", "fp32"):
        assert term in mq.group(1), f"quant section missing {term!r}"
    ms = re.search(r"## Speculative decoding\n(.*?)\n## ", text, re.S)
    assert ms, "DESIGN.md has no '## Speculative decoding' section"
    for term in ("--spec_decode_k", "decode_verify", "rejection sampling",
                 "bit-identical", "prompt-lookup", "spec_fallbacks"):
        assert term in ms.group(1), f"spec section missing {term!r}"


def test_readme_documents_quant_spec_flags():
    text = open(os.path.join(REPO, "README.md")).read()
    for flag in ("--serve_quant", "--quant_drift_max", "--spec_decode_k",
                 "--spec_drafter"):
        assert re.search(rf"\| `{flag}[ A-Z]*`", text), \
            f"README flag table missing {flag}"


def test_cli_serve_and_warmup_parsers_carry_quant_spec_flags():
    """The serve flags must exist on `warmup` too (program-key terms): a
    warmup that can't see them sweeps the wrong keys."""
    from galvatron_tpu.core.arguments import build_parser

    serve = build_parser("serve").parse_args(["--serve_quant", "int8",
                                              "--spec_decode_k", "3"])
    assert serve.serve_quant == "int8" and serve.spec_decode_k == 3
    assert serve.quant_drift_max == pytest.approx(1.0)
    assert serve.spec_drafter == "prompt_lookup"
    warm = build_parser("warmup").parse_args(["--serve_quant", "int8",
                                              "--spec_decode_k", "3"])
    assert warm.serve_quant == "int8" and warm.spec_decode_k == 3
