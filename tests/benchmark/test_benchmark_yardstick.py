"""The yardstick's arithmetic, checked by hand: trace reduction, FLOP counts,
the plain reference against the program's model at a tiny size, the corpus."""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.lib import corpus, flops, reference, stats, xplane  # noqa: E402
from benchmark.lib.xplane import Op  # noqa: E402


def _config(name):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


# -- trace reduction ---------------------------------------------------------

def _op(start, end, text):
    return Op(start, end, *xplane.parse(text))


#: one device, by hand, with names as the TPU's trace gives them (whole HLO
#: text): a while loop spanning everything, two fusions, an asynchronous
#: all-gather whose flight overlaps the second fusion, a Mosaic kernel, a
#: bitcast custom call of no length, a synchronous all-reduce, and an idle gap
#: of 20 before it
HAND = [
    _op(0, 200, "%while.1 = (s32[], bf16[8,64]{1,0}) while((s32[], bf16[8,64]{1,0}) %tuple.1), "
                "condition=%cond, body=%body"),
    _op(0, 40, "%fusion.1 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(bf16[8,64]{1,0:T(8,128)(2,1)} "
               "%p.1), kind=kLoop, calls=%fused_computation"),
    _op(40, 45, "%all-gather-start.2 = (bf16[2,64]{1,0}, bf16[8,64]{1,0:T(8,128)(2,1)}) "
                "all-gather-start(bf16[2,64]{1,0} %fusion.1), dimensions={0}"),
    _op(45, 95, "%fusion.2 = bf16[8,64]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[8,64]{1,0} %p.2), "
                "kind=kOutput, calls=%fused_computation.1"),
    _op(95, 110, "%all-gather-done.2 = bf16[8,64]{1,0:T(8,128)(2,1)} all-gather-done("
                 "(bf16[2,64]{1,0}, bf16[8,64]{1,0}) %all-gather-start.2)"),
    _op(110, 150, "%jvp__.7 = (bf16[2,4,64,16]{3,2,1,0}, f32[2,4,64,1]{3,2,1,0}) custom-call("
                  "bf16[2,3,4,64,16]{4,3,2,1,0} %fusion.2), custom_call_target=\"tpu_custom_call\""),
    _op(150, 150, "%custom-call.9 = bf16[16,64]{1,0} custom-call(bf16[8,64]{1,0} %a, "
                  "bf16[8,64]{1,0} %b), custom_call_target=\"ConcatBitcast\""),
    _op(170, 200, "%all-reduce.3 = f32[]{:T(128)} all-reduce(f32[]{:T(128)} %fusion.2), "
                  "to_apply=%add"),
]
#: a collective the compiler wrapped in a fusion of its own: known by its name
WRAPPED = _op(200, 210, "%async-collective-done.4 = bf16[8,64]{1,0} fusion(bf16[8,64]{1,0} "
                        "%get-tuple-element.5), kind=kCustom, calls=%fused_computation.9")


def test_parse_hlo_text():
    assert [o.category for o in HAND] == [
        "container", "fusion:kLoop", "collective", "fusion:kOutput", "collective",
        "mosaic-kernel", "custom-call:ConcatBitcast", "collective"]
    assert [o.name for o in HAND][:3] == ["while.1", "fusion.1", "all-gather-start.2"]
    assert HAND[1].shape == "bf16[8,64]" and HAND[5].shape == "bf16[2,4,64,16]"
    assert HAND[4].opcode == "all-gather-done" and HAND[4].operand == "all-gather-start.2"
    assert (WRAPPED.category, WRAPPED.opcode) == ("collective", "fusion")
    assert xplane.base_name("%all-gather-start.12") == "all-gather-start"
    # a plain name (no HLO text) falls back to the name's stem
    assert xplane.parse("%copy.4")[:3] == ("copy.4", "copy", "copy")


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert xplane.length([(0, 2), (1, 3), (5, 8)]) == 6
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == [(0, 2), (3, 5), (7, 9)]
    assert xplane.gaps([(0, 2), (1, 3), (5, 8)]) == [(3, 5)]


def test_busy_exposed_and_sums_by_hand():
    # the container is not work: busy is 0..150 and 170..200
    assert xplane.busy_ns(HAND) == 180
    assert xplane.window_of(HAND) == (0, 200)
    # flights: all-gather 40..110 (start to done), all-reduce 170..200
    assert xplane.collective_flights(HAND) == [(40, 110), (170, 200)]
    # a done whose start is not on the line, and a start that is never done, stand alone
    assert xplane.collective_flights([WRAPPED, HAND[2]]) == [(200, 210), (40, 45)]
    flight, exposed = xplane.collective_ns(HAND)
    # fusion.2 hides 45..95 of the all-gather; 5 + 15 + 30 stay exposed
    assert (flight, exposed) == (100, 50)
    assert xplane.category_sums(HAND) == {
        "fusion:kLoop": 40, "collective": 50, "fusion:kOutput": 50, "mosaic-kernel": 40,
        "custom-call:ConcatBitcast": 0}
    assert xplane.top_ops(HAND, 2) == [("fusion:kOutput:fusion.2 bf16[8,64]", 50 / 1e9),
                                       ("fusion:kLoop:fusion.1 bf16[8,64]", 40 / 1e9)]
    # the one gap, 150..170, lies inside the host's sync span, itself inside a step
    spans = [(0, 400, "step"), (140, 180, "sync"), (180, 190, "data")]
    assert xplane.attribute_gaps(HAND, spans) == [("sync", 20 / 1e9)]
    assert xplane.attribute_gaps(HAND, []) == [("between_steps", 20 / 1e9)]


def test_recorded_trace():
    """Device 0 of one traced step of ``baichuan-7b_s512`` on the chip
    (recorded_ops.json, kept with this test; made by PR 24 from the run's
    ``.xplane.pb``): the parser on the trace's own texts, one per category, and
    the reductions on real names, held to the trace's own ``XLA Modules``
    event of that step, which the reduction never reads."""
    with open(os.path.join(HERE, "recorded_ops.json")) as f:
        rec = json.load(f)
    for text, parsed in rec["texts"]:
        assert list(xplane.parse(text)) == parsed
    ops = [Op(*o) for o in rec["ops"]]
    assert len(ops) == rec["expect"]["n"] == 308
    busy, (w0, w1), (m0, m1) = xplane.busy_ns(ops), xplane.window_of(ops), rec["module"]
    assert busy == pytest.approx(rec["expect"]["busy_ns"]) and [w0, w1] == rec["expect"]["window"]
    # the step's operations fill its module event: 271.82 of 271.83 ms
    assert m0 <= w0 and w1 <= m1 and 0.999 * (m1 - m0) < busy <= m1 - m0
    sums = xplane.category_sums(ops)
    assert sums == pytest.approx(rec["expect"]["category_sums"])
    assert sum(sums.values()) == pytest.approx(busy)  # one core, nothing overlaps
    # the cell's four flash-attention kernels, 7.9 ms; one chip, so no collective
    kernels = [o for o in ops if o.category == "mosaic-kernel"]
    assert len(kernels) == 4 and sums["mosaic-kernel"] == pytest.approx(7.897663e6)
    assert xplane.collective_ns(ops) == (0, 0)
    assert xplane.top_ops(ops, 1)[0][0] == "fusion:kOutput:fusion.16 bf16[16,512,4096]"


def test_load_reads_a_profile_without_device_planes(tmp_path):
    """On the CPU the profile has no TPU plane: the loader says so by returning
    no devices, and every trace-reading metric then leaves itself out."""
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    trace = xplane.load(xplane.find_trace(str(tmp_path)))
    assert trace["devices"] == {} and xplane.first_device(trace) is None
    assert trace["stop_unix_ns"] > trace["start_unix_ns"] > 1.5e18
    assert xplane.find_trace(str(tmp_path / "nothing")) is None


# -- operations and bytes -----------------------------------------------------


def test_flops_by_hand():
    b7, opt = _config("baichuan-7b"), _config("opt-1.3b")
    a7, aopt = reference.load(REPO, "baichuan"), reference.load(REPO, "opt")
    # 2 x (4 x 4096^2 + 3 x 4096 x 11008) + 4096 x 64000
    assert flops.matmul_params(hidden=4096, heads=32, ffn=11008, mlp_matrices=3, layers=2,
                               vocab=64000) == 2 * (67_108_864 + 135_266_304) + 262_144_000
    # 24 x (4 x 2048^2 + 2 x 2048 x 8192) + 2048 x 50272 (tied, the GEMM still runs)
    assert flops.matmul_params(hidden=2048, heads=32, ffn=8192, mlp_matrices=2, layers=24,
                               vocab=50272) == 24 * 50_331_648 + 102_957_056 == 1_310_916_608
    assert flops.attention_pairs(4096) == 4096 * 4097 // 2
    assert flops.attention_pairs(8, causal=False) == 64
    # forward: 2 x params + layers x 4 x (n d) x (s + 1) / 2; times 3 with the backward
    assert a7.fwd_flops_per_token(b7, 4096) == 2 * 666_894_336 + 67_125_248
    assert flops.model_flops_per_token(a7, b7, 4096) == 3 * (2 * 666_894_336 + 67_125_248)
    assert flops.model_flops_per_token(a7, b7, 512) == 3 * (2 * 666_894_336 + 2 * 4 * 4096 * 256.5)
    assert flops.model_flops_per_token(aopt, opt, 2048) == 3 * (2 * 1_310_916_608 + 201_424_896)
    # a step of the four-chip cell: the issue's 2.78e14
    assert flops.model_flops_per_token(aopt, opt, 2048) * 16 * 2048 == pytest.approx(
        2.7754e14, rel=1e-4)
    # kernel: 7 GEMMs of 2 d per pair and head; 12 tensors of b n s d bf16
    assert flops.flash_attention_flops(2, 32, 4096, 128, 2) == 14 * 128 * 32 * 2 * 2 * 8_390_656
    assert flops.flash_attention_bytes(2, 32, 4096, 128, 2) == 12 * 2 * 32 * 4096 * 128 * 2 * 2
    with pytest.raises(ValueError, match="no plain reference"):
        reference.load(REPO, "no-such-architecture")


def test_percentiles():
    xs = list(range(1, 102))  # 1..101
    assert stats.percentile(xs, 50) == 51 and stats.percentile(xs, 90) == 91
    assert stats.percentile([3.0], 90) == 3.0
    assert stats.honest_tail(list(range(10))) is None
    q, v = stats.honest_tail(list(range(31)))  # 10 samples beyond index 20
    assert (q, v) == (pytest.approx(100 * 20 / 30), pytest.approx(20))


# -- corpus --------------------------------------------------------------------


def test_corpus_is_seeded_skewed_and_markov():
    a = corpus.make_tokens(7, 50_000, 1000, zipf_a=1.0, follow_p=0.5)
    assert np.array_equal(a, corpus.make_tokens(7, 50_000, 1000, zipf_a=1.0, follow_p=0.5))
    assert not np.array_equal(a, corpus.make_tokens(8, 50_000, 1000, zipf_a=1.0, follow_p=0.5))
    assert a.dtype == np.int32 and a.min() >= 0 and a.max() < 1000
    follows = np.mean(a[1:] == (a[:-1] + 1) % 1000)
    assert 0.48 < follows < 0.53  # the bias, plus chance hits
    counts = np.sort(np.bincount(a, minlength=1000))[::-1]
    assert counts[:10].sum() > 5 * counts[-500:].sum() / 50  # heavy head
    rows = corpus.windows(a, 64, 3)
    assert rows.shape == (3, 65) and np.array_equal(rows[1], a[64:129])
    assert sum(len(d) for d in corpus.documents(a, 300)) == len(a)


# -- the plain reference against the program's model --------------------------

TINY = {
    "baichuan": ({"model_type": "baichuan", "hidden_size": 64, "intermediate_size": 96,
                  "num_attention_heads": 4, "num_hidden_layers": 2, "rms_norm_eps": 1e-5,
                  "rope_theta": 10000.0, "vocab_size": 128},
                 dict(ffn_dim=96)),
    "opt": ({"model_type": "opt", "hidden_size": 64, "ffn_dim": 96, "num_attention_heads": 4,
             "num_hidden_layers": 2, "vocab_size": 128},
            dict(ffn_dim=96, use_bias=True, pos_embed="learned", norm_type="layernorm",
                 act_fn="relu", tie_word_embeddings=True)),
}


@pytest.mark.parametrize("kind", sorted(TINY))
def test_reference_agrees_with_the_program(kind):
    """Same weights (every bias and scale moved off its initial 0 or 1), same
    rows, float32 on both sides: the program's ``lm_loss`` and the reference,
    which shares no code with it, agree to float32 rounding."""
    from galvatron_tpu.models import modeling

    cfg_dict, kw = TINY[kind]
    cfg = modeling.ModelConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                               max_seq_len=32, dtype=jnp.float32, **kw)
    params = modeling.init_model_params(jax.random.key(0), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    params = jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype) for x, k in zip(leaves, keys)])
    rows = np.asarray(jax.random.randint(jax.random.key(2), (3, 33), 0, 128))
    with jax.default_matmul_precision("highest"):
        want = float(modeling.lm_loss(params, jnp.asarray(rows), cfg))
    got = reference.lm_loss(reference.load(REPO, kind), params, rows, cfg_dict, rows_per_call=2)
    assert math.isfinite(got) and got == pytest.approx(want, rel=2e-6)
