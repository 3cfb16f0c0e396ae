"""afmoe-class stacks (Trinity-Large-Preview: gated GQA attention under sandwich norms,
rotary on the sliding-window layers' RING and no position signal on the full layers'
whole slots, the embedding scaled by sqrt(hidden), a biased sigmoid router over experts
of which this copy holds a share beside an ungated shared expert, behind a dense layer) on
the normal path, against the plain reference ``benchmark/references/afmoe.py`` on seeded
random weights, at a small size on the CPU: the full forward; chunked prefill then decoding
through the ring and the whole slots, the ring lapped DURING decode; the engine's tap
rows; the shares of the experts adding up to the uncut layer; each planted fault caught;
the parameter count of the benchmark's cut from shapes."""

import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference
from galvatron_tpu.core.optim import AdamConfig
from galvatron_tpu.core.strategy import HybridParallelConfig
from galvatron_tpu.models import generation, modeling, moe
from galvatron_tpu.models.modeling import PRESETS
from galvatron_tpu.parallel.hybrid import build_runtime
from tests import _stack_harness as harness
from tests._stack_harness import (  # noqa: F401  (`retraced`: a fixture)
    close, forward, retraced, seeded, through_the_cache, worst)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = reference.load(ROOT, "afmoe")

# float32, the same arithmetic in another order (the program sorts the pairs and runs
# grouped GEMMs, attends over a ring or a block of keys at a time with a running softmax;
# the reference loops over key/value heads and query blocks)
F32_TOL = 5e-5
WINDOW, CHUNK, SLOT = 16, 4, 64


def small_cfg(**kw):
    """The cell's layer pattern at small widths: 1 dense layer + one period (S S S F S),
    window 16, 8 experts top-2, all held."""
    base = dict(vocab_size=96, hidden_size=32, num_layers=5, num_heads=4, num_kv_heads=2,
                attn_head_dim=8, ffn_dim=48, max_seq_len=SLOT, sliding_window_size=WINDOW,
                moe_experts=8, moe_top_k=2, moe_ffn_dim=24, moe_shared_ffn_dim=24,
                moe_dense_layers=1, embedding_multiplier=32 ** 0.5, dtype=jnp.float32)
    base.update(kw)
    return PRESETS["trinity-large-preview"].replace(**base)


def ref_cfg(cfg, share=None):
    rank, of = share or cfg.moe_share
    return {"hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "num_hidden_layers": cfg.num_layers, "num_dense_layers": cfg.moe_dense_layers,
            "intermediate_size": cfg.ffn, "moe_intermediate_size": cfg.expert_ffn,
            "sliding_window": cfg.sliding_window_size, "mup_enabled": True,
            "layer_types": ["sliding_attention" if w else "full_attention"
                            for w in cfg.sliding_window_layout],
            "num_experts": cfg.moe_experts // of, "num_experts_per_tok": cfg.moe_top_k,
            "num_shared_experts": 1, "route_norm": True, "route_scale": cfg.moe_route_scale,
            "vocab_size": cfg.vocab_size, "expert_share": {"rank": rank, "of": of},
            "program_flags": ["--seq_length", str(cfg.max_seq_len)]}


def held_by(params, cfg, share):
    """``params`` as rank ``share[0]`` of ``share[1]`` holds them: its experts' stacks."""
    rank, of = share
    n = cfg.moe_experts // of
    layers = [dict(lp, mlp=dict(lp["mlp"], **{k: lp["mlp"][k][rank * n:(rank + 1) * n]
                                               for k in ("w1", "w2", "w3")}))
              if "router" in lp["mlp"] else lp for lp in params["layers"]]
    return dict(params, layers=layers)


def ref_logits(params, rows, cfg, share=None):
    return harness.reference(ARCH, ref_cfg, cfg, share).logits(params, jnp.asarray(rows))


# -- the configuration ------------------------------------------------------------------


def test_preset_runs_the_published_widths():
    cfg = PRESETS["trinity-large-preview"]
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (
        3072, 60, 48, 8, 128)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.expert_ffn, cfg.ffn, cfg.moe_shared_ffn_dim,
            cfg.moe_dense_layers) == (256, 4, 3072, 12288, 3072, 6)
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.sliding_window_size) == (200192, 262144, 4096)
    assert cfg.sliding_window_layout == cfg.rope_layout == (1, 1, 1, 0) * 15
    assert sum(cfg.window_layers) == 45 and all(
        not w for i, w in enumerate(cfg.window_layers) if (i + 1) % 4 == 0)
    assert cfg.moe_router == "sigmoid_topk" and cfg.moe_route_scale == 2.448 and cfg.moe_norm_topk
    assert not cfg.moe_shared_gate and not cfg.tie_word_embeddings
    assert cfg.attn_gate and cfg.post_norms and cfg.qk_norm and cfg.qk_norm_per_head
    assert not cfg.norm_zero_centered and cfg.rotary_dim == 128  # plain * w, the whole head
    assert cfg.embedding_multiplier == 3072 ** 0.5 and (cfg.rope_theta, cfg.norm_eps) == (1e4, 1e-5)
    cut = cfg.replace(num_layers=5, moe_dense_layers=1)
    assert cut.window_layers == (True, True, True, False, True)
    assert (cut.layer_view(2).attn_window, cut.layer_view(2).pos_embed) == (4096, "rope")
    assert (cut.layer_view(3).attn_window, cut.layer_view(3).pos_embed) == (0, "nope")
    assert generation.layer_stacks(cut) == [("window", 0), ("window", 1), ("window", 2),
                                            ("full", 0), ("window", 3)]


def _cell_cfg():
    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args

    with open(os.path.join(ROOT, "benchmark", "configs", "trinity-large-preview.json")) as f:
        config = json.load(f)
    ns = initialize_galvatron("serve", [*config["program_flags"], "--num_slots", "32",
                                        "--prefill_chunk", "1024"])
    return model_config_from_args(ns), config


def test_parameter_counts_are_the_issues_arithmetic():
    """The cut's parameters from shapes, nothing allocated: the issue's numbers part by
    part, `theoretical.layer_param_count` the same, and the reference's served counts
    within them."""
    from galvatron_tpu.search import theoretical as th

    cfg, config = _cell_cfg()
    assert (cfg.num_layers, cfg.moe_dense_layers, cfg.vocab_size, cfg.moe_share, cfg.moe_held,
            cfg.max_seq_len, cfg.param_dtype) == (5, 1, 25024, (0, 8), 32, 16384, jnp.bfloat16)
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    size = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa: E731
    dense, expert = shapes["layers"][0], shapes["layers"][1]
    assert size(dense["attn"]) == size(expert["attn"]) == 3072 * 6144 * 3 + 3072 * 1024 * 2 + 256
    assert sum(size(dense[k]) for k in modeling._layer_norms(cfg)) == 12288
    assert size(dense["mlp"]) == 113_246_208 and size(dense) == 176_173_312
    mlp = expert["mlp"]
    assert size([mlp["w1"], mlp["w2"], mlp["w3"]]) == 32 * 28_311_552 == 905_969_664
    assert size(mlp["shared"]) == 28_311_552 and size(mlp["router"]) == 786_688
    assert size(expert) == 997_995_008
    assert size(shapes["embed"]) == size(shapes["head"]) == 76_873_728
    assert size(shapes) == 4_321_903_872
    assert th.layer_param_count(cfg) == size(expert) and th.other_param_count(cfg) == (
        2 * 76_873_728 + 3072)
    served = ARCH.served_params(config)
    # a forward reads at least the top-4 of a layer's 32 held experts, never fewer weights
    assert served["a_forward"] == size(shapes) - size(shapes["embed"]) - 4 * 28 * 28_311_552
    assert served["a_token"] == 3072 and ARCH.expert_layers(config) == 4
    assert ARCH.expert_step_bytes(config, 12.5) == 2 * 12.5 * 4 * 28_311_552
    # the slot cache of the cell: one full layer whole slots, four rings of 5,120
    layout = generation.cache_layout(cfg, 16384, 1024)
    assert (layout["bytes_per_position_per_layer"], layout["ring_positions"]) == (4096, 5120)
    assert 32 * layout["bytes_per_slot"] == 32 * 4096 * (16384 + 4 * 5120) == 4_831_838_208
    # the least K and V of a live position by `serve_dims` never passes the exact least
    dims = ARCH.serve_dims(config)
    per = 2 * dims["layers"] * dims["kv_heads"] * dims["head_dim"] * 2
    assert all(per <= ARCH.least_bytes_per_position(config, n) + 1e-9
               for n in (1, 4096, 4097, 9000, 16384))


def test_cli_serve_parses_the_cells_flags():
    cfg, config = _cell_cfg()
    assert config["expert_share"] == {"rank": cfg.moe_share[0], "of": cfg.moe_share[1]}
    assert config["num_experts"] == cfg.moe_held
    assert config["published"]["num_experts"] == cfg.moe_experts
    assert [t == "sliding_attention" for t in config["layer_types"][:5]] == list(cfg.window_layers)
    assert ARCH.slot_positions(config) == cfg.max_seq_len == 16384


# -- the full forward -------------------------------------------------------------------


@pytest.mark.parametrize("share", [(0, 1), (1, 2)])
def test_no_cache_forward_matches_the_reference(share):
    cfg = small_cfg(moe_share=share)
    params, rows = seeded(small_cfg(), length=40)
    params = held_by(params, cfg, share)
    close(forward(params, rows, cfg), ref_logits(params, rows, cfg), F32_TOL)


def test_bf16_in_place_of_float32_fails_the_tolerance():
    harness.bf16_fails_the_tolerance(small_cfg(), ref_logits, F32_TOL)


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """The two shares' routed parts, the shared expert counted ONCE, add up to what the
    uncut layer gives; a rank's part is the reference's at that rank."""
    whole = small_cfg()
    params, _ = seeded(whole)
    mlp = params["layers"][2]["mlp"]
    y = jax.random.normal(jax.random.key(5), (2, 24, whole.hidden_size))
    want = moe.moe_topk_block(y, mlp, whole)[0]
    shared = moe.moe_topk_block(y, mlp, whole.replace(moe_route_scale=0.0))[0]
    total = shared
    for rank in range(2):
        cut = whole.replace(moe_share=(rank, 2))
        mine = held_by(params, whole, (rank, 2))["layers"][2]["mlp"]
        total = total + moe.moe_topk_block(y, mine, cut)[0] - shared
    close(total, want, F32_TOL)
    assert worst(total + shared, want) > F32_TOL
    rc = ref_cfg(whole)
    fw = ARCH.published_weights(params, rc)["layers"][2]["mlp"]
    with jax.default_matmul_precision("highest"):
        close(want[:1], ARCH.moe(y[:1], fw, rc), F32_TOL)
        part = dict(fw, experts={k: v[4:] for k, v in fw["experts"].items()})
        mine = held_by(params, whole, (1, 2))["layers"][2]["mlp"]
        close(moe.moe_topk_block(y, mine, whole.replace(moe_share=(1, 2)))[0][:1],
              ARCH.moe(y[:1], part, ref_cfg(whole, (1, 2))), F32_TOL)


# -- the ring and the whole slots ---------------------------------------------------------


def _served(params, cfg, rows):
    """Row 0 through slot 2 (a prompt of 26 = 6 chunks and 2 tokens: chunks end inside
    the window of 16 and past it, the chunk at 20 begins the ring's second lap) decoded
    to 60, three laps of the ring of 20; row 1 through slot 0 (a prompt of 7) decoded to
    30: its ring laps DURING decode."""
    prompts = {2: (rows[0].tolist(), 26), 0: (rows[1].tolist(), 7)}
    return through_the_cache(params, cfg, prompts, {2: 60, 0: 30}, capacity=SLOT)[0]


def test_chunked_prefill_then_decoding_through_both_stacks_matches_the_reference():
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=60)
    want = np.asarray(ref_logits(params, rows, cfg))
    assert generation.ring_positions(cfg, SLOT, CHUNK) == WINDOW + CHUNK and 60 >= 3 * 20
    got = _served(params, cfg, rows)
    close(got[2], want[0], F32_TOL)
    close(got[0], want[1, :30], F32_TOL)


def _without(name):
    """The layers' norm ``name`` taken out of the tree: `modeling.post_norm` then passes."""
    def plant(cfg, params, monkeypatch):
        return cfg, dict(params, layers=[{k: v for k, v in lp.items() if k != name}
                                         for lp in params["layers"]])
    return plant


def _bias_in_the_weights(cfg, params, monkeypatch):
    """The selection bias added to the scores the WEIGHTS are taken from (the choice
    stays: top-k of (s + b) + 0)."""
    real = moe.router_scores
    monkeypatch.setattr(moe, "router_scores",
                        lambda xt, router, cfg_: real(xt, router, cfg_) + router["bias_was"])
    layers = [dict(lp, mlp=dict(lp["mlp"], router=dict(
        lp["mlp"]["router"], bias=jnp.zeros_like(lp["mlp"]["router"]["bias"]),
        bias_was=lp["mlp"]["router"]["bias"]))) if "router" in lp["mlp"] else lp
        for lp in params["layers"]]
    return cfg, dict(params, layers=layers)


#: fault -> (cfg, params, monkeypatch) -> the (cfg, params) the program then runs
FAULTS = {
    "gate_dropped": lambda cfg, params, _: (cfg.replace(attn_gate=False), params),
    "post_attn_norm_dropped": _without("post_attn_norm"),
    "post_mlp_norm_dropped": _without("post_mlp_norm"),
    "rotary_on_the_full_layer": lambda cfg, params, _: (cfg.replace(rope_layout=(1,) * 60), params),
    "embedding_unscaled": lambda cfg, params, _: (cfg.replace(embedding_multiplier=1.0), params),
    "bias_in_the_weights": _bias_in_the_weights,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_tolerance(monkeypatch, retraced, fault):
    """Each part of the layer the reference states and a pre-norm GQA stack lacks, taken
    out of the CACHED forwards (and the training forward with them): the tolerance these
    tests compare by tells each from the sound program."""
    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=60)
    want = np.asarray(ref_logits(params, rows, cfg))
    cfg, params = FAULTS[fault](cfg, params, monkeypatch)
    retraced()  # (the planted scores are bound when a forward is traced)
    got = _served(params, cfg, rows)
    assert worst(got[2], want[0]) > 4 * F32_TOL and worst(got[0], want[1, :30]) > 4 * F32_TOL
    assert worst(forward(params, rows, cfg), want) > F32_TOL


def _lockstep(params, cfg, rows):
    """Both rows of a plain K/V stack's position-major cache at once: 8 positions in one
    forward at offset 0, then a token a step -> the logits of all 12 positions."""
    cache = generation.init_kv_cache(cfg, 2, 32)
    pre, cache = harness.step_forward(params, cfg, cache, rows[:, :8], jnp.int32(0))
    rest, _ = harness.decode(params, cfg, cache, {b: (rows[b].tolist(), 8, 12) for b in (0, 1)},
                             slots=2)
    return np.concatenate([np.asarray(pre), np.stack([rest[0], rest[1]])], axis=1)


def test_a_served_model_with_an_embedding_multiplier_scales_its_embedding():
    """`generation._embed_at` applies ``embedding_multiplier`` as `modeling.embed` does
    (granite's 12.0 on a plain K/V stack: a served model with a multiplier was served
    wrong before PR 61, which no served preset had exposed)."""
    cfg = PRESETS["llama-0.3b"].replace(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4, ffn_dim=48, max_seq_len=32,
        dtype=jnp.float32, embedding_multiplier=12.0)
    params = modeling.init_model_params(jax.random.key(0), cfg)
    rows = jax.random.randint(jax.random.key(1), (2, 12), 0, 64, jnp.int32)
    want = forward(params, rows, cfg)
    close(_lockstep(params, cfg, rows), want, 1e-5)
    assert worst(forward(params, rows, cfg.replace(embedding_multiplier=1.0)), want) > 1e-3


def test_a_plain_stack_with_the_gate_and_the_post_norms_is_served_whole():
    """Neither switch needs a window: on a plain K/V stack the cached forward (position-major
    `KVCache`) equals the training forward, and the slot and the paged engine both serve
    what plain generation gives."""
    from galvatron_tpu.serving import Engine

    cfg = PRESETS["llama-0.3b"].replace(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4, num_kv_heads=2, ffn_dim=48,
        max_seq_len=32, dtype=jnp.float32, attn_gate=True, post_norms=True)
    params, rows = seeded(cfg, batch=2, length=12)
    want = forward(params, rows, cfg)
    assert isinstance(generation.init_kv_cache(cfg, 2, 32), generation.KVCache)
    close(_lockstep(params, cfg, rows), want, 1e-5)
    assert worst(forward(params, rows, cfg.replace(attn_gate=False)), want) > 1e-3
    prompts = [rows[0, :9].tolist(), rows[1, :5].tolist()]
    ref = generation.generate_np(params, cfg, prompts, max_new_tokens=6)
    for paged in (dict(), dict(kv_num_blocks=-1, kv_block_size=8)):
        with Engine(params, cfg, num_slots=2, prefill_chunk=8, eos_id=-1, **paged) as eng:
            assert eng.generate(prompts, max_new_tokens=6) == ref


def test_the_decode_kernel_takes_the_cells_shapes():
    """`kv_decode.decode_path` answers "kernel" from the shapes alone at Trinity's: heads
    of 128, 6 query rows a key/value head, rings of 5,120 and slots of 16,384 places in
    whole key blocks (group 6 against `_attend_rows`: tests/test_kv_decode.py)."""
    from galvatron_tpu.ops import kv_decode

    cfg, _ = _cell_cfg()
    ring = generation.ring_positions(cfg, cfg.max_seq_len, 1024)
    group = cfg.num_heads // cfg.kv_heads
    assert (ring, group, cfg.head_dim) == (5120, 6, 128)
    for places in (ring, cfg.max_seq_len):
        assert places % kv_decode.KEY_BLOCK == 0
        assert kv_decode.decode_path(places, cfg.head_dim, group, jnp.bfloat16) == "kernel"
    # what a step then fetches: the rows' lengths rounded up to the key block, a ring's
    # no more than the ring
    read = generation.cache_read_positions(cfg, [100, 5000, 16000], 32, 16384, ring=ring)
    block = kv_decode.KEY_BLOCK
    up = lambda n: -(-n // block) * block  # noqa: E731
    assert read["full"] == up(100) + up(5000) + up(16000) + 29 * block
    assert read["window"] == up(100) + up(5000) + ring + 29 * block


# -- the engine ---------------------------------------------------------------------------


def test_the_engines_tap_rows_are_the_references_and_its_spans_count_the_touched_experts(monkeypatch):
    """Three requests through the engine (prompts past and inside the window, answers
    that lap the ring), every token's logits row kept by the tap: the rows equal the
    reference's full forward over prompt + served tokens; the ``decode`` spans carry the
    held experts a step touched."""
    from galvatron_tpu.obs.tracing import tracer

    # (a draw is a function of (seed, request id, token index), the ids a counter of the
    # PROCESS: at 1e-4 the row whose two best lie 7e-4 apart draws the second at about one
    # id in 800, so the ids start at 0 here, whatever the worker served before)
    harness.first_request_ids(monkeypatch)
    cfg = small_cfg(moe_share=(1, 2))
    params, rows = seeded(small_cfg(), batch=3, length=30)
    params = held_by(params, cfg, (1, 2))
    prompts = [rows[0, :26].tolist(), rows[1, :5].tolist(), rows[2, :13].tolist()]
    new = [30, 40, 12]
    engine = harness.engine(cfg, params)
    tracer.enable(capacity=1 << 13)
    tracer.clear()
    try:
        bufs = [np.zeros((n, cfg.vocab_size), np.float32) for n in new]
        reqs = [engine.submit_request(p, n, temperature=1e-4, capture_logits=b)
                for p, n, b in zip(prompts, new, bufs)]
        for r in reqs:
            r.future.result(timeout=120)
        served = [list(r.generated) for r in reqs]
        spans = [e for e in tracer.snapshot() if e.get("ph") == "X"]
        stats = engine.stats()
    finally:
        tracer.disable()
        engine.close()
    for prompt, got, buf, req in zip(prompts, served, bufs, reqs):
        assert req.logits_rows == len(got)
        seq = jnp.asarray([prompt + got[:-1]], jnp.int32)
        want = np.asarray(ref_logits(params, seq, cfg))[0, len(prompt) - 1:]
        close(buf, want, F32_TOL)
        assert [int(np.argmax(r)) for r in buf] == got
    decode = [e["args"] for e in spans if e["name"] == "decode"]
    assert decode and all(a["moe_held_experts"] == 4 for a in decode)
    stacks = decode[0]
    # (a step's expert counters ride the NEXT step's span: read with its ids, a step late)
    assert "moe_held_experts_touched" not in decode.pop(0)
    # 3 rows x top-2 = 6 pairs a step on 8 experts scored, 4 held: some held go without a row
    assert all(0 <= a["moe_held_experts_touched"] <= 4 for a in decode)
    assert any(0 < a["moe_held_experts_touched"] < 4 for a in decode)
    assert all(a["moe_held_experts_touched"] <= 3 * a["moe_held_pairs_per_token"] + 1e-6
               for a in decode)
    assert (stacks["kv_full_layers"], stacks["kv_window_layers"]) == (1, 4)
    prefill = [e["args"] for e in spans if e["name"] == "prefill"]
    assert prefill and all(0 <= a["moe_held_experts_touched"] <= 4 for a in prefill)
    per = 2 * 2 * 8 * 4
    assert stats["cache_bytes"] == 3 * per * (SLOT + 4 * (WINDOW + CHUNK))


def test_a_cached_forwards_held_share_gives_tiles_to_the_touched_experts_alone(monkeypatch):
    """A prompt chunk and a decode step through `generation.forward_with_cache` at widths
    the bounded held path takes (interpreted here): every expert layer asks `moe.held_layout`
    for no empty tiles, the logits are the reference's as before, and the forward's
    ``moe_held_experts_touched`` (`engine._router_counters`) is the experts its layouts'
    used tiles name: the experts whose weights the step fetched."""
    from galvatron_tpu.serving import engine

    whole = small_cfg(hidden_size=128, moe_ffn_dim=128, moe_shared_ffn_dim=128,
                      embedding_multiplier=128 ** 0.5)
    cfg = whole.replace(moe_share=(1, 2))
    assert moe.held_path_counts(cfg)["bounded"] and cfg.moe_held == 4
    params, rows = seeded(whole, batch=1, length=9)
    params = held_by(params, cfg, (1, 2))
    asked, named = [], []
    real = moe.held_layout

    def recording(*args, empty_tiles=True):
        lay = real(*args, empty_tiles=empty_tiles)
        asked.append(empty_tiles)
        jax.debug.callback(lambda g, n: named.append(len(set(g[:int(n[0])].tolist()))),
                           lay.tile_group, lay.num_tiles, ordered=True)
        return lay

    monkeypatch.setattr(moe, "held_layout", recording)

    @partial(jax.jit, static_argnames=("slot_form",))
    def forward(cache, tokens, offsets, slot, slot_form):
        stats = []
        lg, cache = generation.forward_with_cache(
            params, tokens, cfg, cache, offsets, slot=slot if slot_form else None,
            moe_stats=stats)
        return lg, cache, engine._router_counters(stats, cfg, tokens.size)

    want = np.asarray(ref_logits(params, rows, cfg))[0]
    cache = generation.init_kv_cache(cfg, 3, SLOT, tokens=8)
    steps = [(rows[:, :8], jnp.int32(0), jnp.int32(1), True, lambda lg: lg[0], want[:8]),
             (jnp.zeros((3, 1), jnp.int32).at[1, 0].set(rows[0, 8]),
              jnp.asarray([0, 8, 0], jnp.int32), None, False, lambda lg: lg[1], want[8:9])]
    for tokens, offsets, slot, slot_form, mine, ref in steps:
        del named[:]
        lg, cache, counters = forward(cache, tokens, offsets, slot, slot_form)
        jax.effects_barrier()
        close(mine(lg), ref, F32_TOL)
        assert len(named) == 4  # the expert layers (the first layer is dense)
        touched = float(counters["moe_held_experts_touched"])
        assert touched == pytest.approx(sum(named) / 4) and 0 < touched <= 4
        assert 0.0 < float(counters["moe_live_rows_share"]) <= 1.0
    # (the step's 3 rows x top-2 leave held experts without a row; the chunk's 16 pairs fewer)
    assert min(named) < 4
    assert asked and not any(asked)


def test_held_experts_touched_counts_the_held_experts_with_a_row():
    """`moe.held_experts_touched` from the layers' statistics alone, a mean over the layers;
    every forward of a model with dropless expert layers carries it (`_router_counters`)."""
    cfg, _ = _cell_cfg()
    f = jnp.zeros((256,)).at[jnp.asarray([0, 5, 31, 32, 200])].set(1 / 32)
    stats = [(f, f), (f.at[7].set(1 / 32), f)]
    # experts 0, 5, 31 (and 7 in the second layer) of the held 0..31
    assert float(moe.held_experts_touched(stats, (cfg.moe_first_held, cfg.moe_held))) == 3.5


def test_the_engine_serves_it_under_int8_weights():
    """`--serve_quant int8` (the benchmark's control below the stated precision): the
    engine's parity gate runs the TRAINING forward on the quantized tree, so the gated
    block takes `QuantTensor` projections as the cached forwards do, and the two agree."""
    from galvatron_tpu.ops import quant

    cfg = small_cfg()
    params, rows = seeded(cfg, batch=2, length=24)
    qparams = quant.quantize_params(params, cfg)
    assert isinstance(qparams["layers"][0]["attn"]["wqkv"], quant.QuantTensor)
    want = forward(qparams, rows, cfg)
    cache = generation.init_kv_cache(cfg, 2, SLOT, tokens=24)
    got, _ = harness.step_forward(qparams, cfg, cache, rows, jnp.zeros((2,), jnp.int32))
    close(got, want, 1e-4)
    assert worst(want, forward(params, rows, cfg)) > 1e-4  # int8 is not float32
    with harness.engine(cfg, params, serve_quant="int8", quant_drift_max=1e9) as engine:
        assert engine.quant_parity["max_abs_logit_drift"] > 0
        out = engine.generate([rows[0, :9].tolist()], max_new_tokens=4)
    assert len(out[0]) == 9 + 4


# -- training -----------------------------------------------------------------------------


def test_the_runtime_trains_it_on_one_device():
    _, state = harness.trains_on_one_device(small_cfg(max_seq_len=32), steps=6, drop=0.2)
    assert {"post_attn_norm", "post_mlp_norm"} <= set(state["params"]["layers"][0])
    assert "wgate" in state["params"]["layers"][0]["attn"]


@pytest.mark.parametrize("field", ["attn_gate", "post_norms"])
def test_context_parallelism_is_refused_by_name(field):
    """The ring / Ulysses layers call `modeling.attn_output` themselves: a gate or a norm
    after the block would be dropped without a word, so `build_runtime` refuses cp > 1."""
    cfg = PRESETS["llama-0.3b"].replace(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4, num_kv_heads=2, ffn_dim=48,
        max_seq_len=32, dtype=jnp.float32, qk_norm=True, qk_norm_per_head=True, **{field: True})
    hp = HybridParallelConfig.uniform(2, cp=2, mixed_precision="fp32")
    with pytest.raises(ValueError, match="not implemented with attn_gate or post_norms"):
        build_runtime(cfg, hp, adam=AdamConfig(lr=3e-3), global_batch_size=4, seq_len=32)
