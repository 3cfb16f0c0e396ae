"""The seam of a layer kind (`models/mixers.py`): a kind is one module and one
row of ``MIXERS``, and what a model's layers do not implement is one table that
the runtime, the plan checker, the search and generation all read.

(a) a third kind that lives in THIS file alone (a gated linear map: no state, no
tp) is initialised, trained, priced, refused, diagnosed, left out and named by
the program with no line outside the file knowing it;
(b) for every limit the table yields for the three presets that have any, cut to
test size: `build_runtime` raises its sentence, `plan_check` reports it under its
code and the search's space leaves it out under its tag.
"""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.analysis.plan_check import check_plan
from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models import mixers, modeling
from galvatron_tpu.models.generation import init_kv_cache
from galvatron_tpu.models.modeling import PRESETS, ModelConfig
from galvatron_tpu.parallel.hybrid import build_runtime
from galvatron_tpu.parallel.mesh import build_mesh
from galvatron_tpu.search import theoretical as th
from galvatron_tpu.search.cost_model import ProfiledHardware
from galvatron_tpu.search.search_engine import SearchEngine, SearchSpace

# -- (a) the toy kind: everything the interface asks of a kind's module ------------


def init_params(key, cfg):
    h = cfg.hidden_size
    k_in, k_gate, k_out = jax.random.split(key, 3)
    return {"w_in": modeling._dense_init(k_in, h, h, cfg.param_dtype),
            "w_gate": modeling._dense_init(k_gate, h, h, cfg.param_dtype),
            "w_out": modeling._dense_init(k_out, h, h, cfg.param_dtype)}


def annotations(cfg):
    return {"w_in": ("fsdp", None), "w_gate": ("fsdp", None), "w_out": (None, "fsdp")}


@jax.named_scope("glm")
def block(x, p, cfg, place=None):
    gate = jax.nn.sigmoid(x @ p["w_gate"].astype(x.dtype))
    return ((x @ p["w_in"].astype(x.dtype)) * gate) @ p["w_out"].astype(x.dtype)


def param_count(cfg):
    return 3 * cfg.hidden_size ** 2


def saved_bytes_per_token(cfg, itemsize):
    return 3 * cfg.hidden_size * itemsize  # the two projections and their product


def fwd_flops_per_token(cfg):
    return 0.0  # nothing beside its weights


GLM = mixers.Mixer(
    kind="glm", module=__name__, layer="gated linear layer", mixer="the gated linear map",
    tag="gated_linear_layers",
    lacks={"tp": "the map's three projections carry no tp sharding",
           "kv_cache": "a key/value cache has no row for a layer without keys"})


@pytest.fixture
def glm(monkeypatch):
    """The kind registered for one test, and a two-layer stack with it."""
    monkeypatch.setitem(mixers.MIXERS, "glm", GLM)
    return ModelConfig(vocab_size=96, hidden_size=32, num_layers=2, num_heads=4, ffn_dim=64,
                       max_seq_len=16, attn_impl="xla", dtype=jnp.float32,
                       layer_kinds=("glm", "attention"))


def _one_device():
    return build_mesh(pp=1, devices=jax.devices()[:1])


def test_a_third_kind_initialises_and_trains_a_step(glm):
    params = modeling.init_model_params(jax.random.key(0), glm)
    assert set(params["layers"][0]) >= {"glm", "mlp"} and "attn" not in params["layers"][0]
    assert "attn" in params["layers"][1] and "glm" not in params["layers"][1]
    assert sum(a.size for a in jax.tree.leaves(params["layers"][0]["glm"])) == param_count(glm)
    mesh, axes = _one_device()
    hp = HybridParallelConfig.uniform(2, mixed_precision="fp32")
    rt = build_runtime(glm, hp, mesh=mesh, axes=axes, global_batch_size=4, seq_len=16)
    rows = np.asarray(jax.random.randint(jax.random.key(1), (4, 17), 0, 96, jnp.int32))
    state = rt.init_state(jax.random.key(0))
    before = np.asarray(state["params"]["layers"][0]["glm"]["w_gate"]).copy()
    state, loss = rt.train_step(state, rt.shard_batch(rows))
    assert np.isfinite(float(loss)) and float(loss) == pytest.approx(np.log(96), rel=0.2)
    assert not np.array_equal(before, np.asarray(state["params"]["layers"][0]["glm"]["w_gate"]))


def test_a_third_kind_is_priced_by_its_own_module(glm):
    assert mixers.has_mixer_layers(glm)
    attention = th.layer_param_count(glm)
    assert th.layer_param_count(glm, kind="glm") == attention - 4 * 32 * 32 + param_count(glm)
    assert th.total_param_count(glm) == sum(
        a.size for a in jax.tree.leaves(modeling.init_model_params(jax.random.key(0), glm)))
    costs = th.analytic_model_costs(glm, seq_len=16)
    toy, full = costs.layer_types[0], costs.layer_types[1]
    assert toy.parameter_mb == pytest.approx(th.layer_param_count(glm, kind="glm") * 4 / 1e6)
    # no score pairs: linear in the sequence, and cheaper than the attention layer
    twice = th.analytic_model_costs(glm, seq_len=32).layer_types[0]
    assert twice.fwd_ms_per_sample == pytest.approx(2 * toy.fwd_ms_per_sample, rel=1e-9)
    assert toy.fwd_ms_per_sample < full.fwd_ms_per_sample
    s = LayerStrategy()
    assert th.layer_activation_mb_per_sample(glm, s, 16, "fp32", kind="glm") == pytest.approx(
        th.layer_activation_mb_per_sample(glm, s, 16, "fp32")
        - (16 * (3 * 32 + 32) * 4 + 4.0 * 4 * 16 * 16) / 1e6 + 16 * 3 * 32 * 4 / 1e6)


def test_a_third_kind_is_refused_diagnosed_and_left_out_by_its_row(glm):
    table = mixers.limits(glm)
    assert [(t.what, t.tag, t.code, t.layers) for t in table] == [
        ("tp", "gated_linear_layers_no_tp", "GTA019", (0,)), ("kv_cache", None, None, (0,)),
        ("pp", "interleaved_layer_kinds_no_pp", "GTA020", (0, 1))]
    hp = HybridParallelConfig(layer_strategies=[LayerStrategy(tp=2), LayerStrategy(tp=2)],
                              mixed_precision="fp32")
    with pytest.raises(ValueError, match=re.escape(
            "tensor parallelism (tp>1) is not implemented for gated linear layers (layers [0] "
            "of this plan): the map's three projections carry no tp sharding")):
        build_runtime(glm, hp, global_batch_size=8, seq_len=16)
    found = [d for d in check_plan(hp, model_config=glm, world_size=8, global_bsz=8)
             if d.code == "GTA019"]
    assert [d.message for d in found] == [
        "layer 0: tp=2 on a gated linear layer — tensor parallelism is not implemented for "
        "the gated linear map"]
    assert found[0].field == "tp_sizes_enc[0]"
    # tp on the attention layer alone is the plan's to choose
    ok = HybridParallelConfig(layer_strategies=[LayerStrategy(), LayerStrategy(tp=2)],
                              mixed_precision="fp32")
    assert not [d for d in check_plan(ok, model_config=glm, world_size=8, global_bsz=8)
                if d.code in ("GTA019", "GTA020")]
    engine = SearchEngine(th.analytic_model_costs(glm), ProfiledHardware(), num_layers=2,
                          space=SearchSpace(world_size=4), memory_budget_mb=15360.0,
                          model_config=glm)
    assert engine._standing == ["gated_linear_layers_no_tp", "interleaved_layer_kinds_no_pp"]
    assert engine.space.max_tp == 1 and engine.space.pp_choices == [1]
    with pytest.raises(ValueError, match="generation .* gated linear layers: a key/value cache "
                                         "has no row for a layer without keys; train-only"):
        init_kv_cache(glm, 1, 8)


def test_a_third_kind_shows_in_the_run_s_fingerprint(glm, monkeypatch, tmp_path):
    """`trainer.train` on the stack: the ``build_runtime`` span counts the kind
    under ``layer_kinds``, and the registered kinds' kernels keep their keys (a
    kind without kernels adds none)."""
    from galvatron_tpu.core.arguments import initialize_galvatron
    from galvatron_tpu.core.trainer import train

    monkeypatch.setitem(PRESETS, "toy-glm", glm)
    spans = str(tmp_path / "spans.json")
    train(initialize_galvatron("train", [
        "--model_size", "toy-glm", "--seq_length", "16", "--global_train_batch_size", "8",
        "--mixed_precision", "fp32", "--train_iters", "2", "--trace_spans", spans]),
        verbose=False)
    (span,) = [e for e in json.load(open(spans))["traceEvents"]
               if e["ph"] == "X" and e["name"] == "build_runtime"]
    assert span["args"]["layer_kinds"] == {"glm": 1, "attention": 1}
    assert [k for k in span["args"] if k.endswith("_path")] == [
        "ssm_scan_path", "ssm_conv_path", "ssm_step_path", "gdn_scan_path", "gdn_conv_path",
        "shortconv_conv_path", "moe_held_path"]


def test_the_registry_loads_a_kind_s_module_only_for_a_stack_that_has_it():
    """A dense model imports no mixer and no kernel of one (``setup_s``), and the
    table and the registry themselves import neither jax nor a kernel."""
    code = (
        "import sys\n"
        "from galvatron_tpu.models import mixers\n"
        "from galvatron_tpu.analysis import plan_check\n"
        "assert 'jax' not in sys.modules, 'the table pulled jax in'\n"
        "from galvatron_tpu.models.modeling import PRESETS, init_model_params\n"
        "import jax\n"
        "jax.eval_shape(lambda k: init_model_params(k, PRESETS['opt-1.3b'].replace(num_layers=1)),\n"
        "               jax.random.key(0))\n"
        "counts = mixers.path_counts(PRESETS['opt-1.3b'])\n"
        "assert counts['ssm_scan_path'] == counts['gdn_conv_path'] == {'fused': 0, 'plain': 0}\n"
        "assert mixers.limits(PRESETS['opt-1.3b']) == []\n"
        "loaded = [m for m in sys.modules if m.startswith('galvatron_tpu.') and m.rsplit('.', 1)[1]\n"
        "          in ('ssm', 'gdn', 'ssd', 'gated_delta')]\n"
        "assert not loaded, loaded\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]


# -- (b) the limits table, held to its three readers --------------------------------

CUT = {
    "granite-4.0-h-micro": dict(
        vocab_size=96, hidden_size=64, num_layers=8, num_heads=4, num_kv_heads=2, ffn_dim=96,
        max_seq_len=64, ssm_heads=8, ssm_head_dim=16, ssm_state=16, ssm_chunk=16),
    "qwen3-next-80b-a3b": dict(
        vocab_size=96, hidden_size=32, num_layers=4, num_heads=4, num_kv_heads=2, attn_head_dim=16,
        ffn_dim=80, max_seq_len=64, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8,
        gdn_value_dim=8, moe_experts=16, moe_top_k=4, moe_ffn_dim=24, moe_shared_ffn_dim=24,
        moe_share=(1, 4)),
    "olmoe-1b-7b": dict(
        vocab_size=96, hidden_size=64, num_layers=2, num_heads=4, ffn_dim=32, max_seq_len=32,
        moe_experts=8, moe_top_k=2),
    # (a window is no kind: its limits are the stack's, from the same table)
    "smallthinker-21b-a3b": dict(
        vocab_size=96, hidden_size=32, num_layers=4, num_heads=4, num_kv_heads=2, attn_head_dim=8,
        ffn_dim=24, max_seq_len=32, sliding_window_size=8, moe_experts=8, moe_top_k=2,
        moe_ffn_dim=24),
    # (a kind with a per-row state and no cache limit: served; the state's own two limits)
    "lfm2-24b-a2b": dict(
        vocab_size=96, hidden_size=32, num_layers=4, num_heads=4, num_kv_heads=2, ffn_dim=48,
        max_seq_len=32, moe_experts=8, moe_top_k=2, moe_ffn_dim=24),
}


def cut(preset):
    return PRESETS[preset].replace(dtype=jnp.float32, **CUT[preset])


CASES = [(preset, i) for preset in CUT for i in range(len(mixers.limits(cut(preset))))]
IDS = [f"{preset}-{i}-{mixers.limits(cut(preset))[i].what}" for preset, i in CASES]


def _breaking(cfg, limit):
    """(cfg, plan) that asks of every layer what ``limit`` is about."""
    n, kw = cfg.num_layers, {"mixed_precision": "fp32"}
    if limit.what in mixers.DEGREES:
        kw["layer_strategies"] = [LayerStrategy(**{limit.what: 2}) for _ in range(n)]
    else:
        kw["layer_strategies"] = [LayerStrategy() for _ in range(n)]
    if limit.what == "pp":
        kw.update(pp=2, chunks=2)
    if limit.what == "fp16":
        kw["mixed_precision"] = "fp16"
    if limit.what == "pack_sequences":
        cfg = cfg.replace(pack_sequences=True, attn_impl="xla")
    if limit.what == "attn_impl":
        cfg = cfg.replace(attn_impl="flash")
    return cfg, HybridParallelConfig(**kw)


def test_the_three_presets_yield_what_the_issue_counts():
    assert [len(mixers.limits(cut(p))) for p in CUT] == [6, 9, 4, 9, 10] and len(CASES) == 38
    # a windowed stack's five (another attention path, cp, packing, pp, the paged backend)
    assert [t.what for t in mixers.limits(cut("smallthinker-21b-a3b"))[:5]] == [
        "attn_impl", "cp", "pack_sequences", "pp", "paged_kv"]
    assert mixers.limits(PRESETS["llama-7b"]) == []
    # a stack with a per-row state: the kind's three, the state's two, then the stack's
    served = mixers.limits(cut("lfm2-24b-a2b"))
    assert [t.what for t in served] == ["tp", "cp", "pack_sequences", "paged_kv", "spec_decode",
                                        "pp", "ep", "pp", "cp", "fp16"]
    assert not [t for t in served if t.what == "kv_cache"]  # attention + shortconv is served
    # a Mamba-2 stack is served likewise (PR 68: a state of two parts a row)
    assert [t.what for t in mixers.limits(cut("granite-4.0-h-micro"))] == [
        "tp", "cp", "pack_sequences", "paged_kv", "spec_decode", "pp"]
    # the stack that still cannot be served says why, in its kind's own sentence
    for preset, clause in (("qwen3-next-80b-a3b", "holds no recurrent (conv + delta rule) state"),):
        refusals = [t.sentence() for t in mixers.limits(cut(preset)) if t.what == "kv_cache"]
        assert len(refusals) == 1 and clause in refusals[0], preset
    latent = cut("lfm2-24b-a2b").replace(layer_kinds=("shortconv", "mla") * 2, mla_kv_rank=8)
    assert any("interleaves cache layouts" in t.sentence() for t in mixers.limits(latent))


@pytest.mark.parametrize("preset,i", CASES, ids=IDS)
def test_every_limit_is_refused_reported_and_left_out(preset, i):
    cfg = cut(preset)
    limit = mixers.limits(cfg)[i]
    if limit.what == "kv_cache":  # no plan asks for a cache: generation's to refuse
        with pytest.raises(ValueError, match=re.escape(limit.sentence())):
            init_kv_cache(cfg, 1, 8)
        assert limit.tag is None and limit.code is None
        return
    if limit.what in ("paged_kv", "spec_decode"):
        # nor for a block pool or a draft window: the engine's to refuse
        from galvatron_tpu.serving import Engine

        asked = {"kv_num_blocks": -1} if limit.what == "paged_kv" else {"spec_decode_k": 2}
        with pytest.raises(ValueError, match=re.escape(limit.sentence())):
            Engine(None, cfg, num_slots=2, prefill_chunk=8, **asked)
        assert limit.tag is None and limit.code is None
        return
    cfg, hp = _breaking(cfg, limit)
    table = mixers.limits(cfg)
    assert table[i] == limit and limit.broken_by(cfg, hp)
    # the runtime raises the FIRST limit of the table that the plan breaks: this one,
    # or an earlier one on the same degree (a recurrent stack's cp before the expert path's)
    first = next(t for t in table if t.broken_by(cfg, hp))
    assert first.what == limit.what
    with pytest.raises(ValueError, match=re.escape(first.sentence(first.broken_by(cfg, hp)))):
        build_runtime(cfg, hp, global_batch_size=8, seq_len=cfg.max_seq_len)
    # the plan checker: one diagnostic a layer the limit names (one for the run)
    found = [d for d in check_plan(hp, model_config=cfg, world_size=8, global_bsz=8)
             if d.code == limit.code and limit.diagnostic in d.message]
    if limit.code is None:
        assert not limit.diagnostic
    elif limit.what in mixers.DEGREES:
        assert [d.field for d in found] == [f"{limit.what}_sizes_enc[{j}]" for j in limit.layers]
        assert all(d.message.startswith(f"layer {j}: {limit.what}=2 ")
                   for d, j in zip(found, limit.layers))
    else:
        assert [d.message for d in found] == [f"pp=2 {limit.diagnostic}"]
    # the search: the tag stands, and the space has no such candidate left
    engine = SearchEngine(
        th.analytic_model_costs(cfg), ProfiledHardware(), num_layers=cfg.num_layers,
        space=SearchSpace(world_size=8, allow_cp=True, allow_ep=True, moe_experts=cfg.moe_experts),
        memory_budget_mb=15360.0, model_config=cfg)
    if limit.tag is None:
        # nothing the search enumerates
        assert limit.what in ("pack_sequences", "fp16", "attn_impl")
    else:
        assert limit.tag in engine._standing
        assert {"tp": engine.space.max_tp == 1, "cp": not engine.space.allow_cp,
                "ep": not engine.space.allow_ep, "pp": engine.space.pp_choices == [1]}[limit.what]


@pytest.mark.parametrize("preset", list(CUT))
def test_a_plan_inside_the_limits_passes_all_three(preset):
    cfg = cut(preset)
    hp = HybridParallelConfig.uniform(cfg.num_layers, dp_type="zero3", mixed_precision="fp32")
    assert not any(t.broken_by(cfg, hp) for t in mixers.limits(cfg))
    assert not [d for d in check_plan(hp, model_config=cfg, world_size=8, global_bsz=8)
                if d.code in ("GTA014", "GTA019", "GTA020")]
    build_runtime(cfg, hp, global_batch_size=8, seq_len=cfg.max_seq_len)
