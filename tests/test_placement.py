"""One placement per layer (parallel/placement.py): the rules ``place_layer``
reads from a ``LayerStrategy``, that every engine asks it, that equal
strategies share one placement, and that ``models/`` knows no mesh."""

import ast
import dataclasses
import os
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.models.placement import LOCAL, Placement
from galvatron_tpu.parallel import placement
from galvatron_tpu.parallel.mesh import build_mesh
from galvatron_tpu.parallel.placement import LayerPlacement, place_layer

DENSE = ModelConfig(vocab_size=128, hidden_size=64, num_layers=4, num_heads=4, ffn_dim=128,
                    max_seq_len=32, attn_impl="flash")
SWITCH = DENSE.replace(moe_experts=4)
DROPLESS = DENSE.replace(moe_experts=4, moe_router="softmax_topk", moe_top_k=2)
HYBRID = DENSE.replace(attn_impl="xla", layer_kinds=("ssm", "attention", "ssm", "ssm"), ssm_heads=4)

PINS = ("qkv_pin", "attn_out_pin", "kernel_wrap", "tp_overlap", "moe_pin", "token_wrap")

# (id, model, strategy, devices) -> the pins that apply, (dp, tp, ep) axes on the
# mesh ('x0', 'x1' / one axis on two devices), activation spec, sp, and the two
# per-strategy config overrides (mlp_recompute, attn_impl)
TABLE = [
    ("tp1_dp", DENSE, LayerStrategy(), 4,
     {"kernel_wrap"}, (("x0", "x1"), (), ()), P(("x0", "x1"), None, None), False,
     ("policy", "flash")),
    ("tp2", DENSE, LayerStrategy(tp=2), 4,
     {"qkv_pin", "kernel_wrap"}, (("x0",), ("x1",), ()), P(("x0",), None, None), False,
     ("policy", "flash")),
    ("tp2_sp_overlap", DENSE, LayerStrategy(tp=2, sp=True, tp_overlap=True), 4,
     {"qkv_pin", "kernel_wrap", "tp_overlap"}, (("x0",), ("x1",), ()),
     P(("x0",), ("x1",), None), True, ("policy", "flash")),
    ("zero3_tp2", DENSE, LayerStrategy(tp=2, dp_type="zero3"), 4,
     {"qkv_pin", "attn_out_pin", "kernel_wrap"}, (("x0",), ("x1",), ()),
     P(("x0",), None, None), False, ("policy", "flash")),
    ("ep2_switch", SWITCH, LayerStrategy(ep=2), 4,
     {"kernel_wrap", "moe_pin"}, (("x0", "x1"), (), ("x1",)), P(("x0", "x1"), None, None),
     False, ("policy", "flash")),
    ("dropless_2dev", DROPLESS, LayerStrategy(), 2,
     {"kernel_wrap", "token_wrap"}, (("x0",), (), ()), P(("x0",), None, None), False,
     ("policy", "flash")),
    # ring cp: the ring layer carries its own shard_maps, so no kernel wrap
    ("cp2_ring", DENSE, LayerStrategy(cp=2, cp_impl="ring"), 4,
     set(), (("x0",), (), ()), P(("x0",), ("x1",), None), False, ("policy", "ring")),
    # a stack with state-space layers: the fused scan (ops/ssd.py) is a Mosaic call
    # whatever the attention layers run, so its layers wrap their kernels too
    ("ssm_stack_xla_attn", HYBRID, LayerStrategy(dp_type="zero3"), 4,
     {"kernel_wrap"}, (("x0", "x1"), (), ()), P(("x0", "x1"), None, None), False,
     ("policy", "xla")),
    ("ckpt_full", DENSE, LayerStrategy(ckpt="full"), 4,
     {"kernel_wrap"}, (("x0", "x1"), (), ()), P(("x0", "x1"), None, None), False,
     ("off", "flash")),
]


@pytest.mark.parametrize("cfg,s,devices,pins,dp_tp_ep,act_spec,sp,overrides",
                         [row[1:] for row in TABLE], ids=[row[0] for row in TABLE])
def test_place_layer_rules(cfg, s, devices, pins, dp_tp_ep, act_spec, sp, overrides):
    mesh, axes = build_mesh(pp=1, devices=jax.devices()[:devices])
    layer_cfg, place = place_layer(cfg, s, mesh, axes)
    assert isinstance(place, LayerPlacement) and place.mesh is mesh
    assert {name for name in PINS if getattr(place, name)} == pins
    assert (place.dp_axes, place.tp_axes, place.ep_axes) == dp_tp_ep
    assert place.act_spec == act_spec and place.sp is sp
    # the flattened (B·S) token dim: the batch axes, then the sequence axes
    assert place.token_axes == tuple(
        a for e in act_spec[:2] if e for a in ((e,) if isinstance(e, str) else e))
    assert place.kernel_tp == (2 if s.tp == 2 and "kernel_wrap" in pins else 1)
    assert (layer_cfg.mlp_recompute, layer_cfg.attn_impl) == overrides
    assert layer_cfg == cfg.replace(mlp_recompute=overrides[0], attn_impl=overrides[1])
    # one device: the same two overrides, and the placement every mesh-free caller passes
    mesh1, axes1 = build_mesh(pp=1, devices=jax.devices()[:1])
    one = LayerStrategy(ckpt=s.ckpt)
    cfg1, place1 = place_layer(cfg, one, mesh1, axes1)
    assert place1 is LOCAL and cfg1.mlp_recompute == overrides[0]


def test_equal_strategies_share_one_placement():
    """What keeps ``_decoder_layer_once`` (and the seams' jitted programs) to
    one trace for the layers of one plan entry: placements are static
    arguments, compared and hashed by value."""
    mesh, axes = build_mesh(pp=1, devices=jax.devices()[:4])
    make = lambda: LayerStrategy(tp=2, sp=True, tp_overlap=True, dp_type="zero3")  # noqa: E731
    a, b = place_layer(DENSE, make(), mesh, axes), place_layer(DENSE, make(), mesh, axes)
    assert a[1] is not b[1] and a == b and hash(a[1]) == hash(b[1]) and hash(a[0]) == hash(b[0])
    other = place_layer(DENSE, LayerStrategy(tp=2, sp=True, dp_type="zero3"), mesh, axes)[1]
    assert other != a[1]
    assert LOCAL == Placement() and hash(LOCAL) == hash(Placement()) and LOCAL != a[1]


XLA = DENSE.replace(attn_impl="xla")
ENCDEC = XLA.replace(num_layers=2, enc_layers=2, enc_seq=16, max_seq_len=16, pos_embed="learned")


@pytest.mark.parametrize("cfg,plan,asker,positions", [
    (XLA, dict(), "_make_layer_hook", 4),  # one a layer
    (XLA, dict(pp=2, chunks=2), "make_block_fn", 2),  # one a stage position
    (XLA, dict(pp=2, chunks=2, pipeline_type="pipedream_flush"), "make_block_fn", 2),
    (XLA, dict(pp=2, vpp=2, chunks=2), "make_block_fn", 1),  # one a virtual-stage position
    (ENCDEC, dict(pp=2, chunks=2), "_make_section_fns", 2),  # an encoder and a decoder position
], ids=["pp1", "gpipe", "1f1b", "interleaved", "encdec"])
def test_every_engine_asks_place_layer(monkeypatch, cfg, plan, asker, positions):
    from galvatron_tpu.parallel.hybrid import build_runtime

    asked = []
    real = placement.place_layer

    def recorder(cfg, s, mesh, axes):
        asked.append(sys._getframe(1).f_code.co_name)
        return real(cfg, s, mesh, axes)

    monkeypatch.setattr(placement, "place_layer", recorder)
    hp = HybridParallelConfig.uniform(4, tp=2, mixed_precision="fp32", **plan)
    rt = build_runtime(cfg, hp, global_batch_size=8)
    assert asked.count(asker) == positions, asked
    # the rest is the runtime's seam count, once a layer, and nothing else
    assert sorted(set(asked)) == sorted({asker, "tp_overlap_seam_counts"}), asked
    assert asked.count("tp_overlap_seam_counts") == rt.hp.num_layers


def _models_modules():
    root = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "galvatron_tpu", "models")
    for dirpath, _, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                yield os.path.join(dirpath, name)


def test_models_never_import_parallel():
    """The arrow points one way: ``parallel/`` imports ``models/``."""
    offenders = []
    for path in _models_modules():
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""] + [
                    "%s.%s" % (node.module, a.name) for a in node.names]
            offenders += [(path, node.lineno, n) for n in names
                          if n.startswith("galvatron_tpu.parallel")]
    assert not offenders, offenders


def test_model_config_holds_no_mesh():
    """A model description carries no mesh, axis names or placement."""
    for f in dataclasses.fields(ModelConfig):
        text = ("%s %s %r" % (f.name, f.type, f.default)).lower()
        assert not any(word in text for word in ("mesh", "ctx", "axes", "placement", "shard")), f
