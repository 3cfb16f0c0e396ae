"""The names the program gives its work, and the tracer's second sink and
process hooks (PR 25): kernel ``name=``s, ``jax.named_scope``s in the compiled
step's ``op_name``s, ``TraceAnnotation``s while a profiler window is open, the
``jax.monitoring`` and ``gc`` spans, and the trainer's set-up spans and
``profile_window`` record.  All on the CPU; the chip's compiler sees the same
names in ``tests/test_topology_aot.py``."""

import ast
import gc
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.obs import flight, stepstats, tracing
from galvatron_tpu.obs.tracing import _NULL_SPAN, tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: kernel name -> file that holds its ``pl.pallas_call`` (PERF.md §3's table)
KERNEL_NAMES = {
    "flash_fwd_grid": "flash_attention.py", "flash_fwd_blocked": "flash_attention.py",
    "flash_fwd_qkv": "flash_attention.py", "flash_bwd_blocked": "flash_attention.py",
    "flash_bwd_dkv": "flash_attention.py", "flash_bwd_dq": "flash_attention.py",
    "flash_paged_decode": "flash_attention.py",
    "moe_gmm": "grouped_matmul.py", "moe_gmm_dlhs": "grouped_matmul.py",
    "moe_tgmm": "grouped_matmul.py",
    # the fused Mamba-2 scan (PR 34): read through the `scan` scope they run under
    "ssd_fwd": "ssd.py", "ssd_bwd": "ssd.py", "ssd_decay": "ssd.py", "ssd_decay_bwd": "ssd.py",
    # the conv + SiLU in front of it as one op (PR 40): read through the `conv` scope;
    # not `ssd_*` (tests/test_topology_aot.py holds that list to the scan's four)
    "ssm_conv_fwd": "ssd.py", "ssm_conv_bwd": "ssd.py",
    # the fused gated delta rule (PR 48): read through the `gdn/scan` scope
    # (`gdn_scan_ms_per_step`, `gdn_scan_roofline`)
    "gdn_fwd": "gated_delta.py", "gdn_bwd": "gated_delta.py",
    # a held share's permutations and SwiGLU, bounded by the pairs held (PR 49): read
    # through the `mlp/dispatch`, `mlp/experts`, `mlp/combine` scopes they run under
    "moe_held_rows": "moe_held.py", "moe_held_pairs": "moe_held.py",
    "moe_held_swiglu": "moe_held.py", "moe_held_swiglu_bwd": "moe_held.py",
    # a decode window's latent attention, bounded a row by its length (PR 52): read
    # through the `attn_core` scope it runs under (`mla_attn_ms_per_step`,
    # `mla_decode_attn_roofline`)
    "mla_decode": "mla_decode.py",
    # PR 53: a prompt chunk's latent attention, expansion included (read by
    # `mla_prefill_chunk_attn_ms` under `attn_core` > `expand`)
    "mla_chunk": "mla_prefill.py",
    # PR 55: rows' windows over a head-major K/V stack, bounded a row by its length
    # (read through `full` | `window` > `attn_core`: `full_attn_ms_per_step`,
    # `window_attn_ms_per_step`, `kv_decode_attn_roofline`)
    "kv_decode": "kv_decode.py",
    # PR 66: a prompt chunk over the same stacks, its scores kept on the chip (read by
    # `kv_prefill_chunk_attn_ms` under the PREFILL program's `full` | `window` >
    # `attn_core`; the decode readers take the decode program's operations alone)
    "kv_chunk": "kv_prefill.py",
    # PR 68: a served Mamba-2 layer's single step over the state stack in place (read by
    # name, `ssm_step_roofline`, and under `ssm/step`: `ssm_step_ms_per_step`,
    # `ssm_state_hbm_roofline`), and a prompt chunk's rows of that stack in and out (under
    # the PREFILL program's `ssm/state_read` / `ssm/state_write`)
    "ssm_step": "ssd.py", "ssm_state_read": "ssd.py", "ssm_state_write": "ssd.py",
}


def _pallas_call_names():
    """{file: [names of each ``pl.pallas_call``'s literal ``name=``]}; a call
    without one, or with one that is not made of string literals, fails."""
    ops_dir = os.path.join(REPO, "galvatron_tpu", "ops")
    found = {}
    for fn in sorted(os.listdir(ops_dir)):
        if not fn.endswith(".py"):
            continue
        tree = ast.parse(open(os.path.join(ops_dir, fn)).read())
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                continue
            kw = {k.arg: k.value for k in node.keywords}
            assert "name" in kw, f"{fn}:{node.lineno}: pl.pallas_call without name="
            value = kw["name"]
            literals = ([value.body, value.orelse] if isinstance(value, ast.IfExp) else [value])
            for lit in literals:
                assert isinstance(lit, ast.Constant) and isinstance(lit.value, str), (
                    f"{fn}:{node.lineno}: name= is not a string literal")
                found.setdefault(fn, []).append(lit.value)
    return found


@pytest.mark.parametrize("name", sorted(KERNEL_NAMES))
def test_every_pallas_call_has_a_name_from_the_table(name):
    found = _pallas_call_names()
    assert name in found[KERNEL_NAMES[name]]
    # and nothing outside the table: a new kernel joins it, with its metric
    assert {n for names in found.values() for n in names} == set(KERNEL_NAMES)
    assert all(n.startswith(("flash_fwd", "flash_bwd", "flash_paged",
                             "moe_gmm", "moe_tgmm", "moe_held_", "ssd_", "ssm_conv_", "ssm_step",
                             "ssm_state_", "gdn_",
                             "mla_", "kv_"))
               for n in KERNEL_NAMES)


# ---------------------------------------------------------------------------
# scopes in the compiled step
# ---------------------------------------------------------------------------

SCOPES = ("embed", "layer_0", "layer_1", "attn", "qkv_proj", "attn_core", "out_proj", "mlp",
          "norm", "head", "loss", "optimizer", "grad_accum")


@pytest.fixture(scope="module")
def toy_step_text():
    """Compiled text of a tiny two-layer train step with two micro-batches."""
    from galvatron_tpu.core.checkpoint import abstract_state_of
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import ModelConfig
    from galvatron_tpu.parallel.hybrid import build_runtime
    from galvatron_tpu.parallel.mesh import build_mesh

    cfg = ModelConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=2,
                      ffn_dim=128, max_seq_len=32)
    hp = HybridParallelConfig.uniform(2, chunks=2, mixed_precision="fp32")
    mesh, axes = build_mesh(pp=1, devices=jax.devices()[:1])
    rt = build_runtime(cfg, hp, mesh=mesh, axes=axes, adam=AdamConfig(lr=1e-3),
                       global_batch_size=4, seq_len=32)
    batch = jax.ShapeDtypeStruct((4, 33), jnp.int32, sharding=rt.batch_sharding)
    return rt.train_step.lower(abstract_state_of(rt), batch).compile().as_text()


@pytest.mark.parametrize("scope", SCOPES)
def test_compiled_step_carries_the_scope(toy_step_text, scope):
    import re

    names = re.findall(r'op_name="([^"]*)"', toy_step_text)
    assert any(re.search(rf"[/(]{scope}[/)]", n) for n in names), scope


#: what a dropless top-k MoE layer with qk-norm adds below ``mlp`` and ``attn``
MOE_SCOPES = ("router", "dispatch", "experts", "combine", "qk_norm")


@pytest.fixture(scope="module")
def olmoe_step_text():
    """Lowered text (with locations: the scopes) of the one-layer OLMoE step,
    tiny widths, as ``cli train --model_size olmoe-1b-7b --num_layers 1`` builds it."""
    from galvatron_tpu.core.checkpoint import abstract_state_of
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.parallel.hybrid import build_runtime
    from galvatron_tpu.parallel.mesh import build_mesh

    cfg = PRESETS["olmoe-1b-7b"].replace(vocab_size=128, hidden_size=64, num_layers=1,
                                         num_heads=2, ffn_dim=32, max_seq_len=32,
                                         moe_experts=8, moe_top_k=2)
    hp = HybridParallelConfig.uniform(1, mixed_precision="fp32")
    mesh, axes = build_mesh(pp=1, devices=jax.devices()[:1])
    rt = build_runtime(cfg, hp, mesh=mesh, axes=axes, adam=AdamConfig(lr=1e-3),
                       global_batch_size=4, seq_len=32)
    batch = jax.ShapeDtypeStruct((4, 33), jnp.int32, sharding=rt.batch_sharding)
    return rt.train_step.lower(abstract_state_of(rt), batch).as_text(debug_info=True)


@pytest.mark.parametrize("scope", MOE_SCOPES)
def test_lowered_olmoe_step_carries_the_scope(olmoe_step_text, scope):
    """Each new scope is in the lowered step, forward and backward, below the
    scope ``benchmark/lib/scoped.py`` knows (``mlp``; ``attn`` for ``qk_norm``)."""
    import re

    parent = "attn" if scope == "qk_norm" else "mlp"
    names = set(re.findall(r'"(jit\(train_step\)[^"]*)"', olmoe_step_text))
    mine = [n for n in names if re.search(rf"/{parent}/(?:[^/\"]+/)*{scope}/", n)]
    assert mine, scope
    assert any("transpose(" in n for n in mine), f"{scope}: no backward operation carries it"
    assert all("layer_0" in n for n in mine)


#: what a state-space layer of a hybrid stack opens in place of ``attn``
SSM_SCOPES = ("in_proj", "conv", "scan", "gate_norm", "out_proj")


@pytest.fixture(scope="module")
def granite_step_text():
    """Lowered text (with locations) of a six-layer granite step under full-layer
    recomputation, tiny widths: five state-space layers and the attention layer."""
    from galvatron_tpu.core.checkpoint import abstract_state_of
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.parallel.hybrid import build_runtime
    from galvatron_tpu.parallel.mesh import build_mesh

    cfg = PRESETS["granite-4.0-h-micro"].replace(
        vocab_size=128, hidden_size=64, num_layers=6, num_heads=4, num_kv_heads=2, ffn_dim=96,
        max_seq_len=64, ssm_heads=4, ssm_head_dim=16, ssm_state=16, ssm_chunk=32)
    hp = HybridParallelConfig.uniform(6, ckpt="full", mixed_precision="fp32")
    mesh, axes = build_mesh(pp=1, devices=jax.devices()[:1])
    rt = build_runtime(cfg, hp, mesh=mesh, axes=axes, adam=AdamConfig(lr=1e-3),
                       global_batch_size=2, seq_len=64)
    batch = jax.ShapeDtypeStruct((2, 65), jnp.int32, sharding=rt.batch_sharding)
    lowered = rt.train_step.lower(abstract_state_of(rt), batch)
    return lowered.as_text(), lowered.compile().as_text()


@pytest.mark.parametrize("scope", SSM_SCOPES)
def test_compiled_granite_step_carries_the_scope(granite_step_text, scope):
    """Each of the mixer's scopes is in the compiled step below ``ssm``, forward and
    backward, in a state-space layer and in no attention layer (the layers are one
    function a kind, so the ``layer_<i>`` prefix exists only once its calls are
    inlined: the compiled text, as the profiler's ``op_name``s); ``attn``, ``mlp`` and
    ``norm`` stay what ``benchmark/lib/scoped.py`` knows."""
    import re

    names = {n for n in re.findall(r'op_name="([^"]*)"', granite_step_text[1])
             if n.startswith("jit(train_step)")}
    mine = [n for n in names if re.search(rf"/ssm/(?:[^/\"]+/)*{scope}/", n)]
    assert mine, scope
    assert any("transpose(" in n for n in mine), f"{scope}: no backward operation carries it"
    assert all(re.search(r"layer_[0-4]\b", n) for n in mine)
    assert not any("layer_5" in n for n in names if "/ssm/" in n)
    attn = [n for n in names if "/attn/" in n]
    assert attn and all("layer_5" in n for n in attn)
    assert any("/mlp/" in n and "layer_0" in n for n in names)


#: what a Gated DeltaNet layer opens in place of ``attn`` (the state-space layer's five
#: names, under ``gdn``), what gated attention adds inside ``attn``, and what an expert
#: layer with a shared expert adds beside the four MoE scopes under ``mlp`` (PR 47)
GDN_SCOPES = ("in_proj", "conv", "scan", "gate_norm", "out_proj")


@pytest.fixture(scope="module")
def qwen3_next_step_names():
    """``op_name``s of a compiled four-layer qwen3-next step (one period) under
    full-layer recomputation, tiny widths, rank 1 of 4 of the experts."""
    import re

    from galvatron_tpu.core.checkpoint import abstract_state_of
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.parallel.hybrid import build_runtime
    from galvatron_tpu.parallel.mesh import build_mesh

    cfg = PRESETS["qwen3-next-80b-a3b"].replace(
        vocab_size=128, hidden_size=32, num_layers=4, num_heads=4, num_kv_heads=2,
        attn_head_dim=16, ffn_dim=80, max_seq_len=64, gdn_key_heads=2, gdn_value_heads=4,
        gdn_key_dim=8, gdn_value_dim=8, gdn_chunk=32, moe_experts=16, moe_top_k=4,
        moe_ffn_dim=24, moe_shared_ffn_dim=24, moe_share=(1, 4))
    hp = HybridParallelConfig.uniform(4, ckpt="full", mixed_precision="fp32")
    mesh, axes = build_mesh(pp=1, devices=jax.devices()[:1])
    rt = build_runtime(cfg, hp, mesh=mesh, axes=axes, adam=AdamConfig(lr=1e-3),
                       global_batch_size=2, seq_len=64)
    batch = jax.ShapeDtypeStruct((2, 65), jnp.int32, sharding=rt.batch_sharding)
    text = rt.train_step.lower(abstract_state_of(rt), batch).compile().as_text()
    return {n for n in re.findall(r'op_name="([^"]*)"', text) if n.startswith("jit(train_step)")}


@pytest.mark.parametrize("scope", GDN_SCOPES)
def test_compiled_qwen3_next_step_carries_the_mixers_scope(qwen3_next_step_names, scope):
    import re

    names = qwen3_next_step_names
    mine = [n for n in names if re.search(rf"/gdn/(?:[^/\"]+/)*{scope}/", n)]
    assert mine, scope
    assert any("transpose(" in n for n in mine), f"{scope}: no backward operation carries it"
    assert all(re.search(r"layer_[0-2]\b", n) for n in mine)
    assert not any("layer_3" in n for n in names if "/gdn/" in n)
    assert not any("/ssm/" in n for n in names)


@pytest.mark.parametrize("scope,under,layers", [
    ("gate", "attn", "3"), ("qk_norm", "attn", "3"), ("rope", "attn", "3"),
    ("attn_core", "attn", "3"), ("qkv_proj", "attn", "3"), ("out_proj", "attn", "3"),
    ("router", "mlp", "0-3"), ("dispatch", "mlp", "0-3"), ("experts", "mlp", "0-3"),
    ("combine", "mlp", "0-3"), ("shared_expert", "mlp", "0-3")])
def test_compiled_qwen3_next_step_carries_the_attention_and_expert_scopes(
        qwen3_next_step_names, scope, under, layers):
    import re

    mine = [n for n in qwen3_next_step_names if re.search(rf"/{under}/(?:[^/\"]+/)*{scope}/", n)]
    assert mine, scope
    assert any("transpose(" in n for n in mine), f"{scope}: no backward operation carries it"
    assert all(re.search(rf"layer_[{layers}]\b", n) for n in mine)


def test_hybrid_stack_traces_each_kind_once(granite_step_text):
    """Two layer programs for six layers: the lowered module holds one function a kind
    (and its backward), called from each ``layer_<i>``."""
    import re

    funcs = set(re.findall(r"func\.func private @(_decoder_layer_once\w*)\(", granite_step_text[0]))
    assert 2 <= len(funcs) <= 4, funcs  # forward and backward of each kind, never 6 x


def test_backward_is_marked_by_transpose(toy_step_text):
    import re

    names = re.findall(r'op_name="([^"]*)"', toy_step_text)
    bwd = [n for n in names if "transpose(" in n]
    assert any("layer_0" in n and "attn" in n for n in bwd)
    assert any("optimizer" in n and "transpose(" not in n for n in names)


# ---------------------------------------------------------------------------
# tracer: off costs nothing, on feeds two sinks and the process's hooks
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# scopes in the serving programs (PR 39: the cached forwards carry the step's names)
# ---------------------------------------------------------------------------

SERVING_SCOPES = ("embed", "layer_0/attn/qkv_proj", "layer_0/attn/cache_write",
                  "layer_0/attn/attn_core", "layer_0/attn/out_proj", "layer_0/mlp",
                  "layer_1/attn/cache_write", "head")
SERVING_PROGRAMS = ("serving_decode", "serving_prefill", "serving_decode_verify",
                    "serving_paged_decode", "serving_paged_prefill")


@pytest.fixture(scope="module")
def serving_texts():
    """Compiled text of the engine's declared programs for a toy model, as the
    AOT registry enumerates them (what ``cli serve`` warms and the loop calls)."""
    from galvatron_tpu.aot import registry
    from galvatron_tpu.models.modeling import ModelConfig
    from galvatron_tpu.serving import engine  # noqa: F401  (registers the serving family)

    cfg = ModelConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=2,
                      ffn_dim=128, max_seq_len=32)
    texts = {}
    for blocks in (0, -1):
        ctx = registry.ProgramContext(cfg=cfg, num_slots=2, prefill_chunk=8, max_seq_len=32,
                                      kv_block_size=8, kv_num_blocks=blocks, spec_decode_k=2)
        for spec in registry.enumerate_programs(ctx, include=("serving",)):
            texts[spec.name] = spec.fn.lower(*spec.args).compile().as_text()
    return texts


@pytest.mark.parametrize("scope", SERVING_SCOPES)
@pytest.mark.parametrize("program", SERVING_PROGRAMS)
def test_compiled_serving_program_carries_the_scope(serving_texts, program, scope):
    import re

    names = re.findall(r'op_name="([^"]*)"', serving_texts[program])
    assert any(re.search(rf"[/(]{scope}(?:[/)]|$)", n) for n in names), (program, scope)


#: a stack with sliding-window layers (PR 54): ``window`` | ``full`` between ``attn`` and
#: the scopes above, by the stack the layer's keys and values live in; the expert
#: layer's own under ``mlp`` (the router's GEMV there too, though it reads the
#: attention block's input)
WINDOWED_SCOPES = ("layer_0/attn/full/qkv_proj", "layer_0/attn/full/cache_write",
                   "layer_0/attn/full/attn_core", "layer_0/attn/full/out_proj",
                   "layer_1/attn/window/qkv_proj", "layer_1/attn/window/cache_write",
                   "layer_1/attn/window/attn_core", "layer_1/attn/window/out_proj",
                   "layer_1/mlp/router", "layer_1/mlp/dispatch", "layer_1/mlp/experts",
                   "layer_1/mlp/combine")


@pytest.fixture(scope="module")
def windowed_texts():
    from galvatron_tpu.aot import registry
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.serving import engine  # noqa: F401  (registers the serving family)

    cfg = PRESETS["smallthinker-21b-a3b"].replace(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4, num_kv_heads=2,
        attn_head_dim=8, ffn_dim=24, max_seq_len=32, sliding_window_size=8, moe_experts=8,
        moe_top_k=2, moe_ffn_dim=24)
    ctx = registry.ProgramContext(cfg=cfg, num_slots=2, prefill_chunk=8, max_seq_len=32,
                                  spec_decode_k=2)
    return {spec.name: spec.fn.lower(*spec.args).compile().as_text()
            for spec in registry.enumerate_programs(ctx, include=("serving",))}


@pytest.mark.parametrize("scope", WINDOWED_SCOPES)
@pytest.mark.parametrize("program", SERVING_PROGRAMS[:3])
def test_a_windowed_stacks_serving_program_carries_the_scope(windowed_texts, program, scope):
    import re

    names = re.findall(r'op_name="([^"]*)"', windowed_texts[program])
    assert any(re.search(rf"[/(]{scope}(?:[/)]|$)", n) for n in names), (program, scope)


#: a stack whose conv layers keep a per-row state beside the attention layers' keys and
#: values (PR 58): ``shortconv`` in place of the attention's scopes in a conv layer, ``full``
#: over an attention layer's as in a windowed stack (what `full_attn_ms_per_step`,
#: `kv_prefill_chunk_attn_ms` and `kv_decode_attn_roofline` key on)
STATE_SCOPES = ("layer_0/attn/shortconv/in_proj", "layer_0/attn/shortconv/state_read",
                "layer_0/attn/shortconv/conv", "layer_0/attn/shortconv/state_write",
                "layer_0/attn/shortconv/out_proj", "layer_2/attn/full/qkv_proj",
                "layer_2/attn/full/cache_write", "layer_2/attn/full/attn_core",
                "layer_2/attn/full/out_proj", "layer_2/mlp/router", "layer_2/mlp/experts")


@pytest.fixture(scope="module")
def state_texts():
    from galvatron_tpu.aot import registry
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.serving import engine  # noqa: F401  (registers the serving family)

    cfg = PRESETS["lfm2-24b-a2b"].replace(
        vocab_size=128, hidden_size=32, num_layers=3, num_heads=4, num_kv_heads=2, ffn_dim=48,
        max_seq_len=32, moe_experts=8, moe_top_k=2, moe_ffn_dim=24)
    ctx = registry.ProgramContext(cfg=cfg, num_slots=2, prefill_chunk=8, max_seq_len=32)
    return {spec.name: spec.fn.lower(*spec.args).compile().as_text()
            for spec in registry.enumerate_programs(ctx, include=("serving",))}


#: a stack whose Mamba-2 layers keep a conv tail and a scan state a row (PR 68): ``ssm`` in
#: place of the attention's scopes, ``step`` in the decode program where the prompt chunk
#: has ``scan``; the un-gated activation under the experts' scope (what the ``ssm_*`` serving
#: readers and `serve_expert_ms_per_step` key on)
SSM_SERVING_SCOPES = ("layer_0/attn/ssm/in_proj", "layer_0/attn/ssm/state_read",
                      "layer_0/attn/ssm/conv", "layer_0/attn/ssm/gate_norm",
                      "layer_0/attn/ssm/out_proj", "layer_0/attn/ssm/state_write",
                      "layer_0/mlp/experts/relu2", "layer_0/mlp/shared_expert",
                      "layer_3/attn/full/attn_core")


@pytest.fixture(scope="module")
def ssm_serving_texts():
    from galvatron_tpu.aot import registry
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.serving import engine  # noqa: F401  (registers the serving family)

    cfg = PRESETS["nemotron-3-nano-30b-a3b"].replace(
        vocab_size=128, hidden_size=32, num_layers=4, num_heads=4, num_kv_heads=2,
        attn_head_dim=8, ffn_dim=24, max_seq_len=32, ssm_heads=8, ssm_head_dim=4, ssm_state=8,
        ssm_groups=4, ssm_chunk=8, moe_experts=8, moe_top_k=2, moe_ffn_dim=24,
        moe_shared_ffn_dim=40)
    ctx = registry.ProgramContext(cfg=cfg, num_slots=2, prefill_chunk=8, max_seq_len=32)
    return {spec.name: spec.fn.lower(*spec.args).compile().as_text()
            for spec in registry.enumerate_programs(ctx, include=("serving",))}


@pytest.mark.parametrize("scope", SSM_SERVING_SCOPES + ("layer_0/attn/ssm/step",
                                                        "layer_0/attn/ssm/scan"))
@pytest.mark.parametrize("program", SERVING_PROGRAMS[:2])
def test_a_mamba_stacks_serving_program_carries_the_scope(ssm_serving_texts, program, scope):
    import re

    names = re.findall(r'op_name="([^"]*)"', ssm_serving_texts[program])
    has = any(re.search(rf"[/(]{scope}(?:[/)]|$)", n) for n in names)
    if scope.endswith(("/step", "/scan")):  # the decode step has `step`, the chunk `scan`
        assert has == (scope.endswith("/step") == ("decode" in program)), (program, scope)
    else:
        assert has, (program, scope)
    assert not [n for n in names if "/shortconv/" in n or "/window/" in n], program
    # a layer of its mixer alone opens no `mlp`
    assert not [n for n in names if "layer_2/mlp" in n], program


#: a served Granite 4.0-H Small stack (PR 70): a ROUTED MLP behind every mixer, so both
#: programs carry the ``ssm`` scopes AND, under ``mlp``, the five the expert readers key on
#: (`serve_expert_ms_per_step`); the shared SwiGLU
#: expert's scope is ``shared_expert``
GRANITE_SMALL_SCOPES = ("layer_0/attn/ssm/in_proj", "layer_0/attn/ssm/state_read",
                        "layer_0/attn/ssm/conv", "layer_0/attn/ssm/gate_norm",
                        "layer_0/attn/ssm/out_proj", "layer_0/attn/ssm/state_write",
                        "layer_0/mlp/router", "layer_0/mlp/dispatch", "layer_0/mlp/experts",
                        "layer_0/mlp/combine", "layer_0/mlp/shared_expert",
                        "layer_5/attn/full/attn_core", "layer_5/mlp/experts")


@pytest.fixture(scope="module")
def granite_small_texts():
    from galvatron_tpu.aot import registry
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.serving import engine  # noqa: F401  (registers the serving family)

    cfg = PRESETS["granite-4.0-h-small"].replace(
        vocab_size=128, hidden_size=32, num_layers=6, num_heads=4, num_kv_heads=2, ffn_dim=24,
        max_seq_len=32, ssm_heads=8, ssm_head_dim=4, ssm_state=8, ssm_chunk=8, moe_experts=8,
        moe_top_k=3, moe_ffn_dim=24, moe_shared_ffn_dim=40, moe_share=(0, 2))
    ctx = registry.ProgramContext(cfg=cfg, num_slots=2, prefill_chunk=8, max_seq_len=32)
    return {spec.name: spec.fn.lower(*spec.args).compile().as_text()
            for spec in registry.enumerate_programs(ctx, include=("serving",))}


@pytest.mark.parametrize("scope", GRANITE_SMALL_SCOPES + ("layer_0/attn/ssm/step",
                                                          "layer_0/attn/ssm/scan"))
@pytest.mark.parametrize("program", SERVING_PROGRAMS[:2])
def test_a_served_granite_stacks_program_carries_the_scope(granite_small_texts, program, scope):
    import re

    names = re.findall(r'op_name="([^"]*)"', granite_small_texts[program])
    has = any(re.search(rf"[/(]{scope}(?:[/)]|$)", n) for n in names)
    if scope.endswith(("/step", "/scan")):  # the decode step has `step`, the chunk `scan`
        assert has == (scope.endswith("/step") == ("decode" in program)), (program, scope)
    else:
        assert has, (program, scope)


@pytest.mark.parametrize("scope", STATE_SCOPES)
@pytest.mark.parametrize("program", SERVING_PROGRAMS[:2])
def test_a_state_stacks_serving_program_carries_the_scope(state_texts, program, scope):
    import re

    names = re.findall(r'op_name="([^"]*)"', state_texts[program])
    assert any(re.search(rf"[/(]{scope}(?:[/)]|$)", n) for n in names), (program, scope)
    assert not [n for n in names if "/window/" in n], program


def test_the_training_forward_opens_the_conv_layers_three_scopes():
    import re

    import jax

    from galvatron_tpu.models import modeling
    from galvatron_tpu.models.modeling import PRESETS

    cfg = PRESETS["lfm2-24b-a2b"].replace(
        vocab_size=128, hidden_size=32, num_layers=3, num_heads=4, num_kv_heads=2, ffn_dim=48,
        max_seq_len=32, moe_experts=8, moe_top_k=2, moe_ffn_dim=24)
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, 16), "int32")
    text = jax.jit(lambda p, t: modeling.forward(p, t, cfg)).lower(shapes, tokens).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("in_proj", "conv", "out_proj"):
        assert any(f"layer_0/shortconv/{scope}" in n for n in names), scope
    assert not [n for n in names if "state_read" in n or "state_write" in n]


#: gated attention under sandwich norms (PR 61): ``gate`` between ``attn_core`` and
#: ``out_proj`` under ``window`` | ``full``; ``post_attn_norm`` behind the attention,
#: ``post_mlp_norm`` behind the MLP (the names a reader of the sandwich norms would key on)
GATED_SCOPES = ("layer_0/attn/window/gate", "layer_3/attn/full/gate",
                "layer_0/attn/post_attn_norm", "layer_3/attn/post_attn_norm",
                "layer_0/post_mlp_norm", "layer_1/post_mlp_norm", "layer_1/mlp/shared_expert",
                "layer_3/attn/full/attn_core", "layer_4/attn/window/cache_write")


def _gated_cfg():
    from galvatron_tpu.models.modeling import PRESETS

    return PRESETS["trinity-large-preview"].replace(
        vocab_size=128, hidden_size=32, num_layers=5, num_heads=4, num_kv_heads=2,
        attn_head_dim=8, ffn_dim=48, max_seq_len=32, sliding_window_size=8, moe_experts=8,
        moe_top_k=2, moe_ffn_dim=24, moe_shared_ffn_dim=24, moe_dense_layers=1)


@pytest.fixture(scope="module")
def gated_texts():
    from galvatron_tpu.aot import registry
    from galvatron_tpu.serving import engine  # noqa: F401  (registers the serving family)

    ctx = registry.ProgramContext(cfg=_gated_cfg(), num_slots=2, prefill_chunk=8, max_seq_len=32)
    return {spec.name: spec.fn.lower(*spec.args).compile().as_text()
            for spec in registry.enumerate_programs(ctx, include=("serving",))}


@pytest.mark.parametrize("scope", GATED_SCOPES)
@pytest.mark.parametrize("program", SERVING_PROGRAMS[:2])
def test_a_gated_sandwich_stacks_serving_program_carries_the_scope(gated_texts, program, scope):
    import re

    names = re.findall(r'op_name="([^"]*)"', gated_texts[program])
    assert any(re.search(rf"[/(]{scope}(?:[/)]|$)", n) for n in names), (program, scope)


#: a latent stack with an indexer and a latent ring (PR 65): ``indexer`` and ``select``
#: beside ``attn_core`` under ``full``, the headwise ``gate``, the ring's ``cache_write``
#: under ``window`` (the names ``benchmark/metrics/_dsa.py`` and ``_swa.py`` key on)
DSA_SCOPES = ("layer_0/attn/full/qkv_proj", "layer_1/attn/full/indexer",
              "layer_1/attn/full/select", "layer_1/attn/full/attn_core",
              "layer_1/attn/full/gate", "layer_1/attn/full/cache_write",
              "layer_2/attn/window/cache_write", "layer_2/attn/window/attn_core",
              "layer_4/attn/window/gate", "layer_4/attn/window/out_proj")


def _dsa_cfg():
    from galvatron_tpu.models.modeling import PRESETS

    return PRESETS["dots3-note-prev"].replace(
        vocab_size=128, hidden_size=32, num_layers=5, num_heads=4, attn_head_dim=12, ffn_dim=48,
        max_seq_len=32, mla_kv_rank=16, mla_nope_dim=8, mla_rope_dim=4, mla_v_dim=8,
        mla_q_rank=24, mla_index_heads=4, mla_index_dim=8, mla_index_topk=8,
        sliding_window_size=8, swa_num_heads=2, swa_nope_dim=12, swa_rope_dim=4, swa_v_dim=8,
        swa_kv_rank=24, swa_q_rank=20, moe_experts=8, moe_top_k=2, moe_ffn_dim=24,
        moe_shared_ffn_dim=24)


@pytest.fixture(scope="module")
def dsa_texts():
    from galvatron_tpu.aot import registry
    from galvatron_tpu.serving import engine  # noqa: F401  (registers the serving family)

    ctx = registry.ProgramContext(cfg=_dsa_cfg(), num_slots=2, prefill_chunk=8, max_seq_len=32)
    return {spec.name: spec.fn.lower(*spec.args).compile().as_text()
            for spec in registry.enumerate_programs(ctx, include=("serving",))}


@pytest.mark.parametrize("scope", DSA_SCOPES)
@pytest.mark.parametrize("program", SERVING_PROGRAMS[:2])
def test_a_sparse_latent_stacks_serving_program_carries_the_scope(dsa_texts, program, scope):
    import re

    names = re.findall(r'op_name="([^"]*)"', dsa_texts[program])
    # (the scope's parts in order: a prompt chunk's ``indexer`` scores and ``select`` lie in a
    # branch of a ``cond``, whose own words stand between ``full`` and theirs)
    pattern = "[/(]" + "/(?:[^/]+/)*?".join(map(re.escape, scope.split("/"))) + "(?:[/)]|$)"
    assert any(re.search(pattern, n) for n in names), (program, scope)


def test_the_training_forward_opens_the_gate_and_the_post_norms(serving_texts):
    import re

    import jax

    from galvatron_tpu.models import modeling

    cfg = _gated_cfg()
    shapes = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, 16), "int32")
    text = jax.jit(lambda p, t: modeling.forward(p, t, cfg)).lower(shapes, tokens).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("layer_2/attn/gate", "layer_3/post_attn_norm", "layer_0/post_mlp_norm",
                  "layer_4/post_mlp_norm"):
        assert any(scope in n for n in names), scope
    # and no other stack's program carries any of the three
    for program, plain in serving_texts.items():
        plain_names = re.findall(r'op_name="([^"]*)"', plain)
        assert not [n for n in plain_names
                    if re.search(r"/(gate|post_attn_norm|post_mlp_norm)(/|$)", n)], program


def test_a_plain_stacks_serving_programs_carry_neither_window_nor_full(serving_texts):
    """The two scopes are a windowed stack's alone: the accepted cells' op names, the
    recorded fixtures and ``lib/scoped.SCOPES`` stay as they are."""
    import re

    for program, text in serving_texts.items():
        names = re.findall(r'op_name="([^"]*)"', text)
        assert not [n for n in names if "/window/" in n or "/full/" in n], program
        assert not [n for n in names if "/shortconv/" in n], program


@pytest.fixture()
def traced():
    assert not tracer.enabled
    tracer.enable(capacity=4096)
    try:
        yield tracer
    finally:
        tracer.disable()
        tracer.clear()


def test_tracer_off_has_no_hook_and_no_annotation(monkeypatch):
    assert not tracer.enabled
    assert tracer._on_gc not in gc.callbacks
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: pytest.fail("annotation while off"))
    tracer.profiling = True  # a window open, the tracer off: still nothing
    try:
        assert tracer.span("step", step=1) is _NULL_SPAN
        tracing._install_jax_listeners()
        jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(7)).block_until_ready()
        gc.collect()
        tracer.record_span("jax_compile", 0.5)
    finally:
        tracer.profiling = False
    assert tracer.snapshot() == []


def test_gc_callback_lives_while_the_tracer_is_on(traced, monkeypatch):
    assert traced._on_gc in gc.callbacks
    monkeypatch.setattr(tracing, "GC_SPAN_MIN_S", 0.0)
    with traced.span("step", step=5):
        gc.collect()
    spans = [r for r in traced.snapshot() if r["name"] == "gc"]
    assert spans and spans[-1]["args"]["generation"] == 2 and spans[-1]["args"]["step"] == 5
    traced.disable()
    assert traced._on_gc not in gc.callbacks
    traced.enable()  # the fixture's disable() finds its callback again


def test_short_collections_leave_no_span(traced):
    gc.collect()  # settle, then a young-generation pass of an empty heap
    traced.clear()
    gc.collect(0)
    assert [r for r in traced.snapshot() if r["name"] == "gc" and r["dur"] < 1e3] == []


def test_jax_listeners_record_trace_lower_compile_with_the_open_step(traced, monkeypatch):
    monkeypatch.setattr(tracing, "JAX_TRACE_SPAN_MIN_S", 0.0)
    with traced.span("step", step=3):
        with traced.span("fwd_bwd", step=3):
            jax.jit(lambda x: jnp.sin(x) * 2.5 + 0.125)(jnp.ones(11)).block_until_ready()
    recs = traced.snapshot()
    for name in ("jax_trace", "jax_lower", "jax_compile"):
        mine = [r for r in recs if r["name"] == name]
        assert mine, name
        assert all(r["args"]["step"] == 3 for r in mine)
        assert all(r["ph"] == "X" and r["dur"] > 0 for r in mine)
    compile_args = [r["args"] for r in recs if r["name"] == "jax_compile"][-1]
    assert {"hit", "retrieval_s", "fun_name"} <= set(compile_args)
    # a span reported after the fact ends at the report: inside the step
    step = next(r for r in recs if r["name"] == "step")
    last = [r for r in recs if r["name"] == "jax_compile"][-1]
    assert step["ts"] <= last["ts"] and last["ts"] + last["dur"] <= step["ts"] + step["dur"] + 1.0
    # before any loop: no step
    jax.jit(lambda x: jnp.cos(x) - 0.375)(jnp.ones(13)).block_until_ready()
    assert "step" not in traced.snapshot()[-1]["args"]


def test_a_warm_call_records_nothing(traced):
    f = jax.jit(lambda x: x * 1.75 - 2.0)
    x = jnp.ones(17)
    f(x).block_until_ready()
    traced.clear()
    for _ in range(3):
        f(x).block_until_ready()
    assert [r["name"] for r in traced.snapshot()] == []


def test_spans_open_annotations_only_while_a_window_is_open(traced, monkeypatch):
    seen = []

    class Spy:
        def __init__(self, name, **kw):
            self.rec = (type(self).__name__, name, kw)

        def __enter__(self):
            seen.append(("enter",) + self.rec)

        def __exit__(self, *exc):
            seen.append(("exit",) + self.rec)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", type("TraceAnnotation", (Spy,), {}))
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation",
                        type("StepTraceAnnotation", (Spy,), {}))
    with traced.span("step", step=7):
        pass
    assert seen == []
    traced.profiling = True
    with traced.span("step", step=8):
        with traced.span("sync", step=8):
            pass
    traced.profiling = False
    assert seen == [
        ("enter", "StepTraceAnnotation", "train", {"step_num": 8}),
        ("enter", "TraceAnnotation", "sync", {}),
        ("exit", "TraceAnnotation", "sync", {}),
        ("exit", "StepTraceAnnotation", "train", {"step_num": 8}),
    ]
    assert [r["name"] for r in traced.snapshot()] == ["step", "sync", "step"]


def test_profiler_window_flags_the_tracer_and_keeps_its_record(monkeypatch, tmp_path):
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    run = tmp_path / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(b"")
    pw = flight.ProfilerWindow(str(tmp_path), 2, 4)
    pw.maybe_start(1)
    assert not tracer.profiling
    pw.maybe_start(2)
    assert tracer.profiling and pw.maybe_stop(2) is None
    rec = pw.maybe_stop(3, verbose=False)
    assert not tracer.profiling
    assert rec == flight.last_profile_window() == {
        "trace_dir": str(tmp_path), "xplane": str(run / "host.xplane.pb"),
        "start_step": 2, "stop_step": 4, "first_step": 2, "last_step": 3}
    assert pw.close() is None  # closed once


def test_a_span_reported_on_a_named_track_is_no_threads(traced):
    with traced.span("iteration", step=4):
        traced.record_span("queue_wait", 0.25, track="serving queue", rid=9)
        traced.record_span("gc", 0.002)
    wait, own, _ = traced.snapshot()
    assert (wait["tid"], wait["tname"], wait["depth"]) == (
        tracing._track_tid("serving queue"), "serving queue", 0)
    assert wait["tid"] != own["tid"] == threading.get_ident() and own["depth"] == 1
    assert wait["args"] == {"rid": 9, "step": 4} and wait["dur"] == pytest.approx(0.25e6)
    assert tracing._track_tid("serving queue") != tracing._track_tid("another")


def test_an_iteration_span_is_the_serving_step_boundary():
    assert tracing._annotation("iteration", {"step": 12}).__class__ is (
        jax.profiler.StepTraceAnnotation)
    # without a number, and under any other name, a plain annotation
    assert tracing._annotation("iteration", {}).__class__ is jax.profiler.TraceAnnotation
    assert tracing._annotation("sample", {"step": 12}).__class__ is jax.profiler.TraceAnnotation


@pytest.mark.parametrize("ending", ["reached", "timed_out", "raised"])
def test_capture_profile_is_one_profiler_window(monkeypatch, tmp_path, ending):
    """What ``POST /profile`` runs: the tracer is flagged while the capture is
    open, whatever ends it, and where it went is kept with the steps covered."""
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    run = tmp_path / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(b"")
    inside = []
    steps = iter([40, 40, 41, 43, 43] if ending == "reached" else [40, 40, 41, 41, 41, 41])

    def counter():
        inside.append(tracer.profiling)
        if ending == "raised" and len(inside) == 3:
            raise OSError("the engine went away")
        return next(steps, 41)

    kw = dict(timeout_s=30.0 if ending == "reached" else 0.05, poll_s=0.0)
    if ending == "raised":
        with pytest.raises(OSError):
            flight.capture_profile(str(tmp_path), 3, counter, timeout_s=30.0)
        # the finally's own reading raised nothing more: the window is closed
    else:
        out = flight.capture_profile(str(tmp_path), 3, counter, **kw)
        assert out == {"trace_dir": str(tmp_path), "xplane": str(run / "host.xplane.pb"),
                       "steps_captured": 3 if ending == "reached" else 1, "requested": 3,
                       "timed_out": ending == "timed_out"}
    assert inside[0] is False and all(inside[1:]) and not tracer.profiling
    win = flight.last_profile_window()
    assert win["xplane"] == str(run / "host.xplane.pb") and win["first_step"] == 40
    assert win["last_step"] == {"reached": 42, "timed_out": 40, "raised": 40}[ending]


def test_capture_profile_without_a_profiler_raises_and_flags_nothing(monkeypatch, tmp_path):
    def no_xprof(d):
        raise ValueError("no xprof")

    monkeypatch.setattr(jax.profiler, "start_trace", no_xprof)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        flight.capture_profile(str(tmp_path), 1, lambda: 0)
    assert not tracer.profiling


# ---------------------------------------------------------------------------
# the trainer end to end
# ---------------------------------------------------------------------------

TINY_TRAIN = [
    "--model_size", "llama-0.3b", "--num_layers", "2", "--hidden_size", "64",
    "--num_heads", "4", "--vocab_size", "256", "--seq_length", "32",
    "--global_train_batch_size", "8", "--mixed_precision", "fp32",
]


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    from galvatron_tpu.core.arguments import initialize_galvatron
    from galvatron_tpu.core.trainer import train
    from galvatron_tpu.data.shards import write_sharded_dataset

    tmp = tmp_path_factory.mktemp("traced_run")
    rng = np.random.default_rng(0)
    docs = [rng.integers(0, 256, size=200, dtype=np.int64) for _ in range(64)]
    prefix = str(tmp / "corpus")
    write_sharded_dataset(prefix, docs, 256)
    spans, mpath = str(tmp / "spans.json"), str(tmp / "m.jsonl")
    train(initialize_galvatron("train", TINY_TRAIN + [
        "--train_iters", "6", "--trace_spans", spans, "--metrics_path", mpath,
        "--data_path", prefix, "--prefetch_depth", "2", "--profile_steps", "2:4"]),
        verbose=False)
    events = json.load(open(spans))["traceEvents"]
    records = [json.loads(line) for line in open(mpath)]
    return events, records


@pytest.mark.parametrize("name", ["build_runtime", "init_state", "data_open", "jax_trace",
                                  "jax_lower", "jax_compile", "data_produce"])
def test_traced_train_exports_the_span(traced_run, name):
    events, _ = traced_run
    mine = [e for e in events if e["ph"] == "X" and e["name"] == name]
    assert mine, name
    steps = {e["args"].get("step") for e in mine}
    if name in ("build_runtime", "init_state", "data_open"):
        assert len(mine) == 1 and steps == {None}
    if name == "data_produce":
        # its own track, its own index, never a step
        main_tid = next(e["tid"] for e in events if e["name"] == "step")
        assert steps == {None} and all(e["tid"] != main_tid for e in mine)
        assert [e["args"]["batch"] for e in mine][:3] == [0, 1, 2]
    if name == "jax_compile":
        # the step program compiles (or loads) inside the call's first step
        assert 0 in steps


def test_build_runtime_span_counts_the_seams_by_name(traced_run):
    """``tp_overlap_seams`` on the ``build_runtime`` span: the three keys a
    reader may count on (ring / plain / batchwise; PERF.md §3), all 0 without
    tensor parallelism."""
    events, _ = traced_run
    (span,) = [e for e in events if e["ph"] == "X" and e["name"] == "build_runtime"]
    assert span["args"]["tp_overlap_seams"] == {"ring": 0, "plain": 0, "batchwise": 0}
    # and the scan of its state-space layers (PR 34): a stack with none counts none
    # (9 / 0 in the granite cell: tests/test_ssm.py holds the count to `ops/ssd.scan_path`)
    assert span["args"]["ssm_scan_path"] == {"fused": 0, "plain": 0}


def test_build_runtime_span_counts_the_conv_path_beside_the_scan_path(traced_run):
    """``ssm_conv_path`` (PR 40) on the same span, with the same two keys: which
    conv the state-space layers take, by `ops/ssd.conv_path` (9 / 0 in the granite
    cell: tests/test_ssm.py); a stack with no such layer counts none."""
    events, _ = traced_run
    (span,) = [e for e in events if e["ph"] == "X" and e["name"] == "build_runtime"]
    assert span["args"]["ssm_conv_path"] == {"fused": 0, "plain": 0}
    assert list(span["args"]).index("ssm_conv_path") == list(span["args"]).index("ssm_scan_path") + 1
    # and the same two for Gated DeltaNet layers (PR 47), behind them: none here
    assert span["args"]["gdn_scan_path"] == span["args"]["gdn_conv_path"] == {"fused": 0, "plain": 0}
    # (between them since PR 70: the body a SERVED row's single step takes,
    # `ops/ssd.step_path`; none here)
    assert span["args"]["ssm_step_path"] == {"fused": 0, "plain": 0}
    assert list(span["args"]).index("ssm_step_path") == list(span["args"]).index("ssm_conv_path") + 1
    assert list(span["args"]).index("gdn_scan_path") == list(span["args"]).index("ssm_step_path") + 1
    # and the path a held share of the experts takes (PR 49), behind those: no share here
    assert span["args"]["moe_held_path"] == {"bounded": 0, "worst_case": 0}
    # (between them since PR 58: the gated short convolution's one body, none here)
    assert span["args"]["shortconv_conv_path"] == {"fused": 0, "plain": 0}
    assert list(span["args"]).index("shortconv_conv_path") == list(span["args"]).index("gdn_conv_path") + 1
    assert list(span["args"]).index("moe_held_path") == list(span["args"]).index("shortconv_conv_path") + 1


def test_traced_train_logs_the_profile_window(traced_run):
    _, records = traced_run
    recs = [r for r in records if r["event"] == "profile_window"]
    assert len(recs) == 1
    rec = recs[0]
    assert (rec["start_step"], rec["stop_step"], rec["first_step"], rec["last_step"]) == (2, 4, 2, 3)
    assert os.path.basename(rec["xplane"]).endswith(".xplane.pb")
    assert rec["trace_dir"] == flight.last_profile_window()["trace_dir"]
    assert not tracer.enabled and not tracer.profiling and tracer._on_gc not in gc.callbacks


def test_train_iter_records_of_a_topk_moe_run_carry_aux_loss_and_load(tmp_path):
    """``moe_aux_loss`` and ``moe_load_max_over_mean`` ride the ``train_iter``
    record of a dropless top-k MoE model, beside a ``loss`` that is the cross
    entropy alone; a dense model's record has neither."""
    from galvatron_tpu.core.arguments import initialize_galvatron
    from galvatron_tpu.core.trainer import train

    mpath = str(tmp_path / "m.jsonl")
    out = train(initialize_galvatron("train", [
        "--model_size", "olmoe-1b-7b", "--num_layers", "1", "--hidden_size", "64",
        "--num_heads", "2", "--ffn_dim", "32", "--moe_experts", "8", "--vocab_size", "128",
        "--seq_length", "32", "--global_train_batch_size", "8", "--mixed_precision", "fp32",
        "--train_iters", "3", "--metrics_path", mpath]), verbose=False)
    assert out["runtime"].cfg.moe_dropless and out["runtime"].cfg.moe_top_k == 8
    iters = [r for r in map(json.loads, open(mpath)) if r["event"] == "train_iter"]
    assert len(iters) == 3
    for r in iters:
        assert 0.5 < r["moe_aux_loss"] < 64 and r["moe_load_max_over_mean"] >= 1.0
        assert abs(r["loss"] - np.log(128)) < 1.0  # the cross entropy, no auxiliary term in it


@pytest.mark.parametrize("hidden,width,path", [(128, 128, "bounded"), (64, 32, "worst_case")])
def test_a_held_share_names_its_path_and_its_rows_share(tmp_path, monkeypatch, hidden, width, path):
    """``moe_held_path`` on the ``build_runtime`` span and in the checkpoint's
    fingerprint, ``moe_held_rows_share`` beside ``moe_held_pairs_per_token`` in every
    ``train_iter`` record of a model that holds a share of its experts (PR 49)."""
    from galvatron_tpu.core.arguments import initialize_galvatron
    from galvatron_tpu.core.checkpoint import latest_step, read_manifest, step_path
    from galvatron_tpu.core.trainer import train
    from galvatron_tpu.models.modeling import PRESETS

    # (the head, DeltaNet and expert sizes have no flag: the test narrows the preset)
    monkeypatch.setitem(PRESETS, "qwen3-next-80b-a3b", PRESETS["qwen3-next-80b-a3b"].replace(
        attn_head_dim=16, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16, gdn_value_dim=16,
        moe_top_k=4, moe_ffn_dim=width, moe_shared_ffn_dim=width))
    mpath, spans, ckpt = (str(tmp_path / n) for n in ("m.jsonl", "spans.json", "ckpt"))
    train(initialize_galvatron("train", [
        "--model_size", "qwen3-next-80b-a3b", "--moe_share", "1/4", "--num_layers", "1",
        "--hidden_size", str(hidden), "--num_heads", "2", "--moe_experts", "16",
        "--vocab_size", "128", "--seq_length", "32", "--global_train_batch_size", "8",
        "--mixed_precision", "fp32", "--train_iters", "2", "--metrics_path", mpath,
        "--trace_spans", spans, "--save", ckpt]), verbose=False)
    want = {"bounded": 0, "worst_case": 0, path: 1}
    (span,) = [e for e in json.load(open(spans))["traceEvents"]
               if e["ph"] == "X" and e["name"] == "build_runtime"]
    assert span["args"]["moe_held_path"] == want
    manifest = read_manifest(step_path(ckpt, latest_step(ckpt)))
    assert manifest["meta"]["fingerprint"]["moe_held_path"] == want
    iters = [r for r in map(json.loads, open(mpath)) if r["event"] == "train_iter"]
    assert len(iters) == 2
    from galvatron_tpu.ops.grouped_matmul import row_tile

    # a device's 32 tokens x 4 pairs over 16 scored experts are 8 rows an expert: float32's
    # floor tile (`row_tile`, PR 57; 256 until then: one tile of pairs + 5 of the groups = 1,536
    # rows, 4 of 6 in use whatever they hold). The buffer has 128 + 5 x 8 rows; the 4 held
    # experts' tiles are in use whatever they hold, every held pair's row is, and an expert
    # wastes less than a tile: (the mean over the devices keeps the three, all linear)
    assert row_tile(32, 4, 16, jnp.float32) == 8
    for r in iters:
        assert 0.0 < r["moe_held_pairs_per_token"] < 4.0
        pairs = r["moe_held_pairs_per_token"] * 32
        assert max(4 * 8, pairs) / 168 - 1e-6 <= r["moe_held_rows_share"] <= (pairs + 4 * 8) / 168


def test_train_iter_records_lost_the_derived_wait(traced_run):
    _, records = traced_run
    iters = [r for r in records if r["event"] == "train_iter"]
    assert len(iters) == 6
    assert not any(k in r for r in iters for k in (
        "comm_wait_ms", "bubble_fraction", "moe_aux_loss", "moe_load_max_over_mean"))


# ---------------------------------------------------------------------------
# the program's FLOP count is the benchmark's
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("config,seq", [("baichuan-7b", 4096), ("baichuan-7b", 512),
                                        ("opt-1.3b", 2048)])
def test_model_flops_per_token_equal_the_benchmarks(config, seq):
    import sys

    sys.path.insert(0, REPO)
    from benchmark.lib import flops, reference
    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args

    doc = json.load(open(os.path.join(REPO, "benchmark", "configs", f"{config}.json")))
    arch = reference.load(REPO, doc["model_type"])
    cfg = model_config_from_args(initialize_galvatron(
        "train", doc["program_flags"] + ["--seq_length", str(seq)]))
    st = stepstats.StepStats(cfg, 4, seq, num_devices=1)
    mine = st.model_flops_per_step / st.tokens_per_step
    assert mine == pytest.approx(flops.model_flops_per_token(arch, doc, seq), rel=1e-12)


def test_attention_core_counts_what_the_mask_keeps():
    from galvatron_tpu.models.modeling import ModelConfig

    cfg = ModelConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4, max_seq_len=32)
    assert stepstats.attn_core_flops_per_token(cfg, 32) == 4.0 * 64 * 33 / 2
    assert stepstats.attn_core_flops_per_token(cfg.replace(causal=False), 32) == 4.0 * 64 * 32
