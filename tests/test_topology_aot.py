"""REAL-TPU compile validation, no chip needed: topology-only AOT.

The CPU simulation runs Pallas kernels in interpret mode (plain jnp ops
GSPMD can partition), so it can never catch a kernel the chip's compiler
refuses — a block shape Mosaic cannot tile, too much VMEM, a kernel that
is not partitionable on a multi-device mesh ("Mosaic kernels cannot be
automatically partitioned", what LayerPlacement.shard_kernel exists for) —
or a step program that does not fit the device's memory.  These tests
AOT-compile for a described, not attached, v5e:2x2
(jax.experimental.topologies): the real TPU compiler and the real Mosaic
lowering.  Nothing runs, so they say nothing about results or times.

Code that asks ``jax.default_backend()`` still sees the CPU here, so every
test steers the kernels' interpret switch off by monkeypatch
(``real_mosaic``) and asserts the kernel is IN the compiled text
(``tpu_custom_call``) — a compile that silently took the interpret path
proves nothing.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold libtpu, and under pytest-xdist every worker
imports every test file (/opt/skills/guides/on-chip-measurement §2).  Keep
all such tests in THIS file so they land on one worker.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_smoke import HBM_V5E_GIB


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def real_mosaic(monkeypatch):
    """Lower the real kernels (interpret off: the ONE switch every kernel module
    and every choice of a kernel over its plain body asks) with the persistent
    cache off: a described-device executable is written to the cache but cannot
    be read back without a chip, and the next run would warn on every such entry."""
    from galvatron_tpu.aot.cache import persistent_cache_off
    from tests._stack_harness import on_a_chip

    on_a_chip(monkeypatch)
    with persistent_cache_off():
        yield


def _kernel_text(fn, *avals) -> str:
    text = jax.jit(fn).lower(*avals).compile().as_text()
    assert "tpu_custom_call" in text, "the compiled program holds no Mosaic kernel"
    return text


def _compile(cfg, hp, devices, bsz=8, seq=512):
    from galvatron_tpu.core.checkpoint import abstract_state_of
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.parallel.hybrid import build_runtime
    from galvatron_tpu.parallel.mesh import build_mesh

    mesh, axes = build_mesh(pp=hp.pp, devices=list(devices))
    rt = build_runtime(
        cfg, hp, mesh=mesh, axes=axes, adam=AdamConfig(lr=1e-3),
        global_batch_size=bsz, seq_len=seq,
    )
    batch = jax.ShapeDtypeStruct((bsz, seq + 1), jnp.int32, sharding=rt.batch_sharding)
    compiled = rt.train_step.lower(abstract_state_of(rt), batch).compile()
    if cfg.attn_impl == "flash":
        assert "tpu_custom_call" in compiled.as_text(), (
            "attn_impl='flash' but the compiled step holds no Mosaic kernel"
        )
    return compiled, compiled.memory_analysis()


# --- the main path's kernels at llama-7b widths (tier-1, ~2 s each) --------

B, S, H, KV, D = 4, 2048, 32, 8, 128


def _rope_avals(one_chip):
    t = jax.ShapeDtypeStruct((S, D // 2), jnp.float32, sharding=one_chip)
    return t, t


#: what one opt-1.3b layer hands the kernels on a chip of the four-chip cell's
#: plan (tp 4, 4 micro-batches): no RoPE, 8 of 32 heads, d 64
OPT_B, OPT_H, OPT_D = 4, 8, 64

def _kernel_names(text):
    """The flash kernels among the compiled ENTRY's instructions, by their
    ``pallas_call(name=)`` (bare autodiff prefixes ``jvp_`` / ``transpose_jvp_``
    to it, a scoped step program does not; XLA appends ``.<n>``)."""
    import re

    found = [re.search(r"flash_(?:fwd|bwd)_[a-z]+", n) for n, _ in _entry_work(text)]
    return [m.group(0) for m in found if m]


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("entry", ["qkv", "hm", "hm_gqa", "opt_qkv", "opt_hm"])
def test_flash_kernels_compile_at_7b_width(entry, grad, one_chip, real_mosaic):
    """The two entries modeling._attn_block_headmajor dispatches to — stacked
    qkv (MHA) and head-major (here MHA and the 8-kv-head GQA form) — with
    fused RoPE, forward and forward+backward, at b4 x s2048 x 32 heads x d128
    bf16: what one llama-7b layer hands the kernels. And the same two entries
    WITHOUT RoPE at opt-1.3b's per-chip shape, b4 x s2048 x 8 heads x d64.
    Every one takes the blocked family: row blocks of 1024 forward with RoPE
    and of 512 without, one combined backward, no grid kernel."""
    from galvatron_tpu.ops import flash_attention as fa

    def aval(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    assert fa.flash_tileable(S)
    rope = not entry.startswith("opt")
    b, h, d = (B, H, D) if rope else (OPT_B, OPT_H, OPT_D)
    tables = _rope_avals(one_chip) if rope else ()
    assert fa.flash_qkv_supported(S, d, True)
    if entry.endswith("qkv"):
        args = (aval(b, 3, h, S, d),)

        def fwd(qkv, *t):
            return fa.flash_attention_qkv(qkv, rope=t or None)
    else:
        kv = KV if entry == "hm_gqa" else h
        args = (aval(b, h, S, d), aval(b, kv, S, d), aval(b, kv, S, d))

        def fwd(q, k, v, *t):
            return fa.flash_attention_hm(q, k, v, causal=True, rope=t or None)

    fn = fwd
    if grad:
        def fn(*a):
            t = a[len(args):]
            loss = lambda *qkv: jnp.sum(fwd(*qkv, *t).astype(jnp.float32))  # noqa: E731
            return jax.grad(loss, argnums=tuple(range(len(args))))(*a[:len(args)])

    names = _kernel_names(_kernel_text(fn, *args, *tables))
    fwd_name = "flash_fwd_qkv" if entry.endswith("qkv") else "flash_fwd_blocked"
    # these and no other: no flash_fwd_grid, flash_bwd_dkv or flash_bwd_dq
    assert names == [fwd_name] * (2 if rope else 4) + ["flash_bwd_blocked"] * grad, names


_ONE_CHIP_STEP = {}


def _one_chip_step(topo):
    """(compiled text, memory analysis) of the step program chip_smoke.py
    trains, compiled once for the tests that read it (call under
    ``real_mosaic``)."""
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import PRESETS

    if not _ONE_CHIP_STEP:
        cfg = PRESETS["llama-7b"].replace(num_layers=2, attn_impl="flash")
        assert (cfg.hidden_size, cfg.num_heads, cfg.ffn_dim, cfg.vocab_size,
                cfg.max_seq_len) == (4096, 32, 11008, 32000, 2048)
        hp = HybridParallelConfig.uniform(2, mixed_precision="bf16")
        compiled, ma = _compile(cfg, hp, topo.devices[:1], bsz=4, seq=2048)
        _ONE_CHIP_STEP.update(text=compiled.as_text(), ma=ma)
    return _ONE_CHIP_STEP["text"], _ONE_CHIP_STEP["ma"]


def test_one_chip_train_step_compiles_at_7b_width(topo, real_mosaic):
    """The step program chip_smoke.py trains: llama-7b widths, 2 layers,
    global batch 4, seq 2048, bf16 compute over fp32 params with Adam, flash
    attention — it must hold the kernels and fit one 16 GB chip."""
    _, ma = _one_chip_step(topo)
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total < HBM_V5E_GIB * 2**30, f"{total / 2**30:.2f} GiB"


# --- the names the metrics match (PERF.md §3's table) -----------------------

#: scopes of galvatron_tpu's step program; ``layer_<i>`` is matched apart
STEP_SCOPES = ("embed", "attn", "qkv_proj", "attn_core", "out_proj", "mlp", "norm", "head",
               "loss", "optimizer", "grad_accum", "grad_sync", "redistribute",
               "allgather_einsum", "einsum_reducescatter")


def _entry_lines(text):
    """The instructions of the compiled text's ENTRY computation, a line each."""
    entry = text[text.index("\nENTRY "):]
    return entry[:entry.index("\n}")].splitlines()


def _entry_work(text):
    """(instruction name, op_name or "") of the ENTRY computation's fusions and
    custom calls: the operations a device trace shows."""
    import re

    rows = []
    for line in _entry_lines(text):
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .*? (fusion|custom-call)\(", line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            rows.append((m.group(1), op.group(1) if op else ""))
    return rows


def _has_scope(op_name):
    import re

    parts = [re.sub(r"^(?:\w+\()+|\)+$", "", p) for p in op_name.split("/")]
    return any(p in STEP_SCOPES or re.fullmatch(r"layer_\d+", p) for p in parts)


@pytest.mark.parametrize("prefix", ["flash_fwd", "flash_bwd"])
def test_one_chip_train_step_names_its_kernels(topo, real_mosaic, prefix):
    """A Pallas kernel's ``name=`` is its instruction's name in the compiled
    step (what a device trace's events are called), forward and backward."""
    text, _ = _one_chip_step(topo)
    kernels = [n for n, _ in _entry_work(text) if n.startswith(prefix)]
    assert kernels, f"no instruction named {prefix}* in the compiled step"
    # 2 layers x 2 row blocks forward, 2 layers x 1 combined backward
    assert len(kernels) == (4 if prefix == "flash_fwd" else 2), kernels


def test_one_chip_train_step_scopes_cover_its_work(topo, real_mosaic):
    """At least 90% of the compiled step's ENTRY fusions and custom calls carry
    one of the program's scopes in ``op_name``; every Mosaic call is a named
    kernel under ``attn_core``."""
    text, _ = _one_chip_step(topo)
    rows = _entry_work(text)
    scoped = [r for r in rows if _has_scope(r[1])]
    assert len(rows) > 100 and len(scoped) >= 0.9 * len(rows), (
        len(scoped), len(rows), [r for r in rows if not _has_scope(r[1])][:10])
    mosaic = [r for r in rows if r[0].startswith(("flash_fwd", "flash_bwd"))]
    assert mosaic and all("attn_core" in op for _, op in mosaic), mosaic
    assert any("transpose(" in op for _, op in mosaic)  # the backward, marked for free


# --- OLMoE at its published widths: dropless top-8 of 64, sort + grouped GEMM ----

_OLMOE_STEP = {}


def _olmoe_step(topo):
    """(compiled text, memory analysis) of the step `olmoe-1b-7b_s4096` trains:
    one OLMoE layer at published widths, batch 4 x 4096 = 16,384 tokens =
    131,072 routed pairs over 64 experts, forward + backward + Adam."""
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import PRESETS

    if not _OLMOE_STEP:
        cfg = PRESETS["olmoe-1b-7b"].replace(num_layers=1, attn_impl="flash")
        assert (cfg.hidden_size, cfg.num_heads, cfg.ffn, cfg.vocab_size, cfg.max_seq_len,
                cfg.moe_experts, cfg.moe_top_k, cfg.qk_norm) == (
                    2048, 16, 1024, 50304, 4096, 64, 8, True)
        hp = HybridParallelConfig.uniform(1, mixed_precision="bf16")
        compiled, ma = _compile(cfg, hp, topo.devices[:1], bsz=4, seq=4096)
        _OLMOE_STEP.update(text=compiled.as_text(), ma=ma)
    return _OLMOE_STEP["text"], _OLMOE_STEP["ma"]


def test_olmoe_step_compiles_and_fits_one_chip(topo, real_mosaic):
    """A grouped GEMM the chip's compiler refuses, or a step that outgrows 16 GB
    at batch 4 (the cell would then have to take batch 2), shows here."""
    _, ma = _olmoe_step(topo)
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total < HBM_V5E_GIB * 2**30, f"{total / 2**30:.2f} GiB"


def test_olmoe_step_names_its_kernels_and_scopes(topo, real_mosaic):
    """The grouped GEMM kernels keep their ``name=`` as instruction names and
    sit under ``mlp/experts``, forward and backward; every new scope reaches
    the compiled ENTRY; the attention is the RoPE stacked-qkv flash family."""
    text, _ = _olmoe_step(topo)
    rows = _entry_work(text)
    gemms = [(n, op) for n, op in rows if n.startswith(("moe_gmm", "moe_tgmm"))]
    kinds = sorted(n.split(".")[0] for n, _ in gemms)
    # forward gate, up, down; backward a dlhs and a tgmm for each
    assert kinds == ["moe_gmm"] * 3 + ["moe_gmm_dlhs"] * 3 + ["moe_tgmm"] * 3, kinds
    assert all("/mlp/" in op and "experts" in op for _, op in gemms), gemms
    assert sum("transpose(" in op for _, op in gemms) == 6
    ops = [op for _, op in rows]
    for scope in ("router", "dispatch", "experts", "combine", "qk_norm"):
        assert any(f"/{scope}/" in op for op in ops), scope
    assert sorted(set(_kernel_names(text))) == ["flash_bwd_blocked", "flash_fwd_qkv"]
    # (at this size the compiler adds buffer-placement custom calls that carry
    # no op_name and do no work: left out of the count)
    work = [r for r in rows if r[1] or not r[0].startswith("custom-call")]
    scoped_rows = [r for r in work if _has_scope(r[1])]
    assert len(scoped_rows) >= 0.9 * len(work), (len(scoped_rows), len(work))


def test_granite_mixer_compiles_at_published_widths(one_chip, real_mosaic):
    """One Mamba-2 mixer of `granite-4.0-h-micro_s8192`, forward + backward under
    recomputation, as the chip's compiler sees it: 64 heads of 64, state 128, 32
    chunks of 256 over 8192 tokens. A scan it refuses, or score blocks (64 x 32 x
    256 x 256 a sequence) that outgrow what a layer may take beside 12 GiB of
    state, shows here; the five scopes reach the compiled ENTRY under ``ssm``.
    The scan is the fused kernels (PR 34): ``ssd_fwd`` once for the replayed
    forward, ``ssd_bwd`` once, both under ``ssm/scan`` with the two small
    ``ssd_decay`` kernels, none borrowing the ``flash_`` prefix that the
    ``flash_*_ms_per_step`` readers go by; the temporaries are the 67 MB of
    entering states and the layer's own activations, no score block.
    (The ten-layer step compiles in two minutes, 1.3 of them the 8192-key
    ``flash_bwd_blocked``: a chip run's job, PERF.md §6.)"""
    from galvatron_tpu.models import ssm
    from galvatron_tpu.models.modeling import PRESETS

    cfg = PRESETS["granite-4.0-h-micro"].replace(mlp_recompute="off")
    assert ssm.ssm_dims(cfg) == (4096, 4352, 8512) and cfg.ssm_chunk == 256
    shapes = jax.eval_shape(lambda k: ssm.init_params(k, cfg), jax.random.key(0))
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16, sharding=one_chip)

    def loss(x_, p_):
        with jax.named_scope("layer_0"):
            y = jax.checkpoint(lambda a, b: ssm.block(a, b, cfg))(x_, p_)
        return jnp.sum(y.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, p).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1 * 2**30, f"{temp / 2**30:.2f} GiB"
    rows = _entry_work(compiled.as_text())
    ops = [op for _, op in rows]
    for scope in ("in_proj", "conv", "scan", "gate_norm", "out_proj"):
        mine = [op for op in ops if f"/ssm/{scope}/" in op]
        assert mine and any("transpose(" in op for op in mine), scope
    kernels = sorted((n.split(".")[0], op) for n, op in rows if n.startswith("ssd_"))
    assert [n for n, _ in kernels] == ["ssd_bwd", "ssd_decay", "ssd_decay_bwd", "ssd_fwd"], kernels
    assert all("/ssm/scan/" in op for _, op in kernels), kernels
    assert not [n for n, _ in rows if n.startswith("flash_")]
    # the conv + SiLU is the fused op (PR 40): x, B and C are three windows of in_proj's
    # output, so ``ssm_conv_fwd`` three times (the replay) and ``ssm_conv_bwd`` three
    # times, all under ``ssm/conv``; nothing float32 of the size of the conv's channels
    # (or of a window) is left there, and no slice of them anywhere in the layer
    conv = sorted((n.split(".")[0], op) for n, op in rows if n.startswith("ssm_conv_"))
    assert [n for n, _ in conv] == ["ssm_conv_bwd"] * 3 + ["ssm_conv_fwd"] * 3, conv
    assert all("/ssm/conv/" in op for _, op in conv), conv
    import re

    for line in _entry_lines(compiled.as_text()):
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) (fusion|slice|copy)\(", line)
        if not m:
            continue
        if m.group(2) == "fusion" and "/ssm/conv/" in line:
            assert "f32[1,8192," not in m.group(1), line
        if m.group(2) != "fusion":
            assert not re.search(r"\[1,8192,(4096|4352|128)\]", m.group(1)), line
    # (the plain conv's mixer: 607.5 MiB; the entering states and the layer's activations)
    assert temp < 500 * 2**20, f"{temp / 2**20:.1f} MiB"


def test_qwen3_next_mixer_compiles_at_published_widths(one_chip, real_mosaic):
    """One Gated DeltaNet mixer of `qwen3-next-80b-a3b_s4096`, forward and backward under
    recomputation, as the chip's compiler sees it: 16 key / 32 value heads of 128, 64
    chunks of 64 over 4096 tokens, batch 4. The five scopes reach the compiled ENTRY
    under ``gdn``; the conv + SiLU is the fused op granite's mixer takes (one window of
    8192 channels at column 0 of in_proj's output: ``ssm_conv_fwd`` / ``ssm_conv_bwd``
    under ``gdn/conv``); the delta rule is its own kernels (PR 48: `ops/gated_delta`'s
    ``gdn_fwd`` / ``gdn_bwd`` under ``gdn/scan``, the forward once plain and once replayed
    under autodiff's mark, each call site under its own layer's name): no triangular
    solve and no loop is left under ``scan``. The mixer's temporaries: 5.35 GiB while
    autodiff kept the plain rule's float32 systems, solutions and carried states; 2.33
    GiB while the kernels kept q and k normalised head-major beside the conv's output;
    2.14 GiB now that they keep that output alone, the scalars and one entering state a
    chunk (537 MB)."""
    from galvatron_tpu.models import gdn
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.ops import gated_delta, pallas_common

    cfg = PRESETS["qwen3-next-80b-a3b"].replace(mlp_recompute="off")
    assert gdn.gdn_dims(cfg) == (2048, 4096, 8192, 12288) and cfg.gdn_chunk == 64
    assert gdn.path_counts(cfg.replace(num_layers=4))["conv"] == {"fused": 3, "plain": 0}
    assert gdn.path_counts(cfg.replace(num_layers=4))["scan"] == {"fused": 3, "plain": 0}
    assert 1.1 * gated_delta._fused_vmem_mb(2, 128, 128, 32, 2) <= pallas_common.VMEM_LIMIT_MB
    shapes = jax.eval_shape(lambda k: gdn.init_params(k, cfg), jax.random.key(0))
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    x = jax.ShapeDtypeStruct((4, 4096, 2048), jnp.bfloat16, sharding=one_chip)

    def loss(x_, p_):
        with jax.named_scope("layer_0"):
            y = jax.checkpoint(lambda a, b: gdn.block(a, b, cfg))(x_, p_)
        return jnp.sum(y.astype(jnp.float32) ** 2)  # (not linear: the forward is kept)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(x, p).compile()
    text = compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2.3 * 2**30, f"{temp / 2**30:.2f} GiB"
    import math
    import re

    ops = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("in_proj", "conv", "scan", "gate_norm", "out_proj"):
        mine = [op for op in ops if f"/gdn/{scope}/" in op]
        assert mine and any("transpose(" in op for op in mine), scope
    under_scan = [op for op in ops if "/gdn/scan/" in op]
    assert not [op for op in under_scan if "triangular_solve" in op or "while" in op]
    assert not [ln for ln in text.splitlines() if "/gdn/scan/" in ln and " while(" in ln]
    rows = _entry_work(text)
    conv = sorted((n.split(".")[0], op) for n, op in rows if n.startswith("ssm_conv_"))
    assert [n for n, _ in conv] == ["ssm_conv_bwd", "ssm_conv_fwd", "ssm_conv_fwd"], conv
    assert all("/gdn/conv/" in op for _, op in conv), conv
    rule = sorted((n.split(".")[0], "transpose(" in op, op) for n, op in rows if n.startswith("gdn_"))
    assert [(n, t) for n, t, _ in rule] == [
        ("gdn_bwd", True), ("gdn_fwd", False), ("gdn_fwd", True)], rule
    assert all("/gdn/scan/" in op and op.endswith("/pallas_call") for _, _, op in rule), rule
    assert not [n for n, _ in rows if n.startswith(("flash_", "ssd_"))]
    # the kernels read q, k and v where the conv wrote them and normalise q and k (PR 73):
    # what XLA keeps under ``scan`` is the scalars (2 MiB arrays), do's transposition to
    # head-major and the concatenation of dq, dk, dv, both bf16: no slice, nothing float32
    # of a projection's size (eight 128 MiB copies a layer until then), and no float32 copy
    # without a name (token-major blocks with XLA's norms made four of 256 MiB a layer,
    # `scope_coverage` 96.2% in the cell: PERF.md §6, PR 48)
    xla_scan = [ln for ln in _entry_lines(text)
                if "/gdn/scan/" in ln and not re.search(r" (custom-call|get-tuple-element)\(", ln)]
    assert not [ln for ln in xla_scan if re.search(r" = \S+ slice\(", ln)]
    wide = [ln.split(" = ")[0].strip() for ln in xla_scan
            if (m := re.search(r" = \(?f32\[([\d,]+)\]", ln))
            and math.prod(int(d) for d in m.group(1).split(",")) >= 2**22]
    assert not wide, wide
    big = [ln for ln in xla_scan if re.search(r" = bf16\[4,\d+,\d+(,128)?\]", ln)]
    assert len(big) == 2, big  # do head-major; [dq | dk | dv]
    nameless = [ln.split(" = ")[0].strip() for ln in _entry_lines(text)
                if re.search(r" = f32\[(2048,8|4,4096),\d+(,128)?\]\S* copy\(", ln)
                and "op_name=" not in ln]
    assert not nameless, nameless


@pytest.mark.parametrize("tokens", [32, 1024])
@pytest.mark.parametrize("model,scored,held,top_k,hidden,width", [
    ("sarvam-105b", 128, 32, 8, 4096, 2048), ("smallthinker-21b-a3b", 64, 16, 6, 2560, 768)])
def test_held_experts_serve_unjoined_at_published_widths(one_chip, real_mosaic, model, scored, held,
                                                         top_k, hidden, width, tokens):
    """The expert layer of `sarvam-105b_serve_long_above_knee` (top-8 over 128 experts of
    width 2048, SwiGLU) and of `smallthinker-21b-a3b_serve_long_above_knee` (top-6 over 64
    of width 768, ReGLU, routed from the attention block's input), a decode step's 32
    tokens and a prefill chunk's 1,024, rank 0 of 4 holding a quarter, weights HELD in
    bf16, as the chip's compiler sees it: the bounded path's kernels, gate and up a
    grouped GEMM each, no pass that joins or copies a stack of the experts' weights
    (1 GB a layer: 16 of a 42 ms decode step while the two were joined every step,
    PERF.md section 6, PR 51), the row buffer at the tile `row_tile` names for the shape
    (PR 57: 784 rows a sarvam decode step, 8,704 at tiles of 256) and every grouped GEMM
    reading its rows and its weights where their producer left them."""
    import re

    from galvatron_tpu.models import modeling, moe
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.ops.grouped_matmul import TILE_M, row_tile

    cfg = PRESETS[model].replace(moe_share=(0, 4), param_dtype=jnp.bfloat16, dtype=jnp.bfloat16)
    assert (cfg.moe_experts, cfg.moe_held, cfg.moe_top_k, cfg.hidden_size, cfg.expert_ffn) == (
        scored, held, top_k, hidden, width)
    assert moe.held_path_counts(cfg.replace(num_layers=4))["worst_case"] == 0
    shapes = jax.eval_shape(
        lambda k: {"mlp": moe.init_moe_params(k, cfg),
                   "mlp_norm": {"scale": jnp.zeros((cfg.hidden_size,), cfg.param_dtype)}},
        jax.random.key(0))
    assert shapes["mlp"]["w1"].dtype == jnp.bfloat16 and shapes["mlp"]["router"]["w"].dtype == jnp.float32
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    x = jax.ShapeDtypeStruct((1, tokens, hidden), jnp.bfloat16, sharding=one_chip)

    def layer(x_, p_):
        with jax.named_scope("layer_1"):
            router_x = x_ if cfg.moe_router_input == "attn" else None
            return modeling.mlp_residual(x_, p_, cfg, router_x=router_x)[0]

    compiled = jax.jit(layer).lower(x, p).compile()
    text = compiled.as_text()
    kernels = sorted(n.split(".")[0] for n, _ in _entry_work(text) if n.startswith("moe_"))
    assert kernels == sorted(["moe_held_rows", "moe_gmm", "moe_gmm", "moe_held_swiglu", "moe_gmm",
                              "moe_held_pairs"]), kernels
    # no operation writes a stack of the held experts' weights (held x hidden x width or its double)
    lines = _entry_lines(text)
    entry = "\n".join(lines)
    assert not re.search(rf"= bf16\[{held},{hidden},({width}|{2 * width})\]\S* (fusion|copy|concatenate)\(",
                         entry)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2**30
    # the buffer's rows under the rule: a decode step's 2 / 3 rows an expert take bf16's
    # floor, a chunk's 64 / 96 the power of two they fill
    tile = row_tile(tokens, top_k, scored, jnp.bfloat16)
    assert tile == {32: 16, 1024: 64}[tokens]
    rows = moe.buffer_rows(tokens * top_k, held + 1, tile)
    assert rows == {("sarvam-105b", 32): 784, ("sarvam-105b", 1024): 10304,
                    ("smallthinker-21b-a3b", 32): 464, ("smallthinker-21b-a3b", 1024): 7232}[model, tokens]
    at_256 = moe.buffer_rows(tokens * top_k, held + 1, TILE_M)
    assert f"bf16[{rows},{hidden}]" in entry and f"[{at_256}," not in entry
    # every grouped GEMM reads the rows a `moe_*` kernel wrote and a parameter, in place:
    # no copy into another layout, no fusion between (a `copy-start` / `copy-done` pair is
    # the compiler's prefetch of the same layout into another memory, not a re-layout)
    made = {m.group(1): m.group(2) for m in (
        re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .*? copy-(?:start|done)\(%([\w.\-]+)", ln) for ln in lines) if m}
    gemms = [re.search(r"custom-call\(([^)]*)\)", ln).group(1).replace("%", "").split(", ")
             for ln in lines if re.match(r"\s*%moe_gmm[\w.]* = ", ln)]
    assert len(gemms) == 3
    for _, _, lhs, rhs in gemms:
        while lhs in made:
            lhs = made[lhs]
        assert lhs.split(".")[0] in ("moe_held_rows", "moe_held_swiglu"), lhs
        assert rhs.startswith("p_") and re.search(r"w[123]_", rhs), rhs


@pytest.mark.parametrize("cell", ["trinity_bounded", "nemotron_plain"])
def test_a_cached_forwards_expert_layer_takes_the_forward_only_layout(one_chip, real_mosaic,
                                                                      monkeypatch, cell):
    """The expert layer of `trinity-large-preview_serve_agent_above_knee` as a CACHED forward
    runs it (`generation._mlp_at`: `moe_topk_block(forward_only=True)`, PR 62), a decode
    step's 32 tokens, rank 0 of 8 holding 32 of 256, weights held in bf16, as the chip's
    compiler sees it: the same six kernels over a layout that gives an expert without a row
    no tile (`used_tile`'s clamp lowers for the chip), at the row tile of the shape. And that
    of `nemotron-3-nano-30b-a3b_serve_chat_above_knee` (64 tokens, 32 of 128 held), whose
    un-gated experts take the PLAIN held path: the same layout there too (PR 69), its two
    products still `moe_gmm_dlhs` (up) and `moe_gmm` (down)."""
    from galvatron_tpu.models import generation, moe
    from galvatron_tpu.models.modeling import PRESETS

    if cell == "trinity_bounded":
        cfg = PRESETS["trinity-large-preview"].replace(
            moe_share=(0, 8), param_dtype=jnp.bfloat16, dtype=jnp.bfloat16)
        assert (cfg.moe_experts, cfg.moe_held, cfg.moe_top_k, cfg.expert_ffn) == (256, 32, 4, 3072)
        tokens, path = 32, "bounded"
        want = ["moe_held_rows", "moe_gmm", "moe_gmm", "moe_held_swiglu", "moe_gmm",
                "moe_held_pairs"]
    else:
        cfg = _nemotron_cut()
        assert (cfg.moe_experts, cfg.moe_held, cfg.moe_top_k, cfg.expert_ffn) == (128, 32, 6, 1856)
        tokens, path, want = 64, "worst_case", ["moe_gmm_dlhs", "moe_gmm"]
    assert moe.held_path_counts(cfg)[path] == sum(cfg.mlp_layers)
    shapes = jax.eval_shape(
        lambda k: {"mlp": moe.init_moe_params(k, cfg),
                   "mlp_norm": {"scale": jnp.zeros((cfg.hidden_size,), cfg.param_dtype)}},
        jax.random.key(0))
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    x = jax.ShapeDtypeStruct((tokens, 1, cfg.hidden_size), jnp.bfloat16, sharding=one_chip)
    asked = []
    real = moe.held_layout

    def recording(*args, **kw):
        asked.append((args[2], kw))
        return real(*args, **kw)

    monkeypatch.setattr(moe, "held_layout", recording)
    compiled = jax.jit(lambda x_, p_: generation._mlp_at(x_, p_, cfg, None)).lower(x, p).compile()
    assert asked == [(16, {"empty_tiles": False})]
    kernels = sorted(n.split(".")[0] for n, _ in _entry_work(compiled.as_text())
                     if n.startswith("moe_"))
    assert kernels == sorted(want), kernels


def _lowered_serving_program(cfg, name, one_chip, **context):
    """The engine's declared program ``name`` (the AOT registry's twin of what ``cli
    serve`` warms) for ``cfg`` under `registry.ProgramContext(**context)`, lowered for
    one described chip from shapes alone."""
    from galvatron_tpu.aot import registry
    from galvatron_tpu.serving import engine  # noqa: F401  (registers the serving family)

    ctx = registry.ProgramContext(cfg=cfg, **context)
    spec, = [sp for sp in registry.enumerate_programs(ctx, include=("serving",))
             if sp.name == name]
    args = [a if a is cfg else jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), a)
        for a in spec.args]
    return spec.fn.lower(*args)


def _sarvam_serving_program(name, one_chip):
    """(cfg, the compiled program ``name`` of `sarvam-105b_serve_long_above_knee`: the
    5-layer cut, 32 slots x 16,384 positions x 576 of bf16 latent, chunk 1,024)."""
    from galvatron_tpu.models.modeling import PRESETS

    cfg = PRESETS["sarvam-105b"].replace(num_layers=5, vocab_size=65536, moe_share=(0, 4),
                                         max_seq_len=16384, param_dtype=jnp.bfloat16,
                                         dtype=jnp.bfloat16)
    return cfg, _lowered_serving_program(cfg, name, one_chip, num_slots=32, prefill_chunk=1024,
                                         max_seq_len=16384).compile()


def _moved_slabs(text, slab):
    """Results of a cache slab's size or more that are no parameter, in-place update or bitcast."""
    return [(op, shape) for op, n, shape in _entry_results(text)
            if n >= slab and op not in ("parameter", "get-tuple-element", "tuple", "bitcast",
                                        "dynamic-update-slice")]


def test_sarvam_decode_step_reads_the_latent_cache_in_place(one_chip, real_mosaic):
    """`_decode_step` of `sarvam-105b_serve_long_above_knee` (the 5-layer cut, 32 slots x
    16,384 positions x 576 of bf16 latent, 64 heads) as the chip's compiler sees it:
    every layer's attention is the kernel `mla_decode` under ``attn_core`` between the
    two ``absorb`` products (so Mosaic takes the kernel at the real widths), handed the
    stacked cache WHOLE: the chip keeps a slot's positions on the lanes
    (``{2,3,1,0}``: its compact layout for a width that is no multiple of 128), the
    kernel's operand is a bitcast of that, and no operation copies a layer's slab
    (32, 16384, 576), let alone the cache (two copies of 3.0 GB a step when the
    kernel asked for the latent position-major); the donated cache is aliased and the
    temporaries are 0.14 GiB (1.15 with the plain body's float32 scores)."""
    import re

    cfg, compiled = _sarvam_serving_program("serving_decode", one_chip)
    text = compiled.as_text()
    entry = _entry_lines(text)
    kernels = [line for line in entry if "custom-call(" in line and "mla_decode" in line]
    assert len(kernels) == cfg.num_layers
    for i, line in enumerate(sorted(kernels, key=lambda l: int(re.search(r"layer_(\d+)", l).group(1)))):
        assert f"/layer_{i}/attn/attn_core" in line and "bf16[5,32,576,16384]{3,2,1,0}" in line
    names = re.findall(r'op_name="([^"]*)"', text)
    assert sum("/attn/attn_core/absorb" in n for n in names) >= 2 * cfg.num_layers
    # the cache's way through the step: parameter, in-place updates, bitcasts for the kernel
    slab = 32 * 16384 * 576
    moved = _moved_slabs(text, slab)
    assert not moved, moved[:4]
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == cfg.num_layers * slab * 2
    assert ma.temp_size_in_bytes < 0.25 * 2**30, f"{ma.temp_size_in_bytes / 2**30:.3f} GiB"
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < HBM_V5E_GIB * 2**30, f"{total / 2**30:.2f} GiB"


def test_sarvam_prefill_chunk_keeps_its_scores_on_the_chip(one_chip, real_mosaic):
    """`_prefill_chunk` of the same cell (a chunk of 1,024 tokens into one of 32 slots) as
    the chip's compiler sees it: every layer's chunk attention is the kernel `mla_chunk`
    under ``attn_core`` > ``expand`` (the expansion through ``W_kvb`` runs inside it, so
    the benchmark's ``expand`` mark stays on the program), handed the stacked cache
    WHOLE as a bitcast of the chip's own layout: no operation copies a layer's slab, no
    float32 score block (64, 1024, 1024) of the plain body exists, nor any loop over key
    blocks; the donated cache and the engine's rows are aliased and the temporaries are
    0.29 GiB (0.36 with the plain body)."""
    import re

    cfg, compiled = _sarvam_serving_program("serving_prefill", one_chip)
    text = compiled.as_text()
    entry = _entry_lines(text)
    kernels = [line for line in entry if "custom-call(" in line and "mla_chunk" in line]
    assert len(kernels) == cfg.num_layers
    for i, line in enumerate(sorted(kernels, key=lambda l: int(re.search(r"layer_(\d+)", l).group(1)))):
        assert f"/layer_{i}/attn/attn_core/expand" in line
        assert "bf16[5,32,576,16384]{3,2,1,0}" in line
    assert not re.search(r"f32\[(1,)?64,1024,1024\]", text)
    assert not any(" while(" in line and "/attn/" in line for line in text.splitlines())
    slab = 32 * 16384 * 576
    moved = _moved_slabs(text, slab)
    assert not moved, moved[:4]
    ma = compiled.memory_analysis()
    rows_bytes = 32 * cfg.vocab_size * 2  # the engine's logits rows, set in place
    assert ma.alias_size_in_bytes == cfg.num_layers * slab * 2 + rows_bytes
    assert ma.temp_size_in_bytes < 0.32 * 2**30, f"{ma.temp_size_in_bytes / 2**30:.3f} GiB"
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < HBM_V5E_GIB * 2**30, f"{total / 2**30:.2f} GiB"


def test_dots3_decode_step_reads_the_latent_ring_in_place_along_its_arc(one_chip, real_mosaic):
    """`_decode_step` of `dots3-note-prev_serve_reason_above_knee` (F F S S S, 32 slots x
    20,480; the sliding layers' ring 2,048 places x 1,088 of bf16, 64 heads, a window of
    513) as the chip's compiler sees it: EVERY layer's decode attention is the kernel
    `mla_decode`, the two full layers' under ``full`` > ``attn_core`` (under the selection)
    and, PR 71, the three sliding layers' under ``window`` > ``attn_core`` over the ring
    where it lies: the chip keeps the 1,088-wide ring with its places on the lanes
    (``{2,3,1,0}``, as it keeps the 576-wide slots), the kernel's operand is a bitcast of
    the in-place write's result, its grid is (32 rows, `ring_steps` of the block
    `ring_block` gives the shape), and no operation copies the ring, a layer's slab of it,
    or a slot stack."""
    import re

    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.ops import mla_decode, pallas_common

    cfg = PRESETS["dots3-note-prev"].replace(
        num_layers=5, moe_dense_layers=1, vocab_size=19008, moe_share=(0, 8), max_seq_len=20480,
        param_dtype=jnp.bfloat16, dtype=jnp.bfloat16)
    assert mla_decode.decode_path(2048, 1088, 64, 1024, jnp.bfloat16, span=513) == "kernel"
    compiled = _lowered_serving_program(cfg, "serving_decode", one_chip, num_slots=32,
                                        prefill_chunk=1024, max_seq_len=20480).compile()
    text = compiled.as_text()
    kernels = sorted((line for line in _entry_lines(text)
                      if "custom-call(" in line and "mla_decode" in line),
                     key=lambda l: int(re.search(r"layer_(\d+)", l).group(1)))
    assert len(kernels) == 5
    for i, line in enumerate(kernels):
        stack = "full" if i < 2 else "window"
        assert f"/layer_{i}/attn/{stack}/attn_core/mla_decode" in line
        assert ("bf16[2,32,576,20480]{3,2,1,0}" if i < 2 else "bf16[3,32,1088,2048]{3,2,1,0}") in line
    # the ring's way through the step: parameter, in-place updates, bitcasts for the kernel
    assert "bf16[3,32,2048,1088]{2,3,1,0" in text and "bf16[3,32,2048,1088]{3,2,1,0" not in text
    # (the indexer's loop over key blocks carries the index-key stack through, by reference)
    moved = [(op, shape) for op, shape in _moved_slabs(text, 32 * 2048 * 1088) if op != "while"]
    assert not moved, moved[:4]
    # (the grid a ring layer's kernel walks: rows x the arc's blocks, not the ring's)
    block = mla_decode.ring_block(2048, 513)
    steps = pallas_common.ring_steps(1, 513, 2048, block)
    assert block in mla_decode.RING_BLOCKS and steps * block < 2048
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == 2 * (2 * 32 * 20480 * (576 + 128) + 3 * 32 * 2048 * 1088)
    assert ma.temp_size_in_bytes < 0.1 * 2**30, f"{ma.temp_size_in_bytes / 2**30:.3f} GiB"
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < HBM_V5E_GIB * 2**30, f"{total / 2**30:.2f} GiB"


def test_qwen3_next_held_experts_compile_at_published_widths(one_chip, real_mosaic):
    """The expert layer of `qwen3-next-80b-a3b_s4096` (16,384 tokens x top-10 over 512
    experts of width 512, rank 0 of 16 holding 32, a buffer of 172,288 rows), forward
    and backward under recomputation, as the chip's compiler sees it. The held share
    takes the bounded path (PR 49): the kernels of `ops/moe_held.py` and the grouped
    GEMMs keep their names under ``dispatch`` / ``experts`` / ``combine``: the
    forward's five, four of them again in the replay under autodiff's ``transpose(``
    (the backward needs no combined output, so the replay gathers no pair), the
    backward's eight beside them; and NO operation of XLA's runs over a row buffer
    (172,288 rows of 512 / 1024 / 2048 values, or the 163,840 pairs' gathered rows):
    what is left over the buffer's length is the layout's scalar passes."""
    import re

    from galvatron_tpu.models import modeling, moe
    from galvatron_tpu.models.modeling import PRESETS

    cfg = PRESETS["qwen3-next-80b-a3b"].replace(mlp_recompute="off", moe_share=(0, 16))
    assert (cfg.hidden_size, cfg.expert_ffn, cfg.moe_experts, cfg.moe_held, cfg.moe_top_k) == (
        2048, 512, 512, 32, 10)
    assert moe.held_path_counts(cfg.replace(num_layers=4)) == {"bounded": 4, "worst_case": 0}
    shapes = jax.eval_shape(
        lambda k: {"mlp": moe.init_moe_params(k, cfg),
                   "mlp_norm": {"scale": jnp.zeros((cfg.hidden_size,), cfg.param_dtype)}},
        jax.random.key(0))
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    x = jax.ShapeDtypeStruct((4, 4096, 2048), jnp.bfloat16, sharding=one_chip)

    def loss(x_, p_):
        with jax.named_scope("layer_0"):
            y, _ = jax.checkpoint(lambda a, b: modeling.mlp_residual(a, b, cfg))(x_, p_)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(x, p).compile()
    text = compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 4.0 * 2**30, f"{temp / 2**30:.2f} GiB"
    rows = _entry_work(text)
    kernels = sorted((n.split(".")[0], re.search(r"/mlp/(\w+)/", op).group(1))
                     for n, op in rows if n.startswith("moe_"))
    forward = [("moe_held_rows", "dispatch"), ("moe_gmm", "experts"),
               ("moe_held_swiglu", "experts"), ("moe_gmm", "experts"), ("moe_held_pairs", "combine")]
    assert kernels == sorted(forward + forward[:-1] + [  # the backward:
        ("moe_held_pairs", "combine"), ("moe_held_rows", "combine"),
        ("moe_gmm_dlhs", "experts"), ("moe_tgmm", "experts"), ("moe_held_swiglu_bwd", "experts"),
        ("moe_gmm_dlhs", "experts"), ("moe_tgmm", "experts"), ("moe_held_pairs", "dispatch"),
    ]), kernels
    mine = [op for n, op in rows if n.startswith("moe_")]
    assert all("layer_0" in op for op in mine) and sum("transpose(" in op for op in mine) == 12
    ops = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("router", "dispatch", "experts", "combine", "shared_expert"):
        assert any(f"/mlp/{scope}/" in op for op in ops), scope
    # nothing of XLA's over a row buffer: not a gather, not a zero-fill, not a sum
    wide = re.compile(r"\[(?:172288,(?:512|1024|2048|8,128)|163840,2048|16384,10,2048)\]")
    over = [ln.split(" = ")[0].strip() for ln in text.splitlines()
            if " = " in ln and wide.search(ln.split(" = ")[1].split("(")[0])
            and not re.search(r" (custom-call|parameter|get-tuple-element|bitcast)\(", ln)]
    assert not over, over[:5]


def test_qwen3_next_attention_compiles_at_head_size_256(one_chip, real_mosaic):
    """The gated attention layer of the same cell: 16 query / 2 key-value heads of 256
    over 4096 keys, batch 4. ``s * lanes(d)`` = 4096 x 256 is exactly the blocked
    kernels' envelope, so the no-RoPE GQA instance runs (the partial rotary is applied
    in front of it): ``flash_fwd_blocked`` / ``flash_bwd_blocked`` under
    ``attn/attn_core``, never run at d 256 before this PR; the gate has its own scope."""
    from galvatron_tpu.models import modeling
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.ops.flash_attention import _use_blocked

    cfg = PRESETS["qwen3-next-80b-a3b"].replace(mlp_recompute="off", attn_impl="flash")
    assert _use_blocked(4096, 256, True, 1024, 1024) and not _use_blocked(8192, 256, True, 1024, 1024)
    shapes = jax.eval_shape(lambda k: modeling.init_layer_params(k, cfg), jax.random.key(0))
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
                     shapes["attn"])
    x = jax.ShapeDtypeStruct((4, 4096, 2048), jnp.bfloat16, sharding=one_chip)

    def loss(x_, p_):
        with jax.named_scope("layer_3"):
            y = jax.checkpoint(lambda a, b: modeling.attn_block(
                a, b, cfg, modeling.rope_tables(cfg, 4096)))(x_, p_)
        return jnp.sum(y.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, p).compile()
    rows = _entry_work(compiled.as_text())
    kernels = sorted((n.split(".")[0], op) for n, op in rows if n.startswith("flash_"))
    # (the no-RoPE forward is a call a block of 512 query rows: 8 of them, replayed once)
    assert [n for n, _ in kernels] == ["flash_bwd_blocked"] + ["flash_fwd_blocked"] * 8, kernels
    assert all("/attn/attn_core/" in op for _, op in kernels), kernels
    import re

    # (the gate's multiply rides inside a neighbour's fusion: read every instruction's name)
    ops = set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    for scope in ("qkv_proj", "qk_norm", "rope", "gate", "out_proj"):
        assert any(f"/attn/{scope}/" in op for op in ops), scope


@pytest.mark.parametrize("sizes", [
    dict(h=4, p=64, g=2, n=128, chunk=128, dtype=jnp.bfloat16),  # head blocks of 2, two groups
    dict(h=8, p=128, g=2, n=256, chunk=256, dtype=jnp.float32),  # a head a lane tile, state 256
], ids=["p64_g2_bf16", "p128_n256_f32"])
def test_fused_ssd_kernels_compile_across_their_envelope(sizes, one_chip, real_mosaic):
    """Corners of `ops/ssd.scan_path`'s envelope the granite sizes do not
    touch: what it calls fused, Mosaic lowers, forward and backward."""
    from galvatron_tpu.ops import ssd

    h, p, g, n, chunk, dtype = (sizes[k] for k in ("h", "p", "g", "n", "chunk", "dtype"))
    assert ssd.scan_path(h, p, g, n, chunk, dtype) == "fused"
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    args = (sd((2, 500, h, p), dtype), sd((2, 500, h), jnp.float32), sd((h,), jnp.float32),
            sd((2, 500, g, n), dtype), sd((2, 500, g, n), dtype))
    text = _kernel_text(
        jax.grad(lambda *t: jnp.sum(ssd.ssd_scan(*t, chunk).astype(jnp.float32)), argnums=range(5)),
        *args)
    import re

    # (bare autodiff wraps the names: jvp_ssd_fwd_, transpose_jvp_ssd_bwd__)
    found = [re.search(r"ssd_(?:decay_bwd|decay|fwd|bwd)", n) for n, _ in _entry_work(text)]
    assert sorted(m.group(0) for m in found if m) == [
        "ssd_bwd", "ssd_decay", "ssd_decay_bwd", "ssd_fwd"], found


@pytest.mark.parametrize("sizes", [
    dict(b=2, s=500, width=128, col0=0, c=128, k=2, dtype=jnp.float32),  # one lane tile, padded
    dict(b=1, s=4096, width=17024, col0=8448, c=8448, k=4, dtype=jnp.float32),  # float32, 256-wide
    dict(b=2, s=2048, width=8512, col0=4096, c=4096, k=4, dtype=jnp.bfloat16),  # granite's x, batch 2
], ids=["c128_k2_f32", "c8448_f32", "c4096_b2_bf16"])
def test_fused_conv_kernels_compile_across_their_envelope(sizes, one_chip, real_mosaic):
    """Corners of `ops/ssd.conv_path`'s envelope: what it calls fused, Mosaic
    lowers, forward and backward, under the kernels' own names (bare autodiff
    wraps them: jvp_ssm_conv_fwd_, transpose_jvp_ssm_conv_bwd__)."""
    from galvatron_tpu.ops import ssd

    b, s, width, col0, c, k, dtype = (sizes[key] for key in "b s width col0 c k dtype".split())
    assert ssd.conv_path((c,), k, dtype) == "fused"
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    text = _kernel_text(
        jax.grad(lambda *t: jnp.sum(ssd.conv_silu_fused(*t, col0).astype(jnp.float32)),
                 argnums=(0, 1, 2)),
        sd((b, s, width), dtype), sd((k, c), jnp.float32), sd((c,), jnp.float32))
    import re

    found = [re.search(r"ssm_conv_(?:fwd|bwd)", n) for n, _ in _entry_work(text)]
    assert sorted(m.group(0) for m in found if m) == ["ssm_conv_bwd"], found


def test_granite_attention_takes_the_blocked_gqa_kernel_at_8192(one_chip, real_mosaic):
    """32 query heads over 8 key/value heads of 64, no rotary tables, softmax
    scale 1/64, 8192 keys: the forward is ``flash_fwd_blocked``."""
    from galvatron_tpu.ops.flash_attention import flash_attention_hm

    q = jax.ShapeDtypeStruct((1, 32, 8192, 64), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8, 8192, 64), jnp.bfloat16, sharding=one_chip)
    text = _kernel_text(
        lambda q_, k_, v_: flash_attention_hm(q_, k_, v_, causal=True, sm_scale=0.015625),
        q, kv, kv)
    assert sorted(set(_kernel_names(text))) == ["flash_fwd_blocked"]


def test_olmoe_block_partitions_on_four_chips(topo, real_mosaic):
    """Under a data-parallel mesh each device routes its own tokens inside a
    ``shard_map``: the Mosaic kernels compile for four chips and keep their names."""
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import PRESETS

    cfg = PRESETS["olmoe-1b-7b"].replace(num_layers=1, attn_impl="flash", vocab_size=1024,
                                         max_seq_len=512)
    hp = HybridParallelConfig.uniform(1, dp_type="zero3", mixed_precision="bf16")
    compiled, _ = _compile(cfg, hp, topo.devices, bsz=8, seq=512)
    names = [n for n, _ in _entry_work(compiled.as_text())]
    assert sum(n.startswith(("moe_gmm", "moe_tgmm")) for n in names) == 9, names[:40]
    assert not any(n.startswith("shard_map") for n in names)


def test_granite_layers_partition_on_four_chips(topo, real_mosaic):
    """A Mamba-2 layer (granite's head, state and chunk sizes; 16 heads), data-parallel
    over four chips with ZeRO-3 (what `ssm.annotations` shards for, and `build_runtime` admits):
    GSPMD cannot partition a Mosaic call, so `ssm.block` hands the fused scan to
    `place.shard_kernel` and each device runs it on its own batch rows. Forward
    and backward lower and compile; the kernels keep their names."""
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import PRESETS

    cfg = PRESETS["granite-4.0-h-micro"].replace(
        num_layers=1, attn_impl="flash", vocab_size=1024, max_seq_len=512, hidden_size=512,
        num_heads=8, num_kv_heads=2, ffn_dim=1024, ssm_heads=16)
    assert cfg.kinds == ("ssm",)
    hp = HybridParallelConfig.uniform(1, dp_type="zero3", mixed_precision="bf16")
    compiled, _ = _compile(cfg, hp, topo.devices, bsz=8, seq=512)
    names = [n.split(".")[0] for n, _ in _entry_work(compiled.as_text())]
    for kernel in ("ssd_fwd", "ssd_bwd", "ssd_decay", "ssd_decay_bwd"):
        assert names.count(kernel) == 1, (kernel, [n for n in names if n.startswith("ssd_")])
    # the fused conv (PR 40) under the same wrap: three windows forward, three backward
    for kernel in ("ssm_conv_fwd", "ssm_conv_bwd"):
        assert names.count(kernel) == 3, (kernel, [n for n in names if n.startswith("ssm_")])
    assert not any(n.startswith("shard_map") for n in names)


def test_gated_deltanet_layer_partitions_on_four_chips(topo, real_mosaic):
    """A Gated DeltaNet layer (the published head sizes, 2 key / 4 value heads), data-parallel
    over four chips with ZeRO-3: `gdn.block` hands the fused delta rule (PR 48) to
    `place.shard_kernel` as it hands the conv, and each device runs the kernels on its own
    batch rows. Forward and backward lower and compile; the kernels keep their names."""
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import PRESETS

    cfg = PRESETS["qwen3-next-80b-a3b"].replace(
        num_layers=1, attn_impl="flash", vocab_size=1024, max_seq_len=512, hidden_size=512,
        num_heads=4, num_kv_heads=2, gdn_key_heads=2, gdn_value_heads=4, moe_experts=8,
        moe_top_k=2, moe_ffn_dim=128, moe_shared_ffn_dim=128, moe_share=(0, 1))
    assert cfg.kinds == ("gdn",)
    hp = HybridParallelConfig.uniform(1, dp_type="zero3", mixed_precision="bf16")
    compiled, _ = _compile(cfg, hp, topo.devices, bsz=8, seq=512)
    names = [n.split(".")[0] for n, _ in _entry_work(compiled.as_text())]
    for kernel in ("gdn_fwd", "gdn_bwd", "ssm_conv_fwd", "ssm_conv_bwd"):
        assert names.count(kernel) == 1, (kernel, [n for n in names if n.startswith(("gdn_", "ssm_"))])
    assert not any(n.startswith("shard_map") for n in names)


# --- the four-chip cell: opt-1.3b widths under the searched plan, tp 4 + sp with the
# --- collective-matmul rings (ops/collective_matmul.py) on the projection seams ----

_OPT_STEP = {}
#: what the parent of PR 32 (PR 30's tree) emits for ``_opt_four_chip_step``'s search
#: arguments, every key of the document but the predicted cost and throughput
PARENT_PLAN = {
    "pp_deg": 1, "vpp_deg": 1, "tp_sizes_enc": "4,4", "tp_consecutive_flags": "1,1",
    "dp_types_enc": "0,0", "dp_type_names": "ddp,ddp", "checkpoint": "0,0", "sp_flags": "1,1",
    "cp_sizes_enc": "1,1", "cp_impls": "ring,ring", "ep_sizes_enc": "1,1",
    "tp_overlap_flags": "1,1", "pp_division": "2", "chunks": 1, "pipeline_type": "gpipe",
    "vocab_tp": 4, "vocab_sp": 0, "embed_dp_type": "ddp", "default_dp_type": "ddp",
    "mixed_precision": "bf16", "mlp_recompute": "policy", "grad_overlap": 0, "global_bsz": 16,
    "memory_mb": 2664.005632, "num_devices": 4, "memory_constraint_gb": 10.0,
}


def _opt_four_chip_step(topo, tmp_path_factory):
    """(plan, runtime's ``tp_overlap_seams``, compiled text) of the step the
    cell ``opt-1.3b_4chip_searched`` trains, at two of its 24 layers: the plan is
    what ``cli search`` emits for the cell's arguments (``--attn_impl flash``,
    which ``auto`` resolves to on a TPU), with the cell's 4 micro-batches set
    by hand (two layers fit the budget in one)."""
    import dataclasses
    import json

    from galvatron_tpu import cli
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import PRESETS

    if not _OPT_STEP:
        path = str(tmp_path_factory.mktemp("opt_plan") / "plan.json")
        assert cli.main([
            "search", "--model_size", "opt-1.3b", "--num_layers", "2", "--num_devices", "4",
            "--seq_length", "2048", "--mixed_precision", "bf16", "--attn_impl", "flash",
            "--analytic_costs", "1", "--memory_constraint_gb", "10", "--settle_bsz", "16",
            "--output_config_path", path]) == 0
        with open(path) as f:
            doc = json.load(f)
        hp = dataclasses.replace(HybridParallelConfig.load(path), chunks=4)
        cfg = PRESETS["opt-1.3b"].replace(num_layers=2, attn_impl="flash", max_seq_len=2048)
        assert (cfg.hidden_size, cfg.num_heads, cfg.ffn) == (2048, 32, 8192)
        from galvatron_tpu.core.optim import AdamConfig
        from galvatron_tpu.parallel.hybrid import build_runtime
        from galvatron_tpu.parallel.mesh import build_mesh
        from galvatron_tpu.core.checkpoint import abstract_state_of

        mesh, axes = build_mesh(pp=1, devices=list(topo.devices))
        rt = build_runtime(cfg, hp, mesh=mesh, axes=axes, adam=AdamConfig(lr=1e-3),
                           global_batch_size=16, seq_len=2048)
        batch = jax.ShapeDtypeStruct((16, 2049), jnp.int32, sharding=rt.batch_sharding)
        from galvatron_tpu.models import modeling

        traced, layer = [], modeling.decoder_layer
        modeling.decoder_layer = lambda *a, **k: traced.append(k["place"]) or layer(*a, **k)
        try:
            lowered = rt.train_step.lower(abstract_state_of(rt), batch)
        finally:
            modeling.decoder_layer = layer
        _OPT_STEP["layer_traces"] = traced
        from galvatron_tpu.analysis import comm_audit as ca

        footprint = ca.extract_footprint(lowered.as_text(), program="train_step")
        ca.attribute_collectives(footprint, mesh.devices, mesh.axis_names)
        _OPT_STEP.update(doc=doc, hp=hp, seams=rt.tp_overlap_seams, footprint=footprint,
                         text=lowered.compile().as_text())
    return _OPT_STEP["doc"], _OPT_STEP["seams"], _OPT_STEP["text"]


def _layer_collectives(text):
    """(opcode, op_name) of the collectives a layer's scopes own in the compiled text."""
    import re

    return [(m.group(1), m.group(2)) for m in re.finditer(
        r" (all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)(?:-start)?\("
        r"[^\n]*?op_name=\"([^\"]*)\"", text) if "layer_" in m.group(2)]


def test_four_chip_searched_plan_sets_tp_overlap(topo, real_mosaic, tmp_path_factory):
    """The search enumerates ``tp_overlap`` unasked and prices it from the seams'
    shapes: the cell's plan is tp 4 + sp with the ring on every layer, and
    every projection seam of every layer passes the ring's shape test."""
    doc, seams, _ = _opt_four_chip_step(topo, tmp_path_factory)
    assert (doc["tp_sizes_enc"], doc["sp_flags"], doc["tp_overlap_flags"]) == ("4,4", "1,1", "1,1")
    assert seams == {"ring": 8, "plain": 0, "batchwise": 4}
    # the plan document the parent (PR 30's tree) emits for the same arguments, key for
    # key: pricing the batch-wise gathers moved nothing in it but the predicted cost
    assert {k: doc[k] for k in PARENT_PLAN} == PARENT_PLAN, {
        k: (doc.get(k), v) for k, v in PARENT_PLAN.items() if doc.get(k) != v}


def test_four_chip_step_traces_its_layers_once(topo, real_mosaic, tmp_path_factory):
    """Layers of one plan entry have equal placements, so ``_decoder_layer_once``
    runs the layer's Python once for all of them (``trace_lower_s`` of the
    cell; its ``_cache_size()`` stays 0, being called under the step's trace)."""
    _opt_four_chip_step(topo, tmp_path_factory)
    (place,) = _OPT_STEP["layer_traces"]
    assert place.tp_overlap and place.sp and place.kernel_tp == 4


@pytest.mark.parametrize("scope,per_layer", [("allgather_einsum", 12), ("einsum_reducescatter", 24)])
def test_four_chip_step_runs_its_seams_on_the_ring(topo, real_mosaic, tmp_path_factory, scope,
                                                   per_layer):
    """``collective-permute`` flights under the two scopes, two lanes of three hops
    a ring: the all-gather ring in the MLP's up projection and in the down
    projection's backward (12 a layer), the reduce-scatter ring in both output
    projections and in the backward of both input projections (24 a layer)."""
    _, _, text = _opt_four_chip_step(topo, tmp_path_factory)
    permutes = [op for kind, op in _layer_collectives(text)
                if kind == "collective-permute" and f"/{scope}/" in op]
    assert len(permutes) == 2 * per_layer, len(permutes)
    assert all(any(f"/{s}/" in op for s in ("qkv_proj", "out_proj", "mlp")) for op in permutes)


def test_four_chip_step_keeps_no_monolithic_collective_at_a_ring_seam(topo, real_mosaic,
                                                                      tmp_path_factory):
    """Inside the layers every collective belongs to one of the two scopes, and
    what is not a permute there is only the gathers of the two seams whose
    all-gather side puts out head-major dims (qkv forward, out_proj backward):
    one a row of the device's micro-batch of 4, none merged with another, each
    of a row and not of the batch; no reduce-scatter, no all-reduce, no
    fusion of one with its GEMM. The plan checker's GTC012 reads the same
    from the lowered text."""
    import collections
    import re

    from galvatron_tpu.analysis import comm_audit as ca

    _, _, text = _opt_four_chip_step(topo, tmp_path_factory)
    in_layers = _layer_collectives(text)
    assert in_layers and all(
        "/allgather_einsum/" in op or "/einsum_reducescatter/" in op for _, op in in_layers), [
            (k, op) for k, op in in_layers
            if "/allgather_einsum/" not in op and "/einsum_reducescatter/" not in op][:5]
    whole = {(kind, next(sc for sc in ("qkv_proj", "out_proj", "mlp") if f"/{sc}/" in op),
              "transpose(" in op) for kind, op in in_layers if kind != "collective-permute"}
    assert whole == {("all-gather", "qkv_proj", False), ("all-gather", "out_proj", True)}, whole
    # a gather the compiler carries inside the next GEMM's fusion is written once in each
    # computation of its chain (one ``chain_id``); the others once, by their own name
    gathers = collections.defaultdict(set)
    for m in re.finditer(r"%(all-gather[\w.-]*) = bf16\[([\d,]+)\][^\n]*? all-gather(?:-start)?\("
                         r"[^\n]*?op_name=\"([^\"]*)\"", text):
        name, shape, op = m.groups()
        if "layer_" not in op:
            continue
        chain = re.search(r'chain_id="(\d+)"', m.group(0))
        assert shape == "1,2048,2048", (shape, op)  # a row of the micro-batch, the sequence whole
        layer = re.search(r"layer_(\d+)", op).group(1)
        gathers[layer, "out_proj" if "/out_proj/" in op else "qkv_proj", "transpose(" in op].add(
            "chain " + chain.group(1) if chain else name)
    assert {k: len(v) for k, v in gathers.items()} == {
        (layer, *side): 4 for layer in "01"
        for side in (("qkv_proj", False), ("out_proj", True))}, dict(gathers)
    # the fusion of a reduce-scatter with its GEMM (the parent's 139 ms) is left
    # to the embedding's way into the sequence-parallel layout
    fused = re.findall(r"calls=%all-reduce-scatter[^\n]*?op_name=\"([^\"]*)\"", text)
    assert not [op for op in fused if "layer_" in op], fused
    fp = _OPT_STEP["footprint"]
    assert any(c.kind == "collective_permute" for c in fp.collectives)
    assert "GTC012" not in [d.code for d in ca.resharding_lint(_OPT_STEP["hp"], [fp], world=4)]


def test_one_chip_step_has_no_ring(topo, real_mosaic):
    """No tensor parallelism on one chip: the step program chip_smoke.py trains
    (and every one-chip cell) holds no permute, no shard_map'd seam, and its
    runtime counts no seam (the lowered text of both ``baichuan-7b`` cells is
    compared with the parent's by digest in PERF.md §6)."""
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.parallel.hybrid import build_runtime
    from galvatron_tpu.parallel.mesh import build_mesh

    text, _ = _one_chip_step(topo)
    assert "collective-permute" not in text
    assert not any("allgather_einsum" in op or "einsum_reducescatter" in op
                   for _, op in _entry_work(text))
    mesh, axes = build_mesh(pp=1, devices=list(topo.devices[:1]))
    rt = build_runtime(PRESETS["baichuan-7b"].replace(num_layers=2, attn_impl="flash"),
                       HybridParallelConfig.uniform(2, mixed_precision="bf16"), mesh=mesh,
                       axes=axes, global_batch_size=2, seq_len=4096)
    assert rt.tp_overlap_seams == {"ring": 0, "plain": 0, "batchwise": 0}


# --- the serving programs at opt-1.3b widths: the slot cache is written in place ---

def _entry_results(text):
    """(opcode, elements of its largest result array, result type) of every
    instruction of the ENTRY computation."""
    import math
    import re

    rows = []
    for line in _entry_lines(text):
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if m:
            sizes = [math.prod(int(d) for d in dims.split(",") if d)
                     for dims in re.findall(r"\b[a-z]+\d*\[([\d,]*)\]", m.group(1))]
            rows.append((m.group(2), max(sizes, default=0), m.group(1)))
    return rows


@pytest.mark.parametrize("program,slots,spec_k", [
    ("serving_decode", 8, 0), ("serving_decode", 16, 0),
    ("serving_prefill", 8, 0), ("serving_prefill", 16, 0),
    ("serving_decode_verify", 8, 4),
])
def test_serving_programs_write_the_slot_cache_in_place(program, slots, spec_k, one_chip,
                                                        real_mosaic):
    """The engine's declared programs (the AOT twins ``cli serve`` warms) at
    ``opt-1.3b`` widths, slots x 2048, chunk 256, for one v5e chip: what the
    cell ``opt-1.3b_serve_above_knee`` runs. The cached forwards carry the
    stacked cache (L, B, S, kv, d) through the layers and write it with
    ``dynamic-update-slice`` alone, so the compiled program moves no layer's
    slab: no copy, scatter or fusion whose result is a slab or more, the
    donated cache aliased input to output, temporaries far under the cache's
    size (5.42 GiB before PR 38 at 8 slots; 12 slots did not fit)."""
    from galvatron_tpu.models.modeling import PRESETS

    cfg, smax = PRESETS["opt-1.3b"], 2048
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.head_dim,
            cfg.vocab_size, cfg.max_seq_len) == (2048, 24, 32, 64, 50272, smax)
    compiled = _lowered_serving_program(cfg, program, one_chip, num_slots=slots, prefill_chunk=256,
                                        max_seq_len=smax, spec_decode_k=spec_k).compile()
    ma = compiled.memory_analysis()
    slab = slots * smax * cfg.kv_heads * cfg.head_dim
    cache_bytes = 2 * cfg.num_layers * slab * 2  # k and v, bf16
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total < HBM_V5E_GIB * 2**30, f"{total / 2**30:.2f} GiB"
    assert ma.temp_size_in_bytes < 0.5 * 2**30, f"{ma.temp_size_in_bytes / 2**30:.3f} GiB"
    # (the prefill program also takes and hands back the engine's logits rows,
    # slots x vocabulary in bf16, its lanes padded to whole tiles of 128; a
    # prompt's last row is set into them in place)
    rows_bytes = slots * -(-cfg.vocab_size // 128) * 128 * 2 if program == "serving_prefill" else 0
    assert ma.alias_size_in_bytes == cache_bytes + rows_bytes
    # results of a slab or more: the in-place updates of the stacked cache, and
    # the tied embedding table's conversion to bf16 (a weight, not the cache)
    moved = [(op, shape) for op, n, shape in _entry_results(compiled.as_text())
             if n >= slab and str(cfg.vocab_size) not in shape
             and op not in ("parameter", "get-tuple-element", "tuple", "bitcast",
                            "dynamic-update-slice")]
    assert not moved, moved[:4]


@pytest.mark.parametrize("program", ["serving_decode", "serving_prefill"])
def test_opt_serving_programs_take_none_of_the_latent_paths(program, one_chip, real_mosaic):
    """A stack of plain attention over K and V slots takes none of the latent
    attention's paths: what `opt-1.3b_serve_above_knee` runs (16 slots x 2048, chunk
    256) names no ``mla_`` kernel and none of the latent attention's scopes. (That the
    text is the parent's to the letter is `experiments/step_text_digest.py`'s to say,
    run on both trees: PERF.md section 6.)"""
    from galvatron_tpu.models.modeling import PRESETS

    text = _lowered_serving_program(PRESETS["opt-1.3b"], program, one_chip, num_slots=16,
                                    prefill_chunk=256, max_seq_len=2048).as_text()
    assert "mla_" not in text and "attn_core/expand" not in text and "absorb" not in text


def test_serving_sampler_compiles_for_the_chip_without_a_sort(one_chip, real_mosaic):
    """The engine's draw at the cell's shapes (16 slots x 50,272 bf16 logits) for
    one v5e chip: one program whose every operand is data, no sort over the
    vocabulary (the cuts are bisected), temporaries of a few rows' size, and the
    ``sample`` scope on its work."""
    from galvatron_tpu.aot import registry
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.serving import engine  # noqa: F401  (registers the serving family)

    cfg = PRESETS["opt-1.3b"]
    ctx = registry.ProgramContext(cfg=cfg, num_slots=16, prefill_chunk=256, max_seq_len=2048)
    spec, = [sp for sp in registry.enumerate_programs(ctx, include=("serving",))
             if sp.name == "serving_sample"]
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip) for a in spec.args]
    assert [a.shape for a in args] == [(16, 50272), (2, 16), (6, 16), (16,)]  # (the last ids: PR 64)
    compiled = spec.fn.lower(*args).compile()
    text = compiled.as_text()
    assert " sort(" not in text and "while(" in text
    assert "jit(_sample_rows)/sample/" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_serving_decode_step_names_its_device_work(one_chip, real_mosaic):
    """The decode step of the cell ``opt-1.3b_serve_above_knee`` (8 slots x 2048
    at ``opt-1.3b`` widths) as the chip's compiler leaves it: at least nine in ten
    of the ENTRY computation's fusions carry a scope of the cached forward
    (``embed``, ``layer_<i>/attn/{qkv_proj,cache_write,attn_core,out_proj}``,
    ``mlp``, ``norm``, ``head``), so a device trace of a serving iteration reads
    by name, and every layer's cache updates sit under ``cache_write``. (Here
    395 of 420; the rest are 24 ``slice_bitcast_fusion`` the compiler makes of
    a layer's q / k / v split, which it names after nothing.)"""
    import re

    from galvatron_tpu.models.modeling import PRESETS

    cfg = PRESETS["opt-1.3b"]
    text = _lowered_serving_program(cfg, "serving_decode", one_chip, num_slots=8,
                                    prefill_chunk=256, max_seq_len=2048).compile().as_text()
    # fusions only: the 26 ``ConcatBitcast`` custom calls are the compiler's own
    # (it joins the slices of a weight it prefetched) and carry no metadata at all
    work = [(n, op) for n, op in _entry_work(text) if not n.startswith("custom-call")]
    scoped = [n for n, op in work if _has_scope(op)]
    assert len(work) > 400 and len(scoped) >= 0.9 * len(work), (
        len(scoped), len(work), [n for n, op in work if not _has_scope(op)][:8])
    names = re.findall(r'op_name="([^"]*)"', text)
    for i in (0, cfg.num_layers - 1):
        for scope in ("qkv_proj", "cache_write", "attn_core", "out_proj"):
            assert any(f"/layer_{i}/attn/{scope}" in n for n in names), (i, scope)
        assert any(f"/layer_{i}/mlp" in n for n in names), i
    updates = [line for line in _entry_lines(text) if " dynamic-update-slice(" in line]
    assert len(updates) == 2 * 8 * cfg.num_layers
    assert all("/attn/cache_write" in line for line in updates)


def test_flash_multichip_compile_smoke(topo, real_mosaic):
    """One minimal multi-chip flash compile in the default selection — the
    cheapest canary for the Mosaic-partitioning failure class (a regression
    here means every real-pod flash config is broken)."""
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
    from galvatron_tpu.models.modeling import ModelConfig

    cfg = ModelConfig(
        vocab_size=256, hidden_size=256, num_layers=2, num_heads=2,
        max_seq_len=256, dtype=jnp.bfloat16, attn_impl="flash",
    )
    hp = HybridParallelConfig(
        pp=1, layer_strategies=[LayerStrategy(tp=2, dp_type="zero3")] * 2,
        chunks=1, vocab_tp=2, mixed_precision="bf16",
    )
    compiled, _ = _compile(cfg, hp, topo.devices, bsz=8, seq=256)
    # the kernels keep their names under shard_map (they were ``shard_map.<n>``)
    names = [n for n, _ in _entry_work(compiled.as_text())]
    assert not any(n.startswith("shard_map") for n in names)
    # causal, learned positions (no RoPE), s 256 x d 128: the blocked family
    # under shard_map, one row block and one combined backward a layer
    # (the metrics match instruction names by their start: no prefix allowed)
    flash = sorted(n.split(".")[0] for n in names if n.startswith("flash_"))
    assert flash == ["flash_bwd_blocked"] * 2 + ["flash_fwd_qkv"] * 2, (flash, names[:20])


@pytest.mark.slow
def test_flash_multichip_compiles_on_tpu_topology(topo, real_mosaic):
    """Flash train step compiles for a real 8-chip v5e topology across the
    strategy classes (dp / tp+zero3 / pp gpipe / pp 1F1B + SP); per-device
    memory_analysis is populated."""
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
    from galvatron_tpu.models.modeling import ModelConfig

    cfg = ModelConfig(
        vocab_size=512, hidden_size=512, num_layers=4, num_heads=4,
        max_seq_len=512, dtype=jnp.bfloat16, attn_impl="flash",
    )
    cells = [
        HybridParallelConfig(pp=1, layer_strategies=[LayerStrategy(tp=1)] * 4,
                             chunks=1, vocab_tp=1, mixed_precision="bf16"),
        HybridParallelConfig(pp=1, layer_strategies=[LayerStrategy(tp=2, dp_type="zero3")] * 4,
                             chunks=1, vocab_tp=2, mixed_precision="bf16"),
        HybridParallelConfig(pp=2, layer_strategies=[LayerStrategy(tp=1)] * 4,
                             chunks=2, pipeline_type="gpipe", vocab_tp=1,
                             mixed_precision="bf16"),
        HybridParallelConfig(pp=2, layer_strategies=[LayerStrategy(tp=2, sp=True)] * 4,
                             chunks=4, pipeline_type="pipedream_flush", vocab_tp=2,
                             mixed_precision="bf16"),
    ]
    for hp in cells:
        _, ma = _compile(cfg, hp, topo.devices)
        assert ma is None or ma.argument_size_in_bytes > 0


@pytest.mark.slow
def test_cp_multichip_compiles_on_tpu_topology(topo, real_mosaic):
    """Ring and Ulysses context parallelism compile multi-chip with dp>1 —
    their shard_maps must manualize the dp axes too (the per-hop Mosaic
    kernels sit inside), not only the cp axes."""
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
    from galvatron_tpu.models.modeling import ModelConfig

    cfg = ModelConfig(
        vocab_size=512, hidden_size=512, num_layers=2, num_heads=4,
        max_seq_len=1024, dtype=jnp.bfloat16, attn_impl="flash",
    )
    for impl in ("ring", "a2a"):
        hp = HybridParallelConfig(
            pp=1,
            layer_strategies=[LayerStrategy(tp=1, cp=2, cp_impl=impl)] * 2,
            chunks=1, vocab_tp=1, mixed_precision="bf16",
        )
        _compile(cfg, hp, topo.devices, bsz=8, seq=1024)


@pytest.mark.slow
def test_mixed_tp_flash_compiles_on_tpu_topology(topo, real_mosaic):
    """Layerwise-mixed TP (the reference's signature heterogeneity) with
    flash kernels compiles multi-chip — each layer's shard_map carries its
    own (dp, tp) split."""
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
    from galvatron_tpu.models.modeling import ModelConfig

    cfg = ModelConfig(
        vocab_size=512, hidden_size=512, num_layers=4, num_heads=4,
        max_seq_len=512, dtype=jnp.bfloat16, attn_impl="flash",
    )
    hp = HybridParallelConfig(
        pp=1,
        layer_strategies=[
            LayerStrategy(tp=2, dp_type="zero3", sp=True),
            LayerStrategy(tp=2, dp_type="ddp", ckpt=True),
            LayerStrategy(tp=1, dp_type="zero3"),
            LayerStrategy(tp=1, dp_type="ddp"),
        ],
        vocab_tp=2,
        mixed_precision="bf16",
    )
    _compile(cfg, hp, topo.devices)


@pytest.mark.slow
def test_1f1b_vocab_tp_sp_crash_adjacent_cell_compiles(topo, real_mosaic):
    """The compiling NEIGHBOUR of the XLA SPMD CHECK-crash cell: pp2 ×
    pipedream_flush × tp2 × sp=TRUE × vocab_tp=2 must keep compiling on the
    real TPU toolchain — the search guarantees sp rides every tp>1 strategy
    under vocab_tp>1 1F1B (search_engine 'spmd_crash_pp_1f1b_tp_no_sp_
    vocab_tp'), so this cell is exactly what searched winners emit."""
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
    from galvatron_tpu.models.modeling import ModelConfig

    cfg = ModelConfig(
        vocab_size=512, hidden_size=512, num_layers=4, num_heads=4,
        max_seq_len=512, dtype=jnp.bfloat16, attn_impl="flash",
    )
    hp = HybridParallelConfig(
        pp=2, layer_strategies=[LayerStrategy(tp=2, sp=True)] * 4,
        chunks=4, pipeline_type="pipedream_flush", vocab_tp=2,
        mixed_precision="bf16",
    )
    _compile(cfg, hp, topo.devices)


@pytest.mark.slow
def test_mlp_recompute_buffer_accounting_tp2_zero3_sp(topo, real_mosaic):
    """Compiled-buffer accounting for the activation-memory policy at the
    tp2+zero3+sp cell (the round-5 audit's diseased class), via the
    compiled memory_analysis path:

    - 'one gate save per layer': switching policy -> off must grow temp by
      at least L x one full-width activation-product save (the duplicate
      the policy eliminates) — if a second gate copy ever returns under the
      policy, the off/policy gap collapses below the floor and this fails;
    - 'no fp32-widened backward buffers': the policy-mode temp must sit
      BELOW off-mode temp minus the duplicate-product floor, i.e. the norm
      fp32 (B,S,H) saves and the fp32 cross-entropy cast are also gone
      (they are the remainder of the measured gap).

    Uses the xla attention channel — the audit showed the gate/norm/CE
    inflation is attention-impl independent."""
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
    from galvatron_tpu.models.modeling import ModelConfig

    cfg = ModelConfig(
        vocab_size=512, hidden_size=512, num_layers=4, num_heads=4,
        max_seq_len=512, dtype=jnp.bfloat16, attn_impl="xla",
    )
    temps = {}
    for mode in ("off", "policy"):
        hp = HybridParallelConfig(
            pp=1,
            layer_strategies=[LayerStrategy(tp=2, dp_type="zero3", sp=True)] * 4,
            chunks=1, vocab_tp=2, mixed_precision="bf16", mlp_recompute=mode,
        )
        _, ma = _compile(cfg.replace(mlp_recompute=mode), hp, topo.devices, bsz=8, seq=512)
        if ma is None:
            pytest.skip("memory_analysis unavailable")
        temps[mode] = ma.temp_size_in_bytes / 1e6
    # duplicate-product floor: (b_local=4, s=512, ffn/tp=704) bf16 per layer
    # (the swiglu activation product the policy recomputes instead of saving)
    prod_mb = 4 * 512 * (1408 // 2) * 2 / 1e6
    floor = 4 * prod_mb  # L = 4 layers
    gap = temps["off"] - temps["policy"]
    assert gap >= floor, (temps, floor)
    # measured round-6: off 144.0 -> policy 129.5 total (gap ~14.5 MB vs the
    # 5.8 MB product floor; the remainder is the fp32 norm/CE widenings) —
    # a policy-mode temp within 5% of off means the widenings returned
    assert temps["policy"] <= temps["off"] * 0.95, temps


def _kv_kernel_calls(text, kernel):
    """The ENTRY's custom calls of the Pallas kernel ``kernel`` (`kv_decode` | `kv_chunk`)."""
    import re

    return [line for line in _entry_lines(text)
            if "custom-call(" in line and re.search(rf"/{kernel}/pallas_call", line)]


def _kv_program_kernels(text, name):
    """The custom calls of the kernel a K/V stack's serving program ``name`` attends through
    (`kv_decode` a decode step, `kv_chunk` a prompt chunk); the other's has none."""
    kernel, other = (("kv_decode", "kv_chunk") if name == "serving_decode"
                     else ("kv_chunk", "kv_decode"))
    assert not _kv_kernel_calls(text, other)
    return _kv_kernel_calls(text, kernel)


def _handed_as_bitcasts(call):
    """The last two operands of a kernel's custom call (its K and V stacks) are bitcasts."""
    import re

    handed = re.search(r"custom-call\(([^)]*)\)", call).group(1).split(", ")[-2:]
    assert all(re.match(r"(/\*index=\d+\*/)?%bitcast", h) for h in handed), handed


@pytest.mark.parametrize("dtype,rows,positions,span,heads", [
    (jnp.float32, 1024, 16384, 0, (4, 7, 128)), (jnp.float32, 1024, 5120, 4096, (4, 7, 128)),
    (jnp.float32, 1024, 16384, 0, (8, 4, 64)), (jnp.bfloat16, 512, 5120, 4096, (8, 6, 128)),
    (jnp.bfloat16, 16, 16384, 0, (8, 4, 64)), (jnp.bfloat16, 1024, 5120, 4096, (8, 4, 64))],
    ids=["f32_rows", "f32_ring", "d64_f32_rows", "bf16_half_a_chunk_ring", "d64_bf16_a_tile_of_rows",
         "d64_bf16_ring"])
def test_kv_chunk_takes_what_its_rule_lets_in(one_chip, real_mosaic, dtype, rows, positions, span,
                                              heads):
    """What the benchmark's cells do not run but `kv_prefill.chunk_path` lets in, at their
    widths (32 slots; smallthinker's 4 key/value heads of 128 under 7 grouped query heads,
    trinity's 8 under 6, lfm2's 8 of 64 under 4): float32, a chunk of 512 rows and of one
    tile of rows, a ring at a head of 64: Mosaic takes each. A head of 64 is read
    TRANSPOSED, which is how the chip keeps such a stack (``{3,4,2,1,0}``): the kernel's
    operands are bitcasts of the stacks, nothing of a stack's size is copied."""
    import re

    from galvatron_tpu.ops import kv_prefill

    kv, g, d = heads
    assert kv_prefill.chunk_path(positions, d, rows, dtype) == "kernel"
    stack = jax.ShapeDtypeStruct((4, 32, kv, positions, d), dtype, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda q, k, v, slot, offset: kv_prefill.attend_chunk(
        q, k, v, 2, slot, offset, scale=d ** -0.5, span=span)).lower(
        jax.ShapeDtypeStruct((1, rows, kv, g, d), dtype, sharding=one_chip), stack, stack,
        scalar, scalar).compile()
    text = compiled.as_text()
    call, = _kv_kernel_calls(text, "kv_chunk")
    assert "tpu_custom_call" in call
    assert not _moved_slabs(text, 32 * kv * positions * d)
    word = "bf16" if dtype == jnp.bfloat16 else "f32"
    if d % 128:
        kept = re.escape(f"{word}[4,32,{kv},{positions},{d}]") + r"\{3,4,2,1,0:"
        assert len(re.findall(rf"= {kept}\S* parameter\(", text)) == 2
        assert call.count(f"{word}[4,32,{kv},{d},{positions}]{{4,3,2,1,0}}") == 2, call
        _handed_as_bitcasts(call)
    else:
        assert call.count(f"{word}[4,32,{kv},{positions},{d}]{{4,3,2,1,0}}") == 2, call


def test_trinity_prefill_chunk_keeps_its_scores_on_the_chip(one_chip, real_mosaic):
    """`_prefill_chunk` of `trinity-large-preview_serve_agent_above_knee` (5 layers, 32
    slots: 1 full layer x 16,384 positions and 4 window layers x a ring of 5,120, K and V
    of 8 heads of 128 under 6 grouped query heads, an output gate and q/k norms outside
    ``attn_core``) as the chip's compiler sees it: one `kv_chunk` custom call under
    ``window`` | ``full`` > ``attn_core`` of each layer, handed both stacks whole where
    they lie; no float32 score block of 48 x 1,024 x 1,024, no loop over key blocks under
    ``attn``, nothing as large as a ring layer's slab copied; the program fits the chip."""
    import re

    from galvatron_tpu.models.modeling import PRESETS

    cfg = PRESETS["trinity-large-preview"].replace(
        num_layers=5, moe_dense_layers=1, vocab_size=25024, moe_share=(0, 8), max_seq_len=16384,
        param_dtype=jnp.bfloat16, dtype=jnp.bfloat16)
    compiled = _lowered_serving_program(cfg, "serving_prefill", one_chip, num_slots=32,
                                        prefill_chunk=1024, max_seq_len=16384).compile()
    text = compiled.as_text()
    kernels = _kv_kernel_calls(text, "kv_chunk")
    under = sorted(re.search(r"/layer_(\d+)/attn/(\w+)/attn_core", line).groups() for line in kernels)
    assert under == sorted((str(i), "window" if windowed else "full")
                           for i, windowed in enumerate(cfg.window_layers))
    for line in kernels:
        stack = ("bf16[4,32,8,5120,128]{4,3,2,1,0}" if "/window/" in line
                 else "bf16[1,32,8,16384,128]{4,3,2,1,0}")
        assert line.count(stack) >= 2, line
    assert not _kv_kernel_calls(text, "kv_decode")
    assert not re.search(r"f32\[(1,)?8,6,1024,1024\]", text)
    assert not any(" while(" in line and "/attn/" in line for line in text.splitlines())
    moved = _moved_slabs(text, 32 * 5120 * 8 * 128)
    assert not moved, moved[:4]
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes
             + ma.temp_size_in_bytes)
    print(f"serving_prefill: arguments {ma.argument_size_in_bytes / 2**30:.3f} GiB, temporaries "
          f"{ma.temp_size_in_bytes / 2**30:.3f} GiB, in all {total / 2**30:.3f} GiB")
    assert total < HBM_V5E_GIB * 2**30, f"{total / 2**30:.2f} GiB"


@pytest.mark.parametrize("dtype,s,positions,span,heads", [
    (jnp.bfloat16, 5, 16384, 0, (4, 7, 128)), (jnp.bfloat16, 5, 5120, 4096, (4, 7, 128)),
    (jnp.float32, 18, 16384, 0, (4, 7, 128)), (jnp.float32, 1, 5120, 4096, (4, 7, 128)),
    (jnp.bfloat16, 5, 16384, 0, (8, 4, 64)), (jnp.float32, 1, 16384, 0, (8, 4, 64)),
    (jnp.bfloat16, 1, 5120, 4096, (8, 4, 64)), (jnp.float32, 32, 16384, 0, (8, 4, 64))],
    ids=["bf16_verify5_rows", "bf16_verify5_ring", "f32_verify18_rows", "f32_decode_ring",
         "d64_bf16_verify5_rows", "d64_f32_decode_rows", "d64_bf16_decode_ring",
         "d64_f32_verify32_rows"])
def test_kv_decode_takes_what_its_rule_lets_in(one_chip, real_mosaic, dtype, s, positions, span,
                                               heads):
    """What the benchmark's cells do not run but `kv_decode.decode_path` lets in, at their
    widths (32 slots; smallthinker's 4 key/value heads of 128 under 7 grouped query heads,
    lfm2's 8 of 64 under 4): a verify window of 1 + 4, float32, the most query rows
    (`MAX_QUERY_ROWS` 128: 18 x 7 = 126, 32 x 4), over whole rows and over a ring: Mosaic
    takes each, no temporary beside the kernel. A head of 64 is read TRANSPOSED, which is
    how the chip keeps such a stack (positions on the lanes, ``{3,4,2,1,0}``): the
    kernel's operands are bitcasts of the stacks, nothing is copied."""
    import re

    from galvatron_tpu.ops import kv_decode

    kv, g, d = heads
    assert kv_decode.decode_path(positions, d, s * g, dtype) == "kernel"
    stack = jax.ShapeDtypeStruct((4, 32, kv, positions, d), dtype, sharding=one_chip)
    compiled = jax.jit(lambda q, k, v, first: kv_decode.attend_rows(
        q, k, v, 2, first, scale=d ** -0.5, span=span)).lower(
        jax.ShapeDtypeStruct((32, s, kv, g, d), dtype, sharding=one_chip), stack, stack,
        jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kv_decode" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
    if d % 128:
        word = "bf16" if dtype == jnp.bfloat16 else "f32"
        kept = re.escape(f"{word}[4,32,{kv},{positions},{d}]") + r"\{3,4,2,1,0:"
        assert len(re.findall(rf"= {kept}\S* parameter\(", text)) == 2
        call, = [line for line in _entry_lines(text) if "custom-call(" in line and "kv_decode" in line]
        assert call.count(f"{word}[4,32,{kv},{d},{positions}]{{4,3,2,1,0}}") == 2, call
        _handed_as_bitcasts(call)


@pytest.mark.parametrize("name", ["serving_decode", "serving_prefill"])
def test_smallthinker_serving_programs_fit_one_chip_and_write_the_cache_in_place(
        one_chip, real_mosaic, name):
    """Both programs of `smallthinker-21b-a3b_serve_long_above_knee` (16 layers, 32 slots:
    4 full layers x 16,384 positions and 12 window layers x a ring of 4,096 + 1,024, K
    and V of 4 heads of 128 in bf16, head-major; chunk 1,024) as the chip's compiler sees
    them: the donated cache of both stacks is aliased whole (8.32 GB), no operation but
    an in-place update has a result as large as a window layer's slab (from a
    position-major cache the compiler copied every stack whole each step, 7.76 GiB of
    temporaries; a ring's chunk as two read-merge-write updates re-laid the ring stacks,
    3.9 GiB), every layer runs under ``window`` / ``full``, and weights, cache and
    temporaries fit the chip (11.6 and 11.7 GiB of 15.75). Every layer of the decode
    step attends through the kernel `kv_decode` (one custom call under ``full`` |
    ``window`` > ``attn_core`` of each of the 16, handed its K and V stacks whole
    where they lie: no float32 score of 32 x 28 x 16,384 or x 5,120 is left), every
    layer of a prompt chunk through the kernel `kv_chunk` the same way (PR 66: no
    float32 score block of 28 x 1,024 x 1,024, no loop over key blocks under ``attn``)."""
    import re

    from galvatron_tpu.models.modeling import PRESETS

    cfg = PRESETS["smallthinker-21b-a3b"].replace(
        num_layers=16, vocab_size=37984, moe_share=(0, 4), max_seq_len=16384,
        param_dtype=jnp.bfloat16, dtype=jnp.bfloat16)
    compiled = _lowered_serving_program(cfg, name, one_chip, num_slots=32, prefill_chunk=1024,
                                        max_seq_len=16384).compile()
    text = compiled.as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for i, windowed in enumerate(cfg.window_layers):
        stack = "window" if windowed else "full"
        assert any(f"/layer_{i}/attn/{stack}/attn_core" in n for n in names), (i, stack)
        assert any(f"/layer_{i}/attn/{stack}/cache_write" in n for n in names), (i, stack)
    kernels = _kv_program_kernels(text, name)
    under = sorted(re.search(r"/layer_(\d+)/attn/(\w+)/attn_core", line).groups() for line in kernels)
    stacks = [(str(i), "window" if windowed else "full") for i, windowed in enumerate(cfg.window_layers)]
    assert under == sorted(stacks)
    for line in kernels:  # the stacks as they lie, not a layer's slab cut out
        stack = ("bf16[12,32,4,5120,128]{4,3,2,1,0}" if "/window/" in line
                 else "bf16[4,32,4,16384,128]{4,3,2,1,0}")
        assert line.count(stack) >= 2, line
    if name == "serving_decode":
        assert not re.search(r"f32\[32,4,7,(1,)?(16384|5120)\]", text)
    else:
        assert not re.search(r"f32\[(1,)?4,7,1024,1024\]", text)
        assert not any(" while(" in line and "/attn/" in line for line in text.splitlines())
    ma = compiled.memory_analysis()
    cache = 2 * 32 * 4 * 128 * 2 * (4 * 16384 + 12 * 5120)
    assert cache == 8_321_499_136 and ma.alias_size_in_bytes >= cache
    ring_slab = 32 * 5120 * 4 * 128
    moved = _moved_slabs(text, ring_slab)
    assert not moved, moved[:4]
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes
             + ma.temp_size_in_bytes)
    print(f"{name}: arguments {ma.argument_size_in_bytes / 2**30:.3f} GiB, temporaries "
          f"{ma.temp_size_in_bytes / 2**30:.3f} GiB, in all {total / 2**30:.3f} GiB")
    assert total < HBM_V5E_GIB * 2**30, f"{total / 2**30:.2f} GiB"


@pytest.mark.parametrize("name", ["serving_decode", "serving_prefill"])
def test_lfm2_serving_programs_fit_one_chip_and_copy_neither_stack(one_chip, real_mosaic, name):
    """Both programs of `lfm2-24b-a2b_serve_long_above_knee` (22 layers, 32 slots: the 5
    attention layers' K and V of 8 heads of 64 in bf16 over 16,384 positions, head-major,
    and the 17 conv layers' state of 2 x 2,048 values a row; chunk 1,024) as the chip's compiler
    sees them: the donated cache of both stacks is aliased whole (5.37 GB of K and V at
    their plain size, a head of 64 costing no padding, and 4.5 MB of state), no operation
    but an in-place update has a result as large as an attention layer's slab (1.07 GB) or
    as the whole state stack, every attention layer runs under ``full`` and every conv
    layer under ``shortconv`` with its ``state_read`` and ``state_write``, and weights,
    cache and temporaries fit the chip. The chip keeps a head of 64 with the positions on
    the lanes (``{3,4,2,1,0}``), and every attention layer of the decode step attends
    through the kernel `kv_decode` reading that in place: one custom call under ``full`` >
    ``attn_core`` of each of the 5, handed both stacks whole as the bitcasts
    (5, 32, 8, 64, 16384), and no float32 score of 32 x 32 x 16,384 is left; a prompt
    chunk attends through the kernel `kv_chunk`, handed the same bitcasts (PR 66: no
    float32 score block of 32 x 1,024 x 1,024, no loop over key blocks under ``attn``)."""
    import re

    from galvatron_tpu.models import generation
    from galvatron_tpu.models.modeling import PRESETS

    cfg = PRESETS["lfm2-24b-a2b"].replace(
        num_layers=22, vocab_size=16384, moe_share=(0, 4), max_seq_len=16384,
        param_dtype=jnp.bfloat16, dtype=jnp.bfloat16)
    compiled = _lowered_serving_program(cfg, name, one_chip, num_slots=32, prefill_chunk=1024,
                                        max_seq_len=16384).compile()
    text = compiled.as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for i, (stack, _) in enumerate(generation.layer_stacks(cfg)):
        scopes = (("shortconv/state_read", "shortconv/conv", "shortconv/state_write")
                  if stack == "state" else ("full/attn_core", "full/cache_write"))
        for scope in scopes:
            assert any(f"/layer_{i}/attn/{scope}" in n for n in names), (i, scope)
    ma = compiled.memory_analysis()
    kv, state = 2 * 5 * 32 * 8 * 16384 * 64 * 2, 17 * 32 * 2 * 2048 * 2
    assert (kv, state) == (5_368_709_120, 4_456_448)
    assert ma.alias_size_in_bytes >= kv + state
    layout = generation.cache_layout(cfg, 16384, 1024)
    assert 32 * layout["bytes_per_slot"] == kv + state
    # neither stack is copied: nothing as large as one layer's K or V slab moves, and no
    # result has the state stack's shape but its in-place updates
    moved = _moved_slabs(text, 32 * 8 * 16384 * 64)
    assert not moved, moved[:4]
    kernels = _kv_program_kernels(text, name)
    under = sorted(int(re.search(r"/layer_(\d+)/attn/full/attn_core", line).group(1)) for line in kernels)
    full = [i for i, (stack, _) in enumerate(generation.layer_stacks(cfg)) if stack == "full"]
    assert len(full) == 5 and under == full
    for line in kernels:  # the stacks where they lie, transposed by a bitcast
        assert line.count("bf16[5,32,8,64,16384]{4,3,2,1,0}") == 2, line
        _handed_as_bitcasts(line)
    if name == "serving_decode":
        assert not re.search(r"f32\[32,8,4,(1,)?16384\]", text)
    else:
        assert not re.search(r"f32\[(1,)?8,4,1024,1024\]", text)
        assert not any(" while(" in line and "/attn/" in line for line in text.splitlines())
    # (a layer's write is a fusion whose root updates the stack in place: its name says so)
    state_results = [line.strip()[:120] for line in _entry_lines(text)
                     if re.match(r"\s*(?:ROOT )?%[\w.\-]+ = bf16\[17,32,4096\]\S* (?!parameter|bitcast)", line)
                     and "state_write/dynamic_update_slice" not in line]
    assert not state_results, state_results[:4]
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes
             + ma.temp_size_in_bytes)
    print(f"{name}: arguments {ma.argument_size_in_bytes / 2**30:.3f} GiB, temporaries "
          f"{ma.temp_size_in_bytes / 2**30:.3f} GiB, in all {total / 2**30:.3f} GiB")
    assert total < HBM_V5E_GIB * 2**30, f"{total / 2**30:.2f} GiB"


# -- nemotron_h at its published widths (PR 68): the Mamba-2 mixer over the state stack, the
# un-gated experts of width 1856 on a hidden of 2688 ------------------------------------------


def _nemotron_cut():
    from galvatron_tpu.models.modeling import PRESETS

    return PRESETS["nemotron-3-nano-30b-a3b"].replace(
        num_layers=15, vocab_size=32768, moe_share=(0, 4), param_dtype=jnp.bfloat16)


@pytest.mark.parametrize("form", ["decode_step", "prompt_chunk"])
def test_nemotron_mixer_moves_the_state_stack_in_place(one_chip, real_mosaic, form):
    """Two Mamba-2 layers of the cell's stack (12 layers x 64 rows: 1.6 GB of float32 scan
    state) as the chip's compiler sees the cached forward: a decode step through the kernel
    `ssm_step`, a prompt chunk through `ssm_state_read` / `ssm_state_write` around the plain
    scan; the stack is donated and NOTHING of its size is copied (as `dynamic_slice` /
    `dynamic_update_slice` the compiler re-laid all of it around every chunk: 1.57 GiB of
    temporaries, the chip's first traced run two copies of f32[12,64,128,4096])."""
    from galvatron_tpu.models import ssm

    cfg = _nemotron_cut()
    sd = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    p = jax.tree.map(sd, jax.eval_shape(lambda k: ssm.init_params(k, cfg), jax.random.key(0)))
    state = jax.tree.map(sd, jax.eval_shape(lambda: ssm.init_state(cfg, 12, 64)))
    assert state.scan.shape == (12, 64, 128, 4096) and state.scan.dtype == jnp.float32
    assert state.conv.shape == (12, 64, 3 * 6144) and state.conv.dtype == jnp.bfloat16
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    if form == "decode_step":
        x = jax.ShapeDtypeStruct((64, 1, 2688), jnp.bfloat16, sharding=one_chip)
        at = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)

        def fn(x_, p_, st, offsets):
            y, st = ssm.cached_block(x_, p_, cfg, st, 3, None, offsets, 0)
            return ssm.cached_block(x_ + y, p_, cfg, st, 7, None, offsets, 0)

        args, names = (x, p, state, at), ["ssm_step"] * 2
    else:
        x = jax.ShapeDtypeStruct((1, 1024, 2688), jnp.bfloat16, sharding=one_chip)

        def fn(x_, p_, st, slot, start, last):
            y, st = ssm.cached_block(x_, p_, cfg, st, 3, slot, start, last)
            return ssm.cached_block(x_ + y, p_, cfg, st, 7, slot, start, last)

        args, names = (x, p, state, i32, i32, i32), ["ssm_state_read", "ssm_state_write"] * 2
    compiled = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()
    text = compiled.as_text()
    import re

    found = [m.group(0) for n, _ in _entry_work(text)
             for m in [re.search(r"ssm_(?:step|state_read|state_write)", n)] if m]
    assert sorted(found) == sorted(names), found
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 0.2 * 2**30, f"{ma.temp_size_in_bytes / 2**30:.2f} GiB"
    assert ma.alias_size_in_bytes >= 12 * 64 * 2134016
    assert not [line[:160] for line in text.splitlines()
                if " copy(" in line and "12,64,128,4096" in line]


@pytest.mark.parametrize("tokens", [64, 1024], ids=["decode_step", "prompt_chunk"])
def test_nemotron_ungated_experts_compile_without_a_copy_of_the_stack(one_chip, real_mosaic,
                                                                      tokens):
    """An expert layer of the cell: 32 held experts of width 1856 on a hidden of 2688,
    ``down(relu(up x)^2)``, through the plain held path (`moe_held.held_path`: worst_case).
    The up projection is held OUT-major and multiplied by `moe_gmm_dlhs`; held (32, 2688,
    1856) the compiler lays the stack K-minor and copies 330 MB of it in front of the call."""
    from galvatron_tpu.models import moe

    cfg = _nemotron_cut()
    sd = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    p = jax.tree.map(sd, jax.eval_shape(lambda k: moe.init_moe_params(k, cfg), jax.random.key(0)))
    assert p["w1"].shape == (32, 1856, 2688) == p["w2"].shape
    x = jax.ShapeDtypeStruct((tokens, 1, 2688), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda x_, p_: moe.moe_topk_block(x_, p_, cfg, forward_only=True)[0]
                       ).lower(x, p).compile()
    text = compiled.as_text()
    kernels = [n for n, _ in _entry_work(text) if "moe_gmm" in n]
    assert len(kernels) == 2 and any("moe_gmm_dlhs" in n for n in kernels), kernels
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.15 * 2**30, f"{temp / 2**30:.2f} GiB"
    assert not [line[:160] for line in text.splitlines()
                if " copy(" in line and "bf16[32," in line and "1856" in line]



# -- granite-4.0-h-small at its published widths (PR 70): ONE scan group of 8192 lanes through
# the state kernels, 36 of 72 gated experts of width 768 on the bounded path ---


def _granite_small_cut():
    from galvatron_tpu.models.modeling import PRESETS

    return PRESETS["granite-4.0-h-small"].replace(
        num_layers=10, vocab_size=50176, moe_share=(0, 2), param_dtype=jnp.bfloat16)


@pytest.mark.parametrize("form", ["decode_step", "prompt_chunk"])
def test_granite_small_mixer_moves_the_wide_state_in_place(one_chip, real_mosaic, form):
    """Two Mamba-2 layers of the cell's stack (9 layers x 32 rows x 4 MiB: 1.2 GB of float32
    scan state) as the chip's compiler sees the cached forward: a decode step through
    `ssm_step` (the one group of 8192 lanes a block of 4 MiB: 16 MiB resident, which Mosaic
    takes under `pallas_common.VMEM_LIMIT_MB`), a prompt chunk through `ssm_state_read` /
    `ssm_state_write` around the plain scan; the stack is donated and NOTHING of its size
    is copied."""
    import re

    from galvatron_tpu.models import ssm

    cfg = _granite_small_cut()
    assert ssm.path_counts(cfg)["step"] == {"fused": 9, "plain": 0}
    sd = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    p = jax.tree.map(sd, jax.eval_shape(lambda k: ssm.init_params(k, cfg), jax.random.key(0)))
    state = jax.tree.map(sd, jax.eval_shape(lambda: ssm.init_state(cfg, 9, 32)))
    assert state.scan.shape == (9, 32, 128, 8192) and state.scan.dtype == jnp.float32
    assert state.conv.shape == (9, 32, 3 * 8448) and state.conv.dtype == jnp.bfloat16
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    if form == "decode_step":
        x = jax.ShapeDtypeStruct((32, 1, 4096), jnp.bfloat16, sharding=one_chip)
        at = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)

        def fn(x_, p_, st, offsets):
            y, st = ssm.cached_block(x_, p_, cfg, st, 3, None, offsets, 0)
            return ssm.cached_block(x_ + y, p_, cfg, st, 7, None, offsets, 0)

        args, names = (x, p, state, at), ["ssm_step"] * 2
    else:
        x = jax.ShapeDtypeStruct((1, 1024, 4096), jnp.bfloat16, sharding=one_chip)

        def fn(x_, p_, st, slot, start, last):
            y, st = ssm.cached_block(x_, p_, cfg, st, 3, slot, start, last)
            return ssm.cached_block(x_ + y, p_, cfg, st, 7, slot, start, last)

        args, names = (x, p, state, i32, i32, i32), ["ssm_state_read", "ssm_state_write"] * 2
    compiled = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()
    text = compiled.as_text()
    found = [m.group(0) for n, _ in _entry_work(text)
             for m in [re.search(r"ssm_(?:step|state_read|state_write)", n)] if m]
    assert sorted(found) == sorted(names), found
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 0.4 * 2**30, f"{ma.temp_size_in_bytes / 2**30:.2f} GiB"
    assert ma.alias_size_in_bytes >= 9 * 32 * 4244992
    assert not [line[:160] for line in text.splitlines()
                if " copy(" in line and "9,32,128,8192" in line]


@pytest.mark.parametrize("tokens", [32, 1024], ids=["decode_step", "prompt_chunk"])
def test_granite_small_held_experts_take_the_bounded_kernels(one_chip, real_mosaic, tokens):
    """An expert layer of the cell as a CACHED forward runs it: 36 of 72 held (neither a
    power of two), top-10, gated, width 768 = 6 lane tiles on a hidden of 4096, a decode
    step's 32 tokens (4.4 rows an expert: tile 16) and a prompt chunk's 1,024 (142: tile
    128): the bounded path's six kernels, no copy of a stack of the experts' weights."""
    import re

    from galvatron_tpu.models import generation, moe

    cfg = _granite_small_cut()
    assert (cfg.moe_experts, cfg.moe_held, cfg.moe_top_k, cfg.expert_ffn) == (72, 36, 10, 768)
    assert moe.held_path_counts(cfg) == {"bounded": 10, "worst_case": 0}
    assert moe.layer_row_tile(cfg, tokens) == (16 if tokens == 32 else 128)
    shapes = jax.eval_shape(
        lambda k: {"mlp": moe.init_moe_params(k, cfg),
                   "mlp_norm": {"scale": jnp.zeros((cfg.hidden_size,), cfg.param_dtype)}},
        jax.random.key(0))
    assert shapes["mlp"]["w1"].shape == (36, 4096, 768)
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    x = jax.ShapeDtypeStruct((tokens, 1, 4096) if tokens == 32 else (1, tokens, 4096),
                             jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda x_, p_: generation._mlp_at(x_, p_, cfg, None)).lower(x, p).compile()
    text = compiled.as_text()
    kernels = sorted(n.split(".")[0] for n, _ in _entry_work(text) if n.startswith("moe_"))
    assert kernels == sorted(["moe_held_rows", "moe_gmm", "moe_gmm", "moe_held_swiglu", "moe_gmm",
                              "moe_held_pairs"]), kernels
    assert not re.search(r"= bf16\[36,4096,(768|1536)\]\S* (fusion|copy|concatenate)\(",
                         "\n".join(_entry_lines(text)))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2**30
