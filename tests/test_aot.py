"""AOT compile subsystem (galvatron_tpu/aot): keys, store, warmup, warm starts.

Key invalidation is the safety contract: every term of the program key —
XLA flags, plan hash, model shape, jax version, abstract signature — must
force a miss when it changes and a hit when it does not.  The e2e tests pin
the measurable claim: `warmup` (or a prior run) makes the NEXT start's
compile a cache lookup, the manifest reports hits for every registered
program, and a proven-warm start shrinks the watchdog's first-step grace.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from galvatron_tpu.aot import cache as aot_cache
from galvatron_tpu.aot import registry as aot_registry
from galvatron_tpu.aot import warmup as aot_warmup
from galvatron_tpu.core.strategy import HybridParallelConfig
from galvatron_tpu.models.modeling import ModelConfig

TINY = dict(
    vocab_size=128, hidden_size=32, num_layers=2, num_heads=2, ffn_dim=64,
    max_seq_len=16, dtype=jnp.float32, param_dtype=jnp.float32, attn_impl="xla",
)


def tiny_cfg(**kw):
    return ModelConfig(**{**TINY, **kw})


def tiny_hp(**kw):
    return HybridParallelConfig.uniform(2, mixed_precision="fp32", **kw)


@pytest.fixture
def tmp_cache(tmp_path):
    """Redirect the process-wide persistent cache to a fresh dir and RESTORE
    the suite's shared .jax_cache afterwards — the rest of the suite's
    warm-cache timing must not be collateral."""
    old = getattr(jax.config, "jax_compilation_cache_dir", None)
    d = str(tmp_path / "aot_cache")
    aot_cache.enable_persistent_cache(d)
    yield d
    if old:
        aot_cache.enable_persistent_cache(old, min_compile_time_s=0.5)


# ---------------------------------------------------------------------------
# program keys: every term invalidates
# ---------------------------------------------------------------------------


class TestProgramKey:
    TOPO = {"platform": "cpu", "device_kind": "cpu", "device_count": 8,
            "process_count": 1}
    FLAGS = {"XLA_FLAGS": ["--xla_foo=1"], "LIBTPU_INIT_ARGS": None}

    def key(self, **over):
        kw = dict(
            plan=tiny_hp(), model_cfg=tiny_cfg(),
            abstract_args=(jax.ShapeDtypeStruct((8, 17), jnp.int32),),
            topology=self.TOPO, xla_flags=self.FLAGS, jax_version="1.0/2.0",
        )
        kw.update(over)
        return aot_cache.program_key("train_step", **kw)

    def test_identical_inputs_hash_identically(self):
        assert self.key() == self.key()

    def test_changed_xla_flag_forces_miss(self):
        assert self.key() != self.key(
            xla_flags={"XLA_FLAGS": ["--xla_foo=2"], "LIBTPU_INIT_ARGS": None}
        )

    def test_changed_plan_hash_forces_miss(self):
        assert self.key() != self.key(plan=tiny_hp(tp=2))
        assert self.key() != self.key(plan=tiny_hp(ckpt="full"))

    def test_changed_model_shape_forces_miss(self):
        assert self.key() != self.key(model_cfg=tiny_cfg(hidden_size=64))
        assert self.key() != self.key(model_cfg=tiny_cfg(vocab_size=256))

    def test_changed_jax_version_forces_miss(self):
        assert self.key() != self.key(jax_version="1.1/2.0")

    def test_changed_abstract_signature_forces_miss(self):
        assert self.key() != self.key(
            abstract_args=(jax.ShapeDtypeStruct((16, 17), jnp.int32),)
        )

    def test_plan_provenance_keys_do_not_change_the_key(self):
        # same property plan_hash gives plans: provenance keys and key order
        # never matter — a re-searched identical strategy stays warm
        d = tiny_hp().to_json_dict()
        d2 = dict(d, search_cost_ms=123.4, num_devices=8, model_size="x")
        assert self.key(plan=d) == self.key(plan=d2)

    def test_executed_config_is_part_of_the_key(self):
        assert self.key() != self.key(model_cfg=tiny_cfg(attn_impl="flash"))
        assert self.key() != self.key(model_cfg=tiny_cfg(pack_sequences=True))

    def test_flag_token_order_is_normalized(self):
        a = {"XLA_FLAGS": sorted(["--b=1", "--a=2"]), "LIBTPU_INIT_ARGS": None}
        assert self.key(xla_flags=a) == self.key(
            xla_flags=aot_cache.xla_flag_signature({"XLA_FLAGS": "--b=1 --a=2"})
        )

    def test_duplicate_flag_tokens_do_not_change_the_key(self):
        # a launcher's XLA_FLAGS + force_cpu_world's append of the SAME
        # world flag must key identically to stating it once (caught live:
        # warmup --force_world 8 under a CPU-sim launcher never hit)
        once = aot_cache.xla_flag_signature({"XLA_FLAGS": "--a=2 --b=1"})
        twice = aot_cache.xla_flag_signature({"XLA_FLAGS": "--a=2 --b=1 --a=2"})
        assert self.key(xla_flags=once) == self.key(xla_flags=twice)


# ---------------------------------------------------------------------------
# manifest store: atomic accounting
# ---------------------------------------------------------------------------


def test_store_accounting_and_invalidation(tmp_path):
    store = aot_cache.ArtifactStore(str(tmp_path))
    assert store.lookup("aot:abc") is None
    store.record_compile("aot:abc", program="train_step", compile_ms=123.0, hit=False)
    e = store.lookup("aot:abc")
    assert e["program"] == "train_step" and e["compiles"] == 1 and e["hits"] == 0
    store.record_compile("aot:abc", program="train_step", compile_ms=5.0, hit=True)
    e = store.lookup("aot:abc")
    assert e["compiles"] == 2 and e["hits"] == 1
    assert e["first_compile_ms"] == 123.0 and e["last_compile_ms"] == 5.0
    assert store.stats()["session_hits"] == 1 and store.stats()["session_misses"] == 1
    # no stray tmp files survive the committed writes
    assert not [f for f in os.listdir(str(tmp_path)) if ".tmp." in f]
    assert store.invalidate() == 1
    assert store.lookup("aot:abc") is None
    assert store.stats()["invalidations"] == 1


def test_store_tolerates_torn_manifest(tmp_path, capsys):
    store = aot_cache.ArtifactStore(str(tmp_path))
    store.record_compile("aot:k", program="p", compile_ms=1.0, hit=False)
    with open(store.manifest_path, "w") as f:
        f.write('{"schema": "galvatron-aot-v1", "programs": {"aot:k"')  # torn
    # the manifest is parsed once per store instance (a P-program sweep must
    # not pay P full parses of an ever-growing file), so the torn file
    # surfaces to the NEXT process's store — the crash-restart case the
    # tolerance exists for
    fresh = aot_cache.ArtifactStore(str(tmp_path))
    assert fresh.lookup("aot:k") is None  # reset, not raised
    assert "resetting" in capsys.readouterr().out
    fresh.record_compile("aot:k2", program="p", compile_ms=1.0, hit=False)
    assert fresh.lookup("aot:k2") is not None
    # and the reset commit is durable: a third store reads it back clean
    assert aot_cache.ArtifactStore(str(tmp_path)).lookup("aot:k2") is not None


def test_resolve_compile_cache_dir_precedence(tmp_path, monkeypatch):
    class NS:
        compile_cache_dir = None
        save = None

    ns = NS()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # nothing given: the one fixed path inside the checkout, --save or not
    assert aot_cache.resolve_compile_cache_dir(ns) == os.path.join(repo, ".jax_cache")
    ns.save = str(tmp_path / "ckpt")
    assert aot_cache.resolve_compile_cache_dir(ns) == os.path.join(repo, ".jax_cache")
    # without the env an explicit flag places it; the disable spellings disable
    ns.compile_cache_dir = str(tmp_path / "x")
    assert aot_cache.resolve_compile_cache_dir(ns) == str(tmp_path / "x")
    for off in ("0", "off", "none"):
        ns.compile_cache_dir = off
        assert aot_cache.resolve_compile_cache_dir(ns) is None
    # the env places the cache from outside: it beats the default AND the flag
    envd = str(tmp_path / "envd")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", envd)
    ns.compile_cache_dir = None
    assert aot_cache.resolve_compile_cache_dir(ns) == envd
    ns.compile_cache_dir = str(tmp_path / "x")
    assert aot_cache.resolve_compile_cache_dir(ns) == envd
    # ... and enable_persistent_cache will not redirect away from it either
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert aot_cache.enable_persistent_cache(str(tmp_path / "x")) == envd
        assert jax.config.jax_compilation_cache_dir == envd
    finally:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        aot_cache.enable_persistent_cache(prev)


# ---------------------------------------------------------------------------
# registry: enumeration from shapes alone
# ---------------------------------------------------------------------------


def test_enumerate_programs_covers_every_registered_family():
    ctx = aot_registry.ProgramContext(cfg=tiny_cfg(), hp=tiny_hp(), global_bsz=8)
    names = {s.name for s in aot_registry.enumerate_programs(ctx)}
    assert {"train_step", "eval_loss", "init_state",
            "serving_prefill", "serving_decode", "generate"} <= names
    # plan-free context: the trainer family (needs_plan) is skipped
    free = aot_registry.ProgramContext(cfg=tiny_cfg())
    free_names = {s.name for s in aot_registry.enumerate_programs(free)}
    assert "train_step" not in free_names
    assert {"serving_prefill", "serving_decode", "generate"} <= free_names


def test_enumerate_include_filters_by_family_and_name():
    ctx = aot_registry.ProgramContext(cfg=tiny_cfg(), hp=tiny_hp(), global_bsz=8)
    only = aot_registry.enumerate_programs(ctx, include=("serving_decode",))
    assert [s.name for s in only] == ["serving_decode"]
    fam = aot_registry.enumerate_programs(ctx, include=("serving",))
    assert {s.name for s in fam} == {"serving_prefill", "serving_decode", "serving_sample"}


def test_non_causal_model_has_no_serving_or_generate_programs():
    ctx = aot_registry.ProgramContext(cfg=tiny_cfg(causal=False, objective="mlm"))
    assert aot_registry.enumerate_programs(ctx) == []


def test_cli_warmup_and_train_parsers_agree_on_step_program_terms():
    """`cli warmup` must warm the exact keys a default train run consults:
    every step-program flag is a program_key term, so the two parsers must
    share the flags AND their defaults, and the warmup sweep mirrors the
    trainer's adam construction. Caught live: the train parser's
    --weight_decay 0.01 vs AdamConfig's 0.0 default keyed every cli-warmup
    train_step apart from every real run (init_state hit, train_step
    missed)."""
    from galvatron_tpu.core.arguments import (
        adam_config_from_args,
        initialize_galvatron,
    )

    w = initialize_galvatron("warmup", [])
    t = initialize_galvatron("train", [])
    assert adam_config_from_args(w) == adam_config_from_args(t)
    for flag in ("mixed_precision", "attn_impl", "mlp_recompute",
                 "pack_sequences", "lr", "weight_decay", "grad_clip"):
        assert getattr(w, flag) == getattr(t, flag), flag
    # and the non-default path: an explicit optimizer flag must be
    # expressible on the warmup surface and land in the same config
    w2 = initialize_galvatron("warmup", ["--weight_decay", "0.2"])
    t2 = initialize_galvatron("train", ["--weight_decay", "0.2"])
    assert adam_config_from_args(w2) == adam_config_from_args(t2)
    # serve/generate must be able to EXPRESS the one step-program term they
    # share with warmup (an explicit --attn_impl is a program-key term; a
    # flag warmup can pass but serve cannot would warm unreachable keys)
    s = initialize_galvatron("serve", ["--attn_impl", "xla"])
    assert s.attn_impl == "xla"
    assert initialize_galvatron("generate", []).attn_impl == w.attn_impl == "auto"


# ---------------------------------------------------------------------------
# warmup: second pass hits, no recompile; failures isolate
# ---------------------------------------------------------------------------


def test_warmup_twice_second_pass_all_hits_no_recompile(tmp_path):
    from galvatron_tpu.analysis.guards import recompile_guard

    # manifest-level semantics only: the store gets a fresh dir (hit/miss
    # must start cold) while the compiles themselves ride the suite's warm
    # shared .jax_cache — redirecting the process cache here would re-pay
    # cold XLA compiles on every tier-1 run for no extra coverage
    store = aot_cache.ArtifactStore(str(tmp_path))
    ctx = aot_registry.ProgramContext(cfg=tiny_cfg(), hp=tiny_hp(tp=2), global_bsz=8)
    specs = aot_registry.enumerate_programs(
        ctx, include=("train_step", "serving_decode")
    )
    assert {s.name for s in specs} == {"train_step", "serving_decode"}
    first = aot_warmup.warmup_programs(
        specs, store, plan=ctx.hp, model_cfg=ctx.cfg, verbose=False
    )
    assert all(r["status"] == "compiled" and not r["cache_hit"] for r in first)
    # identical inputs: manifest hits, and the guarded jit caches of the
    # warmed functions grow by NOTHING — warmup never recompiles
    with recompile_guard(*[s.fn for s in specs], allowed=0, label="aot rewarm"):
        second = aot_warmup.warmup_programs(
            specs, store, plan=ctx.hp, model_cfg=ctx.cfg, verbose=False
        )
    assert all(r["status"] == "compiled" and r["cache_hit"] for r in second)
    st = store.stats()
    assert st["session_hits"] == 2 and st["session_misses"] == 2


def test_warmup_isolates_per_program_failure(tmp_path):
    store = aot_cache.ArtifactStore(str(tmp_path))
    good = aot_registry.enumerate_programs(
        aot_registry.ProgramContext(cfg=tiny_cfg()), include=("serving_decode",)
    )[0]

    class Boom:
        def lower(self, *a, **k):
            raise RuntimeError("Protocol Buffer reflection usage error")

    bad = aot_registry.ProgramSpec("doomed", Boom(), ())
    reports = aot_warmup.warmup_programs(
        [bad, good], store, model_cfg=tiny_cfg(), verbose=False
    )
    assert reports[0]["status"] == "failed"
    assert "Protocol Buffer" in reports[0]["error"]
    assert reports[1]["status"] == "compiled"  # the sweep continued


def test_warmup_report_splits_lower_ms_from_compile_ms(tmp_path):
    """The auditor is lower-only, warmup is lower+compile: the report must
    carry the two phases separately so their numbers are comparable — and
    the footprint sink sees the lowered StableHLO text of every program,
    with a sink failure degrading to a warning, never killing the sweep."""
    store = aot_cache.ArtifactStore(str(tmp_path))
    spec = aot_registry.enumerate_programs(
        aot_registry.ProgramContext(cfg=tiny_cfg()), include=("serving_decode",)
    )[0]
    texts = []
    [r] = aot_warmup.warmup_programs(
        [spec], store, model_cfg=tiny_cfg(), verbose=False,
        footprint_sink=lambda s, t: texts.append((s.name, t)),
    )
    assert r["status"] == "compiled"
    assert r["lower_ms"] is not None and r["lower_ms"] >= 0.0
    assert r["compile_ms"] is not None and r["compile_ms"] >= 0.0
    assert [n for n, _ in texts] == ["serving_decode"]
    assert "func.func" in texts[0][1]  # lowered StableHLO, not a repr

    def boom(s, t):
        raise RuntimeError("sink exploded")

    [r2] = aot_warmup.warmup_programs(
        [spec], store, model_cfg=tiny_cfg(), verbose=False, footprint_sink=boom,
    )
    assert r2["status"] == "compiled"


def test_manifest_write_failure_does_not_abort_sweep(tmp_path, monkeypatch):
    """The manifest is advisory: a store write failure (disk full, read-only
    mount) after an expensive compile degrades to a warning, never kills the
    sweep or `cli serve` startup."""
    store = aot_cache.ArtifactStore(str(tmp_path))
    monkeypatch.setattr(
        store, "record_compile",
        lambda *a, **k: (_ for _ in ()).throw(OSError("No space left on device")),
    )
    spec = aot_registry.enumerate_programs(
        aot_registry.ProgramContext(cfg=tiny_cfg()), include=("serving_decode",)
    )[0]
    [report] = aot_warmup.warmup_programs(
        [spec], store, model_cfg=tiny_cfg(), verbose=False
    )
    assert report["status"] == "compiled"
    assert "No space left" in report["manifest_error"]


def test_trainer_program_batch_aval_tracks_packing():
    """A packed run dispatches (B, 2·(S+1)) rows (data/packing.py), not
    (B, S+1): the trainer-family aval must track cfg.pack_sequences or the
    warmed key is one the run never consults — and a manifest hit on the
    wrong-shape key would wrongly drop the watchdog's first-step grace."""
    S = TINY["max_seq_len"]
    packed = aot_registry.ProgramContext(
        cfg=tiny_cfg(pack_sequences=True), hp=tiny_hp(), global_bsz=8
    )
    spec = next(s for s in aot_registry.enumerate_programs(packed)
                if s.name == "train_step")
    assert spec.args[1].shape == (8, 2 * (S + 1))
    plain = aot_registry.ProgramContext(cfg=tiny_cfg(), hp=tiny_hp(), global_bsz=8)
    spec = next(s for s in aot_registry.enumerate_programs(plain)
                if s.name == "train_step")
    assert spec.args[1].shape == (8, S + 1)


def test_serialized_executable_roundtrip(tmp_cache):
    # a FRESH jax cache matters here: an executable deserialized from a warm
    # compile cache serializes into an unloadable blob on CPU, which
    # save_executable must (and does) detect and refuse to record
    store = aot_cache.ArtifactStore(tmp_cache)
    spec = aot_registry.enumerate_programs(
        aot_registry.ProgramContext(cfg=tiny_cfg()), include=("serving_decode",)
    )[0]
    [report] = aot_warmup.warmup_programs(
        [spec], store, model_cfg=tiny_cfg(), serialize=True, verbose=False
    )
    assert store.load_executable("aot:missing") is None
    if not report.get("serialized"):
        # the backend (or this executable's provenance — e.g. it was itself
        # deserialized) cannot round-trip: the refusal must leave NO .exec
        # file and NO serialized marker behind
        assert not [f for f in os.listdir(tmp_cache) if f.endswith(".exec")]
        assert not store.lookup(report["key"]).get("serialized")
        pytest.skip("backend cannot round-trip serialized AOT executables")
    loaded = store.load_executable(report["key"])
    assert loaded is not None
    assert store.lookup(report["key"]).get("serialized") is True


# ---------------------------------------------------------------------------
# watchdog: warm-cache hint shrinks the first-step grace
# ---------------------------------------------------------------------------


def test_watchdog_first_step_scale_warm_vs_cold():
    from galvatron_tpu.core.watchdog import HangWatchdog

    fired = []
    # warm hint: the first armed step runs at the NORMAL deadline — a real
    # first-step hang is detected in ~timeout, not 10x it
    wd = HangWatchdog(0.2, fired.append, exit_code=None, first_step_scale=1.0,
                      poll_s=0.02)
    wd.arm(0)
    time.sleep(0.6)
    assert wd.fired and fired == [0]
    wd.close()
    # cold default: the same wait sits far inside the 10x compile grace
    fired2 = []
    wd2 = HangWatchdog(0.2, fired2.append, exit_code=None, poll_s=0.02)
    wd2.arm(0)
    time.sleep(0.6)
    assert not wd2.fired and fired2 == []
    # a known-recompile step (rampup) keeps the compile-length deadline
    # even on a warm watchdog
    wd2.disarm()
    wd2.arm(1, warmup=True)
    time.sleep(0.6)
    assert not wd2.fired
    wd2.close()


# ---------------------------------------------------------------------------
# e2e: warmup → train reports hits for every program, lower startup compile
# ---------------------------------------------------------------------------


def _train_args(d, cache, tag, extra=()):
    return [
        "--model_size", "llama-0.3b", "--num_layers", "2", "--hidden_size", "32",
        "--num_heads", "2", "--ffn_dim", "64", "--vocab_size", "128",
        "--seq_length", "16", "--global_train_batch_size", "8",
        "--train_iters", "3", "--mixed_precision", "fp32",
        "--compile_cache_dir", cache,
        "--metrics_path", os.path.join(d, f"metrics_{tag}.jsonl"),
        *extra,
    ]


def _read_warmup_events(d, tag):
    recs = [json.loads(l) for l in open(os.path.join(d, f"metrics_{tag}.jsonl"))]
    cc = [r for r in recs if r["event"] == "compile_cache"]
    aw = [r for r in recs if r["event"] == "aot_warmup"]
    assert len(aw) == 1
    return cc, aw[0]


def test_warm_start_end_to_end(tmp_cache, tmp_path):
    """The acceptance pin: warm the plan (here via a first run — `cli
    warmup` drives the same warmup_plan path, covered by the CI smoke job),
    then a 3-iter run on the same plan reports a cache hit for EVERY
    registered trainer program and measurably lower startup compile_ms."""
    from galvatron_tpu.core.arguments import initialize_galvatron
    from galvatron_tpu.core.trainer import train

    d = str(tmp_path)
    ns = initialize_galvatron("train", _train_args(d, tmp_cache, "cold"))
    train(ns, verbose=False)
    cc_cold, aw_cold = _read_warmup_events(d, "cold")
    # the consult warms exactly what a fresh-start run dispatches
    assert {r["program"] for r in cc_cold} == {"train_step", "init_state"}
    assert all(not r["hit"] for r in cc_cold)
    assert aw_cold["warm_hint"] is False

    ck = os.path.join(d, "ck")
    ns = initialize_galvatron(
        "train", _train_args(d, tmp_cache, "warm", extra=["--save", ck])
    )
    train(ns, verbose=False)
    cc_warm, aw_warm = _read_warmup_events(d, "warm")
    assert {r["program"] for r in cc_warm} == {"train_step", "init_state"}
    assert all(r["hit"] for r in cc_warm), cc_warm
    assert aw_warm["warm_hint"] is True
    assert aw_warm["startup_compile_ms"] < aw_cold["startup_compile_ms"], (
        aw_cold, aw_warm,
    )


def test_elastic_prewarm_on_replan(tmp_path, monkeypatch):
    """The re-plan→restart path: prepare_topology prewarms the NEW plan's
    programs into the artifact cache, installs the cache dir on the child's
    args, and a subsequent trainer consult of the same plan reports hits —
    which is exactly what arms the reduced first-step watchdog grace.

    The prewarm rides the suite's shared .jax_cache (auto-resolution — the
    same path a supervised child takes): manifest accounting is what the
    test pins, and a fresh jax cache would re-pay a cold XLA compile on
    every tier-1 run for no extra coverage."""
    from galvatron_tpu.core import elastic
    from galvatron_tpu.core.arguments import initialize_galvatron

    d = str(tmp_path)
    plan_path = os.path.join(d, "plan_live.json")
    hp_live = tiny_hp()
    pd = hp_live.to_json_dict()
    pd["num_devices"] = 8
    with open(plan_path, "w") as f:
        json.dump(pd, f)
    args = _train_args(d, "unused", "elastic", extra=["--load", os.path.join(d, "ck")])
    i = args.index("--compile_cache_dir")
    del args[i:i + 2]  # auto-resolution: configured suite cache wins
    ns = initialize_galvatron("train", args)
    # a committed checkpoint recorded on a 4-device world, live world 8:
    # the GTA017 mismatch routes through the re-plan, which we pin to the
    # prepared plan file (the search itself is covered by test_elastic)
    monkeypatch.setattr(
        elastic, "_read_fingerprint",
        lambda load: {"world_size": 4, "plan_hash": "sha256:stale",
                      "global_bsz": 8},
    )
    import galvatron_tpu.search.replan as replan

    monkeypatch.setattr(
        replan, "resolve_plan_for_topology",
        lambda *a, **k: (plan_path, "cache"),
    )
    info = elastic.prepare_topology(ns, verbose=False)
    assert info is not None and info["plan_path"] == plan_path
    prewarm = info["prewarm"]
    assert prewarm is not None and prewarm["failed"] == 0
    assert prewarm["compiled"] == 1  # the step program IS the restart cost
    cache_dir = ns.compile_cache_dir
    assert cache_dir  # prewarm made the consult explicit for train()
    assert ns.galvatron_config_path == plan_path and ns.allow_topology_change
    # the trainer-side consult of the SAME plan now hits — the warm hint
    from galvatron_tpu.core.arguments import (
        adam_config_from_args,
        model_config_from_args,
        resolve_execution_config,
    )

    cfg = resolve_execution_config(model_config_from_args(ns), ns)
    store = aot_cache.ArtifactStore(cache_dir)
    reports = aot_warmup.warmup_plan(
        cfg, HybridParallelConfig.load(plan_path), global_bsz=8, store=store,
        include=("train_step",), adam=adam_config_from_args(ns), verbose=False,
    )
    ts = next(r for r in reports if r["program"] == "train_step")
    assert ts["cache_hit"] is True, reports
