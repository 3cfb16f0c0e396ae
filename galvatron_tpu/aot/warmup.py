"""AOT warmup: compile every registered program from abstract shapes.

``cli warmup`` (and the trainer's startup consult, and the elastic child's
re-plan prewarm) all funnel through here: enumerate the programs a
(plan × ModelConfig × mesh) run needs (`aot/registry.py`), ``lower`` each
from its abstract inputs, ``compile``, and account the result against the
plan-keyed manifest (`aot/cache.py`).  With the persistent compile cache
enabled, a warmed program's next compile — in ANY process on this host —
is a disk deserialize instead of an XLA compile, which is what turns a
trainer start, an elastic restart, or a serving cold-start into a cache
lookup.

Failure isolation is the contract: one program failing to compile (a kernel
the backend's compiler refuses, a backend without some feature) degrades to a per-program ``status: failed`` report and a printed
warning — it must never abort the sweep, because the other programs' warmth
is exactly as valuable without it.

Each report also carries the compiled program's ``memory_analysis`` peak
buffer numbers where the backend exposes them, next to the cost model's
analytic prediction — the same number GTA015 gates plans on — so a warmup
sweep doubles as a cheap feasibility cross-check.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence

from galvatron_tpu.aot import cache as aot_cache
from galvatron_tpu.aot import registry as aot_registry


def force_cpu_world(n_devices: int) -> None:
    """Simulate an ``n_devices``-wide CPU platform — the ONE copy of the
    recipe (``cli warmup --force_world``, the elastic child's sim-world
    bootstrap, the multi-chip dry run's child and tests/conftest.py all call
    it): XLA_FLAGS append + platform pin, which wins over ``JAX_PLATFORMS``
    in the environment.  Must run before the first backend touch;
    permanently redirects this process to CPU."""
    import jax

    flag = f"--xla_force_host_platform_device_count={int(n_devices)}"
    cur = os.environ.get("XLA_FLAGS", "")
    if flag not in cur.split():  # idempotent: a duplicate token would also
        # key the compile cache apart from a run whose env already had it
        os.environ["XLA_FLAGS"] = (cur + " " + flag).strip()
    jax.config.update("jax_platforms", "cpu")


def memory_stats(compiled) -> Optional[Dict[str, float]]:
    """Peak-buffer numbers from the compiled program's ``memory_analysis``:
    state (arguments + outputs − aliased, so a donated train state counts
    once) and temp (grads + activations + scratch) in MB — the decomposition
    `search/memory_fidelity.py` validates the cost model against."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 — optional per backend
        return None
    if ma is None:
        return None
    try:
        state = (
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            - ma.alias_size_in_bytes
        ) / 1e6
        temp = ma.temp_size_in_bytes / 1e6
        out = {
            "state_mb": round(state, 3),
            "temp_mb": round(temp, 3),
            "total_mb": round(state + temp, 3),
        }
        code = getattr(ma, "generated_code_size_in_bytes", None)
        if code is not None:
            out["code_bytes"] = int(code)
        return out
    except AttributeError:
        return None


def predicted_train_memory_mb(cfg, hp, world: int, global_bsz: int) -> Optional[float]:
    """The cost model's analytic per-device MB for this plan — the exact
    number the GTA015 feasibility check gates on — so warmup reports carry
    predicted-vs-compiled memory side by side.  None where the analytic
    pricing does not apply (vision/MoE corner shapes)."""
    try:
        from galvatron_tpu.search.memory_fidelity import predicted_train_mb
        from galvatron_tpu.search.theoretical import analytic_model_costs

        return round(
            predicted_train_mb(analytic_model_costs(cfg), cfg, hp, world, global_bsz),
            1,
        )
    except Exception:  # noqa: BLE001 — a cross-check must not fail the sweep
        return None


def compile_program(
    spec: aot_registry.ProgramSpec,
    store: Optional[aot_cache.ArtifactStore] = None,
    *,
    plan: Any = None,
    model_cfg: Any = None,
    serialize: bool = False,
    verbose: bool = True,
    footprint_sink: Any = None,
) -> Dict[str, Any]:
    """AOT-lower + compile ONE program, failure-isolated.

    Returns ``{program, key, status: compiled|failed, cache_hit, lower_ms,
    compile_ms, memory, error}``.  ``lower_ms`` (tracing + StableHLO
    emission) is split from ``compile_ms`` (XLA) so these rows are directly
    comparable with the lower-only comm auditor's numbers.  ``cache_hit`` is
    manifest-based: the key was recorded by an earlier warmup/run, so the
    persistent cache serves the executable and ``compile_ms`` is
    deserialization, not XLA.  ``footprint_sink``, when given, is called
    with each program's lowered StableHLO text as
    ``footprint_sink(spec, text)`` — the warmup comm-footprint hook
    (sink failures are isolated like everything else here)."""
    from galvatron_tpu.obs.tracing import tracer

    key = None
    try:
        key = aot_cache.program_key(
            spec.name,
            plan=plan,
            # the spec's executed config (what the engine actually compiled
            # from) beats the caller's pre-build view for keying — the two
            # must agree between a prewarm and a later startup consult
            model_cfg=spec.meta.get("exec_cfg", model_cfg),
            abstract_args=spec.args,
            abstract_kwargs=spec.kwargs,
            donate=spec.meta.get("donate"),
            extra=spec.meta.get("key_extra"),
        )
    except Exception as e:  # noqa: BLE001 — keying must not abort the sweep
        if verbose:
            print(f"aot: keying {spec.name} failed: {type(e).__name__}: {e}")
    hit = bool(store is not None and key is not None and store.lookup(key))
    report: Dict[str, Any] = {
        "program": spec.name,
        "key": key,
        "cache_hit": hit,
        "status": "compiled",
        "lower_ms": None,
        "compile_ms": None,
        "memory": None,
        "error": None,
    }
    t0 = time.perf_counter()
    try:
        with tracer.span("aot_compile", program=spec.name, hit=hit):
            lowered = spec.fn.lower(*spec.args, **spec.kwargs)
            report["lower_ms"] = round((time.perf_counter() - t0) * 1000.0, 1)
            if footprint_sink is not None:
                try:
                    footprint_sink(spec, lowered.as_text())
                except Exception as e:  # noqa: BLE001 — the footprint is
                    # advisory: losing it must never cost the warmup
                    if verbose:
                        print(f"aot: WARNING — footprint sink failed for "
                              f"{spec.name}: {type(e).__name__}: {e}")
            t1 = time.perf_counter()
            compiled = lowered.compile()
            report["compile_ms"] = round((time.perf_counter() - t1) * 1000.0, 1)
    except Exception as e:  # noqa: BLE001 — per-program isolation IS the contract
        # e.g. a program the backend's compiler refuses: warn, move on
        report["status"] = "failed"
        report["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        if report["compile_ms"] is None:
            report["compile_ms"] = round((time.perf_counter() - t0) * 1000.0, 1)
        if verbose:
            print(f"aot: WARNING — {spec.name} failed to compile "
                  f"({report['error']}); continuing the sweep")
        return report
    report["memory"] = memory_stats(compiled)
    if store is not None and key is not None:
        try:
            store.record_compile(
                key,
                program=spec.name,
                compile_ms=report["compile_ms"],
                hit=hit,
                meta={"family": spec.meta.get("family")},
            )
            if serialize and not hit:
                report["serialized"] = store.save_executable(key, compiled)
        except Exception as e:  # noqa: BLE001 — manifest is advisory: losing
            # it costs accounting, never correctness (and never the sweep)
            report["manifest_error"] = f"{type(e).__name__}: {str(e)[:300]}"
            if verbose:
                print(f"aot: WARNING — {spec.name} compiled but the manifest "
                      f"write failed ({report['manifest_error']}); continuing")
    if verbose:
        mem = report["memory"]
        mem_s = f", peak {mem['total_mb']:.0f} MB" if mem else ""
        print(
            f"aot: {spec.name}: {'hit' if hit else 'miss'}, "
            f"lower {report['lower_ms']:.0f} ms, "
            f"compile {report['compile_ms']:.0f} ms{mem_s}"
        )
    return report


def warmup_programs(
    specs: Sequence[aot_registry.ProgramSpec],
    store: Optional[aot_cache.ArtifactStore] = None,
    *,
    plan: Any = None,
    model_cfg: Any = None,
    serialize: bool = False,
    verbose: bool = True,
    footprint_sink: Any = None,
) -> List[Dict[str, Any]]:
    """Compile every spec (failure-isolated); one report per program."""
    from galvatron_tpu.obs.tracing import tracer

    with tracer.span("aot_warmup", programs=len(specs)):
        return [
            compile_program(
                s, store, plan=plan, model_cfg=model_cfg,
                serialize=serialize, verbose=verbose,
                footprint_sink=footprint_sink,
            )
            for s in specs
        ]


def warmup_plan(
    cfg,
    hp,
    *,
    global_bsz: int,
    seq_len: Optional[int] = None,
    store: Optional[aot_cache.ArtifactStore] = None,
    include: Optional[Sequence[str]] = None,
    num_slots: int = 4,
    prefill_chunk: int = 32,
    kv_block_size: int = 16,
    kv_num_blocks: int = 0,
    serve_quant: str = "off",
    spec_decode_k: int = 0,
    adam: Any = None,
    serialize: bool = False,
    verbose: bool = True,
    footprint_sink: Any = None,
) -> List[Dict[str, Any]]:
    """Warm every registered program of one (plan × model × live mesh):
    enumerate from the registry, compile each, attach the GTA015 analytic
    memory prediction to the train_step report for the cross-check."""
    import jax

    ctx = aot_registry.ProgramContext(
        cfg=cfg, hp=hp, global_bsz=global_bsz, seq_len=seq_len,
        num_slots=num_slots, prefill_chunk=prefill_chunk, adam=adam,
        kv_block_size=kv_block_size, kv_num_blocks=kv_num_blocks,
        serve_quant=serve_quant, spec_decode_k=spec_decode_k,
    )
    try:
        specs = aot_registry.enumerate_programs(ctx, include=include)
    except Exception as e:  # noqa: BLE001 — an unbuildable family must not abort
        if verbose:
            print(f"aot: WARNING — program enumeration failed: "
                  f"{type(e).__name__}: {str(e)[:300]}")
        return [{
            "program": "<enumerate>", "key": None, "cache_hit": False,
            "status": "failed", "lower_ms": None, "compile_ms": None,
            "memory": None, "error": f"{type(e).__name__}: {str(e)[:300]}",
        }]
    reports = warmup_programs(
        specs, store, plan=hp, model_cfg=cfg, serialize=serialize,
        verbose=verbose, footprint_sink=footprint_sink,
    )
    pred = (
        predicted_train_memory_mb(cfg, hp, jax.device_count(), global_bsz)
        if hp is not None
        else None
    )
    if pred is not None:
        for r in reports:
            if r["program"] == "train_step":
                r["predicted_train_mb"] = pred
                mem = r.get("memory")
                if mem and mem.get("total_mb"):
                    r["predicted_over_compiled"] = round(
                        pred / mem["total_mb"], 3
                    )
    return reports


def warmup_runtime(
    rt,
    global_bsz: int,
    seq_len: int,
    *,
    store: Optional[aot_cache.ArtifactStore] = None,
    plan: Any = None,
    model_cfg: Any = None,
    include: Optional[Sequence[str]] = None,
    verbose: bool = True,
) -> List[Dict[str, Any]]:
    """Trainer-startup warmup over an ALREADY-BUILT runtime (no second
    ``build_runtime``): compile the programs the run will dispatch so the
    loop's first step pays a persistent-cache deserialize, not an XLA
    compile, and the manifest tells the watchdog whether this start was
    warm.  ``include`` narrows to specific programs (the trainer passes the
    ones its own path will actually call); default = the whole family."""
    ctx = aot_registry.ProgramContext(
        cfg=rt.cfg, hp=rt.hp, global_bsz=global_bsz, seq_len=seq_len,
        mesh=rt.mesh, axes=rt.axes, runtime=rt,
    )
    specs = aot_registry.enumerate_programs(
        ctx, include=include if include is not None else ("trainer",)
    )
    return warmup_programs(
        specs, store,
        plan=plan if plan is not None else rt.hp,
        model_cfg=model_cfg if model_cfg is not None else rt.cfg,
        verbose=verbose,
    )


def summarize(reports: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    return {
        "programs": len(reports),
        "compiled": sum(1 for r in reports if r["status"] == "compiled"),
        "failed": sum(1 for r in reports if r["status"] == "failed"),
        # hits/misses partition the COMPILED programs (compiled = hits +
        # misses, programs = compiled + failed): a key known to the manifest
        # whose program fails THIS sweep is a failure, not a hit — nothing
        # got warm
        "hits": sum(
            1 for r in reports if r["status"] == "compiled" and r.get("cache_hit")
        ),
        "misses": sum(
            1 for r in reports if r["status"] == "compiled" and not r.get("cache_hit")
        ),
        "total_compile_ms": round(
            sum(r["compile_ms"] or 0.0 for r in reports), 1
        ),
    }


def write_report(path: str, reports: Sequence[Dict[str, Any]]) -> None:
    """JSONL: one record per program + one trailing summary record."""
    with open(path, "w") as f:
        for r in reports:
            f.write(json.dumps(r) + "\n")
        f.write(json.dumps({"summary": summarize(reports)}) + "\n")
