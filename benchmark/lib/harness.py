"""The harness: one cell, one process, the product's own train path (a serving
cell's runner is ``lib/serve.py``; ``run`` picks by the traffic file's ``kind``).

``run_cell`` is a function of the files under a benchmark root (the directory
that holds ``BENCHMARK.json``), so the tests drive it at a tiny size on the
CPU from a temporary copy; ``run.py`` alone demands the TPU.

A run is: corpus from the seed -> (searched plan) -> ONE ``train()`` call: its
first ``WARM_ITERS`` steps compile or load from the cache and warm up (all of
it set-up), the steps after them are the window -> the reference check on the
weights the call leaves.  The trainer has neither a time bound nor a step
callback, so the window is closed through a flag the product has: it polls
``--preempt_notice_file`` at the top of every step and drains cleanly when the
file exists.  A watcher thread reads the run's ``train_iter`` records and
writes that file once ``seconds`` have passed since the last warm-up step.
The window is whole steps on the host clock that the program's logger stamps
on every synced step's record.
"""

from __future__ import annotations

import gc
import json
import math
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

from benchmark.lib import corpus, import_file, reference, xplane

#: leading steps of the call that are set-up: compile or cache load, warm-up
WARM_ITERS = 3
#: steps of the traced run that the profiler records (steady, inside the window)
PROFILE_STEPS = (WARM_ITERS + 4, WARM_ITERS + 9)
#: the call's own bound, far beyond any window; the watcher closes it long before
MAX_ITERS = 100_000
#: program's bf16 forward loss against the float32 reference: one bf16 ulp of
#: the loss, the bound PR 23 fixed for two bf16 plans of one model
REFERENCE_RTOL = 2.0 ** -8
#: float32 elements of logits the reference may hold in one call (1 GiB)
REFERENCE_LOGITS = 1 << 28
#: how far the first loss may lie from what an untrained model gives (below);
#: over seeds it strays 0.09 at most on the chip (sd 0.055: the Zipf head of the
#: first batch weighs a few random logits heavily)
FIRST_LOSS_TOL = 0.3


def say(msg: str) -> None:
    print(msg, flush=True)


class BenchmarkError(RuntimeError):
    """The run cannot produce a result (wrong device, broken manifest)."""


# ---------------------------------------------------------------------------
# the data the harness is driven by
# ---------------------------------------------------------------------------


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str) -> Dict[str, Any]:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(root: str, name: str):
    """(cell, configuration, traffic) of the cell ``name``; each found by the
    name ``BENCHMARK.json`` gives it."""
    manifest = load_manifest(root)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise BenchmarkError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = _read_json(os.path.join(root, entry["file"]))
    traffic = _read_json(os.path.join(root, "benchmark", "traffic", cell["traffic"] + ".json"))
    return cell, config, traffic


def load_peaks(root: str) -> Dict[str, Any]:
    return _read_json(os.path.join(root, "benchmark", "lib", "peaks.json"))


def discover_metrics(root: str) -> List[Any]:
    """Every module of ``benchmark/metrics`` that declares a ``NAME``."""
    mdir = os.path.join(root, "benchmark", "metrics")
    mods = []
    for fn in sorted(os.listdir(mdir)):
        if not fn.endswith(".py") or fn.startswith("_"):
            continue
        mod = import_file(os.path.join(mdir, fn), f"_benchmark_metric_{fn[:-3]}")
        if hasattr(mod, "NAME"):
            mods.append(mod)
    return mods


# ---------------------------------------------------------------------------
# pieces of a run
# ---------------------------------------------------------------------------


def write_corpus(out_dir: str, seed: int, vocab_size: int, spec: Dict[str, Any]):
    """The seeded token stream, written in the program's shard format.
    Returns (``--data_path`` prefix, tokens)."""
    from galvatron_tpu.data.shards import write_sharded_dataset

    tokens = corpus.make_tokens(seed, int(spec["tokens"]), vocab_size,
                                zipf_a=spec["zipf_a"], follow_p=spec["follow_p"])
    prefix = os.path.join(out_dir, "corpus")
    write_sharded_dataset(prefix, corpus.documents(tokens, int(spec["doc_len"])), vocab_size)
    return prefix, tokens


def resolve_plan(root: str, out_dir: str, cell, config, traffic) -> Dict[str, Any]:
    """``plan`` of a traffic file -> train flags.  ``"single"``: the trainer's
    defaults; ``{"file": path}``: a plan kept with the benchmark;
    ``{"search": flags}``: the product's own search, run here, in process."""
    plan = traffic["plan"]
    if plan == "single":
        return {"flags": [], "doc": None, "search_s": None}
    if "file" in plan:
        path = os.path.join(root, plan["file"])
        return {"flags": ["--galvatron_config_path", path], "doc": _read_json(path),
                "search_s": None}
    from galvatron_tpu import cli

    path = os.path.join(out_dir, "searched_plan.json")
    argv = ["search", *config["program_flags"], "--num_devices", str(cell["chips"]),
            "--seq_length", str(traffic["seq_len"]), "--mixed_precision", "bf16",
            "--attn_impl", "auto", *plan["search"], "--output_config_path", path]
    say("search: python -m galvatron_tpu.cli " + " ".join(argv))
    t0 = time.time()
    rc = cli.main(argv)
    search_s = time.time() - t0
    if rc or not os.path.exists(path):
        raise BenchmarkError(f"search returned {rc} and left no plan at {path}")
    doc = _read_json(path)
    say("plan: " + json.dumps({k: doc.get(k) for k in (
        "pp_deg", "tp_sizes_enc", "dp_type_names", "sp_flags", "checkpoint", "chunks",
        "pipeline_type", "vocab_tp", "search_cost_ms", "memory_mb")}))
    return {"flags": ["--galvatron_config_path", path], "doc": doc, "search_s": search_s}


def train_argv(config, traffic, *, seed: int, data_prefix: str, iters: int,
               metrics_path: str, plan_flags: Sequence[str],
               extra: Sequence[str] = ()) -> List[str]:
    return [
        *config["program_flags"],
        "--seq_length", str(traffic["seq_len"]),
        "--global_train_batch_size", str(traffic["global_batch"]),
        "--mixed_precision", "bf16", "--attn_impl", "auto", "--check_loss", "1",
        "--seed", str(seed), "--data_path", data_prefix, "--prefetch_depth", "2",
        "--metrics_path", metrics_path, "--train_iters", str(iters),
        *plan_flags, *traffic.get("train_flags", []), *extra,
    ]


def run_train(argv: Sequence[str]) -> Dict[str, Any]:
    """What ``python -m galvatron_tpu.cli train <argv>`` runs."""
    from galvatron_tpu.core.arguments import initialize_galvatron
    from galvatron_tpu.core.trainer import train

    say("train: python -m galvatron_tpu.cli train " + " ".join(argv))
    return train(initialize_galvatron("train", list(argv)), verbose=False)


def read_train_iters(path: str) -> List[Dict[str, Any]]:
    """The ``train_iter`` records logged so far (the window's closer reads the
    file while the trainer appends: a last line still being written is left)."""
    recs = []
    if not os.path.exists(path):
        return recs
    with open(path) as f:
        for line in f:
            if not line.endswith("\n"):
                break
            rec = json.loads(line)
            if rec.get("event") == "train_iter":
                recs.append(rec)
    return recs


class WindowCloser(threading.Thread):
    """Closes the measured window from outside the trainer: once the warm-up
    steps are logged, waits ``seconds`` from the last of them, then until at
    least ``min_steps`` more are logged, and writes the notice file that makes
    the trainer stop at its next step boundary.  Sleeps between a few reads
    of a small file; ``cancel`` ends it when the call ends by itself.

    During the last warm-up step it also collects the garbage that tracing and
    lowering left and freezes what survives, so that no full collection of
    that heap (40-130 ms, seen as one slow step in one run of four) falls
    into the window; ``cancel`` thaws it."""

    def __init__(self, metrics_path: str, notice_path: str, seconds: float, min_steps: int):
        super().__init__(name="benchmark-window-closer", daemon=True)
        self.metrics_path, self.notice_path = metrics_path, notice_path
        self.seconds, self.min_steps = seconds, min_steps
        self._cancelled = threading.Event()

    def cancel(self) -> None:
        self._cancelled.set()
        self.join()
        gc.unfreeze()

    def run(self) -> None:
        deadline, frozen = None, False
        while not self._cancelled.is_set():
            recs = read_train_iters(self.metrics_path)
            if not frozen and len(recs) >= WARM_ITERS - 1:
                gc.collect()
                gc.freeze()
                frozen = True
            if deadline is None and len(recs) >= WARM_ITERS:
                deadline = recs[WARM_ITERS - 1]["ts"] + self.seconds
            if deadline is not None:
                wait = deadline - time.time()
                if wait <= 0 and len(recs) >= WARM_ITERS + self.min_steps:
                    with open(self.notice_path, "w") as f:
                        f.write("benchmark window closed\n")
                    return
                self._cancelled.wait(wait if wait > 0 else 0.05)
            else:
                self._cancelled.wait(0.2)


def read_spans(path: str, epoch_wall: float) -> List[Dict[str, Any]]:
    """The trainer's exported span trace as ``{name, start, end, step}`` with
    times in seconds on the unix clock."""
    spans = []
    for ev in _read_json(path)["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        start = epoch_wall + ev["ts"] / 1e6
        spans.append({"name": ev["name"], "start": start, "end": start + ev["dur"] / 1e6,
                      "step": ev.get("args", {}).get("step")})
    return spans


def check_widths(rt_cfg, config) -> None:
    """The program ran the sizes the configuration file states."""
    want = {
        "hidden_size": config["hidden_size"], "num_heads": config["num_attention_heads"],
        "ffn": config.get("intermediate_size") or config.get("ffn_dim"),
        "num_layers": config["num_hidden_layers"], "vocab_size": config["vocab_size"],
        "tie_word_embeddings": config["tie_word_embeddings"],
    }
    got = {k: getattr(rt_cfg, k) for k in want}
    if got != want:
        raise BenchmarkError(f"the program ran {got}, the configuration file says {want}")


def expected_first_loss(config) -> float:
    """The loss of an untrained model: logits that are independent of the
    target and normal with the variance the configuration file states for the
    program's initialization give ``ln(vocab) + variance / 2``, not
    ``ln(vocab)``."""
    return math.log(int(config["vocab_size"])) + float(config["initial_logit_variance"]) / 2


def reference_check(out: Dict[str, Any], rows, config, arch) -> Dict[str, float]:
    """The program's forward loss on ``rows`` through its own runtime
    (``eval_loss``: its kernels, its sharding, bf16) against the plain float32
    reference on the same weights."""
    rt, state = out["runtime"], out["state"]
    got = float(rt.eval_loss(state, rt.shard_batch(rows)))
    params = state["params"]
    if rt.flatten_params is not None:
        params = rt.flatten_params(params)
    # the optimizer's moments go before the reference's float32 activations come
    out.clear()
    del state
    gc.collect()
    per_call = max(1, REFERENCE_LOGITS // ((rows.shape[1] - 1) * int(config["vocab_size"])))
    want = reference.lm_loss(arch, params, rows, config, rows_per_call=per_call)
    return {"program": got, "reference": want, "rel": abs(got - want) / abs(want)}


def memory_stats(devices) -> List[Dict[str, int]]:
    return [dict(d.memory_stats() or {}) for d in devices]


def peak_bytes(stats: Sequence[Dict[str, int]]) -> int:
    """Fullest device: live arrays at their peak plus what the runtime reserved
    for the step program's temporaries, which this runtime counts apart."""
    return max((st.get("peak_bytes_in_use", 0) + st.get("peak_bytes_reserved", 0)
                for st in stats), default=0)


def collect_per_layer(root: str, name: str, ctx: Dict[str, Any], result: Dict[str, Any]) -> None:
    """Every per-layer metric ``BENCHMARK.json`` declares for cell ``name``,
    each read by its own module under ``benchmark/metrics``; a reader that
    finds nothing to read returns None and its metric is left out."""
    manifest = load_manifest(root)
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    declared = {m["name"]: m for m in manifest["per_layer"]}
    for mod in discover_metrics(root):
        entry, moved = declared.get(mod.NAME), end_to_end.get(mod.MOVES)
        # a metric without a list of cells is read in every cell that reports
        # the end-to-end metric it moves
        if entry is None or moved is None or not all(
                name in e.get("workloads", [name]) for e in (entry, moved)):
            continue
        value = mod.compute(ctx)
        if value is not None:
            result["metrics"][mod.NAME] = {"value": float(value), "unit": mod.UNIT}


def device_breakdown(ctx: Dict[str, Any], result: Dict[str, Any]) -> None:
    """``busy_s`` / ``window_s`` of the traced window (mean over the devices
    used) and the breakdown: the device operations that took most time, and
    the longest idle gaps by the host span open at the time."""
    import numpy as np

    used = [ops for ops in ((ctx["trace"] or {}).get("devices") or {}).values() if ops]
    if not used:
        return
    device = result["device"]
    device["busy_s"] = float(np.mean([xplane.busy_ns(o) for o in used])) / 1e9
    device["window_s"] = float(np.mean([b - a for a, b in map(xplane.window_of, used)])) / 1e9
    ops0 = xplane.first_device(ctx["trace"])
    t0 = ctx["trace"]["start_unix_ns"] or 0
    host = [((s["start"] * 1e9 - t0), (s["end"] * 1e9 - t0), s["name"])
            for s in ctx["spans"] if s["name"] != "step"]
    result["breakdown"] = {
        "device_ops": [[k, v] for k, v in xplane.top_ops(ops0)],
        "idle_gaps": [[k, v] for k, v in xplane.attribute_gaps(ops0, host)],
    }


# ---------------------------------------------------------------------------
# one run of one cell
# ---------------------------------------------------------------------------


def run_cell(root: str, name: str, *, seed: int, seconds: float, trace: bool,
             out_dir: str, t_start: float, peaks_row: Optional[Dict[str, Any]] = None,
             min_steps: int = 20) -> Dict[str, Any]:
    """Run cell ``name`` once and return the result object (``run.py`` prints
    it as the last line).  ``peaks_row`` is the device's row of the peaks
    table; None (the CPU tests) leaves out every metric that needs a peak.
    ``min_steps`` keeps the window long enough for the loss at step 20 to
    exist and for the profiled steps to be steady ones."""
    import jax

    from galvatron_tpu.aot.cache import enable_persistent_cache, resolve_compile_cache_dir
    from galvatron_tpu.obs import tracing as program_tracing

    cell, config, traffic = load_cell(root, name)
    arch = reference.load(root, config["model_type"])
    chips = int(cell["chips"])
    batch, seq = int(traffic["global_batch"]), int(traffic["seq_len"])
    say(f"cell {name}: config {cell['config']} traffic {cell['traffic']} chips {chips} "
        f"global batch {batch} x seq {seq} seed {seed} seconds {seconds} trace {int(trace)}")
    say(f"compile cache: {enable_persistent_cache(resolve_compile_cache_dir())}")
    os.makedirs(out_dir, exist_ok=True)

    def mark(what: str) -> None:
        say(f"set-up: {what} at t+{time.time() - t_start:.1f} s")

    mark("imports done and device reached")
    data_prefix, tokens = write_corpus(out_dir, seed, int(config["vocab_size"]), traffic["corpus"])
    plan = resolve_plan(root, out_dir, cell, config, traffic)
    mark("corpus written" + (" and plan searched" if plan["search_s"] else ""))
    # -- one call: warm-up steps (set-up), then whole steps until the window closes --
    metrics_path = os.path.join(out_dir, "train.jsonl")
    notice_path = os.path.join(out_dir, "window_closed")
    extra: List[str] = ["--preempt_notice_file", notice_path]
    if trace:
        extra += ["--trace_spans", os.path.join(out_dir, "spans.json"),
                  "--trace_dir", os.path.join(out_dir, "profile"),
                  "--profile_steps", "%d:%d" % PROFILE_STEPS, "--trace_ring", "65536"]
    closer = WindowCloser(metrics_path, notice_path, seconds, min_steps)
    closer.start()
    t_call = time.time()
    try:
        out = run_train(train_argv(config, traffic, seed=seed, data_prefix=data_prefix,
                                   iters=MAX_ITERS, metrics_path=metrics_path,
                                   plan_flags=plan["flags"], extra=extra))
    finally:
        closer.cancel()
    call_s = time.time() - t_call
    rt = out["runtime"]
    mesh_devices = list(rt.mesh.devices.flat)
    stats = memory_stats(mesh_devices)
    say(f"train call: {call_s:.1f} s, attn_impl {rt.cfg.attn_impl}, stopped by {out['signaled']!r}")
    check_widths(rt.cfg, config)
    if out["signaled"] != "notice":
        raise BenchmarkError(f"the call ended by {out['signaled']!r}, not by the window's close")

    # -- the program against the plain reference, on the weights the call leaves --
    ref = reference_check(out, corpus.windows(tokens, seq, batch), config, arch)
    ref_ok = ref["rel"] <= REFERENCE_RTOL
    say(f"reference: program forward loss {ref['program']:.6f}, float32 reference "
        f"{ref['reference']:.6f}, relative difference {ref['rel']:.3e} "
        f"(bound {REFERENCE_RTOL:.3e}) -> {'ok' if ref_ok else 'FAILED'}")
    del out, rt
    gc.collect()

    recs = read_train_iters(metrics_path)
    if len(recs) <= WARM_ITERS:
        raise BenchmarkError(f"the call logged {len(recs)} steps, no window")
    first, window = recs[WARM_ITERS - 1], recs[WARM_ITERS:]
    kept = len(window)
    losses = [r["loss"] for r in recs]
    finite = [isinstance(x, float) and math.isfinite(x) for x in losses]
    failed = sum(1 for ok in finite[WARM_ITERS:] if not ok)
    window_s = window[-1]["ts"] - first["ts"]
    if not 0 < window_s <= call_s:
        raise BenchmarkError(f"window of {window_s:.3f} s inside a call of {call_s:.3f} s")
    tokens_per_s_per_chip = len(window) * batch * seq / window_s / chips
    setup_s = first["ts"] - t_start

    untrained = expected_first_loss(config)
    margin = float(traffic["loss_drop_by_step_20"])
    checks = {
        "reference": ref_ok,
        "finite": all(finite),
        "first_loss": finite[0] and abs(losses[0] - untrained) <= FIRST_LOSS_TOL,
        "learns": len(losses) > 20 and finite[20] and losses[20] < losses[0] - margin,
    }
    say(f"losses: first {losses[0]}, step 20 {losses[20] if len(losses) > 20 else 'n/a'}, "
        f"last {losses[-1]} (an untrained model gives {untrained:.3f}, the first may differ by "
        f"{FIRST_LOSS_TOL}; required drop by step 20: {margin})")
    say(f"checks: {json.dumps(checks)}")
    say(f"window: {len(window)} steps after {WARM_ITERS} warm-up steps, {window_s:.3f} s of a "
        f"{call_s:.3f} s call ({len(window) * batch * seq} tokens); set-up {setup_s:.1f} s")
    say("memory: " + "; ".join(
        f"dev{i} peak_bytes_in_use {st.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB + "
        f"peak_bytes_reserved {st.get('peak_bytes_reserved', 0) / 2**30:.2f} GiB"
        for i, st in enumerate(stats)))

    d0 = mesh_devices[0]
    device: Dict[str, Any] = {"platform": d0.platform, "kind": d0.device_kind,
                              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes(stats)}
    result: Dict[str, Any] = {"correct": all(checks.values()), "attempted": kept,
                              "failed": failed, "metrics": {}, "device": device}
    if not trace:
        result["metrics"] = {
            "tokens_per_s_per_chip": {"value": tokens_per_s_per_chip, "unit": "tokens/s/chip"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        return result

    # -- traced run: per-layer metrics from spans, records and the trace -----
    spans = read_spans(os.path.join(out_dir, "spans.json"), program_tracing.tracer.epoch_wall)
    in_window = {r["step"] for r in window}
    trace_path = xplane.find_trace(os.path.join(out_dir, "profile"))
    ctx = {
        "cell": cell, "config": config, "traffic": traffic, "chips": chips, "arch": arch,
        "records": window, "tokens_per_s_per_chip": tokens_per_s_per_chip,
        "spans": [sp for sp in spans if sp["step"] in in_window],
        "step_s": [sp["end"] - sp["start"] for sp in spans
                   if sp["name"] == "step" and sp["step"] in in_window],
        "setup_spans": [sp for sp in spans if sp["step"] not in in_window],
        "trace": xplane.load(trace_path) if trace_path else None,
        "n_profiled": PROFILE_STEPS[1] - PROFILE_STEPS[0],
        "memory_peak_bytes": device["memory_peak_bytes"],
        "plan": plan["doc"],
        "search_s": plan["search_s"], "peaks": peaks_row, "say": say,
    }
    collect_per_layer(root, name, ctx, result)
    device_breakdown(ctx, result)
    return result


def run(root: str, name: str, **kw) -> Dict[str, Any]:
    """One run of cell ``name`` by the runner its traffic file's ``kind`` names:
    ``"serve"`` is ``lib/serve.run_serve_cell``, absent is ``run_cell``."""
    _, _, traffic = load_cell(root, name)
    if traffic.get("kind", "train") == "serve":
        from benchmark.lib import serve

        return serve.run_serve_cell(root, name, **kw)
    return run_cell(root, name, **kw)
