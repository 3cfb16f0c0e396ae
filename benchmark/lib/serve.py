"""The serve runner: one cell, one process, the product's own serving engine.

``run_serve_cell`` is what ``harness.run`` picks for a cell whose traffic file
says ``"kind": "serve"``.  A run is: weights from the seed (one jitted call, in
the dtypes the program serves) -> ``serving.Engine`` built from the flags
``cli serve`` takes -> warm-up requests through both programs (all of it
set-up) -> an open loop from ``lib/traffic.py`` -> the window -> what the
engine served against the plain float32 reference, every kept greedy token
against its row's best and every kept sampled token against the distribution
its request stated (``lib/reference.py``: nothing of the program).

No HTTP, no children, no CPU fallback (``run.py`` demands the TPU; the tests
drive this file tiny on the CPU).  The engine is the program's, unchanged, and
so are its taps (PR 39): a token's time is ``Request.token_times`` (this
process's ``time.time()``, read after the draw), and for a seeded share of the
requests ``submit_request(capture_logits=...)`` copies the float32 logits row
each token is drawn from into room the benchmark made during set-up.

The window's clock is ``time.time()`` in this process.  Requests are timed from
when they were DUE, not from when the generator got to them; how late the
generator ran is printed.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from benchmark.lib import harness, reference, traffic as traffic_lib, xplane
from benchmark.lib.harness import BenchmarkError, say
from benchmark.lib.stats import percentile

#: the generator's p99 lateness above which a run says that it was starved
LATE_WARN_MS = 5.0
#: decode iterations the traced run's profiler window covers
PROFILE_ITERS = 50
#: mass past ``top_p`` a sampled token may lie before it counts as outside the
#: nucleus: a float32 cumulative sum over 50,272 terms errs by ~1e-4; at the
#: cell's near-flat rows 1e-3 admits ~300 ids past the cut, and a sampler with
#: no nucleus still lands outside with probability 4.9% a token (PERF.md section 2)
NUCLEUS_SLACK = 1e-3
#: |z| of the sampled tokens' summed log-probability against its exact
#: expectation (``reference.sampled_tokens_check``): N(0, 1) for a right sampler
#: (false alarm 2e-9 a run); temperature 1.0 for 0.8 reads ~9 over 1,500 tokens
LOGPROB_Z_MAX = 6.0


class RowStore:
    """Room for the logits rows ``correct`` compares: one array, made and
    touched during set-up.  (Rows kept as arrays of their own, 200 KB each,
    slowed the engine's iterations from 97 to 138 ms within seconds on the
    chip's host: PERF.md section 6.)  ``reserve`` is the generator thread's,
    once a request; the engine's thread writes the lines reserved."""

    def __init__(self, rows: int, vocab: int):
        self.buf = np.full((rows, vocab), 0.0, np.float32)  # written, so its pages exist
        self.used = 0

    def reserve(self, n: int) -> Optional[int]:
        if self.used + n > len(self.buf):
            return None
        start, self.used = self.used, self.used + n
        return start

    def take(self, n: int) -> Optional[np.ndarray]:
        """``n`` lines of the room as the engine's logits tap wants them (the
        caller's writable float32 array), or None where there is none left: such
        a request is timed only."""
        start = self.reserve(n)
        return None if start is None else self.buf[start:start + n]


def stamps_of(rec: Dict[str, Any]) -> List[float]:
    """When each token of a submitted request was drawn, on this process's clock."""
    return rec["req"].token_times if rec["req"] is not None else []


def sampled_check(kept: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Every token of the kept SAMPLED requests against the row the tap kept for
    it and the sampling the request stated (``reference.sampled_tokens_check``)."""
    draws = ((r["rows"][k], tok, r["temperature"], 0, r["top_p"])
             for r in kept if not r["greedy"] for k, tok in enumerate(r["generated"]))
    return reference.sampled_tokens_check(draws, NUCLEUS_SLACK)


def not_best(rows: np.ndarray, tokens: Sequence[int]) -> int:
    """How many of ``tokens`` do not have the largest logit of the row they
    were drawn from (tokens whose logits tie share the best)."""
    rows = rows[:len(tokens)]
    return int((rows[np.arange(len(tokens)), list(tokens)] < rows.max(-1)).sum())


# What the runner used until PR 41, in place of the program's taps: a list that
# stamps every ``append`` and copies ``Engine._last_logits[req.slot]``.  No run
# uses it any more.  It stays because ``tests/test_serving_obs.py``, which lies
# outside the benchmark's directories, holds the program's taps to it bit for
# bit; the PR that may edit that test deletes both (PERF.md section 7).


class StampedTokens(list):
    """A request's generated tokens; notes this process's clock at every
    ``append``, which is where the engine hands a token over.  With ``row_of``
    (a function that returns the logits row the engine has just drawn the
    token from) a greedy request's token is held to that row at once
    (``not_best`` counts the tokens whose logit is not the row's largest), and
    with ``store`` the row is copied into the lines reserved there
    (``lines[k]`` is the k-th token's, None where there was no row)."""

    def __init__(self, row_of=None, store: Optional[RowStore] = None, start: Optional[int] = None,
                 greedy: bool = False):
        super().__init__()
        self.stamps: List[float] = []
        self.lines: List[Optional[int]] = []
        self.checked = self.not_best = 0
        self._row_of, self._store, self._start, self._greedy = row_of, store, start, greedy

    def append(self, tok) -> None:
        self.stamps.append(time.time())
        row = self._row_of() if self._row_of is not None else None
        line = None
        if row is not None:
            if self._greedy:
                self.checked += 1
                self.not_best += int(row[tok] < row.max())
            if self._start is not None:
                line = self._start + len(self)
                self._store.buf[line] = row
        self.lines.append(line)
        super().append(tok)


def stamp(engine, req, *, greedy: bool = False, store: Optional[RowStore] = None) -> StampedTokens:
    """Hand ``req`` a ``StampedTokens`` in place of its list (see the note
    above: kept for one test outside the benchmark, used by no run)."""
    def row():
        return None if req.slot is None else engine._last_logits[req.slot]

    start = store.reserve(req.max_new_tokens) if store is not None else None
    stamped = StampedTokens(row if greedy or start is not None else None, store, start, greedy)
    early, req.generated = req.generated, stamped
    for tok in early:
        stamped.stamps.append(time.time())
        stamped.lines.append(None)
        list.append(stamped, tok)
    return stamped


# ---------------------------------------------------------------------------
# weights and engine
# ---------------------------------------------------------------------------


def seed_key(seed: int, tag: int):
    """A PRNG key from a seed of any size (the driver's exceed 2**31)."""
    import jax
    import jax.numpy as jnp

    data = np.random.SeedSequence([int(seed), int(tag)]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(data, jnp.uint32), impl="threefry2x32")


def make_weights(cfg, seed: int):
    """The program's parameter tree (its structure, shapes and dtypes, from
    ``eval_shape`` of its initialiser), filled by the benchmark in ONE jitted
    call on the device: normal with deviation 0.02 (the published init_std),
    norm scales around 1.  Biases are not zero, so a dropped bias shows."""
    import jax
    import jax.numpy as jnp

    from galvatron_tpu.models import modeling

    abstract = jax.eval_shape(lambda k: modeling.init_model_params(k, cfg), jax.random.key(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def fill(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = str(getattr(path[-1], "key", ""))
            x = 0.02 * jax.random.normal(jax.random.fold_in(key, i), leaf.shape, jnp.float32)
            if name == "scale" or name.endswith("norm"):
                x = 1.0 + x
            out.append(x.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(fill)(seed_key(seed, 0xBEEF))


def build_engine(config, spec, seed: int, params=None, overrides: Sequence[str] = ()):
    """``serving.Engine`` as ``cli serve`` builds it, from the configuration's
    ``program_flags`` and the traffic file's ``serve_flags``.  Differs from
    ``cli serve`` in two stated ways: ``eos_id`` is -1 (every request runs to
    the length it was drawn with) and the weights come from ``make_weights``.
    Returns (engine, cfg, params)."""
    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args
    from galvatron_tpu.serving import Engine

    argv = [*config["program_flags"], *spec["serve_flags"], *overrides, "--seed", str(int(seed))]
    say("engine: python -m galvatron_tpu.cli serve " + " ".join(argv) + "  (eos_id -1)")
    ns = initialize_galvatron("serve", argv)
    cfg = model_config_from_args(ns)
    if ns.attn_impl != "auto":
        cfg = cfg.replace(attn_impl=ns.attn_impl)
    if params is None:
        params = make_weights(cfg, seed)
    engine = Engine(
        params, cfg, num_slots=ns.num_slots, prefill_chunk=ns.prefill_chunk,
        max_queue=ns.max_queue,
        request_ttl_s=ns.request_ttl_s if ns.request_ttl_s > 0 else None,
        eos_id=-1, pad_id=0, seed=ns.seed, deadline_policy=ns.deadline_policy,
        max_engine_restarts=ns.max_engine_restarts, drain_timeout_s=ns.drain_timeout_s,
        flight_dir=None, kv_block_size=ns.kv_block_size, kv_num_blocks=ns.kv_num_blocks,
        prefix_cache=ns.prefix_cache == "on", serve_quant=ns.serve_quant,
        quant_drift_max=ns.quant_drift_max, spec_decode_k=ns.spec_decode_k,
        spec_drafter=ns.spec_drafter,
    )
    return engine, cfg, params


# ---------------------------------------------------------------------------
# the open loop
# ---------------------------------------------------------------------------


class Generator(threading.Thread):
    """Submits request ``i`` at its due time whether or not earlier ones have
    finished, and notes how late each submission was.  One thread, asleep
    between arrivals."""

    def __init__(self, engine, requests: List[Dict[str, Any]], t0: float,
                 store: Optional[RowStore] = None):
        super().__init__(name="benchmark-load-generator", daemon=True)
        self.engine, self.requests, self.t0, self.store = engine, requests, t0, store
        self.records: List[Dict[str, Any]] = []
        self._stop_event = threading.Event()

    def stop(self) -> None:
        self._stop_event.set()
        self.join()

    def run(self) -> None:
        for r in self.requests:
            due = self.t0 + r["due_s"]
            wait = due - time.time()
            if wait > 0 and self._stop_event.wait(wait):
                return
            if self._stop_event.is_set():
                return
            rows = (self.store.take(r["max_new_tokens"])
                    if self.store is not None and r["capture"] else None)
            rec = {"i": r["i"], "due": due, "submitted": time.time(), "greedy": r["greedy"],
                   "temperature": r["temperature"], "top_p": r["top_p"],
                   "prompt": r["tokens"], "max_new_tokens": r["max_new_tokens"], "req": None,
                   "rows": rows, "error": None}
            try:
                rec["req"] = self.engine.submit_request(
                    r["tokens"], r["max_new_tokens"], temperature=r["temperature"],
                    top_p=r["top_p"], capture_logits=rows)
            except Exception as e:  # noqa: BLE001 - a refused request is a failed one, counted
                rec["error"] = type(e).__name__
            self.records.append(rec)


def offer(engine, requests: List[Dict[str, Any]], win: Dict[str, Any], seconds: float, *,
          tracer=None, trace_dir: Optional[str] = None, store: Optional[RowStore] = None
          ) -> Dict[str, Any]:
    """Offer ``requests`` to ``engine`` in an open loop and hold the window:
    it opens ``settle_s`` after the event ``win["opens"]`` names (``traffic_start``,
    or ``all_slots_used``: every slot has been occupied at once) and lasts
    ``seconds`` (the queue's depth is read at both ends: above the knee it
    grows); after it, first tokens still owed to requests that were due
    inside it are waited for, ``first_token_grace_s`` at most.  With a
    ``tracer`` a profiler window covers ``PROFILE_ITERS`` decode iterations
    early in the window."""
    num_slots = engine.slots.num_slots
    t0 = time.time()
    gen = Generator(engine, requests, t0, store)
    gen.start()
    profiled = 0
    try:
        if win["opens"] == "all_slots_used":
            limit = t0 + 30.0
            while engine.slots.active_count < num_slots:
                if time.time() > limit:
                    raise BenchmarkError(f"after 30 s of traffic only {engine.slots.active_count} "
                                         f"of {num_slots} slots are in use")
                time.sleep(0.02)
            t_event = time.time()
        elif win["opens"] == "traffic_start":
            t_event = t0
        else:
            raise BenchmarkError(f"window.opens {win['opens']!r}: not a rule the runner knows")
        t_open = t_event + float(win["settle_s"])
        t_close = t_open + seconds
        say(f"window: opens {t_open - t0:.2f} s into the traffic ({win['opens']} + "
            f"{win['settle_s']} s), lasts {seconds} s")
        time.sleep(max(0.0, t_open - time.time()))
        depth_open = engine.scheduler.depth
        if tracer is not None:
            time.sleep(max(0.0, t_open + min(2.0, seconds / 4) - time.time()))
            profiled = profile_iterations(engine, tracer, trace_dir, PROFILE_ITERS,
                                          timeout_s=max(1.0, t_close - time.time()))
        time.sleep(max(0.0, t_close - time.time()))
        depth_close = engine.scheduler.depth
        grace = time.time() + float(win.get("first_token_grace_s", 0.0))
        while time.time() < grace and any(
                t_open <= r["due"] < t_close and r["req"] is not None and not stamps_of(r)
                and not r["req"].future.done() for r in list(gen.records)):
            time.sleep(0.05)
    finally:
        gen.stop()
    return {"t0": t0, "t_open": t_open, "t_close": t_close, "gen": gen, "profiled": profiled,
            "queue_depth": (depth_open, depth_close)}


def cancel_open(records: List[Dict[str, Any]]) -> None:
    """Ask the engine to drop what is still queued or decoding; however such a
    request then ends (cancelled, shed by the drain), it was open, not failed."""
    for rec in records:
        if rec["req"] is not None and not rec["req"].future.done():
            rec["dropped"] = True
            rec["req"].cancel("benchmark window closed")


def release_cache(engine, timeout_s: float = 20.0) -> None:
    """Once the engine stands idle (what was open is cancelled, nothing is
    queued), let go of its slot cache, so that the engine's own shutdown can make
    the fresh one it wants: ``KVSlots.reset`` builds a new cache BEFORE it drops
    the old, and at 16 slots x 2048 two caches (2 x 6 GiB beside 4.9 GiB of
    weights) do not fit a 16 GB chip: ``drain`` and ``close`` then raise
    RESOURCE_EXHAUSTED (my chip run, PR 41, call 1; PERF.md section 7: the
    program's to mend, a restart after a crash takes the same path).  The window
    is closed and the memory read by then; nothing timed or compared is touched."""
    deadline = time.time() + timeout_s
    while (engine.slots.active_count or not engine.scheduler.empty()) and time.time() < deadline:
        time.sleep(0.01)
    time.sleep(0.2)  # the iteration that freed the last slot ends, its cache handed back
    engine.slots.cache = None


def outcome(rec: Dict[str, Any]) -> str:
    """``completed`` (the drawn number of tokens, all served), ``failed``
    (refused, raised, expired or short) or ``open`` (still running when the
    run ended, or cancelled by the runner then)."""
    if rec["error"]:
        return "failed"
    req, fut = rec["req"], rec["req"].future
    if rec.get("dropped") or not fut.done() or fut.cancelled():
        return "open"
    if fut.exception() is not None:
        return "failed"
    want = len(rec["prompt"]) + rec["max_new_tokens"]
    ok = (len(fut.result()) == want and len(req.generated) == rec["max_new_tokens"]
          and req.finish_reason == "length")
    return "completed" if ok else "failed"


def window_numbers(records: List[Dict[str, Any]], t_open: float, t_close: float
                   ) -> Dict[str, Any]:
    """What the window holds, from the times the engine notes a token
    (``Request.token_times``): tokens drawn inside it; for the requests DUE
    inside it, the time from due to first token; every gap between consecutive tokens of a request whose later
    token fell inside it.  Nothing outside the window counts."""
    tokens, ttft, itl, due_in = 0, [], [], []
    for rec in records:
        stamps = stamps_of(rec)
        tokens += sum(1 for s in stamps if t_open <= s < t_close)
        itl += [b - a for a, b in zip(stamps, stamps[1:]) if t_open <= b < t_close]
        if t_open <= rec["due"] < t_close:
            due_in.append(rec)
            if stamps:
                ttft.append(stamps[0] - rec["due"])
    return {"tokens": tokens, "ttft_s": ttft, "itl_s": itl, "due_in": due_in}


def window_work(records: List[Dict[str, Any]], t_open: float, t_close: float, chunk: int
                ) -> Dict[str, int]:
    """What the window's forwards had to work on, rebuilt from the requests' own
    records (prompt length, ``admitted_at``, ``token_times``), not from the
    cache's capacity: the counts ``lib/flops.py`` turns into least bytes and
    FLOPs.  A request admitted inside the window is prefilled there, ``chunk``
    tokens a call, each call attending to the positions up to its end.  Token k
    of a request, drawn inside the window and not its last, is fed through the
    decode step that follows the draw, at position ``prompt + k``, attending to
    the ``prompt + k + 1`` positions then live in its slot."""
    out = {"decode_tokens": 0, "decode_positions": 0, "prefills": 0, "prefill_chunks": 0,
           "prefill_tokens": 0, "prefill_positions": 0, "prefill_pairs": 0}
    for rec in records:
        req = rec["req"]
        if req is None or req.admitted_at is None:
            continue
        p = len(rec["prompt"])
        if t_open <= req.admitted_at < t_close:
            ends = [min(p, e) for e in range(chunk, p + chunk, chunk)]
            out["prefills"] += 1
            out["prefill_chunks"] += len(ends)
            out["prefill_tokens"] += p
            out["prefill_positions"] += sum(ends)
            out["prefill_pairs"] += p * (p + 1) // 2
        for k, t in enumerate(req.token_times):
            if t_open <= t < t_close and k < rec["max_new_tokens"] - 1:
                out["decode_tokens"] += 1
                out["decode_positions"] += p + k + 1
    return out


# ---------------------------------------------------------------------------
# correct: what the engine served against the plain reference
# ---------------------------------------------------------------------------


def compare_rows(arch, params, config, rows: List[Dict[str, Any]], pad_to: int, most: int
                 ) -> Dict[str, Any]:
    """Each checked request's logits rows, as the engine had them on the host
    when it drew the served tokens (prefill's last position, then every decode
    step), against the float32 reference run ONCE over prompt + served tokens
    at ``highest``.  One request a call, padded to one length and ``most``
    rows, so one program and ~0.4 GiB of reference logits at the published
    sizes.  Both sides have each row's mean taken off (a softmax does not see
    it).  Returns the sums over all compared rows (squared error, squared
    reference, the Kullback-Leibler divergence of the engine's softmax from
    the reference's) and the worst row's relative error."""
    import jax
    import jax.numpy as jnp

    w = arch.published_weights(params, config)

    def one(w_, tokens, served, first, valid):
        with jax.default_matmul_precision("highest"):
            w_ = jax.tree.map(lambda a: a.astype(reference.F32), w_)
            lg = arch.logits(w_, tokens[None, :-1], config)[0]
        at = jnp.minimum(first + jnp.arange(most), pad_to - 1)
        ref = lg[at]
        ref_c = ref - ref.mean(-1, keepdims=True)
        got_c = served - served.mean(-1, keepdims=True)
        e2 = jnp.sum((got_c - ref_c) ** 2, -1)
        r2 = jnp.sum(ref_c ** 2, -1)
        logp = jax.nn.log_softmax(ref_c)
        kl = jnp.sum(jnp.exp(logp) * (logp - jax.nn.log_softmax(got_c)), -1)
        zero = jnp.zeros_like(e2)
        return tuple(jnp.where(valid, x, zero) for x in (e2, r2, kl))

    step = jax.jit(one)
    vocab = int(config["vocab_size"])
    out = {"rows": 0, "e2": 0.0, "r2": 0.0, "kl": 0.0, "worst_row": 0.0}
    for row in rows:
        seq = list(row["prompt"]) + list(row["generated"])
        if len(seq) > pad_to or len(row["generated"]) > most:
            raise BenchmarkError(f"a served sequence of {len(seq)} tokens ({len(row['generated'])} "
                                 f"served) exceeds {pad_to} ({most})")
        buf = np.zeros((pad_to + 1,), np.int32)
        buf[:len(seq)] = seq
        served = np.zeros((most, vocab), np.float32)
        served[:len(row["rows"])] = row["rows"]
        valid = np.arange(most) < len(row["rows"])
        e2, r2, kl = (np.asarray(x, np.float64) for x in step(
            w, jnp.asarray(buf), jnp.asarray(served), np.int32(len(row["prompt"]) - 1),
            jnp.asarray(valid)))
        out["rows"] += int(valid.sum())
        out["e2"] += float(e2.sum())
        out["r2"] += float(r2.sum())
        out["kl"] += float(kl.sum())
        if valid.any():
            out["worst_row"] = max(out["worst_row"], float(np.sqrt(e2[valid] / r2[valid]).max()))
    return out


def pick_checked(finished: List[Dict[str, Any]], seed: int, n: int) -> List[Dict[str, Any]]:
    """The longest finished request whose rows were kept and a seeded sample
    of the others."""
    if not finished:
        return []
    by_len = sorted(finished, key=lambda r: -(len(r["prompt"]) + len(r["generated"])))
    rest = by_len[1:]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xC0DE]))
    take = rng.permutation(len(rest))[:max(0, n - 1)]
    return [by_len[0]] + [rest[i] for i in sorted(take)]


# ---------------------------------------------------------------------------
# spans and the profiler window (traced run)
# ---------------------------------------------------------------------------


def serving_peak_bytes(stats: Sequence[Dict[str, int]]) -> int:
    """Fullest device while it serves: the arrays alive when the window closed
    (weights and slot cache, constant through it) plus the largest reservation
    of a program's temporaries (the decode step's or a prefill chunk's).  Not
    ``peak_bytes_in_use``: that is set-up's transient (the weights' float32
    draw beside their copy), which no request pays for and which hid the step
    from PR 38 on; and not its sum with ``peak_bytes_reserved`` as for a train
    step: the two peaks fall at different times and their sum exceeds the chip.
    The run prints all three."""
    return max((st.get("bytes_in_use", 0) + st.get("peak_bytes_reserved", 0) for st in stats),
               default=0)


def ring_spans(tracer) -> List[Dict[str, Any]]:
    """The tracer ring's spans as ``{name, start, end, step, args}`` on the unix
    clock (the form the training readers take; serving spans carry no step)."""
    out = []
    for ev in tracer.snapshot():
        if ev.get("ph") != "X":
            continue
        start = tracer.epoch_wall + ev["ts"] / 1e6
        out.append({"name": ev["name"], "start": start, "end": start + ev["dur"] / 1e6,
                    "step": None, "args": ev.get("args", {})})
    return out


def profile_iterations(engine, tracer, trace_dir: str, iters: int, timeout_s: float) -> int:
    """A ``jax.profiler`` window over the next ``iters`` decode iterations;
    while it is open the program's spans are TraceAnnotations too."""
    import jax

    start = engine.counters.get("steps")
    jax.profiler.start_trace(trace_dir)
    tracer.profiling = True
    deadline = time.time() + timeout_s
    try:
        while engine.counters.get("steps") - start < iters and time.time() < deadline:
            time.sleep(0.02)
    finally:
        done = engine.counters.get("steps") - start  # stopping takes seconds, the engine runs on
        tracer.profiling = False
        jax.profiler.stop_trace()
    return done


# ---------------------------------------------------------------------------
# one run of one serving cell
# ---------------------------------------------------------------------------


def run_serve_cell(root: str, name: str, *, seed: int, seconds: float, trace: bool,
                   out_dir: str, t_start: float, peaks_row: Optional[Dict[str, Any]] = None,
                   overrides: Sequence[str] = ()) -> Dict[str, Any]:
    """Run serving cell ``name`` once and return the result object.
    ``overrides`` (further ``cli serve`` flags: the engine's own int8 weights,
    which are the control) are for ``benchmark/control.py`` and the tests; the
    benchmark's runs pass none."""
    import jax

    from galvatron_tpu.aot.cache import enable_persistent_cache, resolve_compile_cache_dir
    from galvatron_tpu.obs.tracing import tracer

    cell, config, spec = harness.load_cell(root, name)
    arch = reference.load(root, config["model_type"])
    chips = int(cell["chips"])
    say(f"cell {name}: config {cell['config']} traffic {cell['traffic']} (serve) chips {chips} "
        f"seed {seed} seconds {seconds} trace {int(trace)}")
    say(f"compile cache: {enable_persistent_cache(resolve_compile_cache_dir())}")
    os.makedirs(out_dir, exist_ok=True)
    marks: List[Dict[str, Any]] = []  # the runner's own set-up spans, on the host's clock

    def mark(what: str, span: Optional[str] = None, since: Optional[float] = None) -> float:
        now = time.time()
        say(f"set-up: {what} at t+{now - t_start:.1f} s")
        if span:
            marks.append({"name": span, "start": since, "end": now, "step": 0, "args": {}})
        return now

    t = mark("imports done and device reached")
    if trace:
        tracer.enable(capacity=1 << 17)
    win = spec["window"]
    horizon = traffic_lib.horizon_s(spec, seconds)
    requests = traffic_lib.schedule(seed, spec, int(config["vocab_size"]), horizon)
    t = mark(f"schedule drawn ({len(requests)} requests over {horizon:.0f} s)")
    engine, cfg, params = build_engine(config, spec, seed, overrides=overrides)
    harness.check_widths(cfg, config)
    limits = spec["correct"]
    store = RowStore(int(limits["rows_kept"]), int(config["vocab_size"]))
    jax.block_until_ready(params)
    t = mark("weights made and engine built", "build_runtime", t)
    num_slots, prefill_chunk = engine.slots.num_slots, engine.prefill_chunk
    # both programs and both host samplers, through the scheduler: a prompt of
    # two chunks, then decode; greedy and sampled
    warm = (requests[0]["tokens"] * (1 + prefill_chunk))[:prefill_chunk + 1]
    sampling = spec["sampling"]
    # (their own deadline: a first run compiles both programs inside them)
    engine.generate([warm], max_new_tokens=2, ttl_s=3600.0)
    t = mark("both programs compiled or loaded, first request answered", "step", t)
    engine.generate([warm[:8]] * min(2, num_slots), max_new_tokens=3, ttl_s=3600.0,
                    temperature=float(sampling["temperature"]), top_p=float(sampling["top_p"]))
    setup_s = time.time() - t_start
    mark("warm-up requests answered: set-up ends")
    gc.collect()
    gc.freeze()

    # -- the open loop and the window -------------------------------------
    run = offer(engine, requests, win, seconds, store=store,
                tracer=tracer if trace else None, trace_dir=os.path.join(out_dir, "profile"))
    gc.unfreeze()
    t0, t_open, t_close, gen, profiled = (
        run[k] for k in ("t0", "t_open", "t_close", "gen", "profiled"))
    t_end = time.time()
    mem = harness.memory_stats(jax.local_devices())  # at the window's close, the engine still full
    records = gen.records
    stats = engine.stats()
    cancel_open(records)
    release_cache(engine)
    audit = engine.drain(timeout_s=20.0)

    # -- what the window holds ---------------------------------------------
    num = window_numbers(records, t_open, t_close)
    outcomes = {rec["i"]: outcome(rec) for rec in records}
    attempted = len(num["due_in"])
    failed = sum(1 for rec in num["due_in"] if outcomes[rec["i"]] == "failed")
    any_failed = sorted(i for i, o in outcomes.items() if o == "failed")
    completed = [rec for rec in records if outcomes[rec["i"]] == "completed"]
    # how late the generator ran where it can be read as a fast server: on the
    # requests due inside the window (the opening burst is due all at once and
    # handed over one by one, before the window; its lateness is printed beside)
    late_ms = [1e3 * (rec["submitted"] - rec["due"]) for rec in num["due_in"]]
    late_p99 = percentile(late_ms, 99) if late_ms else 0.0
    late_all = percentile([1e3 * (rec["submitted"] - rec["due"]) for rec in records] or [0.0], 99)
    say(f"traffic: {len(records)} submitted in {t_end - t0:.1f} s, {len(completed)} completed, "
        f"{len(any_failed)} failed {any_failed[:8]}, {attempted} due inside the window; queue "
        f"depth {run['queue_depth'][0]} at the window's opening, {run['queue_depth'][1]} at its "
        f"close, engine restarts {stats['engine_restarts']}")
    starved = f"  WARNING: above {LATE_WARN_MS} ms, the generator was starved"
    say(f"generator_late_ms_p99 {late_p99:.3f} over the {len(late_ms)} requests due inside the "
        f"window ({late_all:.3f} over all {len(records)}, the opening burst among them)"
        + starved * (late_p99 > LATE_WARN_MS))
    tokens_per_s = num["tokens"] / seconds / chips
    say(f"window: {num['tokens']} tokens in {seconds} s = {tokens_per_s:.3f} tokens/s/chip; "
        f"{len(num['ttft_s'])} first tokens, {len(num['itl_s'])} gaps between tokens; "
        f"set-up {setup_s:.1f} s")
    itl, ttft = [1e3 * x for x in num["itl_s"]], [1e3 * x for x in num["ttft_s"]]
    if itl and ttft:
        say("latency, ms: itl mean %.3f p50 %.3f p90 %.3f p95 %.3f p99 %.3f; ttft mean %.3f p50 "
            "%.3f p95 %.3f" % (sum(itl) / len(itl), *(percentile(itl, q) for q in (50, 90, 95, 99)),
                               sum(ttft) / len(ttft), percentile(ttft, 50), percentile(ttft, 95)))
    say("memory: " + "; ".join(
        f"dev{i} live at the window's close {st.get('bytes_in_use', 0) / 2**30:.2f} GiB + "
        f"peak_bytes_reserved {st.get('peak_bytes_reserved', 0) / 2**30:.2f} GiB = the step's "
        f"{serving_peak_bytes([st]) / 2**30:.2f} GiB (memory_peak_bytes); set-up's transient, "
        f"peak_bytes_in_use, {st.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB"
        for i, st in enumerate(mem)))

    # -- correct: free the engine's device state, then the reference ---------
    spans = ring_spans(tracer) if trace else []
    if trace:
        tracer.disable()
    # the rows the engine's tap wrote: one a served token (no request ends on an eos here)
    kept = [{"i": rec["i"], "prompt": rec["prompt"], "generated": list(rec["req"].generated),
             "greedy": rec["greedy"], "temperature": rec["temperature"], "top_p": rec["top_p"],
             "rows": rec["rows"][:rec["req"].logits_rows]}
            for rec in completed if rec["rows"] is not None]
    checked = pick_checked(kept, seed, int(limits["requests"]))
    greedy = {"served": sum(len(r["generated"]) for r in kept if r["greedy"]),
              "wrong": sum(not_best(r["rows"], r["generated"]) for r in kept if r["greedy"])}
    t_sampled = time.time()
    sampled = sampled_check(kept)
    sampled_s = time.time() - t_sampled
    smax = engine.slots.max_seq_len
    engine.slots.cache = None
    engine.params = None
    del engine, gen
    gc.collect()
    t_ref = time.time()
    most = max(sh["output_len"] for sh in traffic_lib.grid(spec))
    cmp = compare_rows(arch, params, config, checked, pad_to=smax, most=most)
    ref_s = time.time() - t_ref
    rel_err = (cmp["e2"] / cmp["r2"]) ** 0.5 if cmp["r2"] > 0 else float("nan")
    kl_mean = cmp["kl"] / cmp["rows"] if cmp["rows"] else float("nan")
    checks = {
        "logits": cmp["rows"] > 0 and kl_mean <= float(limits["logits_kl_max"]),
        "greedy_tokens": greedy["served"] > 0 and greedy["wrong"] == 0,
        # (a mix of greedy requests alone has no sampled token to hold)
        "sampled_tokens": (sampled["inside"] > 0 or int(spec["sampling"]["greedy_every"]) == 1)
        and sampled["outside"] == 0 and abs(sampled["z"]) <= LOGPROB_Z_MAX,
        "lengths": not any_failed,
        "no_leak": not audit["leaked"],
        "no_restart": stats["engine_restarts"] == 0,
    }
    lines = [
        f"correct: logits_kl {kl_mean:.4e} (limit {limits['logits_kl_max']}): the mean divergence "
        f"of the engine's softmax from the float32 reference's over {cmp['rows']} rows of "
        f"{len(checked)} requests {[r['i'] for r in checked]}; read, no limit: the rows' relative "
        f"error {rel_err:.6f}, the worst row's {cmp['worst_row']:.6f}; reference took {ref_s:.1f} s",
        f"correct: greedy_tokens_not_best {greedy['wrong']} (limit 0) of {greedy['served']} served "
        f"greedy tokens of the finished requests whose rows were kept, each against the row it "
        f"was drawn from",
        f"correct: sampled_outside_nucleus {sampled['outside']} (limit 0), sampled_logprob_z "
        f"{sampled['z']:.4f} (limit +-{LOGPROB_Z_MAX:g}) over {sampled['tokens']} sampled tokens of "
        f"the finished requests whose rows were kept, each against the row it was drawn from and "
        f"the temperature and top_p its request stated: outside = the mass of strictly larger "
        f"logits >= top_p + {NUCLEUS_SLACK:g} ({sampled['past_cut']} more within that slack, in "
        f"neither number); z = (sum ln p {sampled['logp']:.3f} - expected {sampled['mean']:.3f}) "
        f"/ sqrt(variance {sampled['var']:.3f}) over the {sampled['inside']} inside the support; "
        f"took {sampled_s:.1f} s",
        f"correct: failed_requests {len(any_failed)} (limit 0); leaked_slots "
        f"{int(audit['leaked'])} (limit 0); engine_restarts {stats['engine_restarts']} (limit 0)",
        f"checks: {json.dumps(checks)}",
    ]
    for line in lines:
        say(line)
        print(line, file=sys.stderr, flush=True)

    d0 = jax.local_devices()[0]
    device: Dict[str, Any] = {"platform": d0.platform, "kind": d0.device_kind,
                              "count": len(jax.devices()),
                              "memory_peak_bytes": serving_peak_bytes(mem)}
    result: Dict[str, Any] = {"correct": all(checks.values()), "attempted": attempted,
                              "failed": failed, "metrics": {}, "device": device,
                              "compared": {"logits_kl": kl_mean, "rows": cmp["rows"],
                                           "logits_rel_err": rel_err,
                                           "worst_row": cmp["worst_row"],
                                           "greedy_requests": sum(r["greedy"] for r in kept),
                                           "greedy_served": greedy["served"],
                                           "greedy_not_best": greedy["wrong"],
                                           "sampled_requests": sum(not r["greedy"] for r in kept),
                                           "sampled_tokens": sampled["tokens"],
                                           "sampled_outside_nucleus": sampled["outside"],
                                           "sampled_logprob_z": sampled["z"],
                                           "sampled_check_s": sampled_s,
                                           "reference_s": ref_s,
                                           "limits": {
                                               "logits_kl": float(limits["logits_kl_max"]),
                                               "greedy_not_best": 0,
                                               "sampled_outside_nucleus": 0,
                                               "sampled_logprob_z": LOGPROB_Z_MAX},
                                           "checks": checks}}
    # what a serving run can report end to end; a cell reports those of them
    # that BENCHMARK.json lists it under
    end_to_end = {
        "serve_tokens_per_s_per_chip": (tokens_per_s, "tokens/s/chip"),
        "ttft_p95_ms": (percentile(ttft, 95) if ttft else None, "ms"),
        "itl_p95_ms": (percentile(itl, 95) if itl else None, "ms"),
        "setup_s": (setup_s, "s"),
    }
    manifest = harness.load_manifest(root)
    if not trace:
        for entry in manifest["end_to_end"]:
            value, unit = end_to_end.get(entry["name"], (None, None))
            if value is not None and name in entry.get("workloads", [name]):
                result["metrics"][entry["name"]] = {"value": float(value), "unit": unit}
        return result

    # -- traced run: per-layer metrics from spans, stamps and the trace -----
    trace_path = xplane.find_trace(os.path.join(out_dir, "profile"))
    in_window = [s for s in spans if t_open <= s["end"] and s["start"] < t_close]
    # before the traffic: the program's ``jax_*`` spans, and the runner's own
    # three marks under the names the set-up readers take from a trainer
    # (``build_runtime``: weights and engine; ``step``: the first request, in
    # which both programs are traced, lowered and compiled or loaded, then run)
    first = next(m for m in marks if m["name"] == "step")
    setup_spans = marks + [
        dict(s, step=0 if first["start"] <= s["start"] and s["end"] <= first["end"] else None)
        for s in spans if t_start <= s["start"] and s["end"] <= t0]
    ctx = {
        "cell": cell, "config": config, "traffic": spec, "chips": chips, "arch": arch,
        "spans": in_window, "setup_spans": setup_spans, "records": [], "step_s": [],
        "trace": xplane.load(trace_path) if trace_path else None, "trace_path": trace_path,
        "n_profiled": profiled, "memory_peak_bytes": device["memory_peak_bytes"],
        "peaks": peaks_row, "say": say,
        "serve": {"num_slots": num_slots, "prefill_chunk": prefill_chunk,
                  "ttft_s": num["ttft_s"], "itl_s": num["itl_s"], "seconds": seconds,
                  "work": window_work(records, t_open, t_close, prefill_chunk)},
    }
    harness.collect_per_layer(root, name, ctx, result)
    harness.device_breakdown(ctx, result)
    ops0 = xplane.first_device(ctx["trace"])
    if "busy_s" in device:
        say(f"device: busy {device['busy_s']:.3f} s of a traced window of {device['window_s']:.3f} "
            f"s, idle {100 * (1 - device['busy_s'] / device['window_s']):.2f}%; peak memory "
            f"{device['memory_peak_bytes'] / 2**30:.2f} GiB")
    if ops0 and profiled:
        say(f"device time by operation, ms an iteration over {profiled} profiled iterations: "
            + "; ".join(f"{k} {1e3 * v / profiled:.3f}" for k, v in xplane.top_ops(ops0, n=24)))
        say("device time by category, ms an iteration: " + "; ".join(
            f"{k} {v / 1e6 / profiled:.3f}" for k, v in sorted(xplane.category_sums(ops0).items(),
                                                                   key=lambda kv: -kv[1])))
    result["compared"] = result.pop("compared")  # the numbers compared come last in the line
    return result
