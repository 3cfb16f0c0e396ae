"""Continuous-batching generation engine: one jitted decode step, many requests.

The serialized server path (``server.GenerationService.generate`` under the
global lock) pays a full prefill+decode ``generate`` per request; aggregate
throughput is one request at a time no matter how many chips sit idle. This
engine instead runs ONE fixed-shape jitted decode step per iteration over a
persistent slot-based KV cache ([[kv_slots]]): every active request occupies
a batch row, new requests join between iterations via chunked prefill into
their slot, and finished rows retire and free their slot immediately
(iteration-level scheduling — Orca, OSDI '22). Overlapping requests share
every forward pass instead of queueing on a lock.

Static shapes are the point on TPU: a small DECLARED set of compiled
programs exists for the engine's whole lifetime — ``_decode_step`` at
``(num_slots, 1)``, ``_prefill_chunk`` at ``(1, prefill_chunk)``, plus
``_decode_verify`` at ``(num_slots, 1+k)`` when speculative decoding is on
(``spec_decode_k > 0``) — slot index, per-row offsets, and prompt contents
are all traced operands, so the jit cache stays bounded at the declared
count regardless of traffic mix (no per-request recompiles). The original
2-program pin grew deliberately: every member of the set is enumerable
up-front (aot/registry), swept by ``cli warmup``, and re-warmed on crash
recovery — an UNdeclared third program is still a bug the recompile guard
catches.

``--serve_quant int8`` swaps the fp weights for per-channel int8
(ops.quant) ONCE at engine load — the quantized avals flow into every
program key, so the int8 engine warms its own artifact set — and the load
parity-gates the measured max-abs logit drift against a declared bound.

``kv_num_blocks != 0`` swaps the contiguous slot cache for the paged
backend ([[paged_kv]]): K/V lives in a shared block pool addressed through
per-request block tables (a fixed ``(num_slots, max_blocks)`` int32 traced
operand), with copy-on-write prefix sharing and block-headroom admission.
The engine keeps the same pinned-program discipline — the paged prefill
and decode twins replace the slot pair one-for-one.

Sampling runs on the DEVICE, behind the decode step: the step's ``(num_slots,
V)`` logits rows stay there and ONE batched program (``_sample_rows`` over
``generation.draw_rows``) draws every active slot's next token from them.
Everything a request may set (temperature, top-k, top-p) and the integers its
randomness comes from (engine seed, request id, token index) are per-slot
ARRAYS, never static arguments, so a greedy, a top-k and a nucleus request are
the same compiled program and a request's tokens do not depend on its
neighbours or its slot. A prompt's last row reaches the rows inside the prefill
program (traced slot and row index). Greedy draws match ``generate``'s
on-device argmax bit-for-bit, which is what the parity tests pin.

The drawn ids STAY on the device too (``Engine._ids``) and are the next step's
``tokens`` operand, so the loop runs one step ahead of the host's bookkeeping
(``Engine._step_ahead``): the host dispatches step N+1 and its draw, and only
then waits for step N's ids and books them while the device runs step N+1. An
iteration costs max(device, host), not their sum. Rows come to the host only
for a request that taps them (``capture_logits``), by a whole-buffer copy that
needs no program, of the rows the booked tokens were drawn from. The
speculative engine (``spec_decode_k > 0``) keeps the synchronous host path
(``_step_host``, ``_sample_host``): its verifier scores drafts against whole
rows with ``generation.host_probs`` and draws its residual from an edited row,
so its rows have to be on the host before the next window can be built.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import Future
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from galvatron_tpu.analysis.locks import lock_check_armed, lock_metrics, make_condition
from galvatron_tpu.core import faults
from galvatron_tpu.models import generation, mixers, moe
from galvatron_tpu.models.generation import KVCache
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.obs.tracing import tracer as _obs_tracer
from galvatron_tpu.serving import resilience as rz
from galvatron_tpu.serving import speculative
from galvatron_tpu.serving.kv_slots import SlotKVCache
from galvatron_tpu.serving.paged_kv import PagedKVCache
from galvatron_tpu.serving.scheduler import Request, Scheduler
from galvatron_tpu.utils.metrics import Counters, Histogram, QuantileWindow

#: decode-iteration latency bucket bounds (seconds): an iteration is
#: single-digit milliseconds on TPU and tens on CPU CI — the request-level
#: DEFAULT_LATENCY_BUCKETS would dump everything into the first bucket
_DECODE_STEP_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
)


def _keep_row(rows, logits, slot, last):
    """``rows`` (num_slots, V) with row ``slot`` replaced by row ``last`` of a
    chunk's ``logits`` (C, V); ``slot`` and ``last`` traced. The rows carry the
    logits' own dtype: a draw sees no precision below its row's."""
    if rows.dtype != logits.dtype:
        raise ValueError(
            f"the engine's rows are {rows.dtype} and the model's logits "
            f"{logits.dtype}: a row must be kept in the precision it was computed in"
        )
    row = jax.lax.dynamic_slice_in_dim(logits, last, 1, axis=0)
    return jax.lax.dynamic_update_slice_in_dim(rows, row, slot, axis=0)


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache", "rows"))
def _prefill_chunk(params, cfg: ModelConfig, cache, tokens, slot, offset,
                   rows, last):
    """Prefill one chunk of one request into its slot.

    tokens: (1, C) — the request's tokens [offset, offset+C) padded at the
    tail; slot/offset are traced scalars, so every chunk of every request
    reuses this one compiled program. Row ``last`` of the chunk's (C, V)
    logits lands in row ``slot`` of ``rows`` (``_keep_row``): after a
    prompt's final chunk that is the row its first token is drawn from.
    Returns (rows, cache, `_router_counters` of the chunk, as `_decode_step`'s).
    Garbage k/v written by tail padding is invisible forever: positions
    beyond a row's own query offset are causally masked, and each decode
    step overwrites its position before attending to it. A layer's per-row
    STATE is written as of row ``last``: the padding never reaches it."""
    stats: list = []
    logits, cache = generation.forward_with_cache(
        params, tokens, cfg, cache, offset, slot=slot, moe_stats=stats, last=last
    )
    return (_keep_row(rows, logits[0], slot, last), cache,
            _router_counters(stats, cfg, tokens.size))


def _router_counters(stats, cfg: ModelConfig, tokens: int) -> Dict[str, jax.Array]:
    """A forward's expert-layer counters from its layers' router statistics, over
    the forward's ``tokens`` tokens (rows without a request among them): the (token,
    expert) pairs a token puts on the experts held here, the fullest held expert's
    pairs over the even share, the held experts that got a row at all
    (`moe.held_experts_touched`: the experts whose weights the forward fetched, since a
    cached forward's `moe.held_layout` gives the others no tile) and, of a
    held share, the share of the rows its kernels multiply that hold a pair
    (`moe.live_rows_share`). Empty for a model without dropless expert layers."""
    if not stats:
        return {}
    held = (cfg.moe_first_held, cfg.moe_held)
    out = {"moe_held_pairs_per_token": moe.held_pairs_per_token(stats, held),
           "moe_load_imbalance": moe.load_max_over_mean(
               stats, cfg.moe_experts, cfg.moe_top_k, held),
           "moe_held_experts_touched": moe.held_experts_touched(stats, held)}
    if cfg.moe_holds_share:
        out["moe_live_rows_share"] = moe.live_rows_share(stats, cfg, tokens)
    return out


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def _decode_step(params, cfg: ModelConfig, cache, tokens, offsets):
    """One decode iteration over ALL slots: tokens (B,) at per-row positions
    offsets (B,). A row that takes no step carries offset 0 (and whatever token
    the device's ids hold for it) — its write lands at position 0 of its own
    slot, free or finished, and is overwritten by the next prefill before
    any query can attend it. Returns ((B, V) next-position logits, cache,
    `_router_counters` of the step: a few scalars left on the device, which
    the engine reads while the tracer is on and no caller otherwise)."""
    stats: list = []
    logits, cache = generation.forward_with_cache(
        params, tokens[:, None], cfg, cache, offsets, moe_stats=stats
    )
    return logits[:, 0], cache, _router_counters(stats, cfg, tokens.size)


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def _decode_verify(params, cfg: ModelConfig, cache, tokens, offsets):
    """Speculative verify step: tokens (B, 1+k) — column 0 is each row's
    sampled token, columns 1..k its drafted continuation — scored in ONE
    forward at per-row positions ``offsets`` (the per-row q_offset machinery
    that already powers chunked prefill handles s>1 rows natively). Returns
    ((B, 1+k, V) logits, cache): row logits[:, j] is the target
    distribution AFTER consuming column j, which is exactly what rejection
    sampling scores draft j+1 against. Rejected-draft k/v written at
    positions past the accepted length is overwritten by the next step's
    window before any query attends it — the same scatter-then-attend
    discipline the (0, 0) inactive rows rely on. The third value as
    `_decode_step`'s."""
    stats: list = []
    logits, cache = generation.forward_with_cache(
        params, tokens, cfg, cache, offsets, moe_stats=stats
    )
    return logits, cache, _router_counters(stats, cfg, tokens.size)


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("pool", "rows"))
def _paged_prefill_chunk(params, cfg: ModelConfig, pool: KVCache, tokens, table,
                         offset, rows, slot, last):
    """Paged twin of ``_prefill_chunk``: tokens (1, C) land in the request's
    blocks via its (1, max_blocks) table row; ``offset`` is a (1,) traced
    position. Tail-padding garbage goes to the null block or to positions
    past the query offset — invisible either way. Returns (rows, pool)."""
    logits, pool = generation.forward_with_cache_paged(
        params, tokens, cfg, pool, table, offset
    )
    return _keep_row(rows, logits[0], slot, last), pool


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("pool",))
def _paged_decode_step(params, cfg: ModelConfig, pool: KVCache, tokens, tables,
                       offsets):
    """Paged twin of ``_decode_step``: one iteration over ALL slots, K/V
    addressed through the full (num_slots, max_blocks) table. Inactive rows
    carry (0, 0) and an all-null table row — their write lands in the null
    block, which is never attended."""
    logits, pool = generation.forward_with_cache_paged(
        params, tokens[:, None], cfg, pool, tables, offsets
    )
    return logits[:, 0], pool


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("pool",))
def _paged_decode_verify(params, cfg: ModelConfig, pool: KVCache, tokens,
                         tables, offsets):
    """Paged twin of ``_decode_verify``: the (B, 1+k) window lands in each
    row's blocks through the full table. Window positions past a row's
    reserved footprint resolve to the null block — written, never attended
    (only accepted positions are ever queried again, and acceptance is
    capped by the row's admission-time budget)."""
    logits, pool = generation.forward_with_cache_paged(
        params, tokens, cfg, pool, tables, offsets
    )
    return logits, pool


#: the engine's counters; ``draws_device`` / ``draws_host`` count the tokens
#: drawn by ``_sample_rows`` and by ``_sample_host`` (the speculative engine's);
#: ``steps_ahead`` the decode steps dispatched while the previous step's ids were
#: not yet on the host, ``row_steps_wasted`` the row-steps dispatched for a row
#: that the bookkeeping, one iteration late, then retired (`Engine._step_ahead`)
_COUNTERS = (
    "steps", "prefill_chunks", "latent_chunks_kernel", "kv_chunks_kernel", "prefill_tokens",
    "tokens_generated",
    "engine_restarts", "draft_proposed", "draft_accepted",
    "spec_steps", "spec_fallbacks", "draws_device", "draws_host",
    "steps_ahead", "row_steps_wasted",
)

#: lines of ``_sample_rows``'s two operand tables, one column a slot
_TEMPERATURE, _TOP_P = 0, 1
_ACTIVE, _TOP_K, _RID, _INDEX, _SEED_LO, _SEED_HI = range(6)


@jax.jit
def _sample_rows(rows, knobs, ints, ids):
    """The engine's draw: one token a slot from the device-resident ``rows``
    (num_slots, V), each slot under its own request's parameters. ``knobs``
    (2, num_slots) float32 holds temperature and top-p, ``ints`` (6, num_slots)
    uint32 the active mask, top-k and what the randomness is made from (request
    id, token index, the engine seed's two words): data, all of it, so every mix
    of requests is this one program. Returns (num_slots,) int32: the drawn token
    of an active slot, and of a slot that is not active what ``ids`` (the last
    draws, still on the device) holds for it, so that a draw for one admitted
    slot keeps the others' tokens and the result is the next step's operand."""
    drawn = generation.draw_rows(
        rows, knobs[_TEMPERATURE], ints[_TOP_K].astype(jnp.int32), knobs[_TOP_P],
        jnp.stack([ints[_SEED_LO, 0], ints[_SEED_HI, 0]]), ints[_RID], ints[_INDEX],
    )
    return jnp.where(ints[_ACTIVE] > 0, drawn, ids)


def _prompt_expert_counts(values: Dict[str, Any], chunk: int) -> Dict[str, float]:
    """A ``prefill`` span's expert counters from its chunks' `_router_counters` as
    host numbers (``last``: the last chunk's, ``full``: those of the chunks whose every
    row is real): the last chunk's as they are and, with held experts, what ONE chunk
    of the prompt put on them, a layer: the mean over its chunks of ``chunk`` real rows
    (a ragged last chunk routes its padding too, all of it to the same few experts, so
    it is left out; a prompt shorter than a chunk has no other, and
    ``moe_chunks_counted`` says 0)."""
    last, full = values["last"], values["full"]
    out = dict(last)
    if "moe_held_pairs_per_token" in last:
        of = full or [last]
        out.update(
            moe_chunks_counted=len(full),
            moe_held_pairs=chunk * sum(r["moe_held_pairs_per_token"] for r in of) / len(of),
            moe_held_experts_touched_a_chunk=sum(
                r["moe_held_experts_touched"] for r in of) / len(of))
    return out


def _sample_host(rng: np.random.Generator, logits: np.ndarray,
                 temperature: float, top_k: int, top_p: float) -> int:
    """Host-side sampler mirroring ``generation.sample_logits`` semantics
    (temperature<=0 → greedy; top-k filter; nucleus keeps the smallest
    prefix with cumulative prob >= top_p, always >= 1 token). The processed
    distribution itself lives in ``generation.host_probs`` — shared with
    the speculative verifier, whose acceptance test must score drafts under
    the SAME distribution this sampler draws from."""
    logits = np.asarray(logits, np.float64)
    if temperature <= 0:
        return int(np.argmax(logits))
    p = generation.host_probs(logits, temperature, top_k, top_p)
    return int(rng.choice(len(p), p=p))


class Engine:
    """Continuous-batching engine: submit() → Future, loop thread does the rest.

    Thread model: handler threads call ``submit``/``stats``; ONE loop thread
    owns the device cache, the slot table, and all jit calls. The scheduler
    queue is the only structure both sides touch, and it carries its own lock.
    """

    def __init__(self, params, cfg: ModelConfig, *, num_slots: int = 4,
                 prefill_chunk: int = 32, max_queue: int = 64,
                 request_ttl_s: Optional[float] = 30.0,
                 max_seq_len: Optional[int] = None, eos_id: int = -1,
                 pad_id: int = 0, seed: int = 0,
                 result_timeout_s: float = 600.0, start_loop: bool = True,
                 deadline_policy: str = "partial",
                 max_engine_restarts: int = 3,
                 restart_backoff_s: float = 0.05,
                 drain_timeout_s: float = 30.0,
                 flight_dir: Optional[str] = None,
                 kv_block_size: int = 16,
                 kv_num_blocks: int = 0,
                 prefix_cache: bool = True,
                 serve_quant: str = "off",
                 quant_drift_max: float = 1.0,
                 spec_decode_k: int = 0,
                 spec_drafter: str = "prompt_lookup"):
        if deadline_policy not in ("partial", "fail"):
            raise ValueError(
                f"deadline_policy must be 'partial' or 'fail', got "
                f"{deadline_policy!r}"
            )
        if not cfg.causal or cfg.objective != "clm" or cfg.enc_layers > 0:
            raise ValueError(
                "serving engine requires a decoder-only causal LM (same "
                "constraint as generation.generate)"
            )
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if serve_quant not in ("off", "int8"):
            raise ValueError(
                f"serve_quant must be 'off' or 'int8', got {serve_quant!r}"
            )
        self.serve_quant = serve_quant
        self.quant_drift_max = float(quant_drift_max)
        self.quant_parity: Optional[dict] = None
        if serve_quant == "int8":
            # quantize ONCE, here — the step never touches fp weights — and
            # refuse to serve a quantization that left its accuracy budget:
            # the drift is measured on a probe forward, not assumed
            from galvatron_tpu.ops import quant as _quant

            qparams = _quant.quantize_params(params, cfg)
            self.quant_parity = _quant.parity_report(
                params, qparams, cfg, drift_max=self.quant_drift_max
            )
            params = qparams
        self.spec_k = int(spec_decode_k)
        if self.spec_k < 0:
            raise ValueError(f"spec_decode_k must be >= 0, got {spec_decode_k}")
        self.spec_drafter = spec_drafter if self.spec_k > 0 else None
        self.drafter = (
            speculative.make_drafter(spec_drafter) if self.spec_k > 0 else None
        )
        self.params = params
        self.cfg = cfg
        self.eos_id = int(eos_id)
        self.pad_id = int(pad_id)
        self.seed = int(seed)
        self.result_timeout_s = float(result_timeout_s)
        # kv_num_blocks != 0 selects the paged backend: block-granular KV
        # with COW prefix sharing (serving/paged_kv.py); -1 sizes the pool
        # to the same HBM as the slot cache. 0 keeps the contiguous slot
        # cache. Both expose the same allocator surface to the engine.
        self.paged = int(kv_num_blocks) != 0
        # what a position costs the cache, under its kind's name
        self.cache_layout = generation.cache_layout(cfg)
        if self.paged and self.cache_layout["kind"] != "kv":
            raise ValueError(
                "the paged backend (--kv_num_blocks) holds blocks of K and V; a stack whose "
                f"layers keep a {self.cache_layout['kind']} cache is served from the "
                "slot cache (kv_num_blocks 0)"
            )
        for limit in mixers.limits(cfg):
            if (limit.what == "paged_kv" and self.paged) or (
                    limit.what == "spec_decode" and self.spec_k > 0):
                raise ValueError(limit.sentence())
        if self.paged:
            self.slots = PagedKVCache(
                cfg, num_slots, block_size=kv_block_size,
                num_blocks=kv_num_blocks, max_seq_len=max_seq_len,
                prefix_cache=prefix_cache,
            )
        else:
            # (the most positions one forward writes a slot sizes a windowed stack's ring;
            # what a slot of it holds is known once the slots know that)
            self.slots = SlotKVCache(cfg, num_slots, max_seq_len,
                                     tokens=max(int(prefill_chunk), 1 + self.spec_k))
            self.cache_layout = generation.cache_layout(
                cfg, self.slots.max_seq_len, self.slots.tokens)
        # a chunk longer than the slot would slice past the cache end
        self.prefill_chunk = min(int(prefill_chunk), self.slots.max_seq_len)
        if generation.stacked(cfg) and self.slots.max_seq_len % self.prefill_chunk:
            # a prompt's last chunk is slid left where it would cross the slot's end
            # (`_prefill`), to a start that is no multiple of the chunk: the one write
            # a ring cannot take whole (generation.write_ring), and positions run twice
            # through a layer's state
            raise ValueError(
                f"a stack with sliding-window layers or with layers that keep a state needs "
                f"slots of a whole number of prompt "
                f"chunks: max_seq_len {self.slots.max_seq_len} is no multiple of prefill_chunk "
                f"{self.prefill_chunk} (a chunk then starts at a multiple of the chunk, "
                "never crosses the ring's end and never runs a position twice through a state)")
        # of a latent cache or a `stacked` stack's K and V: which body a prompt chunk's
        # attention takes and the keys a block of it fetches (fixed by the shapes: asked once)
        self.cache_layout.update(
            generation.chunk_layout(cfg, self.prefill_chunk, self.slots.max_seq_len,
                                    self.cache_layout.get("ring_positions")))
        # which body each kind's layers take, of the kinds the stack has (static: asked once)
        self._layer_paths = {k: v for k, v in mixers.path_counts(cfg).items() if any(v.values())}
        self.scheduler = Scheduler(max_queue=max_queue, default_ttl_s=request_ttl_s)
        self.deadline_policy = deadline_policy
        self.drain_timeout_s = float(drain_timeout_s)
        self.supervisor = rz.EngineSupervisor(
            max_restarts=max_engine_restarts, backoff_s=restart_backoff_s,
            flight_dir=flight_dir,
        )
        self.counters = Counters(*_COUNTERS)
        self.ttft = QuantileWindow(512)
        # cumulative-bucket twins of the quantile windows: quantiles are the
        # single-process readout; bucket counts SUM across replicas, so the
        # fleet router aggregates these (snapshots ride /healthz → probe)
        self.ttft_hist = Histogram()
        self.latency_hist = Histogram()
        # per-ITERATION decode latency (the least-measured hot path until
        # now): finer buckets than the request-level histograms — one
        # iteration is milliseconds, not seconds
        self.decode_step_hist = Histogram(_DECODE_STEP_BUCKETS)
        # AOT artifact store for crash warm-rebuilds (set by warm_start);
        # summary of the most recent restart's warm-up, for tests/probes
        self._store = None
        self.last_restart_warm: Optional[dict] = None
        # where a token is drawn is what the engine knows of itself: plain
        # decoding draws on the device from rows that stay there; the
        # speculative verifier needs whole rows on the host and draws there
        self._device_draw = self.spec_k == 0
        # the rows the next tokens are drawn from: on the device (the decode
        # step's logits, a prompt's last row set in by the prefill program),
        # and on the host, float32, the rows of the slots that need them there
        # (a request that taps; every slot of a speculative engine)
        self._rows = self._fresh_rows()
        self._host_rows = np.zeros(
            (self.slots.num_slots, cfg.vocab_size), np.float32
        )
        # the token each slot's request takes next, ON THE DEVICE (the last draws,
        # merged: `_sample_rows`): the next step's operand, read one iteration late
        self._ids = self._fresh_ids()
        # whether a decode step's draw is among them, unread (then the next step is
        # dispatched AHEAD of the host), and that step's `_router_counters`
        self._step_unread = False
        self._router_unread: Dict[str, jax.Array] = {}
        # the rows the tokens now being booked were drawn from (`_last_logits`)
        self._booking = None
        self._by_slot: Dict[int, Request] = {}
        self._rng: Dict[int, np.random.Generator] = {}
        # the expert counters of the last step the tracer saw (host numbers; none for a dense model)
        self._router_counters: Dict[str, float] = {}
        self._busy_s = 0.0
        self._last_step_tps = 0.0
        # GALVATRON_RECOMPILE_GUARD=1 (debug/CI): after the first decode
        # iteration, the engine's programs exist — any further jit-cache
        # growth is a static-arg/shape leak compiling per request, and the
        # guard fails the offending step loudly (analysis/guards.py) instead
        # of letting latency quietly collapse. Per-engine baseline: other
        # engines compiling in the same process (different cfg) would show
        # as growth, so arm it on single-engine runs only.
        self._guard_armed = os.environ.get("GALVATRON_RECOMPILE_GUARD", "") not in ("", "0")
        self._guard_baseline = None
        self._cond = make_condition("engine.cond")
        self._stop = False  # guarded-by: self._cond
        self._draining = False
        self._closed = False
        self._working = False  # loop thread inside one admit+step iteration
        self._thread = threading.Thread(
            target=self._loop, name="serving-engine", daemon=True
        )
        if start_loop:
            self._thread.start()

    # -- client side --------------------------------------------------------

    def submit(self, tokens: Sequence[int], max_new_tokens: int,
               temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
               ttl_s: Optional[float] = None) -> Future:
        """Enqueue one request; the Future resolves to the full token list
        (prompt + completion, eos excluded — ``generate_np`` row semantics).
        Raises ``QueueFull`` on backpressure; the Future fails with
        ``RequestExpired`` if the request out-waits its TTL in queue."""
        return self.submit_request(
            tokens, max_new_tokens, temperature=temperature, top_k=top_k,
            top_p=top_p, ttl_s=ttl_s,
        ).future

    def submit_request(self, tokens: Sequence[int], max_new_tokens: int,
                       temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 0.0,
                       ttl_s: Optional[float] = None,
                       trace_id: Optional[str] = None,
                       capture_logits: Optional[np.ndarray] = None) -> Request:
        """Like :meth:`submit` but returns the :class:`Request`, which
        carries the lifecycle state, ``finish_reason`` (deadline
        truncation), the host times (``admitted_at``, ``token_times``,
        ``finished_at``) and the ``cancel()`` handle the server's disconnect
        poll uses. Refuses immediately — instead of parking a future that
        can never resolve — when the engine is draining or closed.
        ``trace_id`` is the fleet router's correlation id (propagated via
        the X-Galvatron-Trace-Id header, obs/correlate.py); it rides every
        lifecycle instant and the prefill span.

        ``capture_logits`` is the logits tap: the CALLER's writable float32
        array of at least ``(max_new_tokens, vocab_size)``. Before token
        ``k`` is drawn the loop thread copies the row it is drawn from into
        ``capture_logits[k]`` (prefill's last row for ``k`` = 0, then each
        decode step's) and counts ``Request.logits_rows``; it allocates
        nothing. Refused with ``spec_decode_k > 0``: a token accepted from a
        verify window, or drawn after a rejection from a row with the
        rejected token struck out, has no one row it was drawn from."""
        if self._closed:
            raise rz.EngineClosed(
                "engine is closed"
                + (" (crash-restart budget exhausted)"
                   if self.supervisor.gave_up else "")
            )
        if self._draining:
            raise rz.EngineDraining(
                "server is draining: not accepting new requests",
                retry_after_s=self.drain_timeout_s,
            )
        tokens = [int(t) for t in tokens]
        if not tokens:
            raise ValueError("empty prompt")
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 0:
            raise ValueError("max_new_tokens must be >= 0")
        if not self.slots.fits(len(tokens), max_new_tokens):
            raise ValueError(
                f"prompt ({len(tokens)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the engine's slot capacity {self.slots.max_seq_len}"
            )
        if capture_logits is not None:
            self._check_capture(capture_logits, max_new_tokens)
        req = Request(
            tokens=tokens, max_new_tokens=max_new_tokens,
            temperature=float(temperature), top_k=int(top_k),
            top_p=float(top_p), trace_id=trace_id,
            capture_logits=capture_logits,
        )
        if trace_id is not None:
            _obs_tracer.instant("req_queued", rid=req.rid, tokens=len(tokens),
                                trace_id=trace_id)
        else:
            _obs_tracer.instant("req_queued", rid=req.rid, tokens=len(tokens))
        if max_new_tokens == 0:
            # counted as submitted too: terminal outcomes must partition the
            # submitted total or /metrics shows completed > submitted
            self.scheduler.counters.inc("submitted")
            rz.advance(req, rz.COMPLETED, self.scheduler.counters,
                       reason="zero_budget")
            req.finish_reason = "length"
            req.future.set_result(list(tokens))
            return req
        self.scheduler.submit(req, ttl_s=ttl_s)
        with self._cond:
            self._cond.notify()
        if self._closed:
            # close()/give-up raced the enqueue: the shutdown drain may have
            # run before our submit landed, and nothing will ever pop the
            # queue again — fail it here (idempotent if the drain got it)
            # so no caller is left holding a future that cannot resolve
            exc = rz.EngineClosed("engine shut down")
            self.scheduler.drain(exc)
            raise exc
        return req

    def _check_capture(self, buf, max_new_tokens: int) -> None:
        """Refuse a logits tap the loop thread could not write without
        allocating, converting or running past its end."""
        if self.spec_k > 0:
            raise ValueError(
                "capture_logits is not supported with spec_decode_k > 0: a token "
                "taken from a verify window has no one row it was drawn from"
            )
        vocab = self._host_rows.shape[1]
        if not (isinstance(buf, np.ndarray) and buf.dtype == np.float32
                and buf.ndim == 2 and buf.shape[0] >= max_new_tokens
                and buf.shape[1] == vocab and buf.flags.writeable):
            raise ValueError(
                f"capture_logits must be a writable float32 numpy array of at "
                f"least ({max_new_tokens}, {vocab}) = (max_new_tokens, vocab_size); got "
                f"{type(buf).__name__} {getattr(buf, 'dtype', None)} "
                f"{getattr(buf, 'shape', None)}"
            )

    def generate(self, prompts: Sequence[Sequence[int]], max_new_tokens: int = 32,
                 **kw) -> List[List[int]]:
        """Synchronous convenience over ``submit`` (bench/tests): submits all
        prompts at once so they overlap, then gathers in order."""
        futures = [self.submit(p, max_new_tokens, **kw) for p in prompts]
        return [f.result(timeout=self.result_timeout_s) for f in futures]

    def stats(self) -> dict:
        sc = self.scheduler.counters.snapshot()
        ec = self.counters.snapshot()
        ttft = self.ttft.summary()
        tokens = ec["tokens_generated"]
        busy = self._busy_s
        extra = {}
        if self.paged:
            extra = self.slots.block_stats()
            # per-request block footprint, keyed by rid (JSON-safe): what an
            # operator reads to see who is holding the pool
            extra["blocks_held"] = {
                str(req.rid): self.slots.blocks_held(slot)
                for slot, req in self._by_slot.items()
            }
        steps = ec["steps"]
        if lock_check_armed():
            # per-lock hold/contention counters from the runtime validator
            # (analysis/locks.py); the fleet router rolls these into
            # galvatron_lock_* /metrics families per replica
            extra["lock_stats"] = lock_metrics()
        if not self.paged:
            # the slot cache by its layers' kind: what it holds a position, in all
            extra["cache_kind"] = self.cache_layout["kind"]
            extra["cache_bytes"] = self.cache_layout["bytes_per_slot"] * self.slots.num_slots
            if "ring_positions" in self.cache_layout:  # two stacks, one a ring
                extra["kv_ring_positions"] = self.cache_layout["ring_positions"]
            if "full_layers" in self.cache_layout:  # which stacks the cache has, in layers
                extra["cache_stacks"] = {
                    stack: self.cache_layout.get(f"{stack}_layers", 0)
                    for stack in generation.STACKS}
            extra.update(self._layer_paths)
            extra.update(self.step_counters())
            if self.cfg.moe_dropless:
                extra["moe_row_tile_prefill"] = moe.layer_row_tile(self.cfg, self.prefill_chunk)
            if "chunk_path" in self.cache_layout:
                # the body every prompt chunk's attention takes, and how many took the
                # kernel (over ``prefill_chunks``: 1.0 or 0.0)
                extra["chunk_path"] = self.cache_layout["chunk_path"]
                taken = self.cache_layout["kind"] + "_chunks_kernel"
                extra[taken] = ec[taken]
        return {
            "kv_backend": "paged" if self.paged else "slot",
            # the replica's numerics contract rides /healthz: the fleet
            # router refuses to mix replicas whose quant/spec config
            # disagrees (bit-parity across a fleet is only meaningful
            # between identically-configured engines)
            "serve_quant": self.serve_quant,
            "spec_decode_k": self.spec_k,
            "spec_drafter": self.spec_drafter,
            "quant_parity": self.quant_parity,
            # the capacity the replica ACTUALLY reserved (satellite of the
            # silent-clamp fix: a clamped --max_seq_len shows up here)
            "max_seq_len_effective": self.slots.max_seq_len,
            # crash-recovery warmth over HTTP: the chaos harness asserts a
            # restarted engine re-hit its programs in the artifact store
            "restart_warm": self.last_restart_warm,
            **extra,
            "queue_depth": self.scheduler.depth,
            "queue_capacity": self.scheduler.max_queue,
            "queue_saturated": self.scheduler.saturated,
            "active_slots": self.slots.active_count,
            "num_slots": self.slots.num_slots,
            "occupancy": round(self.slots.occupancy, 4),
            "steps": ec["steps"],
            "prefill_chunks": ec["prefill_chunks"],
            "prefill_tokens": ec["prefill_tokens"],
            "tokens_generated": tokens,
            "tokens_per_s": round(tokens / busy, 3) if busy > 0 else 0.0,
            "tokens_per_s_last_step": round(self._last_step_tps, 3),
            "ttft_p50_s": ttft["p50"],
            "ttft_p95_s": ttft["p95"],
            # the fleet bench reads the served tail per replica over HTTP
            "ttft_p99_s": self.ttft.quantile(0.99),
            # serializable cumulative-bucket snapshots: they ride /healthz
            # JSON to the fleet router, which sums them into the fleet-level
            # histograms (quantiles can't aggregate; buckets do)
            "ttft_hist": self.ttft_hist.snapshot(),
            "latency_hist": self.latency_hist.snapshot(),
            "decode_step_hist": self.decode_step_hist.snapshot(),
            # decode-speed observability (the "least-measured hot path"
            # satellite): tokens per decode iteration, batched over slots —
            # ~active-slot width without spec; rising above that width means
            # speculative acceptance is paying — plus the raw draft economy
            "accepted_tokens_per_step": (
                round(tokens / steps, 4) if steps else 0.0
            ),
            "draft_proposed": ec["draft_proposed"],
            "draft_accepted": ec["draft_accepted"],
            "draft_acceptance_rate": (
                round(ec["draft_accepted"] / ec["draft_proposed"], 4)
                if ec["draft_proposed"] else 0.0
            ),
            "spec_steps": ec["spec_steps"],
            "spec_fallbacks": ec["spec_fallbacks"],
            # where tokens are drawn: ``_sample_rows`` on the device, or the
            # speculative engine's ``_sample_host``
            "draws_device": ec["draws_device"],
            "draws_host": ec["draws_host"],
            # how far the loop ran ahead of its bookkeeping (``_step_ahead``): decode
            # steps dispatched before the previous step's ids were on the host, and
            # row-steps spent on a row that was found retired one iteration late
            "steps_ahead": ec["steps_ahead"],
            "row_steps_wasted": ec["row_steps_wasted"],
            "submitted": sc["submitted"],
            "admitted": sc["admitted"],
            "completed": sc["completed"],
            "failed": sc["failed"],
            "rejected_queue_full": sc["rejected_queue_full"],
            "expired": sc["expired"],
            "expired_decode": sc["expired_decode"],
            "cancelled": sc["cancelled"],
            "cancelled_disconnect": sc["cancelled_disconnect"],
            "shed": sc["shed"],
            "engine_restarts": ec["engine_restarts"],
            "draining": self._draining,
            "alive": self.alive,
        }

    def step_counters(self, slots: Optional[Sequence[int]] = None, window: int = 1) -> dict:
        """What the last decode iteration worked on: the cache's bytes a position
        (all layers; ONE layer's of a windowed stack, whose stacks hold different
        positions) under its kind's name, the positions live in ``slots`` (the
        slots in use), of a kind with a cache of its own the positions a layer's
        attention FETCHES for them by construction (a window of ``window`` queries
        a row; `generation.cache_read_positions`: live over read is the share of
        the fetched bytes that were needed) and, of a model with dropless expert
        layers, the row tile the step's expert layers took (`moe.layer_row_tile`),
        the experts held here and the step's `_router_counters` of the last
        iteration the tracer saw (host numbers: no caller waits for the device)."""
        layout, lengths = self.cache_layout, self.slots.lengths
        slots = self.slots.active_slots() if slots is None else slots
        # (on the loop's thread between two spans: no array is built here)
        live = [int(lengths[s]) for s in slots]
        if layout.get("latent_stacks"):
            # a latent stack of more than one width (index keys, a ring): its own
            # counters by stack, under names no reader of a dense latent or of K and V
            # takes for its own (``latent_live_positions`` x a dense latent's bytes would
            # price a sparse layer above what any program need fetch)
            out = mixers.module(mixers.cache_kind(self.cfg)).step_counters(
                layout, [n + window - 1 for n in live], self.slots.num_slots,
                self.slots.max_seq_len, window)
            read = None
        else:
            per_position = (layout["bytes_per_position_per_layer"] if "full_layers" in layout
                            else layout["bytes_per_position"])
            out = {f"{layout['kind']}_cache_bytes_per_position": per_position,
                   f"{layout['kind']}_live_positions": sum(live)}
            read = generation.cache_read_positions(
                self.cfg, [n + window - 1 for n in live], self.slots.num_slots,
                self.slots.max_seq_len, window, ring=layout.get("ring_positions"))
        if isinstance(read, dict):
            # stacks by what a layer keeps: what a layer of each holds live for the rows
            # (a window layer the last ``window`` positions) and fetches, and how many
            # layers each stack has (``kv_cache_bytes_per_position`` is ONE layer's there,
            # and so is ``state_bytes_per_row``, what a state layer reads and writes a row)
            span = layout["window"]
            if "state_layers" in layout:
                out.update(state_layers=layout["state_layers"],
                           state_bytes_per_row=layout["state_bytes_per_row"])
            if "state_part_bytes" in layout:
                # a state of several parts (a Mamba-2 layer's conv tail and scan state):
                # ONE layer's bytes a row by part, and what a decode step's state traffic
                # moves by construction: every row's state read once and written once
                out.update({f"state_{part}_bytes_per_row": size
                            for part, size in layout["state_part_bytes"].items()})
                out["state_step_bytes"] = (2 * self.slots.num_slots * layout["state_layers"]
                                           * layout["state_bytes_per_row"])
            out.update(
                kv_full_live_positions=sum(live),
                kv_window_live_positions=sum(min(n, span) for n in live),
                kv_full_read_positions=read["full"], kv_window_read_positions=read["window"],
                kv_full_layers=layout["full_layers"], kv_window_layers=layout["window_layers"])
        elif read is not None:
            out[f"{layout['kind']}_read_positions"] = read
        if self.cfg.moe_dropless:
            # the row tile the step's expert layers compiled with (static: by shape), and
            # the experts this copy holds, which `moe_held_experts_touched` counts among
            out["moe_row_tile"] = moe.layer_row_tile(self.cfg, self.slots.num_slots * window)
            out["moe_held_experts"] = self.cfg.moe_held
        return {**out, **self._router_counters}

    @property
    def alive(self) -> bool:
        """False once the engine is closed, drained, or gave up restarting
        — what ``/readyz`` keys on."""
        return not self._closed and not self.supervisor.gave_up

    @property
    def busy_retry_after_s(self) -> float:
        """Honest Retry-After hint for admission backpressure (queue full /
        pool saturated): the queue turns over at TTL granularity at worst,
        so a shed client retrying sooner than a fraction of it just burns
        its budget re-queueing."""
        ttl = self.scheduler.default_ttl_s
        return max(1.0, min(ttl if ttl else 5.0, 5.0))

    def reset_metrics(self) -> None:
        """Zero counters/TTFT/throughput accounting (bench: drop warmup
        compile time from the measured window). Call while idle."""
        self.counters = Counters(*_COUNTERS)
        self.scheduler.counters = Scheduler.new_counters()
        # the supervisor's progress detection reads the completed counter:
        # its high-water mark must reset with it, or post-reset completions
        # never register as progress and the restart budget burns early
        self.supervisor.note_counter_reset()
        self.ttft = QuantileWindow(512)
        self.ttft_hist = Histogram()
        self.latency_hist = Histogram()
        self.decode_step_hist = Histogram(_DECODE_STEP_BUCKETS)
        self._busy_s = 0.0
        self._last_step_tps = 0.0

    def step_once(self) -> None:
        """One scheduler+decode iteration, synchronously (tests and
        ``start_loop=False`` callers — deterministic interleaving)."""
        self._iterate()

    def begin_drain(self) -> None:
        """Flip into draining mode without blocking: admission closes
        (``submit`` raises ``EngineDraining``), queued-but-unstarted
        requests are shed fast with the distinct ``SHED`` status, in-flight
        slots keep decoding. Idempotent; :meth:`drain` adds the bounded
        wait + finalization."""
        with self._cond:
            if self._draining:
                return
            self._draining = True
            self._cond.notify_all()
        _obs_tracer.instant(
            "engine_drain_begin", active=self.slots.active_count,
            queued=self.scheduler.depth,
        )
        self.scheduler.shed_all(retry_after_s=self.drain_timeout_s)

    def drain(self, timeout_s: Optional[float] = None) -> dict:
        """Graceful shutdown: shed the queue, let in-flight slots run to
        completion under a bounded deadline, then stop the loop and close.
        Returns the post-drain invariant :meth:`audit` (zero leaked slots
        on every exit path is the contract the chaos harness pins)."""
        timeout_s = self.drain_timeout_s if timeout_s is None else float(timeout_s)
        self.begin_drain()
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            # the allocator, not _by_slot, is the in-flight authority: a
            # request mid-PREFILL holds a slot before it reaches _by_slot,
            # and _working covers the pop→alloc gap inside one iteration —
            # closing under either would fail work the drain promised to
            # finish
            if (self.slots.active_count == 0 and self.scheduler.empty()
                    and not self._working):
                break
            if not self._thread.is_alive():
                break  # start_loop=False or a give-up: nothing will progress
            time.sleep(0.01)
        overran = [r.rid for r in self._by_slot.values()]
        # exit time past the deadline is bounded by ONE loop iteration (the
        # thread cannot be preempted mid-jit-dispatch, only asked to stop at
        # the next iteration boundary) — budget the join accordingly rather
        # than the blind 30 s shutdown default
        self.close(join_timeout_s=max(2.0, timeout_s))
        if overran:
            _obs_tracer.instant("engine_drain_overrun", rids=str(overran))
        audit = self.audit()
        _obs_tracer.instant("engine_drain_done", **{
            k: v for k, v in audit.items() if not isinstance(v, dict)})
        if self.supervisor.flight_dir:
            # every exit path leaves forensics, the graceful one included —
            # the chaos harness asserts a dump exists for drain AND crash
            from galvatron_tpu.obs.flight import dump_flight

            dump_flight(self.supervisor.flight_dir, _obs_tracer,
                        reason="graceful drain", extra=audit)
        return audit

    def audit(self) -> dict:
        """Post-drain/post-traffic invariant check: every slot returned to
        the free list, no request bookkeeping left behind, and (when the
        jit programs exist) the two-program pin intact. On the paged
        backend the block partition is part of the leak proof: after a
        drain every block must be FREE or CACHED (a cached prefix is kept
        warm deliberately — only an OWNED block with no owner is a leak)."""
        slot_audit = self.slots.audit()
        out = {
            "slots_ok": slot_audit["ok"],
            "active_slots": slot_audit["active"],
            "free_slots": slot_audit["free"],
            "num_slots": slot_audit["num_slots"],
            "tracked_requests": len(self._by_slot),
            "queue_depth": self.scheduler.depth,
            "leaked": (not slot_audit["ok"] or slot_audit["active"] != 0
                       or slot_audit["free"] != slot_audit["num_slots"]
                       or bool(self._by_slot)),
            "engine_restarts": self.counters.get("engine_restarts"),
        }
        if self.paged:
            out.update(
                blocks_ok=slot_audit["blocks_ok"],
                blocks_total=slot_audit["blocks_total"],
                blocks_free=slot_audit["blocks_free"],
                blocks_cached=slot_audit["blocks_cached"],
                blocks_active=slot_audit["blocks_active"],
            )
            out["leaked"] = bool(
                out["leaked"] or not slot_audit["blocks_ok"]
                or slot_audit["blocks_active"] != 0
            )
        return out

    def close(self, join_timeout_s: float = 30.0) -> None:
        self._closed = True
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread.is_alive() and threading.current_thread() is not self._thread:
            self._thread.join(timeout=join_timeout_s)
        self._fail_all(rz.EngineClosed("engine shut down"))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- the rows tokens are drawn from ---------------------------------------

    def _fresh_rows(self):
        """Zeroed device rows (num_slots, V) in the logits' dtype, by a copy
        from the host: no program to compile."""
        shape = (self.slots.num_slots, self.cfg.vocab_size)
        return jax.device_put(np.zeros(shape, jnp.dtype(self.cfg.dtype)))

    def _fresh_ids(self):
        """Zeroed device ids (num_slots,), as `_fresh_rows`."""
        return jax.device_put(np.zeros((self.slots.num_slots,), np.int32))

    @property
    def _last_logits(self) -> np.ndarray:
        """(num_slots, V) float32: row ``slot`` is the row that slot's next
        token is drawn from: the token the host books next, so, while it books an
        iteration's tokens, the rows THEY were drawn from (the device is a step
        further by then). The speculative engine holds them on the host;
        otherwise this is a copy of the device's rows made on every read (tests
        and an operator's probe read it; the loop itself does not)."""
        if not self._device_draw:
            return self._host_rows
        rows = self._rows if self._booking is None else self._booking
        return np.asarray(rows).astype(np.float32)

    def _draw(self, slots: Sequence[int], unbooked: int) -> None:
        """Start ``_sample_rows`` for ``slots`` on the device rows: each slot's
        next token under its request's own parameters. Its index in the request's
        stream is ``len(generated)`` + ``unbooked``, the tokens drawn for it that
        the host has not booked yet (0 behind a prompt, 1 behind a step): a
        count, which no token's value moves. The ids stay on the device, merged
        into the other slots' (``_ids``); their copy to the host starts now."""
        n = self.slots.num_slots
        knobs = np.zeros((2, n), np.float32)
        ints = np.zeros((6, n), np.uint32)
        ints[_SEED_LO], ints[_SEED_HI] = self.seed & 0xFFFFFFFF, (self.seed >> 32) & 0xFFFFFFFF
        for slot in slots:
            req = self._by_slot[slot]
            knobs[:, slot] = req.temperature, req.top_p
            ints[:_SEED_LO, slot] = (1, max(req.top_k, 0), req.rid & 0xFFFFFFFF,
                                     len(req.generated) + unbooked)
        self._ids = _sample_rows(self._rows, knobs, ints, self._ids)
        self._ids.copy_to_host_async()

    # -- engine loop (single thread owns cache + slots + jit calls) ---------

    def _loop(self) -> None:
        while True:
            with self._cond:
                while (not self._stop and self.scheduler.empty()
                       and not self._by_slot):
                    # short timeout: TTLs must expire even with no wakeups
                    self._cond.wait(timeout=0.05)
                if self._stop:
                    break
            try:
                self._working = True
                try:
                    self._iterate()
                finally:
                    self._working = False
            except Exception as e:  # noqa: BLE001 — engine must not die silently
                # in-process crash supervision (resilience.EngineSupervisor):
                # fail the unreplayable in-flight work fast, keep queued
                # requests with TTL budget, reset the KV cache, warm-rebuild,
                # and keep looping — give-up closes the engine for good
                try:
                    recovered = self.supervisor.on_crash(self, e)
                except Exception as e2:  # noqa: BLE001 — recovery failed
                    # a crash INSIDE recovery must not strand the loop
                    # thread with live futures: treat it as a give-up
                    self.supervisor.gave_up = True
                    recovered = False
                    e = e2
                if not recovered:
                    self._closed = True
                    self._fail_all(rz.EngineClosed(
                        f"engine gave up after "
                        f"{self.supervisor.restarts_total} restart(s): "
                        f"{type(e).__name__}: {e}"
                    ))
                    break

    def _iterate(self) -> None:
        """One iteration of the loop thread: admissions, then one decode step
        over the slots in use. The span tree (tracer on only; every child on
        this thread, inside its parent): ``iteration`` > ``admit`` >
        ``prefill_dispatch`` (one a request) > ``chunk_dispatch`` (one a chunk);
        ``decode`` (or ``decode_verify``) > ``decode_dispatch``,
        ``decode_wait``, ``logits_readback``; ``sample`` > ``sample_slot``
        (behind the forward it runs beside on the device-draw path, in front of
        it on the speculative engine's: ``_step``). Off this thread, on tracks of
        their own: ``queue_wait`` (``serving queue``) and ``prefill``, a prompt's
        chunks on the device (``device``: ``_prefill``). Tracer on, this thread
        waits for the device where it waits with the tracer off (``decode_wait``,
        ``logits_readback``) and nowhere else. ``step`` is the ``steps`` counter at entry, so a
        compile or a collection inside an iteration names it
        (``Tracer.current_step``) and the profiler groups device work by it."""
        if self.scheduler.empty() and not self._by_slot:
            return  # ``step_once`` on an idle engine: no work, no span
        with _obs_tracer.span(
                "iteration", step=self.counters.get("steps"),
                active=self.slots.active_count, queued=self.scheduler.depth):
            self._admit()
            if self._by_slot:
                self._step()

    def _head_admissible(self) -> bool:
        """Whether the queue's head can be popped now. On the paged backend
        admission additionally gates on BLOCK headroom: the head request
        stays queued (TTL still burning — that is the backpressure signal)
        until the pool's free + evictable blocks cover its worst-case
        footprint, so decode can never hit an empty pool."""
        if not self.paged:
            return not self.scheduler.empty()
        head = self.scheduler.peek()
        if head is None:
            return False
        if head.cancel_requested or head.future.cancelled():
            return True
        return self.slots.can_admit(
            head.tokens, head.max_new_tokens, chunk=self.prefill_chunk)

    def _admit(self) -> None:
        """Admit queued requests into free slots (chunked prefill); the
        ``admit`` span opens only when there is a request to pop."""
        self.scheduler.expire()
        if self.slots.free_slots > 0 and self._head_admissible():
            with _obs_tracer.span("admit") as sp:
                admitted = self._admit_queued()
                # (a stack with a state: each admitted request's first chunk read zeros in
                # its slot's place, whatever the slot held)
                zeroed = {"state_rows_zeroed": admitted} if "state_layers" in self.cache_layout else {}
                sp.set(admitted=admitted, **zeroed)

    def _admit_queued(self) -> int:
        """Pop and prefill while a slot is free and the head is admissible;
        returns how many requests took a slot."""
        admitted = 0
        while self.slots.free_slots > 0 and self._head_admissible():
            req = self.scheduler.pop()
            if req is None:
                break
            if req.cancel_requested or req.future.cancelled():
                # abandoned while queued: terminal before ever taking a slot
                rz.advance(req, rz.CANCELLED, self.scheduler.counters,
                           reason=req.cancel_reason or "abandoned")
                if not req.future.done():
                    req.future.set_exception(rz.RequestCancelled(
                        f"request {req.rid} cancelled while queued "
                        f"({req.cancel_reason or 'abandoned'})"
                    ))
                continue
            try:
                self._prefill(req)
                admitted += 1
            except Exception as e:  # noqa: BLE001 — fail the one request
                if req.slot is not None:
                    self._by_slot.pop(req.slot, None)
                    self._rng.pop(req.slot, None)
                    self.slots.free(req.slot)
                    req.slot = None
                # a deadline that ran out DURING prefill is an expiry, not a
                # failure: no token was ever sampled, so both deadline
                # policies fail it with the TTL's own 503
                if isinstance(e, rz.DeadlineExceeded):
                    rz.advance(req, rz.EXPIRED, self.scheduler.counters,
                               where="prefill")
                else:
                    rz.advance(req, rz.FAILED, self.scheduler.counters,
                               reason=type(e).__name__)
                if not req.future.done():
                    req.future.set_exception(e)
        return admitted

    def _prefill(self, req: Request) -> None:
        """One admission, traced as it runs untraced: the loop thread sends the
        prompt's chunks and its first draw behind whatever the device is at and
        waits for nothing. Its spans (tracing off: no-op singletons):
        ``prefill_dispatch``, on this thread inside ``admit``: the admission's whole
        HOST cost (slot, buffers, the chunks' and the draw's dispatch), around one
        ``chunk_dispatch`` a chunk; and ``prefill``, the prompt's chunks ON THE
        DEVICE, a completion span on the track ``device`` (``Tracer.complete_span``:
        the tracer's worker waits, not this thread): from the ids that were in force
        when the admission began (the step in flight, or the admission before it;
        handed in HERE, so that the worker is waiting on them when they land) to
        the ids behind the prompt's draw (``_ids`` is donated to nothing, so the
        worker may hold both). The speculative engine reads the prompt's row on
        this thread in both loops: its span opens and closes on this thread's clock.
        They are per-request, so the fleet trace_id rides them (batch-wide
        sample/decode spans cover many requests and don't)."""
        attrs = {"rid": req.rid, "tokens": len(req.tokens)}
        if req.trace_id is not None:
            attrs["trace_id"] = req.trace_id
        with _obs_tracer.span("prefill_dispatch", **attrs) as span:
            on_device = _obs_tracer.complete_span(
                "prefill", after=self._ids if self._device_draw else None, **attrs)
            try:
                self._prefill_impl(req, span, on_device)
            except BaseException as e:
                on_device.close(error=type(e).__name__)
                raise

    def _prefill_impl(self, req: Request, span, on_device) -> None:
        t0 = time.perf_counter()
        slot = self.slots.alloc()
        assert slot is not None
        req.slot = slot
        req.admitted_at = time.time()
        # on a track of its own: the wait began before this iteration's spans
        _obs_tracer.record_span(
            "queue_wait", req.admitted_at - req.submitted_at,
            track="serving queue", rid=req.rid, depth=self.scheduler.depth)
        rz.advance(req, rz.PREFILLING, slot=slot)
        toks = np.asarray(req.tokens, np.int32)
        c = self.prefill_chunk
        smax = self.slots.max_seq_len
        matched = 0
        if self.paged:
            # attach the longest cached prefix read-only and reserve the
            # request's WORST-CASE block footprint up front (evicting cold
            # prefix blocks if needed) — decode never allocates, so it can
            # never fail on pool pressure mid-request
            matched = self.slots.attach_prefix(slot, req.tokens)
            self.slots.reserve(slot, len(toks) + req.max_new_tokens)
        starts = list(range(matched, len(toks), c))
        if starts and starts[-1] + c > smax:
            # the fixed-size window must not cross the slot end:
            # dynamic_update_slice would CLAMP the start index, silently
            # shifting the write over earlier positions. Slide the last
            # window left instead — re-prefilling the overlap recomputes
            # identical k/v (deterministic function of tokens + positions),
            # so the rewrite is idempotent.
            starts[-1] = smax - c
        span.set(chunks=len(starts), first_start=matched)
        router: Dict[str, jax.Array] = {}  # the last chunk's `_router_counters`
        full_chunks: list = []  # (tracer on) those of the chunks whose every row is real
        kernel = self.cache_layout.get("chunk_path") == "kernel"
        kind = self.cache_layout["kind"]  # "latent" | "kv": whose chunk kernel it is
        for start in starts:
            # the deadline is end-to-end: a long prompt must not burn chip
            # time prefilling past the moment its client stops waiting
            if req.deadline is not None and time.time() > req.deadline:
                raise rz.DeadlineExceeded(
                    f"request {req.rid} deadline passed during prefill "
                    f"({start}/{len(toks)} tokens in)"
                )
            seq = self.counters.get("prefill_chunks")  # which execution of the prefill program
            faults.prefill_chunk(seq)
            chunk = toks[start:start + c]
            n = len(chunk)
            with _obs_tracer.span("chunk_dispatch", start=start, rows=n, seq=seq):
                # fresh buffer per chunk: on CPU, jnp.asarray may alias the host
                # memory and dispatch is async — mutating a shared buffer for the
                # next chunk would corrupt the in-flight one's input
                buf = np.full((1, c), self.pad_id, np.int32)
                buf[0, :n] = chunk
                # the chunk's last real row goes into the slot's row of the device
                # rows inside the program; the final chunk's is the one that stays
                if self.paged:
                    # the slid-left window may dip below the attached prefix —
                    # COW-copy any shared/registered block the write covers
                    # (recomputed k/v is identical; this protects the CACHE
                    # entry and other holders, not this request's numerics)
                    self.slots.ensure_writable(slot, start, min(start + c, smax))
                    self._rows, self.slots.pool = _paged_prefill_chunk(
                        self.params, self.cfg, self.slots.pool, jnp.asarray(buf),
                        jnp.asarray(self.slots.tables[slot:slot + 1]),
                        jnp.asarray([start], np.int32),
                        self._rows, np.int32(slot), np.int32(n - 1),
                    )
                else:
                    self._rows, self.slots.cache, router = _prefill_chunk(
                        self.params, self.cfg, self.slots.cache, jnp.asarray(buf),
                        np.int32(slot), np.int32(start), self._rows, np.int32(n - 1),
                    )
                    if _obs_tracer.enabled and n == c:
                        full_chunks.append(router)
            self.counters.inc("prefill_chunks")
            self.counters.inc("prefill_tokens", n)
            if kernel:
                self.counters.inc(f"{kind}_chunks_kernel")
        self.slots.lengths[slot] = len(toks)
        if self.paged:
            # publish the prompt's full blocks while the request decodes, so
            # a same-prefix request admitted next iteration already shares
            self.slots.register_prefix(slot, req.tokens)
        self._by_slot[slot] = req
        if self._device_draw:
            # the first token, drawn from the prompt's last row where it lies; it is
            # booked with the others' (`_step_ahead`): nobody waits for the chunks here
            self._draw([slot], unbooked=0)
        else:
            # (the speculative engine draws on the host: this read is its loop's own wait)
            self._host_rows[slot] = np.asarray(self._rows)[slot]
            self._rng[slot] = np.random.default_rng((self.seed, req.rid))
        if _obs_tracer.enabled:
            # (drawing on the host, the read above saw the last chunk through: no ``done``)
            on_device.close(
                done=self._ids if self._device_draw else None,
                scalars={"last": router, "full": full_chunks},
                finish=partial(_prompt_expert_counts, chunk=c), **self._prompt_counts(starts))
        rz.advance(req, rz.DECODING, slot=slot)
        self._busy_s += time.perf_counter() - t0

    def _prompt_counts(self, starts: Sequence[int]) -> Dict[str, Any]:
        """What a ``prefill`` span says of its chunks, from where each began (host
        arithmetic: no array is built and nobody waits for the device): how many and
        how deep (``depth_sum``: the sum of their starts), the expert layers' row
        tile, and the key blocks the chunk attention fetched."""
        c = self.prefill_chunk
        out: Dict[str, Any] = {"chunks": len(starts), "depth_sum": sum(starts)}
        if self.cfg.moe_dropless:
            out["moe_row_tile"] = moe.layer_row_tile(self.cfg, c)
        ring = self.cache_layout.get("ring_positions")  # a windowed stack's, else None
        if ring:
            # chunks that began a new lap of the ring (from there on a chunk overwrites
            # the window layers' oldest positions), and the key blocks a window layer's
            # chunk attention fetched, of the ring's: up to the chunk's end until it lapped
            blocks = [generation.chunk_key_blocks(ring, start + c)[1:] for start in starts]
            out.update(
                ring_wraps=sum(start > 0 and start % ring == 0 for start in starts),
                kv_window_chunk_blocks_read=sum(min(whole, live) for whole, live in blocks),
                kv_window_chunk_blocks=sum(whole for whole, _ in blocks))
        key_block = self.cache_layout.get("chunk_key_block")  # None for a plain `KVCache`
        if key_block:
            # the chunks the chunk kernel took and the key blocks a layer's attention
            # over whole slots fetched for them
            kind = self.cache_layout["kind"]
            kernel = self.cache_layout.get("chunk_path") == "kernel"
            out[f"{kind}_chunks_kernel"] = len(starts) * kernel
            out[f"{kind}_chunk_key_blocks"] = sum(-(-(start + c) // key_block) for start in starts)
        return out

    def _step(self) -> None:
        """One decode iteration over the slots in use: ONE shared forward, every
        slot's next token drawn, every drawn token booked (``_book``).

        Drawing on the device (``_step_ahead``) the loop runs one step ahead of
        its bookkeeping: the forward is dispatched on the ids the device still
        holds, and the tokens booked are the ones drawn an iteration earlier.
        What is ON TIME: a row's offset, its draw's index and its LENGTH
        retirement, which are counts (booked + in flight) and need no token's
        value; a row's last token is never fed to a step. What is ONE ITERATION
        LATE: eos, cancel and deadline, seen when the token is booked. The row's
        step is then already dispatched and wasted (``row_steps_wasted``), its
        id discarded, and the slot freed by the bookkeeping that sees it; an
        admission into it is dispatched after that step in device order. The
        wasted write is harmless for every stack kind: K/V (whole slots or
        paged blocks) take it at the row's own next position of a slot that is
        retired and never attended again, a ring stack's lap starts over with
        the next prompt, and a state stack's row is reset by the next prompt's
        read at offset 0. ``finish_reason``, ``generated`` and ``token_times``
        are what a synchronous loop gives, one iteration later on the clock.

        The speculative engine draws on the host and builds its window from the
        tokens' values: it keeps the synchronous step (``_step_host``)."""
        t0 = time.perf_counter()
        # the chaos seam: engine_crash_at_iter raises here (the supervisor
        # must recover), slow_decode_ms stretches the iteration
        faults.engine_iteration(self.counters.get("steps"))
        step = self._step_ahead if self._device_draw else self._step_host
        sampled, appended, forward = step()
        self.counters.inc("steps")
        self.counters.inc("tokens_generated", appended)
        if self._guard_armed:
            self.assert_cache_bounded()
        dt = time.perf_counter() - t0
        self._busy_s += dt
        if forward:
            self.decode_step_hist.observe(dt)
        if dt > 0:
            self._last_step_tps = sampled / dt

    def _book(self, token_of, rows=None):
        """The host's bookkeeping of one drawn token a slot in use, under the
        ``sample`` span (one ``sample_slot`` a slot that draws): cancelled and
        over-deadline rows retire without it; the tap's row, the token
        (``token_of(slot, req)``), the request's times; eos and an exhausted
        budget retire the row. ``rows``: the host's copy of the rows the tokens
        were drawn from, where a slot taps. Returns (tokens drawn, tokens
        appended, [(slot, token)] of the rows that go on)."""
        sampled = appended = 0
        kept: List[tuple] = []
        retired: List[int] = []
        cancelled: List[int] = []
        expired: List[int] = []
        with _obs_tracer.span("sample", active=self.slots.active_count):
            for slot in self.slots.active_slots():
                req = self._by_slot[slot]
                now = time.time()
                if req.cancel_requested or req.future.cancelled():
                    # a dead client must not keep burning its KV slot: the
                    # disconnect poll set the flag, the slot frees HERE, at
                    # decode-iteration granularity
                    cancelled.append(slot)
                    continue
                if req.deadline is not None and now > req.deadline:
                    # end-to-end deadline at decode-step granularity: one
                    # 4096-token hog can no longer starve everything behind it
                    expired.append(slot)
                    continue
                with _obs_tracer.span("sample_slot", slot=slot, rid=req.rid,
                                      greedy=req.temperature <= 0):
                    if req.capture_logits is not None:
                        # the tap: the row token k was drawn from
                        k = len(req.generated)
                        req.capture_logits[k] = rows[slot]
                        req.logits_rows = k + 1
                    tok = token_of(slot, req)
                    sampled += 1
                    if req.first_token_at is None:
                        req.first_token_at = now
                        self.ttft.add(now - req.submitted_at)
                        self.ttft_hist.observe(now - req.submitted_at)
                    if self.eos_id >= 0 and tok == self.eos_id:
                        req.finish_reason = "eos"
                        retired.append(slot)
                        continue
                    req.generated.append(tok)
                    req.token_times.append(time.time())
                    appended += 1
                    if len(req.generated) >= req.max_new_tokens:
                        req.finish_reason = "length"
                        retired.append(slot)
                        continue
                    kept.append((slot, tok))
        for slot in retired:
            self._retire(slot)
        for slot in cancelled:
            self._retire_cancelled(slot)
        for slot in expired:
            self._retire_deadline(slot)
        return sampled, appended, kept

    def _step_ahead(self):
        """The device-draw iteration (the lag and why it is safe: ``_step``).
        Every slot in use has exactly ONE token drawn and not yet booked, in
        ``_ids`` on the device (behind the last step, or behind its prompt). The
        rows that have budget left after it are fed to the next step straight
        from there and their next draw follows it; only then the host waits for
        the tokens it owes the requests, and books them while the device runs
        the step. The span ``decode`` (one a dispatched step) is covered by
        ``decode_dispatch`` (host: operands, the two jitted calls' return),
        ``decode_wait`` (``Span.sync``, tracer on only, at the place where
        ``read()`` blocks with the tracer off: what the host really waits for, the
        PREVIOUS draws' ids; that step's counters are read with them) and
        ``logits_readback`` (the ids, and the rows of an iteration in which a slot
        taps); ``sample`` follows it. An admission's chunks and draw (``_prefill``)
        lie in the device's queue between the last step and this one, traced or
        not: the ids waited for here are behind them."""
        ids, rows = self._ids, self._rows  # what the tokens to book are, and were drawn from
        behind_a_step, router = self._step_unread, self._router_unread
        tapped = any(req.capture_logits is not None for req in self._by_slot.values())
        if tapped:
            # the whole buffer, no program; started now, it runs as soon as the device
            # is through (these rows are past every donation: the admissions are sent)
            rows.copy_to_host_async()
        nbytes = ids.nbytes + (rows.nbytes if tapped else 0)

        def read():
            return np.asarray(ids), np.asarray(rows) if tapped else None

        # the held token is not the row's last: a count, known without its value
        by = self._by_slot
        fed = [slot for slot in self.slots.active_slots()
               if len(by[slot].generated) + 1 < by[slot].max_new_tokens]
        self._step_unread, self._router_unread = bool(fed), {}
        if fed:
            with _obs_tracer.span("decode", active=len(fed)) as step_span:
                with _obs_tracer.span("decode_dispatch"):
                    self._dispatch_step(fed)
                    self.counters.inc("steps_ahead", int(behind_a_step))
                with _obs_tracer.span("decode_wait") as sp:
                    sp.sync(ids)
                    if _obs_tracer.enabled:
                        # the iteration's counters ride its span; the expert counters are
                        # the step's whose ids have just arrived (their copies were started
                        # with it: a few bytes, kept as host numbers for `stats`)
                        self._router_counters = {k: float(v) for k, v in router.items()}
                        step_span.set(
                            **self.step_counters(fed),
                            steps_ahead=self.counters.get("steps_ahead"),
                            row_steps_wasted=self.counters.get("row_steps_wasted"))
                with _obs_tracer.span("logits_readback", bytes=nbytes):
                    drawn, host_rows = read()
        else:
            # nothing to send (every row holds its last token): the read is the wait
            drawn, host_rows = read()

        drawn = drawn.tolist()
        self._booking = rows
        try:
            sampled, appended, kept = self._book(lambda slot, req: drawn[slot], host_rows)
        finally:
            self._booking = None
        self.counters.inc("draws_device", sampled)
        # a row that was fed and did not go on: eos, cancel or deadline, seen late
        self.counters.inc("row_steps_wasted", len(set(fed) - {slot for slot, _ in kept}))
        return sampled, appended, bool(fed)

    def _dispatch_step(self, fed: Sequence[int]) -> None:
        """Send the decode step for the rows ``fed`` and the draw behind it: the
        step's tokens are the device's ids as they are, a fed row's offset its
        slot's length, every other row's 0 (``_decode_step``). Nothing is waited for."""
        offsets = np.zeros((self.slots.num_slots,), np.int32)
        for slot in fed:
            offsets[slot] = self.slots.lengths[slot]
            self.slots.lengths[slot] += 1
        if self.paged:
            # a copy, of the fed rows' tables alone: the bookkeeping frees slots (and
            # edits ``slots.tables`` in place) while this step is still queued, and a
            # row that takes no step writes to the null block, not to position 0 of a
            # finished request's first block, which a cached prefix may share
            tables = np.zeros_like(self.slots.tables)
            for slot in fed:
                # provably a no-op for a plain step today (decode writes past every
                # registered/shared block), kept as a cheap COW invariant
                off = int(offsets[slot])
                self.slots.ensure_writable(slot, off, min(off + 1, self.slots.max_seq_len))
                tables[slot] = self.slots.tables[slot]
            logits, self.slots.pool = _paged_decode_step(
                self.params, self.cfg, self.slots.pool, self._ids,
                jnp.asarray(tables), jnp.asarray(offsets),
            )
        else:
            logits, self.slots.cache, self._router_unread = _decode_step(
                self.params, self.cfg, self.slots.cache, self._ids, jnp.asarray(offsets),
            )
            if _obs_tracer.enabled:
                for value in self._router_unread.values():  # (read with the step's ids)
                    value.copy_to_host_async()
        self._rows = logits
        self._draw(fed, unbooked=1)

    def _step_host(self):
        """The speculative engine's iteration, synchronous: every slot's token is
        drawn here on the host from the rows the last forward brought back,
        eos/budget-exhausted/cancelled/over-deadline rows retire, then ONE
        shared forward runs for the survivors (a verify window where a row has
        drafts) and its logits come to the host."""
        tokens = np.zeros((self.slots.num_slots,), np.int32)
        offsets = np.zeros((self.slots.num_slots,), np.int32)

        sampled, appended, kept = self._book(lambda slot, req: _sample_host(
            self._rng[slot], self._host_rows[slot], req.temperature, req.top_k, req.top_p))
        self.counters.inc("draws_host", sampled)
        for slot, tok in kept:
            tokens[slot] = tok
            offsets[slot] = self.slots.lengths[slot]
            self.slots.lengths[slot] += 1
        still = [slot for slot, _ in kept]
        drafts = self._build_drafts(still, offsets) if still else {}
        if still and drafts:
            appended += self._verify_step(still, tokens, offsets, drafts)
        elif still:
            logits = self._forward_step("decode", tokens, offsets, still)
            for slot in still:
                self._host_rows[slot] = logits[slot]
        return sampled, appended, bool(still)

    def _forward_step(self, name: str, tokens: np.ndarray, offsets: np.ndarray,
                      still: Sequence[int], **attrs) -> np.ndarray:
        """The speculative engine's shared forward over ALL slots: ``tokens``
        (B,) is the plain decode step (an iteration without a draft), (B, 1+k)
        the verify window. Its span ``name`` (``decode`` / ``decode_verify``) is
        covered by three children: ``decode_dispatch`` (host: operands to the
        device and the jitted call's return), ``decode_wait`` (the device:
        ``Span.sync`` blocks with the tracer on only, where ``np.asarray``
        blocked anyway) and ``logits_readback`` (the copy to the host,
        ``bytes``). Returns the logits on the host."""
        verify = tokens.ndim == 2
        with _obs_tracer.span(name, active=len(still), **attrs) as step_span:
            with _obs_tracer.span("decode_dispatch"):
                if self.paged:
                    smax = self.slots.max_seq_len
                    width = tokens.shape[1] if verify else 1
                    for slot in still:
                        # provably a no-op for a plain step today (decode writes
                        # past every registered/shared block), kept as a cheap
                        # COW invariant so a future sharing scheme cannot
                        # silently corrupt cached prefixes
                        off = int(offsets[slot])
                        self.slots.ensure_writable(slot, off, min(off + width, smax))
                    fn = _paged_decode_verify if verify else _paged_decode_step
                    logits, self.slots.pool = fn(
                        self.params, self.cfg, self.slots.pool, jnp.asarray(tokens),
                        jnp.asarray(self.slots.tables), jnp.asarray(offsets),
                    )
                else:
                    fn = _decode_verify if verify else _decode_step
                    logits, self.slots.cache, router = fn(
                        self.params, self.cfg, self.slots.cache,
                        jnp.asarray(tokens), jnp.asarray(offsets),
                    )
                    if _obs_tracer.enabled:
                        for value in router.values():  # (read inside decode_wait, below)
                            value.copy_to_host_async()
            # np.asarray is the engine's own readback sync (the next iteration
            # needs the logits on the host), so the span closes on realized
            # compute with the tracer off too
            with _obs_tracer.span("decode_wait") as sp:
                sp.sync(logits)
                if _obs_tracer.enabled:
                    # the iteration's counters ride its span (the device is through
                    # and their copies were started with the step: a few bytes, kept as
                    # host numbers for `stats`; inside this child so that the three
                    # still cover the forward)
                    if not self.paged:
                        self._router_counters = {k: float(v) for k, v in router.items()}
                    step_span.set(**self.step_counters(
                        still, tokens.shape[1] if verify else 1))
            with _obs_tracer.span("logits_readback", bytes=logits.nbytes):
                return np.asarray(logits)

    def _build_drafts(self, still, offsets) -> Dict[int, List[int]]:
        """Propose up to ``spec_k`` draft tokens per surviving slot from the
        prompt-lookup drafter. Returns {} — plain decode — when speculation
        is off, no row produced a draft (a wasted (1+k)-wide verify is pure
        overhead), or ANY surviving row lacks ``k+1`` positions of slot
        headroom: ``dynamic_update_slice`` CLAMPS an out-of-range window
        start, which would silently overwrite earlier cache positions (the
        same hazard the prefill slide-left handles), and the paged gather
        clamps table indices past ``max_seq_len`` the same way. Both the
        plain and verify programs are pinned and warm, so the per-iteration
        choice costs nothing."""
        if self.spec_k <= 0 or self.drafter is None:
            return {}
        k = self.spec_k
        smax = self.slots.max_seq_len
        drafts: Dict[int, List[int]] = {}
        for slot in still:
            if int(offsets[slot]) + 1 + k > smax:
                self.counters.inc("spec_fallbacks")
                return {}
            req = self._by_slot[slot]
            budget = req.max_new_tokens - len(req.generated)
            d = self.drafter.draft(
                list(req.tokens) + req.generated, min(k, budget)
            )
            if d:
                drafts[slot] = d
        return drafts

    def _verify_step(self, still, tokens, offsets,
                     drafts: Dict[int, List[int]]) -> int:
        """One speculative decode iteration: score every row's t0+drafts in
        a single (B, 1+k) forward, then run the rejection-sampling
        acceptance loop per row on host.

        Alignment: ``logits[slot, j]`` is the target distribution AFTER
        consuming window column j, so draft ``d[j]`` (window column j+1) is
        scored against ``logits[slot, j]``. On the first rejection the
        rejected token is struck (-inf) from the stored logits — the exact
        residual for a point-mass draft, and an argmax no-op under greedy
        (the rejected token was not the argmax by definition). On full
        acceptance ``logits[slot, len(d)]`` becomes the next iteration's
        sampling distribution. Rows without drafts ride along: their
        column-0 logits are exactly what the plain decode step would have
        produced."""
        k = self.spec_k
        batch = np.full((self.slots.num_slots, 1 + k), self.pad_id, np.int32)
        batch[:, 0] = tokens
        for slot, d in drafts.items():
            batch[slot, 1:1 + len(d)] = d
        logits = self._forward_step("decode_verify", batch, offsets, still, k=k)  # (B, 1+k, V)
        self.counters.inc("spec_steps")
        appended = 0
        retired: List[int] = []
        for slot in still:
            req = self._by_slot[slot]
            d = drafts.get(slot, [])
            L = logits[slot]
            accepted = 0
            rejected_at = -1
            finish = None
            for j, dt in enumerate(d):
                if req.temperature <= 0:
                    ok = int(np.argmax(L[j])) == dt
                else:
                    p = generation.host_probs(
                        L[j], req.temperature, req.top_k, req.top_p
                    )
                    ok = self._rng[slot].random() < p[dt]
                if not ok:
                    rejected_at = j
                    break
                accepted += 1
                if self.eos_id >= 0 and dt == self.eos_id:
                    # matches the sampling loop: eos retires WITHOUT being
                    # appended to the completion
                    finish = "eos"
                    break
                req.generated.append(dt)
                req.token_times.append(time.time())
                appended += 1
                if len(req.generated) >= req.max_new_tokens:
                    finish = "length"
                    break
            self.counters.inc("draft_proposed", len(d))
            self.counters.inc("draft_accepted", accepted)
            if finish is not None:
                req.finish_reason = finish
                retired.append(slot)
                continue
            # only appended tokens advance the row's KV length (the eos /
            # budget cases above never reach here); rejected-draft k/v past
            # the new length is dead weight the next window overwrites
            self.slots.lengths[slot] += accepted
            if rejected_at >= 0:
                resid = np.asarray(L[rejected_at], np.float32).copy()
                resid[d[rejected_at]] = -np.inf
                self._host_rows[slot] = resid
            else:
                self._host_rows[slot] = L[len(d)]
        for slot in retired:
            self._retire(slot)
        return appended

    def assert_cache_bounded(self) -> None:
        """Pin the DECLARED compiled-program set for the engine lifetime:
        the first call records the post-warmup baseline, later calls raise
        ``RecompileError`` on any growth (a static-arg or shape leak). Each
        backend pins its own prefill + decode pair, plus the sampler, or the
        decode_verify program when speculative decoding is on (that engine
        draws on the host) — the 2-program pin became a declared set, not an
        open one; the paged backend's COW block copy
        (one shape forever) compiles lazily at the first shared write, so
        it stays outside the guard."""
        from galvatron_tpu.analysis.guards import RecompileError, cache_sizes

        if self.paged:
            fns = [_paged_prefill_chunk, _paged_decode_step]
            verify = _paged_decode_verify
        else:
            fns = [_prefill_chunk, _decode_step]
            verify = _decode_verify
        fns.append(_sample_rows if self._device_draw else verify)
        sizes = cache_sizes(tuple(fns))
        if self._guard_baseline is None:
            # warmup isn't over until BOTH programs exist: a first step whose
            # requests all retire before the shared forward (1-token answers,
            # instant eos) never compiles _decode_step, and baselining its
            # count at 0 would make the next request's legitimate warmup
            # compile trip the guard and fail every in-flight request
            if all(v > 0 for v in sizes.values()):
                self._guard_baseline = sizes
            return
        grown = {
            k: (self._guard_baseline[k], v)
            for k, v in sizes.items()
            if v > self._guard_baseline[k]
        }
        if grown:
            # re-baseline BEFORE raising: one recompile reports once — a
            # stale baseline would otherwise fail every subsequent step
            # (and request) against growth that already happened
            self._guard_baseline = sizes
            detail = ", ".join(f"{k}: {a}→{b}" for k, (a, b) in grown.items())
            raise RecompileError(
                f"serving engine recompiled after warmup ({detail}): a "
                "static argument or shape is varying per request"
            )

    def _release_slot(self, slot: int) -> Request:
        req = self._by_slot.pop(slot)
        self._rng.pop(slot, None)
        self.slots.free(slot)
        return req

    def _retire(self, slot: int) -> None:
        req = self._release_slot(slot)
        self.latency_hist.observe(time.time() - req.submitted_at)
        rz.advance(req, rz.COMPLETED, self.scheduler.counters,
                   reason=req.finish_reason)
        if not req.future.done():
            req.future.set_result(list(req.tokens) + req.generated)

    def _retire_cancelled(self, slot: int) -> None:
        req = self._release_slot(slot)
        reason = req.cancel_reason or "cancelled"
        rz.advance(req, rz.CANCELLED, self.scheduler.counters,
                   reason=reason, generated=len(req.generated))
        if not req.future.done():
            req.future.set_exception(rz.RequestCancelled(
                f"request {req.rid} cancelled mid-decode ({reason})"
            ))

    def _retire_deadline(self, slot: int) -> None:
        """Over-deadline DECODING request: the slot frees either way; the
        engine's ``deadline_policy`` decides whether the client gets the
        partial text (``"truncated": "deadline"``) or a deadline failure."""
        req = self._release_slot(slot)
        req.finish_reason = "deadline"
        rz.advance(req, rz.EXPIRED, self.scheduler.counters,
                   where="decode", generated=len(req.generated),
                   policy=self.deadline_policy)
        if req.future.done():
            return
        if self.deadline_policy == "partial":
            req.future.set_result(list(req.tokens) + req.generated)
        else:
            req.future.set_exception(rz.DeadlineExceeded(
                f"request {req.rid} exceeded its deadline after "
                f"{len(req.generated)}/{req.max_new_tokens} tokens"
            ))

    def _fail_all(self, exc: Exception) -> None:
        for slot in list(self._by_slot):
            req = self._release_slot(slot)
            rz.advance(req, rz.FAILED, self.scheduler.counters,
                       reason=type(exc).__name__)
            if not req.future.done():
                req.future.set_exception(exc)
        self.slots.reset()
        self.scheduler.drain(exc)

    def _crash_cleanup(self, exc: BaseException,
                       retry_after_s: Optional[float] = None) -> None:
        """Crash recovery, step 1 (called by the supervisor): fail the
        in-flight requests fast — continuous batching cannot replay
        mid-decode KV state, and the failed dispatch may have invalidated
        the donated cache buffers — and keep only the queued requests that
        still have TTL budget. ``retry_after_s`` (the supervisor's backoff)
        rides the failure so the 503 can carry an honest Retry-After."""
        wrapped = rz.EngineRestarted(
            f"engine restarted mid-request ({type(exc).__name__}: {exc}); "
            "please resubmit",
            retry_after_s=retry_after_s,
        )
        for slot in list(self._by_slot):
            req = self._release_slot(slot)
            rz.advance(req, rz.FAILED, self.scheduler.counters,
                       reason="engine_crash")
            if not req.future.done():
                req.future.set_exception(wrapped)
        self.slots.reset()
        # the prefill programs donate the rows too: after a step that died
        # mid-call fresh ones are the only safe state
        self._rows = self._fresh_rows()
        self._host_rows[:] = 0.0
        # and with the rows goes what was drawn from them and not yet booked
        self._ids = self._fresh_ids()
        self._step_unread, self._router_unread = False, {}
        # queued requests were never admitted: they survive the restart —
        # minus the ones whose TTL budget the crash already consumed
        self.scheduler.expire()

    def _warm_rebuild(self) -> None:
        """Crash recovery, step 2: re-warm the pinned programs from the
        AOT artifact store (PR 9) so recovery costs cache-hit milliseconds,
        not a recompile. Best-effort — warmth is optional, serving is not."""
        if self._store is None:
            return
        try:
            from galvatron_tpu.aot import warmup as aot_warmup

            reports = self.warm_start(self._store, verbose=False)
            self.last_restart_warm = aot_warmup.summarize(reports)
        except Exception as e:  # noqa: BLE001 — recovery must not die warming
            _obs_tracer.instant("engine_warm_rebuild_failed", error=repr(e))

    def warm_start(self, store=None, verbose: bool = True) -> List[dict]:
        """AOT-compile the engine's pinned programs from abstract inputs
        (galvatron_tpu/aot): with the persistent compile cache enabled, a
        server restart's first request pays a cache deserialize instead of
        two XLA compiles.  Call before serving traffic (the jit calls happen
        on the caller's thread; the loop thread only ever sees warm
        programs).  Returns the per-program warmup reports."""
        from galvatron_tpu.aot import registry as aot_registry
        from galvatron_tpu.aot import warmup as aot_warmup

        # keep the store: crash recovery re-warms from it (_warm_rebuild),
        # so an engine restart is an artifact-store hit, not a recompile
        if store is not None:
            self._store = store
        ctx = aot_registry.ProgramContext(
            cfg=self.cfg, num_slots=self.slots.num_slots,
            prefill_chunk=self.prefill_chunk, max_seq_len=self.slots.max_seq_len,
            kv_block_size=self.slots.block_size if self.paged else 16,
            kv_num_blocks=self.slots.num_blocks if self.paged else 0,
            serve_quant=self.serve_quant, spec_decode_k=self.spec_k,
        )
        specs = aot_registry.enumerate_programs(ctx, include=("serving",))
        return aot_warmup.warmup_programs(
            specs, store, plan=None, model_cfg=self.cfg, verbose=verbose
        )


# --- AOT program registration (galvatron_tpu/aot): the serving family -------
# The engine's whole design is "a small declared program set for the
# lifetime" — which makes it the cheapest possible warm-start: every member
# is enumerable from (ModelConfig, num_slots, prefill_chunk, serve_quant,
# spec_decode_k) with no weights. int8 engines derive their params avals
# through quantize_params under eval_shape, so the quantized dtype lands in
# every program key (plus an explicit key_extra term) — a warm fp store can
# never satisfy an int8 engine, and crash recovery re-warms the right set.


def _serving_programs(ctx):
    cfg = ctx.cfg
    if not cfg.causal or cfg.objective != "clm" or getattr(cfg, "enc_layers", 0) > 0:
        return []  # same constraint as the Engine ctor
    from galvatron_tpu.aot.registry import ProgramSpec
    from galvatron_tpu.models import modeling

    params_abs = jax.eval_shape(
        lambda k: modeling.init_model_params(k, cfg), jax.random.key(0)
    )
    serve_quant = str(getattr(ctx, "serve_quant", "off") or "off")
    spec_k = int(getattr(ctx, "spec_decode_k", 0) or 0)
    if serve_quant == "int8":
        from galvatron_tpu.ops import quant as _quant

        params_abs = jax.eval_shape(
            lambda p: _quant.quantize_params(p, cfg), params_abs
        )
    key_extra = (
        {"serve_quant": serve_quant} if serve_quant != "off" else None
    )
    max_len = int(min(ctx.max_seq_len or cfg.max_seq_len, cfg.max_seq_len))
    num_slots = max(1, int(ctx.num_slots))
    chunk = min(max(1, int(ctx.prefill_chunk)), max_len)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    # the rows tokens are drawn from, and the draw itself: the same program on
    # both backends; the speculative engine draws on the host and has none
    rows_abs = jax.ShapeDtypeStruct((num_slots, cfg.vocab_size), jnp.dtype(cfg.dtype))
    draw = [] if spec_k > 0 else [ProgramSpec(
        "serving_sample", _sample_rows,
        (rows_abs, jax.ShapeDtypeStruct((2, num_slots), jnp.float32),
         jax.ShapeDtypeStruct((6, num_slots), jnp.uint32), i32(num_slots)),
        # (no weight enters it, but an int8 engine warms a set of its own)
        meta={"num_slots": num_slots, **({"key_extra": key_extra} if key_extra else {})},
    )]
    kv_num_blocks = int(getattr(ctx, "kv_num_blocks", 0) or 0)
    if kv_num_blocks:
        # paged backend: the pool/table shapes are fully determined by
        # (block_size, num_blocks, max_len), so a warm restart re-hits the
        # same artifacts regardless of the allocator's runtime state
        block_size = max(1, int(ctx.kv_block_size))
        max_blocks = -(-max_len // block_size)
        if kv_num_blocks == -1:
            kv_num_blocks = num_slots * max_blocks + 1
        pool_abs = jax.eval_shape(
            lambda: generation.init_kv_cache(cfg, kv_num_blocks, block_size)
        )
        paged_meta = {"kv_block_size": block_size,
                      "kv_num_blocks": kv_num_blocks}
        if key_extra:
            paged_meta["key_extra"] = key_extra
        out = [
            ProgramSpec(
                "serving_paged_prefill", _paged_prefill_chunk,
                (params_abs, cfg, pool_abs, i32(1, chunk), i32(1, max_blocks),
                 i32(1), rows_abs, i32(), i32()),
                meta={"donate": ("pool", "rows"), "num_slots": num_slots,
                      "prefill_chunk": chunk, **paged_meta},
            ),
            ProgramSpec(
                "serving_paged_decode", _paged_decode_step,
                (params_abs, cfg, pool_abs, i32(num_slots),
                 i32(num_slots, max_blocks), i32(num_slots)),
                meta={"donate": ("pool",), "num_slots": num_slots,
                      **paged_meta},
            ),
        ]
        if spec_k > 0:
            out.append(ProgramSpec(
                "serving_paged_decode_verify", _paged_decode_verify,
                (params_abs, cfg, pool_abs, i32(num_slots, 1 + spec_k),
                 i32(num_slots, max_blocks), i32(num_slots)),
                meta={"donate": ("pool",), "num_slots": num_slots,
                      "spec_decode_k": spec_k, **paged_meta},
            ))
        return out + draw
    cache_abs = jax.eval_shape(
        # (a windowed stack's ring is sized as `Engine` sizes it: chunk or verify window)
        lambda: generation.init_kv_cache(cfg, num_slots, max_len, max(chunk, 1 + spec_k))
    )
    slot_meta = {"key_extra": key_extra} if key_extra else {}
    out = [
        ProgramSpec(
            "serving_prefill", _prefill_chunk,
            (params_abs, cfg, cache_abs, i32(1, chunk), i32(), i32(), rows_abs, i32()),
            meta={"donate": ("cache", "rows"), "num_slots": num_slots,
                  "prefill_chunk": chunk, **slot_meta},
        ),
        ProgramSpec(
            "serving_decode", _decode_step,
            (params_abs, cfg, cache_abs, i32(num_slots), i32(num_slots)),
            meta={"donate": ("cache",), "num_slots": num_slots, **slot_meta},
        ),
    ]
    if spec_k > 0:
        # the verify program's key carries k via the (B, 1+k) token aval —
        # sweeping --spec_decode_k at warmup warms each k separately
        out.append(ProgramSpec(
            "serving_decode_verify", _decode_verify,
            (params_abs, cfg, cache_abs, i32(num_slots, 1 + spec_k),
             i32(num_slots)),
            meta={"donate": ("cache",), "num_slots": num_slots,
                  "spec_decode_k": spec_k, **slot_meta},
        ))
    return out + draw


def _register_aot_programs():
    from galvatron_tpu.aot.registry import register_program

    register_program(
        "serving", _serving_programs,
        programs=("serving_prefill", "serving_decode",
                  "serving_decode_verify", "serving_sample",
                  "serving_paged_prefill", "serving_paged_decode",
                  "serving_paged_decode_verify"),
    )


_register_aot_programs()
