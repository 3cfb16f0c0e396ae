"""Continuous-batching serving subsystem.

Four layers, one per module:

- [[kv_slots]] ``SlotKVCache`` — persistent fixed-shape device KV cache,
  host-side slot allocator (per-slot offset/length, alloc/free/reset,
  invariant ``audit``).
- [[paged_kv]] ``PagedKVCache`` — block-granular alternative backend
  (``--kv_num_blocks``): fixed device block pool + per-request block
  tables, refcounted copy-on-write prefix sharing keyed by token-chunk
  hash, LRU eviction of cold prefix blocks, block-headroom admission
  (``NoFreeBlocks`` is its can't-happen-in-the-engine exhaustion error).
- [[scheduler]] ``Scheduler`` — FIFO admission queue with per-request TTL,
  bounded depth (``QueueFull``), expiry (``RequestExpired``), shed-on-drain,
  counters.
- [[resilience]] — the request lifecycle state machine (QUEUED →
  PREFILLING → DECODING → {COMPLETED, FAILED, EXPIRED, CANCELLED, SHED}),
  the in-process ``EngineSupervisor`` crash-restart decision table, and the
  exceptions the server maps to HTTP (``EngineDraining``/``EngineClosed``/
  ``EngineRestarted``/``RequestShed``/``RequestCancelled``/
  ``DeadlineExceeded``).
- [[engine]] ``Engine`` — the loop: one jitted decode step over all slots
  per iteration, chunked prefill on admission, every slot's next token
  drawn on the device by one batched program behind the step (per-request
  parameters as data; the speculative engine draws on the host),
  retire-on-eos/budget/deadline/cancel, graceful ``drain`` with
  a post-drain zero-leak ``audit``.  Optional numerics/speed levers:
  per-channel int8 weights (``--serve_quant int8``, ops/quant.py) and
  speculative decoding with the [[speculative]] prompt-lookup drafter
  (``--spec_decode_k``) — both program-key terms the AOT warmup must see.
- [[speculative]] ``PromptLookupDrafter`` — checkpoint-free n-gram
  drafter + the exactness contract for draft verification (greedy output
  is bit-identical to plain decode; sampling keeps the distribution via
  rejection sampling).
- [[fleet]] ``FleetRouter`` — the horizontal layer (``cli serve-fleet``):
  N engine replicas behind one router with health-driven dispatch
  (STARTING → READY → DRAINING → DEAD), mid-flight failover inside the
  end-to-end deadline, supervised replica restarts, and rolling drain.
  Imported lazily (it spawns subprocesses; most serving users never
  need it): ``from galvatron_tpu.serving.fleet import FleetRouter``.

``server.GenerationService`` submits into the engine via futures; the
legacy serialized ``generate_np`` path remains available when the engine is
disabled (``--num_slots 0``).
"""

from galvatron_tpu.serving.engine import Engine
from galvatron_tpu.serving.kv_slots import SlotKVCache
from galvatron_tpu.serving.paged_kv import NoFreeBlocks, PagedKVCache
from galvatron_tpu.serving.resilience import (
    DeadlineExceeded,
    EngineClosed,
    EngineDraining,
    EngineRestarted,
    EngineSupervisor,
    RequestCancelled,
    RequestShed,
)
from galvatron_tpu.serving.scheduler import (
    QueueFull,
    Request,
    RequestExpired,
    Scheduler,
)
from galvatron_tpu.serving.speculative import PromptLookupDrafter, make_drafter

__all__ = [
    "Engine",
    "PromptLookupDrafter",
    "make_drafter",
    "SlotKVCache",
    "PagedKVCache",
    "NoFreeBlocks",
    "Scheduler",
    "Request",
    "QueueFull",
    "RequestExpired",
    "RequestShed",
    "RequestCancelled",
    "DeadlineExceeded",
    "EngineDraining",
    "EngineClosed",
    "EngineRestarted",
    "EngineSupervisor",
]
