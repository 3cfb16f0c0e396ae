"""Slot-managed persistent KV cache for the continuous-batching engine.

One fixed-shape device cache — what the stack's layers say
(``generation.init_kv_cache``): ``(L, num_slots, max_seq_len, kv_heads,
head_dim)`` k and v for attention, one ``(L, num_slots, max_seq_len, r + dr)``
latent for latent attention, stacks by what a layer keeps where the layers differ
(``generation.SlotStacks``: the full layers at ``max_seq_len``, sliding-window layers
a ring of ``window + tokens`` positions, layers with a per-row state ``(L_state,
num_slots, ...)``) — lives for the whole server lifetime; requests borrow a
*slot* (one batch row) for their duration and return it on retirement
(vLLM's PagedAttention manages blocks within a sequence; here the unit is
the whole-sequence slot, which is what maps onto JAX's static-shape jit:
every decode step sees the same array shapes, so the compiled program is
reused forever — no per-request allocation, no recompiles).

Host side this class is a tiny allocator: a free list plus per-slot
offset/length bookkeeping. Device side it owns the ``KVCache`` pytree that
the engine threads through its jitted prefill/decode calls. Slots are NOT
zeroed on reuse — a new request's prefill writes positions ``[0, P)`` before
any query can see them, and causal masking hides every position beyond a
row's own write offset, so stale keys from the previous occupant are never
attended. A layer's per-row STATE is not zeroed either: the forward that starts a
request at position 0 reads zeros in its place (``mixers``' ``cached_block``), so
neither the previous occupant nor an idle row's decode steps reach the new request.
``reset`` rebuilds every stack, the state among them.
"""

from __future__ import annotations

import warnings
from typing import List, Optional

import numpy as np

from galvatron_tpu.analysis.locks import make_lock
from galvatron_tpu.models import generation
from galvatron_tpu.models.modeling import ModelConfig


def effective_max_seq_len(cfg: ModelConfig, max_seq_len: Optional[int]) -> int:
    """Clamp a caller-requested per-request capacity to the model's trained
    ``cfg.max_seq_len`` — rope tables and position embeddings don't extend
    past it. A request above the model bound used to be clamped *silently*,
    which made ``--max_seq_len 8192`` on a 2k model look honoured while every
    long request was rejected at admission; now the mismatch warns and the
    effective value is surfaced through ``Engine.stats()`` → /healthz."""
    if max_seq_len is None:
        return int(cfg.max_seq_len)
    requested = int(max_seq_len)
    if requested > cfg.max_seq_len:
        warnings.warn(
            f"requested max_seq_len={requested} exceeds model cfg.max_seq_len="
            f"{cfg.max_seq_len}; clamping — the replica serves at most "
            f"{cfg.max_seq_len} tokens per request (see max_seq_len_effective "
            "in /healthz); --seq_length sets the model's bound",
            RuntimeWarning,
            stacklevel=3,
        )
        return int(cfg.max_seq_len)
    return requested


class SlotKVCache:
    """Fixed ``(num_slots, max_seq_len)`` cache of the stack's kind + slot allocator."""

    def __init__(self, cfg: ModelConfig, num_slots: int, max_seq_len: Optional[int] = None,
                 tokens: int = 1):
        """``tokens``: the most positions one forward writes a slot (the engine's
        prompt chunk or verify window): what a windowed stack's ring is sized by
        (``generation.ring_positions``); nothing else reads it, and admission,
        ``fits`` and the scheduler know slots of ``max_seq_len`` only."""
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.cfg = cfg
        self.num_slots = int(num_slots)
        self.max_seq_len = effective_max_seq_len(cfg, max_seq_len)
        self.tokens = min(max(1, int(tokens)), self.max_seq_len)
        # device arrays; reassigned by the engine after every jitted step
        self.cache = generation.init_kv_cache(cfg, self.num_slots, self.max_seq_len, self.tokens)
        # host bookkeeping: length = tokens materialized in the slot so far
        # (prompt + generated); the next token lands at position == length.
        # The allocator lock covers the free list + active set: the engine
        # loop allocates/frees while handler threads read the occupancy
        # views through stats()/healthz
        self._lock = make_lock("kv_slots")
        self.lengths = np.zeros((self.num_slots,), np.int32)
        self._free: List[int] = list(range(self.num_slots - 1, -1, -1))  # guarded-by: self._lock
        self._active: set = set()  # guarded-by: self._lock

    # -- allocator ----------------------------------------------------------

    def alloc(self) -> Optional[int]:
        """Claim a free slot (length reset to 0); None when fully occupied."""
        with self._lock:
            if not self._free:
                return None
            slot = self._free.pop()
            self._active.add(slot)
            self.lengths[slot] = 0
            return slot

    def free(self, slot: int) -> None:
        with self._lock:
            if slot not in self._active:
                raise ValueError(f"slot {slot} is not active")
            self._active.discard(slot)
            self.lengths[slot] = 0
            self._free.append(slot)

    def reset(self) -> None:
        """Release every slot and reallocate the device cache (engine
        failure recovery / drain). The engine's jitted steps DONATE the
        cache buffers — after a step that died mid-call the old arrays may
        already be invalidated, so a fresh cache is the only safe state.
        The old cache is let go of BEFORE the new one is built: two do not fit
        a chip beside the weights at a deployment's size (16 slots x 2048 of
        opt-1.3b: 2 x 6 GiB), and a caller may have dropped it already."""
        with self._lock:
            self._active.clear()
            self.lengths[:] = 0
            self._free = list(range(self.num_slots - 1, -1, -1))
            self.cache = None
            self.cache = generation.init_kv_cache(self.cfg, self.num_slots, self.max_seq_len,
                                                  self.tokens)

    # -- views --------------------------------------------------------------

    @property
    def free_slots(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def active_count(self) -> int:
        with self._lock:
            return len(self._active)

    def active_slots(self) -> List[int]:
        with self._lock:
            return sorted(self._active)

    @property
    def occupancy(self) -> float:
        with self._lock:
            return len(self._active) / self.num_slots

    def fits(self, prompt_len: int, max_new_tokens: int) -> bool:
        """Whole lifetime of the request stays inside the slot: the last
        generated token sits at position prompt_len + max_new_tokens - 1."""
        return prompt_len >= 1 and prompt_len + max_new_tokens <= self.max_seq_len

    def audit(self) -> dict:
        """Allocator invariant check (the drain/chaos harness's zero-leak
        proof): the free list and the active set partition the slot range
        exactly — no double-frees, no leaks, no phantom slots."""
        with self._lock:
            free_set = set(self._free)
            ok = (
                len(free_set) == len(self._free)          # no duplicate frees
                and not (free_set & self._active)         # disjoint
                and (free_set | self._active) == set(range(self.num_slots))
            )
            return {
                "ok": ok,
                "free": len(self._free),
                "active": len(self._active),
                "num_slots": self.num_slots,
            }
