"""Paged-KV serving subsystem (serving/paged_kv.py + the engine's paged
backend): block allocator + COW prefix sharing fuzzed against a pure-Python
reference, bit-exact engine parity (shared prefixes and the slide-left COW
window included), the paged flash-decode op, the max_seq_len clamp warning,
metric exposition, and the DESIGN.md state-machine doc sync."""

import os
import re
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.models import generation
from galvatron_tpu.models.modeling import ModelConfig
from galvatron_tpu.ops import flash_attention as fa
from galvatron_tpu.serving import Engine, NoFreeBlocks, PagedKVCache
from galvatron_tpu.serving.kv_slots import SlotKVCache, effective_max_seq_len
from galvatron_tpu.serving.paged_kv import BLOCK_STATES, NULL_BLOCK, prefix_hashes

from tests._serving_common import CFG, params, prompts as _prompts  # noqa: F401  (`params`: a fixture)

TINY = ModelConfig(
    vocab_size=64,
    hidden_size=32,
    num_layers=1,
    num_heads=2,
    ffn_dim=64,
    max_seq_len=32,
    dtype=jnp.float32,
)


# ---------------------------------------------------------------------------
# allocator + COW semantics
# ---------------------------------------------------------------------------


def test_pool_shape_and_null_block():
    cache = PagedKVCache(TINY, num_slots=2, block_size=4, num_blocks=10)
    # (L, num_blocks, block_size, kv_heads, head_dim): the slot layout with
    # batch=num_blocks, len=block_size
    assert cache.pool.k.shape == (1, 10, 4, 2, 16)
    assert cache.blocks_total == 9  # block 0 is the reserved null block
    s = cache.alloc()
    cache.reserve(s, 32)  # whole sequence
    assert cache.blocks_held(s) == 8
    assert NULL_BLOCK not in cache._slot_blocks[s]
    a = cache.audit()
    assert a["ok"] and a["blocks_ok"], a


def test_pool_must_hold_one_max_length_request():
    with pytest.raises(ValueError, match="cannot hold"):
        PagedKVCache(TINY, num_slots=1, block_size=4, num_blocks=8)


def test_double_free_raises_and_blocks_return():
    cache = PagedKVCache(TINY, num_slots=2, block_size=4, num_blocks=10,
                         prefix_cache=False)
    s = cache.alloc()
    cache.append(s, 10)  # 3 blocks
    assert cache.blocks_free == 6 and cache.blocks_active == 3
    cache.free(s)
    assert cache.blocks_free == 9 and cache.blocks_active == 0
    with pytest.raises(ValueError, match="not active"):
        cache.free(s)


def test_fork_shares_then_cow_diverges():
    cache = PagedKVCache(TINY, num_slots=3, block_size=4, num_blocks=12,
                         prefix_cache=False)
    a = cache.alloc()
    cache.append(a, 8)  # 2 full blocks
    b = cache.fork(a)
    assert cache.blocks_active == 2  # shared, zero copies
    assert list(cache.tables[b, :2]) == list(cache.tables[a, :2])
    # writing into the shared second block on the fork COWs exactly it
    cache.append(b, 1)  # positions [8,9): allocates block 2 for b only
    cache.ensure_writable(b, 7, 8)
    assert cache.cow_copies == 1
    assert cache.tables[b, 1] != cache.tables[a, 1]
    assert cache.tables[b, 0] == cache.tables[a, 0]  # untouched block stays shared
    a_audit = cache.audit()
    assert a_audit["ok"] and a_audit["blocks_ok"], a_audit


def test_prefix_attach_register_and_lru_eviction():
    cache = PagedKVCache(TINY, num_slots=4, block_size=4, num_blocks=12)
    toks = list(range(1, 11))  # 10 tokens: 2 full blocks registerable
    s = cache.alloc()
    assert cache.attach_prefix(s, toks) == 0  # registry empty: full miss
    cache.lengths[s] = 0
    cache.append(s, len(toks))
    assert cache.register_prefix(s, toks) == 2
    cache.free(s)
    assert cache.blocks_cached == 2  # rc-0 registered blocks wait in the LRU
    # an identical prompt attaches both full blocks ((len-1)//bs caps the
    # match so the last token always re-prefills)
    s2 = cache.alloc()
    matched = cache.attach_prefix(s2, toks)
    assert matched == 8 and cache.blocks_held(s2) == 2
    assert cache.prefix_hits == 2 and cache.blocks_cached == 0
    cache.lengths[s2] = matched
    cache.append(s2, len(toks) - matched)
    cache.free(s2)
    assert cache.blocks_cached == 2
    # saturate the pool with an unrelated request: the free list dries up
    # and allocation evicts the LRU'd prefix blocks instead of failing
    s3 = cache.alloc()
    cache.append(s3, 32)  # needs 8 of 9 remaining free
    s4 = cache.alloc()
    cache.append(s4, 8)  # needs 2: 1 free + 1 evicted
    assert cache.prefix_evictions == 1 and cache.blocks_cached == 1
    cache.append(s4, 4)  # one more block: evicts the second
    assert cache.prefix_evictions == 2 and cache.blocks_cached == 0
    with pytest.raises(NoFreeBlocks):
        cache.append(s4, 4)  # nothing free, nothing evictable
    a = cache.audit()
    assert a["ok"] and a["blocks_ok"], a


def test_prefix_hash_chain_is_cumulative():
    # a match at block i implies blocks [0, i] all match: changing ANY
    # earlier token changes every later chunk hash
    h1 = prefix_hashes([1, 2, 3, 4, 5, 6, 7, 8], 4)
    h2 = prefix_hashes([9, 2, 3, 4, 5, 6, 7, 8], 4)
    assert len(h1) == 2
    assert h1[0] != h2[0] and h1[1] != h2[1]


def test_can_admit_counts_cached_as_headroom():
    cache = PagedKVCache(TINY, num_slots=3, block_size=4, num_blocks=10)
    toks = list(range(1, 9))
    s = cache.alloc()
    cache.append(s, 8)
    cache.register_prefix(s, toks)
    cache.free(s)
    assert cache.blocks_free == 7 and cache.blocks_cached == 2
    # 9 usable blocks, 2 CACHED: a 32-token request needs 8 — admissible
    # only because eviction can reclaim the cached pair
    assert cache.can_admit(list(range(40, 64)), 8, chunk=8)
    s2 = cache.alloc()
    cache.reserve(s2, 32)
    assert cache.prefix_evictions >= 1
    # now the pool is pinned: nothing fits
    assert not cache.can_admit([1, 2, 3], 8)


def test_cow_overlap_blocks_reserves_slide_left_spare():
    cache = PagedKVCache(TINY, num_slots=2, block_size=4, num_blocks=20)
    # prompt+chunk within capacity: the last window never slides
    assert cache.cow_overlap_blocks(16, 20, 8) == 0
    # slides left to start=24, below a 28-token match: blocks [6,7) dirty
    assert cache.cow_overlap_blocks(28, 30, 8) == 1
    # window floor beyond the match: nothing shared gets rewritten
    assert cache.cow_overlap_blocks(16, 30, 8) == 0


# ---------------------------------------------------------------------------
# randomized fuzz vs a pure-Python reference allocator
# ---------------------------------------------------------------------------


class _RefBlock:
    __slots__ = ("rc", "hash")

    def __init__(self):
        self.rc = 0
        self.hash = None


class _RefPaged:
    """Object-identity reference model of PagedKVCache's allocator: same
    ops, same raise points, no indices and no device pool — the fuzz
    compares aggregate observables after every operation."""

    def __init__(self, num_slots, block_size, num_blocks, max_seq_len):
        self.bs = block_size
        self.max_seq_len = max_seq_len
        self.max_blocks = -(-max_seq_len // block_size)
        self.num_slots = num_slots
        self.free = num_blocks - 1
        self.lru = []  # CACHED blocks in eviction order
        self.registry = {}
        self.slots = {}
        self.lengths = {}
        self.free_slot_ids = list(range(num_slots - 1, -1, -1))
        self.hits = self.misses = self.evictions = self.cow = 0

    # -- block core (mirrors _take_block/_unref/_claim_cached) ----------------
    def _take(self):
        if self.free:
            self.free -= 1
            return _RefBlock()
        if self.lru:
            b = self.lru.pop(0)
            del self.registry[b.hash]
            b.hash = None
            self.evictions += 1
            return b
        raise NoFreeBlocks("ref pool exhausted")

    def _unref(self, b):
        assert b.rc > 0, "refcount underflow"
        b.rc -= 1
        if b.rc == 0:
            if b.hash is not None:
                self.lru.append(b)
            else:
                self.free += 1

    # -- surface --------------------------------------------------------------
    def alloc(self):
        if not self.free_slot_ids:
            return None
        s = self.free_slot_ids.pop()
        self.slots[s] = []
        self.lengths[s] = 0
        return s

    def free_slot(self, s):
        assert s in self.slots
        for b in self.slots.pop(s):
            self._unref(b)
        del self.lengths[s]
        self.free_slot_ids.append(s)

    def append(self, s, n):
        lo = self.lengths[s]
        hi = lo + n
        if hi > self.max_seq_len:
            raise ValueError("overflow")
        need = -(-hi // self.bs)
        blocks = self.slots[s]
        while len(blocks) < need:  # reserve, one block at a time
            b = self._take()
            b.rc = 1
            blocks.append(b)
        for i in range(lo // self.bs, min(-(-hi // self.bs), len(blocks))):
            b = blocks[i]
            if b.rc == 1 and b.hash is None:
                continue
            nb = self._take()
            nb.rc = 1
            self._unref(b)
            blocks[i] = nb
            self.cow += 1
        self.lengths[s] = hi

    def fork(self, src):
        s = self.alloc()
        if s is None:
            return None
        for b in self.slots[src]:
            b.rc += 1
        self.slots[s] = list(self.slots[src])
        self.lengths[s] = self.lengths[src]
        return s

    def attach(self, s, toks):
        cap = (len(toks) - 1) // self.bs
        hashes = prefix_hashes(toks[: cap * self.bs], self.bs)
        matched = 0
        for h in hashes:
            if h not in self.registry:
                break
            matched += 1
        assert not self.slots[s]
        for h in hashes[:matched]:
            b = self.registry[h]
            if b.rc == 0:
                self.lru.remove(b)
            b.rc += 1
            self.slots[s].append(b)
        self.hits += matched
        self.misses += cap - matched
        return matched * self.bs

    def register(self, s, toks):
        cap = len(toks) // self.bs
        for i, h in enumerate(prefix_hashes(toks[: cap * self.bs], self.bs)):
            if h in self.registry:
                continue
            b = self.slots[s][i]
            if b.hash is not None:
                continue
            b.hash = h
            self.registry[h] = b

    def reset(self, num_blocks):
        counters = self.hits, self.misses, self.evictions, self.cow
        self.__init__(self.num_slots, self.bs, num_blocks, self.max_seq_len)
        # counters are lifetime totals: they survive reset on the real side
        self.hits, self.misses, self.evictions, self.cow = counters


def test_paged_allocator_randomized_fuzz():
    """Property-style fuzz over PagedKVCache vs the reference: identical op
    stream, identical raise points, and after every op the two agree on the
    free/cached/active block partition, per-slot footprints, lengths, and
    the prefix/COW counters — while audit() holds throughout."""
    rng = np.random.RandomState(42)
    NB, BS, NS, MSL = 16, 4, 4, 32
    cache = PagedKVCache(TINY, num_slots=NS, block_size=BS, num_blocks=NB)
    ref = _RefPaged(NS, BS, NB, MSL)
    # three prompt families: shared prefixes occur naturally within a family
    fams = [[(f * 17 + j) % 50 + 1 for j in range(28)] for f in range(3)]

    def both(fn_real, fn_ref):
        """Run the op on both sides; raise points must coincide."""
        err = None
        try:
            r1 = fn_real()
        except (NoFreeBlocks, ValueError) as e:
            r1, err = None, type(e)
        try:
            r2 = fn_ref()
        except (NoFreeBlocks, ValueError) as e:
            assert err is type(e), f"raise mismatch: real={err}, ref={type(e)}"
            return None, True
        assert err is None, f"only the real allocator raised: {err}"
        return (r1, r2), False

    for op in range(400):
        r = rng.rand()
        if r < 0.35:  # admit with prefix attach (the engine's flow)
            toks = fams[rng.randint(3)][: rng.randint(2, 28)]
            s = cache.alloc()
            rs = ref.alloc()
            assert (s is None) == (rs is None)
            if s is not None:
                assert s == rs  # same free-slot stack discipline
                m1 = cache.attach_prefix(s, toks)
                m2 = ref.attach(rs, toks)
                assert m1 == m2, (op, m1, m2)
                cache.lengths[s] = m1
                ref.lengths[rs] = m2
                _, failed = both(
                    lambda: cache.append(s, len(toks) - m1),
                    lambda: ref.append(rs, len(toks) - m2),
                )
                if failed:  # admission would have gated this: back out
                    cache.free(s)
                    ref.free_slot(rs)
                else:
                    cache.register_prefix(s, toks)
                    ref.register(rs, toks)
        elif r < 0.6:  # free (and double-free must raise)
            if cache.active_slots():
                s = cache.active_slots()[rng.randint(cache.active_count)]
                cache.free(s)
                ref.free_slot(s)
                with pytest.raises(ValueError):
                    cache.free(s)
            else:
                with pytest.raises(ValueError):
                    cache.free(int(rng.randint(NS)))
        elif r < 0.75:  # decode growth (COW under the hood when shared)
            if cache.active_slots():
                s = cache.active_slots()[rng.randint(cache.active_count)]
                n = int(rng.randint(1, 5))
                both(lambda: cache.append(s, n), lambda: ref.append(s, n))
        elif r < 0.9:  # fork (pure refcount sharing)
            if cache.active_slots():
                s = cache.active_slots()[rng.randint(cache.active_count)]
                f1 = cache.fork(s)
                f2 = ref.fork(s)
                assert f1 == f2
        else:
            cache.reset()
            ref.reset(NB)
        # -- lockstep observables ------------------------------------------
        assert cache.blocks_free == ref.free, op
        assert cache.blocks_cached == len(ref.lru), op
        assert cache.active_slots() == sorted(ref.slots), op
        for s in cache.active_slots():
            assert cache.blocks_held(s) == len(ref.slots[s]), (op, s)
            assert int(cache.lengths[s]) == ref.lengths[s], (op, s)
        assert cache.prefix_hits == ref.hits, op
        assert cache.prefix_misses == ref.misses, op
        assert cache.prefix_evictions == ref.evictions, op
        assert cache.cow_copies == ref.cow, op
        assert (cache._refcount >= 0).all()
        a = cache.audit()
        assert a["ok"] and a["blocks_ok"], (op, a)


# ---------------------------------------------------------------------------
# paged flash-decode op
# ---------------------------------------------------------------------------


def test_paged_decode_xla_bitwise_matches_contiguous():
    """The gather path reduces to decode_attention over the flattened pages
    — bitwise, which is what makes engine parity an identity, not a
    tolerance."""
    rng = np.random.RandomState(0)
    B, mb, bs, kvh, g, d = 3, 4, 8, 2, 2, 16
    npages = 1 + B * mb
    q = jnp.asarray(rng.randn(B, 1, kvh * g, d), jnp.float32)
    k_pages = jnp.asarray(rng.randn(npages, bs, kvh, d), jnp.float32)
    v_pages = jnp.asarray(rng.randn(npages, bs, kvh, d), jnp.float32)
    perm = rng.permutation(npages - 1)[: B * mb] + 1
    tables = jnp.asarray(perm.reshape(B, mb), jnp.int32)
    offs = jnp.asarray([5, 17, 31], jnp.int32)
    out = fa.paged_decode_attention(q, k_pages, v_pages, tables, offs,
                                    impl="xla")
    flat_k = k_pages[tables].reshape(B, mb * bs, kvh, d)
    flat_v = v_pages[tables].reshape(B, mb * bs, kvh, d)
    ref = fa.decode_attention(q, flat_k, flat_v, q_offset=offs)
    assert (np.asarray(out) == np.asarray(ref)).all()


def test_paged_decode_pallas_matches_xla():
    """The Pallas grid kernel (interpret mode off-TPU) agrees with the XLA
    gather path, including rows whose tables repeat blocks and rows masked
    far short of their reserved capacity."""
    rng = np.random.RandomState(1)
    B, mb, bs, kvh, g, d = 2, 4, 8, 2, 2, 16
    npages = 9
    q = jnp.asarray(rng.randn(B, 1, kvh * g, d), jnp.float32)
    k_pages = jnp.asarray(rng.randn(npages, bs, kvh, d), jnp.float32)
    v_pages = jnp.asarray(rng.randn(npages, bs, kvh, d), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0]], jnp.int32)
    offs = jnp.asarray([30, 9], jnp.int32)  # row 1 never reads its nulls
    out_x = fa.paged_decode_attention(q, k_pages, v_pages, tables, offs,
                                      impl="xla")
    out_p = fa.paged_decode_attention(q, k_pages, v_pages, tables, offs,
                                      impl="pallas")
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# engine parity: paged backend is a memory-layout change, not a model change
# ---------------------------------------------------------------------------


def test_paged_engine_matches_generate_np_greedy(params):
    """Greedy decode through the paged engine is bit-identical to the
    single-shot path — including two requests sharing a long prefix, where
    the second attaches the first's registered blocks instead of
    re-prefilling them."""
    rng = np.random.RandomState(3)
    base = rng.randint(1, CFG.vocab_size, (24,)).tolist()
    prompts = _prompts(2, seed=4) + [base + [7], base + [11, 13]]
    ref = generation.generate_np(params, CFG, prompts, max_new_tokens=6)
    with Engine(params, CFG, num_slots=2, prefill_chunk=8,
                kv_num_blocks=-1, kv_block_size=8) as eng:
        out = eng.generate(prompts, max_new_tokens=6)
        st = eng.stats()
        audit = eng.audit()
    assert out == ref
    assert st["kv_backend"] == "paged"
    assert st["prefix_cache_hits"] >= 3  # 24 shared tokens = 3 full blocks
    assert not audit["leaked"], audit
    assert audit["blocks_active"] == 0, audit


def test_paged_engine_parity_through_slide_left_cow(params):
    """A near-capacity prompt whose attach point sits past the last whole
    prefill window forces the slide-left rewrite INTO the shared prefix:
    ensure_writable must COW those blocks, and the output must still be
    bit-identical (recomputed k/v is deterministic)."""
    rng = np.random.RandomState(5)
    base = rng.randint(1, CFG.vocab_size, (56,)).tolist()  # 7 full blocks
    prompts = [base + [7], base + [11]]
    ref = generation.generate_np(params, CFG, prompts, max_new_tokens=4)
    with Engine(params, CFG, num_slots=2, prefill_chunk=16,
                kv_num_blocks=-1, kv_block_size=8) as eng:
        out = eng.generate(prompts, max_new_tokens=4)
        st = eng.stats()
        audit = eng.audit()
    assert out == ref
    assert st["prefix_cache_hits"] >= 7
    assert st["cow_copies"] >= 1, st  # the slide-left window dirtied shares
    assert not audit["leaked"], audit


def test_paged_admission_waits_for_block_headroom(params):
    """A queued request the pool cannot hold yet stays QUEUED (peek, not
    pop): it admits — and completes — once a retiring request frees its
    blocks."""
    # pool of 9 usable blocks of 8: one (40+16)-token worst case = 7 blocks,
    # so two such requests can never hold blocks concurrently
    eng = Engine(params, CFG, num_slots=2, prefill_chunk=8, start_loop=False,
                 kv_num_blocks=10, kv_block_size=8, prefix_cache=False)
    try:
        p1, p2 = _prompts(2, lo=40, hi=41, seed=6)
        f1 = eng.submit(p1, 16)
        f2 = eng.submit(p2, 16)
        eng.step_once()
        assert eng.slots.active_count == 1  # second request left in queue
        assert eng.scheduler.depth == 1
        steps = 0
        while not (f1.done() and f2.done()):
            eng.step_once()
            steps += 1
            assert steps < 200
        ref = generation.generate_np(params, CFG, [p1, p2], max_new_tokens=16)
        assert [f1.result(timeout=1), f2.result(timeout=1)] == ref
        audit = eng.audit()
        assert not audit["leaked"], audit
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# satellites: clamp warning, exposition, doc sync
# ---------------------------------------------------------------------------


def test_max_seq_len_clamp_warns_and_reports_effective():
    with pytest.warns(RuntimeWarning, match="max_seq_len"):
        assert effective_max_seq_len(TINY, TINY.max_seq_len * 2) == TINY.max_seq_len
    with pytest.warns(RuntimeWarning):
        slots = SlotKVCache(TINY, 2, TINY.max_seq_len + 8)
    assert slots.max_seq_len == TINY.max_seq_len
    with pytest.warns(RuntimeWarning):
        paged = PagedKVCache(TINY, 2, block_size=4,
                             max_seq_len=TINY.max_seq_len + 8)
    assert paged.max_seq_len == TINY.max_seq_len
    # in-range requests stay silent
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert effective_max_seq_len(TINY, 16) == 16
        assert effective_max_seq_len(TINY, None) == TINY.max_seq_len


def test_metrics_exposition_carries_paged_families(params):
    """/metrics grows the kv/prefix families on the paged backend (and the
    scrape stays lint-clean); the slot backend emits none of them — family
    presence IS the backend signal."""
    from galvatron_tpu.models.tokenizer import ByteTokenizer
    from galvatron_tpu.obs.aggregate import exposition_lint
    from galvatron_tpu.obs.prom import server_metrics_text
    from galvatron_tpu.server import GenerationService

    base = list(range(1, 25))
    with Engine(params, CFG, num_slots=2, prefill_chunk=8,
                kv_num_blocks=-1, kv_block_size=8) as eng:
        eng.generate([base + [7], base + [11]], max_new_tokens=3)
        svc = GenerationService(params, CFG, ByteTokenizer(), engine=eng)
        text = server_metrics_text(svc)
    assert exposition_lint(text) == []
    for fam in ("galvatron_kv_blocks_total", "galvatron_kv_blocks_free",
                "galvatron_kv_blocks_cached",
                "galvatron_prefix_cache_hits_total",
                "galvatron_prefix_cache_misses_total",
                "galvatron_prefix_cache_evictions_total",
                "galvatron_kv_cow_copies_total",
                "galvatron_serving_max_seq_len_effective"):
        assert fam in text, fam
    with Engine(params, CFG, num_slots=1, prefill_chunk=8) as slot_eng:
        svc = GenerationService(params, CFG, ByteTokenizer(), engine=slot_eng)
        slot_text = server_metrics_text(svc)
    assert exposition_lint(slot_text) == []
    assert "galvatron_kv_blocks_total" not in slot_text
    assert "galvatron_serving_max_seq_len_effective" in slot_text


def test_design_doc_block_state_machine_in_sync():
    """DESIGN.md § Paged KV cache must name every block state the allocator
    partitions over (same doc-sync contract as the serving lifecycle)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    text = open(os.path.join(root, "docs", "DESIGN.md")).read()
    m = re.search(r"## Paged KV cache\n(.*?)(?:\n## |\Z)", text, re.S)
    assert m, "DESIGN.md has no '## Paged KV cache' section"
    section = m.group(1)
    missing = [s for s in BLOCK_STATES if s not in section]
    assert not missing, f"block states missing from DESIGN.md: {missing}"
    # the section documents the two levers and the null-block trick
    for needle in ("--kv_num_blocks", "null block", "Copy-on-write"):
        assert needle in section, needle
