"""BENCHMARK.json against the builder's contract and against the files it
names: what the driver would refuse before a run fails here first."""

import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.lib import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj\w*|head)_size|_dim$|_rank$|"
                   r"expansion|experts_per_tok")


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest(REPO)


def _line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s and "\t" not in s


def test_top_level(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(manifest["command"]) <= 32 and all(_line(w) for w in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    # the command names no file outside paths
    for word in manifest["command"]:
        if os.path.exists(os.path.join(REPO, word)):
            assert any(word.startswith(p + "/") for p in manifest["paths"]), word
    rs = manifest["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with all 24 cells fits the driver's 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_files_under_paths_have_plain_names(manifest):
    for p in manifest["paths"]:
        for d, dirs, files in os.walk(os.path.join(REPO, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for fn in files:
                rel = os.path.relpath(os.path.join(d, fn), REPO)
                assert PATH.match(rel), rel


def test_configs(manifest):
    names = [c["name"] for c in manifest["configs"]]
    assert 1 <= len(names) <= 24 and len(set(names)) == len(names)
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        # every key the file says it changed is listed, and none is a width
        assert sorted(cfg["reduced"]) == sorted(c["reduced"]) and len(c["reduced"]) <= 16
        assert sorted(cfg["published"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
            assert cfg[key] != cfg["published"][key]


def test_workloads(manifest):
    cells = manifest["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in manifest["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and _line(w["why"])
        cell, config, traffic = harness.load_cell(REPO, w["name"])
        assert traffic.get("kind", "train") in ("train", "serve") and _line(traffic["why"], 2000)
        if traffic.get("kind") == "serve":
            _check_serve_traffic(traffic, config)
            continue
        assert traffic["global_batch"] % w["chips"] == 0
        assert traffic["seq_len"] <= config["max_position_embeddings"]
        assert traffic["corpus"]["tokens"] > 4 * traffic["global_batch"] * (traffic["seq_len"] + 1)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


def _check_serve_traffic(traffic, config):
    """A serving mix is parameters only, and what it asks of the engine fits
    the configuration: the published positions, a rate, a window rule the
    runner knows, limits for what ``correct`` compares."""
    from benchmark.lib import traffic as traffic_lib

    assert set(traffic) - {"knee"} == {"kind", "arrivals", "lengths", "sampling", "corpus",
                                       "serve_flags", "window", "correct", "why"}
    if "knee" in traffic:
        # what ``sweep_knee.py`` and its traced run read on the chip, and the fastest
        # engine the mix is meant to judge: the numbers ``replay.py`` reads
        knee = traffic["knee"]
        assert set(knee) == {"sustained_rps", "engine_ms", "judges_up_to"}
        assert 0 < knee["sustained_rps"] < traffic["arrivals"]["rate_rps"]
        for engine in (knee["engine_ms"], knee["judges_up_to"]):
            assert set(engine) == {"per_slot", "per_iteration", "prefill_chunk"}
            assert all(isinstance(v, (int, float)) and v > 0 for v in engine.values())
    shapes = traffic_lib.grid(traffic)
    assert len(shapes) == traffic["lengths"]["grid"]
    assert max(s["prompt_len"] + s["output_len"] for s in shapes) <= traffic["lengths"]["max_total"]
    assert traffic["lengths"]["max_total"] < config["max_position_embeddings"]
    assert traffic["arrivals"]["rate_rps"] > 0 and traffic["arrivals"]["burst_at_start"] >= 0
    assert traffic["window"]["opens"] in ("all_slots_used", "traffic_start")
    limits = traffic["correct"]
    assert set(limits) == {"requests", "capture_every", "rows_kept", "logits_kl_max"}
    assert limits["logits_kl_max"] > 0 and limits["requests"] >= 1 <= limits["capture_every"]
    # room for the rows of the longest answer at the least
    assert limits["rows_kept"] >= max(s["output_len"] for s in shapes)
    flags = traffic["serve_flags"]
    assert len(flags) % 2 == 0 and all(f.startswith("--") for f in flags[::2])
    # the slot backend as it stands: no paged pool, no quantisation, no speculation
    assert not {"--kv_num_blocks", "--serve_quant", "--spec_decode_k"} & set(flags)
    assert {"--num_slots", "--prefill_chunk"} <= set(flags)


def test_metrics(manifest):
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
    serving = {w["name"] for w in manifest["workloads"]
               if harness.load_cell(REPO, w["name"])[2].get("kind") == "serve"}
    assert serving and serving == set(by_name["serve_tokens_per_s_per_chip"]["workloads"])
    assert not serving & set(by_name["tokens_per_s_per_chip"]["workloads"])
    assert not any("workloads" in m for m in per if m["moves"] == "serve_tokens_per_s_per_chip")
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


def test_peaks_table_has_sources():
    for kind, row in harness.load_peaks(REPO).items():
        assert row["flops_per_s_bf16"] > 0 and row["hbm_bytes_per_s"] > 0 and row["source"], kind
