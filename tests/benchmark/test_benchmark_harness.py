"""The benchmark harness on the CPU: the no-fallback contract, the whole run at
a tiny size from a temporary copy of the benchmark's directories (rehearsal 1 of
the on-chip-measurement guide), and that a new cell, configuration and
per-layer metric are picked up from files alone.  Nothing here describes a TPU
topology or loads libtpu, and no number from these runs is a device number."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.lib import harness  # noqa: E402

TINY_CONFIGS = {
    "tiny-baichuan": {
        "model_type": "baichuan", "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 2, "num_hidden_layers": 2, "rms_norm_eps": 1e-05,
        "rope_theta": 10000.0, "tie_word_embeddings": False, "vocab_size": 256,
        "initial_logit_variance": 1 / 3,
        "program_flags": ["--model_size", "baichuan-7b", "--num_layers", "2", "--hidden_size",
                          "64", "--num_heads", "2", "--ffn_dim", "128", "--vocab_size", "256"],
    },
    "tiny-opt": {
        "model_type": "opt", "hidden_size": 64, "ffn_dim": 128, "num_attention_heads": 2,
        "num_hidden_layers": 2, "tie_word_embeddings": True, "vocab_size": 256,
        "initial_logit_variance": 64 * 0.02 ** 2,
        "program_flags": ["--model_size", "opt-1.3b", "--num_layers", "2", "--hidden_size",
                          "64", "--num_heads", "2", "--ffn_dim", "128", "--vocab_size", "256"],
    },
}
TINY_TRAFFIC = {
    "seq_len": 64, "global_batch": 8, "plan": "single", "train_flags": ["--lr", "1e-2"],
    "corpus": {"tokens": 65536, "doc_len": 256, "zipf_a": 1.0, "follow_p": 0.5},
    "loss_drop_by_step_20": 0.3, "why": "tiny CPU rehearsal",
}
SEARCHED_TRAFFIC = dict(
    TINY_TRAFFIC, plan={"search": ["--analytic_costs", "1", "--memory_constraint_gb", "14",
                                   "--settle_bsz", "8"]})
EXTRA_METRIC = '''"""A metric a later PR might add: counts the window's steps."""
NAME, UNIT, BETTER, SOURCE = "steps_in_window", "steps", "higher", "program_counter"
LAYER, MOVES = "trainer loop", "tokens_per_s_per_chip"


def compute(ctx):
    return len(ctx["records"])
'''


@pytest.fixture()
def tiny_root(tmp_path):
    """A copy of the benchmark's directories plus files only: two tiny
    configurations, one tiny traffic mix, their cells and one more metric."""
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = harness.load_manifest(REPO)
    for cname, cfg in TINY_CONFIGS.items():
        path = f"benchmark/configs/{cname}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(cfg, f)
        manifest["configs"].append({"name": cname, "source": "test", "file": path,
                                    "reduced": [], "why": "test"})
        manifest["workloads"].append({"name": f"{cname}_tiny", "config": cname,
                                      "traffic": "tiny", "chips": 1, "why": "test"})
    manifest["workloads"].append({"name": "tiny-opt_searched", "config": "tiny-opt",
                                  "traffic": "tiny_searched", "chips": 4, "why": "test"})
    for tname, traffic in (("tiny", TINY_TRAFFIC), ("tiny_searched", SEARCHED_TRAFFIC)):
        with open(os.path.join(root, f"benchmark/traffic/{tname}.json"), "w") as f:
            json.dump(traffic, f)
    # a new training cell joins the lists a cell like it is in (throughput names
    # its cells since serving joined), and the searched cell the search readers' too
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        listed = entry.get("workloads", [])
        if "baichuan-7b_s512" in listed:
            listed += [f"{cname}_tiny" for cname in TINY_CONFIGS] + ["tiny-opt_searched"]
        elif entry["name"].startswith("search"):
            listed.append("tiny-opt_searched")
    with open(os.path.join(root, "benchmark/metrics/steps_in_window.py"), "w") as f:
        f.write(EXTRA_METRIC)
    manifest["per_layer"].append({
        "name": "steps_in_window", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "trainer loop", "moves": "tokens_per_s_per_chip"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def test_no_cpu_fallback():
    """Without a TPU the command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--workload",
         "baichuan-7b_s512", "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0, p.stdout
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout
    assert "no CPU fallback" in p.stderr


@pytest.mark.parametrize("cname", sorted(TINY_CONFIGS))
def test_whole_run_tiny(tiny_root, tmp_path, cname):
    """Both block kinds through corpus, warm-up, reference check, measured
    window and both output forms; the added cell, configuration and metric
    come from files alone."""
    cell = f"{cname}_tiny"
    end = harness.run_cell(tiny_root, cell, seed=3, seconds=0.5, trace=False,
                           out_dir=str(tmp_path / "e2e"), t_start=time.time(), min_steps=24)
    assert end["correct"] is True and end["failed"] == 0 and end["attempted"] >= 24
    assert set(end["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    assert all(m["value"] > 0 for m in end["metrics"].values())
    assert set(end["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(end)

    traced = harness.run_cell(tiny_root, cell, seed=4, seconds=0.5, trace=True,
                              out_dir=str(tmp_path / "traced"), t_start=time.time(),
                              min_steps=24)
    assert traced["correct"] is True
    got = set(traced["metrics"])
    # span- and counter-read metrics exist on any backend; the new one is found
    assert {"compile_s", "data_wait_share", "step_ms_p50", "steps_in_window"} <= got
    assert traced["metrics"]["steps_in_window"]["value"] == traced["attempted"]
    # nothing that needs a device trace, a peak or a search exists on the CPU
    assert not got & {"mfu", "device_idle_share", "flash_attention_ms_per_step",
                      "flash_attention_roofline", "collective_ms_per_step", "search_s",
                      "tokens_per_s_per_chip", "setup_s"}
    assert "busy_s" not in traced["device"]
    # the profiler window of the traced run left a trace the reader can open
    assert harness.xplane.find_trace(str(tmp_path / "traced" / "profile"))


#: lowest and highest first loss the chip gave over seeds (my chip runs, PR 24: 14 and 40 seeds)
CHIP_FIRST_LOSSES = {"opt-1.3b": (11.149359703063965, 11.322005271911621),
                     "baichuan-7b": (11.190643310546875, 11.269756317138672)}


@pytest.mark.parametrize("cname", sorted(CHIP_FIRST_LOSSES))
def test_first_loss_band_is_centred_on_an_untrained_model(cname):
    """The band sits on ln(vocab) + variance/2, where the chip's runs are, and
    not on ln(vocab), whose band of 0.5 a run in twenty of opt-1.3b left."""
    with open(os.path.join(REPO, "benchmark", "configs", cname + ".json")) as f:
        centre = harness.expected_first_loss(json.load(f))
    lo, hi = CHIP_FIRST_LOSSES[cname]
    assert lo < centre < hi
    assert max(centre - lo, hi - centre) < harness.FIRST_LOSS_TOL / 3


def test_broken_reference_is_caught(tiny_root, tmp_path, monkeypatch):
    """A program that computes something else than the reference is not correct."""
    from benchmark.lib import reference

    monkeypatch.setattr(reference, "rms_norm", lambda x, scale, eps: x * scale)
    res = harness.run_cell(tiny_root, "tiny-baichuan_tiny", seed=3, seconds=0.2, trace=False,
                           out_dir=str(tmp_path / "bad"), t_start=time.time())
    assert res["correct"] is False


def test_searched_plan_on_four_virtual_devices(tiny_root, tmp_path):
    """Rehearsal 2: search -> plan -> trainer on four virtual CPU devices, in a
    process of its own (this one is pinned to eight)."""
    code = (
        "import json, sys, time; sys.path.insert(0, sys.argv[1]);"
        "from benchmark.lib import harness;"
        "r = harness.run_cell(sys.argv[2], 'tiny-opt_searched', seed=5, seconds=0.2, trace=True,"
        " out_dir=sys.argv[3], t_start=time.time(), min_steps=24);"
        "print(json.dumps(r))"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out_dir = str(tmp_path / "searched")
    p = subprocess.run([sys.executable, "-c", code, REPO, tiny_root, out_dir], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["device"]["count"] == 4
    assert res["metrics"]["search_s"]["value"] > 0
    assert res["metrics"]["search_pred_over_meas"]["value"] > 0
    with open(os.path.join(out_dir, "searched_plan.json")) as f:
        assert json.load(f)["num_devices"] == 4
