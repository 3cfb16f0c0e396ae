"""The replay (``benchmark/replay.py``) off the chip: every serving mix with a
``knee`` block must sit above the knee of the engine the chip read AND of the
fastest engine it is meant to judge, on every seed; and the replay itself is
held to the two readings ISSUE 40 took by hand of the mix as it was before
PR 41 (146.7 and 136.7 tokens/s: a faster engine read LOWER there).  What the
replay prints is a model's reading, never a device number."""

import copy
import glob
import json
import math
import os
import sys
from statistics import median

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import replay  # noqa: E402
from benchmark.lib import harness  # noqa: E402

SEEDS = range(1, 9)
SECONDS = float(harness.load_manifest(REPO)["run_seconds"])
ENGINES = ("engine_ms", "judges_up_to")


def _mixes():
    out = {}
    for path in sorted(glob.glob(os.path.join(REPO, "benchmark", "traffic", "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec.get("kind") == "serve" and "knee" in spec:
            out[os.path.basename(path)[:-5]] = spec
    return out


MIXES = _mixes()
_CACHE = {}


def _summary(name, engine):
    if (name, engine) not in _CACHE:
        spec = MIXES[name]
        _CACHE[name, engine] = replay.summary(spec, spec["knee"][engine], SEEDS, SECONDS)
    return _CACHE[name, engine]


def test_some_serving_mix_states_its_knee():
    assert "serve_open_above_knee" in MIXES


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("name", sorted(MIXES))
def test_every_slot_is_in_use_all_through_the_window(name, engine):
    runs = _summary(name, engine)["runs"]
    assert min(r["occupancy"] for r in runs) >= 99.0, [r["occupancy"] for r in runs]


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("name", sorted(MIXES))
def test_the_queue_ends_deeper_than_it_began(name, engine):
    for r in _summary(name, engine)["runs"]:
        assert r["queue_last"] > r["queue_first"] > 0, r


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("name", sorted(MIXES))
def test_seeds_spread_under_half_the_bound(name, engine):
    """Half of the 3.5% that ``serve_tokens_per_s_per_chip`` may lose: what is
    left for the machine once the traffic's own share is taken."""
    bound = {m["name"]: m for m in harness.load_manifest(REPO)["end_to_end"]}[
        "serve_tokens_per_s_per_chip"]["bound"]
    out = _summary(name, engine)
    assert out["spread"] < bound / 2 == 0.0175
    assert (out["tokens_per_s_max"] - out["tokens_per_s_min"]) / out["tokens_per_s_median"] < bound


@pytest.mark.parametrize("name", sorted(MIXES))
def test_the_faster_engine_reads_higher(name):
    """A gain must read as a gain on every seed, not only in the medians."""
    slow, fast = (_summary(name, e)["runs"] for e in ENGINES)
    assert all(f["tokens_per_s"] > s["tokens_per_s"] for s, f in zip(slow, fast))
    knee = MIXES[name]["knee"]
    assert knee["judges_up_to"]["per_slot"] < knee["engine_ms"]["per_slot"]


@pytest.mark.parametrize("name", sorted(MIXES))
def test_the_rate_lies_above_both_knees(name):
    spec = MIXES[name]
    rate, knee = spec["arrivals"]["rate_rps"], spec["knee"]
    assert rate >= 2.5 * knee["sustained_rps"] > 0
    # what the faster engine completes a second, in requests, is still under the rate
    answer = replay.traffic.mean_output_len(spec)
    assert answer == 158.25
    assert _summary(name, "judges_up_to")["tokens_per_s_max"] / answer < rate
    # and what the chip's engine completes is what the knee block says it sustains
    assert _summary(name, "engine_ms")["tokens_per_s_median"] / answer == pytest.approx(
        knee["sustained_rps"], rel=0.05)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_captured_requests_cover_both_kinds_and_fit_their_room(name):
    spec = MIXES[name]
    limits = spec["correct"]
    assert math.gcd(limits["capture_every"], spec["sampling"]["greedy_every"]) == 1
    for r in _summary(name, "engine_ms")["runs"]:
        # rows written from the first arrival to the window's close
        assert r["captured_rows"] <= limits["rows_kept"], r
        assert r["captured_finished"] >= limits["requests"] >= 8, r


def test_a_mix_that_is_not_coprime_is_refused():
    spec = copy.deepcopy(MIXES["serve_open_above_knee"])
    spec["correct"]["capture_every"] = 2 * spec["sampling"]["greedy_every"]
    with pytest.raises(ValueError, match="coprime"):
        replay.replay(spec, 1, spec["knee"]["engine_ms"], SECONDS)


# --- the replay itself, on the mix as it was before PR 41 -----------------------------

#: what PR 41 changed in ``serve_open_above_knee.json``, back as it was at commit
#: 98140f8 (lengths, sampling, corpus and window are the file's own, unchanged)
OLD = {"arrivals": {"process": "exponential_gap_quantiles", "rate_rps": 0.69,
                    "burst_at_start": 16},
       "serve_flags": ["--num_slots", "8", "--prefill_chunk", "256", "--max_queue", "4096",
                       "--request_ttl_s", "0"],
       "correct": {"requests": 8, "capture_every": 2, "rows_kept": 2048,
                   "logits_kl_max": 0.00014}}


def _old_spec():
    spec = copy.deepcopy(MIXES["serve_open_above_knee"])
    spec.update(copy.deepcopy(OLD))
    # capture_every 2 with greedy_every 4 is what the new generator refuses: the
    # replay reads no row, so keep the old rate of captures out of it
    spec["correct"]["capture_every"] = 3
    return spec


@pytest.mark.parametrize("engine,want,occupancy", [
    ({"per_slot": 2.6, "per_iteration": 17.9, "prefill_chunk": 13.3}, 146.7, 58.0),
    ({"per_slot": 0.05, "per_iteration": 18.6, "prefill_chunk": 13.3}, 136.7, None),
])
def test_the_old_mix_reads_what_issue_40_read_by_hand(engine, want, occupancy):
    """Under its knee: 8 slots at 0.69 requests/s after a burst of 16.  Through
    today's engine 146.7 tokens/s (ledger, PR 39: 146.2 / 150.2), through one
    that samples on the device 136.7: the burst is drained before the window
    opens, the queue ends empty, the seeds spread by several percent."""
    out = replay.summary(_old_spec(), engine, SEEDS, SECONDS)
    assert out["tokens_per_s_median"] == pytest.approx(want, rel=0.02)
    assert all(r["queue_last"] == 0 for r in out["runs"])
    assert out["spread"] > 0.0175
    if occupancy:
        assert median(r["occupancy"] for r in out["runs"]) == pytest.approx(occupancy, abs=4.0)


def test_the_command_prints_one_line_a_seed_and_a_summary(capsys):
    assert replay.main(["--workload", "opt-1.3b_serve_above_knee", "--seeds", "2",
                        "--engine", "2.6,24,13.3", "--seconds", "20"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert [ln.split(" ", 1)[0] for ln in lines] == ["REPLAY", "REPLAY", "SUMMARY"]
    last = json.loads(lines[-1].split(" ", 1)[1])
    assert last["model_not_device"] is True and last["seconds"] == 20.0
    assert last["engine_ms"] == {"per_slot": 2.6, "per_iteration": 24.0, "prefill_chunk": 13.3}
