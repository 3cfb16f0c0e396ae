"""The five ``search_*`` readers and their helper (PR 56): a ``plan_price`` as
``flight.last_plan_price()`` returns it beside a device step small enough to
add up by hand and beside the step recorded on the chip
(``recorded_scoped_step.json``).  No number here is a device number; the
recorded step's are quoted from the chip run that made it."""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.lib import scoped, xplane  # noqa: E402
from benchmark.metrics import _search_terms  # noqa: E402

READERS = {"search_compute_pred_over_meas": "compute_ratio",
           "search_comm_pred_over_meas": "comm_ratio",
           "search_other_pred_over_meas": "other_ratio",
           "search_mem_pred_over_meas": "mem_ratio",
           "search_unpriced_share": "unpriced_share"}

#: a price with every term a reader sums, and two it must leave beside
PRICE = {
    "time_ms": {"compute": 2.0, "overlap_slowdown": 0.2, "dp_exposed": 0.03, "dp_hidden": 0.5,
                "tp_exposed": 0.05, "tp_hidden": 0.7, "cp": 0.01, "ep": 0.02,
                "redistribute": 0.04, "other_compute": 2.6, "other_comm": 0.06,
                "pp_bubble": 0.4, "pp_p2p": 0.07},
    "volume_mb": {"tp_boundary": 0.6, "dp_grad": 0.12},
    "memory_mb": {"states": 300.0, "activations": 150.0, "other": 40.0, "rings": 0.0,
                  "transient": 10.0},
    "basis": {"costs": "analytic", "compute_tflops": 40.0, "fallback_bandwidths": ["allreduce_bw"],
              "overlap_coe": 1.1, "assumed_gbps": {"tp_boundary": 100.0, "dp_grad": 100.0},
              "hidden_terms": ["dp_hidden", "tp_hidden"], "source": "trainer",
              # 2.0 + .2 + .03 + .05 + .01 + .02 + .04 + 2.6 + .06 + .4 + .07
              "total_ms": 5.48},
}

OP = "jit(train_step)/{}"
#: start ns, end ns, instruction, category, op_name, hlo_category: one step of 4,000 ns
SMALL = [
    (0, 100, "fusion.1", "fusion:kLoop", OP.format("jvp(embed)/gather:"), ""),
    (100, 300, "fusion.2", "fusion:kOutput",
     OP.format("grad_accum/while/body/jvp(layer_0)/attn/qkv_proj/dot_general:"), ""),
    (300, 700, "fusion.3", "fusion:kOutput", OP.format("jvp(head)/dot_general:"), ""),
    (700, 1500, "fusion.4", "fusion:kOutput", OP.format("transpose(jvp(head))/dot_general:"), ""),
    (1500, 2000, "fusion.5", "fusion:kOutput",
     OP.format("transpose(jvp(layer_0))/mlp/dot_general:"), ""),
    # communication three ways: a collective fusion, a comm scope, a collective under the head
    (2000, 2300, "fusion.6", "fusion:kCustom", OP.format("transpose(jvp(layer_0))/mlp/dot_general:"),
     "reduce-scatter"),
    (2300, 2350, "fusion.7", "fusion:kLoop", OP.format("jvp(layer_0)/redistribute/sharding_constraint:"),
     ""),
    (2350, 2400, "all-reduce.1", "collective", OP.format("jvp(head)/psum:"), "all-reduce"),
    (2400, 2450, "fusion.8", "fusion:kLoop", OP.format("grad_accum/while/body/closed_call/add:"), ""),
    (2450, 2950, "fusion.9", "fusion:kLoop", OP.format("optimizer/sub:"), ""),
    (2950, 3000, "copy.1", "copy", "", ""),
    (3500, 3600, "fusion.10", "fusion:kLoop", OP.format("my_head_thing/lossy:"), ""),
]
# by hand, ns: comm 300 + 50 + 50 = 400; other 100 + 400 + 800 = 1300;
#   compute 200 + 500 + 50 (grad_accum's own add) = 750; optimizer 500;
#   unscoped 50 + 100 = 150; busy 3100; window 0..3600, idle 500; parts 3600
MEASURED = {"compute": 750e-6, "comm": 400e-6, "other": 1300e-6, "optimizer": 500e-6,
            "unscoped": 150e-6, "idle": 500e-6}
STEP_MS = 4000e-6
PEAK_BYTES = 1_000_000_000
EXPECTED = {
    "search_compute_pred_over_meas": (2.0 + 0.2) / 750e-6,
    "search_comm_pred_over_meas": (0.05 + 0.03 + 0.01 + 0.02 + 0.04 + 0.06 + 0.07) / 400e-6,
    "search_other_pred_over_meas": 2.6 / 1300e-6,
    "search_mem_pred_over_meas": 500.0 * 1e6 / PEAK_BYTES,
    "search_unpriced_share": 100.0 * (500 + 150 + 500) / 4000,
}


def _metric(name):
    spec = importlib.util.spec_from_file_location(
        f"_metric_{name}", os.path.join(REPO, "benchmark", "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ctx(rows, said, step_ms=STEP_MS, n=1, plan=None):
    sops = [scoped.ScopedOp(*r) for r in rows]
    ops = [xplane.Op(o.start, o.end, o.name, o.category) for o in sops]
    return {"trace": {"devices": {0: ops}}, "_scoped_device0": sops, "n_profiled": n,
            "step_s": [step_ms / 1e3] * 3, "memory_peak_bytes": PEAK_BYTES, "say": said.append,
            "plan": plan, "spans": [], "setup_spans": [], "records": [], "chips": 1}


@pytest.fixture
def priced(monkeypatch):
    from galvatron_tpu.obs import flight

    monkeypatch.setattr(flight, "_last_plan_price", json.loads(json.dumps(PRICE)))


@pytest.mark.parametrize("part,row", [
    ("comm", SMALL[5]), ("comm", SMALL[6]), ("comm", SMALL[7]), ("other", SMALL[0]),
    ("other", SMALL[3]), ("compute", SMALL[1]), ("compute", SMALL[8]), ("optimizer", SMALL[9]),
    ("unscoped", SMALL[10]), ("unscoped", SMALL[11])])
def test_each_operation_belongs_to_one_part(part, row):
    assert _search_terms.part_of(scoped.ScopedOp(*row)) == part


def test_parts_add_up_by_hand(priced):
    said = []
    t = _search_terms.of_ctx(_ctx(SMALL, said))
    assert t["measured"] == pytest.approx(MEASURED)
    # measured parts are the device's window; against a 4,000 ns step they miss 400
    assert sum(t["measured"].values()) == pytest.approx(3600e-6)
    assert t["measured_miss_ms"] == pytest.approx(400e-6)
    # predicted parts on the critical path add up to the plan's total
    assert t["predicted_total_ms"] == pytest.approx(5.48)
    assert t["predicted_miss_ms"] == pytest.approx(0.0, abs=1e-12)
    assert t["predicted"]["pp_bubble"] == 0.4 and t["predicted"]["tp_hidden"] == 0.7
    text = "\n".join(said)
    assert "priced by trainer on analytic costs (compute at 40.0 TFLOP/s" in text
    assert "measured parts miss step_ms_p50 by 0.000 ms" in text
    assert "(pp_bubble 0.400 predicted)" in text and "believed hidden, in no ratio" in text
    assert sum(s.startswith("search terms:") for s in said) == 1


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_the_hand_made_step(name, priced):
    said = []
    ctx = _ctx(SMALL, said)
    mod = _metric(name)
    doc = " ".join(mod.__doc__.split())
    assert mod.NAME == name and "not gated" in doc
    assert ("0 is the aim" if name == "search_unpriced_share" else "1.0 is the aim") in doc
    assert mod.compute(ctx) == pytest.approx(EXPECTED[name], rel=1e-12)
    # the table is printed once a run, whichever reader asks first
    _metric("search_unpriced_share").compute(ctx)
    assert sum(s.startswith("search terms:") for s in said) == 1
    declared = {m["name"]: m for m in json.load(open(os.path.join(REPO, "BENCHMARK.json")))
                ["per_layer"]}[name]
    assert (declared["unit"], declared["better"], declared["source"], declared["layer"],
            declared["moves"]) == (mod.UNIT, "lower", "program_counter", "search",
                                   "tokens_per_s_per_chip")
    if name == "search_comm_pred_over_meas":
        text = "\n".join(said)
        # all-reduce.1 is the one collective in flight: 50 ns, nothing else running
        assert "comm in flight 0.000 ms a step, exposed in flight 0.000" in text
        assert "volume tp_boundary: 0.6 MB a step predicted" in text
        assert "the price assumed 100.0" in text
        assert set(declared["workloads"]) == {"opt-1.3b_4chip_searched", "opt-1.3b_4chip_zero3"}
    if name == "search_mem_pred_over_meas":
        assert any("states 300.0" in s and "sum 500.0 against a peak of 1000.0" in s for s in said)


def test_the_recorded_chip_step(priced):
    """Device 0 of one step of ``baichuan-7b_s4096`` (one chip: no comm part):
    compute = forward + backward - head, by the file's own sums."""
    with open(os.path.join(HERE, "recorded_scoped_step.json")) as f:
        rec = json.load(f)
    expect = rec["expect"]
    said = []
    ctx = _ctx(rec["ops"], said, step_ms=283.65)
    t = _search_terms.of_ctx(ctx)
    phases = expect["phase_ns"]
    layer_ns = phases["forward"] + phases["backward"] - expect["head_ns"]
    assert t["measured"]["compute"] == pytest.approx(layer_ns / 1e6) == pytest.approx(146.932, abs=1e-3)
    assert t["measured"]["other"] == pytest.approx(expect["head_ns"] / 1e6)
    assert t["measured"]["optimizer"] == pytest.approx(phases["optimizer"] / 1e6)
    assert t["measured"]["unscoped"] == pytest.approx(phases["unscoped"] / 1e6)
    assert t["measured"]["comm"] == 0.0 and t["comm_ratio"] is None
    ops = xplane.first_device(ctx["trace"])
    a, b = xplane.window_of(ops)
    assert sum(t["measured"].values()) == pytest.approx((b - a) / 1e6)
    assert t["compute_ratio"] == pytest.approx(2.2 / 146.932, rel=1e-5)
    assert _metric("search_comm_pred_over_meas").compute(ctx) is None  # plan "single": left out
    assert _metric("search_other_pred_over_meas").compute(ctx) == pytest.approx(
        2.6 / (expect["head_ns"] / 1e6))
    assert _metric("search_unpriced_share").compute(ctx) == pytest.approx(
        100 * (t["measured"]["optimizer"] + t["measured"]["unscoped"] + t["measured"]["idle"])
        / 283.65)


def test_the_plan_files_price_is_compared_and_its_total_is_the_reference(priced):
    said = []
    doc = {"search_cost_ms": 5.5, "search_price": json.loads(json.dumps(PRICE))}
    t = _search_terms.of_ctx(_ctx(SMALL, said, plan=doc))
    assert t["predicted_total_ms"] == 5.5 and t["predicted_miss_ms"] == pytest.approx(0.02)
    assert any("agree term for term (source trainer)" in s for s in said)
    doc["search_price"]["time_ms"]["compute"] = 9.0
    said.clear()
    _search_terms.of_ctx(_ctx(SMALL, said, plan=doc))
    assert any("DIFFER" in s for s in said)


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("why", ["no price", "the error arg", "no accessor", "no trace", "no scopes"])
def test_reader_leaves_itself_out(name, why, monkeypatch):
    from galvatron_tpu.obs import flight

    said = []
    ctx = _ctx(SMALL, said)
    monkeypatch.setattr(flight, "_last_plan_price", json.loads(json.dumps(PRICE)))
    if why == "no price":
        monkeypatch.setattr(flight, "_last_plan_price", None)
    elif why == "the error arg":
        monkeypatch.setattr(flight, "_last_plan_price", {"error": "ValueError: no such model"})
    elif why == "no accessor":  # the parent of PR 56
        monkeypatch.delattr(flight, "last_plan_price")
    elif why == "no trace":
        ctx["trace"], ctx["_scoped_device0"] = None, None
    else:  # a program that gave its operations no scope: ``scoped.device0`` says None
        ctx["_scoped_device0"] = None
    assert _metric(name).compute(ctx) is None
    assert said == []


def test_a_ratio_without_its_measured_part_is_left_out(priced):
    rows = [r for r in SMALL if _search_terms.part_of(scoped.ScopedOp(*r)) != "other"]
    ctx = _ctx(rows, [])
    assert _metric("search_other_pred_over_meas").compute(ctx) is None
    assert _metric("search_compute_pred_over_meas").compute(ctx) is not None
    ctx = _ctx(SMALL, [])
    ctx["memory_peak_bytes"] = 0
    assert _metric("search_mem_pred_over_meas").compute(ctx) is None


def test_the_five_sit_at_the_tail_of_the_manifest():
    per = json.load(open(os.path.join(REPO, "BENCHMARK.json")))["per_layer"]
    names = [m["name"] for m in per]
    assert names[-5:] == ["search_compute_pred_over_meas", "search_comm_pred_over_meas",
                          "search_other_pred_over_meas", "search_mem_pred_over_meas",
                          "search_unpriced_share"]
    four = {"opt-1.3b_4chip_searched", "opt-1.3b_4chip_zero3"}
    assert all(four <= set(m["workloads"]) for m in per[-5:])
    # the two readers the layer had stay as they were
    old = {m["name"]: m for m in per}
    assert old["search_pred_over_meas"]["workloads"] == ["opt-1.3b_4chip_searched"]
    assert old["search_s"]["moves"] == "setup_s"
