"""``search/price.price_plan`` (PR 56): the search's price of ONE plan by term.

Over a sweep of plans the critical-path terms sum to the DP's own ``cost_ms``,
the memory terms to ``predicted_train_mb`` and (within one DP unit a position)
to the DP's ``memory_mb``; ``comm_volume_breakdown`` is a view of it; the
functions the DP calls return what they returned at the parent (pinned); the
plan file carries it; the trainer prices the plan it runs, whatever its source.
All on the CPU; no number here is a device number."""

import dataclasses
import json

import numpy as np
import pytest

from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy as S
from galvatron_tpu.search import cost_model as cm
from galvatron_tpu.search import price as pr
from galvatron_tpu.search import search_engine as se
from galvatron_tpu.search.memory_fidelity import predicted_train_mb

SEAMS = (("ag", 6144, 128, False), ("rs", 2048, 128, True),
         ("ag", 8192, 128, False), ("rs", 2048, 128, True))
LT = cm.ProfiledLayerType(
    fwd_ms_per_sample=2.0, parameter_mb=80.0,
    activation_mb_per_sample={1: 40.0, 2: 20.0, 4: 10.0, 8: 5.0},
    boundary_activation_mb_per_sample=4.0, tp_seams=SEAMS)
MOE = cm.ProfiledLayerType(
    fwd_ms_per_sample=3.0, parameter_mb=300.0, activation_mb_per_sample={1: 50.0},
    boundary_activation_mb_per_sample=4.0, moe_expert_param_fraction=0.8,
    moe_a2a_mb_per_sample=8.0)
WIDE = dataclasses.replace(LT, fwd_ms_per_sample=3.5, parameter_mb=120.0,
                           boundary_activation_mb_per_sample=6.0)
HW = cm.ProfiledHardware(
    allreduce_bw={"2_1": 150.0, "2_0": 30.0, "4_1": 140.0, "4_0": 25.0, "8_1": 120.0},
    p2p_bw={2: 50.0, 4: 50.0}, overlap_coe=1.1)


def costs_of(layer_types, **kw):
    return cm.ProfiledModelCosts(
        layer_types=layer_types, other_param_mb=100.0, other_act_mb_per_sample=8.0,
        other_fwd_ms_per_sample=0.3, hidden_size=2048, **kw)


ONE = costs_of({0: LT})
MEASURED = costs_of({0: LT}, measured_vocab_slope_ms={1: 0.3, 2: 0.2, 4: 0.15, 8: 0.1},
                    measured_vocab_const_ms={1: 1.0, 2: 0.7, 4: 0.5, 8: 0.4},
                    measured_vocab_mp="bf16")
#: a plan of the sweep: the candidates the DP may choose from (handed to it in
#: place of the enumeration, so that each case IS the plan it names), the
#: evaluate() arguments, and what the case must show beyond the sums
PLANS = {
    "dp": dict(cands=[S()]),
    "tp": dict(cands=[S(tp=4)]),
    "tp strided": dict(cands=[S(tp=2, tp_consec=False)]),
    "tp + sp + tp_overlap": dict(cands=[S(tp=4, sp=True, tp_overlap=True)], nonzero=["tp_hidden"]),
    "zero2": dict(cands=[S(dp_type="zero2")]),
    "zero3": dict(cands=[S(dp_type="zero3")], nonzero=["overlap_slowdown", "dp_hidden"]),
    "zero3, traffic outlasts the compute": dict(
        cands=[S(dp_type="zero3")], costs=costs_of({0: dataclasses.replace(LT, parameter_mb=4000.0)}),
        budget=1e6, nonzero=["dp_exposed"]),
    "full checkpointing": dict(cands=[S(tp=2, ckpt="full")]),
    "selective checkpointing": dict(cands=[S(ckpt="selective")]),
    "cp 2": dict(cands=[S(cp=2)], nonzero=["cp"]),
    "ep 2 on an MoE layer type": dict(cands=[S(ep=2)], costs=costs_of({0: MOE}), nonzero=["ep"]),
    "pp 2 gpipe": dict(cands=[S(tp=2)], pp=2, chunks=4, nonzero=["pp_bubble", "pp_p2p"]),
    "pp 2 1F1B": dict(cands=[S(tp=2, sp=True)], pp=2, chunks=4, ptype="pipedream_flush",
                      nonzero=["pp_bubble", "pp_p2p"]),
    "pp 2 interleaved vpp 2": dict(cands=[S()], pp=2, chunks=4, vpp=2, nonzero=["pp_bubble"]),
    "pp 2 1F1B interleaved vpp 2": dict(cands=[S()], pp=2, chunks=4, vpp=2,
                                        ptype="pipedream_flush"),
    "pp 2 over a ragged division": dict(cands=[S()], pp=2, chunks=2, layers=7),
    "vocab_tp and embed_sdp": dict(cands=[S(tp=2)], vocab=(2, "zero3"), nonzero=["other_comm"]),
    "vocab_tp, measured fit": dict(cands=[S(tp=2)], vocab=(2, "zero3"), costs=MEASURED),
    "two strategies": dict(cands=[S(), S(tp=2, dp_type="zero3")], budget=2700.0,
                           nonzero=["redistribute"], strategies=2),
    "two strategies under pp 2": dict(cands=[S(), S(tp=2, dp_type="zero3")], pp=2, chunks=2,
                                      budget=5200.0, nonzero=["redistribute"], strategies=2),
    "a stack of two layer types": dict(cands=[S(), S(tp=2)], strategies=None,
                                       costs=costs_of({i: LT if i % 2 else WIDE for i in range(8)})),
    "enc-dec coupled gpipe": dict(cands=[S(tp=2)], pp=2, chunks=4, coupled=True,
                                  costs=costs_of({i: LT if i < 4 else WIDE for i in range(8)})),
    "enc-dec coupled 1F1B": dict(cands=[S(tp=2, sp=True)], pp=2, chunks=4, ptype="pipedream_flush",
                                 coupled=True,
                                 costs=costs_of({i: LT if i < 3 else WIDE for i in range(8)})),
    "three sections, pair-stacked": dict(
        cands=[S()], pp=2, chunks=4, coupled=True, section_pipeline=True,
        costs=costs_of({i: (LT, WIDE, MOE)[i // 4] if i < 8 else MOE for i in range(12)}),
        layers=12),
}


def evaluate(case, monkeypatch):
    kw = PLANS[case]
    costs, layers = kw.get("costs", ONE), kw.get("layers", 8)
    eng = se.SearchEngine(
        costs, HW, num_layers=layers, space=se.SearchSpace(world_size=8),
        memory_budget_mb=kw.get("budget", 20000.0),
        section_pipeline=kw.get("section_pipeline", False))
    monkeypatch.setattr(eng, "_feasible_strategies", lambda pp, bsz, chunks: list(kw["cands"]))
    if "vocab" in kw:
        monkeypatch.setattr(se, "_vocab_strategy_pairs", lambda *a, **k: [kw["vocab"]])
    r = eng.evaluate(kw.get("pp", 1), 32, kw.get("chunks", 1), kw.get("ptype", "gpipe"),
                     vpp=kw.get("vpp", 1))
    assert r is not None, case
    return eng, r, costs


@pytest.mark.parametrize("case", sorted(PLANS))
def test_time_terms_sum_to_the_dps_cost(case, monkeypatch):
    eng, r, _ = evaluate(case, monkeypatch)
    price = eng.price(r)
    assert pr.total_ms(price) == pytest.approx(r.cost_ms, rel=1e-9)
    assert price["basis"]["total_ms"] == pr.total_ms(price)
    t = price["time_ms"]
    assert set(t) - {"pipeline_coupled"} == set(pr.TIME_TERMS)
    assert ("pipeline_coupled" in t) == bool(PLANS[case].get("coupled"))
    assert all(v >= 0.0 for v in t.values()), t
    for term in PLANS[case].get("nonzero", []):
        assert t[term] > 0.0, (term, t)
    if "coupled" in PLANS[case]:
        assert "pipeline_coupled" in price["basis"] and t["pipeline_coupled"] > 0
        assert t["pp_bubble"] == t["pp_p2p"] == 0.0
    want = PLANS[case].get("strategies", 1)
    if want is not None:
        assert len(set(map(str, r.config.layer_strategies))) == want
    if want == 1 and not PLANS[case].get("coupled"):
        assert t["redistribute"] == 0.0
    # hidden terms are beside the total, never in it
    assert set(price["basis"]["hidden_terms"]) == set(cm.HIDDEN_TERMS) == {"dp_hidden", "tp_hidden"}
    # priced once: the same object again, and what save_result will write
    assert eng.price(r) is price is r.details["search_price"]


@pytest.mark.parametrize("case", sorted(PLANS))
def test_memory_terms_sum_to_predicted_train_mb_and_to_the_dps_within_a_unit_a_position(
        case, monkeypatch):
    eng, r, costs = evaluate(case, monkeypatch)
    price = eng.price(r)
    mem = price["memory_mb"]
    assert set(mem) == {"states", "activations", "other", "rings", "transient"}
    assert sum(mem.values()) == predicted_train_mb(costs, None, r.config, 8, 32)
    assert price["basis"]["total_memory_mb"] == sum(mem.values())
    assert (mem["rings"] > 0) == (r.config.pp > 1 and r.config.pipeline_type == "pipedream_flush")
    if PLANS[case].get("coupled"):
        return  # the coupled engines' rings and cotangent buffers are the DP's alone
    # the DP rounds every position up to its unit and charges the transient working
    # set at the smallest tp any CANDIDATE has; the price says by how much they differ
    over = price["basis"]["dp_memory_over_terms_mb"]
    assert over == r.memory_mb - sum(mem.values()) and price["basis"]["dp_memory_mb"] == r.memory_mb
    candidates_transient = cm.transient_overhead_mb(costs, min(s.tp for s in PLANS[case]["cands"]))
    positions = len(pr.device_positions(costs, r.config)[0])
    rounding = over - (candidates_transient - mem["transient"])
    assert -1e-6 <= rounding <= eng.unit * positions + 1e-6, (over, rounding, positions)


@pytest.mark.parametrize("case", sorted(PLANS))
def test_comm_volume_breakdown_is_a_view_of_the_price(case, monkeypatch):
    eng, r, costs = evaluate(case, monkeypatch)
    price = eng.price(r)
    assert cm.comm_volume_breakdown(costs, r.config, 8, 32, "bf16") == price["volume_mb"]
    assert all(v > 0 for v in price["volume_mb"].values())
    # every volume term is priced at a bandwidth the hardware profile has
    for term, gbps in price["basis"]["assumed_gbps"].items():
        assert term in price["volume_mb"] and 20.0 < gbps < 160.0, (term, gbps)


#: what the parent (9323caf) returned, bit for bit: the functions the DP calls
PINS = {
    "layer dp": (lambda: cm.layer_time_cost(LT, S(), HW, 8, 1, 16), "0x1.a666666666667p+3"),
    "layer tp4 sp overlap": (lambda: cm.layer_time_cost(
        LT, S(tp=4, sp=True, tp_overlap=True), HW, 8, 1, 16), "0x1.bc57c57c57c58p+3"),
    "layer tp2 strided full ckpt": (lambda: cm.layer_time_cost(
        LT, S(tp=2, tp_consec=False, ckpt="full"), HW, 8, 1, 16), "0x1.28a3d70a3d70bp+4"),
    "layer cp2 fp32": (lambda: cm.layer_time_cost(LT, S(cp=2), HW, 8, 1, 16, "fp32"),
                       "0x1.a9d0369d0369ep+3"),
    "layer ep2 moe 1f1b replay": (lambda: cm.layer_time_cost(
        MOE, S(ep=2, dp_type="zero2"), HW, 8, 2, 16, "bf16", cm.REMAT_FULL_FACTOR),
        "0x1.e994237fa89e6p+4"),
    "other vtp2 zero3 analytic": (lambda: cm.other_time_cost(
        costs_of({0: LT}), HW, 8, 1, 2, "zero3", 16, "bf16", use_measured=False),
        "0x1.9a0da740da740p+2"),
    "other vtp2 zero3 measured": (lambda: cm.other_time_cost(
        costs_of({0: LT}, measured_vocab_slope_ms={2: 0.2}, measured_vocab_const_ms={2: 0.7},
                 measured_vocab_mp="bf16"), HW, 8, 1, 2, "zero3", 16, "bf16"),
        "0x1.6b00000000000p+2"),
    "other vtp1 ddp pp2": (lambda: cm.other_time_cost(costs_of({0: LT}), HW, 8, 2, 1, "ddp", 16),
                           "0x1.6f8af8af8af8ap+1"),
    "pipeline gpipe": (lambda: cm.pipeline_time_cost([3.0, 4.5], 2.5, 2, 4, HW),
                       "0x1.5400000000000p+4"),
    "pipeline 1f1b vpp2": (lambda: cm.pipeline_time_cost(
        [3.0, 4.5, 2.0, 3.3], 2.5, 4, 8, HW, 2, "pipedream_flush"), "0x1.dc00000000000p+5"),
}


@pytest.mark.parametrize("name", sorted(PINS))
def test_the_functions_the_dp_calls_return_what_the_parent_returned(name):
    fn, want = PINS[name]
    assert fn().hex() == want


def test_volumes_are_the_parents_bit_for_bit():
    hp = HybridParallelConfig(pp=2, layer_strategies=[S(tp=2, dp_type="zero3", ckpt="full")] * 4,
                              chunks=2, vocab_tp=2, embed_dp_type="zero3")
    got = cm.comm_volume_breakdown(costs_of({0: LT}), hp, 8, 16)
    assert {k: v.hex() for k, v in got.items()} == {
        "tp_boundary": "0x1.8000000000000p+8", "dp_grad": "0x1.4000000000000p+7",
        "zero3_gather": "0x1.4000000000000p+6", "embed_dp": "0x1.2c00000000000p+6",
        "vocab_embed": "0x1.0100000000000p+5", "pp_p2p": "0x1.0000000000000p+5"}


@pytest.mark.parametrize("twin", ["layer", "other", "pipeline"])
def test_a_twins_terms_add_up_to_its_total(twin):
    if twin == "layer":
        t = cm.layer_time_terms(MOE, S(tp=2, sp=True, tp_overlap=True, dp_type="zero3", ep=2),
                                HW, 8, 1, 16)
        on_path = sum(v for k, v in t.terms().items() if k not in cm.HIDDEN_TERMS)
        assert on_path == pytest.approx(t.total, rel=1e-12)
        assert t.dp_hidden + t.dp_exposed == pytest.approx(sum(
            t.wire_ms[k] for k in ("dp_grad", "zero3_gather")), rel=1e-12)
        assert t.tp_hidden + t.tp_exposed == pytest.approx(t.wire_ms["tp_boundary"], rel=1e-12)
        assert set(t.volume_mb) == {"tp_boundary", "ep_a2a", "dp_grad", "zero3_gather"}
    elif twin == "other":
        t = cm.other_time_terms(costs_of({0: LT}), HW, 8, 1, 2, "zero3", 16)
        assert t.compute + t.comm == t.total
        assert t.comm == pytest.approx(sum(t.wire_ms.values()), rel=1e-12)
        assert set(t.volume_mb) == {"embed_dp", "vocab_embed"}
    else:
        t = cm.pipeline_time_terms([3.0, 4.5], 2.5, 2, 4, HW, pipeline_type="pipedream_flush")
        assert t.work + t.pp_bubble + t.pp_p2p == pytest.approx(t.total, rel=1e-12)
        assert t.work == 4.5 * 4 and t.pp_p2p == pytest.approx(2.5 / 50.0 * (2 + 4 - 1 + 1))


def test_analytic_costs_say_what_they_rest_on():
    from galvatron_tpu.models.modeling import PRESETS
    from galvatron_tpu.search.theoretical import analytic_model_costs, price_model_plan

    cfg = PRESETS["llama-0.3b"].replace(num_layers=2, attn_impl="flash")
    assert analytic_model_costs(cfg).basis == {
        "costs": "analytic", "peak_tflops": 100.0, "efficiency": 0.4, "compute_tflops": 40.0}
    hp = HybridParallelConfig.uniform(2, tp=2, sp=True, tp_overlap=True)
    basis = price_model_plan(cfg, hp, 4, 8)["basis"]
    assert basis["costs"] == "analytic" and basis["compute_tflops"] == 40.0
    assert basis["fallback_bandwidths"] == ["allreduce_bw"] and basis["overlap_coe"] == 1.1
    # a profile says nothing of a rate: "profiled"
    assert pr.price_plan(ONE, HW, hp, 4, 8)["basis"]["costs"] == "profiled"
    assert pr.price_plan(ONE, HW, hp, 4, 8)["basis"]["fallback_bandwidths"] == []


def test_plan_file_round_trips_search_price(tmp_path, monkeypatch):
    from galvatron_tpu.analysis import plan_check

    eng, r, _ = evaluate("two strategies", monkeypatch)
    path = str(tmp_path / "plan.json")
    eng.save_result(r, path)
    doc = json.load(open(path))
    assert doc["search_price"] == json.loads(json.dumps(r.details["search_price"]))
    assert pr.total_ms(doc["search_price"]) == pytest.approx(doc["search_cost_ms"], rel=1e-9)
    assert "search_price" in plan_check.KNOWN_KEYS
    diags = plan_check.check_plan(path, world_size=8)
    assert not [d for d in diags if d.code == "GTA001"], diags
    assert HybridParallelConfig.load(path).layer_strategies == r.config.layer_strategies
    # flat: the form a --metrics_path record takes
    flat = pr.flat(doc["search_price"])
    assert flat["time_ms.compute"] == doc["search_price"]["time_ms"]["compute"]
    assert flat["basis.hidden_terms"] == "dp_hidden,tp_hidden"
    assert all(isinstance(v, (int, float, str, bool)) for v in flat.values())


def test_search_closes_its_sweep_with_the_search_price_span():
    from galvatron_tpu.obs.tracing import tracer

    eng = se.SearchEngine(ONE, HW, num_layers=4, space=se.SearchSpace(world_size=8, pp_choices=[1]),
                          memory_budget_mb=20000.0)
    tracer.enable()
    try:
        best = eng.search([16], max_chunks=2)
        spans = [s for s in tracer.snapshot() if s.get("ph", "X") == "X"]
    finally:
        tracer.disable()
    names = [s["name"] for s in spans]
    assert names.count("search_price") == 1
    price_span = next(s for s in spans if s["name"] == "search_price")
    sweep = next(s for s in spans if s["name"] == "search_sweep")
    # the sweep's last child: after every search_dp, inside search_sweep
    assert all(s["ts"] + s["dur"] <= price_span["ts"] for s in spans if s["name"] == "search_dp")
    assert sweep["ts"] <= price_span["ts"] and (
        price_span["ts"] + price_span["dur"] <= sweep["ts"] + sweep["dur"])
    assert price_span["args"]["total_ms"] == pytest.approx(best.cost_ms, rel=1e-9)
    assert set(price_span["args"]) >= {"total_ms", "hidden_ms", "memory_mb", "volume_mb"}


def test_check_cost_model_prints_the_twins_columns():
    eng = se.SearchEngine(ONE, HW, num_layers=4, space=se.SearchSpace(world_size=8),
                          memory_budget_mb=20000.0)
    s = S(tp=4, sp=True, tp_overlap=True, dp_type="zero3")
    table = eng.check_cost_model(16, strategies=[s])
    head = next(line for line in table.splitlines() if "states MB" in line)
    assert [c.strip() for c in head.split("|")][4:] == ["compute", "tp", "dp", "time ms"]
    row = [c.strip() for c in table.splitlines()[2].split("|")]
    t = cm.layer_time_terms(LT, s, HW, 8, 1, 16)
    assert [float(c) for c in row[4:]] == [
        round(t.compute, 2), round(t.tp_exposed, 2),
        round(t.overlap_slowdown + t.dp_exposed, 2), round(t.total, 2)]


# ---------------------------------------------------------------------------
# the trainer prices the plan it runs
# ---------------------------------------------------------------------------


def test_the_trainers_own_price_of_the_searched_cells_plan_is_the_documents(tmp_path, capsys):
    """``cli search --analytic_costs 1`` with the searched cell's flags, then the
    trainer's rule on the emitted plan with the document's ``search_price`` set aside."""
    from galvatron_tpu import cli
    from galvatron_tpu.core import trainer
    from galvatron_tpu.core.arguments import (
        hybrid_config_from_args, initialize_galvatron, model_config_from_args, resolve_attn_impl)

    path = str(tmp_path / "plan.json")
    model = ["--model_size", "opt-1.3b", "--seq_length", "2048", "--mixed_precision", "bf16",
             "--attn_impl", "auto"]
    assert cli.main(["search", *model, "--num_devices", "4", "--analytic_costs", "1",
                     "--memory_constraint_gb", "10", "--settle_bsz", "16",
                     "--output_config_path", path]) == 0
    capsys.readouterr()
    doc = json.load(open(path))
    assert pr.total_ms(doc["search_price"]) == pytest.approx(doc["search_cost_ms"], rel=1e-9)
    ns = initialize_galvatron("train", [*model, "--global_train_batch_size", "16",
                                        "--galvatron_config_path", path])
    cfg = resolve_attn_impl(model_config_from_args(ns), ns)
    hp = hybrid_config_from_args(ns, cfg.total_layers, 4)
    # the document's price is taken where it is there, for the batch it was searched at
    taken = trainer._price_plan_as_run(doc, cfg, hp, 4, 16)
    assert taken["basis"]["source"] == "plan_file"
    assert {k: taken[k] for k in ("time_ms", "volume_mb", "memory_mb")} == {
        k: doc["search_price"][k] for k in ("time_ms", "volume_mb", "memory_mb")}
    # set aside (and at another batch: the rule search_cost_ms follows) it prices the plan itself
    own = trainer._price_plan_as_run({k: v for k, v in doc.items() if k != "search_price"},
                                     cfg, hp, 4, 16)
    assert own["basis"]["source"] == "trainer"
    for key in ("time_ms", "volume_mb", "memory_mb"):
        assert own[key] == doc["search_price"][key], key
    beside = ("source", "dp_memory_mb", "dp_memory_over_terms_mb", "dp_memory_unit_mb")
    assert {k: v for k, v in own["basis"].items() if k not in beside} == {
        k: v for k, v in doc["search_price"]["basis"].items() if k not in beside}
    assert trainer._price_plan_as_run(doc, cfg, hp, 4, 32)["basis"]["source"] == "trainer"


TINY = ["--num_layers", "2", "--hidden_size", "32", "--num_heads", "2", "--ffn_dim", "64",
        "--vocab_size", "256", "--seq_length", "16", "--global_train_batch_size", "32",
        "--mixed_precision", "fp32", "--train_iters", "4", "--sdp", "1", "--chunks", "4"]


def _train(tmp, name, **kw):
    from galvatron_tpu.core.arguments import initialize_galvatron
    from galvatron_tpu.core.trainer import train
    from galvatron_tpu.obs import flight

    spans, mpath = str(tmp / f"{name}.spans.json"), str(tmp / f"{name}.jsonl")
    train(initialize_galvatron("train", TINY + ["--trace_spans", spans, "--metrics_path", mpath]),
          verbose=False)
    events = json.load(open(spans))["traceEvents"]
    records = [json.loads(line) for line in open(mpath)]
    return events, records, flight.last_plan_price()


@pytest.fixture(scope="module")
def flag_runs(tmp_path_factory):
    """The trainer on the CPU mesh from flags alone (``--sdp 1 --chunks 4``), twice:
    as it is, and with a model that ``theoretical.py`` cannot price."""
    from galvatron_tpu.search import theoretical

    tmp = tmp_path_factory.mktemp("flag_runs")
    priced = _train(tmp, "priced")
    mp = pytest.MonkeyPatch()

    def refuse(cfg, *a, **k):
        raise ValueError("no analytic costs for this model")

    mp.setattr(theoretical, "analytic_model_costs", refuse)
    try:
        unpriced = _train(tmp, "unpriced")
    finally:
        mp.undo()
    return priced, unpriced


def test_flag_plan_carries_plan_price_on_build_runtime(flag_runs):
    (events, records, last), _ = flag_runs
    (span,) = [e for e in events if e["ph"] == "X" and e["name"] == "build_runtime"]
    price = span["args"]["plan_price"]
    assert price["basis"]["source"] == "trainer" and price["basis"]["costs"] == "analytic"
    assert price["basis"]["chunks"] == 4 and price["basis"]["world"] == 8
    assert price["volume_mb"].keys() == {"dp_grad", "zero3_gather", "embed_dp"}
    assert price["time_ms"]["dp_hidden"] > 0 and price["time_ms"]["tp_exposed"] == 0.0
    # beside the keys the span had
    assert {"tp_overlap_seams", "layer_kinds"} <= set(span["args"])
    # its own child span says what the pricing cost
    (child,) = [e for e in events if e["ph"] == "X" and e["name"] == "plan_price"]
    assert span["ts"] <= child["ts"] and child["ts"] + child["dur"] <= span["ts"] + span["dur"]
    # the process's accessor answers the same dict
    assert last == price


def test_flag_plan_logs_one_plan_price_record(flag_runs):
    (_, records, last), _ = flag_runs
    (rec,) = [r for r in records if r["event"] == "plan_price"]
    assert {k: v for k, v in rec.items() if k not in ("event", "ts")} == pr.flat(last)
    assert records.index(rec) < min(i for i, r in enumerate(records) if r["event"] == "train_iter")


def test_flag_plan_has_step_time_drift_now(flag_runs):
    (_, records, last), _ = flag_runs
    iters = [r for r in records if r["event"] == "train_iter"]
    predicted = last["basis"]["total_ms"]
    assert len(iters) == 4
    for r in iters[1:]:
        assert r["step_time_drift"] == pytest.approx(
            (r["iter_ms"] - predicted) / predicted, rel=1e-3)


def test_a_model_that_cannot_be_priced_gives_the_error_arg_and_the_same_run(flag_runs):
    (_, records, _), (events, unpriced, last) = flag_runs
    (span,) = [e for e in events if e["ph"] == "X" and e["name"] == "build_runtime"]
    assert span["args"]["plan_price"] == last == {
        "error": "ValueError: no analytic costs for this model"}
    (rec,) = [r for r in unpriced if r["event"] == "plan_price"]
    assert rec["error"] == "ValueError: no analytic costs for this model"
    ours = [r for r in unpriced if r["event"] == "train_iter"]
    theirs = [r for r in records if r["event"] == "train_iter"]
    # otherwise the parent's run: no drift gauge, the same losses step for step
    assert all(r.get("step_time_drift") is None for r in ours)
    assert [r["loss"] for r in ours] == [r["loss"] for r in theirs]
    assert np.isfinite([r["loss"] for r in ours]).all()


def test_nothing_is_priced_without_a_tracer_or_a_metrics_path(tmp_path):
    from galvatron_tpu.core.arguments import initialize_galvatron
    from galvatron_tpu.core.trainer import train
    from galvatron_tpu.obs import flight

    flight.note_plan_price({"stale": True})
    train(initialize_galvatron("train", TINY[:-6] + ["--train_iters", "1"]), verbose=False)
    assert flight.last_plan_price() is None
