"""The price of ONE whole plan, term by term: what the search's cost model
charges a ``HybridParallelConfig`` in time, in traffic and in memory, through
the same functions the DP's tables are filled from.

``price_plan`` is read by the search (``SearchEngine.save_result`` writes it
into the plan file as ``search_price``), by the trainer (which prices the plan
it runs, whatever its source, and keeps it as ``plan_price``), by
``analysis/comm_audit.py`` (``cost_model.comm_volume_breakdown`` is
``plan_volume_mb``) and by ``memory_fidelity.predicted_train_mb`` (the sum of
``memory_mb``).  Its terms:

``time_ms`` — per device per iteration.  On the critical path, summing to
``SearchEngine.evaluate(...).cost_ms`` for the plan the search returns:
``compute``, ``overlap_slowdown``, ``dp_exposed``, ``tp_exposed``, ``cp``,
``ep`` (``cost_model.layer_time_terms``, over the layers one device holds),
``redistribute`` (``dynamic_programming.transition_cost_ms`` between adjacent
layers of unequal strategy), ``other_compute``, ``other_comm``
(``cost_model.other_time_terms``), ``pp_bubble``, ``pp_p2p``
(``cost_model.pipeline_time_terms``) and, for the coupled enc-dec / K-section
schedules alone, ``pipeline_coupled`` (what ``coupled_pipeline_time_cost`` adds
over the stage's own sum: their fill, drain and p2p in one term).  Off the
critical path (``cost_model.HIDDEN_TERMS``): ``dp_hidden``, ``tp_hidden``.

``volume_mb`` — on-wire MB per device per iteration by ``tp_boundary``,
``cp_ring``, ``ep_a2a``, ``dp_grad``, ``zero3_gather``, ``embed_dp``,
``vocab_embed``, ``pp_p2p``.

``memory_mb`` — ``states``, ``activations`` (the heaviest stage's layers),
``other`` (embed / head / loss), ``rings`` (the 1F1B engines' per-device
constants), ``transient``.

``basis`` — what the prices rest on.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy, balanced_division
from galvatron_tpu.search.cost_model import (
    HIDDEN_TERMS,
    REMAT_FULL_FACTOR,
    ProfiledHardware,
    ProfiledLayerType,
    ProfiledModelCosts,
    _add_mb,
    coupled_pipeline_time_cost,
    layer_memory_cost,
    layer_time_terms,
    other_memory_cost,
    other_time_terms,
    pipeline_time_terms,
    single_1f1b_rings_mb,
    transient_overhead_mb,
)

#: the order the tables print the time terms in
TIME_TERMS = ("compute", "overlap_slowdown", "dp_exposed", "dp_hidden", "tp_exposed",
              "tp_hidden", "cp", "ep", "redistribute", "other_compute", "other_comm",
              "pp_bubble", "pp_p2p")


def layer_type_of(costs: ProfiledModelCosts, i: int) -> ProfiledLayerType:
    """Layer ``i``'s profiled type: its own where the profile carries several
    (a hybrid stack, an enc-dec model, a vision pyramid), else the one."""
    lts = costs.layer_types
    return lts.get(i, lts[0]) if len(lts) > 1 else lts[0]


def type_groups(costs: ProfiledModelCosts, num_layers: int) -> List[list]:
    """Contiguous ``[start, count, layer_type]`` runs over layer indices.
    Grouped by VALUE equality — JSON-loaded profiles materialize a fresh
    ProfiledLayerType per index, so identity would split every layer."""
    groups: List[list] = []
    for i in range(num_layers):
        lt = layer_type_of(costs, i)
        if groups and groups[-1][2] == lt:
            groups[-1][1] += 1
        else:
            groups.append([i, 1, lt])
    return groups


def total_ms(price: Dict) -> float:
    """The plan's predicted step: the critical-path terms of ``time_ms``."""
    return sum(v for k, v in price["time_ms"].items() if k not in HIDDEN_TERMS)


def total_memory_mb(price: Dict) -> float:
    return sum(price["memory_mb"].values())


def flat(price: Dict, prefix: str = "") -> Dict[str, object]:
    """A price as one level of scalars (``time_ms.compute``,
    ``basis.assumed_gbps.tp_boundary``; a list joined by commas): the form a
    ``--metrics_path`` record takes, whose fields are scalars."""
    out: Dict[str, object] = {}
    for key, value in price.items():
        if isinstance(value, dict):
            out.update(flat(value, f"{prefix}{key}."))
        else:
            out[prefix + key] = ",".join(map(str, value)) if isinstance(value, (list, tuple)) else value
    return out


def _plan_traffic(costs, hw, hp, world: int, global_bsz: int, mixed_precision: str):
    """``(volume_mb, wire_ms)`` of a plan by comm term: on-wire MB per device per
    iteration, and the ms that traffic takes at ``hw``'s bandwidths before any
    overlap credit.  Every layer of the plan at its own strategy and the
    lowest-numbered layer type's sizes; a forward-replaying schedule's repeated
    collectives are not counted (``recompute_factor`` left out), as
    ``analysis/comm_audit.py``'s tolerance was set."""
    volume: Dict[str, float] = {}
    wire: Dict[str, float] = {}
    lt = costs.layer_types[min(costs.layer_types)] if costs.layer_types else None
    pp = hp.pp
    parts = [] if lt is None else [
        layer_time_terms(lt, s, hw, world, pp, global_bsz, mixed_precision)
        for s in hp.layer_strategies]
    parts.append(other_time_terms(
        costs, hw, world, pp, max(1, hp.vocab_tp), hp.embed_dp_type, global_bsz,
        mixed_precision, use_measured=False))
    for part in parts:
        for term, mb in part.volume_mb.items():
            _add_mb(volume, term, mb)
        for term, ms in part.wire_ms.items():
            _add_mb(wire, term, ms)
    if pp > 1 and lt is not None:
        # per-iteration per-device boundary p2p: every micro-batch crosses
        # each boundary fwd (activation out) and bwd (grad in), so chunks ×
        # the per-tick message = the full local batch, twice. (The TIME of
        # pp_p2p prices the micro-batch undivided by dp: SearchEngine.
        # _boundary_msg_mb; the two statements differ by dp, PERF.md section 7.)
        s0 = hp.layer_strategies[0]
        dp0 = max(1, world // (pp * s0.tp * max(1, s0.cp)))
        f = 0.5 if mixed_precision in ("bf16", "fp16") else 1.0
        _add_mb(volume, "pp_p2p",
                2.0 * lt.boundary_activation_mb_per_sample * (global_bsz / dp0) * f)
        _add_mb(wire, "pp_p2p", volume.get("pp_p2p", 0.0) / hw.p2p(pp))
    return volume, wire


def plan_volume_mb(
    costs: ProfiledModelCosts,
    hp: HybridParallelConfig,
    world: int,
    global_bsz: int,
    mixed_precision: str = "bf16",
) -> Dict[str, float]:
    """``price_plan(...)["volume_mb"]`` alone (no bandwidth enters a volume):
    what ``cost_model.comm_volume_breakdown`` returns."""
    return _plan_traffic(costs, ProfiledHardware(), hp, world, global_bsz, mixed_precision)[0]


def _heaviest(division: List[int]) -> Tuple[int, int]:
    """(first stage that holds the most layers, its offset in layers)."""
    st = max(range(len(division)), key=lambda i: (division[i], -i))
    return st, sum(division[:st])


def device_positions(
    costs: ProfiledModelCosts, hp: HybridParallelConfig, section_pipeline: bool = False,
) -> Tuple[List[Tuple[ProfiledLayerType, LayerStrategy]], int, Optional[Tuple[bool, list]]]:
    """The stack positions ONE device runs, as the DP lays them out
    (``SearchEngine._evaluate``): ``(positions, layers a position stands for,
    coupled)``.  pp = 1: every layer.  pp > 1, one layer type: the positions of
    the stage that holds the most layers (interleaved: of one virtual stage; the
    device runs ``vpp`` of them).  pp > 1, several types: one virtual stage of EACH sub-stack
    (``coupled`` = (K-section schedule?, the sub-stacks' boundary MB a sample))."""
    strategies = list(hp.layer_strategies)
    L, pp, vpp = len(strategies), hp.pp, max(1, hp.vpp)
    if pp == 1:
        return [(layer_type_of(costs, i), s) for i, s in enumerate(strategies)], 1, None
    if len(costs.layer_types) <= 1:
        lt = layer_type_of(costs, 0)
        if vpp > 1:  # one virtual stage's positions; price_plan counts each vpp times
            return [(lt, s) for s in strategies[: L // (pp * vpp)]], 1, None
        division = list(hp.pp_division) if hp.pp_division else balanced_division(L, pp)
        st, off = _heaviest(division)
        return [(lt, s) for s in strategies[off: off + division[st]]], 1, None
    groups = type_groups(costs, L)
    if len(groups) == 2 and not section_pipeline:
        (_, n_enc, lt_enc), (_, n_dec, lt_dec) = groups
        division = list(hp.pp_division) if hp.pp_division else []
        div_e = division[:pp] if len(division) == 2 * pp else balanced_division(n_enc, pp)
        div_d = division[pp:] if len(division) == 2 * pp else balanced_division(n_dec, pp)
        positions = []
        for lt, div, base in ((lt_enc, div_e, 0), (lt_dec, div_d, n_enc)):
            st, off = _heaviest(div)
            positions += [(lt, s) for s in strategies[base + off: base + off + div[st]]]
        boundaries = [lt_enc.boundary_activation_mb_per_sample,
                      lt_dec.boundary_activation_mb_per_sample]
        return positions, 1, (False, boundaries)
    # pair-stacked sections (pipeline_swin.SwinLayout): section-major, stage-major
    # within a section, two layers a pair sharing one strategy
    from galvatron_tpu.parallel.pipeline_swin import _spread_pairs

    positions, base = [], 0
    for _, cnt, lt in groups:
        div = _spread_pairs(cnt // 2, pp)
        st, off = _heaviest(div)
        positions += [(lt, strategies[base + 2 * (off + q)]) for q in range(div[st])]
        base += cnt
    return positions, 2, (True, [lt.boundary_activation_mb_per_sample for _, _, lt in groups])


def plan_memory_mb(
    costs: ProfiledModelCosts, hp: HybridParallelConfig, world: int, global_bsz: int,
) -> Dict[str, float]:
    """``price_plan(...)["memory_mb"]`` alone (no bandwidth enters it): the
    per-device MB the search charges this plan, by term: the heaviest stage's
    (positions x layer_memory_cost) + the embed/head/loss 'other' term
    (replicated over pp in this runtime, so charged on every stage) + the
    single-stack / interleaved 1F1B engines' per-device constants + the one
    transient working set."""
    pp, L = hp.pp, len(hp.layer_strategies)
    division = list(hp.pp_division) if hp.pp_division else balanced_division(L, pp)
    best, off = None, 0
    for st in range(pp):
        states = acts = 0.0
        for j in range(division[st]):
            mc = layer_memory_cost(
                layer_type_of(costs, off + j), hp.layer_strategies[off + j], world, pp,
                global_bsz, hp.chunks, stage_idx=st, pipeline_type=hp.pipeline_type,
                mixed_precision=hp.mixed_precision, vpp=hp.vpp,
            )
            states += mc.states_mb
            acts += mc.activation_mb
        off += division[st]
        if best is None or states + acts > best[0] + best[1]:
            best = (states, acts)
    rings = 0.0
    if pp > 1 and hp.pipeline_type == "pipedream_flush":
        # THE SAME pricing evaluate() charges, not a re-derivation that could drift
        rings = single_1f1b_rings_mb(
            layer_type_of(costs, 0), hp.layer_strategies[0], world, pp, global_bsz,
            hp.chunks, hp.mixed_precision, vpp=max(1, hp.vpp),
            layers_per_device=max(division),
        )
    return {
        "states": best[0],
        "activations": best[1],
        "other": other_memory_cost(
            costs, world, pp, hp.vocab_tp, hp.embed_dp_type, global_bsz, hp.chunks,
            hp.mixed_precision,
        ),
        "rings": rings,
        "transient": transient_overhead_mb(
            costs, min(s.tp for s in hp.layer_strategies), hp.mixed_precision
        ),
    }


def price_plan(
    costs: ProfiledModelCosts,
    hw: ProfiledHardware,
    hp: HybridParallelConfig,
    world: int,
    global_bsz: int,
    mixed_precision: str = "bf16",
    *,
    use_measured_vocab: bool = True,
    section_pipeline: bool = False,
) -> Dict[str, Dict]:
    """``{"time_ms", "volume_mb", "memory_mb", "basis"}`` of one plan (the
    module's docstring names every term).  ``use_measured_vocab`` /
    ``section_pipeline``: the two things a ``SearchEngine`` knows beyond its
    costs (whether its sweep consumed the measured vocab fit; whether two
    layer types are a vision pyramid and not an enc-dec model)."""
    from galvatron_tpu.search.dynamic_programming import transition_cost_ms

    pp, vpp, chunks = hp.pp, max(1, hp.vpp), hp.chunks
    positions, pos_layers, coupled = device_positions(costs, hp, section_pipeline)
    # EVERY pipedream_flush engine recomputes its (virtual) stage forward from the
    # stashed input in the backward tick, whatever the layer's own ckpt setting
    recompute = REMAT_FULL_FACTOR if pp > 1 and hp.pipeline_type == "pipedream_flush" else None
    mult = pos_layers * (vpp if pp > 1 else 1)
    time_ms = {term: 0.0 for term in TIME_TERMS}
    stage_ms = 0.0  # what the DP's tables hold for these positions, summed
    for lt, s in positions:
        layer = layer_time_terms(
            lt, s, hw, world, pp, global_bsz, mixed_precision, recompute_factor=recompute
        )
        for term, ms in layer.terms().items():
            time_ms[term] += mult * ms
        stage_ms += mult * layer.total
    lt0 = layer_type_of(costs, 0)
    inter = sum(
        transition_cost_ms(a, b, lt0, hw, world, pp, global_bsz, mixed_precision)
        for (_, a), (_, b) in zip(positions, positions[1:])
    ) * (vpp if pp > 1 else 1)
    time_ms["redistribute"] = inter
    stage_ms += inter
    if coupled is not None:
        sections, boundaries = coupled
        time_ms["pipeline_coupled"] = coupled_pipeline_time_cost(
            stage_ms / chunks, boundaries, pp, chunks, hw, global_bsz,
            pipeline_type=hp.pipeline_type, mixed_precision=mixed_precision,
            sections=sections,
        ) - stage_ms
    elif pp > 1:
        f = 0.5 if mixed_precision in ("bf16", "fp16") else 1.0
        pipe = pipeline_time_terms(
            [stage_ms / chunks] * pp,
            lt0.boundary_activation_mb_per_sample * (global_bsz / chunks) * f,
            pp, chunks, hw, vpp=vpp, pipeline_type=hp.pipeline_type,
        )
        time_ms["pp_bubble"], time_ms["pp_p2p"] = pipe.pp_bubble, pipe.pp_p2p
    other = other_time_terms(
        costs, hw, world, pp, max(1, hp.vocab_tp), hp.embed_dp_type, global_bsz,
        mixed_precision, use_measured=use_measured_vocab,
    )
    time_ms["other_compute"], time_ms["other_comm"] = other.compute, other.comm

    volume_mb, wire_ms = _plan_traffic(costs, hw, hp, world, global_bsz, mixed_precision)
    price = {
        "time_ms": time_ms,
        "volume_mb": volume_mb,
        "memory_mb": plan_memory_mb(costs, hp, world, global_bsz),
        "basis": {
            # how the costs were made: theoretical.analytic_model_costs says so of
            # its own; a profile (in-process or loaded) carries no such note
            **({"costs": "profiled"} | dict(costs.basis)),
            # bandwidths priced from ProfiledHardware's defaults, not measured
            "fallback_bandwidths": hw.fallback_sources(pp),
            "overlap_coe": hw.overlap_coe,
            # GB/s each volume term was priced at (MB over ms), volume-weighted
            "assumed_gbps": {term: volume_mb[term] / ms for term, ms in wire_ms.items()
                             if ms > 0 and term in volume_mb},
            "world": world,
            "global_bsz": global_bsz,
            "chunks": chunks,
            "hidden_terms": list(HIDDEN_TERMS),
        },
    }
    if coupled is not None:
        price["basis"]["pipeline_coupled"] = (
            "fill, drain and p2p of the coupled schedule in one term: "
            "coupled_pipeline_time_cost less the stage's own sum")
    price["basis"]["total_ms"] = total_ms(price)
    price["basis"]["total_memory_mb"] = total_memory_mb(price)
    return price
