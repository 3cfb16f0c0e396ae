"""Memory-fidelity harness: MemoryCost predictions vs compiled reality.

The memory side of the cost model decides DP *feasibility* — a strategy
mis-priced in MB silently deletes or falsely admits candidates — so its
terms must be validated against what XLA actually allocates, the way the
time side has its closed ``check_cost_model``/``validate_top_k`` loop
(reference bar: the MemoryCostModel ratio-curve *fits*,
galvatron/core/cost_model.py:56-60 — they fit theirs to measurement; ours
must be at least as grounded).

Measured side: the production ``train_step`` is AOT-compiled against a
device-less TPU **topology** (``jax.experimental.topologies``, e.g.
``v5e:2x4``) and the real TPU compiler's buffer assignment is read via
``memory_analysis()`` — authoritative per-device numbers, no chips needed.
The 8-device CPU simulation is NOT usable for this: its ``memory_analysis``
aggregates across all addressable devices and models none of the TPU
backend's buffer reuse.

Predicted side: the search's own pricing — ``layer_memory_cost`` summed over
the heaviest stage + ``other_memory_cost`` — so the harness validates
exactly what the DP consumes.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List, Optional

from galvatron_tpu.core.strategy import HybridParallelConfig
from galvatron_tpu.search.cost_model import ProfiledModelCosts


# single-host topologies this module knows how to declare to libtpu:
# topology_name → (TPU_ACCELERATOR_TYPE, TPU_CHIPS_PER_HOST_BOUNDS)
_DECLARABLE_TOPOLOGIES = {
    "v5e:2x4": ("v5litepod-8", "2,4,1"),
}


def declare_local_tpu_topology_env(topology: str = "v5e:2x4") -> None:
    """Declare a single-host TPU topology to libtpu via the environment.

    Off GCE, libtpu's topology init retries the GCP metadata server for
    MINUTES (403s) before giving up and proceeding anyway — every
    ``get_topology_desc`` caller pays it, which is most of what a
    topology-AOT test costs.  Declaring the topology up front makes init
    instant.  ``setdefault`` throughout: a real pod's own environment always
    wins.  The MDS skip and the accelerator type must be set TOGETHER —
    type alone SIGILLs libtpu.

    Deliberately a no-op on hosts with local TPU devices (``/dev/accel*`` /
    ``/dev/vfio``): there libtpu's own metadata/env path is authoritative,
    and a declared shape that disagrees with the real machine would poison
    every later backend init in this process (and in forked children).
    Also a no-op for topologies outside ``_DECLARABLE_TOPOLOGIES`` — a
    v5e-8 declaration under a ``v4:...`` request would be a lie libtpu
    acts on."""
    import glob

    if glob.glob("/dev/accel*") or os.path.exists("/dev/vfio"):
        return
    spec = _DECLARABLE_TOPOLOGIES.get(topology)
    if spec is None:
        return
    accelerator_type, chip_bounds = spec
    if os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1") != "1":
        return
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", accelerator_type)
    os.environ.setdefault("TPU_CHIPS_PER_HOST_BOUNDS", chip_bounds)
    os.environ.setdefault("TPU_HOST_BOUNDS", "1,1,1")
    os.environ.setdefault("TPU_WORKER_ID", "0")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")


@dataclass
class FidelityRow:
    label: str
    predicted_mb: float
    measured_mb: float
    # measured decomposition (MB/device): state (arguments minus batch,
    # outputs aliased away), temps (grads + activations + scratch)
    state_mb: float
    temp_mb: float

    @property
    def ratio(self) -> float:
        return self.predicted_mb / max(self.measured_mb, 1e-9)


def predicted_train_mb(
    costs: ProfiledModelCosts,
    cfg,
    hp: HybridParallelConfig,
    world: int,
    global_bsz: int,
) -> float:
    """Per-device MB the search would charge this config: the sum of
    ``price.plan_memory_mb``'s terms (what ``price_plan`` returns as
    ``memory_mb``): the heaviest stage's (positions x layer_memory_cost), the
    embed/head/loss 'other' term, the 1F1B engines' per-device constants and
    the transient working set.  ``cfg`` is not read: the plan names its layers."""
    from galvatron_tpu.search.price import plan_memory_mb

    return sum(plan_memory_mb(costs, hp, world, global_bsz).values())


def measured_train_mb(
    cfg,
    hp: HybridParallelConfig,
    global_bsz: int,
    seq: Optional[int] = None,
    topology: str = "v5e:2x4",
) -> Optional[dict]:
    """AOT-compile the production train step against the TPU topology and
    read the per-device plan: state = arguments + outputs − aliased (the
    donated train state counts once), temp = scratch (grads + activations).
    Returns None where topology AOT is unavailable (no libtpu)."""
    import jax
    import jax.numpy as jnp

    try:
        from jax.experimental import topologies

        declare_local_tpu_topology_env(topology)
        topo = topologies.get_topology_desc(platform="tpu", topology_name=topology)
    except Exception:
        return None
    from galvatron_tpu.core.checkpoint import abstract_state_of
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.parallel.hybrid import build_runtime
    from galvatron_tpu.parallel.mesh import build_mesh

    seq = seq or cfg.max_seq_len
    mesh, axes = build_mesh(pp=hp.pp, devices=list(topo.devices))
    rt = build_runtime(
        cfg, hp, mesh=mesh, axes=axes, adam=AdamConfig(lr=1e-3),
        global_batch_size=global_bsz, seq_len=seq,
    )
    from galvatron_tpu.models.modeling import batch_row_width

    batch = jax.ShapeDtypeStruct(
        (global_bsz, batch_row_width(cfg, seq)),
        jnp.int32, sharding=rt.batch_sharding,
    )
    ma = rt.train_step.lower(abstract_state_of(rt), batch).compile().memory_analysis()
    if ma is None:
        return None
    state = (
        ma.argument_size_in_bytes
        + ma.output_size_in_bytes
        - ma.alias_size_in_bytes
    ) / 1e6
    temp = ma.temp_size_in_bytes / 1e6
    return {"state_mb": state, "temp_mb": temp, "total_mb": state + temp}


def calibrate_costs(
    cfg,
    costs: ProfiledModelCosts,
    global_bsz: int = 16,
    tps=(1, 2),
    topology: str = "v5e:2x4",
) -> Optional[ProfiledModelCosts]:
    """Replace the activation table with TOPOLOGY-MEASURED values — the
    production basis (profiling/model.py measures activations; the analytic
    table only seeds searches before any profiling exists).

    Per-layer per-sample activation at degree tp isolated by the DOUBLE
    difference of compiled temp bytes over (num_layers, batch): layer-count
    difference removes embed/head/loss temps, batch difference removes
    batch-independent transients (casts, per-layer grads) — the same
    difference method the reference's profiler uses on real runs
    (galvatron/core/profiler.py:243-401). Returns None where topology AOT
    is unavailable."""
    import dataclasses as _dc

    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy

    world = 8
    act = {}
    for tp in tps:
        t = {}
        for L in (2, 4):
            for bsz in (global_bsz, 2 * global_bsz):
                c = cfg.replace(num_layers=L)
                h = HybridParallelConfig(
                    layer_strategies=[LayerStrategy(tp=tp)] * L,
                    vocab_tp=tp, mixed_precision="bf16",
                )
                m = measured_train_mb(c, h, bsz, topology=topology)
                if m is None:
                    return None
                t[(L, bsz)] = m["temp_mb"]
        dp = world // tp
        d_samples = global_bsz / dp  # extra samples/device at the 2x batch
        per_layer = (
            (t[(4, 2 * global_bsz)] - t[(2, 2 * global_bsz)])
            - (t[(4, global_bsz)] - t[(2, global_bsz)])
        ) / (2 * d_samples)
        act[tp] = max(per_layer, 0.01)
    lt = costs.layer_types[0]
    new_lt = _dc.replace(lt, activation_mb_per_sample=act)
    return _dc.replace(costs, layer_types={0: new_lt})


def fidelity_row(
    label: str,
    costs: ProfiledModelCosts,
    cfg,
    hp: HybridParallelConfig,
    global_bsz: int,
    world: int = 8,
    topology: str = "v5e:2x4",
) -> Optional[FidelityRow]:
    meas = measured_train_mb(cfg, hp, global_bsz, topology=topology)
    if meas is None:
        return None
    pred = predicted_train_mb(costs, cfg, hp, world, global_bsz)
    return FidelityRow(
        label=label,
        predicted_mb=pred,
        measured_mb=meas["total_mb"],
        state_mb=meas["state_mb"],
        temp_mb=meas["temp_mb"],
    )


def format_rows(rows: List[FidelityRow]) -> str:
    out = [
        f"{'cell':<34} {'pred MB':>9} {'meas MB':>9} {'state':>8} {'temp':>8} {'ratio':>6}"
    ]
    for r in rows:
        out.append(
            f"{r.label:<34} {r.predicted_mb:>9.1f} {r.measured_mb:>9.1f} "
            f"{r.state_mb:>8.1f} {r.temp_mb:>8.1f} {r.ratio:>6.3f}"
        )
    return "\n".join(out)
