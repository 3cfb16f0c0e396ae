"""HLO collective auditor: static comm-footprint extraction, plan-vs-lowered
fidelity gates, and resharding lint.

The cost model (search/cost_model.py) prices per-term communication volumes
that drive the whole strategy search, yet nothing checks those terms against
what XLA actually lowers — and Alpa/GSPMD both report that *silent resharding
inserted by the SPMD partitioner* is the dominant source of surprise comm
volume.  This module closes the static half of that loop with zero execution
and zero devices:

1. **Footprint extraction** (``extract_footprint``): AOT-``lower`` every
   registered program of a (plan × ModelConfig × mesh) via the aot registry
   (abstract inputs only) and walk the StableHLO text.  Two tiers, because
   the two lowering paths leave different evidence:

   - shard_map programs (pipeline engines, tp_overlap collective-matmul,
     ring CP) lower EXPLICIT ``stablehlo.all_reduce`` / ``all_gather`` /
     ``reduce_scatter`` / ``all_to_all`` / ``collective_permute`` ops with
     replica groups and per-shard tensor types → parsed into
     :class:`CollectiveSite` (kind, bytes, replica-group → mesh-axis
     attribution, call-site count, inside-a-loop flag);
   - the GSPMD (pp=1 jit) path lowers NO collectives — only
     ``mhlo.sharding`` entry annotations and ``custom_call @Sharding``
     constraints; those become :class:`ShardingSite` records (tile counts,
     replicated tails) — the evidence the resharding lint and the
     annotation-basis fidelity terms work from.

2. **Fidelity gate** (``fidelity_report``): per plan term, compare the cost
   model's analytic volume (``cost_model.comm_volume_breakdown``, replaying
   the model's OWN constants) against a volume re-derived here from the
   program's *actual* abstract parameter/batch shapes and lowered
   collectives using independent first-principles constants.  A
   ``predicted_over_lowered`` ratio outside the tolerance band is a
   ``GTC001``; a mispriced cost-model constant moves only the predicted
   side and trips the gate in CI instead of surfacing later as an
   unexplained step-time regression.

3. **Resharding lint** (``resharding_lint``): diagnose comm the plan never
   asked for — fully-replicated lowerings of plan-sharded tensors (GTC010,
   generalizing GTA016 from abstract shardings to lowered reality),
   boundary resharding seams a uniform plan never declared (GTC011),
   tp_overlap layers whose lowering still contains the monolithic
   collective the decomposed matmul was supposed to replace (GTC012), and
   collectives on mesh-axis groups no plan term owns (GTC003).

Everything runs under ``JAX_PLATFORMS=cpu`` with a forced host-device world
(``aot.warmup.force_cpu_world``): ``lower()`` only — never ``compile()``,
never execute.
"""

from __future__ import annotations

import json
import re
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from galvatron_tpu.analysis.diagnostics import Diagnostic

# ---------------------------------------------------------------------------
# StableHLO text parsing
# ---------------------------------------------------------------------------

# MLIR element types → bytes (the subset this runtime emits)
DTYPE_BYTES = {
    "f64": 8.0, "f32": 4.0, "bf16": 2.0, "f16": 2.0,
    "f8E4M3FN": 1.0, "f8E5M2": 1.0,
    "i64": 8.0, "ui64": 8.0, "i32": 4.0, "ui32": 4.0,
    "i16": 2.0, "ui16": 2.0, "i8": 1.0, "ui8": 1.0, "i1": 1.0,
}

COLLECTIVE_KINDS = (
    "all_reduce", "all_gather", "reduce_scatter", "all_to_all",
    "collective_permute",
)

_TENSOR_RE = re.compile(r"tensor<((?:\d+x)*)([a-zA-Z][0-9A-Za-z]*)>")
_COLL_RE = re.compile(r"stablehlo\.(%s)\b" % "|".join(COLLECTIVE_KINDS))
# the operand type of a lowered op: `... : (tensor<...>) -> ...` — the
# parenthesis distinguishes it from attribute types like
# `replica_groups = dense<...> : tensor<2x4xi64>` on the same line
_OPERAND_RE = re.compile(r":\s*\((tensor<[^>]*>)")
_GROUPS_RE = re.compile(
    r"(?:replica_groups|source_target_pairs)\s*=\s*dense<(\[\[.*?\]\]|\[\]|[-0-9]+)>"
    r"\s*:\s*tensor<([0-9x]+)i64>"
)
_SHARDING_ATTR_RE = re.compile(r'mhlo\.sharding\s*=\s*"([^"]*)"')
_DEVICES_RE = re.compile(r"devices=\[([0-9,]+)\]")
_ARG_RE = re.compile(
    r"%arg\d+:\s*(tensor<[^>]*>)\s*\{[^}]*mhlo\.sharding\s*=\s*\"([^\"]*)\""
)


def parse_tensor_type(text: str) -> Optional[Tuple[Tuple[int, ...], str, float]]:
    """First ``tensor<...>`` in ``text`` → ``(shape, dtype, MB)``.  None if
    absent or the element type is unknown (tuple/token/dynamic types)."""
    m = _TENSOR_RE.search(text)
    if not m:
        return None
    dims, dtype = m.group(1), m.group(2)
    if dtype not in DTYPE_BYTES:
        return None
    shape = tuple(int(d) for d in dims.split("x") if d)
    n = 1
    for d in shape:
        n *= d
    return shape, dtype, n * DTYPE_BYTES[dtype] / 1e6


def parse_groups(text: str) -> Optional[Tuple[Tuple[int, ...], ...]]:
    """``replica_groups``/``source_target_pairs`` dense attr → group tuples.
    Handles the splat form (``dense<0> : tensor<1x1xi64>``)."""
    m = _GROUPS_RE.search(text)
    if not m:
        return None
    body = m.group(1)
    dims = [int(d) for d in m.group(2).split("x") if d]
    if body.startswith("["):
        try:
            rows = json.loads(body)
        except ValueError:
            return None
        return tuple(tuple(int(v) for v in row) for row in rows)
    v = int(body)  # splat: one value broadcast over the dense shape
    rows, cols = (dims + [1, 1])[:2]
    return tuple(tuple(v for _ in range(cols)) for _ in range(rows))


@dataclass(frozen=True)
class ShardingInfo:
    """One parsed ``mhlo.sharding`` attribute."""

    raw: str
    tile: Tuple[int, ...] = ()  # per-dim tile counts (replicated tail dropped)
    replicated: bool = False

    @property
    def sharded(self) -> bool:
        return any(t > 1 for t in self.tile)


def parse_sharding_attr(raw: str) -> ShardingInfo:
    """``{devices=[4,2,1]<=[8]}`` / ``{replicated}`` / ``{maximal ...}`` →
    structured tile counts.  ``last_tile_dim_replicate`` marks the trailing
    tile entry as a replication factor, not a tensor-dim shard."""
    raw = raw.strip()
    if "replicated" in raw and "last_tile" not in raw:
        return ShardingInfo(raw=raw, replicated=True)
    m = _DEVICES_RE.search(raw)
    if not m:
        return ShardingInfo(raw=raw, replicated="maximal" not in raw)
    tile = tuple(int(v) for v in m.group(1).split(","))
    if "last_tile_dim_replicate" in raw and tile:
        tile = tile[:-1]
    if any(t > 1 for t in tile):
        return ShardingInfo(raw=raw, tile=tile)
    return ShardingInfo(raw=raw, tile=tile, replicated=True)


@dataclass(frozen=True)
class CollectiveSite:
    """One explicit collective op in the lowered text (identical sites
    collapsed via ``count``).  ``tensor_mb`` is the operand's MB as lowered —
    inside a shard_map region that is the PER-DEVICE shard."""

    kind: str
    shape: Tuple[int, ...]
    dtype: str
    tensor_mb: float
    groups: Tuple[Tuple[int, ...], ...]
    group_size: int
    axes: Tuple[str, ...] = ()  # attributed mesh axes; () = unattributed
    in_loop: bool = False
    count: int = 1

    @property
    def wire_mb(self) -> float:
        """Per-participant on-wire MB per execution of this site × count.
        Ring conventions, per device: all_reduce moves 2(g-1)/g × operand;
        all_gather's operand is the SHARD and each device receives g-1 of
        them; reduce_scatter/all_to_all move (g-1)/g of the operand; a
        permute sends the operand once."""
        g = max(1, self.group_size)
        b = self.tensor_mb
        if self.kind == "all_reduce":
            per = 2.0 * (g - 1) / g * b
        elif self.kind == "all_gather":
            per = (g - 1) * b
        elif self.kind in ("reduce_scatter", "all_to_all"):
            per = (g - 1) / g * b
        else:  # collective_permute: one hop
            per = b
        return per * self.count


@dataclass(frozen=True)
class ShardingSite:
    """One sharding annotation: a ``custom_call @Sharding`` constraint
    (``site='constraint'``) or an entry-argument attribute (``site='arg'``,
    same-signature args collapsed via ``count``)."""

    site: str
    shape: Tuple[int, ...]
    dtype: str
    tensor_mb: float
    sharding: ShardingInfo
    count: int = 1


@dataclass
class CommFootprint:
    """The static collective footprint of ONE lowered program."""

    program: str
    collectives: List[CollectiveSite] = field(default_factory=list)
    shardings: List[ShardingSite] = field(default_factory=list)
    module_lines: int = 0
    lower_ms: float = 0.0
    error: Optional[str] = None

    def wire_mb_by_kind(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for c in self.collectives:
            out[c.kind] = out.get(c.kind, 0.0) + c.wire_mb
        return out

    def to_json(self) -> Dict[str, Any]:
        return {
            "program": self.program,
            "module_lines": self.module_lines,
            "lower_ms": round(self.lower_ms, 1),
            "error": self.error,
            "collectives": [
                {
                    "kind": c.kind, "shape": list(c.shape), "dtype": c.dtype,
                    "tensor_mb": round(c.tensor_mb, 6),
                    "wire_mb": round(c.wire_mb, 6),
                    "group_size": c.group_size, "groups": len(c.groups),
                    "axes": list(c.axes), "in_loop": c.in_loop,
                    "count": c.count,
                }
                for c in self.collectives
            ],
            "shardings": [
                {
                    "site": s.site, "shape": list(s.shape), "dtype": s.dtype,
                    "tensor_mb": round(s.tensor_mb, 6),
                    "sharding": s.sharding.raw, "tile": list(s.sharding.tile),
                    "replicated": s.sharding.replicated, "count": s.count,
                }
                for s in self.shardings
            ],
        }


def extract_footprint(text: str, program: str = "?") -> CommFootprint:
    """Walk lowered StableHLO text into a :class:`CommFootprint` — pure text
    analysis, no jax import, so canned modules unit-test the parser."""
    fp = CommFootprint(program=program)
    lines = text.splitlines()
    fp.module_lines = len(lines)

    coll_sites: Dict[Tuple, Dict[str, Any]] = {}
    arg_sites: Dict[Tuple, Dict[str, Any]] = {}
    constraint_sites: List[ShardingSite] = []
    # open-region brace balance of each enclosing stablehlo.while — a
    # collective inside one executes per trip, not once (count stays the
    # STATIC site count; in_loop flags the dynamic multiplicity)
    loop_stack: List[int] = []

    for i, line in enumerate(lines):
        net = line.count("{") - line.count("}")
        is_while = "stablehlo.while" in line
        if loop_stack and not is_while:
            loop_stack[-1] += net
            # only a closing line can end the region (the balance sits at 0
            # between the while header and its `cond {` opener)
            if net < 0:
                while loop_stack and loop_stack[-1] <= 0:
                    loop_stack.pop()
        if is_while:
            loop_stack.append(max(net, 0))

        m = _COLL_RE.search(line)
        if m and "custom_call" not in line:
            kind = m.group(1)
            groups = parse_groups(line) or ()
            tt = None
            om = _OPERAND_RE.search(line)
            if om:
                tt = parse_tensor_type(om.group(1))
            else:
                # region ops (all_reduce/reduce_scatter) print the operand
                # type on the region-closing line — bounded forward scan
                for j in range(i + 1, min(i + 60, len(lines))):
                    om = _OPERAND_RE.search(lines[j])
                    if om:
                        tt = parse_tensor_type(om.group(1))
                        break
                    if _COLL_RE.search(lines[j]):
                        break  # never steal another op's operand line
            shape, dtype, mb = tt if tt else ((), "f32", 0.0)
            if kind == "collective_permute":
                gsize = 2
            else:
                gsize = max((len(g) for g in groups), default=1)
            key = (kind, shape, dtype, groups, bool(loop_stack))
            ent = coll_sites.setdefault(
                key, {"kind": kind, "shape": shape, "dtype": dtype, "mb": mb,
                      "groups": groups, "gsize": gsize,
                      "in_loop": bool(loop_stack), "count": 0},
            )
            ent["count"] += 1
            continue

        if "@Sharding" in line:
            sm = _SHARDING_ATTR_RE.search(line)
            tt = parse_tensor_type(line.rsplit(":", 1)[-1])
            if sm and tt:
                shape, dtype, mb = tt
                constraint_sites.append(ShardingSite(
                    site="constraint", shape=shape, dtype=dtype, tensor_mb=mb,
                    sharding=parse_sharding_attr(sm.group(1)),
                ))
            continue

        if "%arg" in line and "mhlo.sharding" in line:
            for am in _ARG_RE.finditer(line):
                tt = parse_tensor_type(am.group(1))
                if tt is None:
                    continue
                shape, dtype, mb = tt
                key = (shape, dtype, am.group(2))
                ent = arg_sites.setdefault(
                    key, {"shape": shape, "dtype": dtype, "mb": mb,
                          "raw": am.group(2), "count": 0})
                ent["count"] += 1

    fp.collectives = [
        CollectiveSite(
            kind=e["kind"], shape=e["shape"], dtype=e["dtype"],
            tensor_mb=e["mb"], groups=e["groups"], group_size=e["gsize"],
            in_loop=e["in_loop"], count=e["count"],
        )
        for e in coll_sites.values()
    ]
    fp.shardings = constraint_sites + [
        ShardingSite(
            site="arg", shape=e["shape"], dtype=e["dtype"], tensor_mb=e["mb"],
            sharding=parse_sharding_attr(e["raw"]), count=e["count"],
        )
        for _, e in sorted(arg_sites.items(), key=lambda kv: repr(kv[0]))
    ]
    return fp


# ---------------------------------------------------------------------------
# Replica-group → mesh-axis attribution
# ---------------------------------------------------------------------------


def mesh_axis_groups(devices, axis_names: Sequence[str]):
    """For every non-empty subset of mesh axes, the device-id partition that
    varies exactly those axes: ``[(axes_subset, frozenset_of_groups), ...]``
    ordered smallest subset first, so attribution picks the tightest match.
    ``devices`` is the mesh's ndarray of device ids (or Devices, via
    ``.id``)."""
    import itertools

    import numpy as np

    arr = np.asarray(devices)
    ids = np.vectorize(lambda d: getattr(d, "id", d), otypes=[np.int64])(arr)
    n_ax = ids.ndim
    out = []
    for r in range(1, n_ax + 1):
        for subset in itertools.combinations(range(n_ax), r):
            rest = [a for a in range(n_ax) if a not in subset]
            perm = tuple(rest) + subset
            width = 1
            for a in subset:
                width *= ids.shape[a]
            moved = np.transpose(ids, perm).reshape(-1, width)
            groups = frozenset(frozenset(int(v) for v in row) for row in moved)
            out.append((tuple(axis_names[a] for a in subset), groups))
    return out


def attribute_collectives(
    fp: CommFootprint, devices, axis_names: Sequence[str],
) -> List[Diagnostic]:
    """Fill each CollectiveSite's ``axes`` from the mesh layout; GTC005 for
    replica groups that match no mesh-axis subgroup."""
    table = mesh_axis_groups(devices, axis_names)
    diags: List[Diagnostic] = []
    new = []
    for c in fp.collectives:
        axes: Tuple[str, ...] = ()
        if c.groups:
            if c.kind == "collective_permute":
                # a permute lists (src, tgt) pairs: attribute to the smallest
                # axis subset where every pair stays inside one subgroup
                pairs = [frozenset(p) for p in c.groups if len(p) == 2]
                for subset, groups in table:
                    if pairs and all(any(p <= g for g in groups) for p in pairs):
                        axes = subset
                        break
            else:
                want = frozenset(frozenset(g) for g in c.groups)
                for subset, groups in table:
                    if want == groups:
                        axes = subset
                        break
            if not axes:
                diags.append(Diagnostic(
                    "GTC005",
                    f"{fp.program}: {c.kind} over groups of size "
                    f"{c.group_size} matches no mesh-axis subgroup",
                    hint="the lowered grouping disagrees with the plan's "
                    "factored mesh — check tp_consec / axis assignment",
                    field=fp.program,
                ))
        new.append(CollectiveSite(
            kind=c.kind, shape=c.shape, dtype=c.dtype, tensor_mb=c.tensor_mb,
            groups=c.groups, group_size=c.group_size, axes=axes,
            in_loop=c.in_loop, count=c.count,
        ))
    fp.collectives = new
    return diags


def _plan_axis_roles(hp, world: int) -> Dict[Tuple[str, ...], str]:
    """Map each mesh-axis subset the plan's strategies legitimately
    communicate over → its role ('tp'/'cp'/'ep'/'dp'/'pp').  The complement
    of this map is what GTC003 flags as unsolicited."""
    from galvatron_tpu.parallel.mesh import MeshAxes

    pp = max(1, hp.pp)
    m = max(0, (world // pp).bit_length() - 1)
    axes = MeshAxes(pp="pp", data_axes=tuple(f"x{i}" for i in range(m)))
    roles: Dict[Tuple[str, ...], str] = {("pp",): "pp"}
    for s in hp.layer_strategies:
        try:
            if s.tp > 1:
                roles.setdefault(tuple(sorted(axes.tp_axes(s.tp, s.tp_consec))), "tp")
            if s.cp > 1:
                roles.setdefault(tuple(sorted(axes.cp_axes(s.tp, s.tp_consec, s.cp))), "cp")
            if s.ep > 1:
                roles.setdefault(tuple(sorted(axes.ep_axes(s.tp, s.tp_consec, s.ep))), "ep")
            dp = axes.dp_axes(s.tp, s.tp_consec, max(1, s.cp))
            if dp:
                roles.setdefault(tuple(sorted(dp)), "dp")
        except ValueError:
            continue  # plan checker (GTA004) owns degree/extent mismatches
    if hp.vocab_tp > 1:
        try:
            roles.setdefault(tuple(sorted(axes.tp_axes(hp.vocab_tp, True))), "tp")
            dp = axes.dp_axes(hp.vocab_tp, True, 1)
            if dp:
                roles.setdefault(tuple(sorted(dp)), "dp")
        except ValueError:
            pass
    if m:  # full data block: zero3 over all non-pp axes / fused grad sync
        roles.setdefault(tuple(sorted(axes.data_axes)), "dp")
    return roles


# ---------------------------------------------------------------------------
# Lower-only audit over the program registry
# ---------------------------------------------------------------------------


def lower_programs(
    cfg,
    hp,
    *,
    global_bsz: int,
    seq_len: Optional[int] = None,
    include: Optional[Sequence[str]] = None,
    adam: Any = None,
    verbose: bool = False,
) -> Tuple[List[CommFootprint], Any]:
    """AOT-lower every registered program for the plan (``lower()`` only —
    no compile, no execute, no data) and extract each footprint, with
    replica groups attributed against the runtime's own mesh.  Returns
    ``(footprints, mesh)``; a program that fails to lower degrades to a
    footprint carrying ``error`` (the fidelity gate turns it into GTC004)."""
    from galvatron_tpu.aot import registry as aot_registry
    from galvatron_tpu.parallel.hybrid import build_runtime

    kw: Dict[str, Any] = {"global_batch_size": global_bsz, "seq_len": seq_len}
    if adam is not None:
        kw["adam"] = adam
    rt = build_runtime(cfg, hp, **kw)
    ctx = aot_registry.ProgramContext(
        cfg=cfg, hp=hp, global_bsz=global_bsz, seq_len=seq_len,
        mesh=rt.mesh, axes=rt.axes, runtime=rt, adam=adam,
    )
    specs = aot_registry.enumerate_programs(
        ctx, include=include if include is not None else ("trainer",)
    )
    fps: List[CommFootprint] = []
    for spec in specs:
        t0 = time.perf_counter()
        try:
            lowered = spec.fn.lower(*spec.args, **spec.kwargs)
            fp = extract_footprint(lowered.as_text(), program=spec.name)
        except Exception as e:  # noqa: BLE001 — per-program isolation
            fp = CommFootprint(program=spec.name,
                               error=f"{type(e).__name__}: {str(e)[:300]}")
        fp.lower_ms = (time.perf_counter() - t0) * 1000.0
        if fp.error is None:
            fp.attribution_diags = attribute_collectives(  # type: ignore[attr-defined]
                fp, rt.mesh.devices, rt.mesh.axis_names)
        else:
            fp.attribution_diags = []  # type: ignore[attr-defined]
        if verbose:
            print(f"audit: {spec.name}: {fp.module_lines} lines, "
                  f"{len(fp.collectives)} collective site(s), "
                  f"{len(fp.shardings)} sharding site(s), "
                  f"lower {fp.lower_ms:.0f} ms"
                  + (f" — FAILED: {fp.error}" if fp.error else ""))
        fps.append(fp)
    return fps, rt.mesh


# ---------------------------------------------------------------------------
# Fidelity gate: predicted_over_lowered per plan term
# ---------------------------------------------------------------------------

# Independent first-principles constants for the AUDITED side.  Deliberately
# NOT imported from search/cost_model.py: the gate's whole point is that a
# drift in the cost model's constants moves only the predicted side.
_AUDIT_TP_BOUNDARY_COLLECTIVES = 4.0  # Megatron f/g: 2 fwd + 2 bwd
_AUDIT_REMAT_TP_REPLAY = 1.5  # full remat replays the 2 fwd collectives
_AUDIT_ZERO3_GATHER_PASSES = 2.0  # fwd + bwd param gather
_AUDIT_GRAD_FP32_FACTOR = 2.0  # fp32 grad reduce over bf16-priced wire


def _ar_wire(mb: float, n: int) -> float:
    return 0.0 if n <= 1 else 2.0 * (n - 1) / n * mb


def _ag_wire(mb: float, n: int) -> float:
    return 0.0 if n <= 1 else (n - 1) / n * mb


def _param_mb_by_scope(cfg) -> Tuple[Dict[int, float], float]:
    """Actual fp32 parameter MB from the model's abstract init tree:
    ``({layer_idx: MB}, other_MB)`` — the audited side's ground truth for
    parameter-proportional terms, independent of the cost model's analytic
    ``parameter_mb`` arithmetic."""
    import jax

    from galvatron_tpu.models import modeling

    tree = jax.eval_shape(
        lambda k: modeling.init_model_params(k, cfg), jax.random.key(0)
    )
    per_layer: Dict[int, float] = {}
    other = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        mb = float(leaf.dtype.itemsize)
        for d in leaf.shape:
            mb *= d
        mb /= 1e6
        li = None
        for a, b in zip(keys, keys[1:]):
            if a in ("layers", "enc_layers", "blocks") and b.isdigit():
                li = int(b)
                break
        if li is None:
            other += mb
        else:
            per_layer[li] = per_layer.get(li, 0.0) + mb
    return per_layer, other


@dataclass
class FidelityRow:
    term: str
    predicted_mb: float
    lowered_mb: float
    basis: str  # 'collectives' | 'avals' | 'annotations' | 'none'
    tolerance: float

    @property
    def ratio(self) -> Optional[float]:
        if self.lowered_mb <= 0.0:
            return None
        return self.predicted_mb / self.lowered_mb

    @property
    def within(self) -> bool:
        r = self.ratio
        return r is not None and (1.0 / self.tolerance) <= r <= self.tolerance


def lowered_volume_breakdown(
    cfg, hp, world: int, global_bsz: int,
    footprints: Sequence[CommFootprint],
    seq_len: Optional[int] = None,
) -> Dict[str, Tuple[float, str]]:
    """The AUDITED side: per-term on-wire MB per device re-derived from the
    programs' actual abstract shapes and lowered collectives —
    ``{term: (mb, basis)}``.  Where a term's collectives are explicit in the
    lowered text (shard_map paths) the extracted sites ground it directly
    (basis ``collectives``); GSPMD-implied terms — invisible until the
    partitioner runs at compile time — are grounded in the actual parameter
    avals (basis ``avals``) or the boundary activation types the annotations
    carry (basis ``annotations``) instead."""
    f = 0.5 if hp.mixed_precision in ("bf16", "fp16") else 1.0
    per_layer, other_mb = _param_mb_by_scope(cfg)
    seq = seq_len or cfg.sample_len
    hidden = cfg.hidden_size
    act_bytes = 2.0 if f == 0.5 else 4.0
    pp = max(1, hp.pp)
    out: Dict[str, Tuple[float, str]] = {}

    def add(term: str, mb: float, basis: str) -> None:
        if mb <= 0.0:
            return
        prev = out.get(term)
        # explicit-collective grounding beats analytic re-derivation
        if prev is not None and prev[1] == "collectives" and basis != "collectives":
            return
        out[term] = ((prev[0] if prev and prev[1] == basis else 0.0) + mb, basis)

    # explicit-collective grounding: classify attributed sites by role axes
    roles = _plan_axis_roles(hp, world)
    tp_mb = cp_mb = ep_mb = pp_mb = 0.0
    train_fp = next((fp for fp in footprints if fp.program == "train_step"), None)
    if train_fp is not None and train_fp.error is None:
        for c in train_fp.collectives:
            if not c.axes:
                continue
            role = roles.get(tuple(sorted(c.axes)))
            if c.kind == "collective_permute" and "pp" in c.axes:
                # a permute inside a scan over micro-batches executes chunks
                # times per iteration; an unrolled/batched one executes once
                pp_mb += c.wire_mb * (max(1, hp.chunks) if c.in_loop else 1)
            elif role == "tp":
                tp_mb += c.wire_mb
            elif role == "cp":
                cp_mb += c.wire_mb
            elif role == "ep":
                ep_mb += c.wire_mb
    if tp_mb > 0.0:
        add("tp_boundary", tp_mb, "collectives")
    if cp_mb > 0.0:
        add("cp_ring", cp_mb, "collectives")
    if ep_mb > 0.0:
        add("ep_a2a", ep_mb, "collectives")
    if pp_mb > 0.0:
        add("pp_p2p", pp_mb, "collectives")

    # aval/annotation grounding for the GSPMD-implied terms
    for i, s in enumerate(hp.layer_strategies):
        dp = max(1, world // (pp * s.tp * max(1, s.cp)))
        dense_mb = per_layer.get(i, 0.0) / s.tp
        add("dp_grad", _ar_wire(dense_mb * f * _AUDIT_GRAD_FP32_FACTOR, dp), "avals")
        if s.dp_type == "zero3":
            add("zero3_gather",
                _AUDIT_ZERO3_GATHER_PASSES * _ag_wire(dense_mb * f, dp), "avals")
        if s.tp > 1:
            # boundary activation bytes from the model's actual (b, s, h)
            # global types — the same types the @Sharding annotations carry
            local_bsz = global_bsz / dp / max(1, s.cp)
            act_mb = local_bsz * seq * hidden * act_bytes / 1e6
            mb = _AUDIT_TP_BOUNDARY_COLLECTIVES * _ar_wire(act_mb, s.tp)
            if s.ckpt == "full":
                mb *= _AUDIT_REMAT_TP_REPLAY
            add("tp_boundary", mb, "annotations")

    # embedding / head under the vocab strategy
    vocab_tp = max(1, hp.vocab_tp)
    dp_o = max(1, world // (pp * vocab_tp))
    p_mb = other_mb / vocab_tp
    add("embed_dp", _ar_wire(p_mb * f * _AUDIT_GRAD_FP32_FACTOR, dp_o), "avals")
    if hp.embed_dp_type == "zero3":
        add("embed_dp", _AUDIT_ZERO3_GATHER_PASSES * _ag_wire(p_mb * f, dp_o), "avals")
    if vocab_tp > 1:
        act_mb = (global_bsz / dp_o) * seq * hidden * act_bytes / 1e6
        add("vocab_embed", 2.0 * _ar_wire(act_mb, vocab_tp), "annotations")
    return out


def fidelity_report(
    cfg, hp, world: int, global_bsz: int,
    footprints: Sequence[CommFootprint],
    *,
    seq_len: Optional[int] = None,
    tolerance: float = 3.0,
    source: Optional[str] = None,
) -> Tuple[List[FidelityRow], List[Diagnostic]]:
    """``predicted_over_lowered`` per plan term.  The predicted side replays
    the cost model's own volume constants (``comm_volume_breakdown``); the
    lowered side re-derives volumes from actual avals + extracted
    collectives.  Terms outside ``[1/tolerance, tolerance]`` → GTC001;
    predicted terms with zero grounding → GTC002; a failed lowering →
    GTC004 (which suppresses GTC002 — the failure already explains the
    missing grounding)."""
    from galvatron_tpu.search import cost_model
    from galvatron_tpu.search.theoretical import analytic_model_costs

    diags: List[Diagnostic] = []
    any_failed = False
    for fp in footprints:
        if fp.error is not None:
            any_failed = True
            diags.append(Diagnostic(
                "GTC004", f"{fp.program} failed to lower: {fp.error}",
                hint="fix the program (or exclude its family) before "
                "trusting the plan's comm profile", field=fp.program,
                source=source,
            ))
        diags.extend(getattr(fp, "attribution_diags", []))

    predicted = cost_model.comm_volume_breakdown(
        analytic_model_costs(cfg, seq_len=seq_len or 0), hp, world, global_bsz,
        mixed_precision=hp.mixed_precision,
    )
    lowered = lowered_volume_breakdown(
        cfg, hp, world, global_bsz, footprints, seq_len=seq_len
    )
    rows: List[FidelityRow] = []
    for term in sorted(set(predicted) | set(lowered)):
        p = predicted.get(term, 0.0)
        low, basis = lowered.get(term, (0.0, "none"))
        row = FidelityRow(term=term, predicted_mb=p, lowered_mb=low,
                          basis=basis, tolerance=tolerance)
        rows.append(row)
        if p > 0.0 and low <= 0.0:
            if not any_failed:
                diags.append(Diagnostic(
                    "GTC002",
                    f"plan term '{term}' predicts {p:.3f} MB/device but the "
                    "lowering grounds none of it",
                    hint="the engine may have elided the collective (or the "
                    "auditor cannot see this path) — verify before trusting "
                    "the term", field=term, source=source,
                ))
        elif not row.within and row.ratio is not None:
            diags.append(Diagnostic(
                "GTC001",
                f"term '{term}': predicted {p:.3f} MB vs lowered {low:.3f} MB "
                f"per device (ratio {row.ratio:.2f} outside "
                f"[{1.0 / tolerance:.2f}, {tolerance:.2f}], basis {basis})",
                hint="re-derive the cost-model constant for this term (or "
                "raise --tolerance with a comment saying why)",
                field=term, source=source,
            ))
    return rows, diags


def format_fidelity_table(rows: Sequence[FidelityRow]) -> str:
    if not rows:
        return "no comm terms (plan has no multi-device strategy dimension)"
    out = [f"{'term':<14} {'predicted_mb':>12} {'lowered_mb':>11} "
           f"{'pred/lowered':>12} {'basis':<12} status"]
    for r in rows:
        ratio = f"{r.ratio:.3f}" if r.ratio is not None else "—"
        status = ("ok" if r.within
                  else ("ungrounded" if r.ratio is None else "OUT-OF-BAND"))
        out.append(f"{r.term:<14} {r.predicted_mb:>12.3f} {r.lowered_mb:>11.3f} "
                   f"{ratio:>12} {r.basis:<12} {status}")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# Resharding lint
# ---------------------------------------------------------------------------


def resharding_lint(
    hp,
    footprints: Sequence[CommFootprint],
    *,
    world: int = 0,
    source: Optional[str] = None,
) -> List[Diagnostic]:
    """Diagnose comm the plan never asked for:

    - GTC003: an attributed collective over a mesh-axis subset no plan term
      owns — exactly the partitioner-inserted resharding Alpa/GSPMD warn of;
    - GTC010: the plan shards params (zero2/3, tp) or activations
      (tp/sp/cp/vocab_tp) but the lowering left EVERY corresponding
      annotation fully replicated — GSPMD will silently replicate what the
      plan believes is sharded (GTA016 generalized to lowered reality);
    - GTC011: same-shaped boundary constraints carry more distinct shardings
      than the plan declares strategy seams — an undeclared redistribution;
    - GTC012: a tp_overlap layer's lowering has no decomposed ring
      (collective_permute) yet keeps monolithic tp-group collectives — the
      collective-matmul did not fire and its pricing discount is unearned.
    """
    diags: List[Diagnostic] = []
    train_fp = next((fp for fp in footprints if fp.program == "train_step"), None)
    if train_fp is None or train_fp.error is not None:
        return diags

    if world:
        roles = _plan_axis_roles(hp, world)
        stray: Dict[Tuple[str, Tuple[str, ...]], int] = {}
        for c in train_fp.collectives:
            key = tuple(sorted(c.axes))
            if c.axes and key not in roles and not (
                c.kind == "collective_permute" and "pp" in c.axes
            ):
                stray[(c.kind, c.axes)] = stray.get((c.kind, c.axes), 0) + c.count
        for (kind, axes), n in sorted(stray.items()):
            diags.append(Diagnostic(
                "GTC003",
                f"{n} lowered {kind} site(s) over mesh axes {list(axes)} "
                "that no plan term communicates over",
                hint="the partitioner inserted resharding the cost model "
                "never priced — check the layer-boundary sharding specs",
                field="train_step", source=source,
            ))

    wants_param_shard = any(
        s.dp_type in ("zero2", "zero3") or s.tp > 1 for s in hp.layer_strategies
    )
    wants_act_shard = any(
        s.tp > 1 or s.sp or s.cp > 1 for s in hp.layer_strategies
    ) or hp.vocab_tp > 1
    args = [s for s in train_fp.shardings if s.site == "arg"]
    constraints = [s for s in train_fp.shardings if s.site == "constraint"]
    if wants_param_shard and args and not any(s.sharding.sharded for s in args):
        diags.append(Diagnostic(
            "GTC010",
            "plan shards parameters (zero3/tp) but every lowered entry "
            "argument is fully replicated",
            hint="param_spec/model annotations did not reach the jit "
            "in_shardings — each device will hold (and all-gather) full "
            "copies", field="train_step", source=source,
        ))
    if wants_act_shard and constraints and not any(
        s.sharding.sharded for s in constraints
    ):
        diags.append(Diagnostic(
            "GTC010",
            "plan shards activations (tp/sp/cp/vocab_tp) but every lowered "
            "boundary constraint is fully replicated",
            hint="the layer-boundary with_sharding_constraint hook lost its "
            "specs — GSPMD will replicate the boundary and insert gathers",
            field="train_step", source=source,
        ))

    # undeclared seams: distinct shardings per same-shape constraint class.
    # Boundary activations are rank-3 (b, s, h); params of one shape can
    # legitimately shard differently (e.g. wq vs wo), so gate on rank 3.
    declared_seams = sum(
        1 for a, b in zip(hp.layer_strategies, hp.layer_strategies[1:])
        if (a.tp, a.tp_consec, a.sp, a.cp) != (b.tp, b.tp_consec, b.sp, b.cp)
    )
    by_shape: Dict[Tuple, set] = {}
    for s in constraints:
        if len(s.shape) == 3:
            by_shape.setdefault((s.shape, s.dtype), set()).add(s.sharding.raw)
    for (shape, dtype), shardings in sorted(by_shape.items()):
        if len(shardings) > declared_seams + 1:
            diags.append(Diagnostic(
                "GTC011",
                f"boundary tensor {dtype}{list(shape)} lowers under "
                f"{len(shardings)} distinct shardings but the plan declares "
                f"only {declared_seams} strategy seam(s)",
                hint="an undeclared redistribution: every extra sharding is "
                "a resharding collective the cost model never priced",
                field="train_step", source=source,
            ))

    overlap_layers = [i for i, s in enumerate(hp.layer_strategies)
                      if s.tp_overlap and s.tp > 1]
    if overlap_layers:
        has_ring = any(
            c.kind == "collective_permute" and "pp" not in c.axes
            for c in train_fp.collectives
        )
        overlap_tp = {s.tp for s in hp.layer_strategies if s.tp_overlap}
        monolith = [
            c for c in train_fp.collectives
            if c.kind in ("all_gather", "all_reduce")
            and c.group_size in overlap_tp
        ]
        if not has_ring and monolith:
            diags.append(Diagnostic(
                "GTC012",
                f"{len(overlap_layers)} tp_overlap layer(s) lower no "
                "collective_permute ring yet keep "
                f"{sum(c.count for c in monolith)} monolithic tp-group "
                "collective site(s)",
                hint="ops/collective_matmul did not fire (shape/dtype gate?) "
                "— the credit the search gave it (cost_model.tp_overlap_exposed) is unearned",
                field=f"tp_overlap_flags[{overlap_layers[0]}]", source=source,
            ))
    return diags


# ---------------------------------------------------------------------------
# High-level driver + JSONL artifact
# ---------------------------------------------------------------------------


@dataclass
class AuditResult:
    footprints: List[CommFootprint]
    rows: List[FidelityRow]
    diagnostics: List[Diagnostic]


def audit_plan(
    cfg,
    hp,
    *,
    world: int,
    global_bsz: int,
    seq_len: Optional[int] = None,
    include: Optional[Sequence[str]] = None,
    tolerance: float = 3.0,
    adam: Any = None,
    source: Optional[str] = None,
    verbose: bool = False,
) -> AuditResult:
    """Lower-only audit of one (plan × model × mesh): footprints + fidelity
    rows + GTC diagnostics.  Needs ``jax.device_count() == world`` (use
    ``aot.warmup.force_cpu_world`` first — host devices, no hardware)."""
    fps, _mesh = lower_programs(
        cfg, hp, global_bsz=global_bsz, seq_len=seq_len, include=include,
        adam=adam, verbose=verbose,
    )
    rows, diags = fidelity_report(
        cfg, hp, world, global_bsz, fps, seq_len=seq_len,
        tolerance=tolerance, source=source,
    )
    diags.extend(resharding_lint(hp, fps, world=world, source=source))
    return AuditResult(footprints=fps, rows=rows, diagnostics=diags)


def write_footprint_jsonl(path: str, footprints: Sequence[CommFootprint],
                          extra: Optional[Dict[str, Any]] = None) -> None:
    """One record per program (+ an optional trailing context record) — the
    artifact ``cli warmup --report`` writes next to ``memory_analysis`` and
    the CI audit job uploads."""
    with open(path, "w") as f:
        for fp in footprints:
            f.write(json.dumps(fp.to_json()) + "\n")
        if extra:
            f.write(json.dumps(extra) + "\n")
