"""Layer kinds: the interface a kind's module fulfils, the registry of kinds, and
the one table of what a model's layers do not implement.

A layer of a kind other than ``"attention"`` (``ModelConfig.layer_kinds``) runs
that kind's mixer in place of attention; norms and MLP are the same. A kind is
ONE module and ONE row of ``MIXERS``. The module (``Mixer.module``, imported
only where a configuration has such layers: `module`) exposes

- ``init_params(key, cfg)``, ``annotations(cfg)``, ``block(x, p, cfg, place)``:
  what `modeling` initialises, shards and runs under the layer's key ``kind``;
- ``param_count(cfg)``, ``saved_bytes_per_token(cfg, itemsize)``,
  ``fwd_flops_per_token(cfg)``: the mixer's prices (search/theoretical.py);
- ``path_counts(cfg)`` -> ``{kernel: {"fused": n, "plain": m}}`` for the
  row's ``kernels``: which body of each the configuration's layers take (the
  run's fingerprint, `path_counts`);
- where the row says ``cache`` (the kind keeps a cache of its own in the cached
  forwards: no ``kv_cache`` under ``lacks``): ``init_cache(cfg, layers, rows,
  positions, tokens)`` -> a NamedTuple of arrays stacked ``(layers, rows, positions,
  ...)``, ``cache_bytes_per_position(cfg)`` (one layer's), ``cache_layout(cfg, max_len,
  tokens)`` (None: every layer alike; else the kind's own stacks, each with its bytes a
  position, and ``step_counters(layout, lengths, rows, positions, window)``: what a decode
  iteration of them fetches), ``cache_read_positions(
  cfg, lengths, rows, positions, window)`` (what a decode window's attention
  fetches of one layer, by construction), ``chunk_layout(cfg, rows,
  positions)`` (a prompt chunk's: which body its attention takes, the keys a block
  of it fetches) and ``cached_block(x,
  p, cfg, cache, layer, starts, slot, offsets, cos_sin)`` -> ``(y, cache)``, what
  ``models/generation.forward_with_cache`` runs in place of attention over K and V;
- where the row says ``state`` (the kind keeps a per-row STATE in the cached forwards,
  nothing indexed by position: no ``kv_cache`` under ``lacks`` either):
  ``init_state(cfg, layers, rows)`` -> one array stacked ``(layers, rows, ...)``, or,
  where a row keeps parts of different types (the Mamba-2 mixer: a conv tail in the
  compute type and a float32 scan state), a NamedTuple of such arrays, one a part (a
  pytree: the cached forwards carry, donate and rebuild it whole and never look inside);
  ``state_bytes_per_row(cfg)`` (one layer's, all parts; ``state_part_bytes(cfg)`` by
  part where there are several) and ``cached_block(x, p, cfg, state,
  layer, slot, offsets, last)`` -> ``(y, state)``: the layer over the state stack of
  ``models/generation.SlotStacks``, beside the attention layers' keys and values. A
  state is read ZERO by a forward that starts at position 0 and written as of the
  forward's last REAL row (``last``); `limits` says what a stack with one cannot do:
  the paged backend and speculation (a state is no position and cannot be wound
  back), beside the kind's own tp, cp and packing.

The row holds the kind's words and, under ``lacks``, what it does not implement
with the clause that says why. `limits` turns the rows of a configuration's
kinds, its interleaving and its expert path into `Limit`s; `build_runtime`
raises the first one a plan breaks, `plan_check` reports each, the search
leaves each out and names its tag, `init_kv_cache` refuses a stack with one on
the cache (and `serving.Engine` one on the paged backend or on speculation). Plain data and functions of the configuration's fields: nothing here
imports jax or a kernel, or anything of ``parallel/``, ``search/``, ``analysis/``.
"""

from __future__ import annotations

import collections
import dataclasses
import importlib
from typing import Dict, List, Mapping, Optional, Tuple

_NO_PATHS = {"fused": 0, "plain": 0}


@dataclasses.dataclass(frozen=True)
class Mixer:
    kind: str  # the entry of ``layer_kinds``, the key of the layer's parameters, the scope
    module: str  # imported by `module`, never before a configuration has such a layer
    layer: str  # "state-space layer": the refusals' and diagnostics' name for it
    mixer: str  # "the Mamba-2 mixer"
    tag: str  # "state_space_layers": head of the search's standing tags
    # what the kind does not implement ("tp" | "cp" | "pack_sequences" |
    # "kv_cache") -> the clause that says why
    lacks: Mapping[str, str]
    kernels: Tuple[str, ...] = ()  # the bodies `path_counts` reports, "<kind>_<kernel>_path"
    cache: str = ""  # "latent": what the kind's own cache holds a position ("": it has none)
    ring: bool = False  # its cached forward keeps a window layer's entries in a ring of its own
    # "conv" | "conv + scan": what the kind's state holds a row, no positions ("": none)
    state: str = ""


MIXERS: Dict[str, Mixer] = {entry.kind: entry for entry in (
    Mixer(
        kind="ssm", module="galvatron_tpu.models.ssm", layer="state-space layer",
        mixer="the Mamba-2 mixer", tag="state_space_layers", kernels=("scan", "conv", "step"),
        state="conv + scan",
        lacks={
            "tp": "the Mamba-2 mixer's heads, conv channels and scan carry no tp sharding",
            "cp": "the scan's state is not passed between sequence shards",
            "pack_sequences": ("the conv and the scan do not reset their state at segment "
                               "boundaries"),
        }),
    Mixer(
        kind="gdn", module="galvatron_tpu.models.gdn", layer="Gated DeltaNet layer",
        mixer="the Gated DeltaNet mixer", tag="gated_delta_layers", kernels=("scan", "conv"),
        lacks={
            "tp": ("the mixer's heads, conv channels and the delta rule's state carry no tp "
                   "sharding"),
            "cp": "the delta rule's state is not passed between sequence shards",
            "pack_sequences": ("the conv and the delta rule do not reset their state at segment "
                               "boundaries"),
            "kv_cache": "a key/value cache holds no recurrent (conv + delta rule) state",
        }),
    Mixer(
        kind="mla", module="galvatron_tpu.models.mla", layer="latent-attention layer",
        mixer="the latent-attention (MLA) mixer", tag="latent_attention_layers", cache="latent",
        ring=True,
        lacks={
            "tp": ("the mixer's heads share one latent and one rotary key, and its projections "
                   "carry no tp sharding"),
            "cp": "the latent and its rotary key are not passed between sequence shards",
            "pack_sequences": "the latent attention's mask does not stop at segment boundaries",
        }),
    Mixer(
        kind="shortconv", module="galvatron_tpu.models.shortconv",
        layer="gated short-convolution layer", mixer="the gated short-convolution mixer",
        tag="short_conv_layers", kernels=("conv",), state="conv",
        lacks={
            "tp": ("the mixer's three gates are slices of one projection's columns and the "
                   "conv's channels carry no tp sharding"),
            "cp": "the conv's state is not passed between sequence shards",
            "pack_sequences": "the conv does not reset its state at segment boundaries",
        }),
)}


def module(kind: str):
    """The module of a kind's mixer."""
    return importlib.import_module(MIXERS[kind].module)


def cache_kind(cfg) -> Optional[str]:
    """The kind whose own cache the cached forwards of ``cfg``'s stack keep: None
    where every layer is attention over K and V. (A stack that mixes cache layouts
    is refused: `limits`.)"""
    kinds = set(getattr(cfg, "kinds", ()))
    own = [k for k in kinds if k in MIXERS and MIXERS[k].cache]
    return own[0] if own else None


def state_kinds(cfg) -> Tuple[str, ...]:
    """The kinds of ``cfg``'s stack that keep a per-row state in the cached forwards
    (``Mixer.state``), in the registry's order."""
    kinds = set(getattr(cfg, "kinds", ()))
    return tuple(k for k, e in MIXERS.items() if e.state and k in kinds)


def has_mixer_layers(cfg) -> bool:
    """The stack has a layer whose mixer is a registered kind's, not attention
    (each kind is then priced by itself: the in-process profiler measures one)."""
    return any(kind in MIXERS for kind in cfg.kinds)


def path_counts(cfg) -> Dict[str, Dict[str, int]]:
    """``{"<kind>_<kernel>_path": {"fused": n, "plain": m}}`` over every
    registered kind's kernels; a kind the stack lacks reads zeros and its module
    stays unloaded."""
    out = {}
    for kind, entry in MIXERS.items():
        has = entry.kernels and kind in cfg.kinds
        counts = module(kind).path_counts(cfg) if has else {}
        for kernel in entry.kernels:
            out[f"{kind}_{kernel}_path"] = counts.get(kernel, dict(_NO_PATHS))
    return out


def tally(path: str, layers: int) -> Dict[str, int]:
    """``layers`` layers all on ``path``, as a kind's ``path_counts`` reports a kernel."""
    return {**_NO_PATHS, path: layers}


#: the limits that are a layer's degree in the plan; the others are the run's
DEGREES = ("tp", "cp", "ep")


@dataclasses.dataclass(frozen=True)
class Limit:
    """One thing a model's layers do not implement."""

    # "tp" | "cp" | "ep": a layer's degree > 1; "pp" | "pack_sequences" | "fp16" |
    # "attn_impl" (another attention path than XLA's): the run's; "kv_cache":
    # generation's; "paged_kv": the paged serving backend's; "spec_decode": the
    # speculative engine's (``spec_decode_k`` > 0)
    what: str
    layers: Tuple[int, ...]  # the strategy indices it is reported on: a kind's, or all
    refusal: str  # build_runtime's (init_kv_cache's) sentence; "{at}": the layers that break it
    stack: bool = False  # a degree ANY layer of the stack breaks, reported on ``layers``
    tag: Optional[str] = None  # the search's standing tag (None: nothing the search enumerates)
    code: Optional[str] = None  # plan_check's diagnostic (None: nothing a plan file carries)
    diagnostic: str = ""  # what follows "layer 3: tp=2 " / "pp=2 "
    hint: str = ""  # of a run's limit; a degree's names its field

    def broken_by(self, cfg, hp):
        """Where a plan (``core.strategy.HybridParallelConfig``, read by
        attribute) breaks the limit: the layers whose degree does, or whether
        the run does. Falsy where the plan keeps it."""
        if self.what in DEGREES:
            over = [i for i, s in enumerate(hp.layer_strategies) if getattr(s, self.what) > 1]
            return over if self.stack else [i for i in over if i in self.layers]
        return {"pp": hp.pp > 1, "fp16": hp.mixed_precision == "fp16",
                "pack_sequences": bool(cfg.pack_sequences),
                "attn_impl": cfg.attn_impl != "xla"}.get(self.what, False)

    def sentence(self, at=()) -> str:
        """The refusal, given what `broken_by` found."""
        return self.refusal.replace("{at}", str(at))


def _window_limits(cfg, enc: int, every: Tuple[int, ...]) -> List[Limit]:
    """What a stack with sliding-window layers does not implement: the window is a
    mask of XLA's attention (``modeling.attention_xla``) and a ring of the slot
    cache (``generation.SlotStacks``), and of nothing else."""
    kinds = tuple(cfg.kinds)
    at = tuple(enc + i for i, w in enumerate(cfg.window_layers) if w)
    layers = f"sliding-window layers (window {cfg.sliding_window_size}; layers {at})"
    out = [
        Limit("attn_impl", at,
              refusal=("an attention path other than XLA's (attn_impl 'flash' or 'ring') is not "
                       f"implemented for a stack with {layers}: the flash and ring kernels "
                       "(ops/flash_attention.py, parallel/ring.py) carry no window; use "
                       "attn_impl='xla'")),
        Limit("cp", at, stack=True, tag="sliding_window_layers_no_cp", code="GTA019",
              refusal=("context parallelism (cp>1) is not implemented for a stack with "
                       f"{layers}: the ring / Ulysses layers mask causally over whole "
                       "sequences and carry no window; use cp=1"),
              diagnostic=("on a stack with sliding-window layers — the ring / Ulysses layers "
                          "carry no window")),
        Limit("pack_sequences", at,
              refusal=(f"pack_sequences is not implemented for a stack with {layers}: a "
                       "window counts positions of the row, not of the segment")),
        Limit("pp", every, tag="sliding_window_layers_no_pp", code="GTA020",
              refusal=(f"pipeline parallelism (pp>1) is not implemented for a stack with "
                       f"{layers}: the pipeline engines run every layer of a stage under the "
                       "model's one configuration, and the window and the position signal "
                       "change by layer; use pp=1"),
              diagnostic=("on a stack with sliding-window layers — the pipeline engines run "
                          "every layer of a stage under one configuration"),
              hint="use pp_deg 1 for a stack with sliding-window layers"),
        Limit("paged_kv", at,
              refusal=(f"the paged backend (--kv_num_blocks) is not implemented for a stack "
                       f"with {layers}: a block pool holds every layer's positions alike and "
                       "has no ring; serve it from the slot cache (kv_num_blocks 0)")),
    ]
    # (a stack of ONE kind whose cached forward keeps a ring of its own is served)
    own_ring = len(set(kinds)) == 1 and kinds[0] in MIXERS and MIXERS[kinds[0]].ring
    if has_mixer_layers(cfg) and not own_ring:
        out.append(Limit(
            "kv_cache", every,
            refusal=("generation is not implemented for a stack that has both sliding-window "
                     "layers and layers of another kind than attention (this model: "
                     f"{dict(collections.Counter(cfg.kinds))}): the ring holds keys and "
                     "values; train-only")))
    return out


def limits(cfg) -> List[Limit]:
    """What ``cfg``'s layers do not implement, in the order `build_runtime`
    refuses: each recurrent kind's own, pipeline stages over interleaved kinds,
    a windowed stack's, the dropless expert path's."""
    kinds = tuple(getattr(cfg, "kinds", ()))
    enc = getattr(cfg, "enc_layers", 0)
    every = tuple(range(enc + len(kinds)))
    out: List[Limit] = []
    for kind, e in MIXERS.items():
        at = tuple(enc + i for i, k in enumerate(kinds) if k == kind)
        if not at:
            continue
        layers, why = f"{e.layer}s", e.lacks
        if "tp" in why:
            out.append(Limit(
                "tp", at, tag=f"{e.tag}_no_tp", code="GTA019",
                refusal=(f"tensor parallelism (tp>1) is not implemented for {layers} "
                         f"(layers {{at}} of this plan): {why['tp']}; use tp=1 on those layers"),
                diagnostic=(f"on a {e.layer} — tensor parallelism is not implemented for "
                            f"{e.mixer}")))
        if "cp" in why:
            out.append(Limit(
                "cp", at, stack=True, tag=f"{e.tag}_no_cp", code="GTA019",
                refusal=(f"context parallelism (cp>1) is not implemented for a stack with "
                         f"{layers}: {why['cp']}; use cp=1"),
                diagnostic=f"on a {e.layer} — {why['cp']}"))
        if "pack_sequences" in why:
            out.append(Limit(
                "pack_sequences", at,
                refusal=f"pack_sequences is not implemented for {layers}: {why['pack_sequences']}"))
        if "kv_cache" in why:
            out.append(Limit(
                "kv_cache", at,
                refusal=(f"generation is not implemented for a stack with {layers}: "
                         f"{why['kv_cache']}; train-only")))
    if len(set(kinds)) > 1 and cache_kind(cfg):
        out.append(Limit(
            "kv_cache", every,
            refusal=("generation is not implemented for a stack that interleaves cache layouts "
                     f"(this model: {dict(collections.Counter(kinds))}): the slot cache is one "
                     "kind's; train-only")))
    for kind in state_kinds(cfg):
        # what a per-row state cannot do that positions can (models/generation.py)
        e = MIXERS[kind]
        at = tuple(enc + i for i, k in enumerate(kinds) if k == kind)
        out.append(Limit(
            "paged_kv", at,
            refusal=(f"the paged backend (--kv_num_blocks) is not implemented for a stack with "
                     f"{e.layer}s (layers {at}): a block pool holds positions, and the "
                     f"{e.state} state of a row is none; serve it from the slot cache "
                     "(kv_num_blocks 0)")))
        out.append(Limit(
            "spec_decode", at,
            refusal=(f"speculative decoding (spec_decode_k > 0) is not implemented for a stack "
                     f"with {e.layer}s (layers {at}): a rejected draft has already advanced the "
                     f"{e.state} state, and a state cannot be wound back as a position is "
                     "overwritten; use spec_decode_k 0")))
    if cache_kind(cfg) and (getattr(cfg, "windowed", False) or getattr(cfg, "mla_index_topk", 0)):
        out.append(Limit(
            "spec_decode", every,
            refusal=("speculative decoding (spec_decode_k > 0) is not implemented for a "
                     "latent-attention stack with an indexer or sliding-window layers: a verify "
                     "window of several queries a row would attend under a mask over every "
                     "slot's capacity, and no test holds the selection of a rejected draft's "
                     "index keys; use spec_decode_k 0")))
    if len(set(kinds)) > 1:
        out.append(Limit(
            "pp", every, tag="interleaved_layer_kinds_no_pp", code="GTA020",
            refusal=("pipeline parallelism (pp>1) over interleaved layer kinds is not "
                     "implemented: the pipeline engines stack one kind of layer a stage "
                     f"position (this model: {dict(collections.Counter(kinds))}); use pp=1"),
            diagnostic=("over interleaved layer kinds — the pipeline engines "
                        "stack one kind of layer a stage position"),
            hint="use pp_deg 1 for a hybrid stack"))
    if getattr(cfg, "windowed", False):
        out.extend(_window_limits(cfg, enc, every))
    if getattr(cfg, "moe_dropless", False):
        # the sorted-rows path keeps every expert (or its held share) on every
        # device and hands its auxiliary loss up through the GSPMD step
        path = f"the dropless top-k MoE path (moe_router={cfg.moe_router!r})"
        if cfg.moe_holds_share:
            out.append(Limit(
                "ep", every, tag="dropless_topk_moe_no_ep", code="GTA014",
                refusal=(f"expert parallelism (ep>1) on a held share of the experts (moe_share="
                         f"{cfg.moe_share}: this copy holds {cfg.moe_held} of {cfg.moe_experts}) "
                         "is not implemented: the share IS one rank of an expert-parallel "
                         "deployment and the sorted-row path has no expert all-to-all; use ep=1"),
                diagnostic=(f"on a held share of the experts (this copy holds {cfg.moe_held} of "
                            f"{cfg.moe_experts}) — the share is one rank of an expert-parallel "
                            "deployment already")))
        else:
            out.append(Limit(
                "ep", every, tag="dropless_topk_moe_no_ep", code="GTA014",
                refusal=(f"expert parallelism (ep>1) is not implemented for {path}: its "
                         "sorted-row grouped GEMM has no expert all-to-all yet; use ep=1"),
                diagnostic=f"on {path} — its sorted-row grouped GEMM has no expert all-to-all yet"))
        out.append(Limit(
            "pp", every, tag="dropless_topk_moe_no_pp", code="GTA020",
            refusal=(f"pipeline parallelism (pp>1) is not implemented for {path}: the pipeline "
                     "engines carry no auxiliary loss between stages; use pp=1"),
            diagnostic=f"on {path} — the pipeline engines carry no auxiliary loss between stages",
            hint="use pp_deg 1 for a dropless top-k MoE model"))
        out.append(Limit(
            "cp", every, tag="dropless_topk_moe_no_cp", code="GTA019",
            refusal=(f"context parallelism (cp>1) is not implemented for {path}: the "
                     "ring/Ulysses layers hand no router statistics up; use cp=1"),
            diagnostic=f"on {path} — the ring/Ulysses layers hand no router statistics up"))
        out.append(Limit(
            "fp16", every,
            refusal=("fp16 loss scaling is not threaded through the dropless top-k MoE "
                     f"objective (moe_router={cfg.moe_router!r}); use bf16 or fp32")))
    return out
