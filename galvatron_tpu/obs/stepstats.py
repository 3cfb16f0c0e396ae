"""Step accounting: model-FLOPs estimate, tokens/s, achieved TFLOP/s, MFU.

The throughput number alone ("N tokens/s") says nothing about how far from
the hardware ceiling a run sits; MegaScale (NSDI '24) and PaLM report MFU —
model FLOPs per second over the accelerators' peak — as the comparable
utilization metric. This module derives the FLOPs side from ``ModelConfig``
analytically (attention projections + attention core + MLP + vocab head),
so every ``train_iter`` JSONL record and ``RuntimeProfiler`` summary can
carry ``tokens_per_s`` / ``tflops`` / ``mfu`` with no extra measurement.

Two FLOPs totals, following the PaLM convention:

- **model FLOPs** (feeds MFU): fwd + 2x fwd backward, NO recompute — MFU is
  a property of the model and the wall clock, unchanged by checkpointing.
- **hardware FLOPs** (feeds HFU): adds the rematerialized compute — full
  forward per full-ckpt layer, the attention core per selective-ckpt layer,
  the MLP branch when ``mlp_recompute`` is ``gate``/``policy`` (PR 3's
  policy replays the gate product + fp32 norm statistics in backward).

Attention-core FLOPs count the pairs the mask keeps: the causal half,
``s (s + 1) / 2`` of ``s x s``, when the model is causal (what the flash
kernels compute, and what ``benchmark/lib/flops.py`` counts), the full square
otherwise. MoE layers are priced at the dense per-token cost of one expert
(top-1 switch routing); router compute is ignored.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax

from galvatron_tpu.models.modeling import ModelConfig

# per-device peak dense bf16 TFLOP/s by TPU generation (published peaks;
# keyed by substring of device_kind). Override: GALVATRON_PEAK_TFLOPS.
_PEAK_TFLOPS_BY_KIND = (
    ("v6", 918.0),  # Trillium
    ("v5p", 459.0),
    ("v5 lite", 197.0),
    ("v5e", 197.0),
    ("v5litepod", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)


def peak_flops_per_device(override_tflops: float = 0.0) -> Optional[float]:
    """Peak dense FLOP/s of one local device, or None when unknown (CPU,
    unrecognized kind). ``override_tflops`` (or GALVATRON_PEAK_TFLOPS) wins —
    quoting a wrong peak would make every MFU number silently wrong."""
    if override_tflops:
        return float(override_tflops) * 1e12
    env = os.environ.get("GALVATRON_PEAK_TFLOPS", "")
    if env:
        try:
            return float(env) * 1e12
        except ValueError:
            # a peak someone set on purpose and mistyped must not fall back
            # to the table in silence: every MFU after it would be wrong
            raise ValueError(
                f"GALVATRON_PEAK_TFLOPS={env!r} is not a number (TFLOP/s)"
            ) from None
    kind = jax.devices()[0].device_kind.lower()
    for key, tf in _PEAK_TFLOPS_BY_KIND:
        if key in kind:
            return tf * 1e12
    return None


def attn_proj_flops_per_token(cfg: ModelConfig) -> float:
    """QKV + output projection matmul FLOPs for one token, one layer."""
    h, hd = cfg.hidden_size, cfg.head_dim
    qkv_cols = h + 2 * cfg.kv_heads * hd  # q at h, k/v at kv_heads*hd (GQA)
    return 2.0 * h * qkv_cols + 2.0 * h * h


def attn_core_flops_per_token(cfg: ModelConfig, seq_len: int) -> float:
    """q@k^T and p@v for one token against the keys its mask keeps: on
    average ``(seq_len + 1) / 2`` under a causal mask, ``seq_len`` without."""
    keys = (seq_len + 1) / 2.0 if cfg.causal else float(seq_len)
    return 2.0 * 2.0 * keys * cfg.hidden_size


def mlp_flops_per_token(cfg: ModelConfig) -> float:
    n_gemm = 3 if cfg.act_fn == "swiglu" else 2  # gate+up+down vs up+down
    return 2.0 * n_gemm * cfg.hidden_size * cfg.ffn


def layer_fwd_flops_per_token(cfg: ModelConfig, seq_len: int) -> float:
    """Forward FLOPs of one transformer layer for one token."""
    return (
        attn_proj_flops_per_token(cfg)
        + attn_core_flops_per_token(cfg, seq_len)
        + mlp_flops_per_token(cfg)
    )


def head_flops_per_loss_token(cfg: ModelConfig) -> float:
    return 2.0 * cfg.hidden_size * cfg.vocab_size


def _remat_fwd_flops_per_token(cfg: ModelConfig, seq_len: int, hp=None) -> float:
    """Extra forward compute replayed in backward, per token summed over all
    layers (the hardware-FLOPs delta). Per-layer when strategies are known;
    the uniform ``cfg.mlp_recompute`` rule otherwise."""
    strategies = list(getattr(hp, "layer_strategies", None) or [])
    if not strategies:
        class _Uniform:  # cfg-only callers: one pseudo-strategy per layer
            ckpt = 0
        strategies = [_Uniform()] * cfg.total_layers
    total = 0.0
    for s in strategies:
        ckpt = getattr(s, "ckpt", 0)
        if ckpt in (1, "full"):
            total += layer_fwd_flops_per_token(cfg, seq_len)
        elif ckpt in (2, "selective"):
            total += attn_core_flops_per_token(cfg, seq_len)
        elif cfg.mlp_recompute != "off":
            # PR 3 policy/gate: the activation product (and fp32 norm stats,
            # negligible next to the GEMMs) replays once per layer
            total += mlp_flops_per_token(cfg)
    return total


@dataclass
class StepStats:
    """Precomputed per-step FLOPs for one (model, strategy, batch) shape;
    ``per_iter(iter_ms)`` turns a measured step time into the JSONL fields."""

    cfg: ModelConfig
    global_bsz: int
    seq_len: int
    hp: Any = None  # HybridParallelConfig (per-layer remat awareness) or None
    num_devices: int = 0
    peak_tflops_override: float = 0.0

    def __post_init__(self):
        if not self.num_devices:
            self.num_devices = jax.device_count()
        cfg, seq = self.cfg, self.seq_len
        tokens = float(self.global_bsz) * seq
        from galvatron_tpu.models.modeling import loss_tokens_per_sample

        loss_tokens = float(self.global_bsz) * loss_tokens_per_sample(cfg, seq)
        fwd = (
            tokens * cfg.total_layers * layer_fwd_flops_per_token(cfg, seq)
            + loss_tokens * head_flops_per_loss_token(cfg)
        )
        self.model_flops_per_step = 3.0 * fwd  # fwd + 2x fwd backward
        self.hardware_flops_per_step = self.model_flops_per_step + (
            tokens * _remat_fwd_flops_per_token(cfg, seq, self.hp)
        )
        self.tokens_per_step = tokens
        self._peak = peak_flops_per_device(self.peak_tflops_override)

    @property
    def peak_flops_per_device(self) -> Optional[float]:
        return self._peak

    def per_iter(
        self,
        iter_ms: Optional[float],
        global_bsz: Optional[float] = None,
        nonpad_tokens: Optional[float] = None,
    ) -> Dict[str, Optional[float]]:
        """tokens/s, achieved model TFLOP/s (per device), MFU and HFU for one
        measured iteration. ``global_bsz`` rescales the precomputed step
        FLOPs/tokens linearly (batch-size rampup runs at smaller sizes).
        MFU/HFU are None when the device peak is unknown (CPU sim) — a
        made-up denominator would be worse than no number.

        ``nonpad_tokens`` (packed sequences): the batch's real-token count.
        ``tokens_per_s`` and MFU/HFU then count NON-PAD tokens only — padded
        positions burn FLOPs but are not useful work, and counting them made
        MFU silently overstate utilization exactly when packing was off. The
        raw (pad-inclusive) rate stays available as ``tokens_per_s_raw`` so
        pre-packing dashboards keep their meaning, and the ratio is exposed
        as ``packing_efficiency``."""
        if not iter_ms or iter_ms <= 0:
            out: Dict[str, Optional[float]] = {
                "tokens_per_s": None, "tflops_per_device": None,
                "mfu": None, "hfu": None,
            }
            if nonpad_tokens is not None:
                out["tokens_per_s_raw"] = None
                out["packing_efficiency"] = None
            return out
        scale = (global_bsz / self.global_bsz) if global_bsz else 1.0
        s = iter_ms / 1000.0
        raw_tokens = scale * self.tokens_per_step
        useful_frac = 1.0
        if nonpad_tokens is not None and raw_tokens > 0:
            useful_frac = min(1.0, float(nonpad_tokens) / raw_tokens)
        flops_rate = useful_frac * scale * self.model_flops_per_step / s
        out = {
            "tokens_per_s": round(useful_frac * raw_tokens / s, 3),
            "tflops_per_device": round(flops_rate / self.num_devices / 1e12, 4),
            "mfu": None,
            "hfu": None,
        }
        if nonpad_tokens is not None:
            out["tokens_per_s_raw"] = round(raw_tokens / s, 3)
            out["packing_efficiency"] = round(useful_frac, 6)
        if self._peak:
            denom = self._peak * self.num_devices
            out["mfu"] = round(flops_rate / denom, 6)
            out["hfu"] = round(
                useful_frac * scale * self.hardware_flops_per_step / s / denom, 6
            )
        return out


def hbm_gauges() -> Dict[str, float]:
    """Per-device HBM gauges (bytes) where the backend reports them — the
    Prometheus-facing twin of RuntimeProfiler.memory_stats (MB)."""
    out: Dict[str, float] = {}
    for d in jax.devices():
        try:
            st = d.memory_stats()
        except Exception:
            st = None
        if st:
            out[f"dev{d.id}_bytes_in_use"] = float(st.get("bytes_in_use", 0))
            out[f"dev{d.id}_peak_bytes"] = float(st.get("peak_bytes_in_use", 0))
    return out
