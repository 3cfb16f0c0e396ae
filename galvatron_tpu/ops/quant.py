"""Per-channel symmetric int8 weight quantization for the serving path.

Decode is bandwidth-bound: every generated token re-reads the full weight
set, so int8 weights are a near-linear tokens/s win and halve the HBM a
model holds (LLM.int8 — Dettmers et al. 2022 — absmax per-channel recipe,
weight-only variant: activations stay in the compute dtype).

The recipe, per weight W stored ``(in, out…)`` (every dense weight in this
repo contracts over axis 0 — modeling._dense_init):

  scale[c] = max(|W[:, c]|) / 127          (absmax, one per output channel)
  Q[:, c]  = round(W[:, c] / scale[c])     (int8; zero-point 0 — symmetric)

and the matmul dequantizes IN the kernel: ``y = (x · Q) * scale`` with an
fp32 accumulator (``preferred_element_type``), so the int8→compute-dtype
convert fuses into the GEMM and the wide weight tensor is read at 1 byte
per element. int8 values (|q| ≤ 127) are exactly representable in bf16, so
the convert itself is lossless; the only error is the per-channel rounding,
which the engine parity-gates against a declared max-abs logit drift.

``QuantTensor`` is a pytree (NamedTuple) that impersonates the weight array
just enough for the modeling seams: ``.astype`` is the identity (dequant
happens inside the matmul, not ahead of it), ``.shape``/``.ndim`` answer
for the logical (unquantized) weight. Dispatch lives at the TP projection
seams (Placement.proj_up/proj_down, qkv_project, attn_output, lm_head) —
the same seams the collective-matmul overlap owns — via an isinstance
check, so training code never sees a branch.

Quantization happens ONCE, at engine load / ``cli warmup``
(``--serve_quant int8``); the decode step never touches fp weights.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class QuantParityError(ValueError):
    """Quantized logits drifted past the declared bound (--quant_drift_max)."""


class QuantTensor(NamedTuple):
    """int8 weight + per-output-channel f32 scales.

    ``q`` keeps the stored weight's exact shape ``(in, out…)``; ``scale``
    has shape ``q.shape[1:]`` (one scale per output channel, broadcasting
    over the contraction axis). NamedTuple ⇒ automatically a pytree, so
    quantized params flow through jit/eval_shape/tree_map unchanged.
    """

    q: Any      # int8, shape (in, out…)
    scale: Any  # float32, shape (out…)

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def dtype(self):
        # the LOGICAL dtype is "whatever the matmul computes in"; report the
        # storage dtype so memory accounting (size × itemsize) stays honest
        return self.q.dtype

    @property
    def size(self):
        return self.q.size

    def astype(self, dtype):
        """Identity: the modeling seams cast weights to the activation dtype
        right before the matmul — for a QuantTensor the dequantize happens
        inside ``qeinsum`` instead, so the cast is a no-op."""
        del dtype
        return self

    def dequantize(self, dtype=jnp.float32):
        """Materialize the fp weight (fallback paths only — e.g. the
        collective-matmul overlap ring, which streams fp shards)."""
        return (self.q.astype(jnp.float32) * self.scale).astype(dtype)


def quantize_int8(w) -> QuantTensor:
    """Symmetric per-channel absmax quantization of one stored weight.

    Contraction axis is ALWAYS axis 0 in this repo's weight layout
    (modeling._dense_init: ``(in, out…)``; the blocked wqkv's (h, 3, n·hd)
    trailing dims are all output channels). All-zero channels get scale 0
    and quantize to exact zeros — the dequantized matmul contribution is
    exactly 0.0, not NaN.
    """
    w32 = jnp.asarray(w, jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=0)          # (out…)
    scale = absmax / 127.0
    safe = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(w32 / safe), -127, 127).astype(jnp.int8)
    return QuantTensor(q=q, scale=scale.astype(jnp.float32))


def _out_suffix_ok(subscripts: str, qw: QuantTensor) -> None:
    """The scale broadcast below relies on every seam's einsum putting the
    weight's output letters LAST in the output, in order — true for all of
    qkv_project / attn_output / proj_up / proj_down / lm_head. Fail
    loudly (at trace time, free at runtime) if a new caller breaks that."""
    inputs, out = subscripts.replace("...", "").split("->")
    x_sub, w_sub = inputs.split(",")
    w_out = "".join(c for c in w_sub if c not in x_sub)
    if not out.endswith(w_out):
        raise ValueError(
            f"qeinsum needs the weight's output axes trailing in the "
            f"output ({subscripts!r}: weight-only axes {w_out!r} vs "
            f"output {out!r})"
        )
    if qw.scale.ndim != len(w_out):
        raise ValueError(
            f"scale rank {qw.scale.ndim} != weight output rank "
            f"{len(w_out)} for {subscripts!r}"
        )


def qeinsum(subscripts: str, x, qw: QuantTensor):
    """Dequantize-in-kernel einsum: ``einsum(x, q)`` with an fp32
    accumulator, then the per-channel scale applied to the (narrow) output.

    The int8→x.dtype convert is exact (|q| ≤ 127 fits bf16's mantissa) and
    fuses into the GEMM on TPU, so HBM reads the weight at int8 width; the
    scale multiply touches only the output activations — O(out) work, not
    O(in·out).
    """
    _out_suffix_ok(subscripts, qw)
    y = jnp.einsum(
        subscripts, x, qw.q.astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
    return (y * qw.scale).astype(x.dtype)


def qmatmul(x, qw: QuantTensor):
    """``x @ w`` for a 2-D quantized weight (lm_head / interleaved qkv)."""
    y = jnp.matmul(
        x, qw.q.astype(x.dtype), preferred_element_type=jnp.float32
    )
    return (y * qw.scale).astype(x.dtype)


def project(x, w):
    """``x @ w`` in ``x``'s type for a weight that may be served int8: a `QuantTensor`
    dequantises inside its GEMM, anything else is cast to ``x``'s type."""
    return qmatmul(x, w) if isinstance(w, QuantTensor) else x @ w.astype(x.dtype)


# weight keys eligible for quantization, per param sub-dict. Biases, norms,
# and the embedding table (a gather, not a GEMM) stay in the param dtype;
# MoE experts keep fp too (the dispatch einsums contract over the expert
# axis — a different layout contract than the per-channel recipe assumes).
_ATTN_KEYS = ("wqkv", "wo")
_CROSS_KEYS = ("wq", "wkv", "wo")
_MLP_KEYS = ("w13", "w1", "w2")
#: a latent-attention layer's plain GEMMs, the headwise gate's among them (W_kvb is
#: absorbed into the queries and the context a head at a time: it stays fp)
_MLA_KEYS = ("wq", "wqa", "wqb", "wkva", "wo", "wgate")
#: the indexer's three projections of such a layer (``p["index"]``): GEMMs like the others,
#: and the ones a selection hangs on
_MLA_INDEX_KEYS = ("wq", "wk", "ww")
#: a Mamba-2 mixer's two projections (``p["ssm"]``), and an UN-GATED shared expert's two
#: matrices (``p["mlp"]["shared"]`` with ``w1``: nemotron_h's; a gated one's ``w13`` stays
#: fp, as the cells that have one were read): plain GEMMs, most of what a token is
#: multiplied by outside the routed experts in such a stack
_SSM_KEYS = ("in_proj", "out_proj")
_SHARED_UNGATED_KEYS = ("w1", "w2")


def quantize_params(params: Dict[str, Any], cfg) -> Dict[str, Any]:
    """Quantize the GEMM weights of a decoder param tree, returning a new
    tree with ``QuantTensor`` leaves at the projection seams and everything
    else untouched. Safe under ``jax.eval_shape`` (AOT program keys derive
    the int8 avals from this same function)."""
    out = dict(params)
    layers = []
    for layer in params.get("layers", []):
        lp = dict(layer)
        for group, keys in (("attn", _ATTN_KEYS), ("cross", _CROSS_KEYS), ("mla", _MLA_KEYS),
                            ("ssm", _SSM_KEYS)):
            if group in lp:
                gp = dict(lp[group])
                for k in keys:
                    if k in gp and not isinstance(gp[k], QuantTensor):
                        gp[k] = quantize_int8(gp[k])
                if group == "mla" and "index" in gp:
                    gp["index"] = {k: quantize_int8(v) if k in _MLA_INDEX_KEYS
                                   and not isinstance(v, QuantTensor) else v
                                   for k, v in gp["index"].items()}
                lp[group] = gp
        # (a dense MLP: every layer of a dense model, the leading layers of an expert
        # model; the experts' stacks go through the grouped GEMMs and stay fp)
        if "mlp" in lp and "router" not in lp["mlp"]:
            mp = dict(lp["mlp"])
            for k in _MLP_KEYS:
                if k in mp and not isinstance(mp[k], QuantTensor):
                    mp[k] = quantize_int8(mp[k])
            lp["mlp"] = mp
        elif "mlp" in lp and "w1" in lp["mlp"].get("shared", {}):
            shared = dict(lp["mlp"]["shared"])
            for k in _SHARED_UNGATED_KEYS:
                if not isinstance(shared[k], QuantTensor):
                    shared[k] = quantize_int8(shared[k])
            lp["mlp"] = dict(lp["mlp"], shared=shared)
        layers.append(lp)
    if layers:
        out["layers"] = layers
    if "head" in params and not getattr(cfg, "tie_word_embeddings", False):
        hp = dict(params["head"])
        if "w" in hp and not isinstance(hp["w"], QuantTensor):
            hp["w"] = quantize_int8(hp["w"])
        out["head"] = hp
    # tied embeddings: lm_head reads the embedding table transposed — the
    # table also feeds a gather, so it stays fp (quantizing it would trade
    # the embed lookup's exactness for one matmul's bandwidth)
    return out


def quantized_fraction(params: Dict[str, Any]) -> float:
    """Fraction of param ELEMENTS now stored int8 (reporting only)."""
    total = quant = 0
    for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, QuantTensor)
    ):
        if isinstance(leaf, QuantTensor):
            quant += int(np.prod(leaf.q.shape))
            total += int(np.prod(leaf.q.shape))
        else:
            total += int(np.prod(leaf.shape))
    return quant / total if total else 0.0


def parity_report(params_fp, params_q, cfg, *, drift_max: float,
                  probe_tokens=None) -> Dict[str, Any]:
    """Measure (not assume) the quantization drift: run one probe forward
    through both param sets and report the max-abs logit drift plus the
    greedy top-1 agreement over every probe position. Raises
    :class:`QuantParityError` when the drift exceeds the declared bound —
    the engine refuses to serve a quantization that left its budget.
    """
    from galvatron_tpu.models import modeling

    if probe_tokens is None:
        s = int(min(16, cfg.max_seq_len))
        probe_tokens = (np.arange(s, dtype=np.int32) * 7 + 1) % cfg.vocab_size
        probe_tokens = probe_tokens[None, :]
    toks = jnp.asarray(probe_tokens, jnp.int32)
    ref = np.asarray(modeling.forward(params_fp, toks, cfg), np.float32)
    got = np.asarray(modeling.forward(params_q, toks, cfg), np.float32)
    drift = float(np.max(np.abs(got - ref)))
    agree = float(np.mean(np.argmax(got, -1) == np.argmax(ref, -1)))
    report = {
        "max_abs_logit_drift": round(drift, 6),
        "greedy_agree_frac": round(agree, 4),
        "drift_bound": float(drift_max),
        "probe_positions": int(toks.shape[-1]),
    }
    if drift > drift_max:
        raise QuantParityError(
            f"int8 logit drift {drift:.4f} exceeds the declared bound "
            f"{drift_max} (greedy agreement {agree:.2%}) — raise "
            f"--quant_drift_max only if the accuracy budget allows it"
        )
    return report
