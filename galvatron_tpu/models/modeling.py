"""Functional decoder-only Transformer covering the reference model zoo.

One configurable implementation replaces the reference's five per-family
variants (galvatron/models/{gpt_hf,llama_hf,gpt_fa,llama_fa,baichuan}):

- GPT-2 style: learned positions + LayerNorm + GeLU MLP + tied embeddings
  (reference: models/gpt_hf/GPTModel_sequential.py, GPTModel_tensor_parallel.py)
- LLaMA style: RoPE + RMSNorm + SwiGLU + GQA
  (reference: models/llama_hf/LlamaModel_tensor_parallel.py:10-75)
- Baichuan style: LLaMA-like, ALiBi option for the 13B variant
  (reference: models/baichuan/BaiChuanModel_sequential.py)

Everything is pure functions over parameter pytrees — no Module wrapping — so
per-layer hybrid strategies are just per-layer sharding specs applied to the
same code (SURVEY §7 design stance). Each parameter has a logical-axes
annotation consumed by the runtime's sharding rules (parallel/sharding.py).

Attention dispatch mirrors the reference's core-vs-flash switch
(galvatron/core/tensor_parallel/transformer.py:805-820): "xla" einsum path,
"flash" Pallas kernel, "ring" context-parallel ring attention.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
from jax.ad_checkpoint import checkpoint_name

import jax.numpy as jnp
import numpy as np

from galvatron_tpu.models import mixers
from galvatron_tpu.models.placement import LOCAL, Placement
from galvatron_tpu.ops.quant import QuantTensor, qeinsum, qmatmul

Params = Dict[str, Any]


@dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None  # None → MHA; < num_heads → GQA
    ffn_dim: Optional[int] = None  # None → 4h (gelu) or llama 8h/3 rounding
    max_seq_len: int = 2048
    pos_embed: str = "rope"  # 'rope' | 'learned' | 'alibi' | 'nope' (none at all)
    norm_type: str = "rms"  # 'rms' | 'layernorm'
    # 'swiglu' | 'gelu' | 'relu' (OPT-style) | 'relu2' (``relu(x)^2``, un-gated: nemotron_h)
    act_fn: str = "swiglu"
    tie_word_embeddings: bool = False
    # GPT-2-style projection biases on qkv/out/mlp GEMMs (norm biases are
    # governed by norm_type). Requires the blocked qkv layout (no GQA).
    use_bias: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    attn_impl: str = "xla"  # 'xla' | 'flash' | 'ring'
    # decoder (causal LM) vs encoder (bidirectional, e.g. BERT) attention.
    # The reference's legacy encoder support (bert/vit branches,
    # galvatron/core/parallel.py:64-89, cost_model.py model_type).
    causal: bool = True
    # encoder-decoder (T5-class; reference legacy t5 model_type): > 0 adds
    # that many bidirectional encoder layers; the ``num_layers`` decoder
    # layers gain cross-attention over the encoder output. Samples are
    # (B, enc_seq + max_seq_len + 1) token rows: encoder input ‖ decoder
    # stream (deviation from T5: RoPE/learned positions, not relative bias).
    enc_layers: int = 0
    enc_seq: int = 0
    # training objective: 'clm' next-token LM; 'mlm' masked-LM (encoder
    # pretraining) with deterministic token-hash masking (see mlm_loss_sum)
    objective: str = "clm"
    mlm_mask_rate: float = 0.15
    dtype: Any = jnp.bfloat16  # compute dtype
    param_dtype: Any = jnp.float32
    # Mixture-of-Experts (SwitchMLP equivalent, reference:
    # galvatron/core/tensor_parallel/transformer.py:161-295). 0 → dense MLP.
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_sinkhorn_iters: int = 8
    # Router kind. 'switch': the reference's top-1 router (sinkhorn-balanced,
    # static capacity, one-hot dispatch einsums, expert parallelism).
    # 'softmax_topk': OLMoE-class token choice — fp32 softmax, the
    # ``moe_top_k`` largest probabilities as combine weights (not
    # renormalised), dropless (sort by expert + grouped GEMM,
    # moe.moe_topk_block), with the load-balancing loss
    # ``moe_aux_coef * E * sum_e f_e P_e`` added to the loss that is
    # differentiated (the logged loss stays the cross entropy). ep>1 and pp>1
    # are refused for it by build_runtime and left out by the search.
    # 'sigmoid_topk' (sarvam_mla-class layers): the same dropless path with
    # fp32 scores ``s = sigmoid(y Wg)``; the ``moe_top_k`` experts are the
    # largest of ``s + b`` (``b``: the router's selection bias, a parameter no
    # gradient reaches: it SELECTS only and is served as loaded; training leaves
    # it constant), their weights ``moe_route_scale * s_e / sum_chosen s``.
    moe_router: str = "switch"
    moe_top_k: int = 1
    moe_aux_coef: float = 0.0
    moe_route_scale: float = 1.0
    # The dropless path's further settings (Qwen3-Next-class layers; the
    # defaults are OLMoE's layer): the width of ONE routed expert where it is
    # not ``ffn`` (None: ``ffn``); the top-k weights renormalised to sum 1; a
    # shared expert of this width that every token runs, under a sigmoid gate
    # of its own (0: none); and the HELD SHARE ``(r, R)``: this copy holds the
    # contiguous experts ``[r E / R, (r + 1) E / R)`` of the ``moe_experts`` the
    # router scores (one rank of an R-way expert-parallel deployment). Pairs on
    # experts it does not hold are left out of the sum; no code stands in for
    # the other ranks (moe._topk_local). ``ep`` > 1 on a held share is refused.
    moe_ffn_dim: Optional[int] = None
    moe_norm_topk: bool = False
    moe_shared_ffn_dim: int = 0
    moe_shared_gate: bool = True  # False: the shared expert's output is added as it is
    moe_share: Tuple[int, int] = (0, 1)
    # Leading layers whose MLP is the plain MLP of ``ffn`` where the rest are
    # expert layers (``first_k_dense_replace``).
    moe_dense_layers: int = 0
    # RMSNorm with a learned scale on the q and k projections, each over the
    # WHOLE projection width (all heads together) before the split into heads
    # and before rope (OLMoE; HF modeling_olmoe.py q_norm / k_norm).
    qk_norm: bool = False
    # With ``qk_norm``: the norm runs over the head size of EACH head instead, one
    # weight vector of ``head_dim`` for q and one for k, shared by the heads, before
    # rope: plain ``* w`` (LFM2's q_layernorm / k_layernorm), or ``* (1 + w)`` under
    # ``norm_zero_centered`` (Qwen3-Next's q_norm / k_norm).
    qk_norm_per_head: bool = False
    # An output gate on the attention block: a second query-wide projection ``wgate``
    # of the block's normed input whose sigmoid multiplies the attention output per
    # head and channel, in float32, before ``wo`` (the training block
    # ``_attn_block_gated`` and every cached forward, models/generation.py). Nothing
    # else comes with it: the q/k norms are ``qk_norm``'s (per head), rotary
    # ``rotary_fraction``'s and ``rope_layout``'s. Qwen3-Next's full-attention layers
    # and Trinity's layers set it.
    attn_gate: bool = False
    # Rotary on the first ``rotary_fraction`` of each head (Qwen3-Next: a quarter).
    rotary_fraction: float = 1.0
    # Sandwich norms: a second RMSNorm on what the attention block and the MLP block
    # RETURN (``post_attn_norm`` / ``post_mlp_norm``, learned scales of the hidden
    # width), applied before each joins the residual stream: four norms a layer.
    post_norms: bool = False
    # Head size where it is not ``hidden_size / num_heads`` (None: that).
    attn_head_dim: Optional[int] = None
    # Every RMSNorm over the hidden width is ``x * rsqrt(mean(x^2) + eps) * (1 + w)``
    # with ``w`` initialised 0 (Qwen3-Next) in place of ``* w`` from 1.
    norm_zero_centered: bool = False
    # Hybrid stacks (granitemoehybrid- and qwen3_next-class): the kind of every
    # layer, "attention" or a kind of ``models/mixers.MIXERS`` (its mixer in
    # place of attention; the MLP is the same), as published for the WHOLE model;
    # a model cut in depth keeps the first ``num_layers`` entries (``kinds``).
    # Empty: every layer is attention. What a kind or the interleaving does not
    # implement (``mixers.limits``) is refused by build_runtime and left out by
    # the search.
    layer_kinds: Tuple[str, ...] = ()
    # Of each layer, as published for the WHOLE model like ``layer_kinds``: 1, the
    # layer has an MLP (the program's pre-norm layer: a mixer and an MLP, two norms);
    # 0, it is its mixer alone: one norm, one sublayer, no ``mlp`` / ``mlp_norm`` in its
    # parameters (nemotron_h-class stacks, whose published blocks are ONE norm and ONE
    # sublayer each: `blocks_to_layers`). Empty: every layer has one.
    mlp_layout: Tuple[int, ...] = ()
    # Latent attention (layers of kind "mla", models/mla.py): the rank of the
    # compressed key/value latent, the non-rotary and rotary parts of a query /
    # key head and the value head's size. ``attn_head_dim`` is their query head
    # (nope + rope). The latent's RMSNorm is the only q/k norm.
    mla_kv_rank: int = 0
    mla_nope_dim: int = 128
    mla_rope_dim: int = 64
    mla_v_dim: int = 128
    # dots3_note-class latent attention, each off by default (sarvam's layer):
    # low-rank queries (``mla_q_rank`` > 0: W_qa, an RMSNorm of that rank, W_qb);
    # ``mla_rescale``: q after W_qb times (hidden / mla_q_rank)^1/2 and the normed
    # latent times (hidden / mla_kv_rank)^1/2 (LongCat-Flash's mla_scale_q_lora /
    # mla_scale_kv_lora); ``mla_head_gate``: sigmoid(h W_g), ONE scalar a head, on
    # the attention output before W_o (``attn_gate`` is a head AND channel);
    # the indexer of learned sparse attention (``mla_index_topk`` > 0: a query
    # attends the ``mla_index_topk`` keys at or before it that score highest under
    # ``mla_index_heads`` index heads of ``mla_index_dim``, all of them while there
    # are no more; it needs ``mla_q_rank``: the index queries come from the query
    # latent).
    mla_q_rank: int = 0
    mla_rescale: bool = False
    mla_head_gate: bool = False
    mla_index_heads: int = 0
    mla_index_dim: int = 128
    mla_index_topk: int = 0
    # A latent stack's SLIDING-WINDOW layers (``sliding_window_layout``) with sizes
    # of their own (``swa_kv_rank`` > 0): heads, the head's parts, the latent's and
    # the queries' ranks, the rotary base. ``layer_view`` of such a layer carries
    # them under the ``mla_*`` / ``num_heads`` / ``rope_theta`` names and no
    # indexer, so ``models/mla.py`` reads ONE set of names; the cached forwards keep
    # the window layers' latent in a ring beside the full layers' whole slots.
    swa_num_heads: int = 0
    swa_nope_dim: int = 0
    swa_rope_dim: int = 0
    swa_v_dim: int = 0
    swa_kv_rank: int = 0
    swa_q_rank: int = 0
    swa_rope_theta: float = 0.0
    # YaRN rotary scaling (``deepseek_yarn``): (factor, original positions,
    # beta_fast, beta_slow, mscale, mscale_all_dim); empty: plain rotary.
    rope_yarn: Tuple[float, ...] = ()
    # Sliding-window attention by layer (smallthinker-class stacks), as published
    # for the WHOLE model; a model cut in depth keeps the first ``num_layers``
    # entries. ``sliding_window_layout[i]`` 1: query p of layer i sees the keys j
    # with ``p - sliding_window_size < j <= p`` (the window holds the query's own
    # position); 0: every j <= p. ``rope_layout[i]`` 0: layer i has no position
    # signal at all (NoPE) whatever ``pos_embed`` says. Both empty: every layer is
    # the model's. A window layer is attention: its parameters are attention's;
    # the cached forwards keep its keys and values in a ring (models/generation.py).
    # A layer runs under its own VIEW of the configuration (``layer_view``), whose
    # ``attn_window`` is the layer's window (0: none) and whose ``pos_embed`` is
    # "nope" where the layout says so. What a windowed stack does not implement
    # (the flash / ring / context-parallel paths, packing, pipelines, the paged
    # backend) is ``mixers.limits``'s.
    sliding_window_size: int = 0
    sliding_window_layout: Tuple[int, ...] = ()
    rope_layout: Tuple[int, ...] = ()
    attn_window: int = 0
    # The gate's activation of every gated unit (``act_fn`` "swiglu": the dense
    # MLP, the experts, a shared expert): "silu" (SwiGLU) | "relu" (ReGLU).
    glu_act: str = "silu"
    # What a dropless expert layer's router reads: "mlp", the MLP block's normed
    # input like every projection of the block, or "attn", the ATTENTION block's
    # normed input of the same layer (smallthinker: the router sits before the
    # attention).
    moe_router_input: str = "mlp"
    # The softmax router's float32 GEMM: "default" (the chip runs it in one bf16
    # pass; the older softmax routers' programs) | "highest" (a choice no longer
    # flips where two logits lie within a bf16 ulp). The sigmoid router's is
    # always at ``highest``.
    moe_router_precision: str = "default"
    # the gated short convolution (layers of kind "shortconv", models/shortconv.py):
    # taps of its depthwise causal conv (``conv_L_cache``)
    shortconv_taps: int = 3
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # the Gated DeltaNet mixer: key heads (each serves value_heads / key_heads
    # value heads), value heads, their sizes, conv taps, the delta rule's chunk
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_conv: int = 4
    gdn_chunk: int = 64
    # Granite's scalar multipliers (HF config keys of the same names): the
    # softmax scale in place of 1/sqrt(head_dim) (None: that default), the
    # embedding's factor, the factor on every residual branch, and the divisor
    # of the logits.
    attention_multiplier: Optional[float] = None
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # vision families (reference legacy vit/swin model_type branches,
    # galvatron/core/parallel.py:64-89, cost_model.py:76,87-106).
    # image_size > 0 switches the input pipeline from token ids to uint8
    # pixel rows: one sample = (image_size² · num_channels) pixel values in
    # 0..255 stored as int32 ‖ one class label — so the whole runtime keeps
    # its single (B, sample_len+1) int32 batch contract (pipelines, loaders,
    # checkpoints all unchanged).
    image_size: int = 0
    patch_size: int = 16
    num_channels: int = 3
    num_classes: int = 1000
    # Swin: non-empty depths → hierarchical stages; stage s runs depths[s]
    # windowed-attention layers at width hidden_size·2^s and resolution
    # (image_size/patch_size)/2^s per side, with a patch-merging projection
    # between stages. Empty → plain ViT encoder.
    swin_depths: Tuple[int, ...] = ()
    swin_window: int = 7
    # Head-major flash dataflow (einsum projections straight to (b, 3, n, s,
    # hd) + head-major kernels — the production flash path; see
    # _attn_block_headmajor). False routes flash layers through the legacy
    # project→transpose→flash_attention wrapper instead (what a kernel A/B that
    # patches ops.flash_attention.flash_attention needs: the head-major wiring
    # bypasses it; `git show 384a03e:experiments/ab_flash.py`).
    flash_headmajor: bool = True
    # Activation-memory recompute over the MLP/norm/loss regions
    # (--mlp_recompute; DESIGN.md "Activation memory accounting"). The HLO
    # buffer audit (BASELINE.md round 5) showed the backward holding TWO
    # saved copies of the swiglu gate per layer plus fp32-widened (B, S, H)/
    # vocab-shard copies of bf16 activations (norm statistics and the
    # cross-entropy cast) — real HBM that caps feasible batch size.
    #   'policy': jax.checkpoint over the norm+MLP residual branch with a
    #     save_only_these_names('mlp_gate') policy — the gate projection
    #     output is saved exactly once (compute dtype) and everything else
    #     (the fp32 norm statistics, the silu·gate / gelu product) is
    #     recomputed in the backward; standalone norms and the cross-entropy
    #     fp32 cast are likewise rematerialized from their narrow inputs
    #     (cast at the consumer, never saved widened). The default.
    #   'gate': only the activation-product remat — the shape BASELINE.md's
    #     swiglu probe measured (one gate save, fp32 widenings untouched).
    #   'off': the pre-policy behaviour (double gate save + widened saves).
    mlp_recompute: str = "policy"
    # Packed-sequence input rows (--pack_sequences; galvatron_tpu.data):
    # a sample row is [tokens (S+1) ‖ segment ids (S+1)] — documents
    # bin-packed into one fixed-S row. The model then (a) blocks attention
    # across segment boundaries (intra-segment causal mask — cross-document
    # attention is provably impossible), (b) resets rope/learned positions
    # per segment (positions_from_segments), and (c) masks loss at segment
    # boundaries and on padding (split_batch). CLM decoder-only; requires
    # the 'xla' attention path (the Pallas kernels carry no segment mask).
    pack_sequences: bool = False

    @property
    def moe_dropless(self) -> bool:
        """The layers are dropless top-k MoE layers, which hand the router's
        statistics up beside their activations (decoder_layer)."""
        return self.moe_experts > 0 and self.moe_router in ("softmax_topk", "sigmoid_topk")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The kind of each of the ``num_layers`` decoder layers."""
        if not self.layer_kinds:
            return ("attention",) * self.num_layers
        if len(self.layer_kinds) < self.num_layers:
            raise ValueError(
                f"layer_kinds has {len(self.layer_kinds)} entries for {self.num_layers} layers")
        return self.layer_kinds[: self.num_layers]

    @property
    def mlp_layers(self) -> Tuple[bool, ...]:
        """Of each of the ``num_layers`` decoder layers: it has an MLP."""
        if not self.mlp_layout:
            return (True,) * self.num_layers
        if len(self.mlp_layout) < self.num_layers:
            raise ValueError(
                f"mlp_layout has {len(self.mlp_layout)} entries for {self.num_layers} layers")
        return tuple(bool(m) for m in self.mlp_layout[: self.num_layers])

    @property
    def windowed(self) -> bool:
        """Some layer of the stack attends through a sliding window."""
        return self.sliding_window_size > 0 and any(self.window_layers)

    @property
    def window_layers(self) -> Tuple[bool, ...]:
        """Of each of the ``num_layers`` decoder layers: it has a window."""
        if not self.sliding_window_layout or not self.sliding_window_size:
            return (False,) * self.num_layers
        if len(self.sliding_window_layout) < self.num_layers:
            raise ValueError(f"sliding_window_layout has {len(self.sliding_window_layout)} "
                             f"entries for {self.num_layers} layers")
        return tuple(bool(w) for w in self.sliding_window_layout[: self.num_layers])

    def layer_view(self, i: int) -> "ModelConfig":
        """The configuration decoder layer ``i`` runs under: the model's, with the
        layer's own window (``attn_window``) and "nope" for ``pos_embed`` where the
        published layouts say so; the model's own where it has no such layout."""
        if not self.sliding_window_layout and not self.rope_layout:
            return self
        if self.rope_layout and len(self.rope_layout) < self.num_layers:
            raise ValueError(
                f"rope_layout has {len(self.rope_layout)} entries for {self.num_layers} layers")
        rope = not self.rope_layout or bool(self.rope_layout[i])
        view = self.replace(
            attn_window=self.sliding_window_size if self.window_layers[i] else 0,
            pos_embed=self.pos_embed if rope else "nope")
        if view.attn_window and self.swa_kv_rank:
            # a latent stack's window layer: its own sizes, no indexer
            view = view.replace(
                num_heads=self.swa_num_heads, mla_nope_dim=self.swa_nope_dim,
                mla_rope_dim=self.swa_rope_dim, mla_v_dim=self.swa_v_dim,
                mla_kv_rank=self.swa_kv_rank, mla_q_rank=self.swa_q_rank,
                rope_theta=self.swa_rope_theta, mla_index_topk=0,
                attn_head_dim=self.swa_nope_dim + self.swa_rope_dim)
        return view

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def total_layers(self) -> int:
        """Layers carrying a per-layer strategy: encoder + decoder."""
        return self.enc_layers + self.num_layers

    @property
    def grid(self) -> int:
        """Vision: patches per image side at stage 0."""
        return self.image_size // self.patch_size

    @property
    def n_patches(self) -> int:
        return self.grid * self.grid

    @property
    def sample_len(self) -> int:
        """Token length of one training sample (before the +1 label shift)."""
        if self.image_size:
            return self.image_size * self.image_size * self.num_channels
        return self.enc_seq + self.max_seq_len if self.enc_layers else self.max_seq_len

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.hidden_size // self.num_heads

    @property
    def rotary_dim(self) -> int:
        """Leading dims of a head that rotate (the rest pass); a latent-attention
        model's rotary part is a width of its own."""
        if self.mla_kv_rank:
            return self.mla_rope_dim
        return int(self.head_dim * self.rotary_fraction)

    @property
    def expert_ffn(self) -> int:
        """Width of one routed expert of the dropless path."""
        return self.moe_ffn_dim or self.ffn

    @property
    def moe_held(self) -> int:
        """Experts this copy holds (all of them unless ``moe_share`` says less)."""
        return self.moe_experts // self.moe_share[1]

    @property
    def moe_first_held(self) -> int:
        return self.moe_share[0] * self.moe_held

    @property
    def moe_holds_share(self) -> bool:
        """This copy holds fewer experts than its router scores."""
        return self.moe_share[1] > 1

    @property
    def qkv_blocked(self) -> bool:
        """Fused-QKV weight layout: blocked (h, 3, n·hd) without GQA —
        contiguous q/k/v extraction — vs GQA-interleaved (see qkv_dims)."""
        return self.kv_heads == self.num_heads

    @property
    def ffn(self) -> int:
        if self.ffn_dim is not None:
            return self.ffn_dim
        if self.act_fn == "swiglu":
            # llama convention: 2/3 * 4h rounded up to multiple of 256
            f = int(2 * 4 * self.hidden_size / 3)
            return (f + 255) // 256 * 256
        return 4 * self.hidden_size

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Parameter initialization + logical-axes annotations
# ---------------------------------------------------------------------------


def _dense_init(key, in_dim, out_dim, dtype):
    scale = 1.0 / np.sqrt(in_dim)
    return jax.random.uniform(key, (in_dim, out_dim), dtype, -scale, scale)


def qkv_dims(cfg: ModelConfig) -> Tuple[int, int]:
    """(kv groups, per-group width) of the fused QKV projection in the GQA
    (interleaved) layout: columns are interleaved by kv-head group — group g
    holds its n/kv query heads then its k head then its v head (Megatron's
    fused-QKV ColumnParallel layout with GQA head-group splitting, reference:
    galvatron/core/tensor_parallel/transformer.py:679-708) — so TP shards at
    kv-group boundaries never split a q|k|v slice.

    Without GQA (kv_heads == num_heads, ``cfg.qkv_blocked``) the weight is
    instead stored 3D as (h, 3, n·hd) — one slot each for Q/K/V, TP sharding
    the head dim of every slot. The blocked layout makes the q/k/v extraction
    a contiguous slice; the interleaved layout's per-head strided gather
    costs ~2 ms/layer-batch at the 7B shape on v5e."""
    group = (cfg.num_heads // cfg.kv_heads + 2) * cfg.head_dim
    return cfg.kv_heads, group


def qkv_project(x, w, cfg: ModelConfig):
    """Fused QKV GEMM in the stored layout's natural shape: blocked weights
    (h, 3, n·hd) contract via einsum to (…, 3, n·hd); interleaved weights
    (h, kv·group) via a plain matmul. int8-quantized weights (serving,
    ops.quant) dequantize inside the GEMM with an fp32 accumulator."""
    if isinstance(w, QuantTensor):
        if cfg.qkv_blocked:
            return qeinsum("...h,hcd->...cd", x, w)
        return qmatmul(x, w)
    if cfg.qkv_blocked:
        return jnp.einsum("...h,hcd->...cd", x, w.astype(x.dtype))
    return x @ w.astype(x.dtype)


def project_qkv_heads(x, p_attn, cfg: ModelConfig):
    """Fused projection straight to per-head q/k/v — the only supported way
    to consume an attention param dict (qkv_project and split_qkv are
    layout-dependent halves that must always be paired; the optional
    GPT-2-style bias rides the blocked (3, n·hd) slots)."""
    y = qkv_project(x, p_attn["wqkv"], cfg)
    if "wqkv_b" in p_attn:
        y = y + p_attn["wqkv_b"].astype(y.dtype)
    q, k, v = split_qkv(y, cfg)
    if cfg.qk_norm:
        with jax.named_scope("qk_norm"):
            q = qk_norm(q, p_attn["q_norm"], cfg, (-2, -1))
            k = qk_norm(k, p_attn["k_norm"], cfg, (-2, -1))
    return q, k, v


def qk_norm(t, scale, cfg: ModelConfig, axes):
    """RMSNorm of a q or k projection over ALL its heads together (``axes``:
    the head and head-dim axes of ``t``), learned ``scale`` of the whole
    projection width (n·hd,) — OLMoE's q_norm / k_norm, applied before rope;
    with ``cfg.qk_norm_per_head`` over each head's own head_dim, ``scale`` (hd,);
    ``(1 + scale)`` under ``cfg.norm_zero_centered``. fp32 statistics, rematerialized under the 'policy' recompute like every
    other norm (no fp32-widened copy of the projection survives)."""
    n, hd = t.shape[axes[0]], t.shape[axes[1]]
    shape = [1] * t.ndim
    shape[axes[1]] = hd
    if cfg.qk_norm_per_head:
        axes = axes[1:]
    else:
        shape[axes[0]] = n

    def impl(t_, scale_):
        t32 = t_.astype(jnp.float32)
        t32 = t32 * jax.lax.rsqrt(jnp.mean(t32 * t32, axis=axes, keepdims=True) + cfg.norm_eps)
        w32 = scale_.astype(jnp.float32).reshape(shape)
        return (t32 * (1.0 + w32 if cfg.norm_zero_centered else w32)).astype(t_.dtype)

    if cfg.mlp_recompute == "policy":
        impl = jax.checkpoint(impl)
    return impl(t, scale)


def attn_output(o, p_attn, cfg: ModelConfig, dtype):
    """(B, S, n, hd) attention context → (B, S, h) via the output projection
    (+ optional bias, added after the row-parallel reduction)."""
    b, s = o.shape[:2]
    ctx = o.reshape(b, s, cfg.num_heads * cfg.head_dim)
    wo = p_attn["wo"]
    y = qmatmul(ctx, wo) if isinstance(wo, QuantTensor) else ctx @ wo.astype(dtype)
    if "wo_b" in p_attn:
        y = y + p_attn["wo_b"].astype(dtype)
    return y


def project_gate(x, p_attn, cfg: ModelConfig):
    """The output gate's projection of the block's normed input ``x`` (..., h) ->
    (..., n, hd) where the block has one (``cfg.attn_gate``), else None."""
    if not cfg.attn_gate:
        return None
    g = x @ p_attn["wgate"].astype(x.dtype)  # (served int8 weights leave it as it is)
    return g.reshape(*x.shape[:-1], cfg.num_heads, cfg.head_dim)


def gate_output(o, gate):
    """``o * sigmoid(gate)`` per head and channel in float32, both of one layout
    ((..., n, hd), or head-major), under scope ``gate``; ``o`` as it is where the
    block has no gate (None)."""
    if gate is None:
        return o
    with jax.named_scope("gate"):
        return (o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(o.dtype)


def split_qkv(qkv, cfg: ModelConfig):
    """Fused projection → q (…, n, hd), k/v (…, kv, hd). Accepts the blocked
    (…, 3, n·hd) or interleaved (…, kv·group) projection output."""
    if cfg.qkv_blocked:
        lead = qkv.shape[:-2]
        r = qkv.reshape(*lead, 3, cfg.num_heads, cfg.head_dim)
        return r[..., 0, :, :], r[..., 1, :, :], r[..., 2, :, :]
    kv, group = qkv_dims(cfg)
    npg = cfg.num_heads // cfg.kv_heads  # query heads per kv group
    r = qkv.reshape(*qkv.shape[:-1], kv, npg + 2, cfg.head_dim)
    q = r[..., :npg, :].reshape(*qkv.shape[:-1], cfg.num_heads, cfg.head_dim)
    return q, r[..., npg, :], r[..., npg + 1, :]


def _norm_scale_init(cfg: ModelConfig, n: int):
    """A norm's learned scale at its start: 1, or 0 where the norm is ``(1 + w)``."""
    return (jnp.zeros if cfg.norm_zero_centered else jnp.ones)((n,), cfg.param_dtype)


def _without_mlp(p: Params) -> Params:
    """A layer's tree without its MLP and the MLP's norms (``mlp_layout`` 0)."""
    return {k: v for k, v in p.items() if k not in ("mlp", "mlp_norm", "post_mlp_norm")}


def init_layer_params(key, cfg: ModelConfig, cross: bool = False,
                      kind: str = "attention", dense_mlp: bool = False,
                      mlp: bool = True) -> Params:
    """``dense_mlp``: the layer's MLP is the plain one of ``ffn`` though the
    model's are expert layers (a leading layer: ``moe_dense_layers``); ``mlp``
    False: the layer is its mixer alone (``mlp_layout``)."""
    h, hd = cfg.hidden_size, cfg.head_dim
    if not mlp:
        return _without_mlp(init_layer_params(key, cfg.replace(moe_experts=0, ffn_dim=1),
                                              cross=cross, kind=kind))
    if kind in mixers.MIXERS:
        # the mixer in place of attention; norms and MLP are an attention layer's
        k_mix, k_rest = jax.random.split(key)
        p = init_layer_params(k_rest, cfg, cross=cross, dense_mlp=dense_mlp)
        del p["attn"]
        p[kind] = mixers.module(kind).init_params(k_mix, cfg)
        return p
    q_out = cfg.num_heads * hd
    kv_out = cfg.kv_heads * hd
    kv, group = qkv_dims(cfg)
    ks = jax.random.split(key, 8)
    wqkv = _dense_init(ks[0], h, kv * group, cfg.param_dtype)
    if cfg.qkv_blocked:
        wqkv = wqkv.reshape(h, 3, q_out)
    p: Params = {
        "attn_norm": {"scale": _norm_scale_init(cfg, h)},
        "attn": {
            "wqkv": wqkv,
            "wo": _dense_init(ks[3], q_out, h, cfg.param_dtype),
        },
        "mlp_norm": {"scale": _norm_scale_init(cfg, h)},
    }
    if cfg.attn_gate:
        p["attn"]["wgate"] = _dense_init(ks[1], h, q_out, cfg.param_dtype)
    if cfg.post_norms:
        p["post_attn_norm"] = {"scale": _norm_scale_init(cfg, h)}
        p["post_mlp_norm"] = {"scale": _norm_scale_init(cfg, h)}
    if cfg.qk_norm:
        per_head = cfg.qk_norm_per_head
        p["attn"]["q_norm"] = _norm_scale_init(cfg, hd if per_head else q_out)
        p["attn"]["k_norm"] = _norm_scale_init(cfg, hd if per_head else kv_out)
    if cfg.use_bias:
        if not cfg.qkv_blocked:
            raise ValueError("use_bias needs the blocked qkv layout (no GQA)")
        p["attn"]["wqkv_b"] = jnp.zeros((3, q_out), cfg.param_dtype)
        p["attn"]["wo_b"] = jnp.zeros((h,), cfg.param_dtype)
    if cross:  # enc-dec decoder layer: cross-attention over the encoder output
        ck = jax.random.split(ks[7], 4)
        p["cross_norm"] = {"scale": jnp.ones((h,), cfg.param_dtype)}
        p["cross"] = {
            "wq": _dense_init(ck[0], h, q_out, cfg.param_dtype),
            "wkv": _dense_init(ck[1], h, 2 * kv_out, cfg.param_dtype),
            "wo": _dense_init(ck[3], q_out, h, cfg.param_dtype),
        }
        if cfg.norm_type == "layernorm":
            p["cross_norm"]["bias"] = jnp.zeros((h,), cfg.param_dtype)
    if cfg.moe_experts > 0 and not dense_mlp:
        from galvatron_tpu.models import moe

        p["mlp"] = moe.init_moe_params(ks[4], cfg)
    elif cfg.act_fn == "swiglu":
        # fused gate pair [w1 | w3] (Megatron dense_h_to_4h with swiglu,
        # reference ParallelMLP transformer.py:78-159): one wide GEMM; the F
        # boundary aligns with every power-of-two TP shard
        p["mlp"] = {
            "w13": _dense_init(ks[4], h, 2 * cfg.ffn, cfg.param_dtype),
            "w2": _dense_init(ks[6], cfg.ffn, h, cfg.param_dtype),
        }
        if cfg.use_bias:
            p["mlp"]["w13_b"] = jnp.zeros((2 * cfg.ffn,), cfg.param_dtype)
            p["mlp"]["w2_b"] = jnp.zeros((h,), cfg.param_dtype)
    else:
        p["mlp"] = {
            "w1": _dense_init(ks[4], h, cfg.ffn, cfg.param_dtype),
            "w2": _dense_init(ks[6], cfg.ffn, h, cfg.param_dtype),
        }
        if cfg.use_bias:
            p["mlp"]["w1_b"] = jnp.zeros((cfg.ffn,), cfg.param_dtype)
            p["mlp"]["w2_b"] = jnp.zeros((h,), cfg.param_dtype)
    if cfg.norm_type == "layernorm":
        for name in _layer_norms(cfg):
            p[name]["bias"] = jnp.zeros((h,), cfg.param_dtype)
    return p


def _layer_norms(cfg: ModelConfig) -> Tuple[str, ...]:
    """The norms over the hidden width a decoder layer holds."""
    post = ("post_attn_norm", "post_mlp_norm") if cfg.post_norms else ()
    return ("attn_norm", "mlp_norm") + post


def layer_annotations(cfg: ModelConfig, cross: bool = False,
                      kind: str = "attention", dense_mlp: bool = False,
                      mlp: bool = True) -> Params:
    """Logical axes per layer param: 'tp' = Megatron-sharded dim (column-out /
    row-in), 'fsdp' = the dim ZeRO shards (reference: FSDP flat-param sharding,
    galvatron/core/parallel.py:174-207)."""
    if not mlp:
        return _without_mlp(layer_annotations(cfg.replace(moe_experts=0), cross=cross, kind=kind))
    if kind in mixers.MIXERS:
        a = layer_annotations(cfg, cross=cross, dense_mlp=dense_mlp)
        del a["attn"]
        a[kind] = mixers.module(kind).annotations(cfg)
        return a
    a: Params = {
        "attn_norm": {"scale": ("fsdp",)},
        "attn": {
            # blocked layout: TP shards the head dim of each q/k/v slot
            "wqkv": ("fsdp", None, "tp") if cfg.qkv_blocked else ("fsdp", "tp"),
            "wo": ("tp", "fsdp"),
        },
        "mlp_norm": {"scale": ("fsdp",)},
    }
    if cfg.attn_gate:
        a["attn"]["wgate"] = ("fsdp", "tp")
    if cfg.post_norms:
        a["post_attn_norm"] = {"scale": ("fsdp",)}
        a["post_mlp_norm"] = {"scale": ("fsdp",)}
    if cfg.qk_norm:
        # scales of the projection's output width: sharded with the heads (a head's own: whole)
        a["attn"]["q_norm"] = (None,) if cfg.qk_norm_per_head else ("tp",)
        a["attn"]["k_norm"] = (None,) if cfg.qk_norm_per_head else ("tp",)
    if cfg.use_bias:
        # column-parallel biases shard with their output dim; the
        # row-parallel output bias is added once after the reduction
        a["attn"]["wqkv_b"] = (None, "tp")
        a["attn"]["wo_b"] = ("fsdp",)
    if cross:
        a["cross_norm"] = {"scale": ("fsdp",)}
        a["cross"] = {
            "wq": ("fsdp", "tp"),
            "wkv": ("fsdp", "tp"),
            "wo": ("tp", "fsdp"),
        }
        if cfg.norm_type == "layernorm":
            a["cross_norm"]["bias"] = ("fsdp",)
    if cfg.moe_experts > 0 and not dense_mlp:
        from galvatron_tpu.models import moe

        a["mlp"] = moe.moe_annotations(cfg)
    elif cfg.act_fn == "swiglu":
        a["mlp"] = {"w13": ("fsdp", "tp"), "w2": ("tp", "fsdp")}
        if cfg.use_bias:
            a["mlp"]["w13_b"] = ("tp",)
            a["mlp"]["w2_b"] = ("fsdp",)
    else:
        a["mlp"] = {"w1": ("fsdp", "tp"), "w2": ("tp", "fsdp")}
        if cfg.use_bias:
            a["mlp"]["w1_b"] = ("tp",)
            a["mlp"]["w2_b"] = ("fsdp",)
    if cfg.norm_type == "layernorm":
        for name in _layer_norms(cfg):
            a[name]["bias"] = ("fsdp",)
    return a


# --- vision (ViT / Swin) static geometry -----------------------------------


def swin_stage_of(cfg: ModelConfig, i: int) -> Tuple[int, int]:
    """Layer index → (stage, index within stage) for hierarchical Swin."""
    for s, d in enumerate(cfg.swin_depths):
        if i < d:
            return s, i
        i -= d
    raise IndexError(f"layer {i} beyond swin_depths {cfg.swin_depths}")


def swin_geometry(cfg: ModelConfig, stage: int) -> Tuple[int, int, int, int]:
    """Stage → (H, W, C, heads): resolution halves and width/heads double per
    stage (Swin's hierarchical pyramid)."""
    side = cfg.grid >> stage
    return side, side, cfg.hidden_size << stage, cfg.num_heads << stage


def swin_window_for(cfg: ModelConfig, stage: int) -> int:
    """Static per-stage window: ``swin_window`` shrunk to the largest value
    that divides the stage's side (windows must tile the feature map; the
    canonical 224/patch-4 presets keep the full 7)."""
    side = cfg.grid >> stage
    w = min(cfg.swin_window, side)
    while side % w:
        w -= 1
    return w


def vision_layer_cfg(cfg: ModelConfig, i: int) -> ModelConfig:
    """Per-layer shape config for vision layers: identity for ViT; for Swin
    the stage-s widening (C·2^s, heads·2^s — head_dim constant) so the same
    init_layer_params/layer_annotations serve every stage."""
    if not cfg.swin_depths:
        return cfg
    s, _ = swin_stage_of(cfg, i)
    _, _, c, heads = swin_geometry(cfg, s)
    return cfg.replace(hidden_size=c, num_heads=heads, num_kv_heads=None)


def init_vision_base_params(ks, cfg: ModelConfig) -> Params:
    """Non-layer vision params (patch-projection embed / final norm / class
    head) from three keys — the single source both the GSPMD init and the
    pipeline engines' base init draw from. Swin's final_norm/head sit at
    c_last = hidden·2^(stages-1)."""
    patch_dim = cfg.patch_size * cfg.patch_size * cfg.num_channels
    c_last = cfg.hidden_size << max(0, len(cfg.swin_depths) - 1)
    base: Params = {
        "embed": {
            "proj": _dense_init(ks[0], patch_dim, cfg.hidden_size, cfg.param_dtype),
            "pos": jax.random.normal(
                ks[1], (cfg.n_patches, cfg.hidden_size), cfg.param_dtype
            )
            * 0.02,
        },
        "final_norm": {"scale": jnp.ones((c_last,), cfg.param_dtype)},
        "head": {"w": _dense_init(ks[2], c_last, cfg.num_classes, cfg.param_dtype)},
    }
    if cfg.norm_type == "layernorm":
        base["final_norm"]["bias"] = jnp.zeros((c_last,), cfg.param_dtype)
    return base


def vision_base_annotations(cfg: ModelConfig) -> Params:
    a: Params = {
        "embed": {"proj": ("fsdp", "tp"), "pos": ("fsdp", None)},
        "final_norm": {"scale": ("fsdp",)},
        "head": {"w": ("fsdp", "tp")},
    }
    if cfg.norm_type == "layernorm":
        a["final_norm"]["bias"] = ("fsdp",)
    return a


def init_vision_params(key, cfg: ModelConfig) -> Params:
    """ViT/Swin parameter tree: patch-projection embedding + learned position
    table + encoder layers (+ Swin patch-merging projections) + pooled
    classification head. Reference carries vit/swin only as legacy wrapping
    branches (galvatron/core/parallel.py:64-89); here they are live families."""
    if cfg.swin_depths and sum(cfg.swin_depths) != cfg.num_layers:
        raise ValueError(
            f"swin_depths {cfg.swin_depths} sum to {sum(cfg.swin_depths)} but "
            f"num_layers is {cfg.num_layers} (per-layer strategies index the "
            "flattened stage layers; keep them equal)"
        )
    if cfg.image_size % cfg.patch_size:
        raise ValueError(
            f"patch_size {cfg.patch_size} must divide image_size {cfg.image_size}"
        )
    L = cfg.num_layers
    ks = jax.random.split(key, L + 4)
    params = init_vision_base_params([ks[0], ks[1], ks[-1]], cfg)
    params["layers"] = [
        init_layer_params(ks[i + 2], vision_layer_cfg(cfg, i)) for i in range(L)
    ]
    if cfg.swin_depths:
        n_stages = len(cfg.swin_depths)
        mks = jax.random.split(ks[-2], max(1, n_stages - 1))
        params["merges"] = []
        for s in range(n_stages - 1):
            c = cfg.hidden_size << s
            m = {"w": _dense_init(mks[s], 4 * c, 2 * c, cfg.param_dtype),
                 "norm": {"scale": jnp.ones((4 * c,), cfg.param_dtype)}}
            if cfg.norm_type == "layernorm":
                m["norm"]["bias"] = jnp.zeros((4 * c,), cfg.param_dtype)
            params["merges"].append(m)
    return params


def vision_annotations(cfg: ModelConfig) -> Params:
    a = vision_base_annotations(cfg)
    a["layers"] = [
        layer_annotations(vision_layer_cfg(cfg, i)) for i in range(cfg.num_layers)
    ]
    if cfg.swin_depths:
        a["merges"] = []
        for s in range(len(cfg.swin_depths) - 1):
            m = {"w": ("fsdp", None), "norm": {"scale": ("fsdp",)}}
            if cfg.norm_type == "layernorm":
                m["norm"]["bias"] = ("fsdp",)
            a["merges"].append(m)
    return a


def init_model_params(key, cfg: ModelConfig) -> Params:
    if cfg.image_size:
        return init_vision_params(key, cfg)
    ks = jax.random.split(key, cfg.total_layers + 3)
    cross = cfg.enc_layers > 0
    params: Params = {
        "embed": {
            "tok": jax.random.normal(ks[0], (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
            * 0.02
        },
        "layers": [
            init_layer_params(ks[cfg.enc_layers + i + 1], cfg.layer_view(i), cross=cross,
                              kind=kind, dense_mlp=i < cfg.moe_dense_layers,
                              mlp=cfg.mlp_layers[i])
            for i, kind in enumerate(cfg.kinds)
        ],
        "final_norm": {"scale": _norm_scale_init(cfg, cfg.hidden_size)},
    }
    if cross:
        params["enc_layers"] = [
            init_layer_params(ks[i + 1], cfg) for i in range(cfg.enc_layers)
        ]
        params["enc_final_norm"] = {"scale": jnp.ones((cfg.hidden_size,), cfg.param_dtype)}
        if cfg.norm_type == "layernorm":
            params["enc_final_norm"]["bias"] = jnp.zeros((cfg.hidden_size,), cfg.param_dtype)
    if cfg.pos_embed == "learned":
        pos_len = max(cfg.max_seq_len, cfg.enc_seq)
        params["embed"]["pos"] = (
            jax.random.normal(ks[-2], (pos_len, cfg.hidden_size), cfg.param_dtype) * 0.02
        )
    if cfg.norm_type == "layernorm":
        params["final_norm"]["bias"] = jnp.zeros((cfg.hidden_size,), cfg.param_dtype)
    if not cfg.tie_word_embeddings:
        params["head"] = {
            "w": _dense_init(ks[-1], cfg.hidden_size, cfg.vocab_size, cfg.param_dtype)
        }
    return params


def model_annotations(cfg: ModelConfig) -> Params:
    """Embedding is vocab-parallel over its TP axes (reference:
    VocabParallelEmbedding, site_package/megatron/core/tensor_parallel/
    layers.py:157; vocab_tp flag galvatron/core/arguments.py:128-130)."""
    if cfg.image_size:
        return vision_annotations(cfg)
    cross = cfg.enc_layers > 0
    a: Params = {
        "embed": {"tok": ("tp", "fsdp")},
        "layers": [layer_annotations(cfg.layer_view(i), cross=cross, kind=kind,
                                     dense_mlp=i < cfg.moe_dense_layers, mlp=cfg.mlp_layers[i])
                   for i, kind in enumerate(cfg.kinds)],
        "final_norm": {"scale": ("fsdp",)},
    }
    if cross:
        a["enc_layers"] = [layer_annotations(cfg) for _ in range(cfg.enc_layers)]
        a["enc_final_norm"] = {"scale": ("fsdp",)}
        if cfg.norm_type == "layernorm":
            a["enc_final_norm"]["bias"] = ("fsdp",)
    if cfg.pos_embed == "learned":
        a["embed"]["pos"] = ("fsdp", None)
    if cfg.norm_type == "layernorm":
        a["final_norm"]["bias"] = ("fsdp",)
    if not cfg.tie_word_embeddings:
        a["head"] = {"w": ("fsdp", "tp")}
    return a


# ---------------------------------------------------------------------------
# Forward pieces
# ---------------------------------------------------------------------------


def _norm_impl(x, p, cfg: ModelConfig):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    if cfg.norm_type == "rms":
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + cfg.norm_eps)
        scale = p["scale"].astype(jnp.float32)
        out = x32 * (1.0 + scale if cfg.norm_zero_centered else scale)
    else:
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
        out = (x32 - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(dt)


def norm(x, p, cfg: ModelConfig):
    """RMSNorm / LayerNorm, left to XLA's own fusion (reference fused-norm CUDA
    ops: megatron fused_layer_norm / rms_norm, flash-attn dropout_add_rms_norm —
    SURVEY §2.1).

    Under ``mlp_recompute='policy'`` the fp32 statistics are rematerialized
    in the backward from the compute-dtype input — without the wrap, autodiff
    saves an fp32-widened (B, S, H) copy of every normed activation (the
    round-5 HLO buffer audit's 67 MB/layer class)."""
    with jax.named_scope("norm"):
        if cfg.mlp_recompute == "policy":
            return jax.checkpoint(lambda x_, p_: _norm_impl(x_, p_, cfg))(x, p)
        return _norm_impl(x, p, cfg)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor ``0.1 mscale ln(factor) + 1`` (1 without scaling)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """One inverse frequency a rotary pair. With ``rope_yarn`` (``deepseek_yarn``)
    pair i blends ``theta^(-2i/d)`` and the same over ``factor`` by a linear
    ramp between the pairs that make ``beta_fast`` and ``beta_slow`` turns over
    the original positions (floored / ceiled, as the published code does): fast
    pairs keep their frequency, slow ones are interpolated."""
    rot = cfg.rotary_dim  # the whole head unless rotary_fraction says less
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, rot, 2) / rot))
    if not cfg.rope_yarn:
        return inv
    factor, original, beta_fast, beta_slow = cfg.rope_yarn[:4]

    def pair_of(turns):  # the (fractional) pair that makes ``turns`` turns
        return rot * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), rot - 1)
    ramp = np.clip((np.arange(rot // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return inv / factor * ramp + inv * (1.0 - ramp)


def rope_tables(cfg: ModelConfig, seq_len: int, offset: int = 0):
    pos = np.arange(offset, offset + seq_len)
    freqs = np.outer(pos, rope_inv_freq(cfg))  # (S, rot/2)
    scale = 1.0
    if cfg.rope_yarn:
        factor, _, _, _, mscale, mscale_all_dim = cfg.rope_yarn
        scale = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    return (jnp.asarray(np.cos(freqs) * scale, jnp.float32),
            jnp.asarray(np.sin(freqs) * scale, jnp.float32))


def apply_rope(x, cos, sin):
    """x: (B, S, n, hd). Rotate-half convention (reference: rotary_pos_embedding
    apply_rotary_pos_emb, site_package/megatron/core/models/common/embeddings/
    rotary_pos_embedding.py:144).

    ``cos``/``sin`` are ``(S, hd/2)`` tables shared across the batch, or
    ``(B, S, hd/2)`` per-row tables (slot-wise decode: each batch row sits at
    its own absolute position)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    if cos.ndim == 3:
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
    else:
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dt)


def positions_from_segments(seg):
    """Per-segment position ids from a ``(B, S)`` packed segment-id array:
    position i's index within its own segment. Relies on the packer's layout
    contract — segment ids are monotonically non-decreasing along the row
    (documents are laid out contiguously), so a segment's start is the last
    index where the id changed."""
    idx = jnp.arange(seg.shape[1], dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], axis=1
    )
    seg_start = jax.lax.cummax(jnp.where(is_start, idx[None], 0), axis=1)
    return idx[None] - seg_start


def split_packed_inputs(inputs):
    """Packed model-input rows ``(B, 2·S)`` = tokens ‖ segment ids →
    (tokens (B, S), segment ids (B, S), per-segment position ids (B, S))."""
    s = inputs.shape[1] // 2
    tokens = inputs[:, :s]
    seg = inputs[:, s:]
    return tokens, seg, positions_from_segments(seg)


def packed_rope_tables(cfg: ModelConfig, pos_ids):
    """Per-row rope tables for packed sequences: the shared ``(S, hd/2)``
    tables gathered by per-segment positions → ``(B, S, hd/2)`` (the same
    per-row form the serving engine's slot-wise decode uses). For a row that
    is one whole segment this gathers ``arange(S)`` — bit-identical values to
    the unpacked broadcast path."""
    cos, sin = rope_tables(cfg, pos_ids.shape[1])
    return cos[pos_ids], sin[pos_ids]


def alibi_slopes(n_heads: int) -> np.ndarray:
    # standard ALiBi slope schedule (press et al.); baichuan-13B path
    def pow2slopes(n):
        start = 2 ** (-(2 ** -(np.log2(n) - 3)))
        return start * (start ** np.arange(n))

    if np.log2(n_heads).is_integer():
        return pow2slopes(n_heads)
    k = 2 ** int(np.floor(np.log2(n_heads)))
    return np.concatenate([pow2slopes(k), pow2slopes(2 * k)[0::2][: n_heads - k]])


def _repeat_kv(x, n_rep: int):
    if n_rep == 1:
        return x
    b, s, kvh, hd = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, kvh, n_rep, hd)).reshape(
        b, s, kvh * n_rep, hd
    )


#: what a masked score reads, and where a running maximum starts
MASKED_SCORE = -1e30


def key_block(positions: int, most: int) -> int:
    """Keys a step of a blockwise attention over a row of ``positions`` takes: the
    largest divisor of the row up to ``most`` that `math.gcd` finds (all of a small
    or odd row)."""
    block = math.gcd(positions, most)
    return block if positions > most and block >= 8 else positions


def running_softmax(blocks, scored, rows: tuple, width: int):
    """A softmax over keys taken a block at a time, so that only one block's float32
    scores live at once: ``scored(j)`` -> (block ``j``'s masked float32 scores
    (*rows, K), a function from their exponentials (*rows, K) to the block's float32
    share of the output (*rows, width)``; ``blocks`` may be traced. -> (*rows, width)
    float32, normalised. A block wholly masked for a query counts 1 a key against
    `MASKED_SCORE`; the first key the query does see shrinks that to an exact 0, so
    every query has to see a key (causal attention: its own)."""

    def step(j, carry):
        m, total, acc = carry
        scores, weigh = scored(j)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        shrink = jnp.exp(m - m_new)
        e = jnp.exp(scores - m_new[..., None])
        acc = acc * shrink[..., None] + weigh(e)
        return m_new, total * shrink + jnp.sum(e, axis=-1), acc

    init = (jnp.full(rows, MASKED_SCORE, jnp.float32), jnp.zeros(rows, jnp.float32),
            jnp.zeros(rows + (width,), jnp.float32))
    _, total, acc = jax.lax.fori_loop(0, blocks, step, init)
    return acc / total[..., None]


def attention_xla(q, k, v, cfg: ModelConfig, bias=None, q_offset=0, seg_ids=None):
    """Reference einsum attention (the 'CoreAttention' path, reference:
    galvatron/core/tensor_parallel/transformer.py:298-435).

    k/v may be longer than q (KV-cache decode): query i sits at absolute
    position ``q_offset + i`` and sees keys at positions <= its own.
    ``q_offset`` may be a traced scalar, or a traced ``(B,)`` vector giving
    each batch row its own absolute position — the slot-wise entry point used
    by the continuous-batching serving engine, where every row of the batch
    is a different request at a different depth into its sequence.

    ``cfg.attn_window`` (a window layer's view, ``ModelConfig.layer_view``): query
    at position p sees the keys j with ``p - window < j <= p``.

    ``seg_ids`` ((B, S), packed sequences): the causal predicate tightens to
    intra-segment — query i attends to key j only when ``seg[i] == seg[j]``,
    so cross-document attention is structurally impossible. The combine is a
    logical AND on the SAME where/-1e30 pattern the plain causal mask uses:
    a row holding a single segment produces a bit-identical mask, which is
    what makes the packed-vs-padded gradient-parity test exact."""
    b, s, nh, hd = q.shape
    if s == 1 and bias is None and seg_ids is None and cfg.causal and not cfg.attn_window:
        # KV-cache decode: skip the _repeat_kv materialization and the
        # (b, n, 1, k) score reshuffle — the GQA-native dot-product path
        # reads the cache once (tests/test_flash_attention.py parity case)
        from galvatron_tpu.ops.flash_attention import decode_attention

        return decode_attention(q, k, v, q_offset=q_offset, sm_scale=cfg.attention_multiplier)
    k = _repeat_kv(k, nh // k.shape[2])
    v = _repeat_kv(v, nh // v.shape[2])
    scores = jnp.einsum("bqnh,bknh->bnqk", q, k).astype(jnp.float32)
    if cfg.attention_multiplier is None:
        scores = scores / np.sqrt(hd)
    else:
        scores = scores * cfg.attention_multiplier
    if bias is not None:
        scores = scores + bias
    if cfg.causal:
        # (1|B, s): scalar offset broadcasts over the batch; a (B,) offset
        # yields a per-row mask (scores are (b, n, q, k))
        q_pos = jnp.reshape(jnp.asarray(q_offset), (-1, 1)) + jnp.arange(s)[None]
        k_pos = jnp.arange(k.shape[1])
        allowed = k_pos[None, None, :] <= q_pos[:, :, None]
        if cfg.attn_window:  # (a layer's view: the window holds the query's own position)
            allowed = allowed & (k_pos[None, None, :] > q_pos[:, :, None] - cfg.attn_window)
        if seg_ids is not None:
            allowed = allowed & (seg_ids[:, :, None] == seg_ids[:, None, :])
        scores = jnp.where(allowed[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bnqk,bknh->bqnh", probs, v)


def attention(q, k, v, cfg: ModelConfig, bias=None, rope=None, seg_ids=None,
              place: Placement = LOCAL):
    """``rope``: optional (cos, sin) tables. On the flash path they are fused
    into the Pallas kernels (no HBM round-trip of roped q/k); otherwise
    apply_rope runs here before the einsum path. ``seg_ids`` (packed
    sequences) forces the einsum path — the Pallas kernels carry no segment
    mask (build_runtime rejects pack_sequences with attn_impl='flash')."""
    if cfg.attn_impl == "flash" and bias is None and seg_ids is None:
        from galvatron_tpu.ops.flash_attention import flash_attention

        nh = q.shape[2]
        k = _repeat_kv(k, nh // k.shape[2])
        v = _repeat_kv(v, nh // v.shape[2])
        bsnd = (0, 2)  # (b, s, n, d) layout: batch dim 0, head dim 2
        if rope is None:
            kernel = place.shard_kernel(
                lambda q_, k_, v_: flash_attention(
                    q_, k_, v_, causal=cfg.causal, sm_scale=cfg.attention_multiplier),
                [bsnd] * 3,
                bsnd,
            )
            return kernel(q, k, v)
        kernel = place.shard_kernel(
            lambda q_, k_, v_, c_, s_: flash_attention(
                q_, k_, v_, causal=cfg.causal, sm_scale=cfg.attention_multiplier, rope=(c_, s_)
            ),
            [bsnd] * 3 + [(None, None)] * 2,
            bsnd,
        )
        return kernel(q, k, v, *rope)
    if rope is not None:
        q = apply_rope(q, *rope)
        k = apply_rope(k, *rope)
    return attention_xla(q, k, v, cfg, bias=bias, seg_ids=seg_ids)


def _repeat_kv_hm(x, n_rep: int):
    """Head-major GQA repeat: (b, kvh, s, hd) -> (b, kvh*n_rep, s, hd),
    kv-major head order (matches _repeat_kv's interleaving)."""
    if n_rep == 1:
        return x
    b, kvh, s, hd = x.shape
    return jnp.broadcast_to(x[:, :, None], (b, kvh, n_rep, s, hd)).reshape(
        b, kvh * n_rep, s, hd
    )


def projection_seams(cfg: ModelConfig, seq_len: int) -> Tuple[Tuple[str, str, int, bool], ...]:
    """The projections of one token-stream layer that go through ``Placement.proj_up`` /
    ``proj_down``, as ``(scope, kind, width, blockwise)``: ``kind`` "ag"
    (column-parallel, ``width`` its output columns) or "rs" (row-parallel,
    ``width`` its contraction), the width being what the tp axes divide;
    ``blockwise`` False where the seam's all-gather side (an "ag" seam's
    forward, an "rs" seam's backward) puts out head-major dims and so cannot
    ring over the sequence: it gathers whole, or in pieces along the batch
    (ops.collective_matmul._allgather_matmul). What the seams' shape tests
    (ops.collective_matmul.ring_pays, batch_pieces) are asked about by the
    runtime's ``tp_overlap_seams`` count and by the search's pricing; mirrors the
    dispatch in ``attn_block`` / ``_attn_block_headmajor`` / ``mlp_block``."""
    from galvatron_tpu.ops.flash_attention import flash_tileable

    seams = []
    headmajor = (
        cfg.attn_impl == "flash" and cfg.pos_embed != "alibi" and cfg.flash_headmajor
        and not cfg.pack_sequences and not cfg.image_size and flash_tileable(seq_len)
        and (cfg.qkv_blocked or not (cfg.use_bias or cfg.qk_norm))
        and not cfg.attn_gate  # the gated block keeps plain einsums
    )
    if headmajor:
        if cfg.qkv_blocked:
            seams.append(("qkv_proj", "ag", 3 * cfg.num_heads * cfg.head_dim, False))
        seams.append(("out_proj", "rs", cfg.num_heads * cfg.head_dim, False))
    if cfg.moe_experts == 0 and not cfg.image_size:
        up = cfg.ffn * (2 if cfg.act_fn == "swiglu" else 1)
        seams += [("mlp_up", "ag", up, True), ("mlp_down", "rs", cfg.ffn, True)]
    return tuple(seams)


def _attn_block_headmajor(x, p, cfg: ModelConfig, rope, remat_attn: bool, place: Placement):
    """Flash-path attention with head-major (b, h, s, d) dataflow end to end:
    the QKV projection einsums straight to (b, 3, n, s, hd) and the output
    projection consumes (b, n, s, hd), so XLA realizes the head-major layout
    inside the GEMMs instead of materializing reshape+transpose copies
    between the projection and the kernels (~0.32 ms/layer/sample on the
    v5e 7B-shape bench; the copies were ~2.9 ms/layer-batch in the trace)."""
    from galvatron_tpu.ops.flash_attention import (
        flash_attention_hm,
        flash_attention_qkv,
        flash_qkv_supported,
    )

    b, s, h = x.shape
    hd = cfg.head_dim
    n = cfg.num_heads
    w = p["wqkv"].astype(x.dtype)

    def out_proj(o):
        with jax.named_scope("out_proj"):
            y = place.proj_down(
                "bnsd,nde->bse", o, p["wo"].astype(x.dtype).reshape(n, hd, h), w_shard_dim=0
            )
            if "wo_b" in p:
                y = y + p["wo_b"].astype(x.dtype)
            return y

    if cfg.qkv_blocked:
        with jax.named_scope("qkv_proj"):
            qkv = place.proj_up("bsh,hcnd->bcnsd", x, w.reshape(h, 3, n, hd), w_shard_dim=2)
            if "wqkv_b" in p:
                qkv = qkv + p["wqkv_b"].astype(x.dtype).reshape(3, n, hd)[None, :, :, None, :]
            qkv = place.constrain_qkv(qkv)
        if cfg.qk_norm:
            # outside the kernels: the q and k slots of the stacked projection,
            # each over its (n, d) axes together; v passes through
            with jax.named_scope("qk_norm"):
                qkv = jnp.stack(
                    [qk_norm(qkv[:, 0], p["q_norm"], cfg, (1, 3)),
                     qk_norm(qkv[:, 1], p["k_norm"], cfg, (1, 3)), qkv[:, 2]], axis=1)
        if flash_qkv_supported(s, hd, cfg.causal):
            # the kernels consume the STACKED projection output directly —
            # index-mapped block specs instead of q/k/v slice copies
            scale = cfg.attention_multiplier
            if rope is None:  # learned / absolute / no positions: no table operands
                core_qkv = place.shard_kernel(
                    flash_attention_qkv if scale is None
                    else partial(flash_attention_qkv, sm_scale=scale), [(0, 2)], (0, 1))
            else:
                kernel = place.shard_kernel(
                    lambda qkv_, c_, s_: flash_attention_qkv(
                        qkv_, sm_scale=scale, rope=(c_, s_)),
                    [(0, 2), (None, None), (None, None)],
                    (0, 1),
                )

                def core_qkv(qkv_):
                    return kernel(qkv_, *rope)

            if remat_attn:
                core_qkv = jax.checkpoint(core_qkv)
            with jax.named_scope("attn_core"):
                o = place.constrain_attn_out(core_qkv(qkv))
            return out_proj(o)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    else:
        kv, group = qkv_dims(cfg)
        npg = group // hd - 2  # query heads per kv group, per the stored layout
        with jax.named_scope("qkv_proj"):
            r = jnp.einsum("bsh,hknd->bknsd", x, w.reshape(h, kv, npg + 2, hd))
        q = r[:, :, :npg].reshape(b, n, s, hd)
        # GQA-NATIVE: K/V stay at kv_heads — the flash kernels serve each kv
        # group's queries from the resident grouped block (flash_attention_hm
        # kv_rep index maps), group-factor less K/V HBM traffic than the old
        # materialized _repeat_kv_hm copy. EXCEPT when the layer's tp degree
        # does not divide kv_heads: place.shard_kernel shards the head dim
        # over the tp axes, so grouped K/V must be repeated first (the same
        # guard ulysses applies) — q heads always divide tp.
        k = r[:, :, npg]
        v = r[:, :, npg + 1]
        if place.kernel_tp > 1 and kv % place.kernel_tp:
            k = _repeat_kv_hm(k, npg)
            v = _repeat_kv_hm(v, npg)

    qkv_dim, rep_dim = (0, 1), (None, None)
    if rope is None:
        kernel = place.shard_kernel(
            lambda q_, k_, v_: flash_attention_hm(
                q_, k_, v_, causal=cfg.causal, sm_scale=cfg.attention_multiplier),
            [qkv_dim] * 3,
            qkv_dim,
        )

        def core(q_, k_, v_):
            return kernel(q_, k_, v_)
    else:
        kernel = place.shard_kernel(
            lambda q_, k_, v_, c_, s_: flash_attention_hm(
                q_, k_, v_, causal=cfg.causal, sm_scale=cfg.attention_multiplier, rope=(c_, s_)
            ),
            [qkv_dim] * 3 + [rep_dim, rep_dim],
            qkv_dim,
        )

        def core(q_, k_, v_):
            return kernel(q_, k_, v_, *rope)

    if remat_attn:
        core = jax.checkpoint(core)
    with jax.named_scope("attn_core"):
        o = place.constrain_attn_out(core(q, k, v))
    return out_proj(o)


def head_norm(t, w, cfg: ModelConfig):
    """``qk_norm`` per head over the last axis of a head-major ``t`` (..., head_dim),
    weight ``w`` (head_dim,) shared by the heads: ``* (1 + w)`` under
    ``cfg.norm_zero_centered`` (Qwen3-Next's q_norm / k_norm), else ``* w``. fp32
    statistics, rematerialized under the 'policy' recompute like ``qk_norm``."""

    def impl(t_, w_):
        t32 = t_.astype(jnp.float32)
        t32 = t32 * jax.lax.rsqrt(jnp.mean(t32 * t32, axis=-1, keepdims=True) + cfg.norm_eps)
        w32 = w_.astype(jnp.float32)
        return (t32 * (1.0 + w32 if cfg.norm_zero_centered else w32)).astype(t_.dtype)

    if cfg.mlp_recompute == "policy":
        impl = jax.checkpoint(impl)
    return impl(t, w)


def _rope_leading_hm(x, cos, sin):
    """Rotate-half rotary on the leading ``2 * cos.shape[-1]`` dims of a
    head-major (b, n, s, d) tensor; the other dims pass."""
    rot = 2 * cos.shape[-1]
    turned = apply_rope(jnp.swapaxes(x[..., :rot], 1, 2), cos, sin)
    return jnp.concatenate([jnp.swapaxes(turned, 1, 2), x[..., rot:]], axis=-1)


def _attn_block_gated(x, p, cfg: ModelConfig, cos_sin, remat_attn: bool, seg_ids,
                      place: Placement):
    """Gated attention (``cfg.attn_gate``), head-major end to end: q, k, v from
    the GQA-interleaved fused projection, the gate from ``wgate``; per-head norms
    on q and k (``cfg.qk_norm``); rotary on the leading ``rotary_dim`` of each
    head where the layer has a position signal, applied here (the kernels then
    run their no-RoPE GQA instance: a head of 256 at s 4096 lies on the blocked
    envelope's edge); the attention core (XLA's under a window); ``sigmoid(gate)``
    on its output under scope ``gate`` (`gate_output`); the output projection."""
    from galvatron_tpu.ops.flash_attention import flash_attention_hm, flash_tileable

    if cfg.qkv_blocked or (cfg.qk_norm and not cfg.qk_norm_per_head):
        raise ValueError(
            "the gated attention block (attn_gate) reads the GQA-interleaved projection "
            "(num_kv_heads < num_heads) and norms q and k per head (qk_norm_per_head)")
    b, s, h = x.shape
    n, kv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    npg = n // kv
    # (served int8 weights reach this block through `--serve_quant`'s parity forward
    # alone: they keep their stored layout and are laid head-major after the GEMM)
    quantized = isinstance(p["wqkv"], QuantTensor)
    with jax.named_scope("qkv_proj"):
        if quantized:
            r = qkv_project(x, p["wqkv"], cfg).reshape(b, s, kv, npg + 2, hd).transpose(0, 2, 3, 1, 4)
        else:
            r = jnp.einsum("bsh,hknd->bknsd", x,
                           p["wqkv"].astype(x.dtype).reshape(h, kv, npg + 2, hd))
        gate = jnp.einsum("bsh,hnd->bnsd", x, p["wgate"].astype(x.dtype).reshape(h, n, hd))
    q, k, v = r[:, :, :npg].reshape(b, n, s, hd), r[:, :, npg], r[:, :, npg + 1]
    if cfg.qk_norm:
        with jax.named_scope("qk_norm"):
            q, k = head_norm(q, p["q_norm"], cfg), head_norm(k, p["k_norm"], cfg)
    if cfg.pos_embed == "rope":
        with jax.named_scope("rope"):
            q, k = _rope_leading_hm(q, *cos_sin), _rope_leading_hm(k, *cos_sin)
    if cfg.attn_impl == "flash" and seg_ids is None and flash_tileable(s):
        if place.kernel_tp > 1 and kv % place.kernel_tp:
            k, v = _repeat_kv_hm(k, npg), _repeat_kv_hm(v, npg)
        core = place.shard_kernel(
            lambda q_, k_, v_: flash_attention_hm(
                q_, k_, v_, causal=cfg.causal, sm_scale=cfg.attention_multiplier),
            [(0, 1)] * 3, (0, 1))
    else:
        def core(q_, k_, v_):
            o_ = attention_xla(*(jnp.swapaxes(t, 1, 2) for t in (q_, k_, v_)), cfg,
                               seg_ids=seg_ids)
            return jnp.swapaxes(o_, 1, 2)

    if remat_attn:
        core = jax.checkpoint(core)
    with jax.named_scope("attn_core"):
        o = place.constrain_attn_out(core(q, k, v))
    o = gate_output(o, gate)
    with jax.named_scope("out_proj"):
        if quantized:
            return attn_output(jnp.swapaxes(o, 1, 2), p, cfg, x.dtype)
        return jnp.einsum("bnsd,nde->bse", o, p["wo"].astype(x.dtype).reshape(n, hd, h))


@jax.named_scope("attn")
def attn_block(x, p, cfg: ModelConfig, cos_sin=None, alibi=None, remat_attn: bool = False,
               seg_ids=None, place: Placement = LOCAL):
    """``remat_attn`` rematerializes only the attention core (scores/softmax/
    context) in the backward pass — Megatron's "selective" recompute
    (reference: galvatron/core/tensor_parallel/transformer.py:597,615-636).

    ``seg_ids`` (packed sequences) routes through the einsum path with the
    intra-segment mask; the head-major flash fast path is skipped (the Pallas
    kernels carry no segment mask)."""
    b, s, h = x.shape
    hd = cfg.head_dim
    if cfg.attn_window and cfg.attn_impl != "xla":
        # (build_runtime refuses the stack through mixers.limits; a caller that comes
        # past it, ``forward`` alone, is told the table's sentence and not given
        # attention without the window)
        raise ValueError(next(
            limit.sentence() for limit in mixers.limits(cfg) if limit.what == "attn_impl"))
    if cfg.attn_gate:
        return _attn_block_gated(x, p, cfg, cos_sin, remat_attn, seg_ids, place)
    if (
        cfg.attn_impl == "flash" and cfg.pos_embed != "alibi"
        and cfg.flash_headmajor and seg_ids is None
    ):
        from galvatron_tpu.ops.flash_attention import flash_tileable

        # (biases and qk-norm ride the blocked stacked projection only)
        if flash_tileable(s) and (
            ("wqkv_b" not in p and not cfg.qk_norm) or cfg.qkv_blocked
        ):
            rope = cos_sin if cfg.pos_embed == "rope" else None
            return _attn_block_headmajor(x, p, cfg, rope, remat_attn, place)
    # one fused qkv GEMM (~2 ms/layer-batch over three narrow matmuls on the
    # v5e 7B-shape bench); layout per qkv_dims/qkv_project
    with jax.named_scope("qkv_proj"):
        q, k, v = project_qkv_heads(x, p, cfg)
    rope = cos_sin if cfg.pos_embed == "rope" else None
    bias = None
    if cfg.pos_embed == "alibi":
        pos = jnp.arange(s)
        rel = pos[None, :] - pos[:, None]  # (q, k) negative below diag
        bias = (alibi[:, None, None] * rel[None]).astype(jnp.float32)[None]  # (1,n,q,k)

    def core(q_, k_, v_, bias_, seg_):
        return attention(q_, k_, v_, cfg, bias=bias_, rope=rope, seg_ids=seg_, place=place)

    if remat_attn:
        core = jax.checkpoint(core)
    with jax.named_scope("attn_core"):
        o = place.constrain_attn_out(core(q, k, v, bias, seg_ids))
    with jax.named_scope("out_proj"):
        return attn_output(o, p, cfg, x.dtype)


_gelu_tanh = partial(jax.nn.gelu, approximate=True)  # one object: it keys the seam's programs


def relu2(x):
    """``relu(x)^2``: the activation of an un-gated unit (``act_fn`` "relu2")."""
    return jnp.square(jax.nn.relu(x))


def glu_gate(cfg: ModelConfig):
    """The activation on the gate of a gated unit: silu (SwiGLU) or relu (ReGLU)."""
    if cfg.glu_act not in ("silu", "relu"):
        raise ValueError(f"glu_act {cfg.glu_act!r}: 'silu' or 'relu'")
    return jax.nn.relu if cfg.glu_act == "relu" else jax.nn.silu


@jax.named_scope("mlp")
def mlp_block(x, p, cfg: ModelConfig, train: bool = True, place: Placement = LOCAL):
    """SwiGLU or GeLU MLP (reference: ParallelMLP, galvatron/core/
    tensor_parallel/transformer.py:78-159); switch-MoE when moe_experts > 0
    (SwitchMLP, transformer.py:161-295). ``train`` only affects MoE routing
    (sinkhorn-balanced vs raw-argmax).

    The gate/up projection output is checkpoint-named 'mlp_gate': under the
    mlp_residual saveable policy it is the ONE saved residual of the MLP
    branch — the activation product feeding w2 is recomputed in the backward
    instead of being saved as a second full-width copy."""
    if cfg.moe_experts > 0 and "router" in p:  # (a leading dense layer has none)
        from galvatron_tpu.models import moe

        if cfg.moe_dropless:  # callers of mlp_block want activations only
            if cfg.moe_router_input != "mlp":
                raise ValueError(
                    f"moe_router_input={cfg.moe_router_input!r}: this layer's router reads "
                    "another input than the block's; its callers hand it over "
                    "(moe.moe_topk_block's router_x), mlp_block has none")
            return moe.moe_topk_block(x, p, cfg, place=place)[0]
        return moe.moe_block(x, p, cfg, train=train, place=place)
    # the placement's seams only serve the (B, S, H) token stream; vision /
    # windowed layouts keep the plain matmul
    plain = lambda x_, w_: (  # noqa: E731 — non-token (vision) layouts
        qmatmul(x_, w_) if isinstance(w_, QuantTensor) else x_ @ w_
    )
    up = (
        (lambda x_, w_: place.proj_up("bsh,hf->bsf", x_, w_, w_shard_dim=1))
        if x.ndim == 3
        else plain
    )
    down = (
        (lambda x_, w_: place.proj_down("bsf,fh->bsh", x_, w_, w_shard_dim=0))
        if x.ndim == 3
        else plain
    )
    if cfg.act_fn == "swiglu":
        # fused [w1 | w3] gate GEMM (~3.5 ms/layer-batch over two narrow
        # matmuls on the v5e 7B-shape bench)
        f = p["w13"].shape[-1] // 2
        g = up(x, p["w13"].astype(x.dtype))
        if "w13_b" in p:
            g = g + p["w13_b"].astype(x.dtype)
        g = checkpoint_name(g, "mlp_gate")
        gate_act = glu_gate(cfg)
        prod = lambda g_: gate_act(g_[..., :f]) * g_[..., f:]
        if cfg.mlp_recompute == "gate":
            prod = jax.checkpoint(prod)
        y = down(prod(g), p["w2"].astype(x.dtype))
    else:
        overlap = place.tp_overlap and x.ndim == 3
        # a tp_overlap layer leaves mlp_residual's policy region (it would
        # rerun the up projection's ring in the backward and re-derive the
        # seams' programs in every layer) and saves what the region saves
        # without one: its seams take the weights as stored and cast them
        # inside their programs (no compute-dtype copy kept for the backward),
        # and the row-parallel seam applies the activation itself and keeps
        # g, not act(g)
        g = up(x, p["w1"] if overlap else p["w1"].astype(x.dtype))
        if "w1_b" in p:
            g = g + p["w1_b"].astype(x.dtype)
        g = checkpoint_name(g, "mlp_gate")
        act = {"relu": jax.nn.relu, "relu2": relu2}.get(cfg.act_fn, _gelu_tanh)
        if overlap:
            y = place.proj_down("bsf,fh->bsh", g, p["w2"], w_shard_dim=0, activation=act)
        else:
            if cfg.mlp_recompute == "gate":
                act = jax.checkpoint(act)
            y = down(act(g), p["w2"].astype(x.dtype))
    if "w2_b" in p:
        y = y + p["w2_b"].astype(x.dtype)
    return y


def residual_add(x, y, cfg: ModelConfig):
    """``x + residual_multiplier * y``: a branch joining the residual stream."""
    return x + y if cfg.residual_multiplier == 1.0 else x + y * cfg.residual_multiplier


def post_norm(y, p, name: str, cfg: ModelConfig):
    """What a block returns, through the layer's norm ``name`` (``post_attn_norm`` |
    ``post_mlp_norm``, under a scope of that name) where the layer holds one
    (``cfg.post_norms``: sandwich norms), before it joins the residual stream."""
    if name not in p:
        return y
    with jax.named_scope(name):
        return norm(y, p[name], cfg)


def mlp_residual(x, p, cfg: ModelConfig, train: bool = True, place: Placement = LOCAL,
                 router_x=None):
    """``router_x``: what a dropless expert layer's router reads where that is not
    the block's own normed input (``cfg.moe_router_input``; `decoder_layer`).

    x + MLP(norm(x)) — the per-layer MLP residual branch, with the
    activation-memory saveable policy applied when cfg.mlp_recompute ==
    'policy': jax.checkpoint over the norm+MLP region saving ONLY the
    'mlp_gate'-named projection output, so (a) the gate is saved exactly once
    per layer (the probe's jax.checkpoint(silu·gate) shape, now reaching the
    norm too) and (b) no fp32-widened copies of the bf16 residual stream
    survive into the backward — the fp32 norm statistics are recomputed from
    the saved compute-dtype layer input. MoE layers fall back to the plain
    branch (dispatch buffers carry their own sharding pins; the router is
    deterministic but its recompute under a policy region is unvalidated)."""
    if "mlp" not in p:  # the layer is its mixer alone (``mlp_layout``)
        return (x, None) if cfg.moe_dropless else x
    routed = cfg.moe_experts > 0 and "router" in p["mlp"]
    if cfg.moe_dropless and routed:
        # a dropless top-k MoE layer hands the router's statistics up beside
        # the activations: (x, (f, P)) — see decoder_layer
        from galvatron_tpu.models import moe

        normed = norm(x, p["mlp_norm"], cfg)
        with jax.named_scope("mlp"):
            y, stats = moe.moe_topk_block(normed, p["mlp"], cfg, place=place, router_x=router_x)
        return residual_add(x, post_norm(y, p, "post_mlp_norm", cfg), cfg), stats
    if cfg.moe_dropless:
        # a leading dense layer of such a model (``moe_dense_layers``): no router, no statistics
        return mlp_residual(x, p, cfg.replace(moe_experts=0), train=train, place=place), None
    if (
        cfg.mlp_recompute == "policy" and not routed
        # a tp_overlap layer's down seam saves the gate itself (mlp_block);
        # SwiGLU's halves are not device-local, so it keeps the region
        and (not place.tp_overlap or cfg.act_fn == "swiglu")
    ):
        # _norm_impl, not norm: the policy region already remats everything
        # unnamed — a nested per-norm checkpoint would only add bookkeeping.
        def normed(x_, pn_):
            with jax.named_scope("norm"):
                return _norm_impl(x_, pn_, cfg)

        branch = jax.checkpoint(
            lambda x_, pn_, pm_: mlp_block(normed(x_, pn_), pm_, cfg, train=train, place=place),
            policy=jax.checkpoint_policies.save_only_these_names("mlp_gate"),
        )
        return residual_add(
            x, post_norm(branch(x, p["mlp_norm"], p["mlp"]), p, "post_mlp_norm", cfg), cfg)
    y = mlp_block(norm(x, p["mlp_norm"], cfg), p["mlp"], cfg, train=train, place=place)
    return residual_add(x, post_norm(y, p, "post_mlp_norm", cfg), cfg)


def cross_attn_block(x, enc_out, p, cfg: ModelConfig):
    """Cross-attention: queries from the decoder stream, keys/values from the
    encoder output (reference legacy t5 model_type; architecture per standard
    enc-dec transformers). Full (non-causal) visibility over encoder
    positions; no rotary — positions live in the respective streams."""
    b, s, h = x.shape
    hd = cfg.head_dim
    kv_out = cfg.kv_heads * hd
    se = enc_out.shape[1]
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, cfg.num_heads, hd)
    kvp = enc_out.astype(x.dtype) @ p["wkv"].astype(x.dtype)  # fused [k | v] GEMM
    k = kvp[..., :kv_out].reshape(b, se, cfg.kv_heads, hd)
    v = kvp[..., kv_out:].reshape(b, se, cfg.kv_heads, hd)
    o = attention_xla(q, k, v, cfg.replace(causal=False))
    return o.reshape(b, s, cfg.num_heads * hd) @ p["wo"].astype(x.dtype)


def encoder_layer(x, p, cfg: ModelConfig, cos_sin=None, remat_attn: bool = False,
                  place: Placement = LOCAL):
    """Bidirectional self-attention + MLP (the enc-dec encoder stack)."""
    ecfg = cfg if not cfg.causal else cfg.replace(causal=False)
    x = x + post_norm(attn_block(
        norm(x, p["attn_norm"], cfg), p["attn"], ecfg, cos_sin, None, remat_attn=remat_attn,
        place=place,
    ), p, "post_attn_norm", cfg)
    return mlp_residual(x, p, cfg, place=place)


def decoder_layer(
    x, p, cfg: ModelConfig, cos_sin=None, alibi=None, remat_attn: bool = False,
    enc_out=None, seg_ids=None, place: Placement = LOCAL,
):
    """One decoder layer -> x. When ``cfg.moe_dropless`` it returns
    ``(x, router_stats)`` instead: the layer's (f, P) of moe.router_stats,
    which the load-balancing loss needs (forward_with_stats collects them);
    None for a leading dense layer of such a model.

    ``place`` (models/placement.py; static under ``jit``) is what the layer's
    place on a mesh adds to the computation — pins, kernel ``shard_map``s,
    collective-matmul seams; callers without a mesh (serving, the float32
    references, the profiler) pass nothing.

    A layer whose parameters hold a kind of ``mixers.MIXERS`` in place of
    ``attn`` (a hybrid stack, ``cfg.kinds``) runs that kind's mixer there.

    ``cfg`` is the LAYER's view (``ModelConfig.layer_view``) where the model's
    layers differ by a window or a position signal."""
    for kind in mixers.MIXERS:
        if kind in p:
            x = residual_add(x, post_norm(mixers.module(kind).block(
                norm(x, p["attn_norm"], cfg), p[kind], cfg, place=place), p, "post_attn_norm",
                cfg), cfg)
            return mlp_residual(x, p, cfg, place=place)
    normed = norm(x, p["attn_norm"], cfg)
    x = residual_add(x, post_norm(attn_block(
        normed, p["attn"], cfg, cos_sin, alibi,
        remat_attn=remat_attn, seg_ids=seg_ids, place=place,
    ), p, "post_attn_norm", cfg), cfg)
    if enc_out is not None and "cross" in p:
        x = x + cross_attn_block(norm(x, p["cross_norm"], cfg), enc_out, p["cross"], cfg)
    if cfg.moe_router_input == "attn":  # the router reads what the attention block read
        return mlp_residual(x, p, cfg, place=place, router_x=normed)
    return mlp_residual(x, p, cfg, place=place)


@jax.named_scope("embed")
def embed(tokens, params, cfg: ModelConfig, pos_ids=None):
    """``pos_ids`` ((B, S), packed sequences): learned positions gathered by
    per-segment position ids instead of the ``arange(S)`` slice — each packed
    document restarts at position 0 (rope gets the same treatment via
    packed_rope_tables)."""
    x = params["embed"]["tok"].astype(cfg.dtype)[tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    if cfg.pos_embed == "learned":
        s = tokens.shape[1]
        table = params["embed"]["pos"].astype(cfg.dtype)[:s]
        if pos_ids is not None:
            # broadcast-then-gather (not a direct table gather): the backward
            # is then a per-row placement scatter followed by the SAME
            # over-batch reduction the unpacked broadcast-add produces, so a
            # trivially-packed row (positions == arange) yields bit-identical
            # position-table gradients — the packed-vs-padded parity contract
            b = pos_ids.shape[0]
            tbl = jnp.broadcast_to(table[None], (b,) + table.shape)
            x = x + jnp.take_along_axis(tbl, pos_ids[:, :, None], axis=1)
        else:
            x = x + table[None]
    return x


def lm_head(x, params, cfg: ModelConfig):
    if cfg.logits_scaling != 1.0:
        # logits / s as (x / s) W: one pass over (B, S, H), not over (B, S, V)
        x = x / cfg.logits_scaling
    if cfg.tie_word_embeddings:
        # the tied table also feeds the embed gather — it stays fp
        w = params["embed"]["tok"].astype(x.dtype).T
    else:
        w = params["head"]["w"]
        if isinstance(w, QuantTensor):
            return qmatmul(x, w)
        w = w.astype(x.dtype)
    return x @ w


def forward(params, tokens, cfg: ModelConfig, layer_hook=None):
    """Full forward → logits (see forward_with_stats)."""
    return forward_with_stats(params, tokens, cfg, layer_hook=layer_hook)[0]


def forward_with_stats(params, tokens, cfg: ModelConfig, layer_hook=None):
    """Full forward → (logits, router statistics of the dropless MoE layers:
    one (f, P) a layer, empty for every other model).

    ``layer_hook(i, x)`` lets the hybrid-parallel
    runtime insert per-layer sharding constraints and remat (the
    Module_with_relocation + checkpoint_wrapper equivalent, reference:
    galvatron/core/parallel.py:109-172).

    Packed sequences (cfg.pack_sequences): ``tokens`` is the (B, 2·S) packed
    input row (tokens ‖ segment ids, from split_batch); the segment ids drive
    the intra-segment attention mask and per-segment position reset, and are
    handed to the hook as keyword args only in packed mode so non-packing
    hooks keep their signature."""
    seg = pos_ids = None
    if cfg.pack_sequences:
        tokens, seg, pos_ids = split_packed_inputs(tokens)
    if cfg.pos_embed == "rope":
        cos_sin = (
            packed_rope_tables(cfg, pos_ids)
            if pos_ids is not None
            else rope_tables(cfg, tokens.shape[1])
        )
    else:
        cos_sin = None
    alibi = jnp.asarray(alibi_slopes(cfg.num_heads)) if cfg.pos_embed == "alibi" else None
    hook_kw = {"seg_ids": seg} if seg is not None else {}
    x = embed(tokens, params, cfg, pos_ids=pos_ids)
    stats = []
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope(f"layer_{i}"):
            if layer_hook is not None:
                x = layer_hook(i, x, lp, **hook_kw)
            else:
                x = decoder_layer(x, lp, cfg.layer_view(i), cos_sin, alibi, seg_ids=seg)
            if cfg.moe_dropless:
                x, layer_stats = x
                if layer_stats is not None:
                    stats.append(layer_stats)
    with jax.named_scope("head"):
        x = norm(x, params["final_norm"], cfg)
        return lm_head(x, params, cfg), stats


def forward_encdec(params, enc_tokens, dec_tokens, cfg: ModelConfig, layer_hook=None):
    """Encoder-decoder forward → decoder logits. Layer-hook indices cover the
    encoder stack first (0..enc_layers-1) then the decoder
    (enc_layers..total_layers-1); decoder hooks receive ``enc_out``."""
    E = cfg.enc_layers
    cos_e = rope_tables(cfg, enc_tokens.shape[1]) if cfg.pos_embed == "rope" else None
    cos_d = rope_tables(cfg, dec_tokens.shape[1]) if cfg.pos_embed == "rope" else None
    x = embed(enc_tokens, params, cfg)
    for i, lp in enumerate(params["enc_layers"]):
        if layer_hook is not None:
            x = layer_hook(i, x, lp)
        else:
            x = encoder_layer(x, lp, cfg, cos_e)
    enc_out = norm(x, params["enc_final_norm"], cfg)
    y = embed(dec_tokens, params, cfg)
    for j, lp in enumerate(params["layers"]):
        if layer_hook is not None:
            y = layer_hook(E + j, y, lp, enc_out=enc_out)
        else:
            y = decoder_layer(y, lp, cfg, cos_d, None, enc_out=enc_out)
    y = norm(y, params["final_norm"], cfg)
    return lm_head(y, params, cfg)


# ---------------------------------------------------------------------------
# Vision forward (ViT / Swin)
# ---------------------------------------------------------------------------


def vision_embed(pixels, params, cfg: ModelConfig):
    """(B, H·W·C) int32 pixel rows → (B, n_patches, hidden): normalize to
    [-1, 1], patchify by reshape/transpose, linear-project, add learned
    positions. The patchify runs as pure data movement + one batched matmul —
    MXU-shaped, no gather."""
    b = pixels.shape[0]
    p_, g, c = cfg.patch_size, cfg.grid, cfg.num_channels
    x = pixels.astype(cfg.dtype).reshape(b, g, p_, g, p_, c) / 127.5 - 1.0
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, g * g, p_ * p_ * c)
    x = x @ params["embed"]["proj"].astype(cfg.dtype)
    return x + params["embed"]["pos"].astype(cfg.dtype)[None]


def _swin_attn_mask(h: int, w: int, window: int, shift: int) -> np.ndarray:
    """Static (num_windows, w², w²) True=may-attend mask for shifted windows:
    after the cyclic roll, positions wrapped across the image boundary land in
    the same window but must not attend to each other (Swin's shifted-window
    mask, computed here at trace time as a numpy constant)."""
    img = np.zeros((h, w), np.int32)
    cnt = 0
    for hs in (slice(0, h - window), slice(h - window, h - shift), slice(h - shift, None)):
        for ws in (slice(0, w - window), slice(w - window, w - shift), slice(w - shift, None)):
            img[hs, ws] = cnt
            cnt += 1
    wins = (
        img.reshape(h // window, window, w // window, window)
        .transpose(0, 2, 1, 3)
        .reshape(-1, window * window)
    )
    return wins[:, :, None] == wins[:, None, :]


def swin_attention(x, p, lcfg: ModelConfig, h: int, w: int, window: int, shift: int):
    """Windowed multi-head self-attention over an (B, h·w, C) feature map:
    optional cyclic shift, window partition, per-window attention (+ wrap
    mask), reverse. Window sequences are tiny (w²≈49) so the plain XLA einsum
    path is the right kernel — the batched GEMMs land on the MXU."""
    b, _, c = x.shape
    heads, hd = lcfg.num_heads, c // lcfg.num_heads
    x4 = x.reshape(b, h, w, c)
    if shift:
        x4 = jnp.roll(x4, (-shift, -shift), (1, 2))
    nh, nw = h // window, w // window
    ws2 = window * window
    xw = (
        x4.reshape(b, nh, window, nw, window, c)
        .transpose(0, 1, 3, 2, 4, 5)
        .reshape(b * nh * nw, ws2, c)
    )
    q, k, v = project_qkv_heads(xw, p, lcfg)  # fused projection
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / np.sqrt(hd)
    if shift:
        mask = jnp.asarray(_swin_attn_mask(h, w, window, shift))  # (nW, ws2, ws2)
        scores = scores.reshape(b, nh * nw, heads, ws2, ws2)
        scores = jnp.where(mask[None, :, None], scores, -1e30)
        scores = scores.reshape(b * nh * nw, heads, ws2, ws2)
    attn = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", attn, v).reshape(-1, ws2, c)
    o = o @ p["wo"].astype(x.dtype)
    o = (
        o.reshape(b, nh, nw, window, window, c)
        .transpose(0, 1, 3, 2, 4, 5)
        .reshape(b, h, w, c)
    )
    if shift:
        o = jnp.roll(o, (shift, shift), (1, 2))
    return o.reshape(b, h * w, c)


def swin_layer(x, p, cfg: ModelConfig, i: int, remat_attn: bool = False):
    """One Swin block: layer index → static (stage, geometry); odd blocks in a
    stage use the shifted window. Residual + norm + MLP reuse the shared
    transformer pieces at the stage's width."""
    stage, j = swin_stage_of(cfg, i)
    h, w, c, _ = swin_geometry(cfg, stage)
    lcfg = vision_layer_cfg(cfg, i)
    window = swin_window_for(cfg, stage)
    shift = window // 2 if (j % 2 == 1 and window < h) else 0

    def attn(x_):
        return swin_attention(x_, p["attn"], lcfg, h, w, window, shift)

    if remat_attn:
        attn = jax.checkpoint(attn)
    x = x + attn(norm(x, p["attn_norm"], lcfg))
    return mlp_residual(x, p, lcfg)


def patch_merge(x, p, cfg: ModelConfig, stage: int):
    """Swin downsampling between stages: 2×2 neighborhood concat (4C) →
    norm → linear to 2C; resolution quarters, width doubles."""
    h, w, c, _ = swin_geometry(cfg, stage)
    b = x.shape[0]
    x = (
        x.reshape(b, h // 2, 2, w // 2, 2, c)
        .transpose(0, 1, 3, 2, 4, 5)
        .reshape(b, (h // 2) * (w // 2), 4 * c)
    )
    x = norm(x, p["norm"], cfg)
    return x @ p["w"].astype(x.dtype)


def cls_head(y, params, cfg: ModelConfig):
    """Mean-pooled classification head: (B, L, C) → (B, num_classes)."""
    pooled = y.mean(axis=1)
    return pooled @ params["head"]["w"].astype(y.dtype)


def forward_vision(params, pixels, cfg: ModelConfig, layer_hook=None):
    """ViT/Swin forward → class logits. ``layer_hook(i, x, lp)`` carries the
    per-layer hybrid strategies exactly as in the token models; Swin's
    patch-merging projections sit between stages as model-level params (like
    final_norm — replicated/ZeRO, never a per-layer strategy)."""
    x = vision_embed(pixels, params, cfg)
    if cfg.swin_depths:
        i = 0
        for s, depth in enumerate(cfg.swin_depths):
            for _ in range(depth):
                if layer_hook is not None:
                    x = layer_hook(i, x, params["layers"][i])
                else:
                    x = swin_layer(x, params["layers"][i], cfg, i)
                i += 1
            if s < len(cfg.swin_depths) - 1:
                x = patch_merge(x, params["merges"][s], cfg, s)
    else:
        for i, lp in enumerate(params["layers"]):
            if layer_hook is not None:
                x = layer_hook(i, x, lp)
            else:
                x = decoder_layer(x, lp, cfg)  # causal=False → encoder block
    x = norm(x, params["final_norm"], cfg)
    return cls_head(x, params, cfg)


def cls_loss_sum(params, batch, cfg: ModelConfig, layer_hook=None):
    """(nll_sum, sample_count) for image classification on the int32 pixel
    batch contract: row = pixels ‖ label."""
    pixels, labels = split_batch(batch, cfg)
    logits = forward_vision(params, pixels, cfg, layer_hook=layer_hook)
    return cross_entropy_sum(logits, labels, remat=ce_remat(cfg))


def _cross_entropy_sum_impl(logits, labels, ignore_index: int = -100):
    logits = logits.astype(jnp.float32)
    mask = labels != ignore_index
    safe = jnp.where(mask, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    if logits.shape[0] == 1 and logits.ndim == 3:
        # one sequence a (micro-)batch: the compiler turns the gather's backward
        # into a scatter over the FLATTENED logits and pays two relayouts of the
        # whole (S, V) gradient for it (3.8 ms a step at 8192 x 25088, under no
        # name; PERF.md §6, PR 33); a select and a row sum pick the same entry
        # and differentiate to a select
        hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2) == safe[..., None]
        picked = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    else:
        picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (lse - picked) * mask
    return nll.sum(), mask.sum()


def cross_entropy_sum(logits, labels, ignore_index: int = -100, remat: bool = False):
    """(nll_sum, valid_token_count) in fp32 — the accumulation-safe form:
    micro-batch sums combine exactly into the global token-mean even when
    ignore_index masks are unevenly distributed across chunks.

    ``remat``: rematerialize the fp32 cast / log-sum-exp in the backward from
    the compute-dtype logits instead of letting autodiff save the fp32-widened
    (B, S, V/vocab_tp) copy — the "cast at the consumer" rule; loss-carrying
    callers pass ``cfg.mlp_recompute == 'policy'``."""
    with jax.named_scope("loss"):
        if remat:
            return jax.checkpoint(
                partial(_cross_entropy_sum_impl, ignore_index=ignore_index)
            )(logits, labels)
        return _cross_entropy_sum_impl(logits, labels, ignore_index)


def cross_entropy_loss(logits, labels, ignore_index: int = -100):
    """Token-mean cross entropy in fp32. Written shard-friendly: when logits
    are vocab-sharded (vocab_tp), XLA keeps the log-sum-exp partial per shard
    and psums scalars — the vocab-parallel cross entropy of the reference
    (site_package/megatron/core/tensor_parallel/cross_entropy.py:18-155)
    without the hand-written autograd Function."""
    s, n = cross_entropy_sum(logits, labels, ignore_index)
    return s / jnp.maximum(n, 1)


def mlm_positions(tokens, cfg: ModelConfig):
    """Deterministic masked-LM positions: multiplicative token⊕position hash
    thresholded at ``mlm_mask_rate``. Keeping masking a pure function of the
    batch (instead of RNG state) preserves the framework-wide contract that
    loss depends only on (params, batch) — resume/parity tests hold for
    encoders exactly as for decoders."""
    pos = jnp.arange(tokens.shape[-1], dtype=jnp.uint32)
    h = tokens.astype(jnp.uint32) * jnp.uint32(2654435761) + pos * jnp.uint32(40503)
    h = (h ^ (h >> jnp.uint32(16))) * jnp.uint32(2246822519)
    frac = (h & jnp.uint32(0xFFFF)).astype(jnp.float32) / 65536.0
    return frac < cfg.mlm_mask_rate


def mlm_loss_sum(params, batch, cfg: ModelConfig, layer_hook=None):
    """(nll_sum, masked_token_count) BERT-style masked-LM pieces on the same
    (B, S+1) token batches the CLM path uses. The last vocab id serves as
    [MASK]; only masked positions contribute loss."""
    inputs, labels = split_batch(batch, cfg)
    logits = forward(params, inputs, cfg, layer_hook=layer_hook)
    return cross_entropy_sum(logits, labels, remat=ce_remat(cfg))


def batch_row_width(cfg: ModelConfig, seq: int) -> int:
    """Width of one loader batch row — the shape side of the ``split_batch``
    contract, shared by every abstract-batch builder (aot warmup, fidelity
    harness) so they lower the SAME program the run dispatches: vision rows
    flatten to sample_len pixels + label; packed CLM rows are tokens ‖
    segment ids, 2·(S+1) (data/packing.py); plain windows are S+1."""
    if cfg.image_size:
        return cfg.sample_len + 1
    if cfg.pack_sequences:
        return 2 * (seq + 1)
    return seq + 1


def split_batch(batch, cfg: ModelConfig):
    """One (B, sample_len+1) int32 batch row → (model inputs, loss labels) per
    objective. Centralized so the pipeline engines (which re-implement the
    embed→stages→head seam) agree with the GSPMD path on every objective:
    'clm' next-token shift, 'mlm' deterministic masking, 'cls' pixels‖label."""
    if cfg.objective == "cls":
        return batch[:, :-1], batch[:, -1]
    if cfg.objective == "mlm":
        tokens = batch[:, :-1]
        mask = mlm_positions(tokens, cfg)
        return jnp.where(mask, cfg.vocab_size - 1, tokens), jnp.where(mask, tokens, -100)
    if cfg.pack_sequences:
        # packed row (B, 2·(S+1)) = tokens ‖ segment ids. Inputs keep both
        # halves (the model needs the segment ids at every layer); labels are
        # next-token WITHIN a segment only — a position whose successor
        # belongs to a different segment (document boundary) or to padding
        # (segment 0) carries no loss.
        s1 = batch.shape[1] // 2
        tokens, seg = batch[:, :s1], batch[:, s1:]
        inputs = jnp.concatenate([tokens[:, :-1], seg[:, :-1]], axis=1)
        same = (seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] > 0)
        return inputs, jnp.where(same, tokens[:, 1:], -100)
    return batch[:, :-1], batch[:, 1:]


def embed_any(inputs, params, cfg: ModelConfig):
    """Input embedding for either modality: token table or patch projection."""
    if cfg.image_size:
        return vision_embed(inputs, params, cfg)
    return embed(inputs, params, cfg)


def ce_remat(cfg: ModelConfig) -> bool:
    """Whether loss paths should rematerialize the cross-entropy fp32 cast
    (one rule for the GSPMD path and every pipeline engine's head seam)."""
    return cfg.mlp_recompute == "policy"


def head_loss_sum(y, params, labels, cfg: ModelConfig):
    """Final-norm'd features (B, S, H) → (nll_sum, count): LM head + token
    cross entropy, or pooled classification head + class cross entropy."""
    with jax.named_scope("head"):
        logits = cls_head(y, params, cfg) if cfg.objective == "cls" else lm_head(y, params, cfg)
    return cross_entropy_sum(logits, labels, remat=ce_remat(cfg))


def loss_tokens_per_sample(cfg: ModelConfig, seq_len: int) -> int:
    """Static count of loss-carrying positions per sample (fp16 scale seeding;
    mlm uses the expected masked fraction)."""
    if cfg.objective == "cls":
        return 1
    if cfg.objective == "mlm":
        return max(1, int(seq_len * cfg.mlm_mask_rate))
    if cfg.enc_layers > 0:
        return seq_len - cfg.enc_seq
    return seq_len


def lm_loss_sum(params, batch, cfg: ModelConfig, layer_hook=None):
    """(nll_sum, token_count) loss pieces on a (B, S+1) token batch
    (reference synthetic-data convention: models/llama_hf/dataloader.py:5-30).
    Dispatches on cfg.objective: 'clm' next-token; 'mlm' masked-LM; 'cls'
    image classification (vision families); enc-dec models (enc_layers > 0)
    run seq2seq next-token loss on the decoder half of the
    (B, enc_seq + dec_seq + 1) sample."""
    if cfg.objective == "cls":
        return cls_loss_sum(params, batch, cfg, layer_hook=layer_hook)
    if cfg.objective == "mlm":
        return mlm_loss_sum(params, batch, cfg, layer_hook=layer_hook)
    if cfg.enc_layers > 0:
        enc_tokens = batch[:, : cfg.enc_seq]
        dec = batch[:, cfg.enc_seq :]
        logits = forward_encdec(params, enc_tokens, dec[:, :-1], cfg, layer_hook=layer_hook)
        return cross_entropy_sum(logits, dec[:, 1:], remat=ce_remat(cfg))
    # split_batch, not ad-hoc slicing: packed rows carry segment ids the
    # boundary-masked labels must be derived from
    tokens, labels = split_batch(batch, cfg)
    logits = forward(params, tokens, cfg, layer_hook=layer_hook)
    return cross_entropy_sum(logits, labels, remat=ce_remat(cfg))


def lm_loss(params, batch, cfg: ModelConfig, layer_hook=None):
    s, n = lm_loss_sum(params, batch, cfg, layer_hook=layer_hook)
    return s / jnp.maximum(n, 1)


def moe_loss_sum(params, batch, cfg: ModelConfig, layer_hook=None):
    """lm_loss_sum of a dropless top-k MoE model with what its training
    objective adds: (nll_sum, token_count, aux), aux = {"moe_aux_loss": the
    load-balancing loss L_aux of this batch, "moe_load_max_over_mean": the
    fullest expert's pairs over the even share; of a held share also
    "moe_held_pairs_per_token" and "moe_held_rows_share"}. The objective that is
    differentiated is nll_sum / count + cfg.moe_aux_coef * L_aux; the loss
    that is logged and evaluated stays the cross entropy."""
    from galvatron_tpu.models import moe

    tokens, labels = split_batch(batch, cfg)
    logits, stats = forward_with_stats(params, tokens, cfg, layer_hook=layer_hook)
    s, n = cross_entropy_sum(logits, labels, remat=ce_remat(cfg))
    with jax.named_scope("loss"):
        # a held share: the loss over all the experts the router scores, the
        # load and the pairs a token brings over the experts held here
        held = (cfg.moe_first_held, cfg.moe_held) if cfg.moe_holds_share else None
        aux = {
            "moe_aux_loss": moe.load_balancing_loss(stats, cfg.moe_experts),
            "moe_load_max_over_mean": moe.load_max_over_mean(
                stats, cfg.moe_experts, cfg.moe_top_k, held),
        }
        if held:
            aux["moe_held_pairs_per_token"] = moe.held_pairs_per_token(stats, held)
            aux["moe_held_rows_share"] = moe.held_rows_share(stats)
    return s, n, aux


def blocks_to_layers(pattern: str, blocks: Optional[int] = None):
    """A nemotron_h ``hybrid_override_pattern`` (one character a published block: ``M`` a
    Mamba-2 mixer, ``*`` attention, ``E`` an expert MLP; each block ONE norm and ONE
    sublayer, ``x + f(norm(x))``) as this program's layers -> ``(layer_kinds,
    mlp_layout)``. Two such blocks in a row ARE the program's pre-norm layer, so a mixer
    block and the ``E`` behind it make one layer with an MLP, and a mixer block followed
    by another mixer is a layer of its mixer alone (``mlp_layout`` 0). ``blocks``: of the
    first that many published blocks only (a model cut in depth: 26 of the 52 are the
    first 15 layers). An ``E`` with no mixer in front of it is not this program's layer."""
    kinds, mlps = [], []
    for c in pattern[:blocks]:
        if c == "E":
            if not mlps or mlps[-1]:
                raise ValueError(f"block pattern {pattern!r}: an expert block without a mixer "
                                 "block in front of it is not a layer of this program")
            mlps[-1] = 1
        elif c in "M*":
            kinds.append("ssm" if c == "M" else "attention")
            mlps.append(0)
        else:
            raise ValueError(f"block pattern {pattern!r}: {c!r} is none of 'M', '*', 'E'")
    return tuple(kinds), tuple(mlps)


#: nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16's ``hybrid_override_pattern``: 52 blocks,
#: 23 M / 23 E / 6 * = 29 program layers, 6 of them a mixer alone (the M before every *)
NEMOTRON_3_NANO_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
_NEMOTRON_KINDS, _NEMOTRON_MLPS = blocks_to_layers(NEMOTRON_3_NANO_PATTERN)

# Preset configs mirroring the reference model zoo sizes
# (galvatron/models/llama_hf/arguments.py:6, gpt_hf/arguments.py:6)
PRESETS: Dict[str, ModelConfig] = {
    "llama-0.3b": ModelConfig(
        vocab_size=32000, hidden_size=1024, num_layers=24, num_heads=16, max_seq_len=2048
    ),
    "llama-7b": ModelConfig(
        vocab_size=32000, hidden_size=4096, num_layers=32, num_heads=32,
        ffn_dim=11008, max_seq_len=2048,
    ),
    "llama-13b": ModelConfig(
        vocab_size=32000, hidden_size=5120, num_layers=40, num_heads=40,
        ffn_dim=13824, max_seq_len=2048,
    ),
    "llama-30b": ModelConfig(
        vocab_size=32000, hidden_size=6656, num_layers=60, num_heads=52,
        ffn_dim=17920, max_seq_len=2048,
    ),
    "gpt-0.3b": ModelConfig(
        use_bias=True,
        vocab_size=50257, hidden_size=1024, num_layers=24, num_heads=16,
        max_seq_len=1024, pos_embed="learned", norm_type="layernorm", act_fn="gelu",
        tie_word_embeddings=True,
    ),
    "gpt-1.5b": ModelConfig(
        use_bias=True,
        vocab_size=50257, hidden_size=1600, num_layers=48, num_heads=25,
        max_seq_len=1024, pos_embed="learned", norm_type="layernorm", act_fn="gelu",
        tie_word_embeddings=True,
    ),
    "gpt-2.7b": ModelConfig(
        use_bias=True,
        vocab_size=50257, hidden_size=2560, num_layers=32, num_heads=32,
        max_seq_len=2048, pos_embed="learned", norm_type="layernorm", act_fn="gelu",
        tie_word_embeddings=True,
    ),
    "gpt-6.7b": ModelConfig(
        use_bias=True,
        vocab_size=50257, hidden_size=4096, num_layers=32, num_heads=32,
        max_seq_len=2048, pos_embed="learned", norm_type="layernorm", act_fn="gelu",
        tie_word_embeddings=True,
    ),
    # OPT family (decoder-only, ReLU MLPs, learned positions with the
    # characteristic +2 offset — handled at HF import by slicing the table;
    # reference parity target: the gpt_hf-style HF-wrapping family pattern)
    "opt-125m": ModelConfig(
        use_bias=True,
        vocab_size=50272, hidden_size=768, num_layers=12, num_heads=12,
        max_seq_len=2048, pos_embed="learned", norm_type="layernorm", act_fn="relu",
        tie_word_embeddings=True,
    ),
    "opt-1.3b": ModelConfig(
        use_bias=True,
        vocab_size=50272, hidden_size=2048, num_layers=24, num_heads=32,
        max_seq_len=2048, pos_embed="learned", norm_type="layernorm", act_fn="relu",
        tie_word_embeddings=True,
    ),
    "opt-6.7b": ModelConfig(
        use_bias=True,
        vocab_size=50272, hidden_size=4096, num_layers=32, num_heads=32,
        max_seq_len=2048, pos_embed="learned", norm_type="layernorm", act_fn="relu",
        tie_word_embeddings=True,
    ),
    "opt-13b": ModelConfig(
        use_bias=True,
        vocab_size=50272, hidden_size=5120, num_layers=40, num_heads=40,
        max_seq_len=2048, pos_embed="learned", norm_type="layernorm", act_fn="relu",
        tie_word_embeddings=True,
    ),
    "opt-30b": ModelConfig(
        use_bias=True,
        vocab_size=50272, hidden_size=7168, num_layers=48, num_heads=56,
        max_seq_len=2048, pos_embed="learned", norm_type="layernorm", act_fn="relu",
        tie_word_embeddings=True,
    ),
    # encoder families (reference legacy bert support: core/parallel.py:64-89,
    # cost_model.py model_type handling)
    "bert-base": ModelConfig(
        use_bias=True,
        vocab_size=30528, hidden_size=768, num_layers=12, num_heads=12,
        max_seq_len=512, pos_embed="learned", norm_type="layernorm", act_fn="gelu",
        tie_word_embeddings=True, causal=False, objective="mlm",
    ),
    "bert-large": ModelConfig(
        use_bias=True,
        vocab_size=30528, hidden_size=1024, num_layers=24, num_heads=16,
        max_seq_len=512, pos_embed="learned", norm_type="layernorm", act_fn="gelu",
        tie_word_embeddings=True, causal=False, objective="mlm",
    ),
    # encoder-decoder family (reference legacy t5 model_type; positions are
    # learned, not T5 relative bias — documented deviation)
    "t5-base": ModelConfig(
        vocab_size=32128, hidden_size=768, num_layers=12, num_heads=12,
        ffn_dim=3072, max_seq_len=512, enc_layers=12, enc_seq=512,
        pos_embed="learned", norm_type="rms", act_fn="gelu",
        tie_word_embeddings=True,
    ),
    "t5-large": ModelConfig(
        vocab_size=32128, hidden_size=1024, num_layers=24, num_heads=16,
        ffn_dim=4096, max_seq_len=512, enc_layers=24, enc_seq=512,
        pos_embed="learned", norm_type="rms", act_fn="gelu",
        tie_word_embeddings=True,
    ),
    "t5-3b": ModelConfig(
        vocab_size=32128, hidden_size=1024, num_layers=24, num_heads=32,
        ffn_dim=16384, max_seq_len=512, enc_layers=24, enc_seq=512,
        pos_embed="learned", norm_type="rms", act_fn="gelu",
        tie_word_embeddings=True,
    ),
    # vision families (reference legacy vit/swin model_type branches,
    # core/parallel.py:64-89, cost_model.py:76,87-106)
    "vit-base": ModelConfig(
        use_bias=True,
        vocab_size=1, hidden_size=768, num_layers=12, num_heads=12,
        max_seq_len=0, pos_embed="learned", norm_type="layernorm", act_fn="gelu",
        causal=False, objective="cls", image_size=224, patch_size=16,
    ),
    "vit-large": ModelConfig(
        use_bias=True,
        vocab_size=1, hidden_size=1024, num_layers=24, num_heads=16,
        max_seq_len=0, pos_embed="learned", norm_type="layernorm", act_fn="gelu",
        causal=False, objective="cls", image_size=224, patch_size=16,
    ),
    "vit-huge": ModelConfig(
        use_bias=True,
        vocab_size=1, hidden_size=1280, num_layers=32, num_heads=16,
        max_seq_len=0, pos_embed="learned", norm_type="layernorm", act_fn="gelu",
        causal=False, objective="cls", image_size=224, patch_size=14,
    ),
    "swin-base": ModelConfig(
        use_bias=True,
        vocab_size=1, hidden_size=128, num_layers=24, num_heads=4,
        max_seq_len=0, pos_embed="learned", norm_type="layernorm", act_fn="gelu",
        causal=False, objective="cls", image_size=224, patch_size=4,
        swin_depths=(2, 2, 18, 2), swin_window=7,
    ),
    "swin-large": ModelConfig(
        use_bias=True,
        vocab_size=1, hidden_size=192, num_layers=24, num_heads=6,
        max_seq_len=0, pos_embed="learned", norm_type="layernorm", act_fn="gelu",
        causal=False, objective="cls", image_size=224, patch_size=4,
        swin_depths=(2, 2, 18, 2), swin_window=7,
    ),
    "baichuan-7b": ModelConfig(
        vocab_size=64000, hidden_size=4096, num_layers=32, num_heads=32,
        ffn_dim=11008, max_seq_len=4096,
    ),
    # allenai/OLMoE-1B-7B-0125-Instruct: 64 experts of width 1024 (ffn_dim is
    # the EXPERT width), 8 a token, softmax top-k without renormalisation,
    # dropless; qk-norm over the whole projection; router_aux_loss_coef 0.01
    "olmoe-1b-7b": ModelConfig(
        vocab_size=50304, hidden_size=2048, num_layers=16, num_heads=16,
        ffn_dim=1024, max_seq_len=4096, moe_experts=64, moe_router="softmax_topk",
        moe_top_k=8, moe_aux_coef=0.01, qk_norm=True,
    ),
    # ibm-granite/granite-4.0-h-micro (model_type granitemoehybrid): 40 layers,
    # attention at 5, 15, 25, 35 and Mamba-2 mixers elsewhere, every layer with
    # the shared gated MLP (num_local_experts 0: no routed part); GQA 32 / 8
    # heads of 64 without any positional embedding, softmax scale 1/64; the
    # embedding x 12, every residual branch x 0.22, logits / 8; tied head
    "granite-4.0-h-micro": ModelConfig(
        vocab_size=100352, hidden_size=2048, num_layers=40, num_heads=32, num_kv_heads=8,
        ffn_dim=8192, max_seq_len=131072, pos_embed="nope", tie_word_embeddings=True,
        layer_kinds=tuple(
            "attention" if i % 10 == 5 else "ssm" for i in range(40)),
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=1, ssm_conv=4,
        ssm_chunk=256, attention_multiplier=0.015625, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=8.0,
    ),
    # ibm-granite/granite-4.0-h-small (model_type granitemoehybrid, "32B-A9B"): the micro's
    # stack at hidden 4096 with a ROUTED MLP in every layer: 72 experts of width 768
    # (``intermediate_size``), the 10 largest router logits a token, softmax over those 10,
    # beside a shared SwiGLU MLP of 1536 added as it is; Mamba-2 mixers of 128 heads x 64,
    # state 128, ONE scan group (a row's scan state is (128, 8192) float32 = 4 MiB a layer),
    # conv 4, chunks of 256; GQA 32 / 8 heads of 128 without any position signal, softmax
    # scale 1/128; the embedding x 12, every residual branch x 0.22, logits / 16; tied head.
    # Served (``cli serve --param_dtype bf16``: models/generation.py's state stack) and trained
    "granite-4.0-h-small": ModelConfig(
        vocab_size=100352, hidden_size=4096, num_layers=40, num_heads=32, num_kv_heads=8,
        ffn_dim=768, max_seq_len=131072, pos_embed="nope", tie_word_embeddings=True,
        layer_kinds=tuple(
            "attention" if i % 10 == 5 else "ssm" for i in range(40)),
        ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssm_groups=1, ssm_conv=4,
        ssm_chunk=256, attention_multiplier=0.0078125, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=16.0,
        moe_experts=72, moe_router="softmax_topk", moe_top_k=10, moe_ffn_dim=768,
        moe_norm_topk=True, moe_shared_ffn_dim=1536, moe_shared_gate=False,
    ),
    # Qwen/Qwen3-Next-80B-A3B-Instruct (model_type qwen3_next): 48 layers, gated
    # attention at (l + 1) % 4 == 0 and Gated DeltaNet mixers elsewhere; GQA 16 /
    # 2 heads of 256 (not hidden / heads), per-head zero-centred qk norms, rotary
    # on the first quarter of a head, theta 1e7; every norm (1 + w); every MLP 512
    # experts of width 512 (ffn_dim 5120 is the published intermediate_size, used
    # by no layer), top-10 renormalised, a gated shared expert of 512; aux
    # coefficient 0.001 (assumed); untied head
    "qwen3-next-80b-a3b": ModelConfig(
        vocab_size=151936, hidden_size=2048, num_layers=48, num_heads=16, num_kv_heads=2,
        attn_head_dim=256, ffn_dim=5120, max_seq_len=262144, rope_theta=1e7, norm_eps=1e-6,
        rotary_fraction=0.25, attn_gate=True, qk_norm=True, qk_norm_per_head=True,
        norm_zero_centered=True,
        layer_kinds=tuple("attention" if (i + 1) % 4 == 0 else "gdn" for i in range(48)),
        gdn_key_heads=16, gdn_value_heads=32, gdn_key_dim=128, gdn_value_dim=128, gdn_conv=4,
        gdn_chunk=64, moe_experts=512, moe_router="softmax_topk", moe_top_k=10,
        moe_aux_coef=0.001, moe_ffn_dim=512, moe_norm_topk=True, moe_shared_ffn_dim=512,
    ),
    "baichuan-13b": ModelConfig(
        vocab_size=64000, hidden_size=5120, num_layers=40, num_heads=40,
        ffn_dim=13696, max_seq_len=4096, pos_embed="alibi",
    ),
    # sarvamai/sarvam-105b (model_type sarvam_mla): 32 layers of latent attention
    # (64 heads, query head 192 = 128 + 64 rotary, latent 512 + one shared rotary
    # key of 64, value head 128; RMSNorm on the latent alone), YaRN x 40 over 4096
    # positions; layer 0 a SwiGLU MLP of 16384, then 128 experts of width 2048,
    # sigmoid scores with a selection bias, top-8 renormalised x 2.5, one ungated
    # shared expert of 2048; untied head. Served (models/mla.py has the cache).
    "sarvam-105b": ModelConfig(
        vocab_size=262144, hidden_size=4096, num_layers=32, num_heads=64, attn_head_dim=192,
        ffn_dim=16384, max_seq_len=131072, rope_theta=10000.0, norm_eps=1e-6,
        layer_kinds=("mla",) * 32, mla_kv_rank=512, mla_nope_dim=128, mla_rope_dim=64,
        mla_v_dim=128, rope_yarn=(40.0, 4096, 32.0, 1.0, 1.0, 1.0),
        moe_experts=128, moe_router="sigmoid_topk", moe_top_k=8, moe_route_scale=2.5,
        moe_ffn_dim=2048, moe_shared_ffn_dim=2048, moe_shared_gate=False, moe_dense_layers=1,
    ),
    # dots-studio/dots3-note-prev (model_type dots3_note; the language model, no towers, no
    # MTP): 46 layers of latent attention of TWO widths. Full layers (0, 1, 5, 9, ... 45):
    # 128 heads of 128 + 64 rotary / 128 over a 512 + 64 latent, queries through a rank of
    # 1024, a DSA indexer (64 index heads of 128, ONE index key a position) that keeps the
    # 2,048 best keys a query, rotary theta 8e7. Sliding layers (513 keys): 64 heads of
    # 192 + 64 / 128 over a 1,024 + 64 latent, rank-1024 queries, theta 5e4, no indexer.
    # Both: the low-rank rescale, a headwise sigmoid gate. Layer 0 a SwiGLU MLP of 13824,
    # then 256 experts of 1536, sigmoid scores with a selection bias, top-8 renormalised
    # x 1, one ungated shared expert; untied head. Served (models/mla.py: the full layers'
    # latent and index keys in whole slots, the window layers' latent in a ring).
    "dots3-note-prev": ModelConfig(
        vocab_size=152064, hidden_size=5120, num_layers=46, num_heads=128, attn_head_dim=192,
        ffn_dim=13824, max_seq_len=524288, rope_theta=8e7, norm_eps=1e-5,
        layer_kinds=("mla",) * 46, mla_kv_rank=512, mla_nope_dim=128, mla_rope_dim=64,
        mla_v_dim=128, mla_q_rank=1024, mla_rescale=True, mla_head_gate=True,
        mla_index_heads=64, mla_index_dim=128, mla_index_topk=2048,
        sliding_window_size=513, sliding_window_layout=(0, 0) + (1, 1, 1, 0) * 11,
        swa_num_heads=64, swa_nope_dim=192, swa_rope_dim=64, swa_v_dim=128, swa_kv_rank=1024,
        swa_q_rank=1024, swa_rope_theta=5e4,
        moe_experts=256, moe_router="sigmoid_topk", moe_top_k=8, moe_route_scale=1.0,
        moe_ffn_dim=1536, moe_shared_ffn_dim=1536, moe_shared_gate=False, moe_dense_layers=1,
    ),
    # PowerInfer/SmallThinker-21BA3B-Instruct (model_type smallthinker): 52 layers in
    # periods of four, one FULL layer without any position signal (NoPE) then three
    # SLIDING-WINDOW layers of 4096 keys with rotary (theta 1.5e6); GQA 28 / 4 heads
    # of 128 (not hidden / heads); every MLP 64 ReGLU experts of width 768, 6 a
    # token, softmax over the chosen six, routed from the ATTENTION block's normed
    # input; no dense MLP (ffn_dim 768 is used by no layer); untied head. Served
    # (models/generation.py keeps the window layers' keys and values in a ring).
    "smallthinker-21b-a3b": ModelConfig(
        vocab_size=151936, hidden_size=2560, num_layers=52, num_heads=28, num_kv_heads=4,
        attn_head_dim=128, ffn_dim=768, max_seq_len=16384, rope_theta=1.5e6, norm_eps=1e-6,
        sliding_window_size=4096, sliding_window_layout=(0, 1, 1, 1) * 13,
        rope_layout=(0, 1, 1, 1) * 13, moe_experts=64, moe_router="softmax_topk", moe_top_k=6,
        moe_ffn_dim=768, moe_norm_topk=True, glu_act="relu", moe_router_input="attn",
        moe_router_precision="highest",
    ),
    # LiquidAI/LFM2-24B-A2B (model_type lfm2_moe): 40 layers, conv, conv, then
    # (full_attention, conv, conv, conv) nine times, then full_attention, conv: 30 gated
    # short convolutions of 3 taps (models/shortconv.py) and 10 GQA layers, 32 / 8
    # heads of 64, per-head q/k RMSNorm before rotary (theta 1e6); layers 0-1 a SwiGLU
    # MLP of 11776, the other 38 carry 64 experts of width 1536, sigmoid scores with a
    # selection bias, top-4 renormalised, scale 1; tied head (assumed: the LFM2 family
    # ties). Served (models/generation.py keeps the conv layers' state a row beside the
    # attention layers' keys and values).
    "lfm2-24b-a2b": ModelConfig(
        vocab_size=65536, hidden_size=2048, num_layers=40, num_heads=32, num_kv_heads=8,
        ffn_dim=11776, max_seq_len=128000, rope_theta=1e6, norm_eps=1e-5,
        tie_word_embeddings=True, qk_norm=True, qk_norm_per_head=True, shortconv_taps=3,
        layer_kinds=("shortconv",) * 2 + ("attention", "shortconv", "shortconv", "shortconv") * 9
        + ("attention", "shortconv"),
        moe_experts=64, moe_router="sigmoid_topk", moe_top_k=4, moe_route_scale=1.0,
        moe_ffn_dim=1536, moe_norm_topk=True, moe_dense_layers=2,
    ),
    # arcee-ai/Trinity-Large-Preview (model_type afmoe): 60 layers in periods of four,
    # three SLIDING-WINDOW layers of 4096 keys with rotary (theta 1e4) then one FULL
    # layer without any position signal; GQA 48 / 8 heads of 128, per-head q/k RMSNorm,
    # an OUTPUT GATE on the attention (``attn_gate``), a norm AFTER each block
    # (``post_norms``: four RMSNorms a layer); the embedding scaled by sqrt(hidden)
    # (``mup_enabled``); layers 0-5 a SwiGLU MLP of 12288, the other 54 carry 256
    # experts of width 3072, sigmoid scores with a selection bias, top-4 renormalised
    # x 2.448, one ungated shared expert of 3072; untied head. Served (the window
    # layers' keys and values in a ring, as smallthinker's).
    "trinity-large-preview": ModelConfig(
        vocab_size=200192, hidden_size=3072, num_layers=60, num_heads=48, num_kv_heads=8,
        attn_head_dim=128, ffn_dim=12288, max_seq_len=262144, rope_theta=10000.0, norm_eps=1e-5,
        sliding_window_size=4096, sliding_window_layout=(1, 1, 1, 0) * 15,
        rope_layout=(1, 1, 1, 0) * 15, qk_norm=True, qk_norm_per_head=True,
        attn_gate=True, post_norms=True, embedding_multiplier=3072 ** 0.5,
        moe_experts=256, moe_router="sigmoid_topk", moe_top_k=4, moe_route_scale=2.448,
        moe_ffn_dim=3072, moe_norm_topk=True, moe_shared_ffn_dim=3072, moe_shared_gate=False,
        moe_dense_layers=6,
    ),
    # nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (model_type nemotron_h): 52 published
    # blocks of ONE norm and ONE sublayer (`blocks_to_layers`: 29 program layers, 23
    # Mamba-2 mixers of 64 heads x 64, state 128, 8 scan groups, conv 4, chunks of 128,
    # the gate norm WITHIN each group; 6 GQA layers, 32 / 2 heads of 128, no rotary and no
    # other position signal; 6 layers a mixer alone); 23 expert MLPs of 128 UN-GATED
    # ``relu(x)^2`` experts of width 1856, sigmoid scores with a selection bias, top-6
    # renormalised x 2.5, one ungated shared expert of 3712; untied head. Served
    # (models/generation.py keeps a Mamba-2 layer's conv tail and scan state a row
    # beside the attention layers' keys and values: models/ssm.py).
    "nemotron-3-nano-30b-a3b": ModelConfig(
        vocab_size=131072, hidden_size=2688, num_layers=29, num_heads=32, num_kv_heads=2,
        attn_head_dim=128, ffn_dim=1856, max_seq_len=262144, pos_embed="nope", norm_eps=1e-5,
        act_fn="relu2", layer_kinds=_NEMOTRON_KINDS, mlp_layout=_NEMOTRON_MLPS,
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=8, ssm_conv=4, ssm_chunk=128,
        moe_experts=128, moe_router="sigmoid_topk", moe_top_k=6, moe_route_scale=2.5,
        moe_ffn_dim=1856, moe_norm_topk=True, moe_shared_ffn_dim=3712, moe_shared_gate=False,
    ),
}
