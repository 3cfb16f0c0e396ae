"""Operations and bytes the algorithm needs, from shapes alone.

Conventions, fixed here so that no later PR can move them:

- model FLOPs are forward + 2x backward of every GEMM on the token path
  (layers and output head; the embedding lookup is a gather and counts 0);
- attention counts the CAUSAL half of the score matrix, ``s(s+1)/2`` pairs a
  sequence, because a causal model needs no more (the trainer's own stepstats
  counts ``s*s``, which reads high);
- recomputed operations do not count as model FLOPs;
- a multiply-add is 2 operations.

An architecture's own count lives with its reference
(``benchmark/references/<model_type>.py``: ``fwd_flops_per_token``); this file
has the arithmetic they share and the kernels' counts.
"""

from __future__ import annotations


def matmul_params(*, hidden: int, heads: int, ffn: int, mlp_matrices: int, layers: int,
                  vocab: int) -> int:
    """Weights every token of a dense decoder is multiplied by: per layer q, k,
    v, o and the MLP (3 matrices gated, 2 plain), plus the output head (tied
    or not, the GEMM runs)."""
    head_dim = hidden // heads
    return layers * (4 * hidden * heads * head_dim + mlp_matrices * hidden * ffn) + hidden * vocab


def attention_pairs(seq_len: int, causal: bool = True) -> int:
    """Query-key pairs of one sequence."""
    return seq_len * (seq_len + 1) // 2 if causal else seq_len * seq_len


def dense_decoder_fwd(*, hidden: int, heads: int, ffn: int, mlp_matrices: int, layers: int,
                      vocab: int, seq_len: int) -> float:
    """Forward model FLOPs per token of a dense causal decoder at ``seq_len``:
    the GEMMs, and QK^T and PV at 2 * head_dim operations per pair and head."""
    gemm = 2.0 * matmul_params(hidden=hidden, heads=heads, ffn=ffn,
                               mlp_matrices=mlp_matrices, layers=layers, vocab=vocab)
    attn = layers * 2 * 2.0 * hidden * attention_pairs(seq_len) / seq_len
    return gemm + attn


def model_flops_per_token(arch, cfg, seq_len: int) -> float:
    """Forward + backward (2x forward) model FLOPs per trained token, by the
    architecture module ``arch``'s own forward count."""
    return 3.0 * arch.fwd_flops_per_token(cfg, seq_len)


def flash_attention_flops(batch: int, heads: int, seq_len: int, head_dim: int,
                          layers: int) -> float:
    """Operations the flash-attention algorithm needs for forward + backward
    over ``layers`` layers: 2 GEMMs forward (QK^T, PV) and 5 backward (the
    score recomputation that stands in for the stored matrix, dV, dP, dQ,
    dK), each 2*d operations per causal pair and head."""
    return 7 * 2.0 * head_dim * heads * batch * layers * attention_pairs(seq_len)


def flash_attention_bytes(batch: int, heads: int, seq_len: int, head_dim: int,
                          layers: int, itemsize: int = 2) -> float:
    """Least HBM traffic of the same: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv (the per-row
    statistics are 1/head_dim of a tensor and left out)."""
    return 12.0 * batch * heads * seq_len * head_dim * itemsize * layers
