"""Operations and bytes the algorithm needs, from shapes alone.

Conventions, fixed here so that no later PR can move them:

- model FLOPs are forward + 2x backward of every GEMM on the token path
  (layers and output head; the embedding lookup is a gather and counts 0);
- attention counts the CAUSAL half of the score matrix, ``s(s+1)/2`` pairs a
  sequence, because a causal model needs no more (the trainer's own stepstats
  counts ``s*s``, which reads high);
- recomputed operations do not count as model FLOPs;
- a multiply-add is 2 operations.

- a SERVED forward (``serve_fwd_flops``, ``serve_least_bytes``) counts what the
  model needs whatever implements it: the GEMMs of the tokens it holds,
  attention over the positions LIVE in a slot (not the cache's capacity), the
  head once a position that is sampled; every parameter read once a forward at
  the compute width the cell serves in (bf16, 2 bytes; the engine may hold
  more), K and V of the live positions read once a forward and written once a
  token, the logits row written once a sampled position.

An architecture's own count lives with its reference
(``benchmark/references/<model_type>.py``: ``fwd_flops_per_token``, and for a
served model ``serve_dims`` and ``served_params``); this file has the
arithmetic they share and the kernels' counts.
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


def kv_bytes_per_position(dims, itemsize: int = 2) -> int:
    """K and V of one position over all layers (opt-1.3b in bf16: 196,608)."""
    return 2 * dims["layers"] * dims["kv_heads"] * dims["head_dim"] * itemsize


def serve_fwd_flops(arch, cfg, *, tokens: int, attn_pairs: int, rows_out: int) -> float:
    """Forward FLOPs of served work: ``tokens`` through every layer's GEMMs,
    QK^T and PV over ``attn_pairs`` (query, live key) pairs a layer, the head
    for ``rows_out`` sampled positions."""
    d = arch.serve_dims(cfg)
    body = matmul_params(hidden=d["hidden"], heads=d["heads"], ffn=d["ffn"],
                         mlp_matrices=d["mlp_matrices"], layers=d["layers"], vocab=0)
    return (2.0 * body * tokens + 2 * 2.0 * d["heads"] * d["head_dim"] * d["layers"] * attn_pairs
            + 2.0 * d["hidden"] * d["vocab"] * rows_out)


def serve_least_bytes(arch, cfg, *, forwards: int, tokens: int, positions_read: int,
                      rows_out: int, itemsize: int = 2) -> float:
    """Least HBM traffic of served work: the parameters once each of
    ``forwards`` executed forwards (a decode step, a prefill chunk), K and V of
    ``positions_read`` live positions read and of ``tokens`` new ones written,
    ``rows_out`` logits rows written; all at ``itemsize``."""
    d, params = arch.serve_dims(cfg), arch.served_params(cfg)
    return float(itemsize) * (
        forwards * params["a_forward"] + tokens * params["a_token"]
        + rows_out * d["vocab"]) + kv_bytes_per_position(d, itemsize) * (positions_read + tokens)
