"""The expert GEMMs' share of their roofline: the least time the chip could
take for the 9 grouped GEMMs of a step (gate, up, down: forward, and twice
backward; the reference module's ``expert_gemm_flops`` / ``expert_gemm_bytes``,
this chip's share of the tokens) over device 0's time under ``experts``.
Padding rows and recomputed GEMMs count in the time and not in the operations."""

from benchmark.metrics import _moe

NAME, UNIT, BETTER, SOURCE = "moe_expert_gemm_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s_per_chip"


def compute(ctx):
    split = _moe.of_ctx(ctx)
    arch = ctx["arch"]
    if split is None or not ctx["peaks"] or not hasattr(arch, "expert_gemm_flops"):
        return None
    step_s = _moe.under(split, "experts") / 1e9 / ctx["n_profiled"]
    if not step_s:
        return None
    tokens = ctx["traffic"]["global_batch"] * ctx["traffic"]["seq_len"] // ctx["chips"]
    t_flops = arch.expert_gemm_flops(ctx["config"], tokens) / ctx["peaks"]["flops_per_s_bf16"]
    t_bytes = arch.expert_gemm_bytes(ctx["config"], tokens) / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["say"](f"expert GEMM roofline: bound by {'compute' if t_flops >= t_bytes else 'memory'} "
               f"(least {t_flops * 1e3:.3f} ms of operations, {t_bytes * 1e3:.3f} ms of bytes a "
               f"step; {step_s * 1e3:.3f} ms under experts)")
    return 100.0 * max(t_flops, t_bytes) / step_s
