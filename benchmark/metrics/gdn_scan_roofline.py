"""The gated delta rule's share of its roofline: the least time the chip could
take for the delta rules of a step (the chunked algorithm's products a layer,
forward and twice backward, against reading q, k, v, g, beta and writing o and
the gradients once; the reference module's ``gdn_scan_flops`` /
``gdn_scan_bytes``, this chip's share of the tokens) over device 0's time under
``scan``.  Recomputed scans count in the time and not in the operations."""

from benchmark.metrics import _gdn

NAME, UNIT, BETTER, SOURCE = "gdn_scan_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s_per_chip"


def compute(ctx):
    split = _gdn.of_ctx(ctx)
    arch = ctx["arch"]
    if split is None or not ctx["peaks"] or not hasattr(arch, "gdn_scan_flops"):
        return None
    step_s = _gdn.under(split, "scan") / 1e9 / ctx["n_profiled"]
    if not step_s:
        return None
    tokens = ctx["traffic"]["global_batch"] * ctx["traffic"]["seq_len"] // ctx["chips"]
    t_flops = arch.gdn_scan_flops(ctx["config"], tokens) / ctx["peaks"]["flops_per_s_bf16"]
    t_bytes = arch.gdn_scan_bytes(ctx["config"], tokens) / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["say"](f"delta rule roofline: bound by {'compute' if t_flops >= t_bytes else 'memory'} "
               f"(least {t_flops * 1e3:.3f} ms of operations, {t_bytes * 1e3:.3f} ms of bytes a "
               f"step; {step_s * 1e3:.3f} ms under scan)")
    return 100.0 * max(t_flops, t_bytes) / step_s
