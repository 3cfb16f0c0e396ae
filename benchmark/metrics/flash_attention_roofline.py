"""The flash-attention kernels' share of their roofline: the least time the
chip could take for the algorithm's operations and bytes (``lib/flops.py``,
this chip's share of batch and heads) over the device time of the kernels
whose instruction name starts with ``flash_`` (no other Mosaic call counts)."""

from benchmark.lib import flops, scoped, xplane

NAME, UNIT, BETTER, SOURCE = "flash_attention_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s_per_chip"


def compute(ctx):
    ns, calls = scoped.kernel_ns(xplane.first_device(ctx["trace"]) or [], "flash_")
    if not calls or not ctx["peaks"]:
        return None
    step_s = ns / 1e9 / ctx["n_profiled"]
    cfg, traffic = ctx["config"], ctx["traffic"]
    heads = int(cfg["num_attention_heads"])
    shape = dict(batch=traffic["global_batch"], heads=heads, seq_len=traffic["seq_len"],
                 head_dim=int(cfg["hidden_size"]) // heads, layers=int(cfg["num_hidden_layers"]))
    t_flops = flops.flash_attention_flops(**shape) / ctx["chips"] / ctx["peaks"]["flops_per_s_bf16"]
    t_bytes = flops.flash_attention_bytes(**shape) / ctx["chips"] / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["say"](f"flash roofline: bound by {'compute' if t_flops >= t_bytes else 'memory'} "
               f"(least {t_flops * 1e3:.3f} ms of operations, {t_bytes * 1e3:.3f} ms of bytes a step)")
    return 100.0 * max(t_flops, t_bytes) / step_s
