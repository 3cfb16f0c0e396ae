"""A prompt chunk's scan as a share of its roofline: the larger of its operations over the
chip's bf16 FLOP/s and its least bytes over the chip's HBM bytes/s (the reference module's
``ssm_chunk_scan_work`` over the cell's ``prefill_chunk`` positions and the engine's
``state_layers``), over the measured time under ``ssm`` > ``scan`` of the prefill program.
The chunk form on a chip is plain XLA so far (no kernel takes an entering state), so the
share is read by scope and not by a kernel's name.  0 for a stack without served
state-space layers."""

from benchmark.metrics import _ssm_serve

NAME, UNIT, BETTER, SOURCE = "ssm_chunk_scan_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "serve_tokens_per_s_per_chip"


def compute(ctx):
    ms = _ssm_serve.chunk_ms_p50(ctx, ("scan",))
    found = _ssm_serve.state_layers_and_rows(ctx)
    if ms is None or found is None or not ctx.get("peaks"):
        return None
    layers = found[0]
    if not ms or not layers or not hasattr(ctx.get("arch"), "ssm_chunk_scan_work"):
        return 0.0
    tokens = int(ctx["serve"]["prefill_chunk"])
    flops, moved = ctx["arch"].ssm_chunk_scan_work(ctx["config"], tokens, layers)
    flops_ms = 1e3 * flops / ctx["peaks"]["flops_per_s_bf16"]
    bytes_ms = 1e3 * moved / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["say"](f"the scan of one prompt chunk of {tokens} positions, {layers:.0f} layers: "
               f"{flops / 1e9:.2f} GFLOP = {flops_ms:.3f} ms at the chip's peak, {moved / 1e6:.1f} "
               f"MB least = {bytes_ms:.3f} ms at its HBM rate; measured under scan {ms:.3f} ms")
    return 100.0 * max(flops_ms, bytes_ms) / ms
