"""A prompt chunk's routed experts as a share of their roofline: the larger of their
operations over the chip's bf16 FLOP/s and their least bytes over the chip's HBM bytes/s
(the reference module's ``expert_chunk_work``: the held pairs through three matrices; the
touched experts' three matrices once and the pairs' rows in and out), over the measured
time under ``experts`` of the prefill program.  The pairs and the touched experts are the
``prefill`` spans' means over chunks of ``prefill_chunk`` REAL rows
(``_expert_chunk.pairs_and_touched``).  None where the profile holds no chunk or no span
carries the counters; 0 for a model without dropless expert layers."""

from benchmark.metrics import _expert_chunk

NAME, UNIT, BETTER, SOURCE = "expert_prefill_chunk_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "serve_tokens_per_s_per_chip"


def compute(ctx):
    ms = _expert_chunk.chunk_ms_p50(ctx)
    if ms is None or not ctx.get("peaks"):
        return None
    if not ms or not hasattr(ctx.get("arch"), "expert_chunk_work"):
        return 0.0
    read = _expert_chunk.pairs_and_touched(ctx)
    if read is None:
        return None
    pairs, touched = read
    flops, moved = ctx["arch"].expert_chunk_work(ctx["config"], pairs, touched)
    flops_ms = 1e3 * flops / ctx["peaks"]["flops_per_s_bf16"]
    bytes_ms = 1e3 * moved / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["say"](f"routed experts of one prompt chunk: {pairs:.1f} held pairs on {touched:.2f} "
               f"touched experts a layer = {flops / 1e12:.4f} TFLOP = {flops_ms:.3f} ms at the "
               f"chip's peak, {moved / 1e9:.4f} GB least = {bytes_ms:.3f} ms at its HBM rate; "
               f"measured under experts {ms:.3f} ms")
    return 100.0 * max(flops_ms, bytes_ms) / ms
