"""A decode step's conv mixers as a share of the chip's memory bandwidth: the least
bytes (the reference module's ``shortconv_step_bytes``: each conv layer's two
projections and taps read once, every row's state read once and written once) over the
chip's HBM bytes/s, over the measured time under ``shortconv`` of the decode program.
Layers and rows are the engine's (``state_layers`` on the window's ``decode`` spans, the
cell's slots: a decode step runs every row).  Bound by bytes: 32 rows against 16.8 M
weights a layer are 32 FLOP/B against the chip's ridge of 240.  It bounds what a later
claim on this layer can be.  0 for a stack without gated short-convolution layers."""

from benchmark.metrics import _shortconv
from benchmark.metrics._mla import decode_counter

NAME, UNIT, BETTER, SOURCE = "shortconv_hbm_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "serve_tokens_per_s_per_chip"


def compute(ctx):
    ms = _shortconv.ms_p50(ctx, "decode")
    layers = decode_counter(ctx, "state_layers")
    if ms is None or layers is None or not ctx.get("peaks"):
        return None
    if not ms or not layers or not hasattr(ctx.get("arch"), "shortconv_step_bytes"):
        return 0.0
    rows = int(ctx["serve"]["num_slots"])
    least = ctx["arch"].shortconv_step_bytes(ctx["config"], rows, max(layers))
    least_ms = 1e3 * least / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["say"](f"conv mixers of one decode step: {max(layers):.0f} layers x (weights + the state "
               f"of {rows} rows read and written) = {least / 1e9:.4f} GB least = {least_ms:.3f} "
               f"ms at the chip's {ctx['peaks']['hbm_bytes_per_s'] / 1e9:g} GB/s; measured under "
               f"shortconv {ms:.3f} ms")
    return 100.0 * least_ms / ms
