"""A decode step's Mamba-2 mixers as a share of the chip's memory bandwidth: the least
bytes (the reference module's ``ssm_step_bytes``: each layer's weights read once, every
row's conv tail and float32 scan state read once and written once) over the chip's HBM
bytes/s, over the measured time under ``ssm`` of the decode program.  Layers and rows are
the engine's (``state_layers`` on the window's ``decode`` spans, the cell's slots: a decode
step runs every row).  Bound by bytes: 64 rows against 38.7 M weights a layer are ~64
FLOP/B on the projections and under 2 on the state, against the chip's ridge of 240.  A
floor of any implementation (no program can advance a state it has not read), so the share
cannot pass 100.  0 for a stack without served state-space layers."""

from benchmark.metrics import _ssm_serve

NAME, UNIT, BETTER, SOURCE = "ssm_state_hbm_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "serve_tokens_per_s_per_chip"


def compute(ctx):
    ms = _ssm_serve.ms_p50(ctx)
    found = _ssm_serve.state_layers_and_rows(ctx)
    if ms is None or found is None or not ctx.get("peaks"):
        return None
    layers, rows = found
    if not ms or not layers or not hasattr(ctx.get("arch"), "ssm_step_bytes"):
        return 0.0
    least = ctx["arch"].ssm_step_bytes(ctx["config"], rows, layers)
    least_ms = 1e3 * least / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["say"](f"Mamba-2 mixers of one decode step: {layers:.0f} layers x (weights + the state "
               f"of {rows} rows read and written) = {least / 1e9:.4f} GB least = {least_ms:.3f} "
               f"ms at the chip's {ctx['peaks']['hbm_bytes_per_s'] / 1e9:g} GB/s; measured under "
               f"ssm {ms:.3f} ms")
    return 100.0 * least_ms / ms
