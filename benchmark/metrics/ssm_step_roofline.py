"""The kernel ``ssm_step`` (a decode step's single-step body over the state stack, in
place) as a share of its roofline: the least bytes (the reference module's
``ssm_scan_step_bytes``: every row's float32 scan state read once and written once, all
Mamba-2 layers; the conv tail is ``state_read`` / ``state_write``'s, not this kernel's)
over the chip's HBM bytes/s, over the device time of the instructions named ``ssm_step*`` in the
decode program.  Bound by bytes: 6 operations an entry of the state against 8 bytes moved
are under 1 FLOP/B.  0 where the decode program holds no such kernel (a stack without
served state-space layers; a body outside `ops/ssd.step_path`'s rule)."""

from benchmark.metrics import _ssm_serve

NAME, UNIT, BETTER, SOURCE = "ssm_step_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "serve_tokens_per_s_per_chip"


def compute(ctx):
    ms = _ssm_serve.kernel_ms_p50(ctx)
    found = _ssm_serve.state_layers_and_rows(ctx)
    if ms is None or found is None or not ctx.get("peaks"):
        return None
    layers, rows = found
    if not ms or not layers or not hasattr(ctx.get("arch"), "ssm_scan_step_bytes"):
        return 0.0
    least = ctx["arch"].ssm_scan_step_bytes(ctx["config"], rows, layers)
    least_ms = 1e3 * least / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["say"](f"ssm_step kernels of one decode step: the scan state of {rows} rows x "
               f"{layers:.0f} layers read and written = {least / 1e9:.4f} GB least = "
               f"{least_ms:.3f} ms at the chip's {ctx['peaks']['hbm_bytes_per_s'] / 1e9:g} GB/s; "
               f"measured {ms:.3f} ms")
    return 100.0 * least_ms / ms
