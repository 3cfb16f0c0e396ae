"""The decode step's share of the chip's memory bandwidth: the least bytes of ONE
mean decode step of the window (``benchmark/metrics/_serve_work.py``) over the
chip's HBM bytes/s, over ``decode_device_ms_per_step``.  The kernel-side share:
the host's draw does not touch it; the cache's layout (ROADMAP A3 ii) and the
weights' width (A3 iv) move it."""

from benchmark.metrics import _decode_device, _serve_work

NAME, UNIT, BETTER, SOURCE = "decode_step_hbm_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "serve_tokens_per_s_per_chip"


def compute(ctx):
    work, busy_ms = _serve_work.window(ctx), _decode_device.of(ctx, "busy_ms")
    if work is None or not busy_ms:
        return None
    return 100.0 * (1e3 * work["step_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]) / busy_ms
