"""The part of a decode execution under ``qkv_proj`` + ``out_proj`` + ``mlp`` +
``norm`` inside a layer: the passes over the layers' weights (ROADMAP A3 iv:
float32 weights converted every step).  Median over the window's executions,
device 0; None where the program's operations carry no scope."""

from benchmark.metrics import _decode_device

NAME, UNIT, BETTER, SOURCE = "decode_weights_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    return _decode_device.of(ctx, "weights_ms")
