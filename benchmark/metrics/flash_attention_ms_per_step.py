"""Device time of the Mosaic custom calls per profiled step on device 0.  With
``fused_norm`` off (the default) every custom call of the step is a
flash-attention kernel; the count per step is printed beside it."""

from benchmark.lib import xplane

NAME, UNIT, BETTER, SOURCE = "flash_attention_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s_per_chip"


def compute(ctx):
    calls = xplane.mosaic_kernels(ctx["trace"])
    if not calls:
        return None
    ctx["say"](f"kernels: {len(calls) / ctx['n_profiled']:g} Mosaic kernels a step on device 0")
    return sum(o.end - o.start for o in calls) / 1e6 / ctx["n_profiled"]
