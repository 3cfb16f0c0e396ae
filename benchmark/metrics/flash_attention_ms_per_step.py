"""Device time of the flash-attention kernels per profiled step on device 0:
the Pallas calls whose instruction name starts with ``flash_`` (``flash_fwd_*``
and ``flash_bwd_*``, the ``name=`` the program gives each), so its value is
``flash_fwd_ms_per_step + flash_bwd_ms_per_step``.  Until PR 41 it took every
Mosaic custom call, which in a cell with other kernels (the grouped GEMMs of a
routed MLP) counted those too; names and calls a step are printed."""

from benchmark.lib import scoped

NAME, UNIT, BETTER, SOURCE = "flash_attention_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s_per_chip"


def compute(ctx):
    return scoped.kernel_ms_per_step(ctx, "flash_")
