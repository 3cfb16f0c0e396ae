"""Device 0's time a step in the flash-attention backward kernels: operations
whose instruction name starts with ``flash_bwd``, the ``name=`` the program
gives each ``pl.pallas_call`` (``flash_bwd_blocked``, ``flash_bwd_dkv``,
``flash_bwd_dq``).  Names and calls a step are printed."""

from benchmark.lib import scoped

NAME, UNIT, BETTER, SOURCE = "flash_bwd_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s_per_chip"


def compute(ctx):
    return scoped.kernel_ms_per_step(ctx, "flash_bwd")
