"""Device 0's time a step in the flash-attention forward kernels: operations
whose instruction name starts with ``flash_fwd``, the ``name=`` the program
gives each ``pl.pallas_call`` (``flash_fwd_grid``, ``flash_fwd_blocked``,
``flash_fwd_qkv``).  Names and calls a step are printed."""

from benchmark.lib import scoped

NAME, UNIT, BETTER, SOURCE = "flash_fwd_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s_per_chip"


def compute(ctx):
    return scoped.kernel_ms_per_step(ctx, "flash_fwd")
