"""Fullest device's ``peak_bytes_in_use + peak_bytes_reserved`` after the
train call: live arrays plus the step program's temporaries, which this runtime
counts apart (PR 23).  The budget, not a cost: it moves nothing, a cell that
outgrows it fails."""

NAME, UNIT, BETTER, SOURCE = "hbm_peak_gib", "GiB", "lower", "program_counter"
LAYER, MOVES = "device", "tokens_per_s_per_chip"


def compute(ctx):
    return ctx["memory_peak_bytes"] / 2**30 if ctx["memory_peak_bytes"] else None
