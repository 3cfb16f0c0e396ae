"""A decode step's routed experts as a share of the chip's memory bandwidth: the least
bytes (the reference module's ``expert_step_bytes``: the three matrices of the held
experts that got a row, ``moe_held_experts_touched`` on the window's ``decode`` spans, a
mean over the expert layers and the window, read once a layer) over the chip's HBM
bytes/s, over the measured time under ``experts`` of the decode program.  Bound by bytes:
half a row an expert is under 1 FLOP/B against the chip's ridge of 240.  While the held
share's layout gives an EMPTY expert a tile too, the time holds every held expert's
weights and the share reads about touched / held; it bounds what a later claim on this
layer can be.  0 where the iterations carry no such counter or the program no
``experts`` scope (``serve_experts_touched_share`` says where)."""

from benchmark.metrics import _mla
from benchmark.metrics._touched import touched_and_held

NAME, UNIT, BETTER, SOURCE = "serve_expert_hbm_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "serve_tokens_per_s_per_chip"


def compute(ctx):
    ms = _mla.scope_ms_p50(ctx, "decode", ("experts",), ("experts",))
    read = touched_and_held(ctx)
    if ms is None or read is None or not ctx.get("peaks"):
        return None
    touched, held = read
    if not ms or not touched or not hasattr(ctx.get("arch"), "expert_step_bytes"):
        return 0.0
    mean = sum(touched) / len(touched)
    least = ctx["arch"].expert_step_bytes(ctx["config"], mean)
    least_ms = 1e3 * least / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["say"](f"routed experts of one decode step: {mean:.2f} of {held:.0f} held experts "
               f"touched a layer = {least / 1e9:.4f} GB least = {least_ms:.3f} ms at the chip's "
               f"{ctx['peaks']['hbm_bytes_per_s'] / 1e9:g} GB/s; measured under experts "
               f"{ms:.3f} ms")
    return 100.0 * least_ms / ms
