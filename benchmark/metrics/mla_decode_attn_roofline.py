"""A decode step's cached latent attention as a share of the chip's memory
bandwidth: the least bytes (``_mla.latent_step_bytes``: the live positions' latent
read once a layer, the step's new positions written) over the chip's HBM bytes/s,
over the measured time under ``attn_core`` of the decode program.  Live positions
and the cache's bytes a position are the engine's counters on the window's
``decode`` spans (means over the window).  Bound by bytes: 121 FLOP/B at the
published widths against the chip's ridge of 240.  0 for a stack without latent
attention (no time under its scopes, no bytes of a latent: ``_mla``)."""

from benchmark.metrics import _mla

NAME, UNIT, BETTER, SOURCE = "mla_decode_attn_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "serve_tokens_per_s_per_chip"


def compute(ctx):
    core_ms = _mla.scope_ms_p50(ctx, "decode", ("attn_core",), ("absorb",))
    live = _mla.decode_counter(ctx, "latent_live_positions")
    width = _mla.decode_counter(ctx, "latent_cache_bytes_per_position")
    if core_ms is None or live is None or not ctx.get("peaks"):
        return None
    if not core_ms or not live or not width:
        return 0.0
    active = [int(s["args"]["active"]) for s in ctx["spans"]
              if s["name"] == "decode" and "active" in s["args"]]
    mean_live, new = sum(live) / len(live), sum(active) / max(1, len(active))
    least = _mla.latent_step_bytes(mean_live, new, width[0])
    least_ms = 1e3 * least / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["say"](f"latent attention of one decode step: {mean_live:.0f} live positions + {new:.1f} "
               f"new x {width[0]:.0f} B = {least / 1e9:.4f} GB least = {least_ms:.3f} ms at the "
               f"chip's {ctx['peaks']['hbm_bytes_per_s'] / 1e9:g} GB/s; measured under attn_core "
               f"{core_ms:.3f} ms")
    return 100.0 * least_ms / core_ms
