"""A decode step's cached attention of a windowed stack as a share of the chip's
memory bandwidth: the least bytes (the reference module's ``decode_attn_bytes``: K
and V of the positions live in the rows read once a layer, a full layer a row's n,
a window layer min(n, window), and the step's new positions written) over the chip's
HBM bytes/s, over the measured time under ``attn_core`` of the decode program, both
stacks.  Live positions and layers are the engine's counters on the window's
``decode`` spans (means over the window).  Bound by bytes: 7 query heads a key/value
head give ~7 FLOP/B against the chip's ridge of 240.  0 for a stack without
sliding-window layers (no time under its scopes, no counters of its stacks)."""

from benchmark.metrics import _swa

NAME, UNIT, BETTER, SOURCE = "kv_decode_attn_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "serve_tokens_per_s_per_chip"


def compute(ctx):
    ms = _swa.stack_ms_p50(ctx, "decode", ("attn_core",))
    steps = _swa.step_counters(ctx)
    if ms is None or steps is None or not ctx.get("peaks"):
        return None
    core_ms = ms["window"] + ms["full"]
    if not core_ms or not steps or not hasattr(ctx.get("arch"), "decode_attn_bytes"):
        return 0.0
    active = [int(s["args"]["active"]) for s in ctx["spans"]
              if s["name"] == "decode" and "active" in s["args"]]
    mean = {key: sum(step[key] for step in steps) / len(steps) for key in steps[0]}
    new = sum(active) / max(1, len(active))
    least = ctx["arch"].decode_attn_bytes(
        ctx["config"], mean["kv_full_live_positions"], mean["kv_window_live_positions"], new,
        mean["kv_full_layers"], mean["kv_window_layers"])
    least_ms = 1e3 * least / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["say"](f"cached attention of one decode step: {mean['kv_full_live_positions']:.0f} live "
               f"positions x {mean['kv_full_layers']:.0f} full layers + "
               f"{mean['kv_window_live_positions']:.0f} x {mean['kv_window_layers']:.0f} window "
               f"layers + {new:.1f} new = {least / 1e9:.4f} GB least = {least_ms:.3f} ms at the "
               f"chip's {ctx['peaks']['hbm_bytes_per_s'] / 1e9:g} GB/s; measured under attn_core "
               f"{core_ms:.3f} ms ({ms['window']:.3f} window + {ms['full']:.3f} full)")
    return 100.0 * least_ms / core_ms
