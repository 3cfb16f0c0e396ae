"""A decode step's sparse attention as a share of the chip's memory bandwidth: the least
bytes (the reference module's ``dsa_step_bytes``: the index key of every LIVE position read,
the SELECTED positions' latents read, the step's new positions' latent and index key
written, a full layer each) over the chip's HBM bytes/s, over the measured time under
``indexer`` + ``select`` + ``attn_core`` of the decode program's full layers.  A floor of
any implementation (no program can score a key it has not read, nor attend a latent it has
not fetched), so the share cannot pass 100.  Live and selected positions and the layers are
the engine's counters on the window's ``decode`` spans (means over the window).  0 for a
stack without an indexer (``benchmark/metrics/_dsa.py``)."""

from benchmark.metrics import _dsa
from benchmark.metrics._mla import decode_counter

NAME, UNIT, BETTER, SOURCE = "dsa_attn_hbm_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "serve_tokens_per_s_per_chip"


def compute(ctx):
    ms = _dsa.full_scope_ms_p50(ctx, ("indexer", "select", "attn_core"))
    live = decode_counter(ctx, "dsa_live_positions")
    if ms is None or live is None or not ctx.get("peaks"):
        return None
    selected = decode_counter(ctx, "dsa_selected_positions")
    layers = decode_counter(ctx, "dsa_full_layers")
    if not ms or not live or not selected or not layers or not hasattr(
            ctx.get("arch"), "dsa_step_bytes"):
        return 0.0
    active = [int(s["args"]["active"]) for s in ctx["spans"]
              if s["name"] == "decode" and "active" in s["args"]]
    mean_live, mean_sel = sum(live) / len(live), sum(selected) / len(selected)
    new = sum(active) / max(1, len(active))
    least = ctx["arch"].dsa_step_bytes(ctx["config"], mean_live, mean_sel, new, layers[0])
    least_ms = 1e3 * least / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["say"](f"sparse attention of one decode step: {mean_live:.0f} live index keys + "
               f"{mean_sel:.0f} selected latents + {new:.1f} new x {layers[0]:.0f} full layers = "
               f"{least / 1e9:.4f} GB least = {least_ms:.3f} ms at the chip's "
               f"{ctx['peaks']['hbm_bytes_per_s'] / 1e9:g} GB/s; measured under indexer + select "
               f"+ attn_core {ms:.3f} ms")
    return 100.0 * least_ms / ms
