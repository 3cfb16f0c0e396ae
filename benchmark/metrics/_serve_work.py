"""What the serving cell's three shares of the chip's peaks share (PR 42): the
window's work in the counts ``lib/flops.py`` takes.  ``ctx["serve"]["work"]`` is
the runner's (``lib/serve.window_work``: tokens fed and positions live, rebuilt
from the requests' own records); the number of shared forwards is the window's
``decode`` / ``decode_verify`` spans.  Least bytes and FLOPs are the model's,
whatever implements it (``lib/flops.py``'s conventions), so a PR that holds the
weights in bf16 or stops reading a slot's unused positions raises a share and
cannot push it past 100.  None in a context without ``serve`` (a training
cell), without the chip's peaks, without a ``decode`` span, or for an
architecture whose reference has no ``serve_dims`` / ``served_params``."""

from benchmark.lib import flops


def window(ctx):
    """``{"bytes", "flops", "step_bytes", "step_flops", "decode_forwards"}`` of
    the window and of its mean decode step; said once a run."""
    if "_serve_work" not in ctx:
        ctx["_serve_work"] = _window(ctx)
    return ctx["_serve_work"]


def _window(ctx):
    arch = ctx.get("arch")
    if "serve" not in ctx or not ctx.get("peaks") or not hasattr(arch, "served_params"):
        return None
    w, cfg = ctx["serve"].get("work"), ctx["config"]
    fwd = sum(1 for s in ctx["spans"] if s["name"] in ("decode", "decode_verify"))
    if not w or not fwd:
        return None
    whole = dict(tokens=w["decode_tokens"] + w["prefill_tokens"],
                 rows_out=w["decode_tokens"] + w["prefills"])
    step = dict(tokens=w["decode_tokens"] / fwd, rows_out=w["decode_tokens"] / fwd)
    out = {
        "decode_forwards": fwd,
        "bytes": flops.serve_least_bytes(
            arch, cfg, forwards=fwd + w["prefill_chunks"],
            positions_read=w["decode_positions"] + w["prefill_positions"], **whole),
        "flops": flops.serve_fwd_flops(
            arch, cfg, attn_pairs=w["decode_positions"] + w["prefill_pairs"], **whole),
        "step_bytes": flops.serve_least_bytes(
            arch, cfg, forwards=1, positions_read=w["decode_positions"] / fwd, **step),
        "step_flops": flops.serve_fwd_flops(
            arch, cfg, attn_pairs=w["decode_positions"] / fwd, **step),
    }
    peaks = ctx["peaks"]
    ctx["say"](
        f"served work of the window: {fwd} decode forwards over {w['decode_tokens']} tokens "
        f"({w['decode_tokens'] / fwd:.2f} a step) attending to {w['decode_positions']} live "
        f"positions ({w['decode_positions'] / max(1, w['decode_tokens']):.1f} a token); "
        f"{w['prefills']} prompts of {w['prefill_tokens']} tokens in {w['prefill_chunks']} chunks; "
        f"least bytes {out['bytes'] / 1e9:.3f} GB, forward FLOPs {out['flops'] / 1e12:.3f} T")
    ctx["say"](
        f"one mean decode step: least bytes {out['step_bytes'] / 1e9:.4f} GB "
        f"({flops.kv_bytes_per_position(arch.serve_dims(cfg))} of K and V a live position, "
        f"{2 * arch.served_params(cfg)['a_forward'] / 1e9:.4f} GB of parameters in bf16) = "
        f"{1e3 * out['step_bytes'] / peaks['hbm_bytes_per_s']:.3f} ms at the chip's "
        f"{peaks['hbm_bytes_per_s'] / 1e9:g} GB/s; forward FLOPs {out['step_flops'] / 1e9:.2f} G = "
        f"{1e3 * out['step_flops'] / peaks['flops_per_s_bf16']:.3f} ms at its peak: bound by "
        f"bytes")
    return out
