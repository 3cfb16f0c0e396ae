"""The whole window's share of the chip's memory bandwidth: the LEAST bytes its
forwards had to move (``benchmark/metrics/_serve_work.py``: every parameter once
an executed forward in bf16, K and V of the positions live in the slots, the
logits rows) over ``--seconds`` x the chip's HBM bytes/s x chips.  Decode is
bound by bytes, so this is the serving cell's roofline share end to end: host
time (sampling) and bytes moved beyond the least (float32 weights, a slot's
unused positions) both lower it."""

from benchmark.metrics import _serve_work

NAME, UNIT, BETTER, SOURCE = "serve_hbm_roofline", "%", "higher", "program_span"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    work = _serve_work.window(ctx)
    if work is None:
        return None
    return 100.0 * work["bytes"] / (
        ctx["serve"]["seconds"] * ctx["peaks"]["hbm_bytes_per_s"] * ctx["chips"])
