"""The whole window's share of the chip's bf16 peak: forward FLOPs of the tokens
the window processed (decoded tokens and the prompts prefilled inside it;
attention over the live positions; the head once a sampled position:
``benchmark/metrics/_serve_work.py``) over ``--seconds`` x peak FLOP/s x chips.
It reads well under 1%: decode is bound by bytes (``serve_hbm_roofline``), and
the number stands here as the whole step's share under the name the training
cells use, bounding every kernel-side claim; it is not a target."""

from benchmark.metrics import _serve_work

NAME, UNIT, BETTER, SOURCE = "serve_mfu", "%", "higher", "program_span"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"


def compute(ctx):
    work = _serve_work.window(ctx)
    if work is None:
        return None
    return 100.0 * work["flops"] / (
        ctx["serve"]["seconds"] * ctx["peaks"]["flops_per_s_bf16"] * ctx["chips"])
