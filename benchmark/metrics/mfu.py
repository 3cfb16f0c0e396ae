"""Model FLOP/s utilization: tokens a step over the median ``step`` span and the
cell's chips, times the model FLOPs a token needs (the architecture's count
under ``lib/flops.py``'s conventions: forward + 2x backward, causal half of the
attention pairs, no recomputation) over the chip's published bf16 peak.  From
the median step and not the traced window's rate: writing a large trace out
stalls the loop for seconds inside the window."""

from benchmark.lib import flops
from benchmark.lib.stats import percentile

NAME, UNIT, BETTER, SOURCE = "mfu", "%", "higher", "program_span"
LAYER, MOVES = "model step", "tokens_per_s_per_chip"


def compute(ctx):
    if not ctx["peaks"] or not ctx["step_s"]:
        return None
    traffic = ctx["traffic"]
    step_s = percentile(ctx["step_s"], 50)
    rate = traffic["global_batch"] * traffic["seq_len"] / step_s / ctx["chips"]
    per_token = flops.model_flops_per_token(ctx["arch"], ctx["config"], traffic["seq_len"])
    return 100.0 * rate * per_token / ctx["peaks"]["flops_per_s_bf16"]
