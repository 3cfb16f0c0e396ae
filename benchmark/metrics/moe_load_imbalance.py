"""Median over the window's steps of ``moe_load_max_over_mean``: the fullest
expert's (token, expert) pairs over the even share T*k/E, which the program
logs in every ``train_iter`` record of a dropless top-k MoE model.  1 is even
load; the grouped GEMM's row count does not depend on it (the sorted buffer's
size is fixed), the longest group's share of a kernel's tail does."""

from benchmark.lib.stats import percentile

NAME, UNIT, BETTER, SOURCE = "moe_load_imbalance", "ratio", "lower", "program_counter"
LAYER, MOVES = "model step", "tokens_per_s_per_chip"


def compute(ctx):
    loads = [r["moe_load_max_over_mean"] for r in ctx["records"]
             if isinstance(r.get("moe_load_max_over_mean"), (int, float))]
    if not loads:
        return None
    aux = [r["moe_aux_loss"] for r in ctx["records"] if isinstance(r.get("moe_aux_loss"), float)]
    ctx["say"](f"moe load max/mean: first {loads[0]:.3f}, median {percentile(loads, 50):.3f}, "
               f"last {loads[-1]:.3f}; aux loss first {aux[0]:.4f}, last {aux[-1]:.4f}"
               if aux else f"moe load max/mean median {percentile(loads, 50):.3f}")
    return percentile(loads, 50)
