"""What the readers of a serving cell whose stack has gated short-convolution layers
share (PR 58).  The program's cached forward of such a layer opens, under ``layer_<i>`` >
``attn``, the scope ``shortconv`` and under it ``in_proj``, ``state_read``, ``conv``,
``state_write`` and ``out_proj``; the layer keeps a per-row STATE in the slot cache (no
positions), and the ``decode`` spans carry ``state_layers`` and ``state_bytes_per_row``
(ONE layer's) beside the counters of the attention layers' stack.

A serving reader names no cell (``tests/benchmark/test_benchmark_manifest.py``): it is
read wherever ``serve_tokens_per_s_per_chip`` is.  So in a window whose programs ran but
carry no ``shortconv`` scope (every other stack; a program from before this PR) every
reader here answers 0, which is what such a step spends in a short convolution.  None,
and the metric left out, in a context without ``serve`` or with nothing to read at all
(no trace)."""

from benchmark.metrics._mla import scope_ms_p50

MARK = ("shortconv",)
STATE = ("state_read", "state_write")


def ms_p50(ctx, part, wanted=MARK):
    """Median over the executions of program ``part`` (``decode`` | ``prefill``) of the
    device time under a scope of ``wanted``; 0 where the program carries no ``shortconv``
    scope; None where no such program ran under the trace."""
    return scope_ms_p50(ctx, part, wanted, MARK)
