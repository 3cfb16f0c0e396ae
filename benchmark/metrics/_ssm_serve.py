"""What the readers of a serving cell whose stack has Mamba-2 layers share (PR 68).  The
program's cached forward of such a layer opens, under ``layer_<i>`` > ``attn``, the scope
``ssm`` and under it ``in_proj``, ``state_read``, ``conv``, ``step`` (a decode step's
single-step body: the kernel ``ssm_step`` on a chip, over the state stack in place),
``scan`` (a prompt chunk's chunk form), ``gate_norm``, ``out_proj`` and ``state_write``;
the layer keeps a per-row STATE of two parts in the slot cache (a conv tail in the compute
type, a float32 scan state), and the ``decode`` spans carry ``state_layers``,
``state_bytes_per_row`` (ONE layer's, both parts), ``state_conv_bytes_per_row``,
``state_scan_bytes_per_row`` and ``state_step_bytes``.

A serving reader names no cell: it is read wherever ``serve_tokens_per_s_per_chip`` is.
So in a window whose programs ran but carry no ``ssm`` scope (every other serving stack; a
program from before this PR) every reader here answers 0, which is what such a step spends
in a state-space mixer.  None, and the metric left out, in a context without ``serve`` or
with nothing to read at all (no trace).  The four of a decode step read the DECODE program
or the ``decode`` spans only: a profile that holds no prompt chunk leaves none of them out.
The three of a prompt chunk (``ssm_prefill_chunk_ms``, ``ssm_chunk_scan_ms``,
``ssm_chunk_scan_roofline``) read the PREFILL program, answer None where the profile holds
no chunk, and so carry ``workloads`` (as ``shortconv_prefill_chunk_ms`` does).  (The training
cell's ``ssm_ms_per_step`` / ``ssm_scan_*`` read the train step's scopes and carry their
own ``workloads``.)"""

from benchmark.lib.stats import percentile
from benchmark.metrics._mla import decode_counter, program_runs, scope_ms_p50

MARK = ("ssm",)
#: the scopes that move the state: the single step and the tail's read and write
STEP = ("step", "state_read", "state_write")
KERNEL = "ssm_step"


def ms_p50(ctx, wanted=MARK):
    """Median over the decode program's executions of the device time under a scope of
    ``wanted``; 0 where the program carries no ``ssm`` scope; None where no decode program
    ran under the trace."""
    return scope_ms_p50(ctx, "decode", wanted, MARK)


def chunk_ms_p50(ctx, wanted=MARK):
    """`ms_p50` over the PREFILL program's executions (a prompt chunk each); None where the
    profile holds no chunk."""
    return scope_ms_p50(ctx, "prefill", wanted, MARK)


def kernel_ms_p50(ctx, prefix=KERNEL):
    """Median over the decode program's executions of the device time of the instructions
    whose name starts with ``prefix`` (a Pallas kernel's ``name=`` is its instruction's); 0
    where the program has none; None where no decode program ran under the trace."""
    per_run = [sum((o.end - o.start) / 1e6 for o in ex.ops if o.name.startswith(prefix))
               for ex in program_runs(ctx, "decode")]
    return percentile(per_run, 50) if per_run else None


def state_layers_and_rows(ctx):
    """(Mamba-2 layers, rows) of the engine: ``state_layers`` of the window's ``decode``
    spans and the cell's slots (a decode step runs every row); None where there is no
    iteration, (0, rows) where the iterations carry no such counter."""
    layers = decode_counter(ctx, "state_layers")
    if layers is None:
        return None
    return (max(layers) if layers else 0), int(ctx["serve"]["num_slots"])
