"""What the readers of a serving cell whose stack has sliding-window layers share
(PR 54).  The program's cached forward of such a stack opens, under ``layer_<i>`` >
``attn``, one scope more than a plain K/V stack's: ``window`` (the layer's keys and
values live in the ring) or ``full`` (in whole slots), and under it the scopes the
serving readers know (``qkv_proj``, ``cache_write``, ``attn_core``, ``out_proj``).
Its ``decode`` spans carry the iteration's counters by stack:
``kv_full_live_positions`` (sum over the rows in use of their lengths n),
``kv_window_live_positions`` (sum of min(n, window)), ``kv_full_read_positions`` /
``kv_window_read_positions`` (what a layer's attention fetches by construction),
``kv_full_layers`` / ``kv_window_layers`` and ``kv_cache_bytes_per_position`` (ONE
layer's).

A serving reader names no cell (``tests/benchmark/test_benchmark_manifest.py``): it
is read wherever ``serve_tokens_per_s_per_chip`` is.  So in a window whose programs
ran but carry neither ``window`` nor ``full`` (a plain K/V stack, a latent one, a
program from before this PR) every reader here answers 0, which is what such a step
spends in a windowed stack's attention.  None, and the metric left out, in a
context without ``serve`` or with nothing to read at all (no trace for a device
reader, no ``decode`` span for a counter)."""

from benchmark.lib.stats import percentile
from benchmark.metrics._mla import decode_counter, path_of, program_runs

STACKS = ("window", "full")
COUNTERS = ("kv_full_live_positions", "kv_window_live_positions", "kv_full_read_positions",
            "kv_window_read_positions", "kv_full_layers", "kv_window_layers")


def stack_ms_p50(ctx, part, wanted):
    """``{"window": ms, "full": ms}``: the median over the executions of program
    ``part`` (``decode`` | ``prefill``) of the device time of the operations whose
    path holds a scope of ``wanted`` below ``window`` / ``full``; zeros where the
    program carries neither scope; None where no such program ran under the trace."""
    wanted = set(wanted)
    per_run = {stack: [] for stack in STACKS}
    marked = False
    for ex in program_runs(ctx, part):
        paths = [(o, set(path_of(o.op_name))) for o in ex.ops]
        for stack in STACKS:
            inside = [(o, path) for o, path in paths if stack in path]
            marked = marked or bool(inside)
            per_run[stack].append(
                sum((o.end - o.start) / 1e6 for o, path in inside if path & wanted))
    if not per_run["full"]:
        return None
    return {stack: percentile(runs, 50) if marked else 0.0 for stack, runs in per_run.items()}


def step_counters(ctx):
    """The window's ``decode`` iterations' counters by stack, one dict an iteration
    that carries them all: [] where the iterations carry none (another stack), None
    where there is no iteration."""
    columns = {key: decode_counter(ctx, key) for key in COUNTERS}
    if any(values is None for values in columns.values()):
        return None
    if len({len(values) for values in columns.values()}) != 1:
        return []
    return [dict(zip(COUNTERS, row)) for row in zip(*columns.values())]
