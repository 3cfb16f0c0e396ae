"""What the readers of a serving cell whose latent stack has an indexer or a ring share
(PR 65).  The program's cached forward of such a stack opens, under ``layer_<i>`` > ``attn``
> ``full`` (a full layer; ``window``: a sliding layer over the latent ring), beside the
scopes the serving readers know, ``indexer`` (the index projections, the index key's write,
the scores of every live index key) and ``select`` (the exact top-k and the mask it makes of the
scores); ``attn_core`` holds the absorbed attention over the selected keys, a block of keys
at a time under that mask.  Its
``decode`` spans carry the iteration's counters: ``dsa_live_positions`` (the sum over the
rows in use of their lengths n), ``dsa_selected_positions`` (the sum of min(n, index_topk)),
``dsa_read_positions`` / ``dsa_index_read_positions`` (the latents and the index keys a full
layer fetches by construction), ``latent_ring_live_positions`` (the sum of min(n, window)),
``latent_ring_read_positions``, ``dsa_full_layers``, ``latent_ring_layers`` and the three
stacks' bytes a position.

A serving reader names no cell: it is read wherever ``serve_tokens_per_s_per_chip`` is.  So
in a window whose programs ran but carry none of these scopes or counters (every older
serving cell, a program from before this PR) every reader here answers 0, which is what
such a step spends on an indexer or a latent ring.  None, and the metric left out, in a
context without ``serve`` or with nothing to read at all.  Each reads the DECODE program or
the ``decode`` spans ONLY: a profile that holds no prompt chunk leaves none of them out."""

from benchmark.lib.stats import percentile
from benchmark.metrics._mla import decode_counter, path_of, program_runs

MARK = ("indexer", "select")


def full_scope_ms_p50(ctx, wanted):
    """Median over the decode program's executions of the device time under a scope of
    ``wanted`` below ``full``; 0 unless some operation of the program carries ``indexer``
    or ``select``; None where no decode program ran under the trace."""
    wanted, per_run, marked = set(wanted), [], False
    for ex in program_runs(ctx, "decode"):
        paths = [(o, set(path_of(o.op_name))) for o in ex.ops]
        marked = marked or any(path & set(MARK) for _, path in paths)
        per_run.append(sum((o.end - o.start) / 1e6 for o, path in paths
                           if "full" in path and path & wanted))
    if not per_run:
        return None
    return percentile(per_run, 50) if marked else 0.0


def counter_ratio_p50(ctx, over, under, what):
    """The median over the window's ``decode`` spans of counter ``over`` / counter
    ``under``; 0 where the iterations carry neither, None where there is no iteration."""
    top, bottom = decode_counter(ctx, over), decode_counter(ctx, under)
    if top is None or bottom is None:
        return None
    ratios = [a / b for a, b in zip(top, bottom) if b] if len(top) == len(bottom) else []
    if not ratios:
        return 0.0
    ctx["say"](f"{what}, over {len(ratios)} decode iterations: median "
               f"{percentile(ratios, 50):.3f}, min {min(ratios):.3f}, max {max(ratios):.3f}")
    return percentile(ratios, 50)
