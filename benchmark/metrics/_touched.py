"""What the two readers of ``moe_held_experts_touched`` share (PR 61): the counter the
engine notes on the ``decode`` spans of a model with dropless expert layers, beside
``moe_held_experts``, the experts the copy holds."""

from benchmark.metrics._mla import decode_counter


def touched_and_held(ctx):
    """(the iterations' touched counts, the held experts) | None where there is no
    iteration | ([], 0) where the iterations carry no such counter."""
    touched = decode_counter(ctx, "moe_held_experts_touched")
    held = decode_counter(ctx, "moe_held_experts")
    if touched is None:
        return None
    if not touched or not held:
        return [], 0
    return touched, max(held)
