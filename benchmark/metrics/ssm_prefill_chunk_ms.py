"""The part of a prefill-chunk execution under ``ssm``, all Mamba-2 layers: the chunk's two
projections, the conv over [tail | chunk], the chunked scan from the entering state, the
grouped gate norm, the state read as the row enters and written as of the chunk's last real
row.  Median over the window's executions, device 0, the parts printed; 0 for a stack
without served state-space layers."""

from benchmark.metrics import _ssm_serve

NAME, UNIT, BETTER, SOURCE = "ssm_prefill_chunk_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "model step", "serve_tokens_per_s_per_chip"
PARTS = ("in_proj", "state_read", "conv", "scan", "gate_norm", "out_proj", "state_write")


def compute(ctx):
    ms = _ssm_serve.chunk_ms_p50(ctx)
    if ms:
        parts = {part: _ssm_serve.scope_ms_p50(ctx, "prefill", (part,), (part,)) for part in PARTS}
        ctx["say"](f"a prompt chunk's Mamba-2 mixers, {ms:.3f} ms under ssm: " + ", ".join(
            f"{part} {parts[part]:.3f}" for part in PARTS))
    return ms
