"""Granite 4.0-H family entry (``model_type`` granitemoehybrid: Mamba-2 layers beside
NoPE GQA attention 9:1, scalar multipliers on the embedding, every residual branch, the
attention scores and the logits; see models/ssm.py and ops/ssd.py).

- ``granite-4.0-h-micro``: DENSE, the shared gated MLP alone (``num_local_experts`` 0);
- ``granite-4.0-h-small``: every layer a routed MLP of 72 experts of 768, top-10, beside a
  shared SwiGLU MLP of 1536; Mamba-2 mixers of 128 heads in ONE scan group.

Served (``cli serve --param_dtype bf16``): the slot cache keeps, a row and Mamba-2 layer,
the conv's last 3 inputs and the scan's float32 state beside the attention layers' keys
and values (models/generation.py's state stack), and ``residual_multiplier`` scales every
branch of the cached forwards as it does the training layer's. What a state stack refuses,
by name (`mixers.limits`): the paged backend, speculation, and on the Mamba-2 layers tp,
cp and ``--pack_sequences``. A held share of the experts: ``--moe_share R/N``."""

DEFAULT_MODEL = "granite-4.0-h-micro"
SIZES = ("granite-4.0-h-micro", "granite-4.0-h-small")


def main(argv=None):
    from galvatron_tpu.cli import main as cli_main

    return cli_main(argv, model_default=DEFAULT_MODEL)
