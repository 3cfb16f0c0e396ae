"""Granite 4.0-H family entry (ibm-granite/granite-4.0-h-micro: Mamba-2 layers
beside NoPE GQA attention 9:1, the shared gated MLP, scalar multipliers; see
PRESETS['granite-4.0-h-micro'], models/ssm.py and ops/ssd.py)."""

DEFAULT_MODEL = "granite-4.0-h-micro"
SIZES = ("granite-4.0-h-micro",)


def main(argv=None):
    from galvatron_tpu.cli import main as cli_main

    return cli_main(argv, model_default=DEFAULT_MODEL)
