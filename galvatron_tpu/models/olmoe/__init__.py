"""OLMoE family entry (allenai/OLMoE-1B-7B: dropless softmax top-8 routing
over 64 experts, qk-norm; see PRESETS['olmoe-1b-7b'] and models/moe.py)."""

DEFAULT_MODEL = "olmoe-1b-7b"
SIZES = ("olmoe-1b-7b",)


def main(argv=None):
    from galvatron_tpu.cli import main as cli_main

    return cli_main(argv, model_default=DEFAULT_MODEL)
