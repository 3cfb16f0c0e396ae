"""Sarvam family entry (sarvamai/sarvam-105b, ``model_type`` sarvam_mla: latent
attention over a latent slot cache, a sigmoid router with a selection bias over
128 experts beside an ungated shared expert, one leading dense layer, YaRN; see
PRESETS['sarvam-105b'], models/mla.py and models/moe.py's ``sigmoid_topk``).
Served (``cli serve --param_dtype bf16``); trains on the GSPMD path at tp = cp =
pp = 1."""

DEFAULT_MODEL = "sarvam-105b"
SIZES = ("sarvam-105b",)


def main(argv=None):
    from galvatron_tpu.cli import main as cli_main

    return cli_main(argv, model_default=DEFAULT_MODEL)
