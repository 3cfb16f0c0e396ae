"""LFM2 family entry (LiquidAI/LFM2-24B-A2B, ``model_type`` lfm2_moe: gated short
convolutions of 3 taps three to one beside GQA layers with per-head q/k norms, two
leading dense layers, then sigmoid-routed experts with a selection bias; see
PRESETS['lfm2-24b-a2b'], models/shortconv.py and models/generation.py's state stack).
Served (``cli serve --param_dtype bf16``; the slot cache keeps a conv layer's state a
row beside the attention layers' keys and values); trains on the GSPMD path at
tp = cp = pp = 1 on the conv layers."""

DEFAULT_MODEL = "lfm2-24b-a2b"
SIZES = ("lfm2-24b-a2b",)


def main(argv=None):
    from galvatron_tpu.cli import main as cli_main

    return cli_main(argv, model_default=DEFAULT_MODEL)
