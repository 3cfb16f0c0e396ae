"""Trinity family entry (arcee-ai/Trinity-Large-Preview, ``model_type`` afmoe:
sliding-window layers of 4096 keys with rotary three to one before a full layer
without any position signal, per-head q/k norms, an output gate on the attention, a
norm after each block, the embedding scaled by sqrt(hidden), 256 sigmoid-routed
experts and a shared one behind six dense layers; see PRESETS['trinity-large-preview'],
``ModelConfig.attn_gate`` / ``post_norms`` and models/generation.py's ring cache).
Served (``cli serve --param_dtype bf16 --moe_share R/N``); trains on the GSPMD path
with ``attn_impl`` xla at cp = pp = 1."""

DEFAULT_MODEL = "trinity-large-preview"
SIZES = ("trinity-large-preview",)


def main(argv=None):
    from galvatron_tpu.cli import main as cli_main

    return cli_main(argv, model_default=DEFAULT_MODEL)
