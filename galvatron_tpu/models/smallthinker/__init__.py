"""SmallThinker family entry (PowerInfer/SmallThinker-21BA3B-Instruct, ``model_type``
smallthinker: sliding-window layers of 4096 keys with rotary three to one beside
full layers without any position signal, ReGLU experts routed from the attention
block's input; see PRESETS['smallthinker-21b-a3b'], ``ModelConfig.layer_view`` and
models/generation.py's ring cache). Served (``cli serve --param_dtype bf16``);
trains on the GSPMD path with ``attn_impl`` xla at cp = pp = 1."""

DEFAULT_MODEL = "smallthinker-21b-a3b"
SIZES = ("smallthinker-21b-a3b",)


def main(argv=None):
    from galvatron_tpu.cli import main as cli_main

    return cli_main(argv, model_default=DEFAULT_MODEL)
