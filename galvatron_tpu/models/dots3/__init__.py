"""dots3 family entry (dots-studio/dots3-note-prev, ``model_type`` dots3_note, the language
model: latent attention of TWO widths in one stack: full layers of 128 heads over a 512 + 64
latent whose DSA indexer keeps the 2,048 best keys a query, sliding layers of 64 heads over a
1,024 + 64 latent and 513 keys; low-rank queries, the low-rank rescale, a headwise gate; 256
sigmoid-routed experts and a shared one behind a dense layer; see PRESETS['dots3-note-prev'],
``ModelConfig.mla_q_rank`` ... ``swa_*`` and models/mla.py's three cache stacks). Served
(``cli serve --param_dtype bf16 --moe_share R/N``) from the slot cache, no speculation; trains
on the GSPMD path with ``attn_impl`` xla at tp = cp = pp = 1."""

DEFAULT_MODEL = "dots3-note-prev"
SIZES = ("dots3-note-prev",)


def main(argv=None):
    from galvatron_tpu.cli import main as cli_main

    return cli_main(argv, model_default=DEFAULT_MODEL)
