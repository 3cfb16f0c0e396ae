"""Nemotron-H family entry (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type``
nemotron_h: 52 published blocks of one norm and one sublayer, `modeling.blocks_to_layers`
-> 29 program layers, 6 of them a mixer alone; Mamba-2 mixers of 8 scan groups with the
gate norm within each group, GQA 32 / 2 without any position signal, 128 UN-GATED
``relu(x)^2`` experts of width 1856 under a sigmoid router with a selection bias beside
an ungated shared expert; see PRESETS['nemotron-3-nano-30b-a3b'], models/ssm.py and
models/generation.py's state stack).

Served (``cli serve --param_dtype bf16``): the slot cache keeps, a row and Mamba-2
layer, the conv's last 3 inputs (bf16) and the scan's float32 state beside the
attention layers' keys and values; a decode step advances the states in place
(`ops/ssd.ssd_step`), a prompt chunk takes and hands on its state. What a state stack
still refuses, by name (`mixers.limits`): the paged backend (``--kv_num_blocks``),
speculation (``--spec_decode_k``), and on the Mamba-2 layers tp, cp and
``--pack_sequences``. Trains on the GSPMD path at tp = cp = pp = 1."""

DEFAULT_MODEL = "nemotron-3-nano-30b-a3b"
SIZES = ("nemotron-3-nano-30b-a3b",)


def main(argv=None):
    from galvatron_tpu.cli import main as cli_main

    return cli_main(argv, model_default=DEFAULT_MODEL)
