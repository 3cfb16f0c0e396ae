"""Qwen3-Next family entry (Qwen/Qwen3-Next-80B-A3B-Instruct: Gated DeltaNet
layers beside gated partial-RoPE attention of head size 256, 3:1, every MLP a
renormalised top-10 expert layer with a gated shared expert; see
PRESETS['qwen3-next-80b-a3b'], models/gdn.py, ops/gated_delta.py and
models/moe.py's held share). Train-only."""

DEFAULT_MODEL = "qwen3-next-80b-a3b"
SIZES = ("qwen3-next-80b-a3b",)


def main(argv=None):
    from galvatron_tpu.cli import main as cli_main

    return cli_main(argv, model_default=DEFAULT_MODEL)
