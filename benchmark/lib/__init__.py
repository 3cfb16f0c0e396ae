"""The yardstick: corpus, FLOP and byte counts, peaks, trace reduction,
plain reference.  Nothing here imports the program's model code."""

import importlib.util


def import_file(path: str, name: str):
    """The module in the file ``path``: how the harness finds what later PRs
    add as files (metrics, references), also under a temporary root."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
