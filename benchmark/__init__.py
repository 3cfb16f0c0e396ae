"""The training benchmark: harness, yardstick and cells (see PERF.md).

Everything a later PR may add is data or a small module found by name:
``configs/<config>.json``, ``traffic/<traffic>.json``, ``metrics/<metric>.py``.
"""
