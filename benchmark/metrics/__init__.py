"""One module per per-layer metric; the harness imports every module here."""
