"""Unified observability layer (DESIGN.md § Observability).

Four coordinated pieces:

- :mod:`~galvatron_tpu.obs.tracing` — nestable host-side spans with Chrome
  trace-event / Perfetto export; the module-level ``tracer`` singleton is
  the process-wide timeline every subsystem records into.
- :mod:`~galvatron_tpu.obs.stepstats` — model-FLOPs accounting → tokens/s,
  achieved TFLOP/s, MFU/HFU per training iteration.
- :mod:`~galvatron_tpu.obs.prom` — Prometheus text exposition for
  ``GET /metrics`` and the ``--obs_port`` trainer sidecar.
- :mod:`~galvatron_tpu.obs.flight` — crash flight recorder (the tracer ring
  dumped from the trainer's crash path) and bounded ``jax.profiler`` windows
  (``--profile_steps``, ``POST /profile``).
"""

from galvatron_tpu.obs.tracing import Tracer, chrome_trace, tracer
from galvatron_tpu.obs.stepstats import StepStats, peak_flops_per_device
from galvatron_tpu.obs.flight import ProfilerWindow, dump_flight, parse_profile_steps
from galvatron_tpu.obs.prom import ObsServer, PromText, TrainStats, server_metrics_text

__all__ = [
    "Tracer", "chrome_trace", "tracer",
    "StepStats", "peak_flops_per_device",
    "ProfilerWindow", "dump_flight", "parse_profile_steps",
    "ObsServer", "PromText", "TrainStats", "server_metrics_text",
]
