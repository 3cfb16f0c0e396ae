"""galvatron_tpu — a TPU-native automatic-parallelism training framework.

Re-implements the capabilities of Hetu-Galvatron (reference: /root/reference)
from scratch on JAX/XLA/pjit/Pallas:

- a **search engine** (``galvatron_tpu.search``) that, given profiled hardware
  (ICI/DCN collective bandwidths) and model (per-layer time/memory) data, runs
  a dynamic program choosing a per-layer hybrid parallelism strategy over
  {PP degree, TP degree, TP axis layout, DP vs ZeRO-2/ZeRO-3, sequence
  parallelism, activation rematerialization} under a per-chip HBM budget
  (reference: galvatron/core/search_engine.py, dynamic_programming.py);
- a **runtime** (``galvatron_tpu.parallel``) that executes layer-heterogeneous
  strategies on a single ``jax.sharding.Mesh``: per-layer ``NamedSharding``
  rules replace Megatron TP wrappers, ``with_sharding_constraint`` boundaries
  replace activation redistribution (reference: galvatron/core/redistribute.py),
  parameter/optimizer sharding specs replace FSDP wrapping (reference:
  galvatron/core/parallel.py), and hand-written GPipe / 1F1B schedules over
  ``shard_map``/``ppermute`` replace the NCCL p2p pipeline engine (reference:
  galvatron/core/pipeline/pipeline.py);
- **Pallas kernels** (``galvatron_tpu.ops``) for flash attention, fused
  RMSNorm, and ring attention over ICI (long-context context parallelism);
- **profilers** (``galvatron_tpu.profiling``) measuring ICI collective
  bandwidth per (group size, axis layout) — the nccl-tests equivalent — and
  per-layer compute time / memory via measured steps and XLA memory analysis
  (reference: galvatron/core/profiler.py, galvatron/profile_hardware/);
- a **model zoo** (``galvatron_tpu.models``) of GPT/LLaMA-family decoder
  models in functional JAX.

Unlike the reference, there is no vendored Megatron fork and no torch: the
compute path is pure JAX, and the only native component is the C++ dynamic-
programming search core (csrc/dp_core.cpp equivalent).
"""

__version__ = "0.1.0"
