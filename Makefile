# Build/test/bench entry points (reference parity: /root/reference/Makefile
# builds csrc/dp_core; here the native pieces build on demand via ctypes
# loaders, and this Makefile wraps the common workflows).

PY ?= python

.PHONY: all native test test-all dryrun chip-smoke lint check-plan audit-comm chaos serving-chaos fleet-chaos data-smoke warmup clean

all: native

# native components: DP search core + data helpers (C ABI shared objects)
native:
	$(PY) -c "from galvatron_tpu.search.native import get_dp_core; assert get_dp_core() is not None, 'dp_core build failed'; print('dp_core ok')"
	$(PY) -c "from galvatron_tpu.core.data_native import get_data_helpers; print('data_helpers', 'ok' if get_data_helpers() is not None else 'unavailable (NumPy fallback)')"

# CI-budget suite (heavyweight matrices deselected; see pyproject addopts)
test:
	$(PY) -m pytest tests/ -q

# everything, including the @slow compile-bound matrices
test-all:
	$(PY) -m pytest tests/ -q -m ""

# static analysis (docs/DESIGN.md § Static analysis) — four passes, one
# suppression contract:
#   GTA0xx plan checker      (`make check-plan`: plan × model × topology)
#   GTL1xx trace hygiene     (this target: JAX footguns in host code)
#   GTL2xx lock discipline   (this target: guarded-by / order / leaks)
#   GTC0xx collective audit  (`make audit-comm`: lowered-HLO comm footprint)
lint:
	$(PY) -m galvatron_tpu.analysis.lint galvatron_tpu
	$(PY) -m galvatron_tpu.analysis.concurrency galvatron_tpu

check-plan:
	$(PY) -m galvatron_tpu.cli check-plan configs/strategies/*.json --strict 1

# HLO collective auditor (docs/DESIGN.md § Static analysis): AOT-lower every
# registered program per exemplar plan (no compile, no execute) and gate
# predicted_over_lowered per cost-model comm term; one invocation per plan —
# the audit world is forced from each plan's own num_devices
audit-comm:
	for p in configs/strategies/*.json; do \
	  env JAX_PLATFORMS=cpu $(PY) -m galvatron_tpu.cli audit-comm $$p \
	    --strict 1 --report $$(basename $$p .json).footprint.jsonl || exit 1; \
	done

# one elastic chaos scenario (docs/DESIGN.md § Elastic training): an 8→4
# simulated shrink under the supervisor must end in a committed checkpoint
# (CI runs the full GALVATRON_FAULTS matrix — see .github/workflows/ci.yml)
chaos:
	rm -rf /tmp/galvatron_chaos
	env JAX_PLATFORMS=cpu GALVATRON_FAULTS="preempt_at_step=1" \
	  GALVATRON_FAULTS_WORLD="8,4" $(PY) -m galvatron_tpu.cli run-elastic \
	  --model_size llama-0.3b --num_layers 2 --hidden_size 32 --num_heads 2 \
	  --ffn_dim 64 --vocab_size 128 --seq_length 16 \
	  --global_train_batch_size 8 --mixed_precision fp32 --global_tp_deg 2 \
	  --train_iters 4 --save /tmp/galvatron_chaos --save_interval 2 \
	  --max_restarts 3 --step_timeout_s 5 --replan_search_space dp+tp
	$(PY) -c "from galvatron_tpu.core.checkpoint import latest_step; s = latest_step('/tmp/galvatron_chaos'); assert s == 4, s; print('chaos shrink ok: committed step', s)"

# serving chaos harness (docs/DESIGN.md § Serving resilience): a real
# `cli serve` subprocess under injected faults — engine crash mid-decode,
# dead-client stall, SIGTERM mid-load — each must end with zero leaked
# slots, exit 0, and a flight-recorder dump (CI runs the same matrix)
serving-chaos:
	$(PY) experiments/serving_chaos.py crash
	$(PY) experiments/serving_chaos.py stall
	$(PY) experiments/serving_chaos.py sigterm
	$(PY) experiments/serving_chaos.py evict

# fleet chaos harness (docs/DESIGN.md § Serving fleet): a real
# `cli serve-fleet` router over 3 replica subprocesses — killing one
# mid-decode loses zero requests (failover within deadline, warm restart),
# and a rolling drain under load serves 100% of admitted requests with
# every replica exiting 0 (CI job fleet-chaos runs the same matrix)
fleet-chaos:
	$(PY) experiments/serving_chaos.py fleet-kill
	$(PY) experiments/serving_chaos.py fleet-rolling

# data-pipeline smoke (docs/DESIGN.md § Data pipeline): tokenize two tiny
# corpora → 0.7/0.3 mixture → pack → 4 traced train iters; asserts
# packing_efficiency >= 0.9, mixture ratios within the ±1-sample bound, and
# checkpointed per-source cursor exactness
data-smoke:
	env JAX_PLATFORMS=cpu $(PY) experiments/data_smoke.py

# AOT-warm the checked-in exemplar strategy into the repo's .jax_cache —
# the SAME cache tier-1 rides (docs/DESIGN.md § AOT compile subsystem):
# every registered program (train step, eval, init, serving prefill/decode,
# generate) compiles from abstract shapes into the persistent cache, with
# per-program compile_ms + memory_analysis stats in warmup_report.jsonl
warmup:
	env JAX_PLATFORMS=cpu $(PY) -m galvatron_tpu.cli warmup \
	  configs/strategies/llama-0.3b_8dev_16gb.json --force_world 8 \
	  --compile_cache_dir .jax_cache --report warmup_report.jsonl

# CPU SIMULATION of a multi-chip run: sharding/schedule validation in a child
# process on 8 virtual CPU devices — never touches an accelerator
dryrun:
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# the quickest proof the trainer starts on the chip: kernel parity + a few
# train steps at llama-7b widths on ONE TPU chip (fails without a TPU; run it
# on the chip machine through the chip tool; CHIPS=4 runs the multi-chip
# plans instead, on a four-chip host)
CHIPS ?= 1
chip-smoke:
	$(PY) chip_smoke.py --chips $(CHIPS)

clean:
	rm -rf build .jax_cache .pytest_cache
	find . -name __pycache__ -type d -exec rm -rf {} +
