"""chip_smoke.py on the CPU: the no-fallback contract, and the smoke's own
phases at a tiny size on the virtual CPU mesh (the rehearsals that cost no
chip time — /opt/skills/guides/on-chip-measurement §2, steps 1 and 2)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ["--hidden_size", "64", "--num_heads", "2", "--ffn_dim", "128",
        "--vocab_size", "256"]


def test_no_cpu_fallback():
    """Without a TPU the script exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0, p.stdout
    assert '"ok": true' not in p.stdout
    assert "no CPU fallback" in p.stderr


def test_kernel_and_train_phases_tiny():
    """Rehearsal 1: the one-chip phases end to end (interpret-mode kernels,
    trainer through its normal entry) at a size the CPU holds."""
    reps = chip_smoke.phase_kernel_parity(
        batch=1, seq=256, heads=4, kv_heads=2, head_dim=64
    )
    assert [r["case"] for r in reps] == ["flash_attention_qkv mha", "flash_attention_hm gqa"]
    rep = chip_smoke.phase_train(
        model_size="llama-7b", num_layers=2, seq_len=64, global_batch=8,
        iters=3, overrides=TINY,
    )
    assert len(rep["losses"]) == 3 and rep["iter_ms"] > 0
    assert rep["widths"] == (64, 2, 128, 256)
    # the CPU compiles no Mosaic kernel, and the smoke must be able to tell
    assert rep["kernel_calls"] == 0 and rep["attn_impl"] == "xla"


def test_multichip_phase_tiny():
    """Rehearsal 2: the --chips 4 plans on four of the virtual CPU devices."""
    from galvatron_tpu.models.modeling import ModelConfig

    cfg = ModelConfig(
        vocab_size=256, hidden_size=128, num_layers=4, num_heads=4, ffn_dim=256,
        max_seq_len=128, dtype=jnp.bfloat16,
    )
    reps = chip_smoke.phase_multichip(
        jax.devices()[:4], cfg=cfg, global_batch=8, seq_len=128, steps=3,
    )
    assert list(reps) == list(chip_smoke.multichip_plans(4))
    for rep in reps.values():
        assert np.isfinite(rep["losses"]).all()
        assert len(rep["param_bytes"]) == 4
