"""What the serving tests share: the small model they serve, its parameters made once a
module, and random prompts (one definition: test_generation, test_serving,
test_serving_resilience, test_paged_kv and test_quant_spec each carried a copy; one `CFG`
object also means the engine's jitted programs, keyed on it, are one set a process)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.models import modeling
from galvatron_tpu.models.modeling import ModelConfig

CFG = ModelConfig(
    vocab_size=97,
    hidden_size=64,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    ffn_dim=128,
    max_seq_len=64,
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def params():
    return modeling.init_model_params(jax.random.key(0), CFG)


def prompts(n, lo=3, hi=14, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, CFG.vocab_size, (rng.randint(lo, hi),)).tolist()
            for _ in range(n)]
