"""What the runtime and pipeline parity tests share: the small dense model they train, its
optimizer, seeded batches, and a stage-stacked tree unstacked to the flat one (one definition:
test_pipeline, its siblings and test_hybrid_runtime each carried a copy, and test_ops and
test_pipeline_1f1b imported theirs from another test file)."""

import jax
import jax.numpy as jnp
import numpy as np

from galvatron_tpu.core.optim import AdamConfig
from galvatron_tpu.models.modeling import ModelConfig

CFG = ModelConfig(
    vocab_size=128,
    hidden_size=64,
    num_layers=4,
    num_heads=4,
    ffn_dim=128,
    max_seq_len=32,
    dtype=jnp.float32,
)
ADAM = AdamConfig(lr=1e-3, grad_clip=1.0)


def make_batch(seed=0, batch=8, seq=32, vocab=128):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(0, vocab, (batch, seq + 1)), jnp.int32)


def unstack_params(pipe_params, cfg, pp):
    """stage-stacked → flat pp=1 param tree (on host)."""
    lps = cfg.num_layers // pp
    layers = []
    for s in range(pp):
        for j in range(lps):
            layers.append(jax.tree.map(lambda a: np.asarray(a)[s], pipe_params["stages"][j]))
    flat = {k: jax.tree.map(np.asarray, v) for k, v in pipe_params.items() if k != "stages"}
    flat["layers"] = layers
    return flat
