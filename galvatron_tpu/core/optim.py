"""Minimal AdamW with explicit, shardable state.

The reference trains with ``torch.optim.Adam`` over FSDP-flattened params
(models/llama_hf/train_dist.py:53); ZeRO-2 shards optimizer state via FSDP
SHARD_GRAD_OP. Here the optimizer state is a plain pytree ``{mu, nu, count}``
mirroring the param tree, so ZeRO-style sharding is just a sharding spec on
the moment trees (galvatron_tpu.parallel.sharding.param_spec with
``for_opt_state=True``) — GSPMD then emits the reduce-scatter(grad) /
sharded-update / all-gather(param) pattern ZeRO hand-implements.

A hand-rolled optimizer (rather than optax) keeps the state structure
transparent for per-leaf sharding and for the search engine's memory cost
model (4×param model states, reference: galvatron/core/cost_model.py:31).

With ``HybridParallelConfig.grad_overlap`` on, ZeRO-2/3 gradients arrive
here already reduce-scattered per layer: sharding.overlap_grad_sync pins
each layer's gradient cotangent to the opt-state spec during backward, so
XLA issues the reduce-scatter as soon as that layer's backward finishes
instead of in one trailing block. Nothing in this module changes — the
update math is elementwise and sharding-agnostic; only the timing of the
collectives moves.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp


class AdamConfig(NamedTuple):
    lr: float = 1e-4
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: Optional[float] = 1.0
    # optional LRSchedule (galvatron_tpu.core.schedules); when set, the
    # effective lr is lr_schedule(step) — evaluated inside the jitted update
    # from the optimizer step count, so one compiled train_step serves the
    # whole schedule (reference: megatron lr-decay flags, SURVEY §2.6)
    lr_schedule: Optional[Any] = None


def init_opt_state(params) -> Dict[str, Any]:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "mu": jax.tree.map(zeros, params),
        "nu": jax.tree.map(zeros, params),
        "count": jnp.zeros((), jnp.int32),
    }


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree))
    )


def apply_update_with_scaler(state, loss, grads, adam: "AdamConfig", scaler_cfg):
    """fp16 train-state transition: AdamW update skipped atomically on
    gradient overflow, dynamic loss scale advanced (reference:
    site_package/megatron/optimizer/grad_scaler.py DynamicGradScaler +
    the skipped-iteration handling in megatron optimizer step).

    ``grads`` must already be unscaled. ``state`` carries a ``scaler`` entry
    from ``galvatron_tpu.core.schedules.init_scaler_state``.
    """
    import jax.numpy as jnp  # noqa: F811 — keep local for clarity

    from galvatron_tpu.core.schedules import all_finite, scaler_update

    finite = all_finite(grads) & jnp.isfinite(loss)
    new_params, new_opt = adamw_update(state["params"], grads, state["opt"], adam)
    select = lambda new, old: jax.tree.map(lambda a, b: jnp.where(finite, a, b), new, old)
    return {
        "params": select(new_params, state["params"]),
        "opt": select(new_opt, state["opt"]),  # count advances only on clean steps
        "step": state["step"] + 1,
        "scaler": scaler_update(state["scaler"], finite, scaler_cfg),
    }, loss


@jax.named_scope("optimizer")
def adamw_update(params, grads, opt_state, cfg: AdamConfig, lr_scale=1.0):
    """One AdamW step in fp32 master precision; returns (params, opt_state)."""
    count = opt_state["count"] + 1
    if cfg.grad_clip is not None:
        gn = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gn, 1e-12))
        grads = jax.tree.map(lambda g: g.astype(jnp.float32) * scale, grads)
    else:
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    b1c = 1.0 - cfg.b1 ** count.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** count.astype(jnp.float32)
    mu = jax.tree.map(lambda m, g: cfg.b1 * m + (1 - cfg.b1) * g, opt_state["mu"], grads)
    nu = jax.tree.map(lambda v, g: cfg.b2 * v + (1 - cfg.b2) * g * g, opt_state["nu"], grads)
    if cfg.lr_schedule is not None:
        # 0-based step index = count before this update's increment
        lr = cfg.lr_schedule(count.astype(jnp.float32) - 1.0) * lr_scale
    else:
        lr = cfg.lr * lr_scale

    def upd(p, m, v):
        step = lr * (m / b1c) / (jnp.sqrt(v / b2c) + cfg.eps)
        if cfg.weight_decay:
            step = step + lr * cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - step).astype(p.dtype)

    new_params = jax.tree.map(upd, params, mu, nu)
    return new_params, {"mu": mu, "nu": nu, "count": count}
