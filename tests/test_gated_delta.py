"""The fused body of the gated delta rule (`ops/gated_delta.gated_delta_fused`:
the kernels ``gdn_fwd`` / ``gdn_bwd``, interpreted on the CPU by calling the
fused entry directly, as tests/test_ssm.py does for `ssd_scan_fused`) against the
token-by-token recurrence of ``benchmark/references/qwen3_next.py``, forward and
all five gradients, at small sizes; the choice between the two bodies
(`scan_path`) case by case. The plain body's own tests are in
tests/test_qwen3_next.py; the kernels as the chip's compiler sees them in
tests/test_topology_aot.py; their numbers on the chip from
``experiments/ab_gdn.py``."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference
from galvatron_tpu.models import gdn
from galvatron_tpu.models.modeling import PRESETS
from galvatron_tpu.ops import gated_delta as gd
from tests import _stack_harness as harness
from tests._stack_harness import highest_precision, on_a_chip  # noqa: F401  (a fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = reference.load(ROOT, "qwen3_next")
NAMES = "q k v g beta".split()
# the plain body's tolerances (tests/test_qwen3_next.py says what each leaves room for),
# as a share of the largest magnitude alone
F32_TOL, BF16_TOL = 5e-5, 1.5e-1
close = functools.partial(harness.close, floor=0.0)
pytestmark = pytest.mark.usefixtures("highest_precision")


def inputs(s, seed=0, b=2, hk=2, r=2, dk=16, dv=8, decay=0.3, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = ARCH.l2norm(jax.random.normal(ks[0], (b, s, hk, dk))) / np.sqrt(dk)
    # keys that share a direction: the chunk's system is far from the identity
    k = ARCH.l2norm(jax.random.normal(ks[1], (b, s, hk, dk)) + 0.7)
    v = jax.random.normal(ks[2], (b, s, hk * r, dv))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, s, hk * r)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hk * r)))
    weight = jax.random.normal(ks[5], (b, s, hk * r, dv))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta), weight


def recurrent(q, k, v, g, beta):
    r = v.shape[2] // q.shape[2]
    wide = lambda t: t.astype(jnp.float32)  # noqa: E731
    return ARCH.delta_rule_recurrent(jnp.repeat(wide(q), r, 2), jnp.repeat(wide(k), r, 2),
                                     wide(v), g, beta)


def with_gradients(fn, args, weight):
    loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight)  # noqa: E731
    return [fn(*args)] + list(jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args))


def held(got, want, tol, grad_tol):
    for name, a, b in zip(["o"] + ["d" + n for n in NAMES], got, want):
        try:
            close(a, b, tol if name == "o" else grad_tol)
        except AssertionError as e:
            raise AssertionError(f"{name}: {e}") from None


# whole and ragged sequences (one chunk, three, two of which the second is partly
# padding, a chunk and one position), one and two value heads a key head
@pytest.mark.parametrize("r", [1, 2])
@pytest.mark.parametrize("s", [64, 192, 100, 65])
def test_fused_delta_rule_and_its_gradients_are_the_recurrences(s, r):
    args, weight = inputs(s, seed=s + r, r=r)
    held(with_gradients(gd.gated_delta_fused, args, weight),
         with_gradients(recurrent, args, weight), F32_TOL, 5 * F32_TOL)


def test_fused_delta_rule_at_the_published_head_sizes():
    """Dk = Dv = 128, the sizes the kernels' lane tiles are cut for."""
    args, weight = inputs(100, seed=5, b=1, hk=1, r=2, dk=128, dv=128)
    held(with_gradients(gd.gated_delta_fused, args, weight),
         with_gradients(recurrent, args, weight), F32_TOL, 5 * F32_TOL)


@pytest.mark.parametrize("s", [128, 100])
def test_fused_delta_rule_in_bf16_stays_near_the_recurrence(s):
    """bf16 operands, float32 decays, system, inverse and state: as near the
    float32 recurrence on the same (rounded) inputs as the plain body is held."""
    args, weight = inputs(s, seed=s, dtype=jnp.bfloat16)
    got = with_gradients(gd.gated_delta_fused, args, weight)
    assert [t.dtype for t in got] == [jnp.bfloat16] * 4 + [jnp.float32] * 2
    held(got, with_gradients(recurrent, args, weight), BF16_TOL, BF16_TOL)


def test_fused_delta_rule_carries_its_state_across_chunks():
    """`test_chunked_delta_rule_carries_its_state_across_chunks`'s two assertions
    of the kernels: a write in the first chunk is read in the third (the second
    slab: the state crosses a grid step), and nothing later moves anything earlier."""
    (q, k, v, g, beta), _ = inputs(192, seed=3, decay=0.02)
    out = gd.gated_delta_fused(q, k, v, g, beta, 64)
    cut = gd.gated_delta_fused(q, k, v.at[:, :64].set(0), g, beta, 64)
    assert float(jnp.abs(out[:, 128:] - cut[:, 128:]).max()) > 1e-3
    later = gd.gated_delta_fused(q, k, v.at[:, 128:].set(0), g, beta, 64)
    np.testing.assert_array_equal(np.asarray(out[:, :128]), np.asarray(later[:, :128]))


def test_fused_delta_rule_refuses_another_chunk():
    (q, k, v, g, beta), _ = inputs(64)
    with pytest.raises(ValueError, match="chunks of 64"):
        gd.gated_delta_fused(q, k, v, g, beta, 32)


PUBLISHED = dict(hk=16, hv=32, dk=128, dv=128, chunk=64, dtype=jnp.bfloat16)
ENVELOPE = [
    ("published", {}, "fused"),
    ("float32", {"dtype": jnp.float32}, "fused"),
    ("one_value_head_a_key_head", {"hv": 16}, "fused"),
    ("dk_64", {"dk": 64}, "plain"),
    ("dv_192", {"dv": 192}, "plain"),
    ("chunk_32", {"chunk": 32}, "plain"),
    ("chunk_128", {"chunk": 128}, "plain"),
    ("heads_not_in_whole_groups", {"hv": 24}, "plain"),
    ("float16", {"dtype": jnp.float16}, "plain"),
    ("vmem", {"dk": 2048, "dv": 2048}, "plain"),
]


@pytest.mark.parametrize("name,change,want", ENVELOPE, ids=[e[0] for e in ENVELOPE])
def test_scan_path_envelope(monkeypatch, name, change, want):
    sizes = {**PUBLISHED, **change}
    assert gd.scan_path(**sizes) == "plain"  # the CPU: never the kernels
    on_a_chip(monkeypatch)
    assert gd.scan_path(**sizes) == want


def test_dispatch_and_the_counter_ask_scan_path(monkeypatch):
    cfg = PRESETS["qwen3-next-80b-a3b"]
    assert gdn.path_counts(cfg.replace(num_layers=4))["scan"] == {"fused": 0, "plain": 3}
    assert gdn.path_counts(PRESETS["opt-1.3b"])["scan"] == {"fused": 0, "plain": 0}
    on_a_chip(monkeypatch)
    assert gdn.path_counts(cfg.replace(num_layers=4))["scan"] == {"fused": 3, "plain": 0}
    assert gdn.path_counts(cfg.replace(num_layers=8, gdn_key_dim=64))["scan"] == {"fused": 0, "plain": 6}
    asked = []
    monkeypatch.setattr(gdn, "scan_path", lambda *a: asked.append(a) or "plain")
    assert gdn.path_counts(cfg.replace(num_layers=4))["scan"] == {"fused": 0, "plain": 3}
    assert asked == [(16, 32, 128, 128, 64, cfg.dtype)]
