"""The fused body of the gated delta rule (`ops/gated_delta.gated_delta_fused`:
the kernels ``gdn_fwd`` / ``gdn_bwd``, interpreted on the CPU by calling the
fused entry directly, as tests/test_ssm.py does for `ssd_scan_fused`): the conv's
output ``[q | k | v]`` as it lies, the kernels normalising q and k, against the
token-by-token recurrence of ``benchmark/references/qwen3_next.py`` fed the
reference's own L2 norms, forward and the gradients with respect to ``qkv``,
``g`` and ``beta``, at small sizes; `models/gdn.block`'s two branches against
each other; the choice between the two bodies (`scan_path`) case by case. The
plain body's own tests are in tests/test_qwen3_next.py; the kernels as the
chip's compiler sees them in tests/test_topology_aot.py; their numbers on the
chip from ``experiments/ab_gdn.py``."""

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
NAMES = "qkv g beta".split()
# the plain body's tolerances (tests/test_qwen3_next.py says what each leaves room for),
# as a share of the largest magnitude alone
F32_TOL, BF16_TOL = 5e-5, 1.5e-1
close = functools.partial(harness.close, floor=0.0)
pytestmark = pytest.mark.usefixtures("highest_precision")


def inputs(s, seed=0, b=2, hk=2, r=2, dk=16, dv=8, decay=0.3, dtype=jnp.float32):
    """``(qkv, g, beta)`` as the conv hands them on (q and k as they come, of any
    length), the weight of the loss, and the two sizes the entry cannot read off."""
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (b, s, hk * dk))
    # keys that share a direction: the chunk's system is far from the identity
    k = jax.random.normal(ks[1], (b, s, hk * dk)) + 0.7
    v = jax.random.normal(ks[2], (b, s, hk * r * dv))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, s, hk * r)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hk * r)))
    weight = jax.random.normal(ks[5], (b, s, hk * r, dv))
    return (jnp.concatenate([q, k, v], axis=-1).astype(dtype), g, beta), weight, (hk, dk)


def split(qkv, hk, dk, hv):
    """The reference's q (normalised and scaled), k (normalised) and v of ``qkv``,
    float32, a head an axis."""
    lead, wide = qkv.shape[:2], qkv.astype(jnp.float32)
    q = ARCH.l2norm(wide[..., :hk * dk].reshape(*lead, hk, dk)) / np.sqrt(dk)
    k = ARCH.l2norm(wide[..., hk * dk:2 * hk * dk].reshape(*lead, hk, dk))
    return q, k, wide[..., 2 * hk * dk:].reshape(*lead, hv, -1)


def fused(hk, dk):
    return lambda *a: gd.gated_delta_fused(*a, hk, dk)


def recurrent(hk, dk):
    def fn(qkv, g, beta):
        hv = g.shape[2]
        q, k, v = split(qkv, hk, dk, hv)
        return ARCH.delta_rule_recurrent(jnp.repeat(q, hv // hk, 2), jnp.repeat(k, hv // hk, 2),
                                         v, g, beta)
    return fn


def chunked(hk, dk):
    """The plain body as `models/gdn.block` feeds it: `gdn._l2norm`'d q and k
    rounded to the compute dtype."""
    def fn(qkv, g, beta):
        lead, dtype = qkv.shape[:2], qkv.dtype
        q = (gdn._l2norm(qkv[..., :hk * dk].reshape(*lead, hk, dk)) * dk ** -0.5).astype(dtype)
        k = gdn._l2norm(qkv[..., hk * dk:2 * hk * dk].reshape(*lead, hk, dk)).astype(dtype)
        v = qkv[..., 2 * hk * dk:].reshape(*lead, g.shape[2], -1)
        return gd.gated_delta_chunked(q, k, v, g, beta)
    return fn


def with_gradients(fn, args, weight):
    loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight)  # noqa: E731
    return [fn(*args)] + list(jax.grad(loss, argnums=(0, 1, 2))(*args))


def held(got, want, tol, grad_tol):
    for name, a, b in zip(["o"] + ["d" + n for n in NAMES], got, want):
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all()), name
        try:
            close(a, b, tol if name == "o" else grad_tol)
        except AssertionError as e:
            raise AssertionError(f"{name}: {e}") from None


# whole and ragged sequences (one chunk, three, two of which the second is partly
# padding, a chunk and one position), one and two value heads a key head
@pytest.mark.parametrize("r", [1, 2])
@pytest.mark.parametrize("s", [64, 192, 100, 65])
def test_fused_delta_rule_and_its_gradients_are_the_recurrences(s, r):
    args, weight, sizes = inputs(s, seed=s + r, r=r)
    held(with_gradients(fused(*sizes), args, weight),
         with_gradients(recurrent(*sizes), args, weight), F32_TOL, 5 * F32_TOL)


@pytest.mark.parametrize("s", [256, 100])
def test_fused_delta_rule_and_its_gradients_are_the_plain_bodys(s):
    """Fed the same ``qkv``, the kernels with their own norms and `gated_delta_chunked`
    behind `gdn._l2norm`: what `models/gdn.block`'s two branches run."""
    args, weight, sizes = inputs(s, seed=s + 7)
    held(with_gradients(fused(*sizes), args, weight),
         with_gradients(chunked(*sizes), args, weight), F32_TOL, 5 * F32_TOL)


@pytest.mark.parametrize("r", [1, 2])
def test_fused_delta_rule_at_the_published_head_sizes(r):
    """Dk = Dv = 128, the sizes the kernels' lane tiles are cut for: q, k and v
    whole lane blocks of one array (v's first at 2 Hk Dk / (R Dv) = 2 or 1)."""
    args, weight, sizes = inputs(100, seed=5, b=1, hk=1, r=r, dk=128, dv=128)
    held(with_gradients(fused(*sizes), args, weight),
         with_gradients(recurrent(*sizes), args, weight), F32_TOL, 5 * F32_TOL)


@pytest.mark.parametrize("r", [1, 2])
@pytest.mark.parametrize("s", [128, 100])
def test_fused_delta_rule_in_bf16_stays_near_the_recurrence(s, r):
    """bf16 operands, float32 norms, decays, system, inverse and state: as near
    the float32 recurrence on the same (rounded) inputs as the plain body is held."""
    args, weight, sizes = inputs(s, seed=s, r=r, dtype=jnp.bfloat16)
    got = with_gradients(fused(*sizes), args, weight)
    assert [t.dtype for t in got] == [jnp.bfloat16] * 2 + [jnp.float32] * 2
    held(got, with_gradients(recurrent(*sizes), args, weight), BF16_TOL, BF16_TOL)


@pytest.mark.parametrize("which", ["q", "k"])
def test_a_row_of_zeros_takes_the_eps_path(which):
    """A position whose q or k is all zeros (every head of it) normalises to zeros
    under the root's eps, not to NaN, and its gradient is finite and the
    reference's: ``eps^-1/2`` times what reached the normalised row."""
    (qkv, g, beta), weight, (hk, dk) = inputs(100, seed=11)
    first = 0 if which == "q" else hk * dk
    qkv = qkv.at[:, 3::17, first:first + hk * dk].set(0.0)
    got = with_gradients(fused(hk, dk), (qkv, g, beta), weight)
    assert float(jnp.abs(got[1][:, 3::17, first:first + hk * dk]).max()) > 0.0
    held(got, with_gradients(recurrent(hk, dk), (qkv, g, beta), weight), F32_TOL, 5 * F32_TOL)


def test_fused_delta_rule_carries_its_state_across_chunks():
    """`test_chunked_delta_rule_carries_its_state_across_chunks`'s two assertions
    of the kernels: a write in the first chunk is read in the third (the second
    slab: the state crosses a grid step), and nothing later moves anything earlier."""
    (qkv, g, beta), _, (hk, dk) = inputs(192, seed=3, decay=0.02)
    values = slice(2 * hk * dk, None)
    out = gd.gated_delta_fused(qkv, g, beta, hk, dk, 64)
    cut = gd.gated_delta_fused(qkv.at[:, :64, values].set(0), g, beta, hk, dk, 64)
    assert float(jnp.abs(out[:, 128:] - cut[:, 128:]).max()) > 1e-3
    later = gd.gated_delta_fused(qkv.at[:, 128:, values].set(0), g, beta, hk, dk, 64)
    np.testing.assert_array_equal(np.asarray(out[:, :128]), np.asarray(later[:, :128]))


def test_fused_delta_rule_refuses_another_chunk():
    (qkv, g, beta), _, (hk, dk) = inputs(64)
    with pytest.raises(ValueError, match="chunks of 64"):
        gd.gated_delta_fused(qkv, g, beta, hk, dk, 32)


def test_the_kernels_and_the_mixer_share_one_eps():
    assert gdn._L2_EPS == gd.L2_EPS == 1e-6


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL), (jnp.bfloat16, BF16_TOL)],
                         ids=["float32", "bf16"])
def test_block_fused_and_plain_branches_agree(monkeypatch, dtype, tol):
    """`models/gdn.block` with `scan_path`'s answer turned to each body (the
    kernels interpreted: `pallas_common.use_interpret` is what it is on the CPU):
    the mixer's output and every parameter's gradient, a ragged sequence."""
    cfg = PRESETS["qwen3-next-80b-a3b"].replace(
        hidden_size=32, max_seq_len=100, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16,
        gdn_value_dim=8, dtype=dtype)
    assert gd.pallas_common.use_interpret()
    p = gdn.init_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 100, cfg.hidden_size), dtype)
    weight = jax.random.normal(jax.random.key(2), x.shape)

    def loss(p_):
        y = gdn.block(x, p_, cfg)
        return jnp.sum(y.astype(jnp.float32) * weight), y

    def output_and_gradients(body):
        monkeypatch.setattr(gdn, "scan_path", lambda *a: body)
        jaxpr = str(jax.make_jaxpr(lambda p_: gdn.block(x, p_, cfg))(p))
        assert ("triangular_solve" in jaxpr) == (body == "plain")
        assert ("gdn_fwd" in jaxpr) == (body == "fused")
        (_, y), grads = jax.value_and_grad(loss, has_aux=True)(p)
        return {"y": y, **grads}

    harness.close_by_leaf(output_and_gradients("fused"), output_and_gradients("plain"), tol,
                          floor=0.0)


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
    ("v_not_in_whole_blocks_behind_q_and_k", {"hk": 1, "hv": 4}, "plain"),
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
