"""Parity tests for the decomposed collective-matmul (ops/collective_matmul).

The decomposition must be a pure layout/scheduling change: on every mesh
shape it has to reproduce the plain einsum bit-for-nearly-bit, forward AND
backward (the VJP of the AG ring is the RS ring and vice versa — a schedule
bug shows up as a permuted-chunk output or a wrong-chunk gradient, both
caught by allclose against the reference). Runs on the suite's virtual
8-device CPU mesh; tp in {1, 2, 4} x both tp_consec layouts covers single-
axis and multi-axis (tuple ppermute) rings. With four devices the ring is
two-way (half chunks in opposite directions); with two, or a chunk of odd
length, one-way.

The toy shapes are far below what the ring's shape test (``ring_pays``)
lets through, so every test but the shape test's own switches it off
(``always_ring``): a seam that fell back in silence would prove nothing,
and the parity cases assert ``collective_permute`` in the lowered text.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.ops import collective_matmul as cm
from galvatron_tpu.parallel.mesh import build_mesh

B, S, H, F = 4, 16, 8, 12


def _mesh_axes(tp, consec):
    mesh, axes = build_mesh(pp=1)
    return mesh, axes.dp_axes(tp, consec), axes.tp_axes(tp, consec)


def _rand(key, shape, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(key).standard_normal(shape), dtype)


@pytest.fixture
def always_ring(monkeypatch):
    """The ring wherever it can be formed, whatever the shapes."""
    monkeypatch.setattr(cm, "ring_pays", lambda tp, *a, **k: tp > 1)


#: the four projection seams of a layer (Placement.proj_up / proj_down), at toy
#: sizes: name -> (entry point, subscripts, x shape, w shape, w_shard_dim)
N, HD = 4, 2
SEAMS = {
    "qkv_blocked": (cm.allgather_einsum, "bsh,hcnd->bcnsd", (B, S, H), (H, 3, N, HD), 2),
    "mlp_up": (cm.allgather_einsum, "bsh,hf->bsf", (B, S, H), (H, F), 1),
    "out_proj": (cm.einsum_reducescatter, "bnsd,nde->bse", (B, N, S, HD), (N, HD, H), 0),
    "mlp_down": (cm.einsum_reducescatter, "bsf,fh->bsh", (B, S, F), (F, H), 0),
}
#: against the plain einsum of the same dtype: float32 differs by summation
#: order only; bfloat16 rounds each partial sum's hop once more (8 mantissa
#: bits: 2^-7 of the largest value per rounding, sums of 8-12 unit normals)
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 0.25}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("order", ["flat", "gray"])
@pytest.mark.parametrize("consec", [True, False])
@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("seam", sorted(SEAMS))
def test_ring_matches_einsum(seam, tp, consec, order, dtype, always_ring, monkeypatch):
    """Forward and both gradients of every seam on the ring (two-way at tp 4)
    against the plain einsum, in the flattened-index order and in the order a
    2x2's coordinates give (0 -> 1 -> 3 -> 2)."""
    if order == "gray":
        monkeypatch.setattr(
            cm, "mesh_ring_order", lambda mesh, tpa: (0, 1, 3, 2)[:tp] if tp == 4 else (0, 1))
    entry, sub, x_shape, w_shape, w_shard_dim = SEAMS[seam]
    mesh, dp, tpa = _mesh_axes(tp, consec)
    x, w = _rand(10, x_shape, dtype), _rand(11, w_shape, dtype)

    def run(x, w):
        return entry(sub, x, w, mesh=mesh, dp_axes=dp, tp_axes=tpa, w_shard_dim=w_shard_dim)

    def ref(x, w):
        return jnp.einsum(sub, x, w)

    tol = TOL[dtype]
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    np.testing.assert_allclose(f32(run(x, w)), f32(ref(x, w)), atol=tol)
    loss = lambda fn: lambda x, w: jnp.sum(jnp.sin(fn(x, w).astype(jnp.float32)))  # noqa: E731
    # a ring in at least one direction of every seam (the two whose all-gather
    # side puts out head-major dims gather whole there: qkv forward, out_proj backward)
    text = jax.jit(jax.grad(loss(run), argnums=(0, 1))).lower(x, w).as_text()
    assert "collective_permute" in text
    assert ("all_gather" in text) == (seam in ("qkv_blocked", "out_proj"))
    for got, want in zip(jax.grad(loss(run), argnums=(0, 1))(x, w),
                         jax.grad(loss(ref), argnums=(0, 1))(x, w)):
        assert got.dtype == want.dtype
        np.testing.assert_allclose(f32(got), f32(want), atol=tol * 2)


@pytest.mark.parametrize("scatter", [True, False])
def test_reducescatter_seam_applies_and_recomputes_its_activation(scatter, always_ring):
    """``activation=``: the row-parallel seam multiplies act(x), keeps x, and
    differentiates through the activation it recomputes (modeling.mlp_block
    hands it the MLP's gate) — against the plain einsum of act(x)."""
    mesh, dp, tpa = _mesh_axes(4, True)
    x, w = _rand(16, (B, S, F)), _rand(17, (F, H))

    def run(x, w):
        return cm.einsum_reducescatter(
            "bsf,fh->bsh", x, w, mesh=mesh, dp_axes=dp, tp_axes=tpa, w_shard_dim=0,
            scatter_output=scatter, activation=jax.nn.relu)

    def ref(x, w):
        return jnp.einsum("bsf,fh->bsh", jax.nn.relu(x), w)

    np.testing.assert_allclose(run(x, w), ref(x, w), atol=1e-5)
    loss = lambda fn: lambda x, w: jnp.sum(jnp.sin(fn(x, w)))  # noqa: E731
    for got, want in zip(jax.grad(loss(run), argnums=(0, 1))(x, w),
                         jax.grad(loss(ref), argnums=(0, 1))(x, w)):
        np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("entry", ["allgather", "reducescatter"])
def test_seam_takes_the_weight_as_stored(entry, always_ring):
    """A float32 weight under bfloat16 activations (the master parameter, which
    modeling.mlp_block hands a tp_overlap layer's seams uncast): cast inside the
    seam's programs, the result in the activations' dtype, the weight's
    gradient in the weight's."""
    mesh, dp, tpa = _mesh_axes(4, True)
    if entry == "allgather":
        sub, x, w, dim, fn = "bsh,hf->bsf", _rand(18, (B, S, H), jnp.bfloat16), _rand(19, (H, F)), 1, cm.allgather_einsum
    else:
        sub, x, w, dim, fn = "bsf,fh->bsh", _rand(18, (B, S, F), jnp.bfloat16), _rand(19, (F, H)), 0, cm.einsum_reducescatter

    def run(x, w):
        return fn(sub, x, w, mesh=mesh, dp_axes=dp, tp_axes=tpa, w_shard_dim=dim)

    def ref(x, w):
        return jnp.einsum(sub, x, w.astype(x.dtype))

    assert run(x, w).dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(run(x, w), np.float32), np.asarray(ref(x, w), np.float32),
                               atol=TOL[jnp.bfloat16])
    loss = lambda f: lambda x, w: jnp.sum(f(x, w).astype(jnp.float32))  # noqa: E731
    (dx, dw), (rx, rw) = (jax.grad(loss(f), argnums=(0, 1))(x, w) for f in (run, ref))
    assert (dx.dtype, dw.dtype) == (jnp.bfloat16, jnp.float32)
    np.testing.assert_allclose(np.asarray(dx, np.float32), np.asarray(rx, np.float32), atol=0.5)
    np.testing.assert_allclose(dw, rw, atol=0.5)


def test_one_way_ring_for_an_odd_chunk(always_ring):
    """tp 4 with a chunk of odd length cannot be halved: whole chunks one way."""
    mesh, dp, tpa = _mesh_axes(4, True)
    x, w = _rand(12, (B, 12, H)), _rand(13, (H, F))  # 12 / 4 = 3 rows a chunk
    run = lambda x, w: cm.allgather_einsum(  # noqa: E731
        "bsh,hf->bsf", x, w, mesh=mesh, dp_axes=dp, tp_axes=tpa, w_shard_dim=1)
    assert jax.jit(run).lower(x, w).as_text().count("collective_permute") == 3
    np.testing.assert_allclose(run(x, w), jnp.einsum("bsh,hf->bsf", x, w), atol=1e-5)


COORDS_2X2 = [(0, 0, 0), (1, 0, 0), (0, 1, 0), (1, 1, 0)]  # jax.devices() order of a v5e 2x2


@pytest.mark.parametrize("case", ["2x2", "two_groups", "no_coords", "line", "pair"])
def test_ring_order_is_all_nearest_neighbours(case):
    """Pure function of the coordinates: every hop of the order is one link in
    every group; without coordinates, or without such a cycle, 0..T-1."""
    groups = {
        "2x2": [COORDS_2X2],
        # tp 4 x dp 2 on a 2x4: both groups are 2x2 blocks, one ppermute serves both
        "two_groups": [COORDS_2X2, [(x + 2, y, z) for x, y, z in COORDS_2X2]],
        "no_coords": [[None] * 4],
        "line": [[(i, 0, 0) for i in range(4)]],  # no wraparound link: no cycle
        "pair": [[(0, 0, 0), (1, 0, 0)]],
    }[case]
    order = cm.ring_order(groups)
    T = len(groups[0])
    assert sorted(order) == list(range(T))
    if case in ("2x2", "two_groups"):
        for g in groups:
            for p in range(T):
                a, b = g[order[p]], g[order[(p + 1) % T]]
                assert sum(abs(i - j) for i, j in zip(a, b)) == 1, (order, a, b)
        assert order != tuple(range(T))  # 1 -> 2 and 3 -> 0 are diagonals of the 2x2
    else:
        assert order == tuple(range(T))


def test_mesh_ring_order_falls_back_on_cpu_devices():
    mesh, _, tpa = _mesh_axes(4, True)
    assert cm.mesh_ring_order(mesh, tpa) == (0, 1, 2, 3)


@pytest.mark.parametrize("side", ["ring", "narrow", "few_rows", "tp1"])
def test_shape_test_two_sides(side):
    """``ring_pays``: the opt-1.3b MLP seam of the four-chip cell takes the
    ring (so does its attention output projection, local contraction 512:
    the narrowest measured to win); a seam a quarter as wide, a chunk too
    short to fill the MXU in halves, and tp 1 do not. ``exposed_share``
    prices the same decision."""
    assert cm.ring_pays(4, 2048, 512, 2)
    tp, rows, width = {"ring": (4, 2048, 2048), "narrow": (4, 2048, 128),
                       "few_rows": (4, 256, 2048), "tp1": (1, 2048, 2048)}[side]
    assert cm.ring_pays(tp, rows, width, 2) == (side == "ring")
    share = cm.exposed_share(tp, rows, width, 2)
    assert (share < 1.0) == (side == "ring") and share >= 0.0
    if side == "ring":
        # two GEMMs on each piece in hand (the backward of a row-parallel seam)
        assert cm.exposed_share(tp, rows, width, 2, backward_gemms=2) <= share
        assert cm.hop_cover(2, width, 2) == cm.hop_cover(4, width, 2) / 2  # one way at tp 2


#: (tp, local batch, rows a sample, local width) -> pieces along the batch; the
#: first two are the four-chip cell's head-major all-gather sides
BATCH_PIECES_CASES = {
    "cell_qkv_forward": ((4, 4, 2048, 1536), 4),
    "cell_out_proj_backward": ((4, 4, 2048, 512), 4),
    "search_whole_batch": ((4, 16, 2048, 1536), 4),
    "batch_2": ((4, 2, 2048, 1536), 2),
    "batch_1": ((4, 1, 2048, 1536), 1),
    "batch_3": ((4, 3, 2048, 1536), 1),
    "batch_6": ((4, 6, 2048, 1536), 2),
    "quarter_under_min_rows": ((4, 4, 128, 1536), 2),
    "half_under_min_rows": ((4, 4, 64, 1536), 1),
    "narrow": ((4, 4, 2048, 128), 1),
    "tp2": ((2, 4, 2048, 1536), 4),
    "tp1": ((1, 4, 2048, 1536), 1),
}


@pytest.mark.parametrize("case", sorted(BATCH_PIECES_CASES))
def test_batch_pieces_from_shapes(case):
    """``batch_pieces``: the head-major all-gather sides of the four-chip cell
    (a micro-batch of 4 x 2048 on a device) go in four pieces; a batch of one,
    a batch no piece count divides, a piece under ``RING_MIN_PIECE_ROWS``
    rows, a seam too narrow for any ring and tp 1 gather whole.
    ``batch_exposed_share`` prices the same decision: the first piece's
    gather, and what a piece's GEMM leaves of the next."""
    args, pieces = BATCH_PIECES_CASES[case]
    assert cm.batch_pieces(*args, 2) == pieces
    share = cm.batch_exposed_share(*args, 2)
    if pieces == 1:
        assert share == 1.0
    else:
        tp, _, _, width = args
        left = max(0.0, 1.0 - cm.gather_cover(tp, width, 2))
        assert share == (1.0 + (pieces - 1) * left) / pieces and 1.0 / pieces <= share < 1.0


@pytest.mark.parametrize("local_bsz", [16, 4, 2, 1])
def test_search_prices_the_cells_seams_as_the_shape_function_says(local_bsz):
    """``cost_model.tp_overlap_exposed`` over opt-1.3b's four seams at tp 4 +
    sp: the ring's share on the six block-wise sides, ``batch_exposed_share``
    of the local batch on the two head-major all-gather sides (qkv forward,
    out_proj backward), which a batch of one leaves exposed in full."""
    from galvatron_tpu.core.strategy import LayerStrategy
    from galvatron_tpu.models.modeling import PRESETS, projection_seams
    from galvatron_tpu.search.cost_model import ProfiledLayerType, tp_overlap_exposed

    cfg = PRESETS["opt-1.3b"].replace(attn_impl="flash", max_seq_len=2048)
    seams = projection_seams(cfg, 2048)
    assert [(n, k, w, blk) for n, k, w, blk in seams] == [
        ("qkv_proj", "ag", 6144, False), ("out_proj", "rs", 2048, False),
        ("mlp_up", "ag", 8192, True), ("mlp_down", "rs", 8192, True)]
    lt = ProfiledLayerType(
        fwd_ms_per_sample=2.0, parameter_mb=80.0, activation_mb_per_sample={1: 40.0},
        boundary_activation_mb_per_sample=4.0,
        tp_seams=tuple((k, w, 2048, blk) for _, k, w, blk in seams))
    s = LayerStrategy(tp=4, sp=True, tp_overlap=True)
    rows = local_bsz * 2048 // 4
    ring = lambda w, gemms=1: cm.exposed_share(4, rows, w // 4, 2, gemms)  # noqa: E731
    batch = lambda w: cm.batch_exposed_share(4, local_bsz, 2048, w // 4, 2)  # noqa: E731
    want = (batch(6144) + ring(6144)  # qkv_proj: gather forward, ring backward
            + ring(2048) + batch(2048)  # out_proj: ring forward, gather backward
            + ring(8192) + ring(8192) + ring(8192) + ring(8192, 2)) / 8.0
    assert tp_overlap_exposed(lt, s, local_bsz, 2) == pytest.approx(want, abs=1e-12)
    assert (batch(6144) < 1.0) == (batch(2048) < 1.0) == (local_bsz > 1)
    whole = tp_overlap_exposed(lt, s, 1, 2)
    assert tp_overlap_exposed(lt, s, local_bsz, 2) <= whole < 1.0


@pytest.fixture
def toy_batch_pieces(monkeypatch):
    """``batch_pieces`` as shipped but for its two thresholds, which the toy
    shapes are far below; the jitted seams read it while they are traced, so
    their caches are dropped around a switch."""
    monkeypatch.setattr(cm, "RING_MIN_PIECE_ROWS", 1)
    monkeypatch.setattr(cm, "RING_MIN_COVER", 0.0)
    shipped = cm.batch_pieces

    def switch(whole: bool):
        monkeypatch.setattr(cm, "batch_pieces", (lambda *a: 1) if whole else shipped)
        jax.clear_caches()

    yield switch
    jax.clear_caches()


def _small_ints(key, shape, dtype=jnp.float32):
    """Whole numbers in [-2, 2]: every product and every sum of the toy seams
    is then exact in float32, whatever order a GEMM adds in (the CPU's GEMM
    picks its order by the number of rows; the MXU's does not depend on it)."""
    return jnp.asarray(np.random.RandomState(key).randint(-2, 3, shape), dtype)


def _value_and_grads(seam, x, w):
    """(y, dx, dw) of a seam at tp 4 on the suite's mesh (dp 2) under a
    cotangent of small whole numbers, and the all-gathers in the lowered text
    of its forward + backward."""
    entry, sub, _, _, w_shard_dim = SEAMS[seam]
    mesh, dp, tpa = _mesh_axes(4, True)

    def run(x, w):
        return entry(sub, x, w, mesh=mesh, dp_axes=dp, tp_axes=tpa, w_shard_dim=w_shard_dim)

    def loss(x, w):
        y = run(x, w)
        return jnp.sum((y * _small_ints(24, y.shape, y.dtype)).astype(jnp.float32))

    grads = jax.jit(jax.grad(loss, argnums=(0, 1)))
    gathers = grads.lower(x, w).as_text().count("stablehlo.all_gather")
    return [np.asarray(a, np.float32) for a in (run(x, w), *grads(x, w))], gathers


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("local_batch", [4, 2])
@pytest.mark.parametrize("seam", ["qkv_blocked", "out_proj"])
def test_batch_piped_gather_equals_the_whole_gather(seam, local_batch, dtype, always_ring,
                                                    toy_batch_pieces):
    """The head-major all-gather side (qkv forward; out_proj backward, through
    ``_reducescatter_backward``) cut along the batch, one gather and one GEMM
    a piece: the value, ``dx`` and ``dw`` of the whole gather bit for bit
    (every output row depends on its own input row only, and ``dw`` is still
    one GEMM on the whole gathered operand), on operands whose sums are
    exact, so that a row gone to the wrong place is all that can differ."""
    _, _, x_shape, w_shape, _ = SEAMS[seam]
    x = _small_ints(20, (2 * local_batch,) + x_shape[1:], dtype)  # dp 2
    w = _small_ints(21, w_shape, dtype)
    toy_batch_pieces(whole=True)
    want, gathers = _value_and_grads(seam, x, w)
    assert gathers == 1
    toy_batch_pieces(whole=False)
    got, gathers = _value_and_grads(seam, x, w)
    assert gathers == local_batch
    assert all(np.abs(a).max() > 0 for a in want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(want[0], np.asarray(jnp.einsum(SEAMS[seam][1], x, w), np.float32))


@pytest.mark.parametrize("case,batch,seq,gathers", [
    ("four_rows", 8, 1024, 4), ("two_rows", 4, 1024, 2), ("one_row", 2, 1024, 1),
    ("pieces_under_min_rows", 8, 64, 1)])
@pytest.mark.parametrize("seam", ["qkv_blocked", "out_proj"])
def test_batch_pieces_at_its_own_row_threshold(seam, case, batch, seq, gathers, always_ring,
                                               monkeypatch):
    """With ``RING_MIN_PIECE_ROWS`` as shipped: a device's 4 or 2 rows of 1024
    tokens go a row a piece; one row, and pieces of fewer than 256 rows,
    take the whole gather (one ``all_gather`` in the lowered text)."""
    monkeypatch.setattr(cm, "RING_MIN_COVER", 0.0)  # the toy widths
    x = _rand(22, (batch, N, seq, HD) if seam == "out_proj" else (batch, seq, H))
    w = _rand(23, SEAMS[seam][3])
    jax.clear_caches()
    try:
        (y, _, _), found = _value_and_grads(seam, x, w)
    finally:
        jax.clear_caches()
    assert found == gathers
    np.testing.assert_allclose(y, jnp.einsum(SEAMS[seam][1], x, w), atol=1e-4)


def test_seams_below_the_shape_test_stay_plain():
    """Without ``always_ring`` the toy shapes lower to the plain einsum: no
    shard_map, no permute (what every one-chip and narrow layer keeps)."""
    mesh, dp, tpa = _mesh_axes(4, True)
    x, w = _rand(14, (B, S, H)), _rand(15, (H, F))
    text = jax.jit(lambda x, w: cm.allgather_einsum(
        "bsh,hf->bsf", x, w, mesh=mesh, dp_axes=dp, tp_axes=tpa, w_shard_dim=1)).lower(x, w).as_text()
    assert "collective_permute" not in text and "shard_map" not in text


@pytest.mark.parametrize("consec", [True, False])
@pytest.mark.parametrize("tp", [1, 2, 4])
def test_allgather_einsum_matches_einsum(tp, consec, always_ring):
    mesh, dp, tpa = _mesh_axes(tp, consec)
    x, w = _rand(0, (B, S, H)), _rand(1, (H, F))
    ref = jnp.einsum("bsh,hf->bsf", x, w)

    def run(x, w):
        return cm.allgather_einsum(
            "bsh,hf->bsf", x, w, mesh=mesh, dp_axes=dp, tp_axes=tpa, w_shard_dim=1
        )

    np.testing.assert_allclose(run(x, w), ref, atol=1e-5)
    # gradient parity: the ring transposes to the dual ring
    g = jax.grad(lambda x, w: jnp.sum(jnp.sin(run(x, w))), argnums=(0, 1))
    gr = jax.grad(
        lambda x, w: jnp.sum(jnp.sin(jnp.einsum("bsh,hf->bsf", x, w))), argnums=(0, 1)
    )
    for got, want in zip(g(x, w), gr(x, w)):
        np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("scatter", [True, False])
@pytest.mark.parametrize("consec", [True, False])
@pytest.mark.parametrize("tp", [1, 2, 4])
def test_einsum_reducescatter_matches_einsum(tp, consec, scatter, always_ring):
    mesh, dp, tpa = _mesh_axes(tp, consec)
    x, w = _rand(2, (B, S, F)), _rand(3, (F, H))
    ref = jnp.einsum("bsf,fh->bsh", x, w)

    def run(x, w):
        return cm.einsum_reducescatter(
            "bsf,fh->bsh", x, w, mesh=mesh, dp_axes=dp, tp_axes=tpa,
            w_shard_dim=0, scatter_output=scatter,
        )

    np.testing.assert_allclose(run(x, w), ref, atol=1e-5)
    g = jax.grad(lambda x, w: jnp.sum(jnp.sin(run(x, w))), argnums=(0, 1))
    gr = jax.grad(
        lambda x, w: jnp.sum(jnp.sin(jnp.einsum("bsf,fh->bsh", x, w))), argnums=(0, 1)
    )
    for got, want in zip(g(x, w), gr(x, w)):
        np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("consec", [True, False])
def test_blocked_qkv_shape_einsum(consec, always_ring):
    """The 4-operand qkv seam: 'bsh,hcnd->bcnsd' with the head dim sharded
    (w_shard_dim=2) — exercises output-shape derivation for subscripts where
    the sharded letter is neither first nor last."""
    tp = 4
    mesh, dp, tpa = _mesh_axes(tp, consec)
    n, hd = 4, 2
    x, w = _rand(4, (B, S, H)), _rand(5, (H, 3, n, hd))
    ref = jnp.einsum("bsh,hcnd->bcnsd", x, w)
    out = cm.allgather_einsum(
        "bsh,hcnd->bcnsd", x, w, mesh=mesh, dp_axes=dp, tp_axes=tpa, w_shard_dim=2
    )
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_indivisible_shapes_fall_back(always_ring):
    """seq or shard dims the ring does not divide take the plain-einsum path
    (and still produce the right answer) instead of crashing shard_map."""
    tp = 4
    mesh, dp, tpa = _mesh_axes(tp, True)
    x, w = _rand(6, (B, 6, H)), _rand(7, (H, F))  # seq 6 % 4 != 0
    ref = jnp.einsum("bsh,hf->bsf", x, w)
    out = cm.allgather_einsum(
        "bsh,hf->bsf", x, w, mesh=mesh, dp_axes=dp, tp_axes=tpa, w_shard_dim=1
    )
    np.testing.assert_allclose(out, ref, atol=1e-6)
    x2, w2 = _rand(8, (3, S, F)), _rand(9, (F, H))  # batch 3 % dp(2) != 0
    ref2 = jnp.einsum("bsf,fh->bsh", x2, w2)
    out2 = cm.einsum_reducescatter(
        "bsf,fh->bsh", x2, w2, mesh=mesh, dp_axes=dp, tp_axes=tpa, w_shard_dim=0
    )
    np.testing.assert_allclose(out2, ref2, atol=1e-6)


@pytest.mark.parametrize("sp", [True, False])
def test_train_step_parity_with_tp_overlap(sp, always_ring):
    """End-to-end: the same model + data trains to the same losses with the
    collective-matmul decomposition on and off (fp32, tp=4 over the 8-device
    mesh) — the placement's proj_up/proj_down seams change only
    the collective schedule, never the math."""
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import ModelConfig
    from galvatron_tpu.parallel.hybrid import build_runtime

    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        ffn_dim=128, max_seq_len=16, dtype=jnp.float32,
    )
    batch = np.random.RandomState(0).randint(1, 128, (8, 17)).astype(np.int32)
    losses = {}
    for ov in (False, True):
        hp = HybridParallelConfig.uniform(2, tp=4, sp=sp, tp_overlap=ov)
        rt = build_runtime(cfg, hp, global_batch_size=8, seq_len=16)
        st = rt.init_state(jax.random.key(0))
        st, l1 = rt.train_step(st, rt.shard_batch(batch))
        st, l2 = rt.train_step(st, rt.shard_batch(batch))
        losses[ov] = (float(l1), float(l2))
    assert losses[True] == pytest.approx(losses[False], abs=2e-3)
    assert losses[True][1] < losses[True][0]  # it actually learns


def test_grad_overlap_is_loss_invariant():
    """overlap_grad_sync only pins the gradient cotangent's sharding — the
    zero2 train step must produce IDENTICAL losses with it on and off."""
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.modeling import ModelConfig
    from galvatron_tpu.parallel.hybrid import build_runtime

    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        ffn_dim=128, max_seq_len=16, dtype=jnp.float32,
    )
    batch = np.random.RandomState(1).randint(1, 128, (8, 17)).astype(np.int32)
    losses = {}
    for ov in (False, True):
        hp = HybridParallelConfig.uniform(2, dp_type="zero2", grad_overlap=ov)
        rt = build_runtime(cfg, hp, global_batch_size=8, seq_len=16)
        st = rt.init_state(jax.random.key(0))
        st, l1 = rt.train_step(st, rt.shard_batch(batch))
        st, l2 = rt.train_step(st, rt.shard_batch(batch))
        losses[ov] = (float(l1), float(l2))
    assert losses[True] == losses[False]
