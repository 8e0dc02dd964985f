"""The index build's points-on-lanes layout (``subspace.halves_on_lanes``
streamed by ``kmeans.kmeans_batched_t``) against K-means run on the
``(2Ns, n, h_max)`` view that ``split_halves_padded`` makes, and a 960-d
engine against exact k-NN.

Same reduction order, same numbers: the lane layout holds the same values
(zero padding in the same places) and every pass streams the same chunks,
so the jnp paths — and the Pallas kernels in interpret mode — must be
bit-identical to the view.  The dense path (``block_n=0``) sums in another
order; against it the existing float32 tolerance holds (1e-5, the spread of
a few Lloyd updates' summation-order rounding on separated data, where no
assignment flips)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EnginePolicy, SuCoConfig, SuCoEngine, TileConfig, build_index
from repro.core import kmeans, suco
from repro.core import subspace as sub
from repro.core.suco import _build
from repro.data import exact_knn, make_dataset, recall
from repro.kernels.kmeans_assign import ops as kops

SQRT_K, ITERS, SEED = 8, 5, 11
# (d, Ns): even halves 15 wide (an odd width, as d=960's 60 is no multiple
# of 8), and uneven halves (subspaces 7, 7, 7, 9 -> halves 4/3 and 5/4)
SHAPES = [(240, 8), (30, 4)]
MODES = [("lloyd", 512), ("lloyd", 333), ("minibatch", 512)]


def _mixture(n, d, seed=0, k_true=6, spread=8.0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k_true, d)) * spread
    who = rng.integers(0, k_true, n)
    return jnp.asarray(centers[who] + rng.normal(size=(n, d)), jnp.float32)


def _view_build(x, spec, *, algo, block_n):
    """The build on the (2Ns, n, h_max) view: permute, split, concatenate,
    then kmeans_batched, assembled as _build assembles its result."""
    ns = spec.n_subspaces
    h1, h2 = sub.split_halves_padded(spec, sub.permute(spec, x))
    res = kmeans.kmeans_batched(
        jax.random.key(SEED), jnp.concatenate([h1, h2], axis=0), SQRT_K, ITERS,
        algo=algo, block_n=block_n, pair_sqrt_k=SQRT_K,
    )
    a1, a2 = res.assignments[:ns], res.assignments[ns:]
    cells = (a1 * SQRT_K + a2).astype(jnp.int32)
    counts = res.cell_counts
    if counts is None:
        counts = jax.vmap(lambda c: jnp.bincount(c, length=SQRT_K**2))(cells)
    return res.centroids[:ns], res.centroids[ns:], cells, counts


def _lane_build(x, spec, *, algo, block_n):
    return _build(x, jax.random.key(SEED), spec=spec, sqrt_k=SQRT_K,
                  iters=ITERS, algo=algo, block_n=block_n)


def _assert_same(got, want):
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.fixture
def interpret_kernels(monkeypatch):
    """The TPU route of the build, with the Pallas kernels interpreted."""
    monkeypatch.setattr(kmeans, "_use_pallas", lambda impl: impl != "jnp")
    for name in ("kmeans_assign_stats_t", "kmeans_pair_assign_hist_t",
                 "kmeans_assign_batched_t", "halves_to_lanes"):
        monkeypatch.setattr(
            kops, name, functools.partial(getattr(kops, name), interpret=True))
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("n", [300, 130])
@pytest.mark.parametrize("kernel,columns", [(False, False), (True, False),
                                            (True, True)])
@pytest.mark.parametrize("spec_kind", ["contiguous", "sampled"])
@pytest.mark.parametrize("d,ns", SHAPES)
def test_halves_on_lanes_is_the_split_view_transposed(d, ns, spec_kind, kernel,
                                                      columns, n, monkeypatch):
    """Both writers of the layout: the jnp gather and the kernel (in
    interpret mode, reading ``x`` or ``x^T``) in 128-point blocks, of which
    the last runs past the 300 points, and for 130 points lies wholly past
    them."""
    monkeypatch.setattr(kops, "halves_to_lanes", functools.partial(
        kops.halves_to_lanes, interpret=True))
    spec = (sub.contiguous_spec(d, ns) if spec_kind == "contiguous"
            else sub.sampled_spec(d, ns, seed=3))
    x = _mixture(n, d)
    h1, h2 = sub.split_halves_padded(spec, sub.permute(spec, x))
    want = jnp.swapaxes(jnp.concatenate([h1, h2], axis=0), 1, 2)
    got = sub.halves_on_lanes(spec, x, 384, kernel=kernel, columns=columns)
    assert got.shape == (2 * ns, spec.max_half_size, 384)
    np.testing.assert_array_equal(np.asarray(got[:, :, :n]), np.asarray(want))
    assert not np.asarray(got[:, :, n:]).any()


@pytest.mark.parametrize("algo,block_n", MODES)
@pytest.mark.parametrize("d,ns", SHAPES)
def test_lane_build_bit_identical_to_the_split_view(d, ns, algo, block_n):
    x = _mixture(2000, d, seed=d)
    spec = sub.contiguous_spec(d, ns)
    _assert_same(_lane_build(x, spec, algo=algo, block_n=block_n),
                 _view_build(x, spec, algo=algo, block_n=block_n))


@pytest.mark.parametrize("algo,block_n", [("lloyd", 512), ("minibatch", 512)])
@pytest.mark.parametrize("d,ns", SHAPES)
def test_lane_build_kernels_bit_identical_to_the_split_view(
        d, ns, algo, block_n, interpret_kernels):
    x = _mixture(1000, d, seed=d + 1)
    spec = sub.contiguous_spec(d, ns)
    _assert_same(_lane_build(x, spec, algo=algo, block_n=block_n),
                 _view_build(x, spec, algo=algo, block_n=block_n))


def test_column_major_corpus_builds_the_same_index(interpret_kernels):
    """A corpus the device holds column-major (as the TPU holds 1M x 960)
    is read as ``x^T`` by the halves kernel: the same index as row-major."""
    from jax.experimental.layout import Format, Layout

    x = _mixture(1000, 240, seed=9)
    by_columns = jax.device_put(x, Format(
        Layout(major_to_minor=(1, 0)), jax.sharding.SingleDeviceSharding(
            jax.devices()[0])))
    assert suco._stored_by_columns(by_columns)
    assert not suco._stored_by_columns(x)
    cfg = SuCoConfig(n_subspaces=8, sqrt_k=SQRT_K, kmeans_iters=ITERS,
                     build_mode="chunked", block_n=512)
    want, got = build_index(x, cfg), build_index(by_columns, cfg)
    for name in ("centroids1", "centroids2", "cell_ids", "cell_counts"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)))


@pytest.mark.parametrize("d,ns", SHAPES)
def test_lane_build_agrees_with_the_dense_reference(d, ns):
    x = _mixture(2000, d, seed=d + 2)
    spec = sub.contiguous_spec(d, ns)
    dense = _lane_build(x, spec, algo="lloyd", block_n=0)
    for block_n in (512, 333):
        c1, c2, cells, counts = _lane_build(x, spec, algo="lloyd", block_n=block_n)
        np.testing.assert_array_equal(np.asarray(cells), np.asarray(dense[2]))
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(dense[3]))
        for got, want in ((c1, dense[0]), (c2, dense[1])):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)


def test_build_starts_from_the_reference_init_rows():
    """The build's initial centroids, read from its lane layout, are the
    rows ``bench.reference.init_rows`` rebuilds from the k-means seed."""
    from bench import reference

    d, ns, n = 120, 8, 1500
    x = _mixture(n, d, seed=4)
    spec = sub.contiguous_spec(d, ns)
    dims = sub.half_dims(spec)
    n_pad = kmeans.lane_points(n, 2 * ns, spec.max_half_size, SQRT_K, block_n=512)
    xt = sub.halves_on_lanes(spec, x, n_pad)
    xn = np.asarray(x)
    for kseed in (0, 2**31 - 2):
        c0 = kmeans._init_lanes(jax.random.key(kseed), xt, n, SQRT_K, "auto", "lloyd")
        rows = np.asarray(reference.init_rows(kseed, n=n, books=2 * ns, k=SQRT_K))
        for j, c in enumerate(np.asarray(c0)):
            want = np.where(dims[j] >= 0, xn[rows[j]][:, np.maximum(dims[j], 0)], 0)
            np.testing.assert_array_equal(c, want)


@pytest.fixture(scope="module")
def gist_like():
    ds = make_dataset("gaussian_mixture", 8192, 960, m=16, k=10, seed=5)
    cfg = SuCoConfig(n_subspaces=8, sqrt_k=16, kmeans_iters=6, seed=0)
    return ds, build_index(jnp.asarray(ds.x), cfg)


def test_engine_at_960d_holds_recall_and_equals_the_dense_path(gist_like):
    ds, index = gist_like
    x, q = jnp.asarray(ds.x), jnp.asarray(ds.queries)
    assert index.spec.max_half_size == 60
    fused = SuCoEngine(x, index, EnginePolicy(
        alpha=0.05, beta=0.02, mode="fused",
        tiles=TileConfig(block_n=2048, survivor_cap=128)))
    dense = SuCoEngine(x, index, EnginePolicy(alpha=0.05, beta=0.02, mode="dense"))
    got, want = fused.query(q, 10), dense.query(q, 10)
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    np.testing.assert_array_equal(np.asarray(got.dists), np.asarray(want.dists))
    gt, _ = exact_knn(np.asarray(ds.x), np.asarray(ds.queries), 10)
    assert recall(np.asarray(got.ids), gt) >= 0.9
