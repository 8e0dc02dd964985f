"""Compile rehearsals for TPU v5e: the main path's kernels at real widths.

Each test compiles for a *described* v5e chip (no TPU attached) and checks
what only the TPU compiler can: that the Pallas kernels lower under Mosaic
(a ``tpu_custom_call`` in the executable), and that the program fits the
chip's 16 GB of HBM.  The widths are SuCo's at the SIFT1M and GIST1M
shapes: n = 1M points of d = 128 or 960, Ns = 8 subspaces, sqrt(K) = 50,
the batch buckets the engine serves, and the tiles the autotuner plans for
the TPU.  The corpus argument is row-major, as a program's arrays are: a
described chip otherwise lets the compiler choose its layout, and it would
take ``x`` transposed for free.

The topology is described inside a module-scoped fixture, never while a
module is imported, so every test worker collects the same tests.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import kmeans
from repro.core import subspace as sub
from repro.core.suco import SuCoIndex, _build, _pool_size, suco_query_fused
from repro.core.tuning import autotune_build_block_n, autotune_tiles
from repro.kernels.kmeans_assign import ops as kops
from repro.kernels.sc_score.ops import sc_scores_cells_prefilter_compact

N, D, NS, SQRT_K = 1_000_000, 128, 8, 50
WIDTHS = (128, 960)  # sift1m's and gist1m's d
K_CELLS = SQRT_K * SQRT_K
HALF = D // NS // 2
HBM_BYTES = 16 * 2**30
ALPHA, BETA, TOPK = 0.05, 0.02, 10


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    from jax.experimental.layout import Format, Layout

    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=jnp.float32, row_major=True, column_major=False):
        if column_major:
            order = tuple(reversed(range(len(dims))))
        elif row_major:
            order = tuple(range(len(dims)))
        else:  # the compiler picks the layout
            return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=Format(Layout(major_to_minor=order), one_chip))

    return make


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    return compiled.as_text(), mem.temp_size_in_bytes + mem.argument_size_in_bytes


def _tiles(m: int, d: int = D):
    return autotune_tiles(
        N, d, m, _pool_size(N, TOPK, BETA),
        n_subspaces=NS, n_cells=K_CELLS, backend="tpu",
    )


@pytest.mark.parametrize("m", [1, 64])
def test_compact_score_kernel_compiles(shape, m):
    tiles = _tiles(m)

    def stage(ranks, cuts, cells, thr, limit):
        return sc_scores_cells_prefilter_compact(
            ranks, cuts, cells, thr, limit, cap=tiles.survivor_cap,
            bm=tiles.bm, bn=tiles.bn, impl="pallas",
        )

    hlo, nbytes = _compile(
        stage,
        shape((NS, m, K_CELLS), jnp.int32),
        shape((NS, m), jnp.int32),
        shape((NS, tiles.block_n), jnp.int32),
        shape((m,), jnp.int32),
        shape((), jnp.int32),
    )
    assert "tpu_custom_call" in hlo
    assert nbytes < HBM_BYTES


@pytest.mark.parametrize(
    "op",
    [
        lambda x, c: kops.kmeans_assign_stats(
            x, c, bn=4096, impl="pallas", with_assign=False
        ),
        lambda x, c: kops.kmeans_assign_stats(x, c, bn=4096, impl="pallas"),
        lambda x, c: kops.kmeans_assign_batched(x, c, bn=4096, impl="pallas"),
        lambda x, c: kops.kmeans_pair_assign_hist(x, c, bn=4096, impl="pallas"),
    ],
    ids=["stats", "stats_assign", "assign", "pair_hist"],
)
def test_kmeans_kernels_compile_at_build_width(shape, op):
    """All 2*Ns codebooks of half-width 8 over the 1M points: the points
    ride the lanes, so nothing is padded to 128 lanes in HBM."""
    hlo, nbytes = _compile(
        op, shape((2 * NS, N, HALF), row_major=False),
        shape((2 * NS, SQRT_K, HALF)),
    )
    assert "tpu_custom_call" in hlo
    assert nbytes < 2 * 2**30  # the 512 MB of points plus a transposed copy


@pytest.mark.parametrize("d", WIDTHS)
def test_fused_query_compiles_at_sift1m(shape, d):
    """The fused query at the SIFT1M and GIST1M widths with the kernels and
    the pool merge the TPU runs: ``merge_impl="auto"`` resolves to
    ``"sort"`` there (``tests/test_fused_query.py`` checks that
    resolution)."""
    m = 64
    spec = sub.contiguous_spec(d, NS)
    half = d // NS // 2
    index = SuCoIndex(
        shape((NS, SQRT_K, half)),
        shape((NS, SQRT_K, half)),
        shape((NS, N), jnp.int32),
        shape((NS, K_CELLS), jnp.int32),
        spec=spec,
        sqrt_k=SQRT_K,
    )

    def query(x, idx, q):
        return suco_query_fused(
            x, idx, q, k=TOPK, alpha=ALPHA, beta=BETA, tiles=_tiles(m, d),
            score_impl="pallas", merge_impl="sort",
        )

    hlo, nbytes = _compile(query, shape((N, d)), index, shape((m, d)))
    assert "tpu_custom_call" in hlo
    assert nbytes < HBM_BYTES


@pytest.mark.parametrize("d", WIDTHS)
def test_build_compiles_at_sift1m(shape, monkeypatch, d):
    """The whole 1M x d build (20 chunked Lloyd iterations and the fused
    pair-assign + histogram) with the kernels the TPU selects, at the
    autotuned chunk, on the corpus as the chip holds it: row-major at
    d=128, column-major at d=960 (the layout that does not pad 960 to 1,024
    lanes; a traced chip run shows the build's argument so).  It holds
    ``x`` and one copy of it in the k-means kernels' layout, ``(2Ns, h,
    n_pad)`` with ``h`` padded to the 8 sublanes (60 -> 64 at d=960), and
    little else, where splitting, concatenating and transposing ``x`` in
    XLA needed 18.89 GB at d=960."""
    monkeypatch.setattr(kmeans, "_use_pallas", lambda impl: impl != "jnp")
    spec = sub.contiguous_spec(d, NS)
    block_n = autotune_build_block_n(
        N, d, sqrt_k=SQRT_K, n_subspaces=NS, backend="tpu")
    columns = d % 128 != 0

    def build(x):
        return _build(
            x, jax.random.key(0), spec=spec, sqrt_k=SQRT_K, iters=20,
            algo="lloyd", block_n=block_n, columns=columns,
        )

    hlo, nbytes = _compile(build, shape((N, d), column_major=columns))
    assert "tpu_custom_call" in hlo
    assert nbytes < HBM_BYTES
    lanes = lambda v: -(-v // 128) * 128  # noqa: E731
    x_bytes = 4 * (d * lanes(N) if columns else N * lanes(d))
    n_pad = kmeans.lane_points(N, 2 * NS, spec.max_half_size, SQRT_K,
                               block_n=block_n, impl="pallas")
    lanes_bytes = 2 * NS * (-(-spec.max_half_size // 8) * 8) * n_pad * 4
    assert nbytes < x_bytes + lanes_bytes + 128 * 2**20
