"""Fused single-pass query engine: exact parity with the dense reference
and the legacy streaming path, Pareto-prefilter soundness, tiling
autotuner invariants, and the serving-policy knobs that ride along
(traffic-histogram cap, sentinel-id clipping at the gather_rerank op
boundary)."""


import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import (
    EnginePolicy,
    MemoryLimits,
    SuCoConfig,
    SuCoEngine,
    TileConfig,
    autotune_build_block_n,
    autotune_tiles,
    build_index,
    merge_topk_pool,
    suco_query,
    suco_query_fused,
    suco_query_streaming,
)
from repro.core.sc_linear import _resolve_merge_impl
from repro.core.suco import _fused_query
from repro.data import make_dataset

INT_MAX = np.iinfo(np.int32).max


@pytest.fixture(scope="module")
def small():
    ds = make_dataset("gaussian_mixture", 4000, 48, m=16, k=10, seed=0)
    x = jnp.asarray(ds.x)
    idx = build_index(x, SuCoConfig(n_subspaces=8, sqrt_k=24, kmeans_iters=8, seed=0))
    return ds, x, idx


def _assert_bitwise_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))
    np.testing.assert_array_equal(np.asarray(a.scores), np.asarray(b.scores))


# --------------------------- parity suite -----------------------------------


@pytest.mark.parametrize(
    "tiles",
    [
        None,  # autotuned
        TileConfig(block_n=512, survivor_cap=64),
        TileConfig(block_n=333, survivor_cap=64),  # does not divide n=4000
        TileConfig(block_n=1000, survivor_cap=1),  # every chunk overflows
        TileConfig(block_n=4096, survivor_cap=4096),  # never overflows
        TileConfig(block_n=1_000_000, survivor_cap=128),  # single block > n
    ],
)
def test_fused_matches_dense_and_streaming_bitwise(small, tiles):
    """The acceptance contract: ids, distances and scores all bit-identical
    to both the dense reference and the legacy streaming engine, for
    autotuned and adversarial tilings (non-divisible chunks, a survivor
    cap that forces the full-width fallback on every chunk, one that never
    falls back, one block covering the whole dataset)."""
    ds, x, idx = small
    q = jnp.asarray(ds.queries)
    dense = suco_query(x, idx, q, k=10, alpha=0.05, beta=0.02, mode="dense")
    stream = suco_query_streaming(x, idx, q, k=10, alpha=0.05, beta=0.02)
    fused = suco_query_fused(x, idx, q, k=10, alpha=0.05, beta=0.02, tiles=tiles)
    _assert_bitwise_equal(dense, fused)
    _assert_bitwise_equal(stream, fused)


@pytest.mark.parametrize(
    "tiles",
    [
        None,
        TileConfig(block_n=512, survivor_cap=64),  # pruned and fallback chunks
        TileConfig(block_n=1000, survivor_cap=1),  # every chunk falls back
    ],
)
def test_fused_sort_merge_matches_counting(small, tiles):
    """``merge_impl="sort"`` — what "auto" runs on the TPU — serves the
    counting merge's ids, distances and scores and counts the same
    ``QueryStats``: the same pools carry, so the same chunks overflow."""
    ds, x, idx = small
    q = jnp.asarray(ds.queries)
    kw = dict(k=10, alpha=0.05, beta=0.02, metric="l2", tiles=tiles,
              score_impl="auto")
    want, want_stats = _fused_query(x, idx, q, merge_impl="counting", **kw)
    got, got_stats = _fused_query(x, idx, q, merge_impl="sort", **kw)
    _assert_bitwise_equal(want, got)
    assert int(got_stats.overflow_chunks) == int(want_stats.overflow_chunks)
    assert int(got_stats.overflow_rows) == int(want_stats.overflow_rows)
    assert (got_stats.chunks, got_stats.rows) == (want_stats.chunks, want_stats.rows)


@pytest.mark.parametrize(
    "backend, integer, other",
    [("cpu", "counting", "topk"), ("gpu", "counting", "topk"),
     ("tpu", "sort", "sort")],
)
def test_auto_merge_resolves_by_platform(monkeypatch, backend, integer, other):
    """"auto" takes the two-key sort on the TPU; elsewhere the counting
    select for integer-ranged scores and top_k for any other scores."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert _resolve_merge_impl("auto", jnp.int32, 8) == integer
    assert _resolve_merge_impl("auto", jnp.int32, None) == other
    assert _resolve_merge_impl("auto", jnp.float32, 8) == other


def test_fused_tie_break_determinism():
    """Duplicate points produce exact distance ties; the fused path must
    resolve them exactly like the dense pool order (higher score, then
    lower id), on every invocation."""
    rng = np.random.default_rng(3)
    n, d, k = 400, 16, 8
    x = rng.normal(size=(n, d)).astype(np.float32)
    for dup in (9, 17, 33, 101):  # exact duplicates of row 4
        x[dup] = x[4]
    x[11] = x[2]
    ds_x = jnp.asarray(x)
    idx = build_index(ds_x, SuCoConfig(n_subspaces=4, sqrt_k=8, kmeans_iters=4, seed=0))
    q = jnp.asarray(rng.normal(size=(3, d)).astype(np.float32))
    dense = suco_query(ds_x, idx, q, k=k, alpha=0.2, beta=0.2, mode="dense")
    for tiles in (TileConfig(block_n=64, survivor_cap=16),
                  TileConfig(block_n=100, survivor_cap=400)):
        fused = suco_query_fused(ds_x, idx, q, k=k, alpha=0.2, beta=0.2, tiles=tiles)
        _assert_bitwise_equal(dense, fused)


def test_fused_pool_larger_than_n(small):
    """beta > 1: the pool clamps to n, parity still exact."""
    ds, x, idx = small
    q = jnp.asarray(ds.queries)
    dense = suco_query(x, idx, q, k=10, alpha=0.05, beta=1.5, mode="dense")
    fused = suco_query_fused(
        x, idx, q, k=10, alpha=0.05, beta=1.5,
        tiles=TileConfig(block_n=777, survivor_cap=96),
    )
    _assert_bitwise_equal(dense, fused)


def test_fused_l1_metric_parity(small):
    ds, x, idx = small
    q = jnp.asarray(ds.queries)
    dense = suco_query(
        x, idx, q, k=10, alpha=0.05, beta=0.05, metric="l1", mode="dense"
    )
    fused = suco_query_fused(
        x, idx, q, k=10, alpha=0.05, beta=0.05, metric="l1",
        tiles=TileConfig(block_n=700, survivor_cap=128),
    )
    _assert_bitwise_equal(dense, fused)


def test_fused_rejects_k_larger_than_n(small):
    ds, x, idx = small
    q = jnp.asarray(ds.queries)
    with pytest.raises(ValueError, match="k="):
        suco_query_fused(x, idx, q, k=x.shape[0] + 1, alpha=0.05, beta=0.02)


def test_mode_fused_dispatch(small):
    """suco_query(mode="fused") routes to the fused engine; "auto" at small
    n stays dense; bogus modes still raise."""
    ds, x, idx = small
    q = jnp.asarray(ds.queries)
    dense = suco_query(x, idx, q, k=10, alpha=0.05, beta=0.02, mode="dense")
    via_mode = suco_query(x, idx, q, k=10, alpha=0.05, beta=0.02, mode="fused")
    _assert_bitwise_equal(dense, via_mode)
    with pytest.raises(ValueError, match="unknown mode"):
        suco_query(x, idx, q, k=10, alpha=0.05, beta=0.02, mode="bogus")


def test_fused_never_copies_or_streams_x():
    """The fused scan touches x only through O(cap)-row gathers: no live
    intermediate is O(n*d)-sized (in particular no padded copy of x, which
    would double dataset residency), and nothing of size m*n exists."""
    from repro.launch.hlo_analysis import jaxpr_peak_intermediate

    n, d, m, k, beta = 20_000, 32, 32, 10, 0.02
    ds = make_dataset("gaussian_mixture", n, d, m=m, k=k, seed=1)
    x, q = jnp.asarray(ds.x), jnp.asarray(ds.queries)
    cfg = SuCoConfig(n_subspaces=8, sqrt_k=16, kmeans_iters=2, seed=0)
    idx = build_index(x, cfg)
    tiles = TileConfig(block_n=2048, survivor_cap=128)

    jaxpr = jax.make_jaxpr(
        lambda xx, qq: suco_query_fused(
            xx, idx, qq, k=k, alpha=0.05, beta=beta, tiles=tiles
        )
    )(x, q)
    p = max(k, int(beta * n))
    bn = tiles.block_n
    n_pad = -(-n // bn) * bn
    allowed = max(
        2 * m * (bn + p),  # score block + carried pool triple
        cfg.n_subspaces * m * bn,  # per-chunk per-subspace collision gather
        m * p * d,  # overflow-fallback distance gather (pool rows)
        cfg.n_subspaces * n_pad,  # the index's cell ids, reshaped to blocks
        cfg.n_subspaces * m * cfg.n_cells,  # Dynamic-Activation ranks
    )
    got = jaxpr_peak_intermediate(jaxpr)
    assert got <= allowed, f"fused intermediate {got} > allowed {allowed}"
    assert got < n * d, f"fused path materialised an O(n*d) array: {got}"
    assert got < m * n, f"fused path materialised an (m, n)-sized array: {got}"


def test_fused_score_prune_is_one_kernel_launch(small):
    """The score -> prune stage is a single pallas_call per chunk: the
    in-kernel survivor compaction leaves exactly one kernel launch in the
    whole fused-query jaxpr (the chunk scan body runs once per chunk), with
    no host-graph searchsorted/gather stage between the scorer and the
    merge on the pruned path."""
    from repro.analysis.jaxpr_rules import iter_eqns

    ds, x, idx = small
    q = jnp.asarray(ds.queries)
    tiles = TileConfig(block_n=512, survivor_cap=128)
    jaxpr = jax.make_jaxpr(
        lambda xx, qq: suco_query_fused(
            xx, idx, qq, k=10, alpha=0.05, beta=0.02, tiles=tiles,
            score_impl="pallas",
        )
    )(x, q)
    launches = [
        eqn for eqn, _ in iter_eqns(jaxpr) if eqn.primitive.name == "pallas_call"
    ]
    assert len(launches) == 1, (
        f"fused query traced {len(launches)} pallas_call eqns; the "
        "score+prefilter+compaction stage must be exactly one launch"
    )


@pytest.mark.slow
def test_fused_parity_at_100k():
    """Acceptance: bit-identical to dense on n=100k synthetic data for two
    tile configs, and mode="auto" routes this n to the fused engine."""
    n, d, m = 100_000, 16, 8
    ds = make_dataset("gaussian_mixture", n, d, m=m, k=10, seed=2)
    x, q = jnp.asarray(ds.x), jnp.asarray(ds.queries)
    idx = build_index(x, SuCoConfig(n_subspaces=4, sqrt_k=16, kmeans_iters=2, seed=0))
    dense = suco_query(x, idx, q, k=10, alpha=0.03, beta=0.005, mode="dense")
    for tiles in (None, TileConfig(block_n=30_000, survivor_cap=192)):
        fused = suco_query_fused(
            x, idx, q, k=10, alpha=0.03, beta=0.005, tiles=tiles
        )
        _assert_bitwise_equal(dense, fused)
    auto = suco_query(x, idx, q, k=10, alpha=0.03, beta=0.005)
    _assert_bitwise_equal(dense, auto)


# ------------------- Pareto prefilter soundness (property) ------------------


@settings(max_examples=40, deadline=None)
@given(
    m=st.integers(1, 4),
    p=st.integers(1, 24),
    b=st.integers(1, 48),
    hi=st.integers(1, 6),
    seed=st.integers(0, 10_000),
)
def test_prefilter_never_drops_a_kept_row(m, p, b, hi, seed):
    """The fused fast path prunes block rows with score <= the carried pool
    minimum before merging.  Property: the pruned merge (losers replaced
    by sentinels) is bit-identical to the exact full merge — the prefilter
    can never drop a row merge_topk_pool would keep, nor keep one it
    would drop."""
    rng = np.random.default_rng(seed)
    pool_s_raw = rng.integers(-1, hi + 1, size=(m, p)).astype(np.int32)
    pool_i_raw = np.sort(rng.integers(0, 1000, size=(m, p)), axis=1).astype(np.int32)
    # sort pool rows by (score desc, id asc) and sentinel-ify score<0 rows,
    # mirroring a mid-scan carried pool
    for i in range(m):
        order = np.lexsort((pool_i_raw[i], -pool_s_raw[i]))
        pool_s_raw[i] = pool_s_raw[i][order]
        pool_i_raw[i] = pool_i_raw[i][order]
        pool_i_raw[i][pool_s_raw[i] < 0] = INT_MAX
    blk_s = rng.integers(0, hi + 1, size=(m, b)).astype(np.int32)
    blk_i = 1000 + np.arange(b, dtype=np.int32)[None].repeat(m, 0)  # ids ascend

    pool_s, pool_i = jnp.asarray(pool_s_raw), jnp.asarray(pool_i_raw)
    want = merge_topk_pool(pool_s, pool_i, jnp.asarray(blk_s), jnp.asarray(blk_i))

    thr = pool_s_raw[:, -1:]  # pool sorted desc -> min in the last column
    keep = blk_s > thr
    pruned_s = np.where(keep, blk_s, -1).astype(np.int32)
    pruned_i = np.where(keep, blk_i, INT_MAX).astype(np.int32)
    got = merge_topk_pool(pool_s, pool_i, jnp.asarray(pruned_s), jnp.asarray(pruned_i))
    np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(want[1]), np.asarray(got[1]))


# ------------------------------ engine wiring -------------------------------


def test_engine_fused_mode_parity_and_zero_retrace(small):
    """mode="fused" behind EnginePolicy: padded buckets return exactly the
    wrapper's answers, and warmed (bucket, k) executables never retrace."""
    ds, x, idx = small
    policy = EnginePolicy(alpha=0.05, beta=0.02, mode="fused",
                          batch_buckets=(4, 16))
    engine = SuCoEngine(x, idx, policy)
    assert engine.mode == "fused"
    engine.warmup(batch_sizes=(1, 4, 16), ks=(10,))
    warm = engine.compile_count
    q = jnp.asarray(ds.queries)
    for m in (1, 3, 4, 16):
        got = engine.query(q[:m], k=10)
        want = suco_query(
            x, idx, q[:m], k=10, alpha=0.05, beta=0.02, mode="fused"
        )
        _assert_bitwise_equal(got, want)
    assert engine.compile_count == warm, "fused engine retraced after warmup"


def test_engine_auto_resolves_fused_at_streaming_scale():
    """The fused path is the streaming-scale default behind EnginePolicy."""
    n, d = 32_768, 8
    ds = make_dataset("gaussian_mixture", n, d, m=2, k=5, seed=0)
    x = jnp.asarray(ds.x)
    idx = build_index(x, SuCoConfig(n_subspaces=4, sqrt_k=8, kmeans_iters=1, seed=0))
    engine = SuCoEngine(x, idx)
    assert engine.mode == "fused"
    got = engine.query(jnp.asarray(ds.queries), k=5)
    want = suco_query(x, idx, jnp.asarray(ds.queries), k=5,
                      alpha=engine.policy.alpha, beta=engine.policy.beta)
    _assert_bitwise_equal(got, want)


def test_engine_tiles_for_is_pure(small):
    ds, x, idx = small
    engine = SuCoEngine(x, idx, EnginePolicy(mode="fused"))
    before = engine.compile_count
    t1 = engine.tiles_for(3, 10)
    t2 = engine.tiles_for(3, 10)
    assert t1 == t2 and isinstance(t1, TileConfig)
    assert engine.compile_count == before  # introspection never compiles
    pinned = TileConfig(block_n=512, survivor_cap=64)
    assert SuCoEngine(
        x, idx, EnginePolicy(mode="fused", tiles=pinned)
    ).tiles_for(3, 10) == pinned
    # dense engines have no fused tiling
    assert SuCoEngine(x, idx, EnginePolicy(mode="dense")).tiles_for(3, 10) is None


def test_engine_pinned_tiles_parity(small):
    ds, x, idx = small
    q = jnp.asarray(ds.queries)
    tiles = TileConfig(block_n=600, survivor_cap=32)
    engine = SuCoEngine(x, idx, EnginePolicy(alpha=0.05, beta=0.02,
                                             mode="fused", tiles=tiles))
    got = engine.query(q, k=10)
    want = suco_query(x, idx, q, k=10, alpha=0.05, beta=0.02,
                      mode="fused", tiles=tiles)
    _assert_bitwise_equal(got, want)


# --------------------------- policy satellites ------------------------------


def test_observe_histogram_is_bounded_and_resettable():
    policy = EnginePolicy()
    cap = EnginePolicy.TRAFFIC_MAX_BINS
    policy.observe(range(1, cap + 1))
    assert len(policy.traffic) == cap
    policy.observe([cap + 7])  # new size at capacity -> evict, not grow
    assert len(policy.traffic) == cap
    assert policy.traffic[cap + 7] == 1
    # the evicted bin is the least-frequent (smallest size on ties): size 1
    assert 1 not in policy.traffic
    # re-observing an existing size never evicts
    policy.observe([cap + 7] * 5)
    assert policy.traffic[cap + 7] == 6 and len(policy.traffic) == cap
    policy.reset_traffic()
    assert not policy.traffic
    with pytest.raises(ValueError, match="batch size"):
        policy.observe([0])


def test_observe_eviction_keeps_hot_bins():
    policy = EnginePolicy()
    cap = EnginePolicy.TRAFFIC_MAX_BINS
    policy.observe([8] * 100)  # hot bin
    policy.observe(range(10, 10 + cap - 1))  # fill to capacity
    assert len(policy.traffic) == cap
    policy.observe([9999])
    assert policy.traffic[8] == 100  # the hot bin survives eviction


# ------------------------------- autotuner ----------------------------------


def test_autotune_tiles_deterministic_and_bounded():
    t1 = autotune_tiles(48_000, 32, 8, 480, n_subspaces=8, n_cells=256)
    t2 = autotune_tiles(48_000, 32, 8, 480, n_subspaces=8, n_cells=256)
    assert t1 == t2  # same shape -> same tiles -> no retrace
    assert t1.block_n % 512 == 0 and 512 <= t1.block_n <= 1 << 16
    assert t1.bm % 8 == 0 and t1.bn % 128 == 0
    assert 1 <= t1.survivor_cap <= max(64, min(480, t1.block_n))


def test_autotune_tiles_scales_with_memory():
    small_mem = autotune_tiles(
        1_000_000, 32, 8, 2000, n_subspaces=8, n_cells=2500,
        limits=MemoryLimits(fast_bytes=1 << 20, hbm_bytes=1 << 34),
    )
    big_mem = autotune_tiles(
        1_000_000, 32, 8, 2000, n_subspaces=8, n_cells=2500,
        limits=MemoryLimits(fast_bytes=1 << 24, hbm_bytes=1 << 34),
    )
    assert big_mem.block_n >= small_mem.block_n
    # block never exceeds the (rounded-up) dataset
    tiny = autotune_tiles(1000, 8, 1, 10, n_subspaces=4, n_cells=64)
    assert tiny.block_n <= 1024
    with pytest.raises(ValueError, match=">= 1"):
        autotune_tiles(0, 8, 1, 10)


@pytest.mark.parametrize("d", [128, 960])
def test_autotune_tiles_tpu_plan_at_one_million(d):
    """The chip's plan for the two online cells (1M points, batch 64, a
    20,000-candidate pool): the score kernel's VMEM model keeps its slot
    term, so the windowed slot write leaves these tiles where they were."""
    assert autotune_tiles(1_000_000, d, 64, 20_000, backend="tpu") == TileConfig(
        block_n=20_480, bm=64, bn=1_024, survivor_cap=3_328
    )


def test_autotune_build_block_n_bounds():
    bn = autotune_build_block_n(100_000, 32, sqrt_k=50, n_subspaces=8)
    assert bn % 512 == 0 and 512 <= bn <= 1 << 16
    small = autotune_build_block_n(700, 32, sqrt_k=50, n_subspaces=8)
    assert small <= 1024
    with pytest.raises(ValueError, match=">= 1"):
        autotune_build_block_n(100, 0, sqrt_k=8)


def test_tileconfig_validation():
    with pytest.raises(ValueError, match="block_n"):
        TileConfig(block_n=0)
    with pytest.raises(ValueError, match="survivor_cap"):
        TileConfig(block_n=512, survivor_cap=0)


# ------------------ gather_rerank op-boundary validation --------------------


def test_gather_rerank_clips_sentinel_ids():
    """Satellite: pools are padded with -1 / INT32_MAX sentinels; the op
    boundary clips them into range once, so the kernel's scalar-prefetch
    index map can never read out of bounds and the jnp path matches."""
    from repro.kernels.gather_rerank.ops import gather_rerank, gather_rerank_block

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(3, 8)).astype(np.float32))
    ids = jnp.asarray(
        np.array([[0, 5, -1, INT_MAX], [31, -1, -1, 2], [7, 7, 40, -5]], np.int32)
    )
    clipped = jnp.clip(ids, 0, 31)
    got = gather_rerank(ids, x, q, interpret=True)
    want = gather_rerank(clipped, x, q, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.isfinite(np.asarray(got)).all()

    got_b = gather_rerank_block(ids, x, q)
    want_b = gather_rerank_block(clipped, x, q)
    np.testing.assert_array_equal(np.asarray(got_b), np.asarray(want_b))


def test_gather_rerank_block_matches_rerank_candidates_distances(small):
    """The in-pass distance op reproduces rerank_candidates' fp reduction
    bit-for-bit (the whole basis of carrying distances through the pool)."""
    from repro.core.sc_linear import rerank_candidates
    from repro.kernels.gather_rerank.ops import gather_rerank_block

    ds, x, idx = small
    q = jnp.asarray(ds.queries)
    rng = np.random.default_rng(1)
    cand = jnp.asarray(
        rng.integers(0, x.shape[0], size=(q.shape[0], 64)), jnp.int32
    )
    for metric in ("l2", "l1"):
        via_op = gather_rerank_block(cand, x, q, metric=metric)
        via_rerank = rerank_candidates(
            x, q, cand, jnp.zeros_like(cand), 64, metric
        ).dists  # k=64 = pool size -> dists of every candidate, reordered
        # compare as sorted rows (rerank_candidates reorders by distance)
        np.testing.assert_array_equal(
            np.sort(np.asarray(via_op), axis=1),
            np.sort(np.asarray(via_rerank), axis=1),
        )


def test_backend_limits_unknown_backend_warns_and_falls_back():
    """Nothing falls back any more: an unrecognised backend name, or a TPU
    generation without a rehearsed memory model, raises instead of tiling
    the device with another device's model."""
    from repro.core import tuning

    with pytest.raises(ValueError, match="unknown backend"):
        tuning.backend_limits("quantum_annealer")
    with pytest.raises(ValueError, match="no memory model"):
        tuning.tpu_limits("TPU v0 imaginary")
    assert tuning.tpu_limits("TPU v5 lite") == tuning.backend_limits("tpu")
    # known backends stay silent
    import warnings as _warnings

    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        for backend in ("cpu", "gpu", "tpu"):
            tuning.backend_limits(backend)


@pytest.mark.parametrize("m", [1, 8, 64, 128])
@pytest.mark.parametrize("n", [1_000_000, 4_000_000])
def test_autotune_tpu_tiles_fit_the_score_kernel_vmem(n, m):
    """TPU plans keep the compact score kernel's VMEM high-water mark
    inside the v5e budget, so the tiles picked at n=1M compile."""
    from repro.core.tuning import backend_limits
    from repro.kernels.sc_score.kernel import cells_vmem_bytes

    t = autotune_tiles(
        n, 128, m, int(0.02 * n), n_subspaces=8, n_cells=2500, backend="tpu"
    )
    fast = backend_limits("tpu").fast_bytes
    assert cells_vmem_bytes(8, t.bm, t.bn, 2500, t.survivor_cap) <= fast, t
    assert t.bm % 8 == 0 and t.bn % 128 == 0 and t.survivor_cap % 64 == 0


def test_measured_backend_limits_probe_caches_and_quantises(
    tmp_path, monkeypatch
):
    """Tentpole: the active backend's limits are measured once, persisted
    as JSON keyed by device kind, quantised (1 GiB hbm / power-of-two-ish
    fast), and bit-stable across cache hits — the jit-static contract."""
    import json

    from repro.core import tuning

    monkeypatch.setenv(tuning._CACHE_DIR_ENV, str(tmp_path))
    tuning._measured_limits.cache_clear()
    try:
        lim = tuning.measured_backend_limits()
        assert lim.fast_bytes >= tuning._FAST_MIN
        assert lim.fast_bytes <= tuning._FAST_MAX
        assert lim.hbm_bytes >= tuning._HBM_QUANTUM
        assert lim.hbm_bytes % tuning._HBM_QUANTUM == 0
        backend = jax.default_backend()
        path = tmp_path / f"limits_{backend}.json"
        assert path.exists()
        rec = json.loads(path.read_text())
        assert rec["fast_bytes"] == lim.fast_bytes
        assert rec["hbm_bytes"] == lim.hbm_bytes
        assert rec["backend"] == backend
        # disk-cache hit after dropping the in-process cache: no re-probe,
        # identical values (the file is trusted, not re-measured)
        rec["fast_bytes"] = tuning._FAST_MIN
        path.write_text(json.dumps(rec))
        tuning._measured_limits.cache_clear()
        assert tuning.measured_backend_limits().fast_bytes == tuning._FAST_MIN
        # corrupt cache: silently re-probed and rewritten.  A re-probe under
        # load may land on a neighbouring knee, so assert the rewritten file
        # matches the re-measured value, not the first probe.
        path.write_text("{not json")
        tuning._measured_limits.cache_clear()
        lim2 = tuning.measured_backend_limits()
        assert lim2.hbm_bytes == lim.hbm_bytes  # allocator ceiling is exact
        assert json.loads(path.read_text())["fast_bytes"] == lim2.fast_bytes
        # refresh=True drops both caches and re-measures
        lim3 = tuning.measured_backend_limits(refresh=True)
        assert tuning._FAST_MIN <= lim3.fast_bytes <= tuning._FAST_MAX
        # the env kill-switch pins the static table
        monkeypatch.setenv(tuning._MEASURE_ENV, "0")
        assert tuning.backend_limits() == tuning._BACKEND_LIMITS[backend]
        # inactive backends always get the static prior, no probe
        other = "tpu" if backend != "tpu" else "gpu"
        assert tuning.measured_backend_limits(other) == tuning._BACKEND_LIMITS[
            other
        ]
        with pytest.raises(ValueError, match="unknown backend"):
            tuning.measured_backend_limits("quantum_annealer")
    finally:
        tuning._measured_limits.cache_clear()  # drop tmp_path-backed entries


def test_backend_limits_measured_feeds_autotune(tmp_path, monkeypatch):
    """autotune_tiles plans against the measured limits (not the static
    prior) and stays deterministic across calls on one host."""
    from repro.core import tuning

    monkeypatch.setenv(tuning._CACHE_DIR_ENV, str(tmp_path))
    tuning._measured_limits.cache_clear()
    try:
        lim = tuning.backend_limits()
        assert lim == tuning.measured_backend_limits()
        t1 = tuning.autotune_tiles(48_000, 32, 8, 480, n_subspaces=8, n_cells=256)
        t2 = tuning.autotune_tiles(48_000, 32, 8, 480, n_subspaces=8, n_cells=256)
        assert t1 == t2
        explicit = tuning.autotune_tiles(
            48_000, 32, 8, 480, n_subspaces=8, n_cells=256, limits=lim
        )
        assert t1 == explicit
    finally:
        tuning._measured_limits.cache_clear()


def test_autotune_survivor_cap_stays_quantised():
    """Regression (found by the jaxlint tile-shape rule): when the cap
    clamps to min(pool, block_n) it must still land on a 64 multiple, or
    the Pallas prefilter kernel loses its lane alignment."""
    for n, d, m, pool in [
        (50_000, 128, 8, 1_000),  # the case that used to yield cap=1000
        (1_000_000, 96, 64, 20_000),
        (32_768, 16, 1, 33),
        (4_096, 8, 2, 100),
    ]:
        t = autotune_tiles(n, d, m, pool, n_subspaces=8, n_cells=256)
        assert t.survivor_cap % 64 == 0, (n, d, m, pool, t)
        assert t.survivor_cap <= max(64, t.block_n)
