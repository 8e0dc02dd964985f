"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle, with
shape/dtype sweeps (hypothesis + parametrize)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # network-less env: vendored deterministic shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.kernels.pairwise_l2.ops import pairwise_sqdist
from repro.kernels.pairwise_l2.ref import pairwise_sqdist_ref
from repro.kernels.kmeans_assign.ops import (
    kmeans_assign,
    kmeans_assign_batched,
    kmeans_assign_stats,
)
from repro.kernels.kmeans_assign.ref import (
    kmeans_assign_batched_ref,
    kmeans_assign_ref,
    kmeans_stats_ref,
)
from repro.kernels.gather_rerank.ops import gather_rerank
from repro.kernels.gather_rerank.ref import gather_rerank_ref
from repro.kernels.linear_attn.kernel import linear_attn_kernel
from repro.kernels.linear_attn.ref import linear_attn_ref
from repro.kernels.linear_attn.ops import linear_attention


# --------------------------- pairwise_l2 ------------------------------------


@settings(max_examples=12, deadline=None)
@given(
    m=st.integers(1, 70),
    n=st.integers(1, 200),
    d=st.integers(1, 150),
    seed=st.integers(0, 99),
)
def test_pairwise_l2_shapes(m, n, d, seed):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    got = pairwise_sqdist(q, x, interpret=True)
    want = pairwise_sqdist_ref(q, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_l2_dtypes(dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(33, 64)), dtype)
    x = jnp.asarray(rng.normal(size=(129, 64)), dtype)
    got = pairwise_sqdist(q, x, interpret=True)
    want = pairwise_sqdist_ref(q, x)
    tol = 1e-4 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=tol)
    assert got.dtype == jnp.float32  # fp32 accumulate regardless of input


# --------------------------- kmeans_assign ----------------------------------


@settings(max_examples=12, deadline=None)
@given(
    n=st.integers(1, 300),
    k=st.integers(1, 80),
    s=st.integers(1, 40),
    seed=st.integers(0, 99),
)
def test_kmeans_assign_sweep(n, k, s, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, s)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(k, s)), jnp.float32)
    got = kmeans_assign(x, c, interpret=True)
    want = kmeans_assign_ref(x, c)
    assert (np.asarray(got) == np.asarray(want)).all()


@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(1, 6),
    n=st.integers(1, 200),
    k=st.integers(1, 60),
    s=st.integers(1, 30),
    seed=st.integers(0, 99),
)
def test_kmeans_assign_batched_sweep(b, n, k, s, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, n, s)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(b, k, s)), jnp.float32)
    got = kmeans_assign_batched(x, c, bn=64, impl="pallas", interpret=True)
    want = kmeans_assign_batched_ref(x, c)
    assert (np.asarray(got) == np.asarray(want)).all()


@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(1, 5),
    n=st.integers(1, 200),
    k=st.integers(1, 40),
    s=st.integers(1, 30),
    seed=st.integers(0, 99),
)
def test_kmeans_stats_sweep(b, n, k, s, seed):
    """The fused stats kernel (distance + argmin + partial-sum accumulation)
    must reproduce the dense oracle including n % bn != 0 padding."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, n, s)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(b, k, s)), jnp.float32)
    a, sums, counts, inertia = kmeans_assign_stats(
        x, c, bn=64, impl="pallas", interpret=True
    )
    aw, sw, cw, iw = kmeans_stats_ref(x, c)
    assert (np.asarray(a) == np.asarray(aw)).all()
    np.testing.assert_allclose(np.asarray(sums), np.asarray(sw), rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(counts), np.asarray(cw), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(inertia), np.asarray(iw), rtol=1e-4, atol=1e-3
    )


def test_kmeans_stats_without_assign():
    """The stats-only variant (used by Lloyd iterations) must drop the
    assignment output and keep the statistics bit-identical."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 150, 10)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(3, 12, 10)), jnp.float32)
    a1, s1, c1, i1 = kmeans_assign_stats(x, c, bn=64, impl="pallas", interpret=True)
    a0, s0, c0, i0 = kmeans_assign_stats(
        x, c, bn=64, impl="pallas", with_assign=False, interpret=True
    )
    assert a1 is not None and a0 is None
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c0))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))


@settings(max_examples=10, deadline=None)
@given(
    ns=st.integers(1, 4),
    n=st.integers(1, 200),
    k=st.integers(2, 30),
    s=st.integers(1, 20),
    seed=st.integers(0, 99),
)
def test_kmeans_pair_assign_hist_sweep(ns, n, k, s, seed):
    """Fused pair assignment + IMI histogram: Pallas (interpret) vs oracle.
    Assignments must be bit-identical to the batched kernel and the
    histogram exact (one-hot matmul accumulates small integers in f32)."""
    from repro.kernels.kmeans_assign.ops import kmeans_pair_assign_hist
    from repro.kernels.kmeans_assign.ref import kmeans_pair_assign_hist_ref

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2 * ns, n, s)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(2 * ns, k, s)), jnp.float32)
    a, counts = kmeans_pair_assign_hist(x, c, bn=64, impl="pallas", interpret=True)
    aw, cw = kmeans_pair_assign_hist_ref(x, c)
    assert a.dtype == jnp.int32 and counts.dtype == jnp.int32
    assert (np.asarray(a) == np.asarray(aw)).all()
    assert (np.asarray(counts) == np.asarray(cw)).all()
    assert int(np.asarray(counts).sum()) == ns * n


def test_kmeans_pair_assign_hist_rejects_odd_batch():
    from repro.kernels.kmeans_assign.ops import kmeans_pair_assign_hist

    x = jnp.zeros((3, 16, 4), jnp.float32)
    c = jnp.zeros((3, 5, 4), jnp.float32)
    with pytest.raises(ValueError):
        kmeans_pair_assign_hist(x, c, impl="jnp")


# --------------------------- gather_rerank ----------------------------------


@settings(max_examples=10, deadline=None)
@given(
    mq=st.integers(1, 6),
    mc=st.integers(1, 50),
    n=st.integers(4, 300),
    d=st.integers(1, 100),
    seed=st.integers(0, 99),
)
def test_gather_rerank_sweep(mq, mc, n, d, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(mq, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, n, size=(mq, mc)), jnp.int32)
    got = gather_rerank(ids, x, q, interpret=True)
    want = gather_rerank_ref(ids, x, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)


# ---------------------------- linear_attn -----------------------------------


@pytest.mark.parametrize("shift", [0, 1])
@pytest.mark.parametrize("t,chunk", [(64, 16), (100, 32), (32, 32)])
def test_linear_attn_kernel_vs_scan(shift, t, chunk):
    rng = np.random.default_rng(0)
    bh, dk, dv = 4, 16, 24
    q = rng.normal(size=(bh, t, dk)).astype(np.float32) * 0.3
    k = rng.normal(size=(bh, t, dk)).astype(np.float32) * 0.3
    v = rng.normal(size=(bh, t, dv)).astype(np.float32)
    w = rng.uniform(0.2, 0.9995, size=(bh, t, dk)).astype(np.float32)
    u = rng.normal(size=(bh, 1, dk)).astype(np.float32) * 0.2
    tp = -(-t // chunk) * chunk
    pad = lambda a, cv=0.0: np.pad(a, ((0, 0), (0, tp - t), (0, 0)), constant_values=cv)
    o_k, s_k = linear_attn_kernel(
        jnp.asarray(pad(q)), jnp.asarray(pad(k)), jnp.asarray(pad(v)),
        jnp.asarray(pad(w, 1.0)), jnp.asarray(u),
        chunk=chunk, shift=shift, interpret=True,
    )
    o_r, s_r = linear_attn_ref(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(w),
        jnp.asarray(u), shift=shift,
    )
    np.testing.assert_allclose(np.asarray(o_k)[:, :t], np.asarray(o_r), atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=2e-4, rtol=1e-3)


def test_linear_attn_small_decay_stability():
    """The log-space chunk form must survive decays that overflow the naive
    cumprod-ratio formulation (0.2^64 ~ 1e-45 underflow)."""
    rng = np.random.default_rng(1)
    bh, t, dk, dv = 2, 128, 8, 8
    q = rng.normal(size=(bh, t, dk)).astype(np.float32)
    k = rng.normal(size=(bh, t, dk)).astype(np.float32)
    v = rng.normal(size=(bh, t, dv)).astype(np.float32)
    w = np.full((bh, t, dk), 0.2, np.float32)
    u = np.zeros((bh, 1, dk), np.float32)
    o_k, _ = linear_attn_kernel(
        *(jnp.asarray(a) for a in (q, k, v, w, u)), chunk=64, shift=0, interpret=True
    )
    o_r, _ = linear_attn_ref(*(jnp.asarray(a) for a in (q, k, v, w, u)), shift=0)
    assert np.isfinite(np.asarray(o_k)).all()
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-4, rtol=1e-3)


def test_linear_attention_wrapper_routes_to_ref_on_cpu():
    rng = np.random.default_rng(2)
    b, h, t, d = 2, 3, 20, 8
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = mk(b, h, t, d), mk(b, h, t, d), mk(b, h, t, d)
    w = jnp.asarray(rng.uniform(0.5, 0.99, size=(b, h, t, d)), jnp.float32)
    out = linear_attention(q, k, v, w, mode="gla")
    assert out.shape == (b, h, t, d)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("shift", [0, 1])
def test_linear_attn_chunked_jnp_vs_scan(shift):
    from repro.kernels.linear_attn.ref import linear_attn_chunked_jnp

    rng = np.random.default_rng(3)
    bh, t, dk, dv = 3, 128, 12, 20
    q = jnp.asarray(rng.normal(size=(bh, t, dk)), jnp.float32) * 0.3
    k = jnp.asarray(rng.normal(size=(bh, t, dk)), jnp.float32) * 0.3
    v = jnp.asarray(rng.normal(size=(bh, t, dv)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.2, 0.9995, size=(bh, t, dk)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(bh, 1, dk)), jnp.float32) * 0.2
    o_c, s_c = linear_attn_chunked_jnp(q, k, v, w, u, chunk=32, shift=shift)
    o_r, s_r = linear_attn_ref(q, k, v, w, u, shift=shift)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_r), atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_r), atol=2e-4, rtol=1e-3)


# ---------------------------- sc_score (fused) ------------------------------


@settings(max_examples=10, deadline=None)
@given(
    ns=st.integers(1, 8),
    m=st.integers(1, 20),
    n=st.integers(1, 300),
    s=st.integers(1, 40),
    seed=st.integers(0, 99),
)
def test_sc_score_fused_sweep(ns, m, n, s, seed):
    from repro.kernels.sc_score.ops import sc_scores_fused
    from repro.kernels.sc_score.ref import sc_score_ref

    rng = np.random.default_rng(seed)
    qs = jnp.asarray(rng.normal(size=(ns, m, s)), jnp.float32)
    xs = jnp.asarray(rng.normal(size=(ns, n, s)), jnp.float32)
    # thresholds from actual distance quantiles so masks are non-trivial
    d2 = np.maximum(
        (np.asarray(qs)[:, :, None] - np.asarray(xs)[:, None]) ** 2, 0
    ).sum(-1)
    # nudge thresholds off exact distance values so fp32 reduction-order
    # differences between kernel and oracle cannot flip boundary elements
    tau = jnp.asarray(np.quantile(d2, 0.3, axis=2) + 1e-3, jnp.float32)
    got = sc_scores_fused(qs, xs, tau, interpret=True)
    want = sc_score_ref(qs, xs, tau)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_sc_score_fused_equals_core_pipeline():
    """The fused kernel reproduces sc_scores_from_subspaces exactly."""
    from repro.core import contiguous_spec, collision_count
    from repro.core import subspace as sub
    from repro.core.collision import kth_smallest
    from repro.core.sc_linear import sc_scores_from_subspaces
    from repro.kernels.sc_score.ops import sc_scores_fused

    rng = np.random.default_rng(0)
    n, d, mq = 500, 32, 6
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(mq, d)), jnp.float32)
    spec = contiguous_spec(d, 4)
    xs = sub.split_padded(spec, sub.permute(spec, x))
    qs = sub.split_padded(spec, sub.permute(spec, q))
    c = collision_count(n, 0.05)
    want = sc_scores_from_subspaces(xs, qs, c)
    # thresholds exactly as the core path computes them (same matmul-identity
    # rounding; a direct (x-q)^2 formula flips boundary elements)
    from repro.core.distances import pairwise_dist

    d_sub = jax.vmap(lambda xx, qq: pairwise_dist(qq, xx))(xs, qs)  # (Ns,m,n)
    tau = kth_smallest(d_sub, c)  # (Ns, m)
    got = sc_scores_fused(qs, xs, tau, interpret=True)
    assert (np.asarray(got) == np.asarray(want)).all()


@settings(max_examples=10, deadline=None)
@given(
    ns=st.integers(1, 8),
    m=st.integers(1, 20),
    k_cells=st.integers(4, 400),
    bc=st.integers(1, 700),
    seed=st.integers(0, 99),
)
def test_sc_score_cells_sweep(ns, m, k_cells, bc, seed):
    """Chunked IMI entry point: Pallas (interpret) vs jnp oracle, exact."""
    from repro.kernels.sc_score.ops import sc_scores_cells
    from repro.kernels.sc_score.ref import sc_score_cells_ref

    rng = np.random.default_rng(seed)
    ranks = jnp.asarray(
        np.stack([
            np.stack([rng.permutation(k_cells) for _ in range(m)])
            for _ in range(ns)
        ]),
        jnp.int32,
    )
    cuts = jnp.asarray(rng.integers(-1, k_cells, size=(ns, m)), jnp.int32)
    cells = jnp.asarray(rng.integers(0, k_cells, size=(ns, bc)), jnp.int32)
    got = sc_scores_cells(ranks, cuts, cells, impl="pallas", interpret=True)
    want = sc_score_cells_ref(ranks, cuts, cells)
    assert got.dtype == jnp.int32
    assert (np.asarray(got) == np.asarray(want)).all()


@settings(max_examples=10, deadline=None)
@given(
    ns=st.integers(1, 8),
    m=st.integers(1, 20),
    k_cells=st.integers(4, 400),
    bc=st.integers(1, 700),
    seed=st.integers(0, 99),
)
def test_sc_score_cells_prefilter_sweep(ns, m, k_cells, bc, seed):
    """Fused score+prefilter chunk stage: Pallas (interpret) vs jnp oracle,
    exact — scores identical to the plain entry point, keep mask == the
    score-vs-threshold compare."""
    from repro.kernels.sc_score.ops import sc_scores_cells_prefilter
    from repro.kernels.sc_score.ref import (
        sc_score_cells_prefilter_ref,
        sc_score_cells_ref,
    )

    rng = np.random.default_rng(seed)
    ranks = jnp.asarray(
        np.stack([
            np.stack([rng.permutation(k_cells) for _ in range(m)])
            for _ in range(ns)
        ]),
        jnp.int32,
    )
    cuts = jnp.asarray(rng.integers(-1, k_cells, size=(ns, m)), jnp.int32)
    cells = jnp.asarray(rng.integers(0, k_cells, size=(ns, bc)), jnp.int32)
    thr = jnp.asarray(rng.integers(-1, ns + 1, size=(m,)), jnp.int32)
    got_s, got_k = sc_scores_cells_prefilter(
        ranks, cuts, cells, thr, impl="pallas", interpret=True
    )
    want_s, want_k = sc_score_cells_prefilter_ref(ranks, cuts, cells, thr)
    assert got_s.dtype == jnp.int32 and got_k.dtype == jnp.bool_
    assert (np.asarray(got_s) == np.asarray(want_s)).all()
    assert (np.asarray(got_k) == np.asarray(want_k)).all()
    # the fused stage never perturbs the plain scores
    plain = sc_score_cells_ref(ranks, cuts, cells)
    assert (np.asarray(got_s) == np.asarray(plain)).all()


@settings(max_examples=10, deadline=None)
@given(
    ns=st.integers(1, 8),
    m=st.integers(1, 16),
    k_cells=st.integers(4, 200),
    bc=st.integers(1, 600),
    cap=st.integers(1, 300),
    seed=st.integers(0, 99),
)
def test_sc_score_cells_prefilter_compact_sweep(ns, m, k_cells, bc, cap, seed):
    """Fused score + prune + in-kernel survivor compaction: Pallas
    (interpret) vs jnp oracle, exact — including ragged tails (limit < bc),
    overflow (total > cap, first ``cap`` survivors in ascending column
    order), and the sentinel fill of dead slots."""
    from repro.kernels.sc_score.ops import sc_scores_cells_prefilter_compact
    from repro.kernels.sc_score.ref import sc_score_cells_prefilter_compact_ref

    rng = np.random.default_rng(seed)
    ranks = jnp.asarray(
        np.stack([
            np.stack([rng.permutation(k_cells) for _ in range(m)])
            for _ in range(ns)
        ]),
        jnp.int32,
    )
    cuts = jnp.asarray(rng.integers(-1, k_cells, size=(ns, m)), jnp.int32)
    cells = jnp.asarray(rng.integers(0, k_cells, size=(ns, bc)), jnp.int32)
    thr = jnp.asarray(rng.integers(-1, ns + 1, size=(m,)), jnp.int32)
    limit = jnp.int32(int(rng.integers(0, bc + 1)))
    got = sc_scores_cells_prefilter_compact(
        ranks, cuts, cells, thr, limit, cap=cap, impl="pallas", interpret=True
    )
    want = sc_score_cells_prefilter_compact_ref(
        ranks, cuts, cells, thr, limit, cap=cap
    )
    for g, w in zip(got, want):
        assert g.dtype == jnp.int32
        assert (np.asarray(g) == np.asarray(w)).all()


def test_sc_score_cells_equals_dense_suco_scores():
    """Chunked scoring over blocks reassembles the dense suco_scores matrix."""
    from repro.core import SuCoConfig, build_index, collision_count
    from repro.core.suco import suco_cell_ranks, suco_scores
    from repro.kernels.sc_score.ops import sc_scores_cells
    from repro.data import make_dataset

    ds = make_dataset("gaussian_mixture", 1500, 32, m=5, k=10, seed=4)
    x = jnp.asarray(ds.x)
    q = jnp.asarray(ds.queries)
    idx = build_index(x, SuCoConfig(n_subspaces=4, sqrt_k=12, kmeans_iters=3))
    c = collision_count(1500, 0.05)
    want = suco_scores(idx, q, c)  # (m, n) dense
    ranks, cuts = suco_cell_ranks(idx, q, c)
    bn = 400
    blocks = []
    for start in range(0, 1500, bn):
        cells_b = idx.cell_ids[:, start:start + bn]
        blocks.append(np.asarray(sc_scores_cells(ranks, cuts, cells_b, impl="jnp")))
    got = np.concatenate(blocks, axis=1)
    assert (got == np.asarray(want)).all()


# ------------- TPU formulations vs their oracles: edge cases ----------------


def _ranks_cuts(rng, ns, m, k_cells):
    ranks = np.stack([
        np.stack([rng.permutation(k_cells) for _ in range(m)]) for _ in range(ns)
    ])
    cuts = rng.integers(-1, k_cells, size=(ns, m))
    return jnp.asarray(ranks, jnp.int32), jnp.asarray(cuts, jnp.int32)


def _live_survivors(cols, scores, count, cap):
    """Per query, the (column, score) survivors in slot order, dead slots
    (score < 0) dropped — comparable across the two tombstone foldings."""
    out = []
    for c, s, t in zip(np.asarray(cols), np.asarray(scores), np.asarray(count)):
        n = min(int(t), cap)
        out.append([(int(a), int(b)) for a, b in zip(c[:n], s[:n]) if b >= 0])
    return out


@pytest.mark.parametrize(
    "case, ns, m, k_cells, bc, cap, bm, bn",
    [
        ("ties", 3, 9, 64, 600, 64, 8, 256),
        ("limit_lt_bc", 4, 5, 300, 700, 128, 8, 256),
        ("cap_overflow", 2, 16, 50, 900, 64, 8, 512),
        ("tombstones", 5, 12, 200, 520, 256, 8, 128),
        ("wide_k_many_blocks", 8, 20, 2500, 1300, 256, 8, 256),
    ],
)
def test_sc_score_compact_kernel_edge_cases(case, ns, m, k_cells, bc, cap, bm, bn):
    """The MXU one-hot lookup, the triangular-matmul prefix count and the
    per-row slot writes (interpret mode) against the jnp oracle: equal
    scores everywhere, pruned columns past ``limit``, more survivors than
    slots, deleted columns, K walked in several one-hot slices, and
    column ids past 255 (the slot write splits them into bytes)."""
    from repro.kernels.sc_score.ops import sc_scores_cells_prefilter_compact
    from repro.kernels.sc_score.ref import sc_score_cells_prefilter_compact_ref

    rng = np.random.default_rng(hash(case) % 1000)
    ranks, cuts = _ranks_cuts(rng, ns, m, k_cells)
    cells = rng.integers(0, k_cells, size=(ns, bc))
    thr = rng.integers(-1, ns + 1, size=(m,))
    limit, keep = bc, None
    if case == "ties":  # every column in one cell: all scores tie per query
        cells[:] = 3
        thr[:] = -1
    if case == "limit_lt_bc":
        limit = bc - 37
    if case == "cap_overflow":
        thr[:] = -1
        cuts = jnp.full((ns, m), k_cells - 1, jnp.int32)  # every cell active
    if case == "tombstones":
        keep = jnp.asarray(rng.random(bc) > 0.3)
    args = (ranks, cuts, jnp.asarray(cells, jnp.int32), jnp.asarray(thr, jnp.int32),
            jnp.int32(limit), keep)
    got = sc_scores_cells_prefilter_compact(
        *args, cap=cap, bm=bm, bn=bn, impl="pallas", interpret=True
    )
    want = sc_score_cells_prefilter_compact_ref(*args, cap=cap)
    if case == "cap_overflow":
        assert (np.asarray(want[3]) > cap).all()
    if keep is None:
        for g, w in zip(got, want):
            assert (np.asarray(g) == np.asarray(w)).all()
        return
    # Tombstones: the kernel post-masks dead survivors in their slots (and
    # over-counts them) where the oracle never gives them one.  The scores
    # agree exactly; the kernel's live survivors are a prefix of the
    # oracle's, and all of them wherever the kernel's count fits the slots
    # (past that, the caller's exact overflow fallback takes the chunk).
    assert (np.asarray(got[0]) == np.asarray(want[0])).all()
    assert (np.asarray(got[3]) >= np.asarray(want[3])).all()
    live_got = _live_survivors(got[1], got[2], got[3], cap)
    live_want = _live_survivors(want[1], want[2], want[3], cap)
    for g, w, count in zip(live_got, live_want, np.asarray(got[3])):
        assert g == (w if count <= cap else w[: len(g)])


def _keep_pattern_inputs(keep):
    """Compact-kernel inputs whose survivors are exactly ``keep (m, bc)``:
    one subspace, every column its own cell, a row's cell active where it
    keeps the column, and a pool minimum of 0 — a kept column scores 1."""
    m, bc = keep.shape
    ranks = jnp.asarray(np.where(keep, 0, 1)[None], jnp.int32)  # (1, m, bc)
    cuts = jnp.zeros((1, m), jnp.int32)
    cells = jnp.arange(bc, dtype=jnp.int32)[None]
    thr = jnp.zeros((m,), jnp.int32)
    return ranks, cuts, cells, thr


def _window_recount(keep, bn, cap):
    """``(slot_windows, slot_writes)`` per row, counted block by block in
    numpy: a block that moves a row's count from c0 to c1 writes slots
    [c0, min(c1, cap)), the 128-slot windows they fall in."""
    m, bc = keep.shape
    wins, writes = np.zeros(m, np.int64), np.zeros(m, np.int64)
    for r in range(m):
        c0 = 0
        for b0 in range(0, bc, bn):
            c1 = c0 + int(keep[r, b0:b0 + bn].sum())
            hi = min(c1, cap)
            if hi > c0:
                wins[r] += (hi - 1) // 128 - c0 // 128 + 1
                writes[r] += 1
            c0 = c1
    return wins, writes


def _block_keeps(rng, m, bc, bn, per_block):
    """``(m, bc)`` bool: row r keeps ``per_block[r]`` random columns of
    every ``bn``-wide block (row 0 keeps nothing)."""
    keep = np.zeros((m, bc), bool)
    for r in range(1, m):
        for b0 in range(0, bc, bn):
            width = min(bn, bc - b0)
            take = rng.choice(width, min(per_block[r], width), replace=False)
            keep[r, b0 + take] = True
    return keep


@pytest.mark.parametrize(
    "case, m, bc, cap, bn, per_block",
    [
        # 100 a block: slots 0-99, 100-199, 200-299 cross 128 and 256
        ("straddle", 9, 768, 384, 256, (0, 100, 27, 128, 1, 129, 100, 64, 3)),
        # cap reached mid-block: 200 a block fills 384 slots in block 1
        ("cap_mid_block", 8, 768, 384, 256, (0, 200, 200, 150, 255, 2, 0, 77)),
        # 300 a 512-wide block: a row writes 3 windows at once, then stops
        ("wide_blocks", 8, 1024, 640, 512, (0, 300, 512, 129, 1, 500, 0, 255)),
    ],
)
def test_compact_kernel_window_write_exact(case, m, bc, cap, bn, per_block):
    """The windowed slot write (interpret mode) against the oracle, bit
    for bit, on survivor patterns chosen per row: runs of slots that cross
    128-slot window edges, rows that fill the cap partway through a block,
    more than 128 survivors of one row in one block, and a row that keeps
    nothing.  The kernel's window counters equal a numpy recount."""
    from repro.kernels.sc_score.ops import sc_scores_cells_prefilter_compact
    from repro.kernels.sc_score.ref import sc_score_cells_prefilter_compact_ref

    keep = _block_keeps(np.random.default_rng(len(case)), m, bc, bn, per_block)
    args = (*_keep_pattern_inputs(keep), jnp.int32(bc))
    got = sc_scores_cells_prefilter_compact(
        *args, cap=cap, bm=8, bn=bn, impl="pallas", interpret=True
    )
    want = sc_score_cells_prefilter_compact_ref(*args, cap=cap)
    np.testing.assert_array_equal(np.asarray(want[3]), keep.sum(axis=1))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    wins, writes = _window_recount(keep, bn, cap)
    np.testing.assert_array_equal(np.asarray(got[4]), wins)
    np.testing.assert_array_equal(np.asarray(got[5]), writes)
    assert wins[0] == writes[0] == 0


def test_compact_kernel_keeps_every_column_when_thr_is_minus_one():
    """A cold pool (thr = -1): every valid column survives, hundreds of
    them a block for each row, and the columns past ``limit`` score -1 and
    take no slot."""
    from repro.kernels.sc_score.ops import sc_scores_cells_prefilter_compact
    from repro.kernels.sc_score.ref import sc_score_cells_prefilter_compact_ref

    rng = np.random.default_rng(5)
    ns, m, k_cells, bc, cap, bn, limit = 3, 8, 40, 1024, 768, 512, 1000
    ranks, cuts = _ranks_cuts(rng, ns, m, k_cells)
    cells = jnp.asarray(rng.integers(0, k_cells, size=(ns, bc)), jnp.int32)
    thr = jnp.full((m,), -1, jnp.int32)
    args = (ranks, cuts, cells, thr, jnp.int32(limit))
    got = sc_scores_cells_prefilter_compact(
        *args, cap=cap, bm=8, bn=bn, impl="pallas", interpret=True
    )
    want = sc_score_cells_prefilter_compact_ref(*args, cap=cap)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert (np.asarray(got[3]) == limit).all()
    wins, writes = _window_recount(np.arange(bc)[None] < np.full((m, 1), limit), bn, cap)
    np.testing.assert_array_equal(np.asarray(got[4]), wins)  # 4 + 2 a row
    np.testing.assert_array_equal(np.asarray(got[5]), writes)


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_slot_window_counters_match_numpy_recount(impl):
    """``slot_windows``/``slot_writes`` of both paths (the kernel's own, and
    the oracle path's replay) against a numpy recount from the oracle's
    per-block keeps, on random scores and pool minima over 5 blocks."""
    from repro.kernels.sc_score.ops import sc_scores_cells_prefilter_compact
    from repro.kernels.sc_score.ref import sc_score_cells_prefilter_compact_ref

    rng = np.random.default_rng(11)
    ns, m, k_cells, bc, cap, bn = 4, 12, 90, 1200, 300, 256
    ranks, cuts = _ranks_cuts(rng, ns, m, k_cells)
    cells = jnp.asarray(rng.integers(0, k_cells, size=(ns, bc)), jnp.int32)
    thr = jnp.asarray(rng.integers(-1, 3, size=(m,)), jnp.int32)
    args = (ranks, cuts, cells, thr, jnp.int32(bc - 50))
    got = sc_scores_cells_prefilter_compact(
        *args, cap=cap, bm=8, bn=bn, impl=impl, interpret=True
    )
    scores = np.asarray(sc_score_cells_prefilter_compact_ref(*args, cap=cap)[0])
    keep = scores > np.asarray(thr)[:, None]
    wins, writes = _window_recount(keep, bn, 384)  # cap rounded up to lanes
    np.testing.assert_array_equal(np.asarray(got[4]), wins)
    np.testing.assert_array_equal(np.asarray(got[5]), writes)
    assert (writes > 0).any() and (wins > writes).any()


@pytest.mark.parametrize("op", ["assign", "stats", "pair_hist"])
def test_kmeans_kernels_break_ties_to_the_lowest_centroid(op):
    """Duplicate centroids and points sitting exactly on them: the kernels'
    column-wise argmin resolves ties to the lowest index, as jnp.argmin does
    in the oracles; n is not a block multiple and k not a sublane one."""
    from repro.kernels.kmeans_assign.ops import kmeans_pair_assign_hist
    from repro.kernels.kmeans_assign.ref import kmeans_pair_assign_hist_ref

    rng = np.random.default_rng(7)
    b, n, k, s = 4, 300, 13, 8
    c = rng.normal(size=(b, k, s)).astype(np.float32)
    c[:, 5] = c[:, 2]  # duplicate centroid rows
    c[:, 12] = c[:, 2]
    x = c[:, rng.integers(0, k, size=n)]  # every point on a centroid
    x, c = jnp.asarray(x), jnp.asarray(c)
    if op == "assign":
        got = kmeans_assign_batched(x, c, bn=128, impl="pallas", interpret=True)
        want = kmeans_assign_batched_ref(x, c)
    elif op == "stats":
        got = kmeans_assign_stats(x, c, bn=128, impl="pallas", interpret=True)[0]
        want = kmeans_stats_ref(x, c)[0]
    else:
        got, counts = kmeans_pair_assign_hist(x, c, bn=128, impl="pallas", interpret=True)
        want, counts_w = kmeans_pair_assign_hist_ref(x, c)
        assert (np.asarray(counts) == np.asarray(counts_w)).all()
    assert (np.asarray(got) == np.asarray(want)).all()
    assert not np.isin(np.asarray(got), (5, 12)).any()
