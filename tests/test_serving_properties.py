"""Property-based invariants of the serving stack.

Uses the vendored deterministic hypothesis shim
(:mod:`_hypothesis_fallback`) — seeded examples, reproducible failures —
to pin the three algebraic facts the serving subsystem is built on:

* :func:`repro.core.sc_linear.merge_topk_pool` is **chunking-invariant**
  (any ascending-id block partition reproduces the dense lexicographic
  top-p selection bit-for-bit, under all three impls), **order-invariant**
  under ``impl="sort"`` (arbitrary block arrival order — the contract the
  docstring offers callers outside the streaming invariant), and its
  merged pool is a **fixed point** under sentinel merges (idempotence:
  draining an exhausted stream any number of times changes nothing).
  The **counting-select** impl is additionally pinned **bitwise equal**
  to the ``lax.top_k`` baseline on single merges of lawful pools — ties
  at every score level, all-equal scores, duplicate ids across pool and
  block, non-divisible widths, pools down to ``p=1`` — with and without
  carried distances, and ``impl="auto"`` resolves to it exactly when the
  scores are integer-ranged.  The two-key **sort** impl, what
  ``impl="auto"`` resolves to on the TPU, is pinned bitwise equal to
  ``top_k`` on the two block shapes the fused scan merges: pruned
  (ascending-id survivors, unsorted scores) and ``top_k``-sorted.
* ``batch_bucket`` **padding never changes results**: the rowwise
  distance path is bitwise invariant to zero-padded batch rows, which is
  the exact property that makes a padded engine bucket return the
  unpadded computation's top-k.
* :func:`repro.core.suco.autoscale_buckets` always **covers the observed
  max** batch, respects ``max_buckets``, and never proposes a worse
  bucket set (by expected padding waste) than the trivial single-bucket
  cover.
"""

import numpy as np
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core.distances import pairwise_dist
from repro.core.sc_linear import merge_topk_pool, merge_topk_pool_with_dists
from repro.core.suco import (
    DEFAULT_BATCH_BUCKETS,
    autoscale_buckets,
    batch_bucket,
    padding_waste,
)

INT_MAX = np.iinfo(np.int32).max


def _lex_topk(scores: np.ndarray, ids: np.ndarray, p: int):
    """Reference (score desc, id asc) top-p selection, row by row."""
    out_s, out_i = [], []
    for s_row, i_row in zip(scores, ids):
        order = np.lexsort((i_row, -s_row))[:p]
        out_s.append(s_row[order])
        out_i.append(i_row[order])
    return np.asarray(out_s), np.asarray(out_i)


def _merge_blocks(blocks, p: int, impl: str, smax=None):
    """Fold (scores, ids) blocks into a sentinel-initialised top-p pool."""
    m = blocks[0][0].shape[0]
    pool_s = jnp.full((m, p), -1, jnp.int32)
    pool_i = jnp.full((m, p), INT_MAX, jnp.int32)
    for s, i in blocks:
        pool_s, pool_i = merge_topk_pool(
            pool_s, pool_i, jnp.asarray(s), jnp.asarray(i), impl=impl,
            smax=smax,
        )
    return np.asarray(pool_s), np.asarray(pool_i)


@st.composite
def _score_matrix(draw):
    """(scores (m, n) int32 >= 0, pool size p, a random chunk partition)."""
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    m = draw(st.integers(1, 4))
    n = draw(st.integers(1, 40))
    p = draw(st.integers(1, 12))
    # few distinct score values -> dense ties, the case that breaks naive merges
    scores = rng.integers(0, 4, size=(m, n)).astype(np.int32)
    cuts, at = [], 0
    while at < n:
        step = int(rng.integers(1, n - at + 1))
        cuts.append((at, at + step))
        at += step
    return scores, p, cuts


@given(_score_matrix())
@settings(max_examples=25, deadline=None)
def test_merge_topk_pool_chunking_invariant(case):
    """Any ascending-id chunking == the dense lexicographic selection, for
    both impls, including pools larger than the data (sentinel tail)."""
    scores, p, cuts = case
    m, n = scores.shape
    ids = np.broadcast_to(np.arange(n, dtype=np.int32), (m, n))
    want_s, want_i = _lex_topk(
        np.pad(scores, ((0, 0), (0, p)), constant_values=-1),
        np.pad(ids, ((0, 0), (0, p)), constant_values=INT_MAX),
        p,
    )
    for impl in ("topk", "sort", "counting"):
        got_s, got_i = _merge_blocks(
            [(scores[:, a:b], ids[:, a:b]) for a, b in cuts], p, impl,
            smax=3 if impl == "counting" else None,
        )
        np.testing.assert_array_equal(got_s, want_s, err_msg=f"{impl} scores")
        np.testing.assert_array_equal(got_i, want_i, err_msg=f"{impl} ids")


@given(_score_matrix())
@settings(max_examples=15, deadline=None)
def test_merge_topk_pool_order_invariant_with_sort_impl(case):
    """impl="sort" owes callers arbitrary block order: reversing the block
    arrival order must produce the identical pool."""
    scores, p, cuts = case
    m, n = scores.shape
    ids = np.broadcast_to(np.arange(n, dtype=np.int32), (m, n))
    blocks = [(scores[:, a:b], ids[:, a:b]) for a, b in cuts]
    fwd = _merge_blocks(blocks, p, "sort")
    rev = _merge_blocks(blocks[::-1], p, "sort")
    np.testing.assert_array_equal(fwd[0], rev[0])
    np.testing.assert_array_equal(fwd[1], rev[1])


@given(_score_matrix())
@settings(max_examples=15, deadline=None)
def test_merge_topk_pool_idempotent_on_exhausted_stream(case):
    """A merged pool is a fixed point: merging all-sentinel blocks (an
    exhausted stream) any number of times returns the pool bit-for-bit."""
    scores, p, cuts = case
    m, n = scores.shape
    ids = np.broadcast_to(np.arange(n, dtype=np.int32), (m, n))
    blocks = [(scores[:, a:b], ids[:, a:b]) for a, b in cuts]
    for impl in ("topk", "sort", "counting"):
        smax = 3 if impl == "counting" else None
        pool_s, pool_i = _merge_blocks(blocks, p, impl, smax=smax)
        sent_s = np.full((m, 7), -1, np.int32)
        sent_i = np.full((m, 7), INT_MAX, np.int32)
        again_s, again_i = pool_s, pool_i
        for _ in range(2):
            again_s, again_i = merge_topk_pool(
                jnp.asarray(again_s), jnp.asarray(again_i),
                jnp.asarray(sent_s), jnp.asarray(sent_i), impl=impl,
                smax=smax,
            )
        np.testing.assert_array_equal(np.asarray(again_s), pool_s)
        np.testing.assert_array_equal(np.asarray(again_i), pool_i)


@st.composite
def _sorted_pool_and_block(draw):
    """A lawful carried pool (sorted desc, sentinel tail) plus one incoming
    block — the single-merge shape the counting impl must reproduce
    bit-for-bit against the ``lax.top_k`` baseline.  Three score styles
    stress the tie structure: random over the full 0..smax range (ties at
    every level once smax is small), dense binary ties, and all-equal."""
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    m = draw(st.integers(1, 4))
    p = draw(st.integers(1, 13))  # deliberately non-divisible, down to 1
    bw = draw(st.integers(1, 37))
    smax = draw(st.integers(0, 8))
    style = draw(st.integers(0, 2))  # 0 random, 1 dense ties, 2 all-equal

    def scores(shape):
        if style == 2:
            return np.full(shape, smax, np.int32)
        hi = min(1, smax) if style == 1 else smax
        return rng.integers(0, hi + 1, size=shape).astype(np.int32)

    ps = -np.sort(-scores((m, p)), axis=1)  # pool rows sorted desc
    live = rng.integers(0, p + 1, size=m)  # sentinel tail per row
    dead = np.arange(p)[None, :] >= live[:, None]
    ps = np.where(dead, -1, ps).astype(np.int32)
    # ids are free to duplicate across pool and block: both impls select
    # positionally, so parity must not depend on id uniqueness
    pi = np.where(dead, INT_MAX, rng.integers(0, 50, size=(m, p)))
    pd = np.where(dead, np.inf, rng.normal(size=(m, p))).astype(np.float32)
    bs = scores((m, bw))
    bi = rng.integers(0, 50, size=(m, bw)).astype(np.int32)
    bd = rng.normal(size=(m, bw)).astype(np.float32)
    return ps, pd, pi.astype(np.int32), bs, bd, bi, smax


@given(_sorted_pool_and_block())
@settings(max_examples=30, deadline=None)
def test_counting_merge_bitwise_equals_topk(case):
    """The counting-select merge is a drop-in for the lax.top_k baseline:
    bit-identical pools for every tie structure (ties at every score
    level, all-equal scores, duplicate ids across pool and block), pool
    widths down to p=1, and non-divisible block widths — and
    ``impl="auto"`` resolves to it exactly when the scores are declared
    integer-ranged."""
    ps, _pd, pi, bs, _bd, bi, smax = case
    args = tuple(map(jnp.asarray, (ps, pi, bs, bi)))
    want = merge_topk_pool(*args, impl="topk")
    got = merge_topk_pool(*args, impl="counting", smax=smax)
    auto = merge_topk_pool(*args, impl="auto", smax=smax)
    for g, a, w in zip(got, auto, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(g))


@given(_sorted_pool_and_block())
@settings(max_examples=20, deadline=None)
def test_counting_merge_with_dists_bitwise_equals_topk(case):
    """Same contract for the fused engine's joint (score, dist, id) pool:
    the carried exact distances ride the identical selection."""
    ps, pd, pi, bs, bd, bi, smax = case
    args = tuple(map(jnp.asarray, (ps, pd, pi, bs, bd, bi)))
    want = merge_topk_pool_with_dists(*args, impl="topk")
    got = merge_topk_pool_with_dists(*args, impl="counting", smax=smax)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@st.composite
def _streaming_pool_and_block(draw):
    """A carried pool and one block in a shape the fused scan merges: the
    pool sorted by (score desc, id asc) with unique ids and a sentinel
    tail; the block's ids above every pool id, either as pruned survivor
    slots (ascending ids, unsorted scores, dead slots at the sentinel) or
    sorted by score descending as the overflow fallback's ``lax.top_k``
    returns it.  Ties at every level, all-equal scores, ``p`` down to 1 and
    blocks wider and narrower than the pool come from
    :func:`_sorted_pool_and_block`."""
    ps, pd, pi, bs, bd, _bi, smax = draw(_sorted_pool_and_block())
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    m, p = ps.shape
    bw = bs.shape[1]
    ids = np.sort(
        np.stack([rng.choice(50, size=p, replace=False) for _ in range(m)]),
        axis=1,
    )  # ascending along the score-descending pool: ties are id-ascending
    pi = np.where(ps < 0, INT_MAX, ids).astype(np.int32)
    dead = rng.random((m, bw)) < rng.random()  # up to every slot
    bs = np.where(dead, -1, bs).astype(np.int32)
    bi = np.where(dead, INT_MAX, 50 + np.arange(bw)[None, :]).astype(np.int32)
    bd = np.where(dead, np.inf, bd).astype(np.float32)
    if draw(st.booleans()):  # the fallback's top_k-sorted block
        order = np.argsort(-bs, axis=1, kind="stable")
        bs, bi, bd = (np.take_along_axis(a, order, 1) for a in (bs, bi, bd))
    return ps, pd, pi, bs, bd, bi


@given(_streaming_pool_and_block())
@settings(max_examples=30, deadline=None)
def test_sort_merge_bitwise_equals_topk(case):
    """The two-key sort is a drop-in for the lax.top_k baseline on every
    block the fused scan feeds it — ties at every level, all-equal scores,
    sentinel-heavy pools and blocks, ``p`` not a power of two down to 1,
    blocks wider and narrower than the pool."""
    ps, _pd, pi, bs, _bd, bi = case
    args = tuple(map(jnp.asarray, (ps, pi, bs, bi)))
    want = merge_topk_pool(*args, impl="topk")
    got = merge_topk_pool(*args, impl="sort")
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@given(_streaming_pool_and_block())
@settings(max_examples=30, deadline=None)
def test_sort_merge_with_dists_bitwise_equals_topk(case):
    """Same contract for the joint (score, dist, id) pool: the distances
    ride the sort as a third operand and come out bit-identical."""
    args = tuple(map(jnp.asarray, case))
    want = merge_topk_pool_with_dists(*args, impl="topk")
    got = merge_topk_pool_with_dists(*args, impl="sort")
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@st.composite
def _padded_batch(draw):
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    m = draw(st.integers(1, 9))
    n = draw(st.integers(2, 24))
    d = draw(st.integers(2, 16))
    q = rng.normal(size=(m, d)).astype(np.float32)
    x = rng.normal(size=(n, d)).astype(np.float32)
    return q, x, m


@given(_padded_batch())
@settings(max_examples=15, deadline=None)
def test_bucket_padding_never_changes_rowwise_distances(case):
    """The serving-path distance impl is bitwise invariant to the zero rows
    :func:`batch_bucket` padding appends — the property that makes padded
    engine buckets answer exactly like the unpadded batch (and therefore
    padding can never change a top-k result)."""
    q, x, m = case
    b = batch_bucket(m)
    assert b >= m
    q_pad = np.zeros((b, q.shape[1]), np.float32)
    q_pad[:m] = q
    for metric in ("l2", "l1"):
        want = np.asarray(pairwise_dist(jnp.asarray(q), jnp.asarray(x), metric, impl="rowwise"))
        got = np.asarray(pairwise_dist(jnp.asarray(q_pad), jnp.asarray(x), metric, impl="rowwise"))
        np.testing.assert_array_equal(got[:m], want, err_msg=metric)


@st.composite
def _histogram(draw):
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    n_sizes = draw(st.integers(1, 12))
    max_buckets = draw(st.integers(1, 8))
    sizes = rng.integers(1, 200, size=n_sizes)
    return {int(s): int(rng.integers(1, 50)) for s in sizes}, max_buckets


@given(_histogram())
@settings(max_examples=40)
def test_autoscale_buckets_covers_observed_max(case):
    hist, max_buckets = case
    buckets = autoscale_buckets(hist, max_buckets)
    assert len(buckets) <= max_buckets
    assert max(buckets) >= max(hist), (buckets, hist)
    assert buckets == tuple(sorted(buckets))
    # every observed size lands in a configured bucket, never the
    # power-of-two overflow rule
    for msize in hist:
        assert batch_bucket(msize, buckets) in buckets
    # never worse than the trivial single-bucket cover, and exact when the
    # budget covers every distinct size
    waste = padding_waste(hist, buckets)
    assert waste <= padding_waste(hist, (max(hist),))
    if max_buckets >= len(hist):
        assert waste == 0, (buckets, hist)


def test_autoscale_buckets_empty_histogram_is_fallback():
    assert autoscale_buckets({}, 4) == tuple(sorted(DEFAULT_BATCH_BUCKETS))
    assert autoscale_buckets({}, 4, fallback=(4, 16)) == (4, 16)
