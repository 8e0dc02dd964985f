"""SC-Linear (paper Algorithm 1): index-free subspace-collision ANN search.

Linear-scan cost, near-exact recall; the fidelity baseline for SuCo and the
reference semantics for every test in the framework.

Memory note: the naive formulation materialises an ``(Ns, m, n)`` distance
tensor.  We instead ``lax.scan`` over subspaces and keep a single ``(m, n)``
block live — same math, 1/Ns the footprint, and XLA pipelines the blocks.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import subspace
from repro.core.collision import kth_smallest
from repro.core.distances import Metric, pairwise_dist

__all__ = [
    "QueryResult",
    "candidate_pool_size",
    "sc_scores_from_subspaces",
    "sc_linear_query",
    "rerank",
    "rerank_candidates",
    "merge_topk_pool",
    "merge_topk_pool_with_dists",
]


def candidate_pool_size(n: int, k: int, beta: float) -> int:
    """Candidate-pool width for an Alg. 1 re-rank: ``beta * n`` clamped to
    ``[k, n]``.

    The single source of truth for every ``beta * n`` call site (local
    dense/streaming/fused queries, SC-Linear, the sharded engine).  The
    upper clamp matters once ``n`` is a *live* count — after deletions
    ``int(beta * n_total)`` can exceed the survivors, and the lower clamp
    keeps the pool at least ``k`` wide however small ``beta * n`` gets.
    The result is never larger than ``max(k, n)``; callers validate
    ``k <= n`` separately.
    """
    if n < 0:
        raise ValueError(f"n must be >= 0, got {n}")
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    return max(k, min(int(beta * n), n))


class QueryResult(NamedTuple):
    ids: jax.Array  # (..., k) int32 — dataset row ids, ascending distance
    dists: jax.Array  # (..., k) — squared L2 (or L1) distances
    scores: jax.Array  # (..., k) int32 — SC-scores of the returned points


def sc_scores_from_subspaces(
    xs: jax.Array,
    qs: jax.Array,
    count: int,
    metric: Metric = "l2",
) -> jax.Array:
    """``xs: (Ns, n, s), qs: (Ns, m, s) -> (m, n)`` int32 SC-scores.

    Scans over subspaces: per subspace computes the (m, n) distance block,
    derives the per-query collision threshold tau (the ``count``-th smallest
    distance, Definition 1) and accumulates the collision indicator.
    """
    m, n = qs.shape[1], xs.shape[1]

    def body(acc: jax.Array, inp: tuple[jax.Array, jax.Array]):
        x_i, q_i = inp
        d = pairwise_dist(q_i, x_i, metric)  # (m, n)
        tau = kth_smallest(d, count)  # (m,)
        return acc + (d <= tau[:, None]).astype(jnp.int32), None

    init = jnp.zeros((m, n), dtype=jnp.int32)
    scores, _ = jax.lax.scan(body, init, (xs, qs))
    return scores


def rerank_candidates(
    x: jax.Array,
    q: jax.Array,
    cand: jax.Array,
    cand_scores: jax.Array,
    k: int,
    metric: Metric = "l2",
) -> QueryResult:
    """Exact re-rank of an explicit candidate pool (Alg. 1 lines 11-15).

    ``x: (n, d)``, ``q: (m, d)``, ``cand/cand_scores: (m, p)`` — per-query
    candidate row ids and their SC-scores.  Deterministic: distance ties
    resolve to the earlier pool position (``top_k`` tie-break), so two
    callers that present the same pool in the same order get bit-identical
    results.
    """

    def one(qi: jax.Array, cand_i: jax.Array, cs_i: jax.Array) -> QueryResult:
        xc = jnp.take(x, cand_i, axis=0)  # (p, d)
        # impl="rowwise": per-element reduction order is independent of the
        # batch size, so zero-padded serving batches (SuCoEngine buckets)
        # rerank bit-identically to the unpadded computation.
        d = pairwise_dist(qi[None], xc, metric, impl="rowwise")[0]  # (p,)
        # Score < 0 marks a non-candidate slot: pool sentinels and (under
        # live mutation) tombstoned rows.  Real SC-scores are >= 0, so this
        # is a no-op on full immutable pools, and it guarantees a masked
        # slot can never win the distance top_k however close its row is.
        bad = (
            jnp.inf
            if jnp.issubdtype(d.dtype, jnp.floating)
            else jnp.iinfo(d.dtype).max
        )
        d = jnp.where(cs_i < 0, bad, d)
        neg, pos = jax.lax.top_k(-d, k)
        ids = jnp.take(cand_i, pos)
        return QueryResult(ids.astype(jnp.int32), -neg, jnp.take(cs_i, pos))

    return jax.vmap(one)(q, cand, cand_scores)


def rerank(
    x: jax.Array,
    q: jax.Array,
    scores: jax.Array,
    k: int,
    n_candidates: int,
    metric: Metric = "l2",
) -> QueryResult:
    """Paper Alg. 1 lines 11-15: exact re-rank of the top-SC-score pool.

    ``x: (n, d)``, ``q: (m, d)``, ``scores: (m, n)``.
    """
    n = x.shape[0]
    m = max(k, min(n_candidates, n))
    # top_k on int scores breaks ties by lower index — deterministic, and
    # identical to the streaming pool's (score desc, id asc) ordering.
    vals, cand = jax.lax.top_k(scores, m)  # (mq, m)
    return rerank_candidates(x, q, cand, vals, k, metric)


def _counting_sort_block(
    blk_scores: jax.Array, smax: int, p_out: int
) -> jax.Array:
    """Column indices of the top-``p_out`` block entries, (score desc, pos asc).

    The counting select the integer score range admits: scores live in
    ``[-1, smax]`` (sentinel -1, real SC-scores ``0..smax = Ns``), so a
    per-bucket histogram (one ``cumsum`` pass per score level), the
    suffix-cumsum of bucket sizes (the running ``start`` — each bucket's
    first output slot), and a stable compaction (the r-th occurrence of a
    bucket is the first column whose running count reaches r+1 — a binary
    search on the monotone per-bucket cumsum) reproduce a stable
    (score desc, position asc) sort without ``lax.sort`` or any scatter.
    O((smax+2) * bw) histogram work + O((smax+2) * p_out * log bw)
    inversion, versus the O(bw log bw) comparison sort it replaces.
    """
    m, bw = blk_scores.shape
    sv = blk_scores.astype(jnp.int32) + 1  # shift: sentinel -1 -> bucket 0
    u = jnp.arange(p_out, dtype=jnp.int32)
    src = jnp.zeros((m, p_out), jnp.int32)
    start = jnp.zeros((m, 1), jnp.int32)
    for b in range(smax + 1, -1, -1):  # highest bucket fills slots first
        pref = jnp.cumsum((sv == b).astype(jnp.int32), axis=-1)  # (m, bw)
        hist = pref[:, -1:]
        r = u[None, :] - start  # rank within bucket b, if slot u is b's
        in_b = (r >= 0) & (r < hist)
        pos = jax.vmap(lambda c, q: jnp.searchsorted(c, q, side="left"))(
            pref, jnp.clip(r + 1, 1, bw)
        )
        src = jnp.where(in_b, pos.astype(jnp.int32), src)
        start = start + hist
    return src


def _merge_sorted_desc(
    a_s: jax.Array, b_s: jax.Array, p: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Invert the stable merge of two score-descending rows, scatter-free.

    ``a_s: (m, pa)`` and ``b_s: (m, pb)`` are each sorted descending; the
    merged order is (score desc, A before B on ties, original order within
    each).  Every A element's merged position is ``i + #{B > A_i}`` and
    every B element's is ``j + #{A >= B_j}`` — both strictly increasing
    sequences, so the *inverse* map (output slot -> source element) is a
    plain ``searchsorted`` into them.  Returns ``(is_a, i_a, i_b)`` for the
    first ``p`` merged slots: take ``A[i_a]`` where ``is_a`` else ``B[i_b]``.
    """
    t = jnp.arange(p, dtype=jnp.int32)
    na, nb = -a_s, -b_s  # negate: ascending, as searchsorted requires
    right = lambda a, v: jnp.searchsorted(a, v, side="right")
    left = lambda a, v: jnp.searchsorted(a, v, side="left")
    cnt_a = jax.vmap(right)(na, nb)  # per B_j: #A >= B_j (ties -> A first)
    cnt_b = jax.vmap(left)(nb, na)  # per A_i: #B > A_i (strict)
    pos_a = (
        jnp.arange(a_s.shape[1], dtype=jnp.int32)[None, :]
        + cnt_b.astype(jnp.int32)
    )
    pos_b = (
        jnp.arange(b_s.shape[1], dtype=jnp.int32)[None, :]
        + cnt_a.astype(jnp.int32)
    )
    i_a = jax.vmap(left, in_axes=(0, None))(pos_a, t)
    i_b = jax.vmap(left, in_axes=(0, None))(pos_b, t)
    i_a = jnp.minimum(i_a, a_s.shape[1] - 1).astype(jnp.int32)
    i_b = jnp.minimum(i_b, b_s.shape[1] - 1).astype(jnp.int32)
    is_a = jnp.take_along_axis(pos_a, i_a, axis=1) == t[None, :]
    return is_a, i_a, i_b


def _counting_merge(
    pool: tuple[jax.Array, ...], blk: tuple[jax.Array, ...], smax: int
) -> tuple[jax.Array, ...]:
    """Counting-select pool merge: sort the block by counting, then invert
    the sorted-merge.  ``pool[0]``/``blk[0]`` are the scores; the remaining
    arrays (ids, optionally dists) ride through the same gathers."""
    p, bw = pool[0].shape[-1], blk[0].shape[-1]
    # Only the block's top min(p, bw) can survive a p-wide merge.
    src = _counting_sort_block(blk[0], smax, min(p, bw))
    blk_sorted = tuple(jnp.take_along_axis(a, src, axis=1) for a in blk)
    is_a, i_a, i_b = _merge_sorted_desc(pool[0], blk_sorted[0], p)
    return tuple(
        jnp.where(
            is_a,
            jnp.take_along_axis(pa, i_a, axis=1),
            jnp.take_along_axis(ba, i_b, axis=1),
        )
        for pa, ba in zip(pool, blk_sorted)
    )


_MERGE_IMPLS = ("topk", "sort", "counting", "auto")


def _resolve_merge_impl(impl: str, score_dtype, smax: int | None) -> str:
    """``impl="auto"`` picks by platform: the XLA sort on the TPU, where it
    is the fastest merge and the counting select's searchsorted passes
    are slow lane gathers; elsewhere counting exactly when the scores are
    declared integer-ranged (integer dtype + a ``smax`` bound), else
    ``top_k``."""
    if impl not in _MERGE_IMPLS:
        raise ValueError(
            f"impl must be one of {_MERGE_IMPLS}, got {impl!r}"
        )
    integer = jnp.issubdtype(score_dtype, jnp.integer)
    if impl == "auto":
        if jax.default_backend() == "tpu":
            return "sort"
        return "counting" if (smax is not None and integer) else "topk"
    if impl == "counting":
        if smax is None:
            raise ValueError(
                "impl='counting' needs smax (the maximum score, e.g. "
                "n_subspaces for SC-scores)"
            )
        if not integer:
            raise ValueError(
                f"impl='counting' requires integer scores, got {score_dtype}"
            )
    return impl


def merge_topk_pool(
    pool_scores: jax.Array,
    pool_ids: jax.Array,
    blk_scores: jax.Array,
    blk_ids: jax.Array,
    *,
    impl: str = "topk",
    smax: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Merge a score block into a carried top-pool, keeping the pool size.

    ``pool_*: (m, p)``, ``blk_*: (m, b)`` -> ``(m, p)``.  Ordering is
    lexicographic (score desc, id asc) — exactly ``lax.top_k``'s tie-break
    on a dense score row — so a scan of ``merge_topk_pool`` over blocks
    reproduces the dense ``top_k(scores, p)`` selection bit-for-bit.
    Sentinel entries (score -1, id INT32_MAX) sort after every real entry
    (real scores are >= 0) and are expelled as real candidates arrive.

    ``impl="topk"`` (the default) replaces the two-key sort of the
    ``(m, p+b)`` concat with a single ``lax.top_k`` over the scores —
    O((p+b) log k) selection instead of a full O((p+b) log (p+b)) sort
    (see ``benchmarks/micro_merge_pool.py`` for the per-block win).  It is
    bit-compatible with ``impl="sort"`` under the *streaming invariant*
    that every in-repo caller satisfies: blocks arrive in ascending-id
    order (so every real pool id is smaller than every real block id, and
    both segments are id-ascending within equal scores), which makes
    ``top_k``'s position tie-break coincide with the (score desc, id asc)
    order.  Callers merging arbitrarily-ordered blocks must pass
    ``impl="sort"``.

    ``impl="counting"`` exploits the *integer score range*: SC-scores are
    collision counts in ``0..Ns`` (``smax = Ns``; sentinel -1), so the
    block is stably ordered by a per-score-level counting pass
    (:func:`_counting_sort_block`) and merged against the carried pool —
    which every caller holds sorted descending, being this function's own
    output — by inverting the sorted-merge positions with binary searches
    (:func:`_merge_sorted_desc`).  No comparison sort, no ``top_k``, no
    scatter; ~1.4x faster than ``top_k`` at the fused pruned width and
    ~3x at full streaming widths on CPU.  On the TPU its searchsorted
    passes lower to lane gathers and it is far slower than the XLA sort
    (``PERF.md`` section 6).  Bit-compatible with
    ``impl="topk"`` on *any* input whose pool segment is score-descending
    (ties break to the earlier position, exactly ``top_k``'s rule), and
    therefore with ``"sort"`` under the streaming invariant above.
    Requires ``smax`` (scores must lie in ``[-1, smax]`` — out-of-range
    scores are silently dropped) and an integer score dtype.

    ``impl="auto"`` resolves to ``"sort"`` on the TPU, the fastest of
    the four there (``PERF.md`` section 6).  Elsewhere it resolves
    to ``"counting"`` when the scores are declared integer-ranged (integer
    dtype and ``smax`` given), else to ``"topk"``.
    """
    impl = _resolve_merge_impl(impl, pool_scores.dtype, smax)
    p = pool_scores.shape[-1]
    if impl == "counting":
        return _counting_merge(
            (pool_scores, pool_ids), (blk_scores, blk_ids), smax
        )
    s = jnp.concatenate([pool_scores, blk_scores], axis=-1)
    i = jnp.concatenate([pool_ids, blk_ids], axis=-1)
    if impl == "topk":
        vals, pos = jax.lax.top_k(s, p)
        return vals, jnp.take_along_axis(i, pos, axis=-1)
    neg_sorted, ids_sorted = jax.lax.sort((-s, i), num_keys=2)
    return -neg_sorted[..., :p], ids_sorted[..., :p]


def merge_topk_pool_with_dists(
    pool_scores: jax.Array,
    pool_dists: jax.Array,
    pool_ids: jax.Array,
    blk_scores: jax.Array,
    blk_dists: jax.Array,
    blk_ids: jax.Array,
    *,
    impl: str = "topk",
    smax: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`merge_topk_pool` for the fused engine's joint
    ``(sc_score, exact_dist, id)`` pool.

    Selection is identical, per ``impl`` (same knob and semantics as
    :func:`merge_topk_pool`): ``"topk"`` selects with ``lax.top_k`` on the
    scores, whose position tie-break equals the (score desc, id asc) order
    whenever every equal-score run of the concatenated row is already
    id-ascending — true for ascending-id blocks (all block ids exceed all
    pool ids) and equally for a block pre-sorted by (score desc, id asc),
    the fused overflow fallback's shape.  ``"counting"`` is the integer
    counting-select (requires ``smax``); ``"sort"`` the two-key reference
    sort; ``"auto"`` picks sort on the TPU, and elsewhere counting iff the
    scores are integer-ranged.
    The pre-computed exact distances simply ride along through the same
    gather, so the post-scan rerank gather over ``x`` is never needed.
    Sentinel entries carry ``dist = +inf``.
    ``pool_*: (m, p)``, ``blk_*: (m, b)`` -> three ``(m, p)`` arrays.
    """
    impl = _resolve_merge_impl(impl, pool_scores.dtype, smax)
    p = pool_scores.shape[-1]
    if impl == "counting":
        s, i, dd = _counting_merge(
            (pool_scores, pool_ids, pool_dists),
            (blk_scores, blk_ids, blk_dists),
            smax,
        )
        return s, dd, i
    s = jnp.concatenate([pool_scores, blk_scores], axis=-1)
    dd = jnp.concatenate([pool_dists, blk_dists], axis=-1)
    i = jnp.concatenate([pool_ids, blk_ids], axis=-1)
    if impl == "sort":
        neg_sorted, ids_sorted, dd_sorted = jax.lax.sort(
            (-s, i, dd), num_keys=2
        )
        return (
            -neg_sorted[..., :p],
            dd_sorted[..., :p],
            ids_sorted[..., :p],
        )
    vals, pos = jax.lax.top_k(s, p)
    return (
        vals,
        jnp.take_along_axis(dd, pos, axis=-1),
        jnp.take_along_axis(i, pos, axis=-1),
    )


@functools.partial(
    jax.jit, static_argnames=("spec", "k", "alpha", "beta", "metric")
)
def sc_linear_query(
    x: jax.Array,
    q: jax.Array,
    *,
    spec: subspace.SubspaceSpec,
    k: int,
    alpha: float,
    beta: float,
    metric: Metric = "l2",
) -> QueryResult:
    """Algorithm 1 for a batch of queries ``q: (m, d)`` over ``x: (n, d)``."""
    n = x.shape[0]
    xp = subspace.permute(spec, x)
    qp = subspace.permute(spec, q)
    xs = subspace.split_padded(spec, xp)  # (Ns, n, s)
    qs = subspace.split_padded(spec, qp)  # (Ns, m, s)
    c = subspace.collision_count(n, alpha)
    scores = sc_scores_from_subspaces(xs, qs, c, metric)  # (m, n)
    n_candidates = candidate_pool_size(n, k, beta)
    return rerank(x, q, scores, k, n_candidates, metric)


# --------------------------------------------------------------------------
# jaxlint registry hook (see repro.analysis)
# --------------------------------------------------------------------------


def jaxlint_entries():
    """Registry hook: the index-free baseline and the pool-merge scan."""
    from repro.analysis.registry import JaxprEntry

    n, d, m, k = 4_096, 32, 8, 10
    alpha, beta = 0.05, 0.05
    spec = subspace.contiguous_spec(d, 8)
    pool = candidate_pool_size(n, k, beta)

    def make_query():
        S = jax.ShapeDtypeStruct
        return jax.make_jaxpr(
            lambda xx, qq: sc_linear_query(
                xx, qq, spec=spec, k=k, alpha=alpha, beta=beta
            )
        )(S((n, d), jnp.float32), S((m, d), jnp.float32))

    def make_merge_scan(impl: str = "topk", smax: int | None = None):
        mq, p, bn, blocks = 8, 64, 128, 4
        int_max = jnp.iinfo(jnp.int32).max

        def scan_merge(scores, ids):
            init = (
                jnp.full((mq, p), -1, jnp.int32),
                jnp.full((mq, p), int_max, jnp.int32),
            )

            def step(carry, inp):
                return (
                    merge_topk_pool(
                        carry[0], carry[1], *inp, impl=impl, smax=smax
                    ),
                    None,
                )

            return jax.lax.scan(step, init, (scores, ids))[0]

        S = jax.ShapeDtypeStruct
        return jax.make_jaxpr(scan_merge)(
            S((blocks, mq, bn), jnp.int32), S((blocks, mq, bn), jnp.int32)
        )

    def make_merge_with_dists_scan(impl: str = "auto", smax: int | None = 8):
        mq, p, bn, blocks = 8, 64, 128, 4
        int_max = jnp.iinfo(jnp.int32).max

        def scan_merge(scores, dists, ids):
            init = (
                jnp.full((mq, p), -1, jnp.int32),
                jnp.full((mq, p), jnp.inf, jnp.float32),
                jnp.full((mq, p), int_max, jnp.int32),
            )

            def step(carry, inp):
                return (
                    merge_topk_pool_with_dists(
                        *carry, *inp, impl=impl, smax=smax
                    ),
                    None,
                )

            return jax.lax.scan(step, init, (scores, dists, ids))[0]

        S = jax.ShapeDtypeStruct
        return jax.make_jaxpr(scan_merge)(
            S((blocks, mq, bn), jnp.int32),
            S((blocks, mq, bn), jnp.float32),
            S((blocks, mq, bn), jnp.int32),
        )

    return [
        JaxprEntry(
            name="sc_linear.query",
            make=make_query,
            rules=("bounded-intermediate", "pinned-accumulator"),
            # the subspace scan keeps one (m, n) distance block live plus
            # the (Ns, n, s) split views (O(n*d)) and the rerank gather
            budget_bytes=4 * max(2 * m * n, 2 * n * d, m * pool * d),
            note=(
                "Algorithm 1 baseline; its subspace scan sorts (kth_smallest) "
                "by design, so no-scatter-in-scan is intentionally not declared"
            ),
        ),
        JaxprEntry(
            name="sc_linear.merge_pool_scan",
            make=make_merge_scan,
            rules=("no-scatter-in-scan", "pinned-accumulator"),
            note="the carried top-pool merge the streaming engines scan with",
        ),
        JaxprEntry(
            name="sc_linear.merge_pool_counting_scan",
            make=functools.partial(make_merge_scan, impl="counting", smax=8),
            rules=("no-scatter-in-scan", "pinned-accumulator"),
            note=(
                "the counting-select merge (integer score range): per-level "
                "histogram + suffix-cumsum + searchsorted compaction — must "
                "stay sort- and scatter-free inside the scan"
            ),
        ),
        JaxprEntry(
            name="sc_linear.merge_pool_with_dists_scan",
            make=make_merge_with_dists_scan,
            rules=("no-scatter-in-scan", "pinned-accumulator"),
            note=(
                "the fused engine's joint (score, dist, id) pool merge with "
                "impl='auto' resolving to counting — the serving default "
                "off the TPU"
            ),
        ),
    ]
