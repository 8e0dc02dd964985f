"""Batched K-means in JAX (the paper's Algorithm 2 building block).

SuCo runs ``2 * Ns`` small K-means problems (two half-subspaces per
subspace), each with only ``sqrt(K)`` centroids (~50).  All codebooks are
trained in one batched XLA program — the TPU analogue of the paper's "one
OpenMP task per subspace" parallelism.

Index-build memory model (three execution paths, one reference semantics):

* **dense** (``block_n=0``, the reference) — full-batch Lloyd; every
  iteration materialises the ``(B, n, k)`` distance matrix and a
  ``(B, n, k)`` one-hot update.  Fastest for small n (one fused einsum),
  but the one-hot alone is ``k`` times the dataset and caps dataset size.
* **chunked** (``block_n>0``, ``algo="lloyd"``) — the same Lloyd update
  as a blocked ``lax.scan`` over data chunks of ``block_n`` points that
  carries per-centroid ``(sums, counts, inertia)`` accumulators: nothing
  of size ``(n, k)`` is ever live, peak per-iteration memory is
  O(B * block_n * max(k, s)).  Centroids agree with dense up to fp
  summation order; over multiple iterations that noise can flip the
  assignment of points sitting exactly on Voronoi boundaries (exact
  parity on separated data, <0.1% flips otherwise).  On TPU the whole
  per-iteration pass runs
  through the fused Pallas :func:`~repro.kernels.kmeans_assign.ops.
  kmeans_assign_stats` kernel (distance + argmin + partial-sum
  accumulation in VMEM); on CPU the jnp ``lax.scan`` is the oracle path.
* **minibatch** (``algo="minibatch"``) — opt-in web-scale mode: each step
  assigns one *sampled* chunk of ``block_n`` points and moves centroids
  with per-centroid learning rates ``counts_step / counts_total``
  (Sculley-style mini-batch K-means, aggregated form).  O(iters * block_n)
  assignment work instead of O(iters * n) — the right trade for
  million-point builds where full Lloyd epochs are wasteful.  Approximate:
  centroids converge near, not to, the Lloyd fixed point.

The final assignment pass respects ``impl`` ("auto" routes to the fused
Pallas ``kmeans_assign`` kernels on TPU, pure jnp elsewhere) and is
chunked whenever ``block_n>0``.

Every path reads the points in one layout, ``xt (B, s, n_pad)``: points on
the lanes, the first ``n`` columns real and the rest zero — what the
kernels stream.  :func:`kmeans_batched_t` takes it as given (the index
build writes it once, padded to :func:`lane_points`, so no pass copies,
transposes or re-pads it); :func:`kmeans_batched` and :func:`kmeans`
transpose their ``(B, n, s)`` / ``(n, s)`` input into it once.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.distances import pairwise_sqdist
from repro.spans import scope

__all__ = [
    "KMeansResult",
    "kmeans",
    "kmeans_batched",
    "kmeans_batched_t",
    "lane_points",
    "assign",
    "block_batched",
    "lloyd_stats_scan",
    "assign_scan",
    "init_centroids_pp",
]

_ALGOS = ("lloyd", "minibatch")
_INITS = ("auto", "random", "kmeans++")
_MINIBATCH_DEFAULT_BLOCK = 4096
# kmeans++ seeds from a uniform sample of this many points (capped at n):
# enough for D^2 sampling to separate the modes, independent of dataset size.
_PP_SAMPLE_PER_K = 32
_PP_SAMPLE_MIN = 2048


class KMeansResult(NamedTuple):
    centroids: jax.Array  # (k, s) — or (B, k, s) batched
    assignments: jax.Array  # (n,) int32 — or (B, n) batched
    inertia: jax.Array  # () — or (B,); sum of squared distances to the
    # owning centroid.  Lloyd paths report the last update step's inertia
    # (dense-reference semantics); minibatch reports the final full-data
    # inertia from the assignment pass.
    cell_counts: jax.Array | None = None  # (B//2, pair_sqrt_k**2) int32 when
    # requested via ``pair_sqrt_k`` (SuCo IMI occupancy fused into the final
    # assignment scan); None otherwise.


def assign(x: jax.Array, centroids: jax.Array, *, impl: str = "auto") -> jax.Array:
    """``argmin_c ||x - centroid_c||^2`` for every row of ``x``."""
    if _use_pallas(impl):
        from repro.kernels.kmeans_assign import ops as _ops

        return _ops.kmeans_assign(x, centroids)
    d2 = pairwise_sqdist(x, centroids, impl="jnp")  # (n, k)
    return jnp.argmin(d2, axis=1).astype(jnp.int32)


def _use_pallas(impl: str) -> bool:
    if impl == "pallas":
        return True
    if impl == "jnp":
        return False
    if impl != "auto":
        raise ValueError(f"impl must be 'auto'|'jnp'|'pallas', got {impl!r}")
    return jax.default_backend() == "tpu"


def _init_centroids(key: jax.Array, x: jax.Array, k: int) -> jax.Array:
    """Random distinct-row init (the paper uses plain Lloyd; kmeans++ is
    unnecessary at sqrt(K)=50 granularity and costs an extra O(nk) pass)."""
    n = x.shape[0]
    idx = jax.random.permutation(key, n)[:k]
    return jnp.take(x, idx, axis=0)


def init_centroids_pp(
    key: jax.Array, x: jax.Array, k: int, *, sample_n: int = 0
) -> jax.Array:
    """kmeans++-style D^2 seeding (Arthur & Vassilvitskii) over a sample.

    ``sample_n > 0`` seeds from that many uniformly sampled rows instead of
    all of ``x`` — the streaming-friendly form: minibatch never touches the
    full dataset before its final assignment pass, and the seeding keeps
    that property.  O(sample_n * k) work; deterministic given ``key``.
    """
    return _pp_lanes(key, x.T, x.shape[0], k, sample_n)


def _pp_lanes(key: jax.Array, xt: jax.Array, n: int, k: int, sample_n: int) -> jax.Array:
    """:func:`init_centroids_pp` over points on lanes, ``xt (s, >= n)``:
    the same draws, so the same seeds."""
    k_sub, k_first, k_pick = jax.random.split(key, 3)
    if 0 < sample_n < n:
        idx = jax.random.permutation(k_sub, n)[:sample_n]
        xs = jnp.take(xt, idx, axis=1).T
    else:
        xs = xt[:, :n].T
    xf = xs.astype(jnp.float32)
    c0 = xf[jax.random.randint(k_first, (), 0, xs.shape[0])]
    cents = jnp.zeros((k, xs.shape[1]), jnp.float32).at[0].set(c0)
    d2 = jnp.sum((xf - c0) ** 2, axis=-1)

    def body(carry, inp):
        cents, d2 = carry
        i, kt = inp
        # Sample the next seed with prob ∝ D^2; all-zero D^2 (every sampled
        # row already a centroid, duplicate-heavy data) falls back to uniform.
        logits = jnp.log(jnp.maximum(d2, jnp.finfo(jnp.float32).tiny))
        logits = jnp.where(jnp.sum(d2) > 0, logits, jnp.zeros_like(d2))
        c = xf[jax.random.categorical(kt, logits)]
        cents = cents.at[i].set(c)
        d2 = jnp.minimum(d2, jnp.sum((xf - c) ** 2, axis=-1))
        return (cents, d2), None

    (cents, _), _ = jax.lax.scan(
        body,
        (cents, d2),
        (jnp.arange(1, k), jax.random.split(k_pick, k - 1)),
    )
    return cents.astype(xt.dtype)


def _lane_columns(xt: jax.Array, cols: jax.Array) -> jax.Array:
    """``xt (B, s, N), cols (B, k) -> (B, k, s)``: point ``cols[j, t]`` of
    codebook ``j``.  The lane-wide ``(s, 128)`` tile holding each point is
    sliced out of ``xt``, and the point's column is picked from those
    small tiles: a gather, or a one-lane slice, along the lane axis of
    ``xt`` itself would have the compiler lay the whole array out again
    for it (points major, widths padded to 128 lanes)."""
    b, s, n_cols = xt.shape
    k = cols.shape[1]
    w = min(128, n_cols)
    flat = cols.reshape(-1)
    books = jnp.repeat(jnp.arange(b, dtype=flat.dtype), k)
    starts = jnp.minimum(flat - flat % w, n_cols - w)
    tiles = jax.lax.map(
        lambda js: jax.lax.dynamic_slice(xt, (js[0], 0, js[1]), (1, s, w))[0],
        (books, starts),
    )  # (B*k, s, w)
    picked = jnp.take_along_axis(tiles, (flat - starts)[:, None, None], axis=2)
    return picked.reshape(b, k, s)


def _init_batched(
    key: jax.Array, xs: jax.Array, k: int, init: str, algo: str
) -> jax.Array:
    """``(B, n, s) -> (B, k, s)`` initial centroids for every problem
    (:func:`_init_lanes` on the transposed points)."""
    return _init_lanes(key, jnp.swapaxes(xs, 1, 2), xs.shape[1], k, init, algo)


def _init_lanes(
    key: jax.Array, xt: jax.Array, n: int, k: int, init: str, algo: str
) -> jax.Array:
    """``xt (B, s, >= n) -> (B, k, s)`` initial centroids for every problem,
    reading only the ``k`` (or kmeans++'s sampled) point columns: codebook
    ``j`` draws with key ``split(key, B)[j]``, and random init takes the
    first ``k`` points of ``jax.random.permutation`` of ``n``.

    ``init="auto"`` resolves to kmeans++ for minibatch (whose few sampled
    steps cannot recover from a bad random seed the way full Lloyd epochs
    can) and random for lloyd (the paper's choice)."""
    mode = init
    if mode == "auto":
        mode = "kmeans++" if algo == "minibatch" else "random"
    keys = jax.random.split(key, xt.shape[0])
    if mode == "random":
        rows = jax.vmap(lambda kk: jax.random.permutation(kk, n)[:k])(keys)
        return _lane_columns(xt, rows)
    sample_n = min(n, max(_PP_SAMPLE_PER_K * k, _PP_SAMPLE_MIN))
    return jax.vmap(lambda kk, xb: _pp_lanes(kk, xb, n, k, sample_n))(keys, xt)


# --------------------------------------------------------------------------
# Dense reference step (the semantics every streaming path must match)
# --------------------------------------------------------------------------


def _lloyd_step(x: jax.Array, centroids: jax.Array) -> tuple[jax.Array, jax.Array]:
    k = centroids.shape[0]
    d2 = pairwise_sqdist(x, centroids, impl="jnp")  # (n, k)
    a = jnp.argmin(d2, axis=1)
    one_hot = jax.nn.one_hot(a, k, dtype=x.dtype)  # (n, k)
    sums = jnp.einsum("nk,ns->ks", one_hot, x)
    counts = jnp.sum(one_hot, axis=0)  # (k,)
    new = sums / jnp.maximum(counts, 1.0)[:, None]
    # Empty cluster: keep the previous centroid (matches common practice and
    # keeps the update a fixed-shape op).
    new = jnp.where(counts[:, None] > 0, new, centroids)
    inertia = jnp.sum(jnp.take_along_axis(d2, a[:, None], axis=1))
    return new, inertia


# --------------------------------------------------------------------------
# Chunked streaming statistics (shared with the distributed engine)
# --------------------------------------------------------------------------


def block_batched(
    xs: jax.Array, block_n: int
) -> tuple[jax.Array, jax.Array]:
    """``(B, n, s) -> (blocks (nb, B, bn, s), valid (nb, bn) bool)``.

    Zero-pads n up to a multiple of ``bn = min(block_n, n)`` and exposes
    the data as scan-ready chunks; ``valid`` masks the padded tail.
    """
    b, n, s = xs.shape
    bn = max(1, min(block_n, n))
    nb = -(-n // bn)
    xp = jnp.pad(xs, ((0, 0), (0, nb * bn - n), (0, 0)))
    blocks = xp.reshape(b, nb, bn, s).transpose(1, 0, 2, 3)
    valid = (jnp.arange(nb * bn) < n).reshape(nb, bn)
    return blocks, valid


def lloyd_stats_scan(
    blocks: jax.Array,
    valid: jax.Array,
    centroids: jax.Array,
    *,
    cast_init: Callable[[tuple], tuple] = lambda t: t,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One Lloyd assignment pass as a blocked scan with carried accumulators.

    ``blocks: (nb, B, bn, s)``, ``valid: (nb, bn)``, ``centroids: (B, k, s)``
    -> ``(sums (B, k, s) f32, counts (B, k) f32, inertia (B,) f32)``.

    Per chunk only a ``(B, bn, k)`` distance tile and a ``(B, bn, k)``
    weighted one-hot are live — the O(n * k) full-batch intermediates never
    exist.  ``cast_init`` lets shard_map callers mark the zero carries as
    device-varying (VMA) before the scan.
    """
    _, b, _, s = blocks.shape
    k = centroids.shape[1]
    cf = centroids.astype(jnp.float32)

    def body(carry, inp):
        sums, counts, inertia = carry
        xb, vb = inp  # (B, bn, s), (bn,)
        xf = xb.astype(jnp.float32)
        d2 = jax.vmap(lambda xx, cc: pairwise_sqdist(xx, cc, impl="jnp"))(xf, cf)
        a = jnp.argmin(d2, axis=-1)  # (B, bn)
        w = vb.astype(jnp.float32)  # (bn,)
        oh = jax.nn.one_hot(a, k, dtype=jnp.float32) * w[None, :, None]
        sums = sums + jnp.einsum("bnk,bns->bks", oh, xf)
        counts = counts + jnp.sum(oh, axis=1)
        inertia = inertia + jnp.sum(jnp.min(d2, axis=-1) * w[None, :], axis=1)
        return (sums, counts, inertia), None

    init = cast_init(
        (
            jnp.zeros((b, k, s), jnp.float32),
            jnp.zeros((b, k), jnp.float32),
            jnp.zeros((b,), jnp.float32),
        )
    )
    (sums, counts, inertia), _ = jax.lax.scan(body, init, (blocks, valid))
    return sums, counts, inertia


def assign_scan(
    blocks: jax.Array,
    valid: jax.Array,
    centroids: jax.Array,
    *,
    cast_init: Callable = lambda t: t,
    pair_sqrt_k: int = 0,
) -> tuple[jax.Array, jax.Array, jax.Array | None]:
    """Chunked final assignment:
    ``-> (assign (B, nb*bn) int32, inertia (B,), cell_counts | None)``.

    Assignments for padded rows are junk — the caller slices ``[:, :n]``;
    the inertia accumulator masks them out.

    ``pair_sqrt_k > 0`` treats the batch as SuCo's paired half-subspace
    layout — rows ``[:B//2]`` are first halves, ``[B//2:]`` second halves
    of the same subspaces — and additionally accumulates the IMI cell
    occupancy ``bincount(a1 * pair_sqrt_k + a2)`` per chunk into a carried
    ``(B//2, pair_sqrt_k**2) int32`` accumulator: the histogram that used
    to be a second full pass over ``cell_ids`` rides the assignment scan
    for free.
    """
    _, b, _, _ = blocks.shape
    cf = centroids.astype(jnp.float32)
    if pair_sqrt_k and b % 2:
        raise ValueError(f"pair_sqrt_k needs an even batch, got B={b}")
    ns = b // 2

    def body(carry, inp):
        inertia, counts = carry
        xb, vb = inp
        d2 = jax.vmap(lambda xx, cc: pairwise_sqdist(xx, cc, impl="jnp"))(
            xb.astype(jnp.float32), cf
        )
        a = jnp.argmin(d2, axis=-1).astype(jnp.int32)  # (B, bn)
        w = vb.astype(jnp.float32)
        inertia = inertia + jnp.sum(jnp.min(d2, axis=-1) * w[None, :], axis=1)
        if pair_sqrt_k:
            cells = a[:ns] * pair_sqrt_k + a[ns:]  # (ns, bn)
            rows = jnp.arange(ns, dtype=jnp.int32)[:, None]
            wb = jnp.broadcast_to(vb.astype(jnp.int32), cells.shape)
            counts = counts.at[rows, cells].add(wb)
        return (inertia, counts), a

    counts0 = (
        jnp.zeros((ns, pair_sqrt_k * pair_sqrt_k), jnp.int32)
        if pair_sqrt_k
        else jnp.zeros((), jnp.int32)
    )
    init = cast_init((jnp.zeros((b,), jnp.float32), counts0))
    (inertia, counts), a_blocks = jax.lax.scan(body, init, (blocks, valid))
    a = a_blocks.transpose(1, 0, 2).reshape(b, -1)  # (B, nb*bn)
    return a, inertia, counts if pair_sqrt_k else None


def _lane_blocks(xt: jax.Array, n: int, bn: int) -> tuple[jax.Array, jax.Array]:
    """``xt (B, s, nb*bn)`` points on lanes -> :func:`block_batched`'s
    ``(blocks (nb, B, bn, s), valid (nb, bn))``: the jnp scans' chunks."""
    b, s, n_pad = xt.shape
    nb = n_pad // bn
    blocks = xt.reshape(b, s, nb, bn).transpose(2, 0, 3, 1)
    valid = (jnp.arange(n_pad) < n).reshape(nb, bn)
    return blocks, valid


def _stats_chunk(
    xb: jax.Array, centroids: jax.Array, *, impl: str
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One batched Lloyd statistics pass over a chunk on lanes, ``xb (B,
    s, bn)``: fused Pallas kernel on TPU, blocked jnp scan elsewhere."""
    bn = xb.shape[2]
    if _use_pallas(impl):
        from repro.kernels.kmeans_assign import ops as _ops

        # impl="pallas": the dispatch decision is already made — the
        # kernel runs (or fails loudly) instead of silently falling back
        # to the dense jnp oracle.  with_assign=False: the update consumes
        # only the statistics, and an unused pallas output cannot be DCE'd.
        _, sums, counts, inertia = _ops.kmeans_assign_stats_t(
            xb, centroids, n=bn, bn=bn, with_assign=False
        )
        return sums, counts, inertia
    blocks, valid = _lane_blocks(xb, bn, bn)
    return lloyd_stats_scan(blocks, valid, centroids)


def _point_plan(
    n: int, b: int, s: int, k: int, algo: str, block_n: int, pallas: bool
) -> tuple[int, int]:
    """``(bn, n_pad)``: the chunk every full-data pass streams, and the
    point columns of the layout those passes read without a copy."""
    if algo == "minibatch":
        bn = max(1, min(block_n or _MINIBATCH_DEFAULT_BLOCK, n))
    elif pallas:
        bn = block_n or 1024
    else:
        bn = max(1, min(block_n, n)) if block_n else n
    if pallas:
        from repro.kernels.kmeans_assign import ops as _ops

        bn_kernel = _ops.lane_block(b, s, k, n, bn)
        return bn, -(-n // bn_kernel) * bn_kernel
    return bn, -(-n // bn) * bn


def lane_points(
    n: int,
    b: int,
    s: int,
    k: int,
    *,
    algo: str = "lloyd",
    block_n: int = 0,
    impl: str = "auto",
) -> int:
    """Point columns :func:`kmeans_batched_t` streams without a copy, for
    ``b`` codebooks of ``k`` centroids over ``s``-wide subspaces of ``n``
    points: lay the points out with this many (zero-padded) columns and no
    pass re-pads them."""
    return _point_plan(n, b, s, k, algo, block_n, _use_pallas(impl))[1]


def _fit_lanes(xt: jax.Array, n_pad: int) -> jax.Array:
    """``xt`` cut or zero-padded to ``n_pad`` point columns (no-op when the
    caller laid it out with :func:`lane_points` columns)."""
    have = xt.shape[2]
    if have > n_pad:
        return xt[:, :, :n_pad]
    if have < n_pad:
        return jnp.pad(xt, ((0, 0), (0, 0), (0, n_pad - have)))
    return xt


# --------------------------------------------------------------------------
# Training loops
# --------------------------------------------------------------------------


def _kmeans_core(
    key: jax.Array,
    xt: jax.Array,  # (B, s, >= n) points on lanes, zero columns from n on
    n: int,
    c0: jax.Array,  # (B, k, s)
    iters: int,
    algo: str,
    block_n: int,
    impl: str,
    pair_sqrt_k: int = 0,
) -> KMeansResult:
    b, s, _ = xt.shape
    k = c0.shape[1]
    pallas = _use_pallas(impl)
    bn, n_pad = _point_plan(n, b, s, k, algo, block_n, pallas)
    xt = _fit_lanes(xt, n_pad)

    if algo == "minibatch":

        def mb_body(carry, t):
            c, cnts = carry
            kt = jax.random.fold_in(key, t)
            idx = jax.random.randint(kt, (bn,), 0, n)
            xb = jnp.take(xt, idx, axis=2)  # (B, s, bn) — shared sample
            sums, counts, _ = _stats_chunk(xb, c, impl=impl)
            cnts = cnts + counts
            # Aggregated Sculley update: per-centroid learning rate
            # counts / cnts, i.e. c <- c + (batch_sum - batch_count*c)/cnts.
            delta = (sums - counts[..., None] * c.astype(jnp.float32)) / jnp.maximum(
                cnts, 1.0
            )[..., None]
            return (
                (c.astype(jnp.float32) + delta).astype(c.dtype),
                cnts,
            ), None

        (c_fin, _), _ = jax.lax.scan(
            mb_body,
            (c0, jnp.zeros((b, k), jnp.float32)),
            jnp.arange(iters, dtype=jnp.int32),
        )
        a, inertia, counts = _final_assign(xt, n, c_fin, bn=bn, pallas=pallas,
                                           need_inertia=True,
                                           pair_sqrt_k=pair_sqrt_k)
        return KMeansResult(c_fin, a, inertia, counts)

    # algo == "lloyd"
    chunked = block_n > 0

    def lloyd_body(c, _):
        if not chunked:
            new, inertia = jax.vmap(_lloyd_step)(xs, c)
            return new, inertia
        if pallas:
            from repro.kernels.kmeans_assign import ops as _ops

            _, sums, counts, inertia = _ops.kmeans_assign_stats_t(
                xt, c, n=n, bn=bn, with_assign=False
            )
        else:
            sums, counts, inertia = lloyd_stats_scan(blocks, valid, c)
        new = sums / jnp.maximum(counts, 1.0)[..., None]
        new = jnp.where(counts[..., None] > 0, new, c.astype(jnp.float32))
        return new.astype(c.dtype), inertia

    with scope("kmeans.lloyd"):
        if not chunked:  # the dense reference steps on (B, n, s) rows
            xs = jnp.swapaxes(xt[:, :, :n], 1, 2)
        elif not pallas:
            blocks, valid = _lane_blocks(xt, n, bn)
        centroids, inertias = jax.lax.scan(lloyd_body, c0, None, length=iters)
    with scope("kmeans.assign"):
        a, _, counts = _final_assign(
            xt, n, centroids, bn=bn, pallas=pallas,
            need_inertia=False, pair_sqrt_k=pair_sqrt_k,
        )
    return KMeansResult(centroids, a, inertias[-1], counts)


def _final_assign(
    xt: jax.Array,
    n: int,
    centroids: jax.Array,
    *,
    bn: int,
    pallas: bool,
    need_inertia: bool,
    pair_sqrt_k: int = 0,
) -> tuple[jax.Array, jax.Array | None, jax.Array | None]:
    """Final assignment pass over ``xt (B, s, n_pad)`` in ``bn``-point
    chunks -> (assign (B, n) int32, inertia (B,) f32|None, cell_counts|None).

    Routed through the batched Pallas kernels on TPU, the chunked jnp scan
    elsewhere (one chunk of all ``n`` points for the dense reference).
    Lloyd callers pass ``need_inertia=False`` (they report the last update
    step's inertia) so the TPU path can use the assign-only kernel and
    skip the dead one-hot/stats accumulation work entirely; minibatch
    needs the final full-data inertia and takes the fused stats kernel.

    ``pair_sqrt_k > 0`` fuses the SuCo IMI occupancy histogram into the
    scan (see :func:`assign_scan`).  The Lloyd-path TPU route fuses it
    too (:func:`repro.kernels.kmeans_assign.ops.kmeans_pair_assign_hist_t`:
    the histogram accumulates on the MXU inside the assignment kernel);
    only the minibatch TPU path — which additionally needs the full-data
    inertia from the stats kernel — still returns None and leaves the
    caller a bincount over the assignments.
    """
    if pallas:
        from repro.kernels.kmeans_assign import ops as _ops

        if not need_inertia:
            if pair_sqrt_k:
                a, counts = _ops.kmeans_pair_assign_hist_t(
                    xt, centroids, n=n, bn=bn
                )
                return a, None, counts
            return _ops.kmeans_assign_batched_t(xt, centroids, n=n, bn=bn), None, None
        a, _, _, inertia = _ops.kmeans_assign_stats_t(xt, centroids, n=n, bn=bn)
        return a, inertia, None
    blocks, valid = _lane_blocks(xt, n, bn)
    a, inertia, counts = assign_scan(blocks, valid, centroids,
                                     pair_sqrt_k=pair_sqrt_k)
    return a[:, :n], inertia, counts


def _check_args(algo: str, block_n: int, init: str = "auto") -> None:
    if algo not in _ALGOS:
        raise ValueError(f"algo must be one of {_ALGOS}, got {algo!r}")
    if block_n < 0:
        raise ValueError(f"block_n must be >= 0 (0 = dense), got {block_n}")
    if init not in _INITS:
        raise ValueError(f"init must be one of {_INITS}, got {init!r}")


def kmeans(
    key: jax.Array,
    x: jax.Array,
    k: int,
    iters: int,
    *,
    algo: str = "lloyd",
    block_n: int = 0,
    impl: str = "auto",
    init: str = "auto",
) -> KMeansResult:
    """K-means with ``iters`` update steps; deterministic given ``key``.

    ``algo``: "lloyd" (exact full-batch updates) | "minibatch" (sampled
    chunks + learning-rate updates).  ``block_n``: 0 = dense reference
    Lloyd; >0 = chunked streaming updates over ``block_n``-point chunks
    (same update rule; centroids and assignments agree with dense up to
    fp summation-order noise at Voronoi boundaries).  ``impl`` selects
    the assignment backend ("auto" = fused Pallas kernels on TPU, jnp
    elsewhere).  ``init``: "random" | "kmeans++" (sampled D^2 seeding) |
    "auto" (kmeans++ for minibatch, random for lloyd).
    """
    _check_args(algo, block_n, init)
    mode = init
    if mode == "auto":
        mode = "kmeans++" if algo == "minibatch" else "random"
    if mode == "random":
        c0 = _init_centroids(key, x, k)
    else:
        sample_n = min(x.shape[0], max(_PP_SAMPLE_PER_K * k, _PP_SAMPLE_MIN))
        c0 = init_centroids_pp(key, x, k, sample_n=sample_n)
    res = _kmeans_core(key, x.T[None], x.shape[0], c0[None], iters, algo,
                       block_n, impl)
    return KMeansResult(res.centroids[0], res.assignments[0], res.inertia[0])


def kmeans_batched(
    key: jax.Array,
    xs: jax.Array,
    k: int,
    iters: int,
    *,
    algo: str = "lloyd",
    block_n: int = 0,
    impl: str = "auto",
    init: str = "auto",
    pair_sqrt_k: int = 0,
) -> KMeansResult:
    """``xs: (B, n, s)`` -> centroids ``(B, k, s)``, assignments ``(B, n)``.

    One fused program for all ``B`` codebooks (B = 2*Ns for SuCo); same
    ``algo``/``block_n``/``impl``/``init`` contract as :func:`kmeans`.
    ``pair_sqrt_k > 0`` additionally returns the fused IMI cell occupancy
    ``KMeansResult.cell_counts`` from the final-assignment pass (None on
    the minibatch TPU path — see :func:`_final_assign`).  The points are
    transposed once onto lanes (:func:`kmeans_batched_t`).
    """
    return kmeans_batched_t(
        key, jnp.swapaxes(xs, 1, 2), k, iters, n=xs.shape[1], algo=algo,
        block_n=block_n, impl=impl, init=init, pair_sqrt_k=pair_sqrt_k,
    )


def kmeans_batched_t(
    key: jax.Array,
    xt: jax.Array,
    k: int,
    iters: int,
    *,
    n: int,
    algo: str = "lloyd",
    block_n: int = 0,
    impl: str = "auto",
    init: str = "auto",
    pair_sqrt_k: int = 0,
) -> KMeansResult:
    """:func:`kmeans_batched` over points on lanes: ``xt (B, s, n_pad)``,
    of which the first ``n`` columns are the points and the rest are
    zero.  The layout the TPU kernels stream: given
    :func:`lane_points` columns, no Lloyd pass, final assignment or init
    copies, transposes or re-pads the points.
    """
    _check_args(algo, block_n, init)
    with scope("kmeans.init"):
        c0 = _init_lanes(key, xt, n, k, init, algo)
    return _kmeans_core(key, xt, n, c0, iters, algo, block_n, impl, pair_sqrt_k)
