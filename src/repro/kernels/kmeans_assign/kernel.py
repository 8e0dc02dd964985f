"""Fused K-means assignment Pallas kernels: distance + argmin in one pass.

For ``B`` batched codebooks (the SuCo build trains ``B = 2*Ns`` at once)
the kernels produce ``argmin_c ||x - c||^2`` without materialising the
``(n, k)`` distance matrix in HBM.  The points arrive transposed,
``xt: (B, s, n)``: the point axis rides the 128 lanes and the (small)
subspace width the sublanes, so a block of ``bn`` points is lane-dense in
VMEM and in HBM, and the assignments come out as lane-dense ``(B, n)``
rows — no ``(..., 1)`` trailing dim, no width padding to 128 lanes.  Every
codebook (sqrt(K) ~ 50 rows) lives in VMEM for every grid step; points
stream through in ``bn`` blocks, all ``B`` codebooks per step.

Three entry points share that structure (grid ``(n/bn,)``):

* :func:`kmeans_assign_batched_kernel` — assignments only.
* :func:`kmeans_stats_kernel` — the streaming-Lloyd workhorse: per grid
  step it additionally folds the block's one-hot into per-centroid
  ``(sums, counts, inertia)`` accumulator tiles that revisit across the
  point-block grid dimension — one kernel pass yields the complete Lloyd
  sufficient statistics with nothing of size ``(n, k)`` ever leaving VMEM.
* :func:`kmeans_pair_assign_hist_kernel` — final assignment plus the IMI
  occupancy histogram of SuCo's paired half-subspaces.

:func:`halves_to_lanes_kernel` writes that ``(B, s, n)`` layout from the
row-major points once per index build.

Distances are the matmul identity ``|x|^2 + |c|^2 - 2 c.x`` with the
cross term at ``Precision.HIGHEST`` (full f32 on the MXU), the same
formula as the jnp oracle.

Padding contract (enforced by ops.py): pad centroid *rows* with a large
constant so they never win the argmin, pad point columns freely for
assign-only kernels (junk assignments are sliced off); the stats kernels
additionally take a ``(1, n)`` weight row that zeroes padded points out
of the accumulators.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HIGHEST = jax.lax.Precision.HIGHEST
_LANE = 128


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _sqdist_t(x: jax.Array, c: jax.Array) -> jax.Array:
    """``x (s, bn), c (kp, s) -> (kp, bn)`` squared distances, f32."""
    xn = jnp.sum(x * x, axis=0, keepdims=True)  # (1, bn)
    cn = jnp.sum(c * c, axis=1, keepdims=True)  # (kp, 1)
    cross = jax.lax.dot(c, x, precision=_HIGHEST, preferred_element_type=jnp.float32)
    return xn + cn - 2.0 * cross


def _argmin_rows(d2: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Column-wise argmin of ``(kp, bn)`` -> ``(a (1, bn) int32, min (1, bn))``;
    ties resolve to the lowest centroid index, as ``jnp.argmin`` does."""
    mn = jnp.min(d2, axis=0, keepdims=True)
    ids = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    a = jnp.min(jnp.where(d2 == mn, ids, d2.shape[0]), axis=0, keepdims=True)
    return a, mn


def _codebook(x_ref, c_ref, b) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Points, distances' argmin and minimum of codebook ``b``'s block."""
    x = x_ref[b].astype(jnp.float32)  # (s, bn)
    a, mn = _argmin_rows(_sqdist_t(x, c_ref[b].astype(jnp.float32)))
    return x, a, mn


def _assign_kernel(x_ref, c_ref, out_ref):
    def body(b, carry):
        _, a, _ = _codebook(x_ref, c_ref, b)
        out_ref[pl.ds(b, 1), :] = a
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0], body, 0)


def _in_specs(b: int, s: int, kp: int, bn: int, with_weights: bool):
    specs = [
        pl.BlockSpec((b, s, bn), lambda j: (0, 0, j)),
        pl.BlockSpec((b, kp, s), lambda j: (0, 0, 0)),
    ]
    if with_weights:
        specs.append(pl.BlockSpec((1, bn), lambda j: (0, j)))
    return specs


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def kmeans_assign_batched_kernel(
    xt: jax.Array, centroids: jax.Array, *, bn: int = 1024, interpret: bool = False
) -> jax.Array:
    """``(B, s, n), (B, kp, s) -> (B, n)`` batched fused distance+argmin.

    Caller pre-pads: ``n % bn == 0``, ``bn % 128 == 0``, ``kp % 8 == 0``.
    """
    b, s, n = xt.shape
    kp = centroids.shape[1]
    return pl.pallas_call(
        _assign_kernel,
        grid=(n // bn,),
        in_specs=_in_specs(b, s, kp, bn, with_weights=False),
        out_specs=pl.BlockSpec((b, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.int32),
        interpret=interpret,
    )(xt, centroids)


def _pair_assign_hist_kernel(x_ref, c_ref, w_ref, a_ref, counts_ref):
    @pl.when(pl.program_id(0) == 0)  # point blocks revisit the histogram
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    ns, kp, kw = counts_ref.shape
    bn = x_ref.shape[2]
    w = w_ref[...]  # (1, bn) 0/1 point weights
    ids1 = jax.lax.broadcasted_iota(jnp.int32, (kp, bn), 0)
    ids2 = jax.lax.broadcasted_iota(jnp.int32, (kw, bn), 0)

    def body(i, carry):
        _, a1, _ = _codebook(x_ref, c_ref, i)
        _, a2, _ = _codebook(x_ref, c_ref, i + ns)
        a_ref[pl.ds(i, 1), :] = a1
        a_ref[pl.ds(i + ns, 1), :] = a2
        # The pair-cell histogram counts[c1, c2] factorises exactly as the
        # contraction of the two one-hots over the block's points —
        # sum_p oh1[c1, p] * oh2[c2, p] — so the flat (K, bn) cell one-hot
        # never exists: one (kp, kw) MXU contraction per block, 0/1
        # operands exact in bf16, per-block sums <= bn exact in f32, and
        # the running total kept in int32.
        oh1 = jnp.where(ids1 == a1, w, 0.0).astype(jnp.bfloat16)
        oh2 = (ids2 == a2).astype(jnp.bfloat16)
        counts_ref[i] += jax.lax.dot_general(
            oh1, oh2, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ).astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, ns, body, 0)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def kmeans_pair_assign_hist_kernel(
    xt: jax.Array,  # (2*ns, s, n) paired half-subspace points, transposed
    centroids: jax.Array,  # (2*ns, kp, s) paired codebooks
    weights: jax.Array,  # (1, n) 0/1 point weights
    *,
    bn: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused final assignment + IMI occupancy histogram for SuCo's paired
    half-subspace layout: rows ``[:ns]`` of ``xt``/``centroids`` are first
    halves, ``[ns:]`` second halves of the same subspaces.

    ``-> (a (2*ns, n) int32, counts (ns, kp, kw) int32)`` where
    ``counts[i, c1, c2]`` is the weighted occupancy of IMI cell
    ``c1 * k + c2`` in subspace ``i`` (``kw`` = ``kp`` rounded up to the
    lane width) — the histogram rides the assignment kernel's grid in a
    revisiting tile, so no second pass over the assignments ever runs.

    Caller pre-pads ``n % bn == 0``; padded centroid rows must never win
    the argmin and padded points carry weight 0.
    """
    b, s, n = xt.shape
    kp = centroids.shape[1]
    kw = _round_up(kp, _LANE)
    ns = b // 2
    return pl.pallas_call(
        _pair_assign_hist_kernel,
        grid=(n // bn,),
        in_specs=_in_specs(b, s, kp, bn, with_weights=True),
        out_specs=[
            pl.BlockSpec((b, bn), lambda j: (0, j)),
            pl.BlockSpec((ns, kp, kw), lambda j: (0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n), jnp.int32),
            jax.ShapeDtypeStruct((ns, kp, kw), jnp.int32),
        ],
        interpret=interpret,
    )(xt, centroids, weights)


def _accumulate_stats(x_ref, c_ref, w_ref, sums_ref, counts_ref, inertia_ref, assign_ref):
    """Shared stats body: per codebook, distance + argmin + weighted one-hot
    folded into the revisiting accumulator tiles."""

    @pl.when(pl.program_id(0) == 0)  # point blocks revisit the accumulators
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)
        inertia_ref[...] = jnp.zeros_like(inertia_ref)

    kp = c_ref.shape[1]
    bn = x_ref.shape[2]
    w = w_ref[...]  # (1, bn) 0/1 point weights
    ids = jax.lax.broadcasted_iota(jnp.int32, (kp, bn), 0)

    def body(b, carry):
        x, a, mn = _codebook(x_ref, c_ref, b)
        if assign_ref is not None:
            assign_ref[pl.ds(b, 1), :] = a
        oh = jnp.where(ids == a, w, 0.0)  # (kp, bn), padded points zeroed
        sums_ref[b] += jax.lax.dot_general(
            oh, x, (((1,), (1,)), ((), ())),
            precision=_HIGHEST, preferred_element_type=jnp.float32,
        )  # (kp, s)
        counts_ref[b] += jnp.sum(oh, axis=1, keepdims=True)  # (kp, 1)
        inertia_ref[pl.ds(b, 1), :] += jnp.sum(mn * w, axis=1, keepdims=True)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0], body, 0)


def _stats_kernel(x_ref, c_ref, w_ref, assign_ref, sums_ref, counts_ref, inertia_ref):
    _accumulate_stats(x_ref, c_ref, w_ref, sums_ref, counts_ref, inertia_ref, assign_ref)


def _stats_only_kernel(x_ref, c_ref, w_ref, sums_ref, counts_ref, inertia_ref):
    _accumulate_stats(x_ref, c_ref, w_ref, sums_ref, counts_ref, inertia_ref, None)


@functools.partial(jax.jit, static_argnames=("bn", "with_assign", "interpret"))
def kmeans_stats_kernel(
    xt: jax.Array,
    centroids: jax.Array,
    weights: jax.Array,
    *,
    bn: int = 1024,
    with_assign: bool = True,
    interpret: bool = False,
) -> tuple[jax.Array | None, jax.Array, jax.Array, jax.Array]:
    """Fused Lloyd sufficient statistics for ``B`` batched codebooks.

    ``xt: (B, s, n)``, ``centroids: (B, kp, s)``, ``weights: (1, n)`` (0
    for padded points) -> ``(assign (B, n) int32 | None, sums (B, kp, s)
    f32, counts (B, kp, 1) f32, inertia (B, 1) f32)``.

    The accumulator tiles are whole arrays that revisit across the
    point-block grid: each block's weighted one-hot is folded on the MXU
    while the block is already resident for the argmin — the ``(n, k)``
    one-hot/distance matrices never exist outside a ``(kp, bn)`` VMEM
    tile.  ``with_assign=False`` drops the ``(B, n)`` assignment output —
    Lloyd iterations only need the statistics, and XLA cannot DCE an
    unused pallas_call output.  Caller pre-pads ``n % bn == 0``.
    """
    b, s, n = xt.shape
    kp = centroids.shape[1]
    stats_specs = [
        pl.BlockSpec((b, kp, s), lambda j: (0, 0, 0)),
        pl.BlockSpec((b, kp, 1), lambda j: (0, 0, 0)),
        pl.BlockSpec((b, 1), lambda j: (0, 0)),
    ]
    stats_shapes = [
        jax.ShapeDtypeStruct((b, kp, s), jnp.float32),
        jax.ShapeDtypeStruct((b, kp, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, 1), jnp.float32),
    ]
    in_specs = _in_specs(b, s, kp, bn, with_weights=True)
    if not with_assign:
        sums, counts, inertia = pl.pallas_call(
            _stats_only_kernel,
            grid=(n // bn,),
            in_specs=in_specs,
            out_specs=stats_specs,
            out_shape=stats_shapes,
            interpret=interpret,
        )(xt, centroids, weights)
        return None, sums, counts, inertia
    return pl.pallas_call(
        _stats_kernel,
        grid=(n // bn,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((b, bn), lambda j: (0, j))] + stats_specs,
        out_shape=[jax.ShapeDtypeStruct((b, n), jnp.int32)] + stats_shapes,
        interpret=interpret,
    )(xt, centroids, weights)


def _halves_kernel(x_ref, o_ref, *, starts, widths, n, columns):
    """One block of points -> ``o (B, s, bt)``: the ``widths[b]`` dims from
    ``starts[b]`` of every point on lanes, zero rows after them, zero
    columns for points at or past ``n``.  The block is ``x (bt, d)`` rows,
    transposed here, or with ``columns`` already ``x^T (d, bt)``."""
    vt = x_ref[...] if columns else x_ref[...].T  # (d, bt)
    bt = vt.shape[1]
    if n is not None:  # the last blocks run past the points
        col = pl.program_id(0) * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        vt = jnp.where(col < n, vt, jnp.zeros((), vt.dtype))
    s = o_ref.shape[1]
    for b, (a, w) in enumerate(zip(starts, widths)):
        o_ref[b, :w, :] = vt[a:a + w]
        if w < s:
            o_ref[b, w:, :] = jnp.zeros((s - w, bt), o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("starts", "widths", "s", "n_pad", "bt", "columns", "interpret"),
)
def halves_to_lanes_kernel(
    x: jax.Array,
    *,
    starts: tuple[int, ...],
    widths: tuple[int, ...],
    s: int,
    n_pad: int,
    bt: int,
    columns: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """``x (n, d)`` rows, or with ``columns`` ``x^T (d, n)`` -> ``(B, s,
    n_pad)``: codebook ``b`` holds dims ``starts[b] : starts[b] + widths[b]``
    of every point on lanes (zero rows up to ``s``, zero columns from
    ``n``) — the layout the kernels above stream, written in one pass over
    blocks of ``bt`` points (transposed in VMEM unless ``columns``).
    Caller picks ``bt % 128 == 0`` dividing ``n_pad``."""
    n = x.shape[1] if columns else x.shape[0]
    d = x.shape[0] if columns else x.shape[1]
    last = (n - 1) // bt  # blocks past the points re-read the last one
    if columns:
        in_spec = pl.BlockSpec((d, bt), lambda j: (0, jnp.minimum(j, last)))
    else:
        in_spec = pl.BlockSpec((bt, d), lambda j: (jnp.minimum(j, last), 0))
    return pl.pallas_call(
        functools.partial(
            _halves_kernel, starts=starts, widths=widths,
            n=None if n == n_pad else n, columns=columns,
        ),
        grid=(n_pad // bt,),
        in_specs=[in_spec],
        out_specs=pl.BlockSpec((len(starts), s, bt), lambda j: (0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((len(starts), s, n_pad), x.dtype),
        interpret=interpret,
    )(x)
