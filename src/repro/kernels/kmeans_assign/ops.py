"""jit'd wrappers for the kmeans_assign kernel family: padding + dispatch.

The kernels read the points transposed, ``xt (B, s, n)``: points on lanes.
Each op comes in two forms with one padding contract (centroid rows padded
far away, point columns zero-padded to the kernel's block, then masked or
sliced):

* ``*_t`` ops take the points already in that layout, ``xt (B, s, n_pad)``
  with the ``n`` real points first — the index build lays its half
  subspaces out so once (:func:`halves_to_lanes`), padded to
  :func:`repro.core.kmeans.lane_points`, and every pass then streams it as
  is;
* the plain ops take ``(B, n, s)`` (``(n, s)`` for
  :func:`kmeans_assign`) and transpose it on each call.

Families: assignments (:func:`kmeans_assign`, :func:`kmeans_assign_batched`),
fused assignments + per-centroid ``(sums, counts, inertia)`` Lloyd
statistics in one streaming pass (:func:`kmeans_assign_stats`), and the
final pair-assign + IMI occupancy histogram (:func:`kmeans_pair_assign_hist`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.kmeans_assign.kernel import (
    halves_to_lanes_kernel,
    kmeans_assign_batched_kernel,
    kmeans_pair_assign_hist_kernel,
    kmeans_stats_kernel,
)
from repro.kernels.kmeans_assign.ref import (
    kmeans_assign_batched_ref,
    kmeans_assign_ref,
    kmeans_pair_assign_hist_ref,
    kmeans_stats_ref,
)

_CENTROID_PAD = 1.0e6  # padded centroids sit ~1e12 away -> never win argmin
# Per-step VMEM the point block may claim (double-buffered points, the
# assignment rows, and one codebook's (kp, bn) distance temporaries) —
# half of Mosaic's 16 MiB default scoped limit.
_BLOCK_VMEM_BYTES = 8 << 20


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _route_to_ref(impl: str, interpret: bool) -> bool:
    """True when the jnp oracle should run instead of the kernel."""
    if impl not in ("auto", "jnp", "pallas"):
        raise ValueError(f"impl must be 'auto'|'jnp'|'pallas', got {impl!r}")
    return impl == "jnp" or (
        impl == "auto" and jax.default_backend() != "tpu" and not interpret
    )


def lane_block(b: int, s: int, k: int, n: int, bn: int) -> int:
    """Points per grid step of the kernels for ``b`` codebooks of ``k``
    centroids over ``s``-wide subspaces and ``n`` points.

    ``bn`` is a caller-supplied chunk size (e.g. SuCoConfig.block_n) and
    may be arbitrary; the block is a lane multiple (128), capped so the
    per-step working set stays within ``_BLOCK_VMEM_BYTES``.  A block's
    second-minor dim ``s`` equals the array's own, a legal TPU tile.
    """
    kp = _round_up(k, 8)
    per_point = 4 * (
        2 * b * _round_up(s, 8)  # double-buffered point block
        + 2 * _round_up(b, 8)  # double-buffered assignment rows
        + 6 * _round_up(kp, 128)  # distance / one-hot temporaries
    )
    cap = max(128, (_BLOCK_VMEM_BYTES // per_point) // 128 * 128)
    return min(_round_up(bn, 128), _round_up(n, 128), cap)


def _kernel_inputs(xt: jax.Array, centroids: jax.Array, n: int, bn: int):
    """``(xt, cp, bn_, k)``: the points cut or zero-padded to a multiple of
    the kernels' point block (a no-op for a caller that laid them out so),
    the centroid rows padded to a sublane multiple with far-away
    rows, and the kernels' point block."""
    b, s, have = xt.shape
    k = centroids.shape[1]
    bn_ = lane_block(b, s, k, n, bn)
    np_ = _round_up(n, bn_)
    if have > np_:
        xt = xt[:, :, :np_]
    elif have < np_:
        xt = jnp.pad(xt, ((0, 0), (0, 0), (0, np_ - have)))
    cp = jnp.pad(
        centroids, ((0, 0), (0, _round_up(k, 8) - k), (0, 0)),
        constant_values=_CENTROID_PAD,
    )
    return xt, cp, bn_, k


def halves_to_lanes(
    x: jax.Array,
    starts: tuple[int, ...],
    widths: tuple[int, ...],
    s: int,
    n_pad: int,
    *,
    columns: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """``x (n, d) -> (B, s, n_pad)``: codebook ``b`` holds columns
    ``starts[b] : starts[b] + widths[b]`` of ``x`` with the points on lanes,
    zero-padded to ``s`` rows and ``n_pad`` columns — the ``*_t`` ops'
    layout, made by one kernel pass over ``x``: nothing of ``x``'s size is
    held but the result.  Needs ``n >= 128`` points and ``n_pad`` a lane
    multiple, as :func:`repro.core.kmeans.lane_points` gives on the TPU.

    ``columns``: ``x`` is stored column-major (the TPU lays out a corpus so
    when that pads least, as 1M x 960 f32); the kernel then reads ``x^T``,
    which is that storage as it is, instead of a row-major copy of it."""
    n, d = x.shape
    if n_pad % 128 or n_pad < n or n < 128:
        raise ValueError(
            f"needs n >= 128 and n_pad a lane multiple >= n, got n={n}, "
            f"n_pad={n_pad}"
        )
    b = len(starts)
    per_point = x.dtype.itemsize * (
        3 * _round_up(d, 128) + 2 * b * _round_up(s, 8)  # x blocks, x^T, out
    )
    cap = max(1, (_BLOCK_VMEM_BYTES // per_point) // 128)
    units = n_pad // 128
    fits = [u for u in range(1, min(cap, units, n // 128) + 1) if units % u == 0]
    return halves_to_lanes_kernel(
        x.T if columns else x, starts=tuple(starts), widths=tuple(widths),
        s=s, n_pad=n_pad, bt=128 * max(fits), columns=columns,
        interpret=interpret,
    )


def _point_weights(n: int, np_: int) -> jax.Array:
    return (jnp.arange(np_, dtype=jnp.int32) < n).astype(jnp.float32)[None, :]


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def kmeans_assign(
    x: jax.Array, centroids: jax.Array, *, bn: int = 1024, interpret: bool = False
) -> jax.Array:
    """``(n, s), (k, s) -> (n,)`` int32 fused distance+argmin (the batched
    kernel with one codebook)."""
    return kmeans_assign_batched(
        x[None], centroids[None], bn=bn, impl="pallas", interpret=interpret
    )[0]


@functools.partial(jax.jit, static_argnames=("n", "bn", "interpret"))
def kmeans_assign_batched_t(
    xt: jax.Array,
    centroids: jax.Array,
    *,
    n: int,
    bn: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """``(B, s, n_pad), (B, k, s) -> (B, n)`` int32 batched fused
    distance+argmin of the first ``n`` point columns."""
    xt, cp, bn_, _ = _kernel_inputs(xt, centroids, n, bn)
    out = kmeans_assign_batched_kernel(xt, cp, bn=bn_, interpret=interpret)
    return out[:, :n]


@functools.partial(jax.jit, static_argnames=("bn", "impl", "interpret"))
def kmeans_assign_batched(
    x: jax.Array,
    centroids: jax.Array,
    *,
    bn: int = 1024,
    impl: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    """``(B, n, s), (B, k, s) -> (B, n)`` int32 batched fused distance+argmin.

    ``impl``: "jnp" | "pallas" | "auto" (pallas iff running on TPU).
    """
    if _route_to_ref(impl, interpret):
        return kmeans_assign_batched_ref(x, centroids)
    return kmeans_assign_batched_t(
        jnp.swapaxes(x, 1, 2), centroids, n=x.shape[1], bn=bn,
        interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("n", "bn", "with_assign", "interpret")
)
def kmeans_assign_stats_t(
    xt: jax.Array,
    centroids: jax.Array,
    *,
    n: int,
    bn: int = 1024,
    with_assign: bool = True,
    interpret: bool = False,
) -> tuple[jax.Array | None, jax.Array, jax.Array, jax.Array]:
    """:func:`kmeans_assign_stats` over points on lanes, ``xt (B, s,
    n_pad)``, of which the first ``n`` columns are points; the rest must be
    finite (zero) and carry no weight."""
    xt, cp, bn_, k = _kernel_inputs(xt, centroids, n, bn)
    a, sums, counts, inertia = kmeans_stats_kernel(
        xt, cp, _point_weights(n, xt.shape[2]), bn=bn_,
        with_assign=with_assign, interpret=interpret,
    )
    a_out = a[:, :n] if with_assign else None
    return a_out, sums[:, :k], counts[:, :k, 0], inertia[:, 0]


@functools.partial(jax.jit, static_argnames=("bn", "impl", "with_assign", "interpret"))
def kmeans_assign_stats(
    x: jax.Array,
    centroids: jax.Array,
    *,
    bn: int = 1024,
    impl: str = "auto",
    with_assign: bool = True,
    interpret: bool = False,
) -> tuple[jax.Array | None, jax.Array, jax.Array, jax.Array]:
    """Fused Lloyd statistics: ``(B, n, s), (B, k, s) ->``
    ``(assign (B, n) int32 | None, sums (B, k, s) f32, counts (B, k) f32,
    inertia (B,) f32)`` — one streaming pass, no ``(n, k)`` intermediate.

    ``impl``: "jnp" | "pallas" | "auto" (pallas iff running on TPU; the
    jnp oracle is dense and only for small-n validation).
    ``with_assign=False`` skips the ``(B, n)`` assignment output — use it
    for Lloyd iterations, which consume only the statistics.
    """
    if _route_to_ref(impl, interpret):
        a, sums, counts, inertia = kmeans_stats_ref(x, centroids)
        return (a if with_assign else None), sums, counts, inertia
    return kmeans_assign_stats_t(
        jnp.swapaxes(x, 1, 2), centroids, n=x.shape[1], bn=bn,
        with_assign=with_assign, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("n", "bn", "interpret"))
def kmeans_pair_assign_hist_t(
    xt: jax.Array,
    centroids: jax.Array,
    *,
    n: int,
    bn: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """:func:`kmeans_pair_assign_hist` over points on lanes, ``xt (2*Ns, s,
    n_pad)``, of which the first ``n`` columns are points."""
    b = xt.shape[0]
    if b % 2:
        raise ValueError(f"paired layout needs an even batch, got B={b}")
    xt, cp, bn_, k = _kernel_inputs(xt, centroids, n, bn)
    a, counts = kmeans_pair_assign_hist_kernel(
        xt, cp, _point_weights(n, xt.shape[2]), bn=bn_, interpret=interpret
    )
    # padded centroid rows never win the argmin, so their occupancy rows/
    # columns are zero and the (real k)^2 slice is the exact histogram
    return a[:, :n], counts[:, :k, :k].reshape(b // 2, k * k)


@functools.partial(jax.jit, static_argnames=("bn", "impl", "interpret"))
def kmeans_pair_assign_hist(
    x: jax.Array,
    centroids: jax.Array,
    *,
    bn: int = 1024,
    impl: str = "auto",
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused final assignment + IMI occupancy histogram:
    ``(2*Ns, n, s), (2*Ns, k, s) -> (assign (2*Ns, n) int32,
    cell_counts (Ns, k*k) int32)``.

    SuCo's build needs both the final assignments *and* the per-subspace
    IMI cell occupancy ``bincount(a1 * k + a2)``; this op produces both in
    one streaming kernel pass — the histogram accumulates on the MXU as a
    matmul of the two halves' one-hots into a revisiting ``(k, k)`` tile,
    so no second pass over the assignments (and no scatter) ever runs.

    ``impl``: "jnp" | "pallas" | "auto" (pallas iff running on TPU).
    """
    b = x.shape[0]
    if b % 2:
        raise ValueError(f"paired layout needs an even batch, got B={b}")
    if _route_to_ref(impl, interpret):
        return kmeans_pair_assign_hist_ref(x, centroids)
    return kmeans_pair_assign_hist_t(
        jnp.swapaxes(x, 1, 2), centroids, n=x.shape[1], bn=bn,
        interpret=interpret,
    )


__all__ = [
    "lane_block",
    "halves_to_lanes",
    "kmeans_assign",
    "kmeans_assign_batched",
    "kmeans_assign_batched_t",
    "kmeans_assign_stats",
    "kmeans_assign_stats_t",
    "kmeans_pair_assign_hist",
    "kmeans_pair_assign_hist_t",
    "kmeans_assign_ref",
    "kmeans_assign_batched_ref",
    "kmeans_pair_assign_hist_ref",
    "kmeans_stats_ref",
]


# --------------------------------------------------------------------------
# jaxlint registry hook (see repro.analysis)
# --------------------------------------------------------------------------

#: Tile contract for the batched codebook kernels: point blocks ``(B, s,
#: bn)`` keep the points on lanes (a 128 multiple) and the whole subspace
#: width on sublanes — in HBM a width pads only to the next 8 (60 -> 64 at
#: d=960), never to 128 lanes, and the build hands the kernels this layout
#: once (``*_t`` ops), so no pass re-transposes or re-pads the points;
#: codebooks and accumulator tiles are whole arrays that revisit across the
#: point grid.
TILE_CONTRACT = {
    "sublane": 8,
    "lane": 128,
    "double_buffer": 2,
}


def jaxlint_entries():
    from repro.analysis.registry import JaxprEntry, TileEntry

    S = jax.ShapeDtypeStruct
    # SuCo's build at the paper defaults: 2*Ns = 16 codebooks of half-width
    # 8 (d=128), sqrt(K) = 50 centroids padded to 56.
    b, n, s, k, bn = 16, 2_048, 8, 56, 1_024

    def make_batched():
        return jax.make_jaxpr(
            lambda x, c: kmeans_assign_batched_kernel(x, c, bn=bn, interpret=True)
        )(S((b, s, n), jnp.float32), S((b, k, s), jnp.float32))

    def make_stats():
        return jax.make_jaxpr(
            lambda x, c, w: kmeans_stats_kernel(
                x, c, w, bn=bn, with_assign=True, interpret=True
            )
        )(
            S((b, s, n), jnp.float32),
            S((b, k, s), jnp.float32),
            S((1, n), jnp.float32),
        )

    def make_pair_hist():
        return jax.make_jaxpr(
            lambda x, c, w: kmeans_pair_assign_hist_kernel(
                x, c, w, bn=bn, interpret=True
            )
        )(
            S((b, s, n), jnp.float32),
            S((b, k, s), jnp.float32),
            S((1, n), jnp.float32),
        )

    def make_oracle():
        return jax.make_jaxpr(
            lambda x, c: kmeans_assign_stats(x, c, impl="jnp")
        )(S((b, n, s), jnp.float32), S((b, k, s), jnp.float32))

    def make_halves():
        # d=128 split into its 16 halves of 8, in SuCo's first-halves-then-
        # second-halves order.  In the build the points end inside the last
        # block (1M is no lane multiple), an edge block the kernel masks and
        # tests/test_tpu_compile.py compiles; here they tile the grid.
        starts = tuple(16 * i for i in range(8)) + tuple(16 * i + 8 for i in range(8))
        return jax.make_jaxpr(
            lambda x: halves_to_lanes_kernel(
                x, starts=starts, widths=(s,) * b, s=s, n_pad=n, bt=bn,
                interpret=True,
            )
        )(S((n, 2 * 8 * s), jnp.float32))

    point_block = ((2, 128),)  # xt (B, s, bn): points on lanes
    return [
        TileEntry(
            name="kernels.kmeans_assign.batched",
            make=make_batched,
            contract={
                **TILE_CONTRACT,
                "block_align": {
                    0: point_block,
                    2: ((1, 128),),  # assign out (B, bn)
                },
            },
            note="batched fused distance+argmin assignment",
        ),
        TileEntry(
            name="kernels.kmeans_assign.stats",
            make=make_stats,
            contract={
                **TILE_CONTRACT,
                "block_align": {
                    0: point_block,
                    2: ((1, 128),),  # weights (1, bn)
                    3: ((1, 128),),  # assign out (B, bn)
                },
            },
            note="fused Lloyd sufficient statistics",
        ),
        TileEntry(
            name="kernels.kmeans_assign.pair_hist",
            make=make_pair_hist,
            contract={
                **TILE_CONTRACT,
                "block_align": {
                    0: point_block,
                    2: ((1, 128),),  # weights (1, bn)
                    3: ((1, 128),),  # assign out (B, bn)
                    4: ((2, 128),),  # counts (ns, kp, kw)
                },
            },
            note="fused pair assignment + MXU IMI occupancy histogram",
        ),
        TileEntry(
            name="kernels.kmeans_assign.halves",
            make=make_halves,
            contract={
                **TILE_CONTRACT,
                "block_align": {
                    0: ((1, 8),),  # x rows (bt, d): points on sublanes
                    1: point_block,  # out (B, s, bt)
                },
            },
            note="x rows -> the kernels' points-on-lanes half-subspace layout",
        ),
        JaxprEntry(
            name="kernels.kmeans_assign.oracle",
            make=make_oracle,
            rules=("bounded-intermediate", "pinned-accumulator"),
            budget_bytes=4 * 2 * b * n * max(k, s),
            note="jnp oracle of the Lloyd statistics (dense, small-n only)",
        ),
    ]
