"""Subspace sampling and division (paper Definition 3).

A :class:`SubspaceSpec` is a static (hashable) description of how the ``d``
original dimensions are distributed over ``Ns`` subspaces:

* ``perm``   -- a permutation of ``range(d)``; applying it first makes every
  division a *contiguous* slicing problem (the paper's "practical" contiguous
  division is ``perm == identity``; Definition 3's uniform sampling without
  replacement is a random permutation).
* ``bounds`` -- ``Ns+1`` prefix boundaries.  Subspace ``i`` owns permuted dims
  ``bounds[i]:bounds[i+1]``.  Per Definition 3 the first ``Ns-1`` subspaces
  get ``floor(d/Ns)`` dims and the last one picks up the remainder.

For TPU friendliness every ragged view is materialised as a dense, zero-padded
array: zero padding never changes L1/L2 distances, K-means centroids of padded
columns stay at zero, so all downstream math is padding-oblivious.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SubspaceSpec",
    "contiguous_spec",
    "sampled_spec",
    "permute",
    "split_padded",
    "split_query_padded",
    "half_dims",
    "halves_on_lanes",
    "collision_count",
]


@dataclasses.dataclass(frozen=True)
class SubspaceSpec:
    """Static description of a subspace division."""

    d: int
    n_subspaces: int
    perm: tuple[int, ...]
    bounds: tuple[int, ...]

    def __post_init__(self) -> None:
        if len(self.perm) != self.d:
            raise ValueError(f"perm has {len(self.perm)} entries, expected d={self.d}")
        if sorted(self.perm) != list(range(self.d)):
            raise ValueError("perm is not a permutation of range(d)")
        if len(self.bounds) != self.n_subspaces + 1:
            raise ValueError("bounds must have Ns+1 entries")
        if self.bounds[0] != 0 or self.bounds[-1] != self.d:
            raise ValueError("bounds must span [0, d]")
        for a, b in zip(self.bounds, self.bounds[1:]):
            if b <= a:
                raise ValueError("every subspace must own at least one dim")

    @property
    def sizes(self) -> tuple[int, ...]:
        return tuple(b - a for a, b in zip(self.bounds, self.bounds[1:]))

    @property
    def max_size(self) -> int:
        return max(self.sizes)

    # -- halves (used by the IMI: each subspace is product-quantised in two) --
    @property
    def half_sizes(self) -> tuple[tuple[int, int], ...]:
        out = []
        for s in self.sizes:
            h1 = math.ceil(s / 2)
            out.append((h1, s - h1))
        return tuple(out)

    @property
    def max_half_size(self) -> int:
        return max(max(h1, h2) for h1, h2 in self.half_sizes)


def _even_bounds(d: int, n_subspaces: int) -> tuple[int, ...]:
    s = d // n_subspaces
    if s == 0:
        raise ValueError(f"d={d} too small for Ns={n_subspaces}")
    bounds = [i * s for i in range(n_subspaces)] + [d]
    return tuple(bounds)


def contiguous_spec(d: int, n_subspaces: int) -> SubspaceSpec:
    """The paper's practical division: contiguous equal slices (§3.2)."""
    return SubspaceSpec(d, n_subspaces, tuple(range(d)), _even_bounds(d, n_subspaces))


def sampled_spec(d: int, n_subspaces: int, seed: int) -> SubspaceSpec:
    """Definition 3: multi-round uniform sampling without replacement."""
    rng = np.random.default_rng(seed)
    perm = tuple(int(x) for x in rng.permutation(d))
    return SubspaceSpec(d, n_subspaces, perm, _even_bounds(d, n_subspaces))


def permute(spec: SubspaceSpec, x: jax.Array) -> jax.Array:
    """Apply the dim permutation to the trailing axis of ``x``."""
    perm = jnp.asarray(spec.perm, dtype=jnp.int32)
    return jnp.take(x, perm, axis=-1)


def split_padded(spec: SubspaceSpec, x: jax.Array) -> jax.Array:
    """``(..., d) -> (Ns, ..., s_max)`` zero-padded dense subspace view.

    ``x`` must already be permuted (see :func:`permute`).
    """
    s_max = spec.max_size
    parts = []
    for i, (a, b) in enumerate(zip(spec.bounds, spec.bounds[1:])):
        piece = x[..., a:b]
        pad = s_max - (b - a)
        if pad:
            widths = [(0, 0)] * (piece.ndim - 1) + [(0, pad)]
            piece = jnp.pad(piece, widths)
        parts.append(piece)
    return jnp.stack(parts, axis=0)


def split_query_padded(spec: SubspaceSpec, q: jax.Array) -> jax.Array:
    """Convenience alias, kept for call-site readability."""
    return split_padded(spec, q)


def split_halves_padded(spec: SubspaceSpec, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(..., d) -> 2 x (Ns, ..., h_max)`` zero-padded half-subspace views."""
    h_max = spec.max_half_size
    first, second = [], []
    for (a, b), (h1, _h2) in zip(zip(spec.bounds, spec.bounds[1:]), spec.half_sizes):
        p1 = x[..., a : a + h1]
        p2 = x[..., a + h1 : b]
        for piece, acc in ((p1, first), (p2, second)):
            pad = h_max - piece.shape[-1]
            if pad:
                widths = [(0, 0)] * (piece.ndim - 1) + [(0, pad)]
                piece = jnp.pad(piece, widths)
            acc.append(piece)
    return jnp.stack(first, axis=0), jnp.stack(second, axis=0)


def half_dims(spec: SubspaceSpec) -> np.ndarray:
    """``(2Ns, h_max)`` original dimension of each half-subspace row: the
    first halves of all subspaces, then all second halves (the layout of
    :func:`split_halves_padded` concatenated); ``-1`` marks zero padding."""
    ns, h_max = spec.n_subspaces, spec.max_half_size
    perm = np.asarray(spec.perm)
    out = np.full((2 * ns, h_max), -1, np.int64)
    for i, ((a, b), (h1, h2)) in enumerate(
        zip(zip(spec.bounds, spec.bounds[1:]), spec.half_sizes)
    ):
        out[i, :h1] = perm[a : a + h1]
        out[ns + i, :h2] = perm[a + h1 : b]
    return out


def halves_on_lanes(
    spec: SubspaceSpec,
    x: jax.Array,
    n_pad: int,
    *,
    kernel: bool = False,
    columns: bool = False,
) -> jax.Array:
    """``(n, d) -> (2Ns, h_max, n_pad)``: the half-subspace views with the
    points on the minor (lane) axis and zero columns from ``n`` on — the
    layout the K-means kernels stream.  Row ``j`` equals
    ``split_halves_padded`` (first halves, then second halves) transposed;
    padded half dims are zero rows.

    ``kernel=True`` (the TPU path) writes it with one Pallas pass over the
    points (:func:`repro.kernels.kmeans_assign.ops.halves_to_lanes`), so
    ``x`` and the result are all it holds; an XLA transpose into this
    tiling keeps a second whole copy alive.  ``columns`` says ``x`` is
    stored column-major, so the kernel reads ``x^T`` as stored.  Otherwise
    it is a row gather of ``x^T``.
    """
    n, d = x.shape
    if n_pad < n:
        raise ValueError(f"n_pad={n_pad} < n={n}")
    src = half_dims(spec)
    b, h_max = src.shape
    if kernel and n >= 128:  # at least one lane block of points
        from repro.kernels.kmeans_assign.ops import halves_to_lanes

        if spec.perm != tuple(range(d)):
            x, columns = permute(spec, x), False
        bounds = list(zip(spec.bounds, spec.bounds[1:]))
        halves = spec.half_sizes
        starts = [a for a, _ in bounds] + [a + h1 for (a, _), (h1, _) in
                                           zip(bounds, halves)]
        widths = [h1 for h1, _ in halves] + [h2 for _, h2 in halves]
        return halves_to_lanes(x, tuple(starts), tuple(widths), h_max, n_pad,
                               columns=columns)
    rows = jnp.take(x.T, jnp.asarray(np.maximum(src, 0).reshape(-1)), axis=0)
    keep = jnp.asarray((src >= 0).reshape(-1, 1))
    xt = jnp.where(keep, rows, jnp.zeros((), x.dtype)).reshape(b, h_max, n)
    return jnp.pad(xt, ((0, 0), (0, 0), (0, n_pad - n)))


def collision_count(n: int, alpha: float) -> int:
    """Number of per-subspace collisions: the ``alpha * n`` of Definition 1."""
    if not 0.0 < alpha <= 1.0:
        raise ValueError(f"alpha must be in (0, 1], got {alpha}")
    return max(1, int(alpha * n))
