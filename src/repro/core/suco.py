"""SuCo (paper Algorithms 2-4): clustering-based lightweight index + query.

Index (Alg. 2): per subspace, split dims in two halves; K-means with sqrt(K)
centroids per half; IMI = the sqrt(K) x sqrt(K) Cartesian grid.  TPU-adapted
layout (DESIGN.md §3): instead of ragged inverted lists we store

* ``cell_ids   (Ns, n) int32`` — which IMI cell each point falls in,
* ``cell_counts (Ns, K) int32`` — points per cell,

which makes collision counting a dense gather+compare instead of pointer
chasing.

Query (Algs. 3-4): the Dynamic Activation traversal is replaced by its exact
sort-prefix equivalent :func:`activate_cells_sorted` (K <= 4096 cells: one
sort + one cumsum), property-tested against the sequential forms in
:mod:`repro.core.da_numpy`.  A faithful ``lax.while_loop`` port of Algorithm
3 is kept in :func:`dynamic_activation_lax`.

Query-memory model (two execution paths, identical results):

* **dense** (:func:`suco_query` with ``mode="dense"``) — materialises the
  full ``(m, n)`` int32 SC-score matrix and runs one ``top_k`` over all n
  points.  Peak query memory O(m*n); fastest for small n (one fused XLA
  loop, no pool bookkeeping).  The reference semantics.
* **streaming** (:func:`suco_query_streaming`) — a blocked ``lax.scan``
  over data chunks of ``block_n`` points: each chunk's collision counts
  come from the chunked SC-score kernel path
  (:func:`repro.kernels.sc_score.ops.sc_scores_cells`), and a running
  per-query top-``n_candidates`` pool is maintained by
  :func:`repro.core.sc_linear.merge_topk_pool` under the (score desc,
  id asc) order — exactly ``top_k``'s tie-break on the dense matrix, so
  the surviving pool, and therefore the reranked result, is bit-identical
  to the dense path.  Peak query memory O(m*(block_n + n_candidates)).
* **fused** (:func:`suco_query_fused`) — the single-pass engine: while a
  chunk is resident, one fused stage scores it, applies the **Pareto
  prefilter** (only rows beating the carried pool minimum can enter the
  merge — the paper's Pareto observation makes that a thin tail, so the
  merge runs at a compacted ``survivor_cap`` width instead of the full
  chunk width), and computes **exact rerank distances in-pass** for the
  survivors — O(cap) rows of ``x`` per chunk, gathered by global id while
  the chunk's scores are fresh — carrying a joint ``(sc_score,
  exact_dist, id)`` pool; the post-scan rerank gather over ``x``
  disappears and ``x`` is never copied or streamed through the scan.  A
  chunk whose survivors overflow the cap falls back (``lax.cond``) to an
  exact chunk-``top_k`` merge, so results are bit-identical to dense /
  streaming either way.  Tile sizes come from
  :func:`repro.core.tuning.autotune_tiles` unless pinned.

``suco_query(mode="auto")`` (the default) selects dense below
``STREAMING_MIN_N`` points and the fused engine at or above it —
million-point datasets never allocate an (m, n) intermediate; the legacy
streaming path stays available as ``mode="streaming"``.

Index-build memory model (mirrors the query design; see
:mod:`repro.core.kmeans` for the K-means internals):

* **dense** (``SuCoConfig(build_mode="dense")``) — full-batch Lloyd; each
  iteration materialises ``(2Ns, n, sqrtK)`` distance and one-hot
  intermediates.  The reference semantics; fastest for small n.
* **chunked** (``build_mode="chunked"``) — streaming Lloyd: a blocked
  ``lax.scan`` over ``block_n``-point chunks carrying per-centroid
  ``(sums, counts, inertia)`` accumulators, and a chunked final
  assignment.  Peak per-iteration memory O(2Ns * block_n * max(sqrtK,
  h_max)).  Same update rule as dense; the chunked accumulators sum in a
  different fp order, so over multiple Lloyd iterations points sitting
  exactly on Voronoi boundaries can flip cells (in practice <0.1%; exact
  parity on separated data).  On TPU the pass is the fused Pallas
  ``kmeans_assign_stats`` kernel.  Every pass reads the half-subspace
  points in the kernels' layout, ``(2Ns, h_max, n_pad)`` with the points
  on lanes, written once per build from ``x``
  (:func:`repro.core.subspace.halves_on_lanes`): the build holds ``x``
  and that one copy.
* **minibatch** (``build_mode="minibatch"``) — opt-in approximate mode
  for million-point builds: each K-means step assigns one sampled
  ``block_n`` chunk and applies learning-rate centroid updates; the
  only full-data pass left is the final chunked assignment.

``build_mode="auto"`` (the default) picks dense below ``STREAMING_MIN_N``
points and chunked at or above it, so large builds never materialise an
``(n, sqrtK)`` intermediate.  ``minibatch`` is never auto-selected — it
trades accuracy and must be requested.

Serving (the persistent subsystem on top of the algorithms):

* :meth:`SuCoIndex.save` / :meth:`SuCoIndex.load` persist the index as a
  version-stamped npz artifact (bit-identical round trips; unknown
  versions raise) — :func:`load_index_artifact` also recovers the build
  config.
* :class:`SuCoEngine` owns ``(data, index, EnginePolicy)`` for its
  lifetime and serves ``query(q, k)`` through jitted executables keyed by
  ``(padded batch bucket, k)`` (:func:`batch_bucket`): after
  :meth:`SuCoEngine.warmup` covers the traffic mix, no request can
  retrace.  The dense/streaming/fused dispatch — and the fused path's
  tiling (:class:`repro.core.tuning.TileConfig`) — lives in the policy,
  not on the call; :func:`suco_query` stays as the bit-identical
  back-compat wrapper for one-shot use.  The continuous micro-batching
  server over the engine is :mod:`repro.serve.ann`; the sharded
  counterpart is :class:`repro.distributed.engine.ShardedSuCoEngine`.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import os
import tempfile
import zipfile
import zlib
from typing import Iterable, Mapping, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kmeans
from repro.core import subspace as sub
from repro.core.distances import Metric, pairwise_dist
from repro.core.kmeans import (
    assign_scan,
    block_batched,
    kmeans_batched_t,
    lane_points,
)
from repro.core.sc_linear import (
    QueryResult,
    candidate_pool_size,
    merge_topk_pool,
    merge_topk_pool_with_dists,
    rerank,
    rerank_candidates,
)
from repro.core.tuning import TileConfig, autotune_build_block_n, autotune_tiles
from repro.kernels.gather_rerank.ops import gather_rerank_block
from repro.kernels.sc_score.ops import (
    sc_scores_cells,
    sc_scores_cells_prefilter_compact,
)
from repro.spans import scope, span

__all__ = [
    "SuCoConfig",
    "SuCoIndex",
    "build_index",
    "activate_cells_sorted",
    "dynamic_activation_lax",
    "suco_scores",
    "suco_cell_ranks",
    "suco_query",
    "suco_query_streaming",
    "suco_query_fused",
    "STREAMING_MIN_N",
    "INDEX_ARTIFACT_VERSION",
    "ArtifactError",
    "CapacityError",
    "load_index_artifact",
    "assign_points",
    "EnginePolicy",
    "EngineStats",
    "QueryStats",
    "SuCoEngine",
    "batch_bucket",
    "autoscale_buckets",
    "padding_waste",
    "DEFAULT_BATCH_BUCKETS",
]

# mode="auto" switches from the dense (m, n) score matrix to the tiled
# streaming engine at this dataset size (see module docstring); the index
# build's "auto" switches dense -> chunked Lloyd at the same point.
STREAMING_MIN_N = 32_768

_BUILD_MODES = ("auto", "dense", "chunked", "minibatch")

# SuCoIndex.save/load artifact contract: a plain .npz, tagged and
# version-stamped so a serving process refuses artifacts it cannot trust.
# Version 2 added the optional "tombstone" key (live-mutation deletes);
# version 3 adds per-array content checksums ("crc_<key>") and an optional
# "extra_<name>" block (serving-state sidecar: corpus rows, external key
# table, WAL high-water mark — see repro.serve.durability).  Version-1/-2
# artifacts load unchanged (no checksums to verify, no extras).
_ARTIFACT_MAGIC = "suco-index"
INDEX_ARTIFACT_VERSION = 3
_ARTIFACT_READABLE_VERSIONS = (1, 2, 3)

# Payload keys excluded from content checksumming: both are validated
# semantically before any checksum is looked at (magic match, version
# gate), and tests rewrite them in place to probe those gates.
_ARTIFACT_UNCHECKSUMMED = ("artifact", "version")

#: Prefix for caller-supplied serving-state arrays riding in the artifact.
_ARTIFACT_EXTRA_PREFIX = "extra_"

# Keys every readable artifact must carry (the optional config_* block is
# allowed to be absent; these are not).
_ARTIFACT_REQUIRED_KEYS = (
    "artifact",
    "version",
    "centroids1",
    "centroids2",
    "cell_ids",
    "cell_counts",
    "sqrt_k",
    "spec_d",
    "spec_n_subspaces",
    "spec_perm",
    "spec_bounds",
)


def _array_crc(a: np.ndarray) -> np.uint32:
    """CRC32 over an array's dtype, shape, and raw bytes.

    The content checksum stored per payload array (``crc_<key>``): a
    bit-flip inside the npz member that slips past the zip-level CRC (or a
    rewrite that kept the zip consistent) still fails the load loudly with
    the offending key named, instead of silently serving wrong answers.
    """
    a = np.ascontiguousarray(a)
    h = zlib.crc32(str(a.dtype).encode())
    h = zlib.crc32(repr(a.shape).encode(), h)
    h = zlib.crc32(a.tobytes(), h)
    return np.uint32(h & 0xFFFFFFFF)


class ArtifactError(ValueError):
    """A ``SuCoIndex.save`` artifact could not be loaded.

    Raised with the offending path and what exactly failed — a foreign
    file, a version mismatch (found vs expected), missing keys, or a
    truncated/corrupt payload — instead of leaking a bare ``KeyError`` or
    ``zipfile.BadZipFile`` into a serving process.  Subclasses
    ``ValueError`` so existing ``pytest.raises(ValueError)`` gates and
    caller-side handling keep working.
    """


class CapacityError(ValueError):
    """A mutable :class:`SuCoEngine` ran out of pre-allocated insert slots.

    Raised by :meth:`SuCoEngine.insert` when the batch does not fit in the
    remaining ``capacity`` — the signal for the serving layer to trigger a
    re-index + swap (:mod:`repro.serve.mutation`) onto a larger successor.
    Subclasses ``ValueError`` for uniform caller-side handling.
    """


@dataclasses.dataclass(frozen=True)
class SuCoConfig:
    """Static SuCo hyper-parameters (paper defaults: K=50^2, Ns=8, t=20).

    ``build_mode``/``block_n`` select the index-construction memory model
    (see module docstring): "auto" | "dense" | "chunked" | "minibatch",
    with ``block_n`` the streaming chunk size (and the minibatch sample
    size).  ``block_n=0`` autotunes the chunk from the backend's memory
    limits and the dataset shape
    (:func:`repro.core.tuning.autotune_build_block_n`); any positive value
    pins it by hand.
    """

    n_subspaces: int = 8
    sqrt_k: int = 50
    kmeans_iters: int = 20
    seed: int = 0
    build_mode: str = "auto"
    block_n: int = 4096

    @property
    def n_cells(self) -> int:
        return self.sqrt_k * self.sqrt_k


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SuCoIndex:
    """The SuCo index: centroid codebooks + dense IMI occupancy arrays.

    ``tombstone`` (live mutation, optional): a ``(n,) bool`` mask, True for
    deleted (or not-yet-inserted) slots.  ``None`` — the build/load default
    — means every point is live; the immutable query graphs are unchanged.
    A present mask is threaded through every query path's keep-mask so a
    tombstoned id can never enter a candidate pool.  ``cell_counts`` always
    reflects the *live* points only (deletes decrement it), keeping the
    Dynamic-Activation prefix honest after mutation.
    """

    centroids1: jax.Array  # (Ns, sqrtK, h_max)
    centroids2: jax.Array  # (Ns, sqrtK, h_max)
    cell_ids: jax.Array  # (Ns, n) int32
    cell_counts: jax.Array  # (Ns, K) int32
    spec: sub.SubspaceSpec = dataclasses.field(metadata=dict(static=True))
    sqrt_k: int = dataclasses.field(metadata=dict(static=True))
    tombstone: jax.Array | None = None  # (n,) bool, True = deleted slot

    @property
    def n_cells(self) -> int:
        return self.sqrt_k * self.sqrt_k

    @property
    def n_points(self) -> int:
        return self.cell_ids.shape[1]

    @property
    def n_live(self) -> int:
        """Live (non-tombstoned) point count; ``n_points`` when immutable."""
        if self.tombstone is None:
            return self.n_points
        return self.n_points - int(jnp.sum(self.tombstone))

    def memory_bytes(self) -> int:
        """Index footprint (the paper's `O(sqrt(K) d + n Ns)` claim)."""
        arrays = [self.centroids1, self.centroids2, self.cell_ids, self.cell_counts]
        if self.tombstone is not None:
            arrays.append(self.tombstone)
        return sum(a.size * a.dtype.itemsize for a in arrays)

    # ---- live mutation ---------------------------------------------------

    def insert(self, x_new: jax.Array, *, block_n: int = 4096) -> "SuCoIndex":
        """Append ``x_new: (b, d)`` points, assigned to the existing
        centroids — paper Alg. 2's assignment step only, no re-cluster.

        Returns a new index with ``b`` extra live columns: ``cell_ids``
        grows by the chunked :func:`~repro.core.kmeans.assign_scan`
        assignment (the same pass the streaming build runs per chunk),
        ``cell_counts`` absorbs the new occupancy, and the tombstone mask
        (when present) extends with ``False``.  Ids of existing points are
        stable; the new points get ids ``n_points .. n_points + b - 1``.
        Shapes change, so engines serving a fixed-capacity layout use
        :meth:`SuCoEngine.insert` (slot writes, zero retrace) instead.
        """
        x_new = jnp.asarray(x_new)
        if x_new.ndim == 1:
            x_new = x_new[None]
        if x_new.ndim != 2 or x_new.shape[-1] != self.spec.d:
            raise ValueError(
                f"points must be (b, {self.spec.d}), got {x_new.shape}"
            )
        cells, counts_delta, _ = assign_points(
            x_new, self.centroids1, self.centroids2,
            spec=self.spec, sqrt_k=self.sqrt_k, block_n=block_n,
        )
        tomb = self.tombstone
        if tomb is not None:
            tomb = jnp.concatenate([tomb, jnp.zeros(x_new.shape[0], bool)])
        return dataclasses.replace(
            self,
            cell_ids=jnp.concatenate([self.cell_ids, cells], axis=1),
            cell_counts=self.cell_counts + counts_delta,
            tombstone=tomb,
        )

    def delete(self, ids) -> "SuCoIndex":
        """Tombstone the given point ids (idempotent; duplicate ids fine).

        Returns a new index whose tombstone mask marks the ids deleted and
        whose ``cell_counts`` drops the *newly* deleted points' occupancy —
        re-deleting an already-dead id changes nothing.  Shapes are
        preserved, so a :class:`SuCoEngine` can rebind the result without
        retracing.
        """
        ids = np.unique(np.asarray(ids, dtype=np.int64))
        if ids.size == 0:
            return self
        if ids[0] < 0 or ids[-1] >= self.n_points:
            raise ValueError(
                f"ids must be in [0, {self.n_points}), got range "
                f"[{ids[0]}, {ids[-1]}]"
            )
        ids = jnp.asarray(ids, jnp.int32)
        tomb = (
            jnp.zeros(self.n_points, bool)
            if self.tombstone is None
            else self.tombstone
        )
        newly = jnp.logical_not(tomb[ids])  # idempotence: only live ids count
        tomb = tomb.at[ids].set(True)
        # Drop the newly dead points from the IMI occupancy so the
        # Dynamic-Activation prefix keeps targeting live mass.
        dead_cells = self.cell_ids[:, ids]  # (Ns, b)
        rows = jnp.arange(self.cell_ids.shape[0], dtype=jnp.int32)[:, None]
        w = jnp.broadcast_to(newly.astype(jnp.int32), dead_cells.shape)
        counts = self.cell_counts.at[rows, dead_cells].add(-w)
        return dataclasses.replace(self, cell_counts=counts, tombstone=tomb)

    def save(
        self,
        path,
        config: SuCoConfig | None = None,
        *,
        extras: Mapping[str, np.ndarray] | None = None,
    ) -> None:
        """Persist the index as a version-stamped ``.npz`` artifact.

        The artifact holds the four index arrays byte-exactly, the
        :class:`~repro.core.subspace.SubspaceSpec`, and (when given) the
        build :class:`SuCoConfig` — everything a serving process needs to
        reconstruct the index without the original build.  Round trips are
        bit-identical.  Written via an open file handle so the exact
        ``path`` is honoured (``np.savez`` alone appends ``.npz``).

        Version 3 additions: every payload array gets a ``crc_<key>``
        content checksum (verified on load — a bit-flipped block fails
        loudly naming the key), and ``extras`` rides along as
        ``extra_<name>`` arrays — the serving-state sidecar
        (:mod:`repro.serve.durability` stores the corpus rows, the
        external key table, and the WAL high-water mark there).

        The write is **atomic**: the payload lands in a same-directory
        temp file, is fsynced, and is ``os.replace``d onto ``path`` — a
        crash mid-write can never truncate or corrupt an artifact a
        serving process is about to (re)load.  This is what lets the
        re-index handoff (:mod:`repro.serve.mutation`) publish successor
        artifacts under a live server.
        """
        payload: dict[str, np.ndarray] = {
            "artifact": np.asarray(_ARTIFACT_MAGIC),
            "version": np.asarray(INDEX_ARTIFACT_VERSION, np.int32),
            "centroids1": np.asarray(self.centroids1),
            "centroids2": np.asarray(self.centroids2),
            "cell_ids": np.asarray(self.cell_ids),
            "cell_counts": np.asarray(self.cell_counts),
            "sqrt_k": np.asarray(self.sqrt_k, np.int32),
            "spec_d": np.asarray(self.spec.d, np.int32),
            "spec_n_subspaces": np.asarray(self.spec.n_subspaces, np.int32),
            "spec_perm": np.asarray(self.spec.perm, np.int32),
            "spec_bounds": np.asarray(self.spec.bounds, np.int32),
        }
        if self.tombstone is not None:
            payload["tombstone"] = np.asarray(self.tombstone, np.uint8)
        if config is not None:
            payload.update(
                config_n_subspaces=np.asarray(config.n_subspaces, np.int32),
                config_sqrt_k=np.asarray(config.sqrt_k, np.int32),
                config_kmeans_iters=np.asarray(config.kmeans_iters, np.int32),
                config_seed=np.asarray(config.seed, np.int32),
                config_build_mode=np.asarray(config.build_mode),
                config_block_n=np.asarray(config.block_n, np.int32),
            )
        if extras:
            for name, value in extras.items():
                key = _ARTIFACT_EXTRA_PREFIX + name
                if key in payload:
                    raise ValueError(f"duplicate extras key {name!r}")
                payload[key] = np.asarray(value)
        payload.update(
            {
                f"crc_{k}": _array_crc(v)
                for k, v in list(payload.items())
                if k not in _ARTIFACT_UNCHECKSUMMED
            }
        )
        path = os.fspath(path)
        parent = os.path.dirname(path) or "."
        # Same directory: os.replace is atomic only within a filesystem.
        fd, tmp = tempfile.mkstemp(
            dir=parent, prefix=os.path.basename(path) + ".", suffix=".tmp"
        )
        try:
            with os.fdopen(fd, "wb") as f:
                np.savez(f, **payload)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except BaseException:
            # A failed write must not leave temp litter next to the live
            # artifact; the artifact itself was never touched.
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    @classmethod
    def load(cls, path) -> "SuCoIndex":
        """Load an index artifact written by :meth:`save` (bit-identical)."""
        index, _ = load_index_artifact(path)
        return index


@functools.partial(
    jax.jit,
    static_argnames=("spec", "sqrt_k", "iters", "algo", "block_n", "columns"),
)
def _build(
    x: jax.Array,
    key: jax.Array,
    *,
    spec,
    sqrt_k: int,
    iters: int,
    algo: str = "lloyd",
    block_n: int = 0,
    columns: bool = False,
):
    ns = spec.n_subspaces
    n = x.shape[0]
    with scope("halves"):
        # One pass over x into the layout every K-means pass streams:
        # (2Ns, h_max, n_pad), points on lanes, padded once to the passes'
        # chunk — no pass copies, transposes or re-pads the points.
        n_pad = lane_points(
            n, 2 * ns, spec.max_half_size, sqrt_k, algo=algo, block_n=block_n
        )
        xt = sub.halves_on_lanes(
            spec, x, n_pad, kernel=kmeans._use_pallas("auto"), columns=columns
        )
    # block_n=0 is the dense reference; >0 streams every K-means pass —
    # including the final assignment feeding cell_ids — in block_n chunks.
    # pair_sqrt_k fuses the IMI occupancy histogram into that final
    # assignment scan, so cell_counts costs no extra pass over the data.
    res = kmeans_batched_t(
        key, xt, sqrt_k, iters, n=n, algo=algo, block_n=block_n,
        pair_sqrt_k=sqrt_k,
    )
    a1, a2 = res.assignments[:ns], res.assignments[ns:]
    cell_ids = (a1 * sqrt_k + a2).astype(jnp.int32)  # (Ns, n)
    if res.cell_counts is not None:
        counts = res.cell_counts
    else:  # minibatch TPU final assignment (stats kernel) does not fuse it
        counts = jax.vmap(
            lambda c: jnp.bincount(c, length=sqrt_k * sqrt_k).astype(jnp.int32)
        )(cell_ids)
    return res.centroids[:ns], res.centroids[ns:], cell_ids, counts


def _stored_by_columns(x) -> bool:
    """Whether the device holds ``x (n, d)`` column-major: the TPU picks
    that layout for a corpus it would otherwise pad, as 1M x 960 f32 (960
    is no multiple of 128 lanes).  Tracers and host arrays say no."""
    layout = getattr(getattr(x, "format", None), "layout", None)
    return getattr(layout, "major_to_minor", None) == (1, 0)


def build_index(x: jax.Array, config: SuCoConfig, *, spec: sub.SubspaceSpec | None = None) -> SuCoIndex:
    """Algorithm 2.  ``x: (n, d)``; deterministic given ``config.seed``.

    ``config.build_mode`` picks the construction memory model ("auto"
    selects chunked at or above ``STREAMING_MIN_N`` points — see module
    docstring); dense and chunked run the same update rule and agree up
    to fp summation order (boundary points can differ after many
    iterations).
    """
    with span("build.plan"):
        if spec is None:
            spec = sub.contiguous_spec(x.shape[-1], config.n_subspaces)
        mode = config.build_mode
        if mode not in _BUILD_MODES:
            raise ValueError(
                f"unknown build_mode {mode!r}, expected one of {_BUILD_MODES}"
            )
        if mode == "auto":
            mode = "chunked" if x.shape[0] >= STREAMING_MIN_N else "dense"
        if mode != "dense" and config.block_n < 0:
            raise ValueError(
                f"build_mode={mode!r} requires block_n >= 0 (0 = autotune), "
                f"got {config.block_n}"
            )
        algo = "minibatch" if mode == "minibatch" else "lloyd"
        if mode == "dense":
            block_n = 0
        elif config.block_n == 0:  # autotune from backend limits + data shape
            block_n = autotune_build_block_n(
                x.shape[0],
                x.shape[-1],
                sqrt_k=config.sqrt_k,
                n_subspaces=config.n_subspaces,
            )
        else:
            block_n = config.block_n
    with span("build.dispatch"):
        key = jax.random.key(config.seed)
        c1, c2, cell_ids, counts = _build(
            x,
            key,
            spec=spec,
            sqrt_k=config.sqrt_k,
            iters=config.kmeans_iters,
            algo=algo,
            block_n=block_n,
            columns=_stored_by_columns(x),
        )
    return SuCoIndex(c1, c2, cell_ids, counts, spec=spec, sqrt_k=config.sqrt_k)


@functools.partial(jax.jit, static_argnames=("spec", "sqrt_k", "block_n"))
def assign_points(
    x_new: jax.Array,
    centroids1: jax.Array,
    centroids2: jax.Array,
    *,
    spec: sub.SubspaceSpec,
    sqrt_k: int,
    block_n: int = 4096,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Assign ``x_new: (b, d)`` to existing centroids, chunked.

    The incremental-insert core: exactly the build's final-assignment pass
    (:func:`repro.core.kmeans.assign_scan` with the fused IMI histogram)
    over just the new points.  Returns ``(cell_ids (Ns, b) int32,
    counts_delta (Ns, K) int32, inertia () f32)`` — the occupancy delta to
    add to ``cell_counts`` and the new points' assignment inertia (the
    drift monitor's statistic: rising per-point insert inertia vs. the
    build baseline means the centroids no longer describe the data).
    """
    ns = spec.n_subspaces
    b = x_new.shape[0]
    xp = sub.permute(spec, x_new)
    h1, h2 = sub.split_halves_padded(spec, xp)  # 2 x (Ns, b, h_max)
    both = jnp.concatenate([h1, h2], axis=0)  # (2Ns, b, h_max)
    cents = jnp.concatenate([centroids1, centroids2], axis=0)
    blocks, valid = block_batched(both, block_n)
    a, inertia, counts = assign_scan(blocks, valid, cents, pair_sqrt_k=sqrt_k)
    a = a[:, :b]
    cells = (a[:ns] * sqrt_k + a[ns:]).astype(jnp.int32)  # (Ns, b)
    return cells, counts, jnp.sum(inertia)


def load_index_artifact(
    path, *, return_extras: bool = False
) -> tuple[SuCoIndex, SuCoConfig | None] | tuple[
    SuCoIndex, SuCoConfig | None, dict[str, np.ndarray]
]:
    """Load a ``SuCoIndex.save`` artifact -> ``(index, build config | None)``.

    Validates the artifact tag, version, and key inventory before touching
    any payload; an unknown version, a foreign npz, missing keys, or a
    truncated/corrupt file raises :class:`ArtifactError` (a ``ValueError``)
    naming the path and the found-vs-expected state instead of leaking a
    bare ``KeyError``/``BadZipFile`` into a serving process.

    Version-3 artifacts additionally carry per-array content checksums
    (``crc_<key>``): every checksummed array is verified before anything is
    returned, and a mismatch — a bit-flip the zip layer did not catch, or a
    tampered rewrite — raises :class:`ArtifactError` naming the offending
    key.  Pre-checksum artifacts (v1/v2) load with no verification, as
    before.  With ``return_extras=True`` the result is a 3-tuple whose last
    element maps each ``extra_<name>`` sidecar array (serving state written
    by :mod:`repro.serve.durability`) back to ``name``.
    """
    try:
        z = np.load(path, allow_pickle=False)
    except (ValueError, OSError, EOFError, zipfile.BadZipFile) as e:
        # A file truncated before the zip central directory fails here
        # (BadZipFile) rather than at member read time.
        raise ArtifactError(
            f"{path!s}: not a readable npz ({type(e).__name__}: {e})"
        ) from e
    with z:
        names = set(z.files)
        if "artifact" not in names or str(z["artifact"][()]) != _ARTIFACT_MAGIC:
            raise ArtifactError(f"{path!s} is not a {_ARTIFACT_MAGIC} artifact")
        missing = [k for k in _ARTIFACT_REQUIRED_KEYS if k not in names]
        if missing:
            raise ArtifactError(
                f"{path!s}: {_ARTIFACT_MAGIC} artifact is missing keys "
                f"{missing} (found {sorted(names)}) — truncated or "
                "incompletely written file"
            )
        try:
            version = int(z["version"][()])
            if version not in _ARTIFACT_READABLE_VERSIONS:
                raise ArtifactError(
                    f"{path!s}: unsupported {_ARTIFACT_MAGIC} artifact version "
                    f"{version} (this build reads version "
                    f"{INDEX_ARTIFACT_VERSION})"
                )
            # Content checksums (v3): verify BEFORE constructing anything —
            # a serving process must never adopt a bit-flipped centroid
            # block.  Pre-checksum artifacts simply carry no crc_* keys.
            for key in sorted(names):
                if key.startswith("crc_") or f"crc_{key}" not in names:
                    continue
                stored = int(z[f"crc_{key}"][()])
                computed = int(_array_crc(z[key]))
                if computed != stored:
                    raise ArtifactError(
                        f"{path!s}: content checksum mismatch on key "
                        f"{key!r} (stored 0x{stored:08x}, computed "
                        f"0x{computed:08x}) — bit-flipped or tampered "
                        "artifact"
                    )
            spec = sub.SubspaceSpec(
                d=int(z["spec_d"][()]),
                n_subspaces=int(z["spec_n_subspaces"][()]),
                perm=tuple(int(p) for p in z["spec_perm"]),
                bounds=tuple(int(b) for b in z["spec_bounds"]),
            )
            # "tombstone" is the one version-2 key; absent (every v1
            # artifact, and v2 saves of never-mutated indexes) means all
            # points are live.
            tombstone = (
                jnp.asarray(z["tombstone"].astype(bool))
                if "tombstone" in names
                else None
            )
            index = SuCoIndex(
                centroids1=jnp.asarray(z["centroids1"]),
                centroids2=jnp.asarray(z["centroids2"]),
                cell_ids=jnp.asarray(z["cell_ids"]),
                cell_counts=jnp.asarray(z["cell_counts"]),
                spec=spec,
                sqrt_k=int(z["sqrt_k"][()]),
                tombstone=tombstone,
            )
            config = None
            if "config_n_subspaces" in names:
                config = SuCoConfig(
                    n_subspaces=int(z["config_n_subspaces"][()]),
                    sqrt_k=int(z["config_sqrt_k"][()]),
                    kmeans_iters=int(z["config_kmeans_iters"][()]),
                    seed=int(z["config_seed"][()]),
                    build_mode=str(z["config_build_mode"][()]),
                    block_n=int(z["config_block_n"][()]),
                )
            extras: dict[str, np.ndarray] = {}
            if return_extras:
                extras = {
                    k[len(_ARTIFACT_EXTRA_PREFIX):]: z[k]
                    for k in names
                    if k.startswith(_ARTIFACT_EXTRA_PREFIX)
                }
        except ArtifactError:
            raise
        except Exception as e:
            # A member listed in the directory but truncated mid-payload
            # (zlib error, zipfile CRC failure, short read) surfaces here.
            raise ArtifactError(
                f"{path!s}: {_ARTIFACT_MAGIC} artifact payload is corrupt "
                f"({type(e).__name__}: {e}) — truncated file?"
            ) from e
    if return_extras:
        return index, config, extras
    return index, config


# --------------------------------------------------------------------------
# Dynamic Activation
# --------------------------------------------------------------------------


def _cell_ranks_and_cut(
    dists1: jax.Array, dists2: jax.Array, cell_counts: jax.Array, target: int
) -> tuple[jax.Array, jax.Array]:
    """Dynamic Activation as (per-cell rank, cutoff rank).

    ``rank[c]`` is cell c's position in ascending ``dists1+dists2`` order
    (ties by cell id — stable argsort) and ``cut`` the last rank inside
    the minimal prefix whose cumulative count reaches ``target``; the
    activation mask is ``rank <= cut``.  This split form feeds the chunked
    score kernel, which gathers ranks by cell id and compares to the cut.
    """
    cell_dist = (dists1[:, None] + dists2[None, :]).reshape(-1)  # (K,)
    order = jnp.argsort(cell_dist)  # stable -> ties by cell id
    csum = jnp.cumsum(jnp.take(cell_counts, order))
    # First prefix position reaching the target (or everything if impossible).
    reached = csum >= target
    cut = jnp.where(jnp.any(reached), jnp.argmax(reached), csum.shape[0] - 1)
    rank = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
    return rank.astype(jnp.int32), cut.astype(jnp.int32)


def activate_cells_sorted(
    dists1: jax.Array, dists2: jax.Array, cell_counts: jax.Array, target: int
) -> jax.Array:
    """TPU-native Dynamic Activation: exact sort-prefix equivalent of Alg. 3.

    ``dists1/dists2: (sqrtK,)``, ``cell_counts: (K,)`` (row-major over
    ``(c1, c2)``).  Returns a ``(K,)`` bool mask of activated cells: the
    minimal ascending-distance prefix whose cumulative count reaches
    ``target`` — exactly the Multi-sequence / Dynamic-Activation set.
    """
    rank, cut = _cell_ranks_and_cut(dists1, dists2, cell_counts, target)
    return rank <= cut


def dynamic_activation_lax(
    dists1: jax.Array, dists2: jax.Array, cell_counts: jax.Array, target: int
) -> jax.Array:
    """Faithful ``lax.while_loop`` port of paper Algorithm 3.

    Kept for fidelity/testing; the production path is
    :func:`activate_cells_sorted`.  Returns the same ``(K,)`` bool mask.
    """
    k1 = dists1.shape[0]
    k2 = dists2.shape[0]
    idx1 = jnp.argsort(dists1)
    idx2 = jnp.argsort(dists2)
    s1 = jnp.take(dists1, idx1)
    s2 = jnp.take(dists2, idx2)
    counts2d = cell_counts.reshape(k1, k2)

    inf = jnp.asarray(jnp.inf, dists1.dtype)
    state = (
        jnp.zeros(k1, jnp.int32),  # active_idx (column per row)
        jnp.full((k1,), inf).at[0].set(s1[0] + s2[0]),  # active_dists
        jnp.zeros(k1 * k2, bool),  # retrieved mask (over original cell ids)
        jnp.asarray(0, jnp.int32),  # retrieved_num
    )

    def cond(st):
        _, ad, _, got = st
        return jnp.logical_and(got < target, jnp.any(jnp.isfinite(ad)))

    def body(st):
        ai, ad, mask, got = st
        pos = jnp.argmin(ad)
        col = ai[pos]
        c1 = idx1[pos]
        c2 = idx2[col]
        mask = mask.at[c1 * k2 + c2].set(True)
        got = got + counts2d[c1, c2]
        # Activate next row iff this row was popped at column 0 (Alg.3 l.12).
        do_spawn = jnp.logical_and(col == 0, pos < k1 - 1)
        nxt = jnp.minimum(pos + 1, k1 - 1)
        ad = jnp.where(do_spawn, ad.at[nxt].set(s1[nxt] + s2[0]), ad)
        ai = jnp.where(do_spawn, ai.at[nxt].set(0), ai)
        # Advance this row (Alg.3 l.15-17) or retire it.
        can_adv = col < k2 - 1
        newcol = jnp.minimum(col + 1, k2 - 1)
        ad = ad.at[pos].set(jnp.where(can_adv, s1[pos] + s2[newcol], inf))
        ai = ai.at[pos].set(jnp.where(can_adv, newcol, col))
        return ai, ad, mask, got

    _, _, mask, _ = jax.lax.while_loop(cond, body, state)
    return mask


# --------------------------------------------------------------------------
# Query (Algorithm 4)
# --------------------------------------------------------------------------


def _centroid_dists(
    index: SuCoIndex, q: jax.Array, metric: Metric
) -> tuple[jax.Array, jax.Array]:
    """``q: (m, d)`` -> per-subspace query-to-centroid distances
    ``(Ns, m, sqrtK)`` for each half."""
    qp = sub.permute(index.spec, q)
    qh1, qh2 = sub.split_halves_padded(index.spec, qp)  # (Ns, m, h_max)
    # impl="rowwise": centroid distances must be invariant to batch padding
    # (they order the Dynamic-Activation prefix) so a SuCoEngine bucket
    # activates exactly the cells the unpadded batch would.
    d1 = jax.vmap(
        lambda qq, cc: pairwise_dist(qq, cc, metric, impl="rowwise")
    )(qh1, index.centroids1)
    d2 = jax.vmap(
        lambda qq, cc: pairwise_dist(qq, cc, metric, impl="rowwise")
    )(qh2, index.centroids2)
    return d1, d2


def suco_scores(
    index: SuCoIndex,
    q: jax.Array,
    count: int,
    metric: Metric = "l2",
) -> jax.Array:
    """``q: (m, d) -> (m, n)`` int32 SC-scores via the IMI (Alg. 4 l.3-12).

    Scans over subspaces; per subspace the per-point collision test is a
    rank-gather: point j collides iff its cell is inside the activated
    prefix.
    """
    d1, d2 = _centroid_dists(index, q, metric)  # (Ns, m, sqrtK)
    m = q.shape[0]
    n = index.n_points

    def per_subspace(acc, inp):
        d1_i, d2_i, cells_i, counts_i = inp  # (m,sK),(m,sK),(n,),(K,)

        def per_query(d1_q, d2_q):
            mask = activate_cells_sorted(d1_q, d2_q, counts_i, count)  # (K,)
            return jnp.take(mask, cells_i)  # (n,) bool

        collide = jax.vmap(per_query)(d1_i, d2_i)  # (m, n)
        return acc + collide.astype(jnp.int32), None

    init = jnp.zeros((m, n), jnp.int32)
    scores, _ = jax.lax.scan(
        init=init,
        xs=(d1, d2, index.cell_ids, index.cell_counts),
        f=per_subspace,
    )
    return scores


def suco_cell_ranks(
    index: SuCoIndex, q: jax.Array, count: int, metric: Metric = "l2"
) -> tuple[jax.Array, jax.Array]:
    """Per-(subspace, query) Dynamic-Activation state for chunked scoring.

    ``q: (m, d) -> (ranks (Ns, m, K) int32, cuts (Ns, m) int32)`` — the
    split form of :func:`activate_cells_sorted` (mask == rank <= cut).
    O(Ns * m * K) memory, independent of n.
    """
    d1, d2 = _centroid_dists(index, q, metric)  # (Ns, m, sqrtK)

    def per_sub(d1_i, d2_i, counts_i):
        return jax.vmap(
            lambda a, b: _cell_ranks_and_cut(a, b, counts_i, count)
        )(d1_i, d2_i)

    return jax.vmap(per_sub)(d1, d2, index.cell_counts)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QueryStats:
    """Counters of one fused-query execution over its whole padded batch.

    ``overflow_chunks``: chunks in which some row's survivors overflowed
    ``survivor_cap``, so the scan took the exact fallback for the whole
    chunk.  ``overflow_rows``: (row, chunk) pairs whose own survivors
    overflowed — the part of that fallback a per-row one would still do.
    ``rerank_rows``: rows of ``x`` the scan gathered to rerank — ``m *
    survivor_cap`` per pruned chunk, ``m * min(pool, block_n)`` per
    fallback chunk, over the padded batch of ``m`` rows.  ``rerank_live``:
    those of them that held a live candidate (a survivor slot below the
    row's survivor count, a fallback pick that is a real column); the rest
    were gathered for a dead slot.  ``slot_windows``: the 128-slot windows
    the score kernel's survivor write built a one-hot for, and
    ``slot_writes``: the (row, column block) pairs that wrote any slot,
    over every chunk; each such pair once cost a one-hot over all
    ``slot_cap / 128`` windows.  ``chunks`` and ``rows`` are the scan's
    static extent, ``slot_cap`` the kernel's lane-rounded survivor cap.
    """

    overflow_chunks: jax.Array  # () int32
    overflow_rows: jax.Array  # () int32
    rerank_rows: jax.Array  # () int32
    rerank_live: jax.Array  # () int32
    slot_windows: jax.Array  # () int32
    slot_writes: jax.Array  # () int32
    chunks: int = dataclasses.field(metadata=dict(static=True))
    rows: int = dataclasses.field(metadata=dict(static=True))
    slot_cap: int = dataclasses.field(metadata=dict(static=True))


class EngineResult(NamedTuple):
    """What the engine's executable returns: the answer and the fused
    scan's :class:`QueryStats` (``None`` on the dense and streaming paths)."""

    ids: jax.Array
    dists: jax.Array
    scores: jax.Array
    stats: QueryStats | None = None


def _pool_size(n: int, k: int, beta: float) -> int:
    """Candidate-pool size — the shared clamped form
    (:func:`repro.core.sc_linear.candidate_pool_size`)."""
    return candidate_pool_size(n, k, beta)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "alpha", "beta", "metric", "block_n", "score_impl", "merge_impl"
    ),
)
def suco_query_streaming(
    x: jax.Array,
    index: SuCoIndex,
    q: jax.Array,
    *,
    k: int,
    alpha: float,
    beta: float,
    metric: Metric = "l2",
    block_n: int = 4096,
    score_impl: str = "auto",
    merge_impl: str = "auto",
) -> QueryResult:
    """Algorithm 4 as a tiled streaming engine — bit-identical to the dense
    path, peak query memory O(m*(block_n + n_candidates)).

    A ``lax.scan`` over ceil(n / block_n) data chunks: per chunk the
    collision counts come from the chunked SC-score kernel path
    (:func:`sc_scores_cells`), and a carried per-query top pool is merged
    under the (score desc, id asc) order — ``merge_impl`` picks the merge
    algorithm (:func:`repro.core.sc_linear.merge_topk_pool`; "auto"
    resolves to the two-key sort on the TPU and elsewhere to the
    counting-select over the integer ``0..Ns`` score range, bit-identical
    to ``top_k``).  After the scan the pool equals
    the dense ``top_k(scores, n_candidates)`` selection exactly (sentinels
    at score -1 / id INT32_MAX lose to every real point), so the exact
    re-rank returns the same ids/distances as :func:`suco_query`.
    """
    if block_n < 1:
        raise ValueError(f"block_n must be >= 1, got {block_n}")
    n = x.shape[0]
    if k > n:
        # the dense path raises from top_k here; without this the pool would
        # keep (score -1, id INT32_MAX) sentinels and leak them into ids.
        raise ValueError(f"k={k} must be <= n={n}")
    m = q.shape[0]
    c = sub.collision_count(n, alpha)
    ranks, cuts = suco_cell_ranks(index, q, c, metric)  # (Ns,m,K), (Ns,m)
    pool = _pool_size(n, k, beta)

    bn = min(block_n, n)
    n_blocks = -(-n // bn)
    int_max = jnp.iinfo(jnp.int32).max
    cells = jnp.pad(index.cell_ids, ((0, 0), (0, n_blocks * bn - n)))
    cells = cells.reshape(cells.shape[0], n_blocks, bn).transpose(1, 0, 2)
    # Tombstones ride the scan as a per-chunk keep mask; an index without
    # them (tombstone=None — a zero-leaf pytree entry) scans the identical
    # immutable graph.
    keep_blocks = None
    if index.tombstone is not None:
        keepp = jnp.pad(
            jnp.logical_not(index.tombstone), (0, n_blocks * bn - n)
        )
        keep_blocks = keepp.reshape(n_blocks, bn)

    def step(carry, inp):
        pool_s, pool_i = carry
        blk, cells_b, keep_b = inp  # (), (Ns, bn), (bn,) | None
        s = sc_scores_cells(ranks, cuts, cells_b, impl=score_impl)  # (m, bn)
        gids = blk * bn + jnp.arange(bn, dtype=jnp.int32)
        valid = gids < n  # mask chunk padding past the end of the data
        if keep_b is not None:
            valid = jnp.logical_and(valid, keep_b)  # and tombstoned slots
        s = jnp.where(valid[None, :], s, -1)
        ids_b = jnp.broadcast_to(jnp.where(valid, gids, int_max), (m, bn))
        merged = merge_topk_pool(
            pool_s, pool_i, s, ids_b,
            impl=merge_impl, smax=index.spec.n_subspaces,
        )
        return merged, None

    init = (
        jnp.full((m, pool), -1, jnp.int32),
        jnp.full((m, pool), int_max, jnp.int32),
    )
    (pool_s, pool_i), _ = jax.lax.scan(
        step, init, (jnp.arange(n_blocks, dtype=jnp.int32), cells, keep_blocks)
    )
    return rerank_candidates(x, q, pool_i, pool_s, k, metric)


_FUSED_STATIC = (
    "k", "alpha", "beta", "metric", "tiles", "score_impl", "merge_impl"
)


@functools.partial(jax.jit, static_argnames=_FUSED_STATIC)
def suco_query_fused(
    x: jax.Array,
    index: SuCoIndex,
    q: jax.Array,
    *,
    k: int,
    alpha: float,
    beta: float,
    metric: Metric = "l2",
    tiles: TileConfig | None = None,
    score_impl: str = "auto",
    merge_impl: str = "auto",
) -> QueryResult:
    """Algorithm 4 as a **single-pass fused engine**: score -> prune ->
    merge -> rerank in one scan over the data, bit-identical to the dense
    path.

    Per ``block_n``-point chunk, while the chunk is resident:

    1. **score + prune, one launch** — the fused chunk stage
       (:func:`repro.kernels.sc_score.ops.sc_scores_cells_prefilter_compact`)
       computes SC-scores, the Pareto prefilter, *and* the survivor
       compaction in a single kernel: only rows whose score beats the
       carried pool minimum can possibly enter the pool (pool entries
       with equal score always win the (score desc, id asc) tie-break,
       having strictly smaller ids under the streaming invariant), and
       the survivors come back already compacted into a
       ``survivor_cap``-wide buffer in ascending-id order — in-kernel
       prefix count + windowed one-hot slot writes while the score tile
       is resident, so no sort, scatter, or second pass ever touches the
       ``(m, block_n)`` block and the merge's lexicographic tie-break is
       preserved bit-for-bit.  (The jnp oracle — the production CPU
       path — runs the identical compaction as a binary search on the
       keep-mask's cumsum.)
    2. **rerank in-pass** — exact distances for the survivors — O(cap)
       rows of ``x`` per chunk, the rows just scored — are gathered by
       global id (:func:`repro.kernels.gather_rerank.ops.gather_rerank_block`,
       same fp reduction as :func:`repro.core.sc_linear.rerank_candidates`);
       ``x`` itself is never padded, copied, or streamed through the scan.
    3. **merge** — the joint ``(sc_score, exact_dist, id)`` pool merges at
       width ``pool + survivor_cap`` instead of ``pool + block_n``
       (:func:`repro.core.sc_linear.merge_topk_pool_with_dists`;
       ``merge_impl`` selects the algorithm, "auto" resolving to the
       two-key sort on the TPU and elsewhere to the counting-select over
       the integer ``0..Ns`` score range).

    A chunk whose survivor count exceeds ``survivor_cap`` for any query
    (cold pool on the first chunks, adversarial score ties) falls back via
    ``lax.cond`` to an exact ``top_k`` selection of the chunk's own best
    ``min(pool, block_n)`` rows (the merged pool can absorb at most
    ``pool`` of them, so this is bit-identical to merging the whole
    chunk) — slower, identical results, so the fast path's pruning can
    never change an answer.  After the scan the answer is one ``top_k``
    over the carried distances; the post-scan rerank gather over ``x`` of
    the legacy streaming path does not exist.

    ``tiles=None`` autotunes ``(block_n, bm, bn, survivor_cap)`` from the
    backend memory limits and ``(n, d, m, pool)``
    (:func:`repro.core.tuning.autotune_tiles`); pass an explicit
    :class:`~repro.core.tuning.TileConfig` to pin them.
    """
    return _fused_query(
        x, index, q, k=k, alpha=alpha, beta=beta, metric=metric, tiles=tiles,
        score_impl=score_impl, merge_impl=merge_impl,
    )[0]


@functools.partial(jax.jit, static_argnames=_FUSED_STATIC)
def _fused_query(
    x: jax.Array,
    index: SuCoIndex,
    q: jax.Array,
    *,
    k: int,
    alpha: float,
    beta: float,
    metric: Metric,
    tiles: TileConfig | None,
    score_impl: str,
    merge_impl: str,
) -> tuple[QueryResult, QueryStats]:
    """:func:`suco_query_fused` and the :class:`QueryStats` of its scan."""
    n, d = x.shape
    if k > n:
        raise ValueError(f"k={k} must be <= n={n}")
    m = q.shape[0]
    pool = _pool_size(n, k, beta)
    if tiles is None:
        tiles = autotune_tiles(
            n, d, m, pool,
            n_subspaces=index.spec.n_subspaces,
            n_cells=index.n_cells,
            itemsize=x.dtype.itemsize,
        )
    c = sub.collision_count(n, alpha)
    with scope("activate"):
        ranks, cuts = suco_cell_ranks(index, q, c, metric)  # (Ns,m,K), (Ns,m)

    bn = min(tiles.block_n, n)
    cap = min(tiles.survivor_cap, bn)
    n_blocks = -(-n // bn)
    int_max = jnp.iinfo(jnp.int32).max
    cells = jnp.pad(index.cell_ids, ((0, 0), (0, n_blocks * bn - n)))
    cells = cells.reshape(cells.shape[0], n_blocks, bn).transpose(1, 0, 2)
    # Tombstones fold into the fused stage's existing keep-mask (the
    # Pareto prefilter) — no new kernel; tombstone=None traces the
    # identical immutable graph (None contributes no scan leaves).
    keep_blocks = None
    if index.tombstone is not None:
        keepp = jnp.pad(
            jnp.logical_not(index.tombstone), (0, n_blocks * bn - n)
        )
        keep_blocks = keepp.reshape(n_blocks, bn)
    dist_dtype = (
        jnp.float32 if metric == "l2" else jnp.result_type(x.dtype, q.dtype)
    )
    inf = jnp.asarray(jnp.inf, dist_dtype)
    cols = jnp.arange(bn, dtype=jnp.int32)
    slot = jnp.arange(cap, dtype=jnp.int32)

    def step(carry, inp):
        pool_s, pool_d, pool_i = carry
        blk, cells_b, keep_b = inp  # (), (Ns, bn), (bn,) | None
        thr = pool_s[:, -1]  # pool sorted desc -> last col is the minimum
        limit = jnp.minimum(n - blk * bn, bn)  # valid columns this chunk
        with scope("score"):
            s, surv_c, surv_s, total, windows, writes = (
                sc_scores_cells_prefilter_compact(
                    ranks, cuts, cells_b, thr, limit, keep_b,
                    cap=cap, bm=tiles.bm, bn=tiles.bn, impl=score_impl,
                )
            )  # (m, bn), (m, cap), (m, cap), (m,) x 3 — int32, s pre-masked
        gids = blk * bn + cols
        col_ok = cols < limit
        if keep_b is not None:
            # Tombstoned columns must not enter the overflow fallback's
            # top_k either: sentinel ids make their distances +inf below.
            col_ok = jnp.logical_and(col_ok, keep_b)
        ids_b = jnp.broadcast_to(jnp.where(col_ok, gids, int_max), (m, bn))

        def pruned_merge(_):
            # The kernel already compacted the survivors into cap slots in
            # ascending-id order while the score tile was resident — the
            # host graph only rebuilds global ids from the chunk-local
            # columns and masks empty slots to the sentinels.  A slot is
            # live iff it is below the survivor count AND carries a real
            # (>= 0) score — the second clause is vacuous for immutable
            # indexes (survivors beat thr >= -1) and masks the Pallas
            # path's post-hoc tombstoned survivors under mutation.
            with scope("rerank"):
                live = jnp.logical_and(
                    slot[None, :] < total[:, None], surv_s >= 0
                )
                surv_i = jnp.where(live, blk * bn + surv_c, int_max)
                surv_sm = jnp.where(live, surv_s, -1)
                # survivors only ever touch O(cap) rows of x per chunk —
                # the rows just scored, fetched by global id (the op clips
                # the int_max sentinels; their distances are masked to
                # +inf).  impl="jnp" pins the fp reduction to
                # rerank_candidates' rowwise contract on every backend; the
                # Pallas gather kernel stays opt-in until a real-TPU run
                # proves it ulp-identical.
                dists = gather_rerank_block(
                    surv_i, x, q, metric=metric, impl="jnp"
                )
                dists = jnp.where(live, dists, inf)
            with scope("merge"):
                merged = merge_topk_pool_with_dists(
                    pool_s, pool_d, pool_i, surv_sm, dists, surv_i,
                    impl=merge_impl, smax=index.spec.n_subspaces,
                )
            return merged, jnp.sum(live, dtype=jnp.int32)

        def full_merge(_):
            # Exact overflow fallback: the merged top-pool can absorb at
            # most `pool` chunk rows, so selecting the chunk's own top
            # min(pool, bn) by (score desc, id asc) — lax.top_k's position
            # tie-break on ascending-id columns — before merging is
            # bit-identical to merging the whole chunk, at an O(bn)
            # selection instead of an O(pool + bn) one, with distances for
            # `pool` rows instead of `bn`.
            c = min(pool, bn)
            with scope("fallback"):
                top_s, top_pos = jax.lax.top_k(s, c)
                top_i = jnp.take_along_axis(ids_b, top_pos, axis=-1)
                dists = gather_rerank_block(
                    top_i, x, q, metric=metric, impl="jnp"
                )
                dists = jnp.where(top_i == int_max, inf, dists)
                merged = merge_topk_pool_with_dists(
                    pool_s, pool_d, pool_i, top_s, dists, top_i,
                    impl=merge_impl, smax=index.spec.n_subspaces,
                )
                return merged, jnp.sum(top_i != int_max, dtype=jnp.int32)

        over = total > cap  # (m,) rows whose survivors overflow the cap
        overflow = jnp.any(over)
        merged, live_rows = jax.lax.cond(overflow, full_merge, pruned_merge, None)
        counts = (
            overflow, jnp.sum(over, dtype=jnp.int32), live_rows,
            jnp.sum(windows, dtype=jnp.int32), jnp.sum(writes, dtype=jnp.int32),
        )
        return merged, counts

    init = (
        jnp.full((m, pool), -1, jnp.int32),
        jnp.full((m, pool), inf, dist_dtype),
        jnp.full((m, pool), int_max, jnp.int32),
    )
    (pool_s, pool_d, pool_i), counts = jax.lax.scan(
        step, init, (jnp.arange(n_blocks, dtype=jnp.int32), cells, keep_blocks)
    )
    over_chunk, over_rows, live_rows, windows, writes = counts
    # Final selection == rerank_candidates' top_k on the carried pool:
    # ascending distance, ties to the earlier (score desc, id asc) slot.
    with scope("select"):
        neg, pos = jax.lax.top_k(-pool_d, k)
        res = QueryResult(
            jnp.take_along_axis(pool_i, pos, axis=-1).astype(jnp.int32),
            -neg,
            jnp.take_along_axis(pool_s, pos, axis=-1),
        )
    # Rows of x each chunk gathered to rerank: every survivor slot of the
    # pruned path, the chunk's own top min(pool, bn) on the fallback.
    gathered = jnp.where(over_chunk, m * min(pool, bn), m * cap)
    stats = QueryStats(
        overflow_chunks=jnp.sum(over_chunk, dtype=jnp.int32),
        overflow_rows=jnp.sum(over_rows, dtype=jnp.int32),
        rerank_rows=jnp.sum(gathered, dtype=jnp.int32),
        rerank_live=jnp.sum(live_rows, dtype=jnp.int32),
        slot_windows=jnp.sum(windows, dtype=jnp.int32),
        slot_writes=jnp.sum(writes, dtype=jnp.int32),
        chunks=n_blocks,
        rows=m,
        slot_cap=-(-cap // 128) * 128,  # the kernel rounds cap up to lanes
    )
    return res, stats


_QUERY_STATIC = (
    "k", "alpha", "beta", "metric", "mode", "block_n", "score_impl", "tiles",
    "merge_impl",
)


@functools.partial(jax.jit, static_argnames=_QUERY_STATIC)
def suco_query(
    x: jax.Array,
    index: SuCoIndex,
    q: jax.Array,
    *,
    k: int,
    alpha: float,
    beta: float,
    metric: Metric = "l2",
    mode: str = "auto",
    block_n: int = 4096,
    score_impl: str = "auto",
    tiles: TileConfig | None = None,
    merge_impl: str = "auto",
) -> QueryResult:
    """Algorithm 4: k-ANN for a batch ``q: (m, d)`` using the SuCo index.

    ``mode``: "dense" | "streaming" | "fused" | "auto" (fused iff
    n >= ``STREAMING_MIN_N``); all paths return bit-identical results —
    see the module docstring for the memory models.  ``score_impl``
    ("auto" | "jnp" | "pallas") overrides the chunked scorer's kernel
    dispatch (:func:`sc_scores_cells` / the fused prefilter stage); the
    dense path is jnp-only and ignores it.  ``block_n`` sizes the legacy
    streaming path's chunks; the fused path tiles itself from ``tiles``
    (``None`` = autotune, see :func:`repro.core.tuning.autotune_tiles`).
    ``merge_impl`` ("auto" | "topk" | "sort" | "counting") selects the
    pool-merge algorithm for the streaming/fused paths
    (:func:`repro.core.sc_linear.merge_topk_pool`); every impl is
    bit-identical, and "auto" resolves to the two-key sort on the TPU and
    elsewhere to the counting-select over the integer ``0..Ns`` score
    range.  The dense path ignores it.
    """
    res = _query(
        x, index, q, k=k, alpha=alpha, beta=beta, metric=metric, mode=mode,
        block_n=block_n, score_impl=score_impl, tiles=tiles,
        merge_impl=merge_impl,
    )
    return QueryResult(res.ids, res.dists, res.scores)


@functools.partial(jax.jit, static_argnames=_QUERY_STATIC)
def _query(
    x: jax.Array,
    index: SuCoIndex,
    q: jax.Array,
    *,
    k: int,
    alpha: float,
    beta: float,
    metric: Metric,
    mode: str,
    block_n: int,
    score_impl: str,
    tiles: TileConfig | None,
    merge_impl: str,
) -> EngineResult:
    """:func:`suco_query` and, on the fused path, its :class:`QueryStats`."""
    n = x.shape[0]
    if mode not in ("auto", "dense", "streaming", "fused"):
        raise ValueError(f"unknown mode {mode!r}")
    if mode == "fused" or (mode == "auto" and n >= STREAMING_MIN_N):
        res, stats = _fused_query(
            x,
            index,
            q,
            k=k,
            alpha=alpha,
            beta=beta,
            metric=metric,
            tiles=tiles,
            score_impl=score_impl,
            merge_impl=merge_impl,
        )
        return EngineResult(*res, stats)
    if mode == "streaming":
        return EngineResult(*suco_query_streaming(
            x,
            index,
            q,
            k=k,
            alpha=alpha,
            beta=beta,
            metric=metric,
            block_n=block_n,
            score_impl=score_impl,
            merge_impl=merge_impl,
        ))
    c = sub.collision_count(n, alpha)
    scores = suco_scores(index, q, c, metric)  # (m, n)
    if index.tombstone is not None:
        # Tombstoned points score -1 — below every live point, and
        # rerank_candidates masks negative-score slots to +inf distance,
        # so a deleted id can neither crowd out pool slots nor be returned.
        scores = jnp.where(index.tombstone[None, :], -1, scores)
    n_candidates = candidate_pool_size(n, k, beta)
    return EngineResult(*rerank(x, q, scores, k, n_candidates, metric))


# --------------------------------------------------------------------------
# SuCoEngine: the persistent, batched serving subsystem
# --------------------------------------------------------------------------

# Padded batch-size buckets: every request batch is zero-padded up to the
# smallest bucket that fits, so the engine compiles one executable per
# (bucket, k) instead of one per observed batch size.
DEFAULT_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64)


def batch_bucket(m: int, buckets: Sequence[int] = DEFAULT_BATCH_BUCKETS) -> int:
    """The padded batch size serving ``m`` queries: the smallest configured
    bucket >= m, growing by powers of two above the largest bucket (so an
    oversized burst costs one extra executable, not a failure).  Shared by
    the local and sharded engines — one bucketing policy across the stack."""
    if m < 1:
        raise ValueError(f"batch size must be >= 1, got {m}")
    if not buckets:
        raise ValueError("buckets must be non-empty")
    for b in sorted(buckets):
        if m <= b:
            return int(b)
    b = int(max(buckets))
    while b < m:
        b *= 2
    return b


def padding_waste(
    histogram: Mapping[int, int], buckets: Sequence[int] = DEFAULT_BATCH_BUCKETS
) -> int:
    """Expected padded-row waste of serving ``histogram`` with ``buckets``.

    ``histogram`` maps observed micro-batch size -> occurrence count; every
    batch of size m is padded to :func:`batch_bucket`\\ ``(m, buckets)``, so
    the waste is ``sum(count * (bucket(m) - m))`` — the number of all-zero
    query rows the engine computes and throws away.
    """
    return sum(
        int(c) * (batch_bucket(int(m), buckets) - int(m))
        for m, c in histogram.items()
        if c
    )


def autoscale_buckets(
    histogram: Mapping[int, int],
    max_buckets: int = 8,
    *,
    fallback: Sequence[int] = DEFAULT_BATCH_BUCKETS,
) -> tuple[int, ...]:
    """Propose a batch-bucket set for an observed traffic histogram.

    Picks at most ``max_buckets`` bucket sizes minimising the expected
    padding waste (:func:`padding_waste`) of replaying the histogram, by
    exact dynamic programming over the distinct observed sizes: an optimal
    bucket boundary always coincides with some observed size (lowering a
    bucket to the largest size it serves never increases waste), so the
    search space is subsets of the observed sizes that contain the maximum
    — the proposal therefore always covers the observed max batch, and
    oversize bursts still fall through to ``batch_bucket``'s power-of-two
    overflow rule.  An empty histogram returns ``fallback`` unchanged.
    """
    if max_buckets < 1:
        raise ValueError(f"max_buckets must be >= 1, got {max_buckets}")
    hist = {int(m): int(c) for m, c in histogram.items() if int(c) > 0}
    if not hist:
        # An all-zero histogram (every count 0) degenerates to the empty
        # one: nothing observed, so the fallback buckets stand.  A server
        # configured with no fallback would otherwise propose an empty
        # bucket set and crash batch_bucket much later — fail here instead.
        fb = tuple(sorted(set(int(b) for b in fallback)))
        if not fb:
            raise ValueError(
                "autoscale_buckets: empty traffic histogram and empty "
                "fallback bucket set — configure at least one bucket"
            )
        return fb
    if min(hist) < 1:
        raise ValueError(f"batch sizes must be >= 1, got {sorted(hist)[0]}")
    sizes = sorted(hist)
    u = len(sizes)
    n_b = min(max_buckets, u)
    # prefix sums -> O(1) segment waste: serving sizes[i..j] with bucket
    # sizes[j] wastes sizes[j]*sum(cnt) - sum(cnt*size) over the segment.
    pc = [0] * (u + 1)
    pm = [0] * (u + 1)
    for i, s in enumerate(sizes):
        pc[i + 1] = pc[i] + hist[s]
        pm[i + 1] = pm[i] + hist[s] * s

    def seg(i: int, j: int) -> int:  # waste of sizes[i..j] under bucket sizes[j]
        return sizes[j] * (pc[j + 1] - pc[i]) - (pm[j + 1] - pm[i])

    inf = float("inf")
    dp = [[inf] * u for _ in range(n_b + 1)]
    parent: list[list[int]] = [[-1] * u for _ in range(n_b + 1)]
    for j in range(u):
        dp[1][j] = seg(0, j)
    for t in range(2, n_b + 1):
        for j in range(t - 1, u):
            for i in range(t - 2, j):
                c = dp[t - 1][i] + seg(i + 1, j)
                if c < dp[t][j]:
                    dp[t][j] = c
                    parent[t][j] = i
    best_t = min(range(1, n_b + 1), key=lambda t: (dp[t][u - 1], t))
    chosen = []
    t, j = best_t, u - 1
    while j >= 0 and t >= 1:
        chosen.append(sizes[j])
        j = parent[t][j]
        t -= 1
    return tuple(sorted(chosen))


@dataclasses.dataclass(frozen=True)
class EnginePolicy:
    """Query-serving policy owned by :class:`SuCoEngine`.

    What used to travel on every ``suco_query`` call (alpha/beta/metric,
    the execution mode, the scorer kernel impl, the chunk/tile sizes) is
    fixed once per engine; per-request inputs shrink to ``(queries, k)``.
    ``mode="auto"`` resolves against the dataset size a single time at
    engine construction — requests never re-decide it; the large-``n``
    resolution is the **fused** single-pass engine (the legacy chunked
    path stays reachable as ``mode="streaming"``).

    Tiling knobs:

    * ``block_n`` — the legacy streaming path's chunk size (ignored by
      dense and fused modes).
    * ``tiles`` — the fused path's :class:`~repro.core.tuning.TileConfig`
      (chunk size, kernel ``bm``/``bn`` grid tile, survivor-compaction
      width).  ``None`` (the default) autotunes per ``(bucket, k)``
      executable from the backend memory limits and the padded batch
      shape (:func:`repro.core.tuning.autotune_tiles`) — deterministic
      per shape, so warmed executables never retrace.

    The policy also accumulates a traffic histogram (``observe``, fed by
    every engine query) from which :meth:`autoscale_buckets` proposes a
    waste-minimising bucket set; the histogram is observational state, not
    configuration — it never participates in equality or hashing, is
    bounded at ``TRAFFIC_MAX_BINS`` distinct sizes (long-running servers
    must not grow an unbounded dict), and can be dropped wholesale with
    :meth:`reset_traffic`.
    """

    # Bound on distinct batch sizes the traffic histogram tracks; beyond
    # it the least-frequent (smallest on ties) bin is evicted, so the
    # histogram is approximate under adversarial traffic but its memory is
    # O(1) over a server's lifetime.
    TRAFFIC_MAX_BINS = 512

    alpha: float = 0.05
    beta: float = 0.02
    metric: Metric = "l2"
    mode: str = "auto"  # "auto" | "dense" | "streaming" | "fused"
    score_impl: str = "auto"  # chunked scorer kernel dispatch
    merge_impl: str = "auto"  # pool-merge algorithm (sc_linear.merge_topk_pool)
    block_n: int = 4096  # legacy streaming chunk size
    tiles: TileConfig | None = None  # fused-path tiling (None = autotune)
    batch_buckets: tuple[int, ...] = DEFAULT_BATCH_BUCKETS
    traffic: collections.Counter = dataclasses.field(
        default_factory=collections.Counter, init=False, repr=False, compare=False
    )

    def observe(self, batch_sizes: Iterable[int]) -> None:
        """Record observed micro-batch sizes into the traffic histogram.

        Bounded: once ``TRAFFIC_MAX_BINS`` distinct sizes are tracked, a
        new size evicts the least-frequent existing bin (smallest size on
        ties) instead of growing the dict — :meth:`autoscale_buckets`
        keeps seeing the traffic that matters while a long-running server
        with pathological size churn stays O(1)."""
        for m in batch_sizes:
            m = int(m)
            if m < 1:
                raise ValueError(f"batch size must be >= 1, got {m}")
            if (
                m not in self.traffic
                and len(self.traffic) >= self.TRAFFIC_MAX_BINS
            ):
                victim = min(self.traffic.items(), key=lambda kv: (kv[1], kv[0]))
                del self.traffic[victim[0]]
            self.traffic[m] += 1

    def reset_traffic(self) -> None:
        """Drop the accumulated traffic histogram (e.g. after consuming it
        through :meth:`autoscaled`, or on a traffic-shape change)."""
        self.traffic.clear()

    def autoscale_buckets(self, max_buckets: int | None = None) -> tuple[int, ...]:
        """Bucket-set proposal from the observed traffic
        (:func:`autoscale_buckets`); the configured buckets when nothing
        has been observed yet."""
        if max_buckets is None:
            max_buckets = max(len(self.batch_buckets), 1)
        return autoscale_buckets(
            self.traffic, max_buckets, fallback=self.batch_buckets
        )

    def autoscaled(self, max_buckets: int | None = None) -> "EnginePolicy":
        """A new policy serving the observed traffic with minimal padding
        waste (same alpha/beta/metric/mode).  The histogram is carried
        forward so a consumer can still warm exactly the observed sizes
        (``SuCoEngine.warmup(batch_sizes=None)``)."""
        new = dataclasses.replace(
            self, batch_buckets=self.autoscale_buckets(max_buckets)
        )
        new.traffic.update(self.traffic)
        return new

    def degraded(self, level: int) -> "EnginePolicy":
        """The reduced-budget policy at degradation-ladder step ``level``.

        Level 0 is this policy unchanged.  Each further level sheds work
        along the knobs the paper exposes (Section 5.3.3 tuning ranges):

        * ``beta`` halves per level — the candidate pool is the dominant
          rerank cost, and shrinking it is what honestly lowers the
          Theorem-2 floor (:func:`repro.core.theory.degraded_budget_bound`
          charges the pool-spill term ``alpha**Ns / beta``).
        * ``alpha`` shrinks mildly (x0.8 per level) — fewer activated
          cells per subspace, cheaper SC-scoring.
        * pinned ``tiles`` shrink ``survivor_cap`` with the pool (halved
          per level, floored at 64 and kept a 64-multiple per the
          tile-shape lint rule); autotuned tiles (``tiles=None``) need no
          edit — the autotuner re-derives the cap from the reduced pool.

        Deterministic in ``level`` and structural only (fresh traffic
        Counter via ``dataclasses.replace``), so a ladder of pre-warmed
        engines can be built once at server start and swapping levels
        never retraces.
        """
        if level < 0:
            raise ValueError(f"degradation level must be >= 0, got {level}")
        if level == 0:
            return self
        tiles = self.tiles
        if tiles is not None:
            cap = max(64, (tiles.survivor_cap >> level) // 64 * 64)
            tiles = dataclasses.replace(tiles, survivor_cap=cap)
        return dataclasses.replace(
            self,
            alpha=max(self.alpha * 0.8**level, 1e-6),
            beta=self.beta * 0.5**level,
            tiles=tiles,
        )


class EngineStats(NamedTuple):
    executables: int  # compiled (bucket, k) query executables (jit cache)
    batches: int  # query() calls served
    queries: int  # individual queries served (pre-padding)
    padded_queries: int  # wasted padding rows across all batches
    buckets: tuple[tuple[int, int], ...]  # (bucket, k) pairs seen


class SuCoEngine:
    """Owns the SuCo index lifecycle end to end: build-or-load, pre-compiled
    bucketed query executables, and batched serving.

    The engine pins ``(x, index, policy)`` for its lifetime and exposes
    ``query(q, k)``: the batch is zero-padded to a policy bucket
    (:func:`batch_bucket`) and dispatched to a jitted executable keyed by
    ``(bucket, k)`` — after :meth:`warmup` covers the live traffic mix, a
    request can never trigger a retrace (``compile_count`` stays flat).
    Padding is sound because every query path is per-row independent
    (vmapped scoring, per-row top-k/merge), so the first ``m`` rows of a
    padded batch are bit-identical to the unpadded computation — and to
    ``suco_query``, the back-compat wrapper over the same kernels.
    """

    def __init__(
        self,
        x: jax.Array,
        index: SuCoIndex,
        policy: EnginePolicy | None = None,
        *,
        capacity: int | None = None,
    ):
        self.x = jnp.asarray(x)
        self.index = index
        # None -> a fresh default policy per engine (policies carry a mutable
        # traffic histogram, so a shared module-level default would bleed
        # observations across engines).
        policy = EnginePolicy() if policy is None else policy
        self.policy = policy
        if self.x.shape[-1] != index.spec.d:
            raise ValueError(
                f"data dim {self.x.shape[-1]} != index spec d={index.spec.d}"
            )
        if self.x.shape[0] != index.n_points:
            raise ValueError(
                f"data rows {self.x.shape[0]} != index points {index.n_points}"
            )
        n0 = self.x.shape[0]
        if capacity is not None:
            # Mutable layout: pre-pad (x, index) to `capacity` slots so
            # inserts are in-place slot writes — shapes (and therefore the
            # warmed executables) never change.  Empty slots are tombstoned
            # (never scored, never returned) and uncounted in cell_counts.
            if capacity < n0:
                raise ValueError(
                    f"capacity={capacity} must be >= current n={n0}"
                )
            tomb = (
                jnp.zeros(n0, bool) if index.tombstone is None
                else index.tombstone
            )
            self.x = jnp.pad(self.x, ((0, capacity - n0), (0, 0)))
            self.index = dataclasses.replace(
                index,
                cell_ids=jnp.pad(
                    index.cell_ids, ((0, 0), (0, capacity - n0))
                ),
                tombstone=jnp.concatenate(
                    [tomb, jnp.ones(capacity - n0, bool)]
                ),
            )
        self._capacity = capacity
        self._next_slot = n0
        self._n_live = self.index.n_live  # host int, maintained on mutation
        self._insert_inertia = 0.0  # drift statistic: sum over inserts
        self._inserted = 0
        mode = policy.mode
        if mode == "auto":
            # fused is the streaming-scale default: same answers as the
            # legacy chunked path, one pass over the data.
            mode = "fused" if self.x.shape[0] >= STREAMING_MIN_N else "dense"
        if mode not in ("dense", "streaming", "fused"):
            raise ValueError(f"unknown engine mode {policy.mode!r}")
        self._mode = mode
        self._batches = 0
        self._queries = 0
        self._padded = 0
        self._buckets_seen: set[tuple[int, int]] = set()
        self._jit = jax.jit(self._raw_query, static_argnames=("k",))
        self._retired_jit = None  # predecessor executables parked by swap

    # ---- lifecycle -------------------------------------------------------

    @classmethod
    def build(
        cls,
        x: jax.Array,
        config: SuCoConfig = SuCoConfig(),
        *,
        spec: sub.SubspaceSpec | None = None,
        policy: EnginePolicy | None = None,
    ) -> "SuCoEngine":
        """Build the index (Algorithm 2) and wrap it in an engine."""
        x = jnp.asarray(x)
        return cls(x, build_index(x, config, spec=spec), policy)

    @classmethod
    def from_artifact(
        cls, path, x: jax.Array, policy: EnginePolicy | None = None
    ) -> "SuCoEngine":
        """Serve a persisted index (:meth:`SuCoIndex.save`) over ``x``."""
        index, _ = load_index_artifact(path)
        return cls(x, index, policy)

    def autoscaled(self, max_buckets: int | None = None) -> "SuCoEngine":
        """A new engine over the same ``(x, index)`` whose bucket set is the
        autoscale proposal for this engine's observed traffic
        (:meth:`EnginePolicy.autoscale_buckets`).  The new engine starts
        with an empty jit cache — re-run :meth:`warmup` (its no-argument
        form warms exactly the observed traffic) before serving."""
        return SuCoEngine(self.x, self.index, self.policy.autoscaled(max_buckets))

    def save(
        self,
        path,
        config: SuCoConfig | None = None,
        *,
        extras: Mapping[str, np.ndarray] | None = None,
    ) -> None:
        """Persist this engine's index artifact (see :meth:`SuCoIndex.save`)."""
        self.index.save(path, config, extras=extras)

    # ---- live mutation ---------------------------------------------------

    def _require_mutable(self, op: str) -> None:
        if self._capacity is None:
            raise ValueError(
                f"{op} needs a mutable engine — construct with "
                "capacity=<max points> (pre-padded slots keep the warmed "
                "executables' shapes fixed); this engine is immutable"
            )

    def insert(self, x_new: jax.Array) -> np.ndarray:
        """Insert ``x_new: (b, d)`` (or one ``(d,)`` point) into free slots.

        Assignment to the existing centroids reuses the chunked build pass
        (:func:`assign_points`); ``cell_ids``/``cell_counts`` and the
        tombstone mask update in place (functional ``.at[]`` writes on the
        same shapes — the warmed query executables never retrace).  Returns
        the assigned slot ids (stable: slots are never reused until a
        re-index).  Raises :class:`CapacityError` when the batch does not
        fit in the remaining capacity — the re-index trigger.
        """
        self._require_mutable("insert")
        x_new = jnp.asarray(x_new, self.x.dtype)
        if x_new.ndim == 1:
            x_new = x_new[None]
        if x_new.ndim != 2 or x_new.shape[-1] != self.index.spec.d:
            raise ValueError(
                f"points must be (b, {self.index.spec.d}), got {x_new.shape}"
            )
        b = x_new.shape[0]
        if self._next_slot + b > self._capacity:
            raise CapacityError(
                f"insert of {b} points exceeds capacity "
                f"{self._capacity} (next free slot {self._next_slot}) — "
                "re-index onto a larger successor engine"
            )
        cells, counts_delta, inertia = assign_points(
            x_new, self.index.centroids1, self.index.centroids2,
            spec=self.index.spec, sqrt_k=self.index.sqrt_k,
            block_n=self.policy.block_n,
        )
        slots = np.arange(self._next_slot, self._next_slot + b)
        sl = jnp.asarray(slots, jnp.int32)
        self.index = dataclasses.replace(
            self.index,
            cell_ids=self.index.cell_ids.at[:, sl].set(cells),
            cell_counts=self.index.cell_counts + counts_delta,
            tombstone=self.index.tombstone.at[sl].set(False),
        )
        self.x = self.x.at[sl].set(x_new)
        self._next_slot += b
        self._n_live += b
        self._insert_inertia += float(inertia)
        self._inserted += b
        return slots

    def delete(self, ids) -> int:
        """Tombstone the given slot ids; returns how many were newly dead.

        Delegates to :meth:`SuCoIndex.delete` (idempotent, occupancy-
        correcting) and rebinds the same-shape result — zero retrace.
        """
        self._require_mutable("delete")
        before = self.index
        self.index = before.delete(ids)
        newly = int(jnp.sum(before.tombstone != self.index.tombstone))
        self._n_live -= newly
        return newly

    def swap(self, successor: "SuCoEngine") -> None:
        """Atomically become ``successor`` — the warm re-index handoff.

        The successor must already be warmed over at least this engine's
        seen ``(bucket, k)`` set (build it, :meth:`warmup` it, then swap):
        the whole point is that no request ever waits on a compile or is
        dropped across the handoff.  Adoption rebinds every serving field
        in place, so callers holding this engine object — servers, ladders
        — cut over atomically; in-flight results computed on the old
        executables stay valid (their device buffers are unaffected).
        """
        if successor is self:
            return
        missing = self._buckets_seen - successor._buckets_seen
        if missing:
            raise ValueError(
                "swap target is not warmed over the live traffic mix — "
                f"missing (bucket, k) executables {sorted(missing)}; "
                "run successor.warmup(...) over the seen mix first"
            )
        self.x = successor.x
        self.index = successor.index
        self.policy = successor.policy
        self._mode = successor._mode
        # Dropping the last reference to the old jitted dispatcher tears
        # down its compiled executables synchronously (tens of ms) — done
        # inline that teardown WOULD be the swap pause.  Park it instead;
        # release_retired() frees it off the serving path.
        self._retired_jit = self._jit
        self._jit = successor._jit
        self._capacity = successor._capacity
        self._next_slot = successor._next_slot
        self._n_live = successor._n_live
        self._insert_inertia = successor._insert_inertia
        self._inserted = successor._inserted
        self._buckets_seen = set(
            self._buckets_seen | successor._buckets_seen
        )

    def release_retired(self) -> None:
        """Free the predecessor executables a :meth:`swap` parked.

        Compiled-executable teardown is synchronous and slow relative to a
        query step, so ``swap`` defers it; call this from a maintenance
        point (between steps, after the handoff settles) to reclaim the
        memory without the teardown ever appearing inside the cutover."""
        self._retired_jit = None

    def _rebind(
        self, x: jax.Array, index: SuCoIndex, *, n_live: int, next_slot: int
    ) -> None:
        """Adopt mutated ``(x, index)`` in place — same shapes and treedef
        as the current ones, so the warmed executables keep hitting.  The
        propagation hook for sibling engines (degradation-ladder levels)
        that share this engine's data."""
        self.x = x
        self.index = index
        self._n_live = n_live
        self._next_slot = next_slot

    # ---- query -----------------------------------------------------------

    def _raw_query(
        self, x: jax.Array, index: SuCoIndex, q: jax.Array, *, k: int
    ) -> EngineResult:
        # one implementation, two entry points: suco_query runs the same
        # dispatcher, which keeps the wrapper's bit-identical contract
        # true by construction
        p = self.policy
        return _query(
            x, index, q, k=k, alpha=p.alpha, beta=p.beta, metric=p.metric,
            mode=self._mode, block_n=p.block_n, score_impl=p.score_impl,
            tiles=p.tiles, merge_impl=p.merge_impl,
        )

    def tiles_for(self, m: int, k: int) -> TileConfig | None:
        """The fused-path tiling an ``(m, k)`` request resolves to: the
        policy's pinned :class:`~repro.core.tuning.TileConfig`, or the
        autotune result for the request's padded bucket (exactly what the
        dispatched executable uses — deterministic per ``(bucket, k)``, so
        inspecting it never perturbs the jit cache).  ``None`` for
        non-fused engines with no pinned tiles."""
        if self.policy.tiles is not None or self._mode != "fused":
            return self.policy.tiles
        b = batch_bucket(m, self.policy.batch_buckets)
        n, d = self.x.shape
        return autotune_tiles(
            n, d, b, _pool_size(n, k, self.policy.beta),
            n_subspaces=self.index.spec.n_subspaces,
            n_cells=self.index.n_cells,
            itemsize=self.x.dtype.itemsize,
        )

    def query(self, q: jax.Array, k: int) -> QueryResult:
        """Serve a batch ``q: (m, d)`` (or a single ``(d,)`` query) -> top-k.

        Pads to the policy bucket, dispatches the ``(bucket, k)``
        executable, slices the padding back off.  Results are bit-identical
        to ``suco_query`` on the unpadded batch.
        """
        return self.query_with_stats(q, k)[0]

    def query_with_stats(
        self, q: jax.Array, k: int
    ) -> tuple[QueryResult, QueryStats | None]:
        """:meth:`query` and the executable's :class:`QueryStats`, counted
        over the whole padded bucket; ``None`` off the fused path.  Both
        stay on the device until the caller reads them."""
        q = jnp.asarray(q)
        single = q.ndim == 1
        if single:
            q = q[None]
        if q.ndim != 2 or q.shape[-1] != self.index.spec.d:
            raise ValueError(
                f"queries must be (m, {self.index.spec.d}) or "
                f"({self.index.spec.d},), got {q.shape}"
            )
        if not 1 <= k <= self.n_live:
            # k is bounded by the LIVE count: with tombstones, asking for
            # more neighbours than live points would leak sentinel ids.
            raise ValueError(f"k={k} must be in [1, n={self.n_live}]")
        m = q.shape[0]
        b = batch_bucket(m, self.policy.batch_buckets)
        if b != m:
            q = jnp.pad(q, ((0, b - m), (0, 0)))
        out = self._jit(self.x, self.index, q, k=k)
        self._batches += 1
        self._queries += m
        self._padded += b - m
        self._buckets_seen.add((b, k))
        self.policy.observe((m,))  # feed the autoscaler's traffic histogram
        # a wrapped _raw_query that rebuilds a plain QueryResult has no stats
        stats = getattr(out, "stats", None)
        if single:
            return QueryResult(out.ids[0], out.dists[0], out.scores[0]), stats
        if b != m:
            return QueryResult(out.ids[:m], out.dists[:m], out.scores[:m]), stats
        return QueryResult(out.ids, out.dists, out.scores), stats

    def warmup(
        self,
        batch_sizes: Sequence[int] | None = (1,),
        ks: Sequence[int] = (10,),
    ) -> int:
        """Pre-compile one executable per (bucket, k) covering the given
        traffic mix; returns the number of fresh compiles.  After a warmup
        that covers the live mix, ``compile_count`` stays flat forever.

        ``batch_sizes=None`` warms the *observed* traffic: the sizes in the
        policy's accumulated histogram (falling back to ``(1,)`` when no
        traffic has been recorded) — the consumption path for
        :meth:`autoscaled` engines, whose bucket set was proposed from the
        same histogram."""
        if batch_sizes is None:
            batch_sizes = tuple(sorted(self.policy.traffic)) or (1,)
        before = self.compile_count
        d = self.index.spec.d
        for b in sorted({batch_bucket(m, self.policy.batch_buckets)
                         for m in batch_sizes}):
            for k in sorted(set(ks)):
                probe = jnp.zeros((b, d), self.x.dtype)
                jax.block_until_ready(self._jit(self.x, self.index, probe, k=k).ids)
                self._buckets_seen.add((b, k))
        return self.compile_count - before

    # ---- introspection ---------------------------------------------------

    @property
    def mode(self) -> str:
        """The resolved execution mode ("dense" | "streaming" | "fused" —
        the last is what ``mode="auto"`` resolves to at streaming scale)."""
        return self._mode

    @property
    def n_points(self) -> int:
        return self.x.shape[0]

    @property
    def n_live(self) -> int:
        """Live (non-tombstoned, non-empty-slot) point count — the honest
        ``n`` for k-validation and quality bounds under mutation."""
        return self._n_live

    @property
    def capacity(self) -> int | None:
        """Total slots of a mutable engine (``None`` = immutable)."""
        return self._capacity

    @property
    def free_slots(self) -> int:
        """Remaining insert slots (0 for immutable engines)."""
        if self._capacity is None:
            return 0
        return self._capacity - self._next_slot

    @property
    def insert_inertia_per_point(self) -> float:
        """Mean assignment inertia over all points inserted so far — the
        drift monitor's statistic (rising vs. the build-time baseline
        means the centroids no longer describe the incoming data)."""
        if not self._inserted:
            return 0.0
        return self._insert_inertia / self._inserted

    @property
    def compile_count(self) -> int:
        """Number of compiled query executables (the jit cache size) — the
        serving invariant is that this is flat after warmup."""
        return self._jit._cache_size()

    def stats(self) -> EngineStats:
        return EngineStats(
            executables=self.compile_count,
            batches=self._batches,
            queries=self._queries,
            padded_queries=self._padded,
            buckets=tuple(sorted(self._buckets_seen)),
        )


# --------------------------------------------------------------------------
# jaxlint registry hook (see repro.analysis)
# --------------------------------------------------------------------------

# Canonical lint shapes: large enough that the bounded-intermediate budgets
# separate the streaming/fused paths (peak independent of n) from the dense
# reference (peak >= m*n elements) — the same separation the jaxpr memory
# tests assert — and small enough that the one-time index build behind the
# query entries traces in seconds on CPU.
#: Shapes for the jaxlint traces.  ``n`` must be comfortably larger than
#: ``n_subspaces * block_n`` so the streamed peaks (O(m * ns * block_n),
#: constant in n) separate cleanly from the dense (m, n) line.
LINT_QUERY_SHAPES: Mapping[str, int | float] = {
    "n": 60_000,
    "d": 32,
    "m": 32,
    "k": 10,
    "block_n": 2_048,
    "alpha": 0.05,
    "beta": 0.02,
    "n_subspaces": 8,
    "sqrt_k": 16,
}
LINT_BUILD_SHAPES: Mapping[str, int] = {
    "n": 20_000,
    "d": 16,
    "n_subspaces": 4,
    "sqrt_k": 32,
    "block_n": 512,
}


@functools.lru_cache(maxsize=1)
def _lint_problem():
    s = LINT_QUERY_SHAPES
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((s["n"], s["d"])).astype(np.float32))
    q = jnp.asarray(rng.standard_normal((s["m"], s["d"])).astype(np.float32))
    cfg = SuCoConfig(
        n_subspaces=s["n_subspaces"], sqrt_k=s["sqrt_k"], kmeans_iters=2, seed=0
    )
    return x, q, build_index(x, cfg), cfg


def lint_query_budget_bytes(block_n: int, m: int | None = None) -> int:
    """bounded-intermediate budget for a streamed query at the lint shapes:
    the streaming memory claim O(m*(block_n + pool)) plus the index-scale
    terms every path carries (ranks, blocked cell ids, rerank gather)."""
    s = LINT_QUERY_SHAPES
    n, d, k = s["n"], s["d"], s["k"]
    m = s["m"] if m is None else m
    ns = s["n_subspaces"]
    cells = s["sqrt_k"] ** 2
    pool = max(k, int(s["beta"] * n))
    n_pad = -(-n // block_n) * block_n
    elems = max(
        2 * m * (block_n + pool),  # score block + carried pool (merge concat)
        ns * m * block_n,  # per-chunk per-subspace collision gather
        m * pool * d,  # rerank candidate gather
        ns * n_pad,  # the index's cell-id array, reshaped into blocks
        ns * m * cells,  # Dynamic-Activation ranks
    )
    return 4 * elems  # every array in the query stack is 4-byte


def lint_dense_peak_bytes() -> int:
    """The dense reference provably materialises an (m, n) score array; the
    migrated memory tests use this as the separation line."""
    return 4 * LINT_QUERY_SHAPES["m"] * LINT_QUERY_SHAPES["n"]


def _lint_build_budget_bytes() -> int:
    s = LINT_BUILD_SHAPES
    n, d, ns, sqrt_k, bn = (
        s["n"], s["d"], s["n_subspaces"], s["sqrt_k"], s["block_n"],
    )
    h_max = (d // ns + 1) // 2
    n_pad = -(-n // bn) * bn
    codebooks = 2 * ns
    elems = max(
        codebooks * h_max * n_pad,  # the lane layout and its blocks (O(n*d))
        n * d,  # the input itself
        2 * codebooks * bn * max(sqrt_k, h_max),  # per-chunk dist + one-hot
        ns * sqrt_k * sqrt_k,  # cell_counts
    )
    return 4 * elems


def jaxlint_entries():
    """Registry hook: the serving entry points and their invariants."""
    from repro.analysis.registry import JaxprEntry

    s = LINT_QUERY_SHAPES
    k, alpha, beta = s["k"], s["alpha"], s["beta"]
    scan_rules = ("no-scatter-in-scan", "bounded-intermediate", "pinned-accumulator")

    def make_streaming():
        x, q, index, _ = _lint_problem()
        return jax.make_jaxpr(
            lambda xx, qq: suco_query_streaming(
                xx, index, qq, k=k, alpha=alpha, beta=beta, block_n=s["block_n"]
            )
        )(x, q)

    # Lint tiles are pinned to the *static* memory model: the measured
    # limits vary per host, and the lint gate must prove the identical
    # canonical shapes (and bounded-intermediate budgets) everywhere.
    from repro.core.tuning import static_backend_limits

    lint_limits = static_backend_limits()

    def _fused_tiles(m: int) -> TileConfig:
        pool = max(k, int(beta * s["n"]))
        return autotune_tiles(
            s["n"], s["d"], m, pool,
            n_subspaces=s["n_subspaces"], n_cells=s["sqrt_k"] ** 2,
            limits=lint_limits,
        )

    def make_fused():
        x, q, index, _ = _lint_problem()
        return jax.make_jaxpr(
            lambda xx, qq: suco_query_fused(
                xx, index, qq, k=k, alpha=alpha, beta=beta,
                tiles=_fused_tiles(s["m"]),
            )
        )(x, q)

    def make_fused_tombstoned():
        # The live-mutation variant of the fused entry: a ~10% tombstone
        # mask threads through the prefilter keep-mask (docs/index_mutation.md).
        # Same scan rules and budget — the extra arrays (one bool per point,
        # one per chunk column) are smaller than every budgeted term.
        x, q, index, _ = _lint_problem()
        rng = np.random.default_rng(7)
        tomb = jnp.asarray(rng.random(s["n"]) < 0.1)
        tindex = dataclasses.replace(index, tombstone=tomb)
        return jax.make_jaxpr(
            lambda xx, qq: suco_query_fused(
                xx, tindex, qq, k=k, alpha=alpha, beta=beta,
                tiles=_fused_tiles(s["m"]),
            )
        )(x, q)

    def make_dense():
        x, q, index, _ = _lint_problem()
        return jax.make_jaxpr(
            lambda xx, qq: suco_query(
                xx, index, qq, k=k, alpha=alpha, beta=beta, mode="dense"
            )
        )(x, q)

    def make_engine_bucket():
        x, q, index, _ = _lint_problem()
        engine = SuCoEngine(
            x, index,
            EnginePolicy(mode="fused", tiles=_fused_tiles(batch_bucket(5))),
        )
        qb = q[: batch_bucket(5)]  # one warmed (bucket=8, k) executable
        return jax.make_jaxpr(functools.partial(engine._raw_query, k=k))(
            engine.x, engine.index, qb
        )

    def _degraded_tiles(m: int) -> TileConfig:
        p = EnginePolicy(mode="fused").degraded(1)
        pool = max(k, int(p.beta * s["n"]))
        return autotune_tiles(
            s["n"], s["d"], m, pool,
            n_subspaces=s["n_subspaces"], n_cells=s["sqrt_k"] ** 2,
            limits=lint_limits,
        )

    def make_engine_degraded_bucket():
        # The degradation ladder's level-1 engine: same entry point, reduced
        # (alpha, beta) budget.  Proving the same scan/memory invariants
        # here keeps the ladder inside docs/invariants.md — degrading under
        # load must never regress the streaming guarantees.
        x, q, index, _ = _lint_problem()
        engine = SuCoEngine(x, index, EnginePolicy(mode="fused").degraded(1))
        qb = q[: batch_bucket(5)]
        return jax.make_jaxpr(functools.partial(engine._raw_query, k=k))(
            engine.x, engine.index, qb
        )

    def make_build_chunked():
        b = LINT_BUILD_SHAPES
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((b["n"], b["d"])).astype(np.float32))
        cfg = SuCoConfig(
            n_subspaces=b["n_subspaces"], sqrt_k=b["sqrt_k"], kmeans_iters=2,
            seed=0, build_mode="chunked", block_n=b["block_n"],
        )
        return jax.make_jaxpr(lambda xx: build_index(xx, cfg).cell_ids)(x)

    b = LINT_BUILD_SHAPES
    return [
        JaxprEntry(
            name="suco.query_streaming",
            make=make_streaming,
            rules=scan_rules,
            budget_bytes=lint_query_budget_bytes(s["block_n"]),
            note="legacy chunked query: scan over block_n-point chunks",
        ),
        JaxprEntry(
            name="suco.query_fused",
            make=make_fused,
            rules=scan_rules,
            budget_bytes=lint_query_budget_bytes(_fused_tiles(s["m"]).block_n),
            note="single-pass fused query: score/prune/merge/rerank per chunk",
        ),
        JaxprEntry(
            name="suco.query_fused_tombstoned",
            make=make_fused_tombstoned,
            rules=scan_rules,
            budget_bytes=lint_query_budget_bytes(_fused_tiles(s["m"]).block_n),
            note=(
                "fused query over a tombstoned (live-mutation) index: the "
                "delete mask folds into the prefilter keep-mask — same "
                "scan, same memory budget, no new kernel"
            ),
        ),
        JaxprEntry(
            name="suco.query_dense",
            make=make_dense,
            rules=("bounded-intermediate", "pinned-accumulator"),
            budget_bytes=4 * 2 * s["m"] * s["n"] * s["n_subspaces"],
            note=(
                "dense reference path; materialises (m, n) and sorts inside "
                "its subspace scan by design, so no-scatter-in-scan is "
                "intentionally not declared"
            ),
        ),
        JaxprEntry(
            name="suco.engine_fused_bucket",
            make=make_engine_bucket,
            rules=scan_rules,
            budget_bytes=lint_query_budget_bytes(
                _fused_tiles(batch_bucket(5)).block_n
            ),
            note="one SuCoEngine per-(bucket, k) executable, fused mode",
        ),
        JaxprEntry(
            name="suco.engine_degraded_bucket",
            make=make_engine_degraded_bucket,
            rules=scan_rules,
            # The full-budget bound also covers the reduced pool: shrinking
            # beta only shrinks the carried pool and rerank gather.
            budget_bytes=lint_query_budget_bytes(
                _degraded_tiles(batch_bucket(5)).block_n
            ),
            note=(
                "degradation-ladder level-1 executable "
                "(EnginePolicy.degraded): reduced (alpha, beta) budget, "
                "same fused path and invariants"
            ),
        ),
        JaxprEntry(
            name="suco.build_chunked",
            make=make_build_chunked,
            rules=scan_rules,
            budget_bytes=_lint_build_budget_bytes(),
            # The chunked build's scan legitimately scatters into small
            # codebook-sized carries (the fused IMI histogram, the k-means++
            # seed updates); data-sized scatters stay forbidden.
            scatter_budget_elems=2 * b["n_subspaces"] * b["sqrt_k"] ** 2,
            note="chunked index build: every k-means pass streams the data",
        ),
    ]
