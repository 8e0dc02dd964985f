"""Tiling autotuner: pick streaming-query / kernel tile sizes from backend
memory limits and the problem shape instead of hard-coded constants.

The streaming and fused query engines (:mod:`repro.core.suco`) process the
dataset in chunks of ``block_n`` points; the SC-score Pallas kernel tiles
each chunk into ``(bm, bn)`` blocks; and the fused engine additionally
carries a ``survivor_cap``-wide compaction buffer for chunk rows that beat
the carried pool minimum (the Pareto prefilter).  Until this module, those
knobs were frozen at ``4096 / 8 / 512`` — tuned by hand for one CPU host
and one dataset size.  :func:`autotune_tiles` instead sizes them so the
per-chunk working set (resident data chunk + cell ids + score block +
carried pool) fits the backend's fast memory (VMEM on TPU, a per-core L2
budget on CPU), which is what "as fast as the hardware allows" means for a
bandwidth-bound scan: the chunk a step touches should be served from the
closest memory level, and the merge should run as rarely as that allows.

The memory limits the tiler plans against are *measured*, not guessed:
:func:`backend_limits` probes the active backend once per host (cache-knee
timing sweep on CPU, the runtime's reported allocator ceiling for device
memory) and caches the quantised result on disk and in-process — see the
"Measured limits" section below.  The static ``_BACKEND_LIMITS`` table
survives as the prior for absent hardware and as the
``REPRO_MEASURED_LIMITS=0`` escape hatch.

The autotuner is *deterministic* and *shape-only*: given the same
``(n, d, m, pool)`` and backend it always returns the same
:class:`TileConfig` within a host, so jitted executables keyed on tile
sizes never retrace between identical requests (the serving stack's
zero-retrace invariant).  Every knob can still be pinned by hand through
:class:`~repro.core.suco.EnginePolicy` / :class:`~repro.core.suco.SuCoConfig`.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
from pathlib import Path

import jax

__all__ = [
    "MemoryLimits",
    "TileConfig",
    "backend_limits",
    "tpu_limits",
    "measured_backend_limits",
    "static_backend_limits",
    "autotune_tiles",
    "autotune_build_block_n",
]


@dataclasses.dataclass(frozen=True)
class MemoryLimits:
    """Per-device memory budget the tiler plans against.

    ``fast_bytes`` is the working-set budget for one streamed chunk — VMEM
    on TPU, a per-core L2-ish slice on CPU, shared-memory-adjacent L2 on
    GPU.  ``hbm_bytes`` bounds whole-array residency (index + dataset) and
    is only used for sanity clamps.
    """

    fast_bytes: int
    hbm_bytes: int


# Memory model per TPU generation, keyed by ``device_kind`` as JAX reports
# it.  A TPU whose kind is not listed is an error, never a default.
_TPU_LIMITS: dict[str, MemoryLimits] = {
    # TPU v5e: 16 GB of HBM per chip (Google Cloud documentation, "TPU
    # v5e").  fast_bytes is Mosaic's default scoped-VMEM limit, 16 MiB of
    # the chip's 128 MiB VMEM.  Both numbers come from compile rehearsals
    # against a described v5e topology: a kernel holding a 20 MiB VMEM
    # scratch is refused at the default limit and a 12 MiB one compiles,
    # while a 130 MiB scratch is refused at any limit ("would exceed memory
    # (size=134217728)").
    "TPU v5 lite": MemoryLimits(fast_bytes=16 * 2**20, hbm_bytes=16 * 2**30),
}

# Static priors per backend: the plan for a backend this process does not
# run (lint and compile rehearsals plan a TPU as v5e), and the
# ``REPRO_MEASURED_LIMITS=0`` table.  Unknown backends raise.
_BACKEND_LIMITS: dict[str, MemoryLimits] = {
    "tpu": _TPU_LIMITS["TPU v5 lite"],
    # L2 slice per SM-cluster; HBM on a modern part.
    "gpu": MemoryLimits(fast_bytes=4 * 2**20, hbm_bytes=40 * 2**30),
    # Per-core L2 on a server CPU; "hbm" is host RAM.
    "cpu": MemoryLimits(fast_bytes=2 * 2**20, hbm_bytes=32 * 2**30),
}


def tpu_limits(device_kind: str) -> MemoryLimits:
    """The memory model of one TPU generation; unknown kinds raise."""
    try:
        return _TPU_LIMITS[device_kind]
    except KeyError:
        raise ValueError(
            f"no memory model for TPU device kind {device_kind!r} "
            f"(known: {sorted(_TPU_LIMITS)}); add a rehearsed entry to "
            "repro.core.tuning._TPU_LIMITS"
        ) from None


# --------------------------------------------------------------------------
# Measured limits: probe the host once, cache per backend
# --------------------------------------------------------------------------
#
# The static table above is a *prior*, not a measurement: the serving host's
# actual cache topology and device memory decide whether a streamed chunk is
# bandwidth-cheap.  ``backend_limits`` therefore runs a tiny calibration for
# the backend this process is actually executing on — a timed reduction
# sweep to find the cache knee (CPU) and the runtime's reported allocator
# ceiling for device memory — and quantises the result so timing noise
# cannot leak into tile shapes.  The probe runs at most once per host per
# backend: results persist as JSON under ``$REPRO_TUNE_CACHE_DIR`` (default
# ``~/.cache/repro/tuning``), keyed by device kind, and an in-process
# ``lru_cache`` keeps the value bit-stable for jit static arguments — the
# zero-retrace invariant.  ``REPRO_MEASURED_LIMITS=0`` disables the probe
# entirely (static table only); backends other than the active one always
# use the static prior (there is no hardware to measure).

_MEASURE_ENV = "REPRO_MEASURED_LIMITS"  # "0" -> static table only
_CACHE_DIR_ENV = "REPRO_TUNE_CACHE_DIR"  # override the on-disk cache dir
_PROBE_VERSION = 1
_HBM_QUANTUM = 1 << 30  # device memory quantised down to 1 GiB
_FAST_MIN = 1 << 20  # measured fast memory clamps to [1 MiB, 64 MiB]
_FAST_MAX = 1 << 26
# A working set counts as cache-resident while its best per-byte reduction
# time stays within this factor of the small-set baseline; the first size
# past it is the knee.
_KNEE_FACTOR = 1.6


def _probe_cache_dir() -> Path:
    env = os.environ.get(_CACHE_DIR_ENV)
    if env:
        return Path(env)
    xdg = os.environ.get("XDG_CACHE_HOME")
    base = Path(xdg) if xdg else Path(os.path.expanduser("~")) / ".cache"
    return base / "repro" / "tuning"


def _device_kind(backend: str) -> str:
    try:
        devs = jax.devices(backend)
    except RuntimeError:
        return ""
    return devs[0].device_kind if devs else ""


def _measure_hbm_bytes(backend: str) -> int | None:
    """Device-memory ceiling: the runtime's own allocator limit where the
    platform reports one (TPU/GPU ``memory_stats``), physical RAM on CPU.
    A TPU that reports no limit raises — its tiles would be planned blind."""
    dev = jax.devices(backend)[0]
    stats = dev.memory_stats() or {}
    if stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    if backend == "tpu":
        raise RuntimeError(
            f"TPU {dev.device_kind!r} reports no memory_stats()['bytes_limit']"
        )
    try:  # CPU backends rarely report allocator stats: physical RAM
        return int(os.sysconf("SC_PHYS_PAGES")) * int(os.sysconf("SC_PAGE_SIZE"))
    except (AttributeError, OSError, ValueError):
        return None


def _measure_cpu_fast_bytes() -> tuple[int | None, dict]:
    """Find the cache knee with a timed reduction sweep.

    Reduces float32 working sets of power-of-two sizes (256 KiB..64 MiB,
    best-of-3 per size, ~16 MiB of traffic per timing) and returns half the
    largest size whose per-byte time stays within ``_KNEE_FACTOR`` of the
    small-set baseline — half, because the streamed chunk shares the level
    with the kernel's double buffers.  Power-of-two candidates make the
    result self-quantising: run-to-run timing noise must move the knee a
    full octave to change the answer.
    """
    import numpy as np

    sizes = [1 << p for p in range(18, 27)]
    per_byte = []
    for size in sizes:
        arr = np.ones(size // 4, np.float32)
        reps = max(1, (1 << 24) // size)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(reps):
                arr.sum()
            best = min(best, (time.perf_counter() - t0) / (reps * size))
        per_byte.append(best)
    trace = {"sizes": sizes, "per_byte_ns": [t * 1e9 for t in per_byte]}
    base = min(per_byte[:2])
    fast = None
    for size, t in zip(sizes, per_byte):
        if t <= base * _KNEE_FACTOR:
            fast = size
        else:
            break
    if fast is None:
        return None, trace
    return _clamp(fast // 2, _FAST_MIN, _FAST_MAX), trace


def _probe_limits(backend: str) -> tuple[MemoryLimits, dict]:
    static = (
        tpu_limits(_device_kind(backend))
        if backend == "tpu"
        else _BACKEND_LIMITS[backend]
    )
    hbm = _measure_hbm_bytes(backend)
    hbm = (
        max(_HBM_QUANTUM, _round_down(hbm, _HBM_QUANTUM))
        if hbm
        else static.hbm_bytes
    )
    trace: dict = {}
    if backend == "cpu":
        fast, trace = _measure_cpu_fast_bytes()
        fast = fast if fast is not None else static.fast_bytes
    else:
        # VMEM / L2-slice budgets are not queryable through memory_stats;
        # keep the per-device prior and measure only the memory ceiling.
        fast = static.fast_bytes
    return MemoryLimits(fast_bytes=fast, hbm_bytes=hbm), trace


@functools.lru_cache(maxsize=None)
def _measured_limits(backend: str) -> MemoryLimits:
    kind = _device_kind(backend)
    path = _probe_cache_dir() / f"limits_{backend}.json"
    try:
        rec = json.loads(path.read_text())
        if (
            rec.get("version") == _PROBE_VERSION
            and rec.get("backend") == backend
            and rec.get("device_kind") == kind
        ):
            return MemoryLimits(int(rec["fast_bytes"]), int(rec["hbm_bytes"]))
    except (OSError, ValueError, KeyError, TypeError):
        pass  # missing / stale / corrupt cache: re-probe and rewrite
    limits, trace = _probe_limits(backend)
    rec = {
        "version": _PROBE_VERSION,
        "backend": backend,
        "device_kind": kind,
        "fast_bytes": limits.fast_bytes,
        "hbm_bytes": limits.hbm_bytes,
        "probe": trace,
    }
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp")
        tmp.write_text(json.dumps(rec, indent=2, sort_keys=True) + "\n")
        os.replace(tmp, path)
    except OSError:
        pass  # unwritable cache dir: the in-process lru_cache still holds
    return limits


def static_backend_limits(backend: str | None = None) -> MemoryLimits:
    """The static prior for ``backend`` (default: active), never measured.

    For callers that need *host-independent* limits — the jaxlint entry
    hooks pin their canonical tile shapes and bounded-intermediate budgets
    to this model so the lint gate proves the same thing on every machine,
    while serving plans against the measured truth."""
    if backend is None:
        backend = jax.default_backend()
    if backend not in _BACKEND_LIMITS:
        raise ValueError(
            f"static_backend_limits: unknown backend {backend!r} "
            f"(known: {sorted(_BACKEND_LIMITS)})"
        )
    return _BACKEND_LIMITS[backend]


def measured_backend_limits(
    backend: str | None = None, *, refresh: bool = False
) -> MemoryLimits:
    """Measured :class:`MemoryLimits` for ``backend`` (default: active).

    Probes at most once per host per backend (JSON cache keyed by device
    kind, plus an in-process ``lru_cache``); ``refresh=True`` drops both
    caches and re-measures.  Only meaningful for the active backend —
    others return the static prior via the same code path."""
    if backend is None:
        backend = jax.default_backend()
    if backend not in _BACKEND_LIMITS:
        raise ValueError(
            f"measured_backend_limits: unknown backend {backend!r} "
            f"(known: {sorted(_BACKEND_LIMITS)})"
        )
    if backend != jax.default_backend():
        return _BACKEND_LIMITS[backend]
    if refresh:
        _measured_limits.cache_clear()
        try:
            (_probe_cache_dir() / f"limits_{backend}.json").unlink()
        except OSError:
            pass
    return _measured_limits(backend)


def backend_limits(backend: str | None = None) -> MemoryLimits:
    """Memory limits for ``backend`` (default: the active jax backend).

    For the backend this process is running on, the limits are *measured*
    (see :func:`measured_backend_limits`) unless ``REPRO_MEASURED_LIMITS=0``
    pins the static table; other backends use the static prior.  Nothing
    here falls back: an unknown backend, a TPU whose ``device_kind`` has no
    entry in the per-generation table, or a failing probe of the active
    device raises — tiling an accelerator with another device's model is a
    bug to surface, not to serve through."""
    active = jax.default_backend()
    if backend is None:
        backend = active
    if backend not in _BACKEND_LIMITS:
        raise ValueError(
            f"backend_limits: unknown backend {backend!r} "
            f"(known: {sorted(_BACKEND_LIMITS)})"
        )
    if backend != active:
        return _BACKEND_LIMITS[backend]
    if os.environ.get(_MEASURE_ENV, "1") == "0":
        if backend == "tpu":
            return tpu_limits(_device_kind(backend))
        return _BACKEND_LIMITS[backend]
    return _measured_limits(backend)


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """Resolved tiling for the streaming/fused query engines.

    * ``block_n`` — data points per streamed chunk (the ``lax.scan`` step).
    * ``bm`` / ``bn`` — SC-score kernel grid tile (queries x chunk columns);
      multiples of the f32 TPU tile (8 sublanes x 128 lanes).
    * ``survivor_cap`` — fused-path compaction width: the per-chunk budget
      of rows beating the carried pool minimum that merge at the pruned
      (cheap) width; a chunk exceeding it falls back to the exact
      full-width merge (same results, slower — see
      :func:`repro.core.suco.suco_query_fused`).

    Hashable/frozen so it can ride in jit static arguments and in
    :class:`~repro.core.suco.EnginePolicy` equality.
    """

    block_n: int
    bm: int = 8
    bn: int = 512
    survivor_cap: int = 256

    def __post_init__(self):
        if self.block_n < 1:
            raise ValueError(f"block_n must be >= 1, got {self.block_n}")
        if self.bm < 1 or self.bn < 1:
            raise ValueError(f"bm/bn must be >= 1, got {self.bm}/{self.bn}")
        if self.survivor_cap < 1:
            raise ValueError(
                f"survivor_cap must be >= 1, got {self.survivor_cap}"
            )


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _round_down(v: int, mult: int) -> int:
    return (v // mult) * mult


def _clamp(v: int, lo: int, hi: int) -> int:
    return max(lo, min(v, hi))


# Streamed chunks are sized in multiples of this (one f32 lane tile wide
# per subspace row); also the floor so tiny datasets still vectorise.
_BLOCK_QUANTUM = 512
_BLOCK_MAX = 1 << 16  # beyond this the scan stops gaining and pads hurt
_CAP_QUANTUM = 64
# Safety factor on the expected per-chunk survivor count: under the
# paper's Pareto observation the rows beating the carried pool minimum
# are a thin tail, but early chunks (cold pool, low threshold) and
# clustered queries overshoot the uniform estimate — the second chunk
# typically sees ~2x the steady-state tail, so budget well past it.
_CAP_SAFETY = 8


def autotune_tiles(
    n: int,
    d: int,
    m: int,
    pool: int,
    *,
    n_subspaces: int = 8,
    n_cells: int = 2500,
    backend: str | None = None,
    limits: MemoryLimits | None = None,
    itemsize: int = 4,
) -> TileConfig:
    """Pick ``(block_n, bm, bn, survivor_cap)`` for a streamed query.

    ``n/d`` are the dataset shape, ``m`` the (padded) query-batch size,
    ``pool`` the carried candidate-pool width (``max(k, beta*n)``),
    ``n_subspaces``/``n_cells`` the index shape (they size the kernel's
    rank table), ``itemsize`` the dataset dtype width.

    Sizing model (all per query batch, bytes):

    * chunk-resident: ``block_n * (Ns*4 + d*itemsize + m*4)`` — cell ids,
      the data chunk itself, and the int32 score block;
    * carried: ``3 * m * pool * 4`` for the (score, dist, id) pool, twice
      (the merge concatenates pool + survivors).

    The largest ``block_n`` (multiple of 512, clamped to [512, 65536] and
    to roughly an eighth of the dataset) whose total fits
    ``limits.fast_bytes`` wins: bigger chunks mean fewer pool merges — the
    dominant per-chunk cost — while staying inside the memory level that
    makes the scan bandwidth-cheap; the ~n/8 ceiling guarantees the scan
    actually streams (the Pareto prefilter only pays once the carried pool
    has warmed past the first chunks).  ``bm``/``bn`` then tile that chunk
    for the Pallas kernel under a quarter of the same budget (ranks tile +
    cells tile + out tile), and ``survivor_cap`` budgets ``_CAP_SAFETY``
    times the uniform-order expectation ``pool * block_n / n`` of new pool
    entrants per chunk.  When the plan is for a TPU, ``bm``, ``bn`` and
    ``survivor_cap`` then shrink until the score kernel's VMEM high-water
    mark fits ``limits.fast_bytes`` (:func:`_fit_kernel_vmem`).
    """
    if min(n, d, m, pool) < 1:
        raise ValueError(
            f"n/d/m/pool must all be >= 1, got {n}/{d}/{m}/{pool}"
        )
    if limits is None:
        limits = backend_limits(backend)
    fast = limits.fast_bytes

    per_point = n_subspaces * 4 + d * itemsize + m * 4
    carried = 2 * 3 * m * pool * 4
    budget = max(fast - carried, _BLOCK_QUANTUM * per_point)
    block_n = _clamp(
        _round_down(budget // per_point, _BLOCK_QUANTUM),
        _BLOCK_QUANTUM,
        _BLOCK_MAX,
    )
    block_n = min(
        block_n, max(_round_up(n // 8, _BLOCK_QUANTUM), _BLOCK_QUANTUM)
    )
    # When the carried pool alone overflows fast memory (huge beta*n), the
    # cache-residency model bottoms out — but tiny chunks would multiply
    # the per-chunk merges, each already O(pool) wide.  Chunks at least
    # pool-sized keep total merge work O(n), the scan's own order.
    block_n = max(
        block_n, _clamp(_round_up(pool, _BLOCK_QUANTUM), _BLOCK_QUANTUM, _BLOCK_MAX)
    )

    # Kernel grid tile: bm covers the (padded) batch in f32 sublane
    # multiples; bn splits the chunk into lane-multiple column blocks small
    # enough that (ranks tile + cells tile + score tile) sits in a quarter
    # of fast memory, leaving room for Pallas pipelining.
    bm = _clamp(_round_up(m, 8), 8, 128)
    tile_budget = fast // 4 - bm * n_cells * 4
    bn = _clamp(
        _round_down(tile_budget // max(4 * (bm + 1), 1), 128), 128, 2048
    )
    bn = min(bn, max(_round_up(block_n, 128), 128))

    expected = pool * block_n / max(n, 1)
    # The pool/block ceiling is rounded *down* to the quantum so the cap
    # stays a _CAP_QUANTUM multiple even when it clamps (a slightly smaller
    # cap only means earlier exact-fallback merges, never wrong results).
    cap = _clamp(
        _round_up(int(_CAP_SAFETY * expected) + 1, _CAP_QUANTUM),
        _CAP_QUANTUM,
        max(_CAP_QUANTUM, _round_down(min(pool, block_n), _CAP_QUANTUM)),
    )
    if (backend or jax.default_backend()) == "tpu":
        bm, bn, cap = _fit_kernel_vmem(bm, bn, cap, n_subspaces, n_cells, fast)
    return TileConfig(block_n=block_n, bm=bm, bn=bn, survivor_cap=cap)


def _fit_kernel_vmem(
    bm: int, bn: int, cap: int, n_subspaces: int, n_cells: int, fast: int
) -> tuple[int, int, int]:
    """Shrink ``bm``, then ``bn``, then ``cap`` until the compact score
    kernel's VMEM high-water mark (its calibrated model,
    :func:`repro.kernels.sc_score.kernel.cells_vmem_bytes`) fits ``fast``:
    tiles the TPU compiler would refuse are never planned."""
    from repro.kernels.sc_score.kernel import cells_vmem_bytes

    def vmem(bm_, bn_, cap_):
        return cells_vmem_bytes(n_subspaces, bm_, bn_, n_cells, cap_)

    while bm > 8 and vmem(bm, 128, _CAP_QUANTUM) > fast:
        bm = max(8, _round_down(bm // 2, 8))
    while bn > 128 and vmem(bm, bn, cap) > fast:
        bn = max(128, _round_down(bn // 2, 128))
    while cap > _CAP_QUANTUM and vmem(bm, bn, cap) > fast:
        cap -= _CAP_QUANTUM
    return bm, bn, cap


def autotune_build_block_n(
    n: int,
    d: int,
    *,
    sqrt_k: int,
    n_subspaces: int = 8,
    backend: str | None = None,
    limits: MemoryLimits | None = None,
    itemsize: int = 4,
) -> int:
    """Chunk size for the streaming index build (chunked/minibatch Lloyd).

    Each K-means step holds per chunk the ``(2Ns, h_max, block_n)`` slice
    of the points-on-lanes half-subspace layout and ``(sqrtK, block_n)``
    distances for each of the ``2Ns`` codebooks; the largest 512-multiple
    whose sum fits the backend's fast memory keeps the assign/stats pass
    cache-resident without shrinking chunks (and therefore multiplying
    steps) more than the hardware requires.  On the TPU the kernels cap
    their own block further to their VMEM budget
    (:func:`repro.kernels.kmeans_assign.ops.lane_block`).
    """
    if min(n, d, sqrt_k, n_subspaces) < 1:
        raise ValueError(
            f"n/d/sqrt_k/n_subspaces must be >= 1, got "
            f"{n}/{d}/{sqrt_k}/{n_subspaces}"
        )
    if limits is None:
        limits = backend_limits(backend)
    h_max = -(-(-(-d // n_subspaces)) // 2)  # ceil(ceil(d/Ns) / 2)
    per_point = 2 * n_subspaces * (sqrt_k + h_max) * itemsize
    block_n = _clamp(
        _round_down(limits.fast_bytes // per_point, _BLOCK_QUANTUM),
        _BLOCK_QUANTUM,
        _BLOCK_MAX,
    )
    return min(block_n, max(_round_up(n, _BLOCK_QUANTUM), _BLOCK_QUANTUM))


# --------------------------------------------------------------------------
# jaxlint registry hook (see repro.analysis)
# --------------------------------------------------------------------------


def jaxlint_entries():
    """Registry hook: autotuner outputs must respect the TPU tile quanta on
    every backend — a drifted quantum here would produce Pallas blocks that
    fail to lower on real hardware."""
    from repro.analysis.registry import TileEntry

    sweep = (
        # (n, d, m, pool, n_subspaces, n_cells): serving-scale, huge-pool,
        # and minimum-viable shapes
        (50_000, 128, 8, 1_000, 8, 2_500),
        (1_000_000, 96, 64, 20_000, 8, 2_500),
        (32_768, 16, 1, 33, 4, 256),
    )
    configs = tuple(
        autotune_tiles(n, d, m, pool, n_subspaces=ns, n_cells=nc, backend=b)
        for b in ("cpu", "gpu", "tpu")
        for (n, d, m, pool, ns, nc) in sweep
    )
    contract = {
        "sublane": 8,
        "lane": 128,
        "block_quantum": _BLOCK_QUANTUM,
        "cap_quantum": _CAP_QUANTUM,
    }
    return [
        TileEntry(
            name="tuning.autotune_tiles",
            contract=contract,
            tile_configs=configs,
            note="TileConfig quantisation contract across backends",
        )
    ]
