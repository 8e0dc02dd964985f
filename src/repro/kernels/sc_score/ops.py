"""jit'd wrapper for the fused SC-score kernel: pads blocks, dispatches."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.sc_score.kernel import (
    sc_score_cells_kernel,
    sc_score_cells_prefilter_compact_kernel,
    sc_score_cells_prefilter_kernel,
    sc_score_kernel,
)
from repro.kernels.sc_score.ref import (
    sc_score_cells_prefilter_compact_ref,
    sc_score_cells_prefilter_ref,
    sc_score_cells_ref,
    sc_score_ref,
    slot_window_counts,
)


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _pad_cells_inputs(ranks, cuts, cells, *, bm: int, bn: int):
    """Kernel operands for the chunked IMI kernels:
    ``-> (act (Ns, mp, Kp) f32, cells (Ns, bcp) int32, bm_, bn_)``.

    The activation mask ``rank <= cut`` is formed here, once per chunk
    stage (O(Ns * m * K)), so the kernel's lookup is a 0/1 contraction.
    Padded query rows and padded K columns are inactive (nothing collides
    there); padded chunk columns take cell 0 and are sliced off or masked
    by the caller's ``limit``.
    """
    n_sub, m, k_cells = ranks.shape
    bc = cells.shape[1]
    bm_ = min(bm, _round_up(m, 8))
    bn_ = min(bn, _round_up(bc, 128))
    mp, bcp = _round_up(m, bm_), _round_up(bc, bn_)
    kp = _round_up(k_cells, 128)
    act = (ranks <= cuts[:, :, None]).astype(jnp.float32)
    act = jnp.pad(act, ((0, 0), (0, mp - m), (0, kp - k_cells)))
    cellp = jnp.pad(cells, ((0, 0), (0, bcp - bc)))
    return act, cellp, bm_, bn_


def _pad_thr(thr: jax.Array, mp: int) -> jax.Array:
    """``(m,) -> (mp, 1)`` pool minima; padded query rows never survive."""
    m = thr.shape[0]
    return jnp.pad(
        thr.astype(jnp.int32), (0, mp - m),
        constant_values=jnp.iinfo(jnp.int32).max,
    )[:, None]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def sc_scores_fused(
    qs: jax.Array,  # (Ns, m, s)
    xs: jax.Array,  # (Ns, n, s)
    tau: jax.Array,  # (Ns, m)
    *,
    bm: int = 8,
    bn: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused threshold-compare + accumulate; padding contract: padded data
    rows sit at +inf distance (never collide), padded query rows are junk
    and sliced off, padded dims are zeros (distance-neutral)."""
    n_sub, m, s = qs.shape
    n = xs.shape[1]
    bm_ = min(bm, _round_up(m, 8))
    bn_ = min(bn, _round_up(n, 128))
    sp = _round_up(s, 128)
    mp, np_ = _round_up(m, bm_), _round_up(n, bn_)
    qp = jnp.pad(qs, ((0, 0), (0, mp - m), (0, sp - s)))
    xp = jnp.pad(xs, ((0, 0), (0, 0), (0, sp - s)))
    xp = jnp.pad(xp, ((0, 0), (0, np_ - n), (0, 0)), constant_values=1e6)
    taup = jnp.pad(tau, ((0, 0), (0, mp - m)))[:, :, None]
    out = sc_score_kernel(qp, xp, taup, bm=bm_, bn=bn_, interpret=interpret)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "impl", "interpret"))
def sc_scores_cells(
    ranks: jax.Array,  # (Ns, m, K) per-(subspace, query) cell ranks
    cuts: jax.Array,  # (Ns, m) activation cutoff ranks
    cells: jax.Array,  # (Ns, bc) chunk cell ids
    *,
    bm: int = 8,
    bn: int = 512,
    impl: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    """Chunked SuCo collision scores ``-> (m, bc)`` int32.

    ``impl``: "jnp" | "pallas" | "auto" (pallas iff running on TPU; the
    jnp oracle is the production CPU path — interpret-mode Pallas is for
    tests only).  Padding contract for the kernel: padded queries and
    padded K columns are inactive (nothing collides there), padded chunk
    columns look up cell 0 and are sliced off.
    """
    if impl == "jnp" or (impl == "auto" and jax.default_backend() != "tpu"):
        return sc_score_cells_ref(ranks, cuts, cells)
    m, bc = ranks.shape[1], cells.shape[1]
    act, cellp, bm_, bn_ = _pad_cells_inputs(ranks, cuts, cells, bm=bm, bn=bn)
    out = sc_score_cells_kernel(act, cellp, bm=bm_, bn=bn_, interpret=interpret)
    return out[:m, :bc]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "impl", "interpret"))
def sc_scores_cells_prefilter(
    ranks: jax.Array,  # (Ns, m, K) per-(subspace, query) cell ranks
    cuts: jax.Array,  # (Ns, m) activation cutoff ranks
    cells: jax.Array,  # (Ns, bc) chunk cell ids
    thr: jax.Array,  # (m,) carried pool minimum score per query
    *,
    bm: int = 8,
    bn: int = 512,
    impl: str = "auto",
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused chunk stage for the single-pass engine ``-> (scores, keep)``.

    :func:`sc_scores_cells` plus the Pareto prefilter computed while the
    score tile is still resident: ``keep[q, j] = scores[q, j] > thr[q]``
    (``(m, bc)`` bool).  Same ``impl`` dispatch and padding contract as
    :func:`sc_scores_cells`; padded query rows additionally get
    ``thr = INT32_MAX`` so they never survive, and padded chunk columns
    are sliced off before the caller sees them (the caller still masks
    columns past the end of the *data*, which this op cannot know about).
    """
    if impl == "jnp" or (impl == "auto" and jax.default_backend() != "tpu"):
        return sc_score_cells_prefilter_ref(ranks, cuts, cells, thr)
    m, bc = ranks.shape[1], cells.shape[1]
    act, cellp, bm_, bn_ = _pad_cells_inputs(ranks, cuts, cells, bm=bm, bn=bn)
    out_s, out_k = sc_score_cells_prefilter_kernel(
        act, _pad_thr(thr, act.shape[1]), cellp, bm=bm_, bn=bn_,
        interpret=interpret,
    )
    return out_s[:m, :bc], out_k[:m, :bc].astype(bool)


@functools.partial(
    jax.jit, static_argnames=("cap", "bm", "bn", "impl", "interpret")
)
def sc_scores_cells_prefilter_compact(
    ranks: jax.Array,  # (Ns, m, K) per-(subspace, query) cell ranks
    cuts: jax.Array,  # (Ns, m) activation cutoff ranks
    cells: jax.Array,  # (Ns, bc) chunk cell ids
    thr: jax.Array,  # (m,) carried pool minimum score per query
    limit: jax.Array,  # () count of valid chunk columns (traced ok)
    keep_cols: jax.Array | None = None,  # (bc,) bool live-column mask
    *,
    cap: int,
    bm: int = 8,
    bn: int = 512,
    impl: str = "auto",
    interpret: bool = False,
) -> tuple[jax.Array, ...]:
    """One-launch chunk stage for the single-pass engine:
    ``-> (scores, surv_cols, surv_scores, count, slot_windows,
    slot_writes)``.

    :func:`sc_scores_cells_prefilter` plus the survivor compaction the
    fused query used to run as a host-graph cumsum/searchsorted/gather —
    here it happens while the score tile is still resident, so the whole
    score -> prune stage is a single ``pallas_call`` per chunk.  Outputs
    (all int32): the chunk scores with columns ``>= limit`` masked to the
    -1 sentinel, the compacted chunk-local survivor columns and their
    scores (``(m, cap)``, ascending-column order, 0 / -1 in empty slots),
    and the *true* per-query survivor count (``(m,)``, may exceed ``cap``
    — the caller's exact-fallback signal; overflowed slots are dropped).
    ``slot_windows`` and ``slot_writes`` (``(m,)``) count the kernel's
    slot write: the 128-slot windows it wrote and the column blocks in
    which the row wrote any slot, at the kernel's ``bn`` and its
    lane-rounded ``cap``; the jnp path replays them from its keep mask
    (:func:`repro.kernels.sc_score.ref.slot_window_counts`).

    ``keep_cols`` (optional ``(bc,) bool``, default all-live) is the
    live-mutation tombstone mask: False columns are deleted points.  The
    jnp oracle folds it into the validity mask exactly like ``limit`` (a
    dead column scores -1, never survives, never consumes a compaction
    slot).  The Pallas path keeps the existing kernel — no new kernel for
    mutation — and post-masks instead: dead columns' scores and any dead
    survivors' slot scores drop to -1; ``count`` then *overcounts* dead
    survivors, which is conservative (the caller's exact overflow fallback
    fires at worst more often, and its top_k sees the masked -1 scores, so
    answers are unchanged).

    Same ``impl`` dispatch and padding contract as
    :func:`sc_scores_cells`; padded query rows get ``thr = INT32_MAX`` so
    they never survive, and ``cap`` is rounded up to a lane multiple for
    the kernel then sliced back.
    """
    m, bc = ranks.shape[1], cells.shape[1]
    capp = _round_up(cap, 128)
    if impl == "jnp" or (impl == "auto" and jax.default_backend() != "tpu"):
        out = sc_score_cells_prefilter_compact_ref(
            ranks, cuts, cells, thr, limit, keep_cols, cap=cap
        )
        bn_ = min(bn, _round_up(bc, 128))  # the kernel's column block
        keep = out[0] > thr[:, None]
        return (*out, *slot_window_counts(keep, bn=bn_, cap=capp))
    act, cellp, bm_, bn_ = _pad_cells_inputs(ranks, cuts, cells, bm=bm, bn=bn)
    limp = jnp.reshape(limit, (1, 1)).astype(jnp.int32)
    out_s, out_c, out_ss, out_n, out_w, out_r = (
        sc_score_cells_prefilter_compact_kernel(
            act, _pad_thr(thr, act.shape[1]), limp, cellp, bm=bm_, bn=bn_,
            cap=capp, interpret=interpret,
        )
    )
    out_s = out_s[:m, :bc]
    out_c, out_ss, out_n = out_c[:m, :cap], out_ss[:m, :cap], out_n[:m, 0]
    if keep_cols is not None:
        out_s = jnp.where(keep_cols[None, :], out_s, -1)
        dead_slot = jnp.logical_not(jnp.take(keep_cols, out_c))
        out_ss = jnp.where(dead_slot, -1, out_ss)
    return out_s, out_c, out_ss, out_n, out_w[:m, 0], out_r[:m, 0]


__all__ = [
    "sc_scores_fused",
    "sc_scores_cells",
    "sc_scores_cells_prefilter",
    "sc_scores_cells_prefilter_compact",
    "sc_score_ref",
    "sc_score_cells_ref",
    "sc_score_cells_prefilter_ref",
    "sc_score_cells_prefilter_compact_ref",
]


# --------------------------------------------------------------------------
# jaxlint registry hook (see repro.analysis)
# --------------------------------------------------------------------------

# Canonical pre-padded kernel shapes for tile validation (Ns, m, K, chunk,
# subspace width) — already lane/sublane aligned, as the op wrappers
# guarantee before dispatching.
_LINT_NS, _LINT_M, _LINT_K, _LINT_BC, _LINT_S = 4, 8, 2_560, 512, 128

#: TPU tile contract shared by the SC-score kernels: f32/int32 blocks keep
#: a lane-multiple minor dim and a sublane-multiple second-minor dim; the
#: (bm, 1)-shaped per-query columns ride the sublane quantum only.
TILE_CONTRACT = {
    "sublane": 8,
    "lane": 128,
    "double_buffer": 2,
}


def jaxlint_entries():
    from repro.analysis.registry import JaxprEntry, TileEntry

    S = jax.ShapeDtypeStruct
    ns, m, K, bc, s = _LINT_NS, _LINT_M, _LINT_K, _LINT_BC, _LINT_S

    def make_cells():
        return jax.make_jaxpr(
            lambda a, ce: sc_score_cells_kernel(a, ce, bm=8, bn=512, interpret=True)
        )(S((ns, m, K), jnp.float32), S((ns, bc), jnp.int32))

    def make_prefilter():
        return jax.make_jaxpr(
            lambda a, t, ce: sc_score_cells_prefilter_kernel(
                a, t, ce, bm=8, bn=512, interpret=True
            )
        )(S((ns, m, K), jnp.float32), S((m, 1), jnp.int32), S((ns, bc), jnp.int32))

    def make_prefilter_compact():
        return jax.make_jaxpr(
            lambda a, t, lim, ce: sc_score_cells_prefilter_compact_kernel(
                a, t, lim, ce, bm=8, bn=512, cap=128, interpret=True
            )
        )(
            S((ns, m, K), jnp.float32),
            S((m, 1), jnp.int32),
            S((1, 1), jnp.int32),
            S((ns, bc), jnp.int32),
        )

    def make_prefilter_compact_scan():
        # The compact kernel as the fused query runs it: inside the chunk
        # scan.  Gates the in-kernel compaction (prefix matmul + one-hot
        # slot writes) against the no-scatter/no-sort and
        # accumulator-dtype rules.
        def scan_compact(a, t, lim, cells_blocks):
            def step(carry, ce):
                outs = sc_score_cells_prefilter_compact_kernel(
                    a, t, lim, ce, bm=8, bn=512, cap=128, interpret=True
                )
                return carry, outs[3]
            return jax.lax.scan(step, jnp.zeros((), jnp.int32), cells_blocks)

        return jax.make_jaxpr(scan_compact)(
            S((ns, m, K), jnp.float32),
            S((m, 1), jnp.int32),
            S((1, 1), jnp.int32),
            S((4, ns, bc), jnp.int32),
        )

    def make_fused():
        return jax.make_jaxpr(
            lambda q, x, tau: sc_score_kernel(q, x, tau, bm=8, bn=512, interpret=True)
        )(
            S((ns, m, s), jnp.float32),
            S((ns, 1_024, s), jnp.float32),
            S((ns, m, 1), jnp.float32),
        )

    def make_oracle():
        return jax.make_jaxpr(
            lambda r, c, ce: sc_scores_cells(r, c, ce, impl="jnp")
        )(S((ns, m, K), jnp.int32), S((ns, m), jnp.int32), S((ns, bc), jnp.int32))

    return [
        TileEntry(
            name="kernels.sc_score.cells",
            make=make_cells,
            contract={
                **TILE_CONTRACT,
                # mapping index (inputs then outputs) -> ((dim, multiple), ...)
                "block_align": {
                    0: ((1, 8), (2, 128)),  # act (Ns, bm, K)
                    1: ((1, 128),),  # cells (Ns, bn)
                    2: ((0, 8), (1, 128)),  # out (bm, bn)
                },
            },
            note="chunked IMI scorer: one-hot lookup-accumulate",
        ),
        TileEntry(
            name="kernels.sc_score.cells_prefilter",
            make=make_prefilter,
            contract={
                **TILE_CONTRACT,
                "block_align": {
                    0: ((1, 8), (2, 128)),  # act (Ns, bm, K)
                    1: ((0, 8),),  # thr (bm, 1)
                    2: ((1, 128),),  # cells (Ns, bn)
                    3: ((0, 8), (1, 128)),  # scores (bm, bn)
                    4: ((0, 8), (1, 128)),  # keep (bm, bn)
                },
            },
            note="fused chunk stage: scores + Pareto-prefilter mask",
        ),
        TileEntry(
            name="kernels.sc_score.cells_prefilter_compact",
            make=make_prefilter_compact,
            contract={
                **TILE_CONTRACT,
                "block_align": {
                    0: ((1, 8), (2, 128)),  # act (Ns, bm, K)
                    1: ((0, 8),),  # thr (bm, 1)
                    # 2: limit (1, 1) scalar — no alignment demand
                    3: ((1, 128),),  # cells (Ns, bn)
                    4: ((0, 8), (1, 128)),  # scores (bm, bn)
                    5: ((0, 8), (1, 128)),  # surv_cols (bm, cap)
                    6: ((0, 8), (1, 128)),  # surv_scores (bm, cap)
                    7: ((0, 8),),  # count (bm, 1)
                    8: ((0, 8),),  # slot_windows (bm, 1)
                    9: ((0, 8),),  # slot_writes (bm, 1)
                },
            },
            note="one-launch chunk stage: scores + in-kernel survivor compaction",
        ),
        JaxprEntry(
            name="kernels.sc_score.prefilter_compact_scan",
            make=make_prefilter_compact_scan,
            rules=("no-scatter-in-scan", "pinned-accumulator"),
            note=(
                "compact kernel inside the chunk scan: the in-kernel "
                "compaction stays scatter/sort-free"
            ),
        ),
        TileEntry(
            name="kernels.sc_score.fused_distance",
            make=make_fused,
            contract={
                **TILE_CONTRACT,
                "block_align": {
                    0: ((1, 8), (2, 128)),  # qs (1, bm, s)
                    1: ((1, 128), (2, 128)),  # xs (1, bn, s)
                    2: ((1, 8),),  # tau (1, bm, 1)
                    3: ((0, 8), (1, 128)),  # out (bm, bn)
                },
            },
            note="MXU distance + threshold-accumulate scorer",
        ),
        JaxprEntry(
            name="kernels.sc_score.oracle",
            make=make_oracle,
            rules=("bounded-intermediate", "pinned-accumulator"),
            budget_bytes=4 * 2 * ns * m * max(K, bc),
            note="jnp oracle of the chunked scorer (the production CPU path)",
        ),
    ]
